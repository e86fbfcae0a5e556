"""The loaded carry that ``flat.initial_state`` builds from a workload's
snapshot (``fks_tpu.data.snapshot``) against the flat engine's own steps,
leaf by leaf. Device-heavy, and a few items only: the suite's scheduler
queues the files with the most items first, so this one runs in the tail,
beside ``test_cluster_parity.py``, after the latency-gated promotion tests
of ``test_portfolio.py``, ``test_vm_serve.py`` and ``test_pipeline.py``
are through. The forked runners are ``tests/test_snapshot_tiers.py``."""
import dataclasses
import json
import os

import jax
import numpy as np
import pytest

from chipbench import cells
from chipbench.drivers import common
from fks_tpu.data import snapshot as snap_mod
from fks_tpu.models import parametric, zoo
from fks_tpu.sim import flat
from fks_tpu.sim.engine import SimConfig, loop_tables
from tests import pressure_traces as pt

CONFIG = json.load(open(os.path.join(
    cells.HERE, "configs", "openb1523-loaded.json")))


def _param_policy(pod, nodes):
    w = parametric.seed_weights("packing")
    return parametric.score(w, pod, nodes)


POLICIES = {"first_fit": zoo.first_fit(), "best_fit": zoo.best_fit(),
            "parametric": _param_policy}


@pytest.fixture(scope="module")
def pressure(tmp_path_factory):
    """seed -> workload of the small loaded deployments."""
    out = {}
    for seed in pt.SEEDS:
        d = str(tmp_path_factory.mktemp(f"snap{seed}"))
        out[seed] = pt.write_traces(d, seed).parse_workload(
            pt.NODE_FILE, pt.POD_FILE)
    return out


@pytest.fixture(scope="module")
def cluster_cut():
    """The 1,523-node cluster under the first 300 arrivals."""
    files = cells.verify_files(CONFIG)
    return common.parse_workload({"pod_limit": 300}, files)


def _workload(name, pressure, cluster_cut):
    return cluster_cut if name == "openb1523" else pressure[name]


@pytest.mark.parametrize("name,e0", [(2, 200), (5, 130), ("openb1523", 250)])
def test_loaded_carry_is_the_engines_own_leaf_by_leaf(pressure, cluster_cut,
                                                      name, e0):
    """``initial_state`` of the forked workload is the ``FlatState`` that
    ``build_step`` reaches after ``e0`` steps under the placing policy,
    every leaf bit for bit, and the run from it ends in that policy's
    whole-run ``SimResult`` bit for bit: three policies, ``state_pack``
    on and off."""
    wl = _workload(name, pressure, cluster_cut)
    for policy in POLICIES:
        for state_pack in (False, True):
            _leaf_by_leaf(wl, name, e0, policy, state_pack)


def _leaf_by_leaf(wl, name, e0, policy, state_pack):
    case = (name, policy, state_pack)
    pol = POLICIES[policy]
    cfg = SimConfig(state_pack=state_pack, node_prefilter_k=64)
    ktable, max_steps = loop_tables(wl, cfg)
    step = flat.build_step(wl, pol, cfg, ktable, max_steps)

    @jax.jit
    def advance(s, bound):
        return jax.lax.while_loop(
            lambda s: flat.lane_active(s, max_steps) & (s.steps < bound),
            step, s)

    finish = jax.jit(lambda s: flat.finalize(wl, cfg, s))
    stepped = advance(flat.initial_state(wl, cfg), e0)
    forked = dataclasses.replace(
        wl, snapshot=flat.make_snapshot(wl, pol, e0, cfg))
    loaded = flat.initial_state(forked, cfg)
    for field, a, b in zip(stepped._fields, stepped, loaded):
        if a is None:
            assert b is None, (case, field)
            continue
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype and a.shape == b.shape, (case, field)
        assert np.array_equal(a, b), (case, field)
    assert int(loaded.steps) == int(loaded.events_processed) == e0
    whole = finish(advance(stepped, 2 ** 30))
    from_fork = finish(advance(loaded, 2 ** 30))
    for a, b in zip(jax.tree_util.tree_leaves(whole),
                    jax.tree_util.tree_leaves(from_fork)):
        assert np.array_equal(np.asarray(a), np.asarray(b)), case
    assert int(whole.events_processed) > e0
    if name != "openb1523":     # the small deployments run under pressure
        assert int(whole.num_fragmentation_events) > 0
        assert float(whole.policy_score) > 0


def test_every_prefix_of_a_snapshot_is_one(pressure):
    wl = pressure[2]
    cfg = SimConfig(node_prefilter_k=64)
    full = flat.make_snapshot(wl, POLICIES["best_fit"], 200, cfg)
    short = flat.make_snapshot(wl, POLICIES["best_fit"], 120, cfg)
    head = snap_mod.head(full, 120)
    for a, b in zip(jax.tree_util.tree_leaves(head),
                    jax.tree_util.tree_leaves(short)):
        assert np.array_equal(a, b)
