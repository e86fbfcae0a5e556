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


@pytest.fixture(scope="module")
def midrun(tmp_path_factory):
    """The mid-run cell's tiny deployment (``chipbench/selftest/
    midrun.py``): six nodes under the first 500 arrivals of cpu250, whose
    pods leave while others arrive."""
    from chipbench.selftest import midrun as tiny
    from fks_tpu.data import TraceParser

    d = str(tmp_path_factory.mktemp("midrun"))
    tiny.tiny_deployment(d)
    return TraceParser(d).parse_workload("nodes.csv", "pods.csv")


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


#: what the first ``e0`` events of first_fit's run of the tiny mid-run
#: deployment hold: (departures, refused placements, waiting pods with a
#: retry queued, whether event ``e0 - 1`` is a DELETE)
MIDRUN_PREFIXES = {
    16: (0, 0, 0, False),        # arrivals alone: the loaded-cluster case
    128: (44, 0, 0, True),       # departures only, the last event one
    238: (103, 1, 1, False),     # ends on the first refusal, just queued
    301: (131, 7, 1, True),      # ends on a DELETE, a pod waiting
    320: (139, 9, 1, False),     # the selftest's own fork
}


@pytest.mark.parametrize("e0", list(MIDRUN_PREFIXES))
def test_midrun_carry_is_the_engines_own_leaf_by_leaf(midrun, e0):
    """The same, for prefixes of any events: departures, refusals, a pod
    waiting with its retry queued, a prefix that ends on a DELETE. The
    float32 sums (26 such snapshots and 1,002 fragmentation scores at the
    cell's own size) are the step's own bit for bit."""
    snap = flat.make_snapshot(midrun, POLICIES["first_fit"], e0)
    forked = dataclasses.replace(midrun, snapshot=snap)
    at = flat.fork_counts(forked, flat.initial_state(forked, SimConfig()))
    last_is_delete = e0 - 1 not in np.asarray(snap.event).tolist()
    assert (at["departed"], at["prefix_failed"], at["waiting"],
            last_is_delete) == MIDRUN_PREFIXES[e0]
    assert snap.rule == ("earliest_delete" if at["prefix_failed"] else "")
    for policy in POLICIES:
        for state_pack in (False, True):
            _leaf_by_leaf(midrun, "midrun", e0, policy, state_pack)


def test_the_committed_midrun_carry_is_the_engines_own():
    """The cell's own size: 16 nodes, cpu250, 12,288 events of first_fit,
    the forked carry against 12,288 steps of the engine, leaf for leaf,
    and what the configuration says of the state at the fork."""
    from fks_tpu.data import TraceParser

    wl = TraceParser().parse_workload(pod_file="openb_pod_list_cpu250.csv")
    _leaf_by_leaf(wl, "cpu250", 12288, "first_fit", False, whole=False)
    forked = TraceParser().parse_workload(
        pod_file="openb_pod_list_cpu250.csv",
        snapshot_file="openb_snapshot_cpu250_firstfit_e12288.csv")
    s = flat.initial_state(forked, SimConfig())
    assert flat.fork_counts(forked, s) == {
        "residents": 50, "nodes_loaded": 15, "departed": 5618,
        "waiting": 1, "typed_waiting": 0, "prefix_failed": 1002}
    assert (int(s.snap_idx), int(s.pending), int(s.steps)) \
        == (26, 3751 + 50 + 1, 12288)
    assert round(float(s.frag_sum) / 1002, 4) == 0.0617


def _leaf_by_leaf(wl, name, e0, policy, state_pack, whole=True):
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
    if not whole:
        return
    whole = finish(advance(stepped, 2 ** 30))
    from_fork = finish(advance(loaded, 2 ** 30))
    for a, b in zip(jax.tree_util.tree_leaves(whole),
                    jax.tree_util.tree_leaves(from_fork)):
        assert np.array_equal(np.asarray(a), np.asarray(b)), case
    assert int(whole.events_processed) > e0
    if name in pt.SEEDS:        # the small deployments run under pressure
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
