"""Large-cluster scale tier: top-k node prefiltering, packed state
dtypes, and the double-buffered segmented runner.

Contract under test (fks_tpu/sim/engine.py SimConfig doc):
- ``node_prefilter_k=0`` and ``state_pack=False`` compile the
  BIT-IDENTICAL program to the seed default (jaxpr-pinned);
- prefiltering is EXACT for feasibility-gated index-preferring policies
  (first_fit family): same fitness, same placements, on clean and
  faulted workloads, in both engines, at any k (k >= n_padded falls back
  to the dense scan);
- a cordoned node can never enter a candidate slot while any feasible
  node exists;
- ``state_pack`` is exact integer narrowing: bit-identical results;
- decision-trace rows and numeric_flags keep working over the gathered
  candidate view (COL_NODE is always the GLOBAL index);
- the double-buffered segmented runner matches the unsegmented runner
  exactly, with the scale knobs on or off.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fks_tpu.data.build import make_workload
from fks_tpu.data.synthetic import synthetic_workload
from fks_tpu.models import parametric, zoo
from fks_tpu.scenarios import get_suite
from fks_tpu.sim import engine, flat, fused
from fks_tpu.sim.engine import (
    SimConfig, _gather_node_view, _prefilter_candidates,
)
from fks_tpu.sim.types import NodeView, PodView, TraceBuffer
from fks_tpu.utils.segments import segment_budget

CLEAN = parametric.seed_weights("first_fit")


# ------------------------------------------------------------ config API

def test_resolve_prefilter_k():
    assert SimConfig().resolve_prefilter_k(16) == 0
    assert SimConfig(node_prefilter_k=8).resolve_prefilter_k(16) == 8
    # k >= n_padded: the candidate list would be the whole node axis —
    # fall back to the dense (bit-identical) program
    assert SimConfig(node_prefilter_k=16).resolve_prefilter_k(16) == 0
    assert SimConfig(node_prefilter_k=64).resolve_prefilter_k(16) == 0
    with pytest.raises(ValueError, match="node_prefilter_k"):
        SimConfig(node_prefilter_k=-1).resolve_prefilter_k(16)


def test_segment_budget():
    assert segment_budget(100, 10) == 11          # ceil + classic slack 1
    assert segment_budget(100, 10, slack=2) == 12  # double-buffered
    assert segment_budget(101, 10) == 12
    assert segment_budget(1, 4096) == 2


def test_fused_rejects_scale_knobs(micro_workload):
    with pytest.raises(ValueError, match="node_prefilter_k"):
        fused._build_plan(micro_workload, SimConfig(node_prefilter_k=1))
    with pytest.raises(ValueError, match="state_pack"):
        fused._build_plan(micro_workload, SimConfig(state_pack=True))


# ------------------------------------------------- candidate-list kernel

def _node_view_8():
    """8 nodes, 2 GPUs each; nodes 0-5 tiny (cpu 100), 6-7 roomy."""
    n, g = 8, 2
    cpu = jnp.asarray([100] * 6 + [64000] * 2, jnp.int32)
    mem = jnp.full((n,), 262144, jnp.int32)
    milli = jnp.full((n, g), 1000, jnp.int32)
    return NodeView(
        cpu_milli_left=cpu, cpu_milli_total=cpu,
        memory_mib_left=mem, memory_mib_total=mem,
        gpu_left=jnp.full((n,), g, jnp.int32),
        num_gpus=jnp.full((n,), g, jnp.int32),
        gpu_milli_left=milli, gpu_milli_total=milli,
        gpu_mem_total=jnp.full((n, g), 16384, jnp.int32),
        gpu_mask=jnp.ones((n, g), bool),
        node_mask=jnp.ones((n,), bool))


def _pod(cpu=4000, num_gpu=0, gpu_milli=0):
    return PodView(cpu_milli=jnp.int32(cpu), memory_mib=jnp.int32(1024),
                   num_gpu=jnp.int32(num_gpu),
                   gpu_milli=jnp.int32(gpu_milli),
                   creation_time=jnp.int32(0), duration_time=jnp.int32(10))


def test_prefilter_candidates_first_k_feasible():
    nodes = _node_view_8()
    # small pod: every node feasible -> first k ascending global indices
    cand = np.asarray(_prefilter_candidates(
        _pod(cpu=50), nodes, nodes.node_mask, 4))
    np.testing.assert_array_equal(cand, [0, 1, 2, 3])
    # big pod: only nodes 6, 7 fit; tail repeats the FIRST candidate
    cand = np.asarray(_prefilter_candidates(
        _pod(cpu=4000), nodes, nodes.node_mask, 4))
    np.testing.assert_array_equal(cand, [6, 7, 6, 6])


def test_prefilter_candidates_exclude_cordoned():
    nodes = _node_view_8()
    # cordon nodes 6 and 0: a cordoned node must never enter a slot
    # while any feasible node exists
    place_mask = nodes.node_mask & ~jnp.asarray(
        [True, False, False, False, False, False, True, False])
    cand = np.asarray(_prefilter_candidates(
        _pod(cpu=50), nodes, place_mask, 4))
    assert 6 not in cand and 0 not in cand
    np.testing.assert_array_equal(cand, [1, 2, 3, 4])
    # big pod under the same cordon: only node 7 survives; duplicates
    # all point at it
    cand = np.asarray(_prefilter_candidates(
        _pod(cpu=4000), nodes, place_mask, 4))
    np.testing.assert_array_equal(cand, [7, 7, 7, 7])
    # nothing feasible: the list degrades to node 0, which the caller's
    # place_mask[cand] re-mask scores to 0 (dense-sweep-equivalent fail)
    cand = np.asarray(_prefilter_candidates(
        _pod(cpu=999999), nodes, place_mask, 4))
    np.testing.assert_array_equal(cand, [0, 0, 0, 0])


def test_gather_node_view_shapes():
    nodes = _node_view_8()
    sub = _gather_node_view(nodes, jnp.asarray([6, 7, 6], jnp.int32))
    assert sub.cpu_milli_left.shape == (3,)
    assert sub.gpu_milli_left.shape == (3, 2)
    np.testing.assert_array_equal(np.asarray(sub.cpu_milli_left),
                                  [64000, 64000, 64000])


# -------------------------------------------------- jaxpr-pin discipline

@pytest.mark.parametrize("mod", [engine, flat], ids=["exact", "flat"])
def test_scale_knobs_off_compile_identical_program(micro_workload, mod):
    """k=0 + state_pack=False must be invisible to the compiler: same
    jaxpr as the seed default. k>0 (and, flat only, state_pack) change
    the program."""
    off = SimConfig(node_prefilter_k=0, state_pack=False)
    default = SimConfig()

    def jx(cfg):
        return str(jax.make_jaxpr(
            mod.make_param_run_fn(micro_workload, parametric.score, cfg))(
            CLEAN, mod.initial_state(micro_workload, cfg)))

    assert jx(off) == jx(default)
    # micro workload pads to 2 nodes, so k=1 is the smallest real filter
    assert jx(SimConfig(node_prefilter_k=1)) != jx(default)
    if mod is flat:
        assert jx(SimConfig(state_pack=True)) != jx(default)
    else:
        # the exact engine ignores state_pack entirely
        assert jx(SimConfig(state_pack=True)) == jx(default)


# ------------------------------------------------------- parity: default

def test_prefilter_parity_default_trace(default_workload):
    """Prefilter parity at 1e-5 with k in {0, 8, 64} on the default
    trace (16 padded nodes: k=8 really filters; k=64 >= n falls back to
    the dense program, pinned by jaxpr identity below). The two engines
    already differ by retry timing on this trace (first_fit delta 0.002,
    bounded at 4e-2 — see test_default_trace_close_to_exact), so the
    1e-5 budget is charged to what prefiltering ADDS: each engine's k=8
    run against its own dense k=0 run, and the cross-engine gap staying
    inside its documented bound at every k."""
    wl = default_workload
    policy = zoo.ZOO["first_fit"]()
    dense = {}
    for k in (0, 8):
        cfg = SimConfig(node_prefilter_k=k)
        ex = engine.simulate(wl, policy, cfg)
        fl = flat.simulate(wl, policy, cfg)
        assert int(ex.scheduled_pods) == int(fl.scheduled_pods)
        assert abs(float(ex.policy_score) - float(fl.policy_score)) <= 4e-2
        if k == 0:
            dense = {"exact": ex, "flat": fl}
        else:
            for name, res in (("exact", ex), ("flat", fl)):
                d = dense[name]
                assert abs(float(res.policy_score)
                           - float(d.policy_score)) <= 1e-5, name
                np.testing.assert_array_equal(
                    np.asarray(res.assigned_node),
                    np.asarray(d.assigned_node), err_msg=name)

    # k=64 on the 16-node trace: same compiled program as k=0, so the
    # k=0 parity above IS the k=64 parity — pin that claim
    for mod in (engine, flat):
        j64 = str(jax.make_jaxpr(
            mod.make_param_run_fn(wl, parametric.score,
                                  SimConfig(node_prefilter_k=64)))(
            CLEAN, mod.initial_state(wl, SimConfig(node_prefilter_k=64))))
        j0 = str(jax.make_jaxpr(
            mod.make_param_run_fn(wl, parametric.score, SimConfig()))(
            CLEAN, mod.initial_state(wl, SimConfig())))
        assert j64 == j0


# ------------------------------------------------------- parity: faulted

def test_prefilter_parity_faulted_smoke3():
    """Parity holds on a fault-injected scenario workload (cordon events
    flow through place_mask into the prefilter feasibility test)."""
    base = synthetic_workload(4, 24, seed=3)
    suite = get_suite("smoke3", base)
    assert suite.names[2] == "fault1"
    wl = suite.workloads[2]
    policy = zoo.ZOO["first_fit"]()
    dense_e = engine.simulate(wl, policy, SimConfig())
    for k in (1, 2):
        cfg = SimConfig(node_prefilter_k=k)
        ex = engine.simulate(wl, policy, cfg)
        fl = flat.simulate(wl, policy, cfg)
        assert abs(float(ex.policy_score) - float(fl.policy_score)) <= 1e-5
        assert abs(float(ex.policy_score)
                   - float(dense_e.policy_score)) <= 1e-5
        np.testing.assert_array_equal(np.asarray(ex.assigned_node),
                                      np.asarray(dense_e.assigned_node))


# ----------------------------------------------------------- state_pack

def test_state_pack_bit_identical():
    """Packed dtypes are exact integer narrowing: every observable in
    the SimResult matches the unpacked run bit for bit."""
    wl = synthetic_workload(8, 60, seed=2)
    policy = zoo.ZOO["best_fit"]()
    a = flat.simulate(wl, policy, SimConfig())
    b = flat.simulate(wl, policy, SimConfig(state_pack=True))
    for name, va, vb in zip(a._fields, a, b):
        if va is None:
            assert vb is None
            continue
        np.testing.assert_array_equal(np.asarray(va), np.asarray(vb),
                                      err_msg=name)
        # finalize widens packed columns back: dtypes config-independent
        assert np.asarray(va).dtype == np.asarray(vb).dtype, name


def test_state_pack_narrows_carry():
    wl = synthetic_workload(8, 60, seed=2)
    s = flat.initial_state(wl, SimConfig(state_pack=True))
    assert s.gpu_milli_left.dtype == jnp.int16
    assert s.wait_hist.dtype == jnp.int16
    s0 = flat.initial_state(wl, SimConfig())
    assert s0.gpu_milli_left.dtype == jnp.int32


# ------------------------------------------- trace + watchdog invariants

def _skewed_workload():
    """6 tiny nodes then 2 roomy ones; pods only fit on nodes >= 6, so a
    k=2 prefilter must gather the winner back to a GLOBAL index >= 6."""
    nodes = [{"node_id": f"n{i}", "cpu_milli": 100, "memory_mib": 262144,
              "gpus": [], "gpu_memory_mib": 0} for i in range(6)]
    nodes += [{"node_id": f"n{i}", "cpu_milli": 64000,
               "memory_mib": 262144, "gpus": [1000] * 2,
               "gpu_memory_mib": 16384} for i in (6, 7)]
    pods = [{"pod_id": f"p{i}", "cpu_milli": 4000, "memory_mib": 1024,
             "num_gpu": 0, "gpu_milli": 0, "creation_time": i,
             "duration_time": 50} for i in range(4)]
    return make_workload(nodes, pods)


@pytest.mark.parametrize("mod", [engine, flat], ids=["exact", "flat"])
def test_trace_records_global_node_index(mod):
    """TraceBuffer COL_NODE carries the GLOBAL node index after the
    prefilter gather-back, never the local top-k slot."""
    wl = _skewed_workload()
    cfg = SimConfig(node_prefilter_k=2, decision_trace=True)
    res = mod.simulate(wl, zoo.ZOO["first_fit"](), cfg)
    data = np.asarray(res.trace.data)
    count = int(res.trace.count)
    creates = data[:count][data[:count, TraceBuffer.COL_KIND] == 0]
    assert len(creates) == 4
    # all four pods land on the roomy nodes — a local slot would be 0/1
    assert set(creates[:, TraceBuffer.COL_NODE]) <= {6, 7}
    assert np.asarray(res.assigned_node)[0] == 6
    # and the placements match the dense program exactly
    dense = mod.simulate(wl, zoo.ZOO["first_fit"](), SimConfig())
    np.testing.assert_array_equal(np.asarray(res.assigned_node),
                                  np.asarray(dense.assigned_node))


@pytest.mark.parametrize("mod", [engine, flat], ids=["exact", "flat"])
def test_numeric_flags_survive_prefilter(mod):
    """The watchdog sees the gathered [k] score vector; a NaN-emitting
    policy must set the same sticky flags as under the dense sweep."""
    wl = _skewed_workload()

    def nan_policy(pod, nodes):
        return jnp.full(nodes.cpu_milli_left.shape, jnp.nan, jnp.float32)

    dense = mod.simulate(wl, nan_policy, SimConfig(watchdog=True))
    pre = mod.simulate(wl, nan_policy,
                       SimConfig(watchdog=True, node_prefilter_k=2))
    assert int(dense.numeric_flags) != 0
    assert int(pre.numeric_flags) == int(dense.numeric_flags)


# ----------------------------------------- segmented runner / population

def test_segmented_double_buffer_matches_unsegmented():
    wl = synthetic_workload(8, 96, seed=4)
    pop = 3
    params = jnp.tile(jnp.asarray(CLEAN)[None], (pop, 1))
    for cfg in (SimConfig(track_ctime=False),
                SimConfig(track_ctime=False, node_prefilter_k=4,
                          state_pack=True)):
        base = flat.make_population_run_fn(wl, parametric.score, cfg)(
            params, flat.initial_state(wl, cfg))
        for dbuf in (True, False):
            seg = flat.make_segmented_population_run(
                wl, parametric.score, cfg, seg_steps=32,
                double_buffer=dbuf)(params, flat.initial_state(wl, cfg))
            # score: the segmented finalize re-reduces the fitness sum
            # in a different association order — last-ulp float32 noise
            np.testing.assert_allclose(
                np.asarray(base.policy_score), np.asarray(seg.policy_score),
                rtol=1e-6)
            np.testing.assert_array_equal(
                np.asarray(base.assigned_node), np.asarray(seg.assigned_node))


def test_prefilter_under_vmap_population():
    """Prefilter parity holds lane-wise under vmap: a population of
    identical first_fit lanes scores identically with and without it."""
    wl = synthetic_workload(16, 64, seed=1)
    pop = 4
    params = jnp.tile(jnp.asarray(CLEAN)[None], (pop, 1))
    dense = flat.make_population_run_fn(
        wl, parametric.score, SimConfig())(
        params, flat.initial_state(wl, SimConfig()))
    cfg = SimConfig(node_prefilter_k=8, state_pack=True)
    pre = flat.make_population_run_fn(wl, parametric.score, cfg)(
        params, flat.initial_state(wl, cfg))
    np.testing.assert_array_equal(np.asarray(dense.policy_score),
                                  np.asarray(pre.policy_score))
    np.testing.assert_array_equal(np.asarray(dense.assigned_node),
                                  np.asarray(pre.assigned_node))


# --------------------------------------------------------- OpenB loader

def test_openb_node_yaml_loader(tmp_path, monkeypatch):
    from fks_tpu.data.traces import parse_node_yaml

    # repo-root-relative resolution: must work from a foreign cwd
    monkeypatch.chdir(tmp_path)
    nodes = parse_node_yaml()
    assert len(nodes) == 1213
    n0 = nodes[0]
    assert n0["cpu_milli"] == 64000
    assert n0["memory_mib"] == 262144
    assert n0["gpus"] == [1000, 1000]
    assert n0["gpu_memory_mib"] == 16280
    # every record is make_cluster-schema complete
    for n in nodes:
        assert set(n) >= {"node_id", "cpu_milli", "memory_mib", "gpus",
                          "gpu_memory_mib"}


def test_openb_nodes_feed_synthetic_workload():
    from fks_tpu.data.traces import parse_node_yaml

    nodes = parse_node_yaml()
    wl = synthetic_workload(32, 48, seed=0, nodes=nodes)
    assert wl.num_nodes == 32
    assert int(np.asarray(wl.cluster.cpu_total)[0]) == 64000
    with pytest.raises(ValueError, match="exceeds"):
        synthetic_workload(len(nodes) + 1, 8, nodes=nodes)


# ------------------------------------------------------- tooling wiring

def test_scale_tier_schema_and_compare_threshold(tmp_path):
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools"))
    try:
        import check_jsonl_schema as cjs
    finally:
        sys.path.pop(0)
    assert cjs.METRIC_KIND_REQUIRED["scale_tier"] == (
        "nodes", "pods", "events_per_sec", "node_prefilter_k",
        "state_pack")

    from fks_tpu.obs import compare
    th = compare.DEFAULT_THRESHOLDS["scale1k_events_per_sec"]
    assert th.higher_is_better and th.rel == 0.10

    # a bench scale1k JSON line feeds the comparator extractor
    p = tmp_path / "bench.jsonl"
    p.write_text('{"scale1k_events_per_sec": 5000.0}\n')
    rows = compare.compare_runs(str(p), str(p))
    assert any(r["metric"] == "scale1k_events_per_sec" for r in rows)


# ------------------------------------------------------- slow-tier smoke

@pytest.mark.slow
def test_scale_smoke_1k_nodes_10k_pods():
    """The scale-tier shape at reduced pod count: 1k nodes x 10k pods
    runs to completion through the double-buffered segmented runner with
    prefiltering + packed dtypes on (slow tier)."""
    wl = synthetic_workload(1000, 10000, seed=1)
    cfg = SimConfig(max_steps=4 * 10000, track_ctime=False,
                    node_prefilter_k=64, state_pack=True)
    pop = 2
    params = jnp.tile(jnp.asarray(CLEAN)[None], (pop, 1))
    run = flat.make_segmented_population_run(wl, parametric.score, cfg,
                                             seg_steps=8192)
    res = run(params, flat.initial_state(wl, cfg))
    assert not bool(np.asarray(res.truncated).any())
    assert not bool(np.asarray(res.failed).any())
    scheduled = np.asarray(res.scheduled_pods)
    assert (scheduled == scheduled[0]).all()
    assert int(scheduled[0]) >= 9500  # load-calibrated: ~all schedule
    assert np.isfinite(np.asarray(res.policy_score)).all()
