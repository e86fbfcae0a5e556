"""The flat (slot-per-pod) engine vs the exact heap engine.

Contract (fks_tpu/sim/flat.py module docstring):
- on runs with ZERO failed placements the two engines are BIT-IDENTICAL
  (pop order is fully determined by unique (time, tie_rank) keys there);
- on runs with retries only retry TIMING may differ (the flat engine uses
  time-order next-deletion, the exact engine replicates the reference's
  heap-array-order scan); placement rules, refunds, fragmentation scoring,
  snapshot overshoot and fitness arithmetic are shared;
- the default trace's reference policies stay close (scheduled counts
  equal, fitness within a documented tolerance).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fks_tpu.data.build import make_workload
from fks_tpu.models import zoo
from fks_tpu.sim import flat
from fks_tpu.sim.engine import SimConfig, simulate
from tests.test_engine_micro import micro_workload


def _assert_results_equal(a, b):
    for name, va, vb in zip(a._fields, a, b):
        np.testing.assert_array_equal(
            np.asarray(va), np.asarray(vb), err_msg=name)


def _roomy_workload(num_pods=40, seed=0):
    """A workload where every pod always fits -> zero failed placements."""
    rng = np.random.default_rng(seed)
    nodes = [{"node_id": f"n{i}", "cpu_milli": 64000, "memory_mib": 262144,
              "gpus": [1000] * 8, "gpu_memory_mib": 16384} for i in range(4)]
    pods = [{"pod_id": f"pod-{i:04d}",
             "cpu_milli": int(rng.integers(100, 1500)),
             "memory_mib": int(rng.integers(100, 4000)),
             "num_gpu": int(rng.integers(0, 3)),
             "gpu_milli": int(rng.integers(1, 300)),
             "creation_time": int(rng.integers(0, 1000)),
             "duration_time": int(rng.integers(0, 500))}
            for i in range(num_pods)]
    for p in pods:
        if p["num_gpu"] == 0:
            p["gpu_milli"] = 0
    return make_workload(nodes, pods, pad_nodes_to=4, pad_gpus_to=8,
                         pad_pods_to=64)


@pytest.mark.parametrize("policy_name", ["first_fit", "best_fit",
                                         "funsearch_4901"])
@pytest.mark.slow
def test_no_retry_run_bit_identical(policy_name):
    wl = _roomy_workload()
    cfg = SimConfig()
    pol = zoo.ZOO[policy_name]()
    exact = simulate(wl, pol, cfg)
    fastr = flat.simulate(wl, pol, cfg)
    assert int(exact.num_fragmentation_events) == 0  # premise: no failures
    _assert_results_equal(exact, fastr)


@pytest.mark.slow
def test_micro_workload_bit_identical():
    wl = micro_workload()
    for name in ("first_fit", "best_fit"):
        exact = simulate(wl, zoo.ZOO[name]())
        fastr = flat.simulate(wl, zoo.ZOO[name]())
        if int(exact.num_fragmentation_events) == 0:
            _assert_results_equal(exact, fastr)
        else:
            assert int(fastr.scheduled_pods) == int(exact.scheduled_pods)


def test_refuse_all_policy_drops_everything():
    """No deletions ever pending -> every failed pod silently drops
    (reference event_simulator.py:51-58 fall-through) -> score 0."""
    wl = _roomy_workload(num_pods=8)
    res = flat.simulate(wl, lambda pod, nodes: jnp.zeros(
        nodes.node_mask.shape[0], jnp.int32))
    assert float(res.policy_score) == 0.0
    assert int(res.scheduled_pods) == 0
    assert not bool(res.failed)
    assert not bool(res.truncated)  # queue fully drained


@pytest.mark.slow
def test_population_run_matches_single_runs():
    from fks_tpu.models import parametric

    wl = _roomy_workload(num_pods=32, seed=3)
    cfg = SimConfig()
    key = jax.random.PRNGKey(0)
    params = parametric.init_population(key, 4, noise=0.2)
    run_pop = jax.jit(flat.make_population_run_fn(wl, parametric.score, cfg))
    res = run_pop(params, flat.initial_state(wl, cfg))
    single = jax.jit(flat.make_param_run_fn(wl, parametric.score, cfg))
    s0 = flat.initial_state(wl, cfg)
    for i in range(4):
        one = single(params[i], s0)
        np.testing.assert_allclose(np.asarray(res.policy_score)[i],
                                   np.asarray(one.policy_score))
        np.testing.assert_array_equal(np.asarray(res.assigned_node)[i],
                                      np.asarray(one.assigned_node))


@pytest.mark.slow
def test_default_trace_close_to_exact(default_workload):
    """Retry timing is the ONLY divergence; on the reference trace the
    scheduled counts must match and fitness must stay within 4e-2 for the
    published policies. Measured deltas (PROFILE.md): first_fit 0.002,
    best_fit 0.013, funsearch_4901 0.029 — chaotic snowballing from single
    retry-time differences, not systematic bias."""
    cfg = SimConfig()
    # two policies bound the divergence spectrum (first_fit: 3k retries,
    # funsearch_4901: 11k — PROFILE.md); best_fit sits between, checked
    # against its golden constants below without a second exact-engine
    # run (one fewer full-trace CPU pass matters on this single core).
    for name in ("first_fit", "funsearch_4901"):
        exact = simulate(default_workload, zoo.ZOO[name](), cfg)
        fastr = flat.simulate(default_workload, zoo.ZOO[name](), cfg)
        assert int(fastr.scheduled_pods) == int(exact.scheduled_pods), name
        d = abs(float(fastr.policy_score) - float(exact.policy_score))
        assert d < 4e-2, (name, d)
    bf = flat.simulate(default_workload, zoo.ZOO["best_fit"](), cfg)
    assert int(bf.scheduled_pods) == 8152  # golden: all placed
    assert abs(float(bf.policy_score) - 0.4465) < 4e-2


def test_population_with_truncating_lane_terminates():
    """Regression: a lane that exhausts its step budget with events still
    pending (truncated) must not hold the population while_loop's cond
    true through other, finished lanes — lane_active's block-min reduction
    has to stay per-lane on the batched state."""
    from fks_tpu.models import parametric

    wl = _roomy_workload(num_pods=16, seed=5)
    cfg = SimConfig(max_steps=8)  # force truncation for every lane
    run = jax.jit(flat.make_population_run_fn(wl, parametric.score, cfg))
    res = run(parametric.init_population(jax.random.PRNGKey(0), 3, noise=0.1),
              flat.initial_state(wl, cfg))
    assert bool(np.all(np.asarray(res.truncated)))
    assert np.asarray(res.policy_score).tolist() == [0.0, 0.0, 0.0]


@pytest.mark.slow
def test_pod_count_not_block_multiple():
    """Regression: the slot queue pads itself to a whole number of blocks;
    workloads whose padded pod count is not a multiple of the block width
    (e.g. synthetic scale runs) must work, not raise."""
    wl = _roomy_workload(num_pods=40, seed=7)
    wl = make_workload(
        [{"node_id": f"n{i}", "cpu_milli": 64000, "memory_mib": 262144,
          "gpus": [1000] * 8} for i in range(4)],
        [{"pod_id": f"pod-{i:04d}", "cpu_milli": 500, "memory_mib": 500,
          "num_gpu": 0, "gpu_milli": 0, "creation_time": i,
          "duration_time": 3} for i in range(200)],
        pad_nodes_to=4, pad_gpus_to=8, pad_pods_to=200)  # 200 % 128 != 0
    exact = simulate(wl, zoo.ZOO["best_fit"]())
    fastr = flat.simulate(wl, zoo.ZOO["best_fit"]())
    _assert_results_equal(exact, fastr)
    # the opt-in audit must also handle the queue's block padding
    audited = flat.simulate(wl, zoo.ZOO["best_fit"](),
                            SimConfig(validate_invariants=True))
    assert int(audited.invariant_violations) == 0


def test_invariant_audit_clean(default_workload):
    cfg = SimConfig(validate_invariants=True)
    res = flat.simulate(default_workload, zoo.ZOO["best_fit"](), cfg)
    assert int(res.invariant_violations) == 0


@pytest.mark.slow
def test_unpacked_aux_gpus_path_bit_identical():
    """When node_bits + G > 31 the (node, gpu_bits) pair no longer fits one
    int32 aux word and the engine must fall back to a separate aux_gpus
    carry (fks_tpu/sim/flat.py _packable). Same contract as the packed
    path: bit-identical to the exact engine on retry-free runs."""
    rng = np.random.default_rng(3)
    nodes = [{"node_id": f"n{i}", "cpu_milli": 64000, "memory_mib": 262144,
              "gpus": [1000] * 30, "gpu_memory_mib": 16384} for i in range(4)]
    pods = [{"pod_id": f"pod-{i:04d}",
             "cpu_milli": int(rng.integers(100, 1500)),
             "memory_mib": int(rng.integers(100, 4000)),
             "num_gpu": int(rng.integers(0, 5)),
             "gpu_milli": int(rng.integers(1, 400)),
             "creation_time": int(rng.integers(0, 1000)),
             "duration_time": int(rng.integers(0, 500))}
            for i in range(32)]
    for p in pods:
        if p["num_gpu"] == 0:
            p["gpu_milli"] = 0
    wl = make_workload(nodes, pods, pad_nodes_to=4, pad_gpus_to=30,
                       pad_pods_to=32)
    cfg = SimConfig()
    assert not flat._packable(wl.cluster.n_padded, wl.cluster.g_padded)
    assert flat.initial_state(wl, cfg).aux_gpus is not None
    for name in ("first_fit", "best_fit"):
        exact = simulate(wl, zoo.ZOO[name](), cfg)
        fastr = flat.simulate(wl, zoo.ZOO[name](), cfg)
        assert int(exact.num_fragmentation_events) == 0
        _assert_results_equal(exact, fastr)


def test_unpacked_aux_gpus_with_contention():
    """Unpacked path under GPU contention (failed placements + retries +
    delete refunds through the separate gpu-bits carry): observables must
    stay internally consistent and the run must complete."""
    nodes = [{"node_id": "n0", "cpu_milli": 64000, "memory_mib": 262144,
              "gpus": [1000] * 30, "gpu_memory_mib": 16384}]
    # 6 pods each wanting 12 of 30 GPUs: at most 2 fit concurrently
    pods = [{"pod_id": f"pod-{i:02d}", "cpu_milli": 100, "memory_mib": 100,
             "num_gpu": 12, "gpu_milli": 900, "creation_time": i,
             "duration_time": 50} for i in range(6)]
    # pad the node axis to 4 so node_bits(2) + G(30) > 31 -> unpacked
    wl = make_workload(nodes, pods, pad_nodes_to=4, pad_gpus_to=30,
                       pad_pods_to=8)
    assert not flat._packable(wl.cluster.n_padded, wl.cluster.g_padded)
    res = flat.simulate(wl, zoo.ZOO["best_fit"](),
                        SimConfig(validate_invariants=True))
    assert int(res.invariant_violations) == 0
    assert int(res.scheduled_pods) == 6
    assert not bool(res.failed)
    # every assigned pod holds exactly num_gpu distinct GPUs
    bits = np.asarray(res.assigned_gpus)[:6]
    assert all(bin(int(b)).count("1") == 12 for b in bits)


def test_segmented_population_matches():
    """make_segmented_population_run splits the while_loop into bounded
    device calls (the host regains control between them); every SimResult
    field must be identical to the unsegmented runner, including with a
    segment length that forces many host round-trips and one that exceeds
    the whole run (degenerate single segment)."""
    from fks_tpu.models import parametric

    wl = _roomy_workload(num_pods=40, seed=3)
    cfg = SimConfig(track_ctime=False)
    params = parametric.init_population(jax.random.PRNGKey(2), 4, noise=0.1)
    s0 = flat.initial_state(wl, cfg)
    ref = jax.jit(flat.make_population_run_fn(wl, parametric.score, cfg))(
        params, s0)
    for seg in (7, 10_000):
        seg_run = flat.make_segmented_population_run(
            wl, parametric.score, cfg, seg_steps=seg)
        _assert_results_equal(seg_run(params, s0), ref)


def test_segmented_population_with_contention_and_truncation():
    """Segmentation must also agree when lanes fail placements (retries
    queue new events mid-run) and when the step budget truncates lanes."""
    from fks_tpu.models import parametric

    wl = micro_workload()
    cfg = SimConfig(max_steps=9)  # truncates some lanes mid-trace
    params = parametric.init_population(jax.random.PRNGKey(4), 3, noise=0.3)
    s0 = flat.initial_state(wl, cfg)
    ref = jax.jit(flat.make_population_run_fn(wl, parametric.score, cfg))(
        params, s0)
    seg_run = flat.make_segmented_population_run(
        wl, parametric.score, cfg, seg_steps=2)
    _assert_results_equal(seg_run(params, s0), ref)
