"""Fused Pallas kernel (fks_tpu/sim/fused.py) vs the XLA flat engine.

Contract: for the same parametric population the fused kernel reproduces
the flat engine's trajectory EXACTLY on every integer observable
(placements, GPU picks, event/snapshot/fragmentation counts, final node
remnants, truncation/failure flags). Float accumulators (utilization
sums, fragmentation mean, policy score) may differ by a few ulp because
the two programs compile the same f32 arithmetic separately.

CPU runs ask for interpret mode by name, so workloads here are small;
``chip_smoke.py`` runs the Mosaic-compiled kernel on the full default
trace.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fks_tpu.data.build import make_workload
from fks_tpu.models import parametric
from fks_tpu.sim import flat, fused
from fks_tpu.sim.engine import SimConfig

INT_FIELDS = (
    "events_processed", "scheduled_pods", "num_snapshots",
    "num_fragmentation_events", "assigned_node", "assigned_gpus",
    "cpu_left", "mem_left", "gpu_left", "gpu_milli_left", "max_nodes",
    "truncated", "failed", "invariant_violations",
)
FLOAT_FIELDS = (
    "policy_score", "avg_cpu_utilization", "avg_memory_utilization",
    "avg_gpu_count_utilization", "avg_gpu_memory_utilization",
    "gpu_fragmentation_score",
)


def _assert_matches(res, ref):
    for f in INT_FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(res, f)), np.asarray(getattr(ref, f)),
            err_msg=f)
    for f in FLOAT_FIELDS:
        np.testing.assert_allclose(
            np.asarray(getattr(res, f)), np.asarray(getattr(ref, f)),
            rtol=2e-6, atol=2e-6, err_msg=f)


def _run_both(wl, cfg, params, lanes=8):
    run = fused.make_fused_population_run(wl, cfg, lanes=lanes,
                                          interpret=True)
    res = run(params)
    pop = flat.make_population_run_fn(wl, parametric.score, cfg)
    ref = pop(params, flat.initial_state(wl, cfg))
    return res, ref


def _roomy():
    rng = np.random.default_rng(11)
    nodes = [{"node_id": f"n{i}", "cpu_milli": 64000, "memory_mib": 262144,
              "gpus": [1000] * 8, "gpu_memory_mib": 16384} for i in range(4)]
    pods = [{"pod_id": f"pod-{i:04d}",
             "cpu_milli": int(rng.integers(100, 1500)),
             "memory_mib": int(rng.integers(100, 4000)),
             "num_gpu": int(rng.integers(0, 3)),
             "gpu_milli": int(rng.integers(1, 300)),
             "creation_time": int(rng.integers(0, 1000)),
             "duration_time": int(rng.integers(0, 500))}
            for i in range(48)]
    for p in pods:
        if p["num_gpu"] == 0:
            p["gpu_milli"] = 0
    return make_workload(nodes, pods, pad_nodes_to=4, pad_gpus_to=8,
                         pad_pods_to=64)


def _contended():
    rng = np.random.default_rng(7)
    nodes = [{"node_id": f"n{i}", "cpu_milli": 16000, "memory_mib": 32000,
              "gpus": [1000] * 2, "gpu_memory_mib": 8000} for i in range(4)]
    pods = [{"pod_id": f"pod-{i:04d}",
             "cpu_milli": int(rng.integers(500, 6000)),
             "memory_mib": int(rng.integers(500, 12000)),
             "num_gpu": int(rng.integers(0, 3)),
             "gpu_milli": int(rng.integers(100, 1000)),
             "creation_time": int(rng.integers(0, 300)),
             "duration_time": int(rng.integers(10, 200))}
            for i in range(96)]
    for p in pods:
        if p["num_gpu"] == 0:
            p["gpu_milli"] = 0
    return make_workload(nodes, pods, pad_nodes_to=4, pad_gpus_to=2,
                         pad_pods_to=128)


@pytest.mark.slow
def test_roomy_population_matches_flat():
    wl = _roomy()
    cfg = SimConfig(track_ctime=False)
    params = parametric.init_population(jax.random.PRNGKey(0), 8, noise=0.2)
    res, ref = _run_both(wl, cfg, params)
    assert int(np.asarray(ref.truncated).sum()) == 0
    _assert_matches(res, ref)


@pytest.mark.slow
def test_contended_population_matches_flat():
    """Retries, fragmentation events, silent drops, step-budget truncation
    — the full set of failure paths — must match event for event."""
    wl = _contended()
    cfg = SimConfig(track_ctime=False, max_steps=4 * 96)
    params = parametric.init_population(jax.random.PRNGKey(3), 8, noise=0.5)
    res, ref = _run_both(wl, cfg, params)
    assert int(np.asarray(ref.num_fragmentation_events).sum()) > 0
    _assert_matches(res, ref)


@pytest.mark.slow
def test_population_padding_to_lane_multiple():
    """pop not a multiple of lanes: results for the real candidates are
    unchanged by the padding rows."""
    wl = _roomy()
    cfg = SimConfig(track_ctime=False)
    params = parametric.init_population(jax.random.PRNGKey(1), 5, noise=0.2)
    res, ref = _run_both(wl, cfg, params, lanes=8)
    assert np.asarray(res.policy_score).shape == (5,)
    _assert_matches(res, ref)


def test_builder_rejects_unsupported_configs():
    wl = _roomy()
    with pytest.raises(ValueError, match="best_fit"):
        fused.make_fused_population_run(
            wl, SimConfig(gpu_allocator="first_fit"))
    with pytest.raises(ValueError, match="audit"):
        fused.make_fused_population_run(
            wl, SimConfig(validate_invariants=True))


def test_fused_under_shard_map_matches_flat():
    """The pallas_call composes with shard_map over the population mesh:
    per-shard fused chunks + ICI all-gather elite selection must agree
    with the sharded flat engine."""
    from fks_tpu.parallel import make_sharded_eval, population_mesh

    devices = jax.devices()
    if len(devices) < 2:
        pytest.skip("needs the multi-device CPU mesh")
    wl = _roomy()
    cfg = SimConfig(track_ctime=False)
    mesh = population_mesh(devices)
    pop = parametric.init_population(jax.random.PRNGKey(2),
                                     2 * len(devices), noise=0.3)
    sf, idxf, esf = make_sharded_eval(wl, mesh, cfg=cfg, elite_k=4,
                                      engine="fused_interpret")(pop)
    sl, idxl, esl = make_sharded_eval(wl, mesh, cfg=cfg, elite_k=4,
                                      engine="flat")(pop)
    np.testing.assert_allclose(np.asarray(sf), np.asarray(sl),
                               rtol=2e-6, atol=2e-6)
    np.testing.assert_array_equal(np.asarray(idxf), np.asarray(idxl))


def test_unified_population_eval_fused_engine():
    from fks_tpu.parallel import make_population_eval

    wl = _roomy()
    cfg = SimConfig(track_ctime=False)
    params = parametric.init_population(jax.random.PRNGKey(4), 6, noise=0.2)
    res = make_population_eval(wl, cfg=cfg, engine="fused_interpret")(params)
    ref = make_population_eval(wl, cfg=cfg, engine="flat")(params)
    np.testing.assert_allclose(np.asarray(res.policy_score),
                               np.asarray(ref.policy_score),
                               rtol=2e-6, atol=2e-6)
    with pytest.raises(ValueError, match="parametric"):
        make_population_eval(wl, param_policy=lambda p, a, b: 0,
                             engine="fused_interpret")


def test_vmem_guard_rejects_scale_shapes():
    from fks_tpu.data.synthetic import synthetic_workload

    wl = synthetic_workload(1000, 100_000, seed=0)
    with pytest.raises(ValueError, match="VMEM"):
        fused.make_fused_population_run(wl, SimConfig(track_ctime=False),
                                        interpret=True)


def test_sharded_generation_step_fused():
    """device_evolution's training step (eval -> all-gather -> top-k ->
    mutate) drives the fused engine end to end on the virtual mesh."""
    from fks_tpu.parallel import make_sharded_generation_step, population_mesh

    devices = jax.devices()
    if len(devices) < 2:
        pytest.skip("needs the multi-device CPU mesh")
    wl = _roomy()
    cfg = SimConfig(track_ctime=False)
    mesh = population_mesh(devices)
    pop = parametric.init_population(jax.random.PRNGKey(5),
                                     2 * len(devices), noise=0.2)
    step = make_sharded_generation_step(wl, mesh, cfg=cfg, elite_k=4,
                                        engine="fused_interpret")
    new_pop, scores, elite_scores = step(pop, jax.random.PRNGKey(6))
    assert new_pop.shape == pop.shape
    assert np.isfinite(np.asarray(scores)).all()
    assert float(np.max(elite_scores)) >= float(np.min(scores))


def test_parametric_evolution_on_fused_engine():
    """ParametricEvolution (device-resident weight evolution) driving the
    fused kernel for 2 generations improves-or-holds its best score."""
    from fks_tpu.funsearch.device_evolution import ParametricEvolution

    devices = jax.devices()
    if len(devices) < 2:
        pytest.skip("needs the multi-device CPU mesh")
    pe = ParametricEvolution(_roomy(), pop_size=2 * len(devices),
                             cfg=SimConfig(track_ctime=False),
                             engine="fused_interpret", seed=1)
    first = pe.run(1)
    second = pe.run(1)
    assert pe.generation == 2
    assert second.best_score >= 0.0
    assert pe.best_score >= first.best_score
    assert "priority_function" in pe.best_code()


def test_mosaic_lowering_for_tpu_from_cpu():
    """The kernel LOWERS for the TPU target (host-side Mosaic pass) even
    on a CPU-only host. Interpret mode accepts primitives real Mosaic
    rejects — the first on-hardware compile of this kernel failed on a
    ``.at[:, 0].set`` scatter that every interpret-mode test had passed.
    This pins the primitive set the host-side pass accepts; what libtpu's
    own Mosaic passes accept is only proven by ``chip_smoke.py``."""
    wl = _roomy()
    cfg = SimConfig(max_steps=4 * 48, track_ctime=False)
    params = parametric.init_population(jax.random.PRNGKey(0), 8, noise=0.1)
    run = fused.make_fused_population_run(wl, cfg, lanes=8, interpret=False)
    # lower under the kernel's real conditions: the session runs without
    # x64 (the kernel pins i32/f32); under the test harness's global x64
    # the mosaic pass recurses without terminating (jax-internal), which
    # no production path ever hits
    prev_x64 = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)
    try:
        low = jax.jit(run).trace(params).lower(lowering_platforms=("tpu",))
    finally:
        jax.config.update("jax_enable_x64", prev_x64)
    assert "tpu_custom_call" in low.as_text()
