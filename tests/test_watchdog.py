"""Numerics watchdog: in-graph guard semantics (per-lane isolation under
vmap and the 8-virtual-device shard_map mesh, disabled-path bit-identity),
host-side event reporting, and the online parity sentinel.

The guard contract under test (sim/guards.py docstring): watchdog=False
compiles the identical program; watchdog=True is bit-identical whenever no
violation fires; a violating lane is masked to "refuse placement" and
flagged WITHOUT poisoning sibling lanes.
"""
import functools
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fks_tpu import obs
from fks_tpu.funsearch.parity import ParitySentinel
from fks_tpu.models import parametric, zoo
from fks_tpu.obs.watchdog import check_result
from fks_tpu.sim import engine, flat
from fks_tpu.sim.engine import SimConfig
from fks_tpu.sim.guards import (
    FLAG_INF, FLAG_NAN, FLAG_RANGE, combined_flags, describe_flags,
    fitness_flags, sanitize_scores, score_flags,
)

CLEAN = parametric.seed_weights("first_fit")


def _float_first_fit(pod, nodes):
    """Float-scored first-fit. The score guard is a static no-op for the
    integer score dtypes the stock policies emit (the VM masks non-finite
    values before its own int cast), so guard tests ride the supported
    float-policy surface."""
    return jnp.where(zoo.feasible_mask(pod, nodes), 1000.0, 0.0)


def _poison_policy(p, pod, nodes):
    """Param policy: p=0 -> clean float first-fit scores, p=1 -> all-NaN,
    p=2 -> all-Inf. The scalar param lets one vmap/shard_map lane go bad
    while its siblings stay clean."""
    base = _float_first_fit(pod, nodes)
    bad = jnp.where(p >= 1.5, jnp.inf, jnp.nan).astype(base.dtype)
    return jnp.where(p >= 0.5, bad, base)


# ------------------------------------------------------------ guard units

def test_score_flags_classifies_nan_and_inf():
    nan_mask = int(score_flags(jnp.asarray([1.0, jnp.nan]), jnp.bool_(True)))
    inf_mask = int(score_flags(jnp.asarray([jnp.inf, 0.0]), jnp.bool_(True)))
    both = int(score_flags(jnp.asarray([jnp.nan, jnp.inf]), jnp.bool_(True)))
    assert nan_mask == FLAG_NAN
    assert inf_mask == FLAG_INF
    assert both == FLAG_NAN | FLAG_INF
    assert int(score_flags(jnp.asarray([0.5, 2.0]), jnp.bool_(True))) == 0


def test_score_flags_gated_and_integer_noop():
    # a discarded (gate=False) score must not flag
    assert int(score_flags(jnp.asarray([jnp.nan]), jnp.bool_(False))) == 0
    # integer dtypes cannot hold NaN/Inf: statically clean
    assert int(score_flags(jnp.asarray([1, 2], jnp.int32),
                           jnp.bool_(True))) == 0


def test_sanitize_scores_masks_to_refuse():
    out = np.asarray(sanitize_scores(jnp.asarray([1.5, jnp.nan, -jnp.inf])))
    np.testing.assert_array_equal(out, [1.5, 0.0, 0.0])
    # identity for finite inputs and integer dtypes
    np.testing.assert_array_equal(
        np.asarray(sanitize_scores(jnp.asarray([2.0, -3.0]))), [2.0, -3.0])
    ints = jnp.asarray([4, 5], jnp.int32)
    assert sanitize_scores(ints) is ints


def test_fitness_flags_range_check():
    assert int(fitness_flags(jnp.float32(0.5))) == 0
    assert int(fitness_flags(jnp.float32(jnp.nan))) == FLAG_NAN
    assert int(fitness_flags(jnp.float32(jnp.inf))) == FLAG_INF
    assert int(fitness_flags(jnp.float32(-0.1))) == FLAG_RANGE
    assert int(fitness_flags(jnp.float32(1.5))) == FLAG_RANGE


def test_describe_and_combine_flags():
    assert describe_flags(FLAG_NAN | FLAG_INF) == ["nan", "inf"]
    assert describe_flags(0) == []
    assert combined_flags(np.asarray([[0, 1], [4, 0]])) == 5
    assert combined_flags(np.asarray([], np.int32)) == 0
    assert combined_flags(0) == 0


# ----------------------------------------------------- engine integration

@pytest.mark.parametrize("pol", [parametric.as_policy(CLEAN),
                                 _float_first_fit],
                         ids=["int-scores", "float-scores"])
@pytest.mark.parametrize("mod", [engine, flat], ids=["exact", "flat"])
def test_watchdog_enabled_clean_is_bit_identical(micro_workload, mod, pol):
    off = mod.simulate(micro_workload, pol, SimConfig(watchdog=False))
    on = mod.simulate(micro_workload, pol, SimConfig(watchdog=True))
    assert float(on.policy_score) == float(off.policy_score)
    np.testing.assert_array_equal(np.asarray(on.assigned_node),
                                  np.asarray(off.assigned_node))
    assert int(on.scheduled_pods) == int(off.scheduled_pods)
    assert combined_flags(on.numeric_flags) == 0
    assert combined_flags(off.numeric_flags) == 0


@pytest.mark.parametrize("mod", [engine, flat], ids=["exact", "flat"])
def test_nan_policy_flagged_and_fitness_stays_finite(micro_workload, mod):
    cfg = SimConfig(watchdog=True)
    run = jax.jit(mod.make_param_run_fn(micro_workload, _poison_policy, cfg))
    res = run(jnp.float64(1.0), mod.initial_state(micro_workload, cfg))
    assert combined_flags(res.numeric_flags) & FLAG_NAN
    assert np.isfinite(float(res.policy_score))
    inf_res = run(jnp.float64(2.0), mod.initial_state(micro_workload, cfg))
    assert combined_flags(inf_res.numeric_flags) & FLAG_INF
    assert np.isfinite(float(inf_res.policy_score))


def test_watchdog_off_does_not_flag(micro_workload):
    cfg = SimConfig(watchdog=False)
    run = jax.jit(engine.make_param_run_fn(micro_workload, _poison_policy,
                                           cfg))
    res = run(jnp.float64(1.0), engine.initial_state(micro_workload, cfg))
    assert combined_flags(res.numeric_flags) == 0


def test_vmap_population_lane_isolation(micro_workload):
    cfg = SimConfig(watchdog=True)
    run = jax.jit(engine.make_population_run_fn(micro_workload,
                                                _poison_policy, cfg))
    params = jnp.asarray([0.0, 1.0, 0.0, 2.0])
    res = run(params, engine.initial_state(micro_workload, cfg))
    flags = np.asarray(res.numeric_flags)
    assert flags[1] & FLAG_NAN
    assert flags[3] & FLAG_INF
    assert flags[0] == 0 and flags[2] == 0
    # clean lanes are bit-identical to a watchdog-off single-policy run
    ref = engine.simulate(micro_workload, _float_first_fit,
                          SimConfig(watchdog=False))
    scores = np.asarray(res.policy_score)
    assert scores[0] == float(ref.policy_score)
    assert scores[2] == float(ref.policy_score)


def test_shard_map_mesh_lane_isolation(micro_workload):
    from jax.sharding import PartitionSpec as P

    from fks_tpu.parallel.mesh import POP_AXIS, population_mesh

    mesh = population_mesh()
    assert mesh.shape[POP_AXIS] == 8  # conftest forces 8 virtual devices
    cfg = SimConfig(watchdog=True)
    run = engine.make_population_run_fn(micro_workload, _poison_policy, cfg)
    state0 = engine.initial_state(micro_workload, cfg)

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=(P(POP_AXIS),),
                       out_specs=(P(POP_AXIS), P(POP_AXIS)), check_vma=False)
    def shard_run(params_shard):
        res = run(params_shard, state0)
        return res.numeric_flags, res.policy_score

    params = jnp.zeros(8).at[3].set(1.0).at[6].set(2.0)
    flags, scores = jax.jit(shard_run)(params)
    flags, scores = np.asarray(flags), np.asarray(scores)
    assert flags[3] & FLAG_NAN
    assert flags[6] & FLAG_INF
    clean = [i for i in range(8) if i not in (3, 6)]
    assert all(flags[i] == 0 for i in clean)
    ref = engine.simulate(micro_workload, _float_first_fit,
                          SimConfig(watchdog=False))
    for i in clean:
        assert scores[i] == float(ref.policy_score)


# --------------------------------------------------------- host reporting

def test_check_result_emits_watchdog_event(tmp_path):
    class _Res:
        numeric_flags = np.asarray([0, FLAG_NAN | FLAG_INF])

    d = tmp_path / "run"
    with obs.FlightRecorder(str(d)) as rec:
        mask = check_result(_Res(), recorder=rec, generation=4)
    assert mask == FLAG_NAN | FLAG_INF
    events = [json.loads(l) for l in (d / "events.jsonl").read_text()
              .splitlines()]
    wd = [e for e in events if e["kind"] == "watchdog"]
    assert len(wd) == 1
    assert wd[0]["flags"] == mask
    assert wd[0]["kinds"] == ["nan", "inf"]
    assert wd[0]["generation"] == 4


def test_check_result_clean_and_flagless_objects(tmp_path):
    class _Clean:
        numeric_flags = np.zeros(3, np.int32)

    d = tmp_path / "run"
    with obs.FlightRecorder(str(d)) as rec:
        assert check_result(_Clean(), recorder=rec) == 0
        assert check_result(object(), recorder=rec) == 0
    events = (d / "events.jsonl").read_text() \
        if (d / "events.jsonl").exists() else ""
    assert "watchdog" not in events


# --------------------------------------------------------- parity sentinel

class _StubRecord:
    def __init__(self, score, ok=True):
        self.score, self.ok = score, ok


class _StubReference:
    """Stands in for the lazily-built exact CodeEvaluator."""

    def __init__(self, scores):
        self.scores = scores

    def evaluate_one(self, code):
        v = self.scores[code]
        if v == "raise":
            raise RuntimeError("reference blew up")
        if v == "not-ok":
            return _StubRecord(0.0, ok=False)
        return _StubRecord(v)


def _load(d, name):
    p = d / name
    if not p.exists():
        return []
    return [json.loads(l) for l in p.read_text().splitlines()]


def test_parity_sentinel_zero_drift_no_alert(tmp_path):
    d = tmp_path / "run"
    with obs.FlightRecorder(str(d)) as rec:
        s = ParitySentinel(object(), sample=2, tol=1e-5, recorder=rec)
        s._ref = _StubReference({"a": 0.5, "b": 0.25})
        stats = s.check(1, [("a", 0.5), ("b", 0.25)])
    assert stats == {"generation": 1, "checked": 2, "max_drift": 0.0,
                     "alerts": 0, "failed": 0}
    assert s.alerts == 0 and s.checked == 2 and s.max_drift == 0.0
    parity = [m for m in _load(d, "metrics.jsonl") if m["kind"] == "parity"]
    assert len(parity) == 1
    assert parity[0]["checked"] == 2 and parity[0]["tol"] == 1e-5
    assert not [e for e in _load(d, "events.jsonl") if e["kind"] == "alert"]


def test_parity_sentinel_alerts_on_drift(tmp_path):
    d = tmp_path / "run"
    with obs.FlightRecorder(str(d)) as rec:
        s = ParitySentinel(object(), sample=2, tol=1e-5, recorder=rec)
        s._ref = _StubReference({"a": 0.5, "b": 0.26})  # b drifted by 0.01
        stats = s.check(3, [("a", 0.5), ("b", 0.25)])
    assert stats["alerts"] == 1 and s.alerts == 1
    assert stats["max_drift"] == pytest.approx(0.01)
    alerts = [e for e in _load(d, "events.jsonl") if e["kind"] == "alert"]
    assert len(alerts) == 1
    assert alerts[0]["source"] == "parity"
    assert alerts[0]["generation"] == 3
    assert alerts[0]["max_drift"] == pytest.approx(0.01)
    assert alerts[0]["tol"] == 1e-5


def test_parity_sentinel_sample_zero_is_noop():
    s = ParitySentinel(object(), sample=0, recorder=obs.NULL)
    stats = s.check(1, [("a", 1.0)])
    assert stats == {"generation": 1, "checked": 0, "max_drift": 0.0,
                     "alerts": 0}
    assert s._ref is None  # reference evaluator never built


def test_parity_sentinel_survives_reference_failures(tmp_path):
    d = tmp_path / "run"
    with obs.FlightRecorder(str(d)) as rec:
        s = ParitySentinel(object(), sample=3, tol=1e-5, recorder=rec)
        s._ref = _StubReference({"a": "raise", "b": "not-ok", "c": 0.75})
        stats = s.check(2, [("a", 0.1), ("b", 0.2), ("c", 0.75)])
    assert stats["failed"] == 2 and stats["checked"] == 1
    assert s.alerts == 0  # failures are counted, never alerted or raised


def test_parity_sentinel_exact_reference_round_trip(micro_workload):
    """End to end on the real evaluator: re-scoring a candidate against
    the score the same evaluator produced must show zero drift."""
    from fks_tpu.funsearch import template
    from fks_tpu.funsearch.backend import CodeEvaluator

    ev = CodeEvaluator(micro_workload, SimConfig(), engine="exact",
                       use_vm=False)
    code = dict(template.seed_policies())["first_fit"]
    base = ev.evaluate_one(code)
    assert base.ok
    s = ParitySentinel(ev, sample=1, tol=1e-5, recorder=obs.NULL)
    s._ref = ev  # reuse the already-compiled evaluator as the reference
    stats = s.check(0, [(code, float(base.score))])
    assert stats["checked"] == 1
    assert stats["max_drift"] == 0.0
    assert s.alerts == 0
