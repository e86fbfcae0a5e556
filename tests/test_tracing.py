"""Decision-trace instrument + first-divergence localization.

The contract under test (sim/types.TraceBuffer + funsearch/tracing docstrings):
``decision_trace=False`` compiles the IDENTICAL program (the trailing
``trace=None`` state field has zero pytree leaves); ``decision_trace=True``
logs one row per processed event inside the jitted step, per-lane under
vmap and the 8-virtual-device shard_map mesh; ``funsearch.tracing`` aligns two
engines' logs and names the first divergent step; the fused kernel
rejects the instrument with a pointer at the replay path.
"""
import functools
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fks_tpu import cli, obs
from fks_tpu.models import parametric, zoo
from fks_tpu.funsearch import tracing
from fks_tpu.sim import engine, flat, fused
from fks_tpu.sim.engine import SimConfig
from fks_tpu.sim.types import TRACE_KIND_NAMES, TraceBuffer

CLEAN = parametric.seed_weights("first_fit")


def _node_pref_policy(node_idx: int):
    """(param, pod, nodes) policy that always prefers ``node_idx`` among
    the feasible nodes — two different preferences are GUARANTEED to
    diverge at the very first CREATE, which pins down the first-divergence
    localization deterministically."""
    def pol(_p, pod, nodes):
        mask = zoo.feasible_mask(pod, nodes)
        pref = jnp.where(jnp.arange(mask.shape[0]) == node_idx, 2000, 1000)
        return jnp.where(mask, pref, 0)
    return pol


def _lane(trace, i) -> TraceBuffer:
    """Lane ``i`` of a batched TraceBuffer."""
    return TraceBuffer(data=trace.data[i], scores=trace.scores[i],
                       count=trace.count[i])


def _tools(name):
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools"))
    try:
        return __import__(name)
    finally:
        sys.path.pop(0)


# ------------------------------------------------- disabled-path identity

@pytest.mark.parametrize("mod", [engine, flat], ids=["exact", "flat"])
def test_trace_off_compiles_identical_program(micro_workload, mod):
    """decision_trace=False must be invisible to the compiler: same jaxpr
    as the seed default, and no trace on the result."""
    off = SimConfig(decision_trace=False)
    default = SimConfig()
    j_off = jax.make_jaxpr(mod.make_param_run_fn(micro_workload,
                                                 parametric.score, off))(
        CLEAN, mod.initial_state(micro_workload, off))
    j_def = jax.make_jaxpr(mod.make_param_run_fn(micro_workload,
                                                 parametric.score, default))(
        CLEAN, mod.initial_state(micro_workload, default))
    assert str(j_off) == str(j_def)

    on = SimConfig(decision_trace=True)
    j_on = jax.make_jaxpr(mod.make_param_run_fn(micro_workload,
                                                parametric.score, on))(
        CLEAN, mod.initial_state(micro_workload, on))
    assert str(j_on) != str(j_off)

    res = mod.simulate(micro_workload, zoo.ZOO["first_fit"](), off)
    assert res.trace is None


# ------------------------------------------------------- trace invariants

@pytest.mark.parametrize("mod", [engine, flat], ids=["exact", "flat"])
def test_trace_rows_match_processed_events(micro_workload, mod):
    cfg = SimConfig(decision_trace=True)
    res = mod.simulate(micro_workload, zoo.ZOO["first_fit"](), cfg)
    rows = tracing.extract_trace(res)
    assert len(rows) == int(np.asarray(res.events_processed))
    assert len(rows) == int(np.asarray(res.trace.count)) > 0
    for r in rows:
        assert r["kind"] in TRACE_KIND_NAMES
        assert r["pending"] >= 0
        assert r["free_cpu"] >= 0 and r["free_mem"] >= 0
        if r["kind"] == "DELETE":
            assert r["score"] == 0.0 and r["margin"] == 0.0
    assert rows[0]["kind"] == "CREATE"
    # the instrument must not perturb the simulation itself
    off = mod.simulate(micro_workload, zoo.ZOO["first_fit"](), SimConfig())
    assert float(res.policy_score) == float(off.policy_score)
    assert int(res.scheduled_pods) == int(off.scheduled_pods)


@pytest.mark.parametrize("name", ["first_fit", "best_fit"])
def test_exact_and_flat_traces_align(micro_workload, name):
    """Same policy through both engines: the decision logs must agree
    step for step (the flat engine's pod column carries the original
    input-order id precisely so this alignment needs no un-permuting)."""
    cfg = SimConfig(decision_trace=True)
    a = tracing.extract_trace(
        engine.simulate(micro_workload, zoo.ZOO[name](), cfg))
    b = tracing.extract_trace(
        flat.simulate(micro_workload, zoo.ZOO[name](), cfg))
    assert tracing.align_traces(a, b) is None


def test_trace_buffer_saturates_at_trace_len(micro_workload):
    """A trace shorter than the event count keeps the first rows and the
    count stops at capacity instead of wrapping or going out of bounds."""
    full = engine.simulate(micro_workload, zoo.ZOO["first_fit"](),
                           SimConfig(decision_trace=True))
    short = engine.simulate(micro_workload, zoo.ZOO["first_fit"](),
                            SimConfig(decision_trace=True, trace_len=3))
    assert int(short.trace.count) == 3
    np.testing.assert_array_equal(np.asarray(short.trace.data),
                                  np.asarray(full.trace.data)[:3])


# ------------------------------------------------- vmap / mesh isolation

def test_vmap_per_lane_trace_isolation(micro_workload):
    cfg = SimConfig(decision_trace=True)
    run = jax.jit(engine.make_population_run_fn(micro_workload,
                                                parametric.score, cfg))
    params = jnp.stack([parametric.seed_weights("first_fit"),
                        parametric.seed_weights("best_fit")])
    res = run(params, engine.initial_state(micro_workload, cfg))
    single = jax.jit(engine.make_param_run_fn(micro_workload,
                                              parametric.score, cfg))
    for i in range(2):
        sres = single(params[i], engine.initial_state(micro_workload, cfg))
        lane = _lane(res.trace, i)
        assert int(lane.count) == int(sres.trace.count)
        np.testing.assert_array_equal(np.asarray(lane.data),
                                      np.asarray(sres.trace.data))
        np.testing.assert_array_equal(np.asarray(lane.scores),
                                      np.asarray(sres.trace.scores))


def test_shard_map_mesh_per_lane_traces(micro_workload):
    """8-virtual-device mesh: each shard fills its own lane's trace, and
    the gathered result is bit-identical to the vmap run — a single
    ``P(POP_AXIS)`` out_spec covers the whole TraceBuffer subtree as a
    pytree prefix."""
    from jax.sharding import PartitionSpec as P

    from fks_tpu.parallel.mesh import POP_AXIS, population_mesh

    mesh = population_mesh()
    assert mesh.shape[POP_AXIS] == 8  # conftest forces 8 virtual devices
    cfg = SimConfig(decision_trace=True)
    run = engine.make_population_run_fn(micro_workload, parametric.score,
                                        cfg)
    state0 = engine.initial_state(micro_workload, cfg)

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=(P(POP_AXIS),),
                       out_specs=(P(POP_AXIS), P(POP_AXIS)), check_vma=False)
    def shard_run(params_shard):
        res = run(params_shard, state0)
        return res.policy_score, res.trace

    params = parametric.init_population(jax.random.PRNGKey(0), 8, noise=0.1)
    scores, trace = jax.jit(shard_run)(params)
    ref = jax.jit(run)(params, state0)
    np.testing.assert_array_equal(np.asarray(scores),
                                  np.asarray(ref.policy_score))
    np.testing.assert_array_equal(np.asarray(trace.count),
                                  np.asarray(ref.trace.count))
    np.testing.assert_array_equal(np.asarray(trace.data),
                                  np.asarray(ref.trace.data))
    assert int(np.asarray(trace.count).min()) > 0


def test_sharded_eval_returns_traces_when_enabled(micro_workload):
    from fks_tpu.parallel.mesh import (
        make_sharded_eval, pad_population, population_mesh,
    )

    mesh = population_mesh()
    cfg = SimConfig(decision_trace=True)
    ev = make_sharded_eval(micro_workload, mesh, cfg=cfg, elite_k=2)
    params = parametric.init_population(jax.random.PRNGKey(1), 8, noise=0.1)
    padded, real = pad_population(np.asarray(params), mesh)
    out = ev(padded, real)
    assert len(out) == 4  # scores, elite idx, elite scores, traces
    trace = out[3]
    assert np.asarray(trace.data).shape[0] == padded.shape[0]
    rows = tracing.extract_trace(_lane(trace, 0))
    assert rows and rows[0]["kind"] == "CREATE"


# -------------------------------------------- alignment / diff host logic

def _row(**kw):
    base = dict(step=0, kind="CREATE", pod=0, node=1, pending=0,
                free_cpu=10, free_mem=10, free_gpu=0, free_gpu_milli=0,
                score=1.0, margin=0.5)
    base.update(kw)
    return base


def test_align_traces_units():
    a = [_row(), _row(step=1, pod=1)]
    assert tracing.align_traces(a, [dict(r) for r in a]) is None
    # integer field mismatch names the field and both rows
    div = tracing.align_traces(a, [_row(node=0), _row(step=1, pod=1)])
    assert div == {"step": 0, "field": "node", "a": a[0],
                   "b": _row(node=0)}
    # scores compare within tolerance
    assert tracing.align_traces(a, [_row(score=1.0 + 1e-7),
                                    _row(step=1, pod=1)]) is None
    div = tracing.align_traces(a, [_row(score=2.0), _row(step=1, pod=1)])
    assert div["field"] == "score" and div["step"] == 0
    # strict prefix: diverges at the first missing row
    div = tracing.align_traces(a, a[:1])
    assert div == {"step": 1, "field": "length", "a": a[1], "b": None}


def test_extract_trace_rejects_none_and_batched(micro_workload):
    with pytest.raises(ValueError, match="no decision trace"):
        tracing.extract_trace(None)
    cfg = SimConfig(decision_trace=True)
    run = jax.jit(engine.make_population_run_fn(micro_workload,
                                                parametric.score, cfg))
    res = run(jnp.stack([CLEAN, CLEAN]),
              engine.initial_state(micro_workload, cfg))
    with pytest.raises(ValueError, match="batched"):
        tracing.extract_trace(res)


def test_trace_diff_localizes_first_divergence(micro_workload, tmp_path):
    specs = [("prefer0", "exact", _node_pref_policy(0), None),
             ("prefer1", "exact", _node_pref_policy(1), None)]
    d = tmp_path / "run"
    with obs.FlightRecorder(str(d)) as rec:
        record = tracing.trace_diff(micro_workload, specs, recorder=rec,
                                    label="unit")
    assert record["divergent"]
    div = record["first_divergence"]
    assert div["step"] == 0 and div["field"] == "node"
    assert div["a"]["node"] == 0 and div["b"]["node"] == 1
    text = tracing.format_diff(record)
    assert "FIRST DIVERGENCE at step 0" in text
    events = [json.loads(l)
              for l in (d / "events.jsonl").read_text().splitlines()]
    kinds = [e["kind"] for e in events]
    assert kinds.count("decision_trace") == 2
    assert kinds.count("trace_diff") == 1
    # the run dir (embedded trace rows included) passes the schema checker
    cjs = _tools("check_jsonl_schema")
    assert cjs.check_run_dir(str(d))["events.jsonl"] == 3


def test_trace_diff_self_is_clean(micro_workload):
    pp, params = tracing.policy_params(micro_workload,
                                       policy_name="best_fit")
    record = tracing.trace_diff(
        micro_workload,
        [("exact", "exact", pp, params), ("flat", "flat", pp, params)],
        recorder=obs.NULL)
    assert not record["divergent"]
    assert record["first_divergence"] is None
    assert "no divergence" in tracing.format_diff(record)
    steps = record["steps"]
    assert steps["exact"] == steps["flat"] > 0


def test_policy_params_unknown_name(micro_workload):
    with pytest.raises(ValueError, match="unknown policy"):
        tracing.policy_params(micro_workload, policy_name="nope")


# --------------------------------------------------- engine-gate behavior

def test_fused_plan_rejects_decision_trace(micro_workload):
    with pytest.raises(ValueError, match="decision trace"):
        fused._build_plan(micro_workload, SimConfig(decision_trace=True))


def test_replay_rejects_fused(micro_workload):
    with pytest.raises(ValueError):
        tracing.replay(micro_workload, "fused", parametric.score, CLEAN)


# --------------------------------------------------------- CLI + schema

@pytest.fixture
def micro_cli(monkeypatch, micro_workload):
    monkeypatch.setattr(cli, "_parse_workload",
                        lambda args: ("micro", micro_workload))
    return micro_workload


def test_cli_trace_diff_no_divergence_exit_zero(micro_cli, tmp_path,
                                                capsys):
    d = tmp_path / "td"
    rc = cli.main(["trace-diff", "--cpu", "--engines", "exact,flat",
                   "--policy", "first_fit", "--run-dir", str(d)])
    assert rc == 0
    assert "no divergence" in capsys.readouterr().out
    cjs = _tools("check_jsonl_schema")
    counts = cjs.check_run_dir(str(d))
    assert counts["events.jsonl"] == 3


def test_cli_trace_diff_divergence_exit_one(micro_cli, monkeypatch,
                                            capsys):
    fake = {"engines": ["exact", "flat"], "label": "first_fit",
            "steps": {"exact": 2, "flat": 2},
            "scores": {"exact": 0.5, "flat": 0.4}, "score_tol": 1e-5,
            "divergent": True,
            "first_divergence": {"step": 1, "field": "node",
                                 "a": _row(step=1), "b": _row(step=1,
                                                              node=0)}}
    monkeypatch.setattr(tracing, "trace_diff", lambda *a, **k: fake)
    rc = cli.main(["trace-diff", "--cpu", "--engines", "exact,flat",
                   "--policy", "first_fit"])
    assert rc == 1
    assert "FIRST DIVERGENCE" in capsys.readouterr().out


def test_cli_trace_diff_usage_errors(micro_cli):
    assert cli.main(["trace-diff", "--cpu", "--engines", "exact"]) == 2
    assert cli.main(["trace-diff", "--cpu",
                     "--engines", "exact,fused"]) == 2
    assert cli.main(["trace-diff", "--cpu", "--engines", "exact,flat",
                     "--policy", "nope"]) == 2
    assert cli.main(["trace-diff", "--cpu", "--engines", "exact,flat",
                     "--code", "/nonexistent/path.py"]) == 2


def test_schema_checker_embedded_trace_kinds(tmp_path):
    cjs = _tools("check_jsonl_schema")
    good = [{"ts": 1, "kind": "decision_trace", "engine": "exact",
             "events": [{"kind": "CREATE"}, {"kind": "RETRY"}]},
            {"ts": 2, "kind": "trace_diff", "engines": ["a", "b"],
             "divergent": True,
             "first_divergence": {"step": 0, "field": "node",
                                  "a": {"kind": "DELETE"}, "b": None}}]
    cjs.check_kinds("x", good, cjs.EVENT_KIND_REQUIRED)  # no raise
    bad = [{"ts": 1, "kind": "decision_trace", "engine": "exact",
            "events": [{"kind": "SPAWN"}]}]
    with pytest.raises(cjs.SchemaError, match="unknown.*SPAWN"):
        cjs.check_kinds("x", bad, cjs.EVENT_KIND_REQUIRED)
    missing = [{"ts": 1, "kind": "trace_diff", "engines": ["a", "b"]}]
    with pytest.raises(cjs.SchemaError, match="missing"):
        cjs.check_kinds("x", missing, cjs.EVENT_KIND_REQUIRED)


def test_report_summarizes_trace_diffs():
    from fks_tpu.obs.report import _trace_diff_lines
    events = [
        {"kind": "trace_diff", "engines": ["exact", "flat"],
         "divergent": True, "first_divergence": {"step": 7}},
        {"kind": "trace_diff", "engines": ["exact", "flat"],
         "divergent": True, "first_divergence": {"step": 3}},
        {"kind": "trace_diff", "engines": ["exact", "exact#1"],
         "divergent": False, "first_divergence": None},
    ]
    lines = _trace_diff_lines(events)
    assert lines[0] == "trace diffs: 3 recorded, 2 divergent"
    assert any("exact vs flat: first divergent step 3" in l for l in lines)
    assert _trace_diff_lines([]) == []
