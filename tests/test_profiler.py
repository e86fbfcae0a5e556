"""Device-time attribution profiler (fks_tpu.obs.profiler).

The acceptance bar this file holds: a profiled flat-CPU evolve smoke
attributes >= 95% of the measured wall to named stages; the per-stage
compile split agrees with the CompileWatcher's own deltas; the DISABLED
path is a pure no-op (no records, no fences, bit-identical lowering —
also pinned as ``flat_step/profiled`` in the jaxpr manifest); and the
occupancy math (``parallel.mesh.occupancy_stats``) folds pad/scenario/
segment axes the way ``utilization_pct`` expects.
"""
import json
import pathlib
import time

import jax
import jax.numpy as jnp
import pytest

from fks_tpu.obs.profiler import NULL_PROFILER, StageProfiler
from fks_tpu.obs.spans import span as obs_span
from fks_tpu.obs.telemetry import CompileWatcher
from fks_tpu.parallel.mesh import occupancy_stats, pad_stats

FIXTURES = pathlib.Path(__file__).parent / "fixtures"


class _Recorder:
    """Capture ``metric(kind, ...)`` calls (NullRecorder-shaped; the
    profiler's owned CompileWatcher also routes ``event`` through it)."""

    def __init__(self):
        self.rows = []

    def metric(self, kind, *dicts, **fields):
        row = {"kind": kind}
        for d in dicts:
            row.update(d)
        row.update(fields)
        self.rows.append(row)

    def event(self, *a, **kw):
        pass


def _fresh_jit():
    # a new lambda each call -> a new jit cache entry -> a real compile
    return jax.jit(lambda x: jnp.sin(x) * 2.0 + jnp.sum(x))


def test_stage_records_wall_and_compile_split():
    rec = _Recorder()
    with StageProfiler(scope="t", recorder=rec) as prof:
        f = _fresh_jit()
        x = jnp.ones(64)
        with prof.stage("warm", lanes=64) as h:
            h.sync(f(x))
        with prof.stage("steady") as h:
            h.sync(f(x))
    warm, steady = prof.records
    assert warm["stage"] == "warm" and warm["scope"] == "t"
    assert warm["lanes"] == 64 and warm["depth"] == 0
    assert warm["compile_count"] >= 1
    assert 0.0 < warm["compile_seconds"] <= warm["wall_seconds"]
    # the second call hits the jit cache: no compile charged
    assert steady["compile_count"] == 0
    assert steady["compute_seconds"] == steady["wall_seconds"]
    # each stage landed as one device_profile metric
    assert [r["kind"] for r in rec.rows] == ["device_profile"] * 2


def test_stage_is_built_on_one_span():
    """Enabled: one ``obs.span`` per stage, named ``stage/<name>`` unless
    the call site names it, and ``wall_seconds`` is that span's length."""
    from fks_tpu.obs import spans

    with StageProfiler(scope="t", recorder=_Recorder()) as prof:
        with prof.stage("steady", lanes=8) as h:
            h.annotate(pad_waste_fraction=0.25)
        with prof.stage("h2d", span="serve/chunk/h2d", chunk=1) as h2:
            h2.span.set(bytes=64)
    a, b = spans.LOG.snapshot()[-2:]
    assert (a.name, b.name) == ("stage/steady", "serve/chunk/h2d")
    assert a is h.span.record and b is h2.span.record
    # fields given to stage() ride on both; annotate() is the record's
    assert a.fields == {"lanes": 8}
    assert b.fields == {"chunk": 1, "bytes": 64}
    ra, rb = prof.records
    assert ra["wall_seconds"] == pytest.approx(a.t1 - a.t0, abs=1e-6)
    assert rb["wall_seconds"] == pytest.approx(b.t1 - b.t0, abs=1e-6)
    assert ra["lanes"] == 8 and ra["occupancy"] == 0.75
    assert rb["chunk"] == 1 and "bytes" not in rb


def test_disabled_stage_opens_only_the_spans_call_sites_name():
    from fks_tpu.obs import spans

    with obs_span("test/fence") as fence:
        pass
    with NULL_PROFILER.stage("steady", lanes=8) as h:
        assert h.span is None
    with NULL_PROFILER.stage("h2d", span="serve/chunk/h2d", chunk=0) as h2:
        assert h2.sync("value") == "value"     # no fence when disabled
        h2.annotate(anything=1)
        h2.span.set(bytes=32)
    new = [r for r in spans.LOG.snapshot() if r.seq > fence.record.seq]
    assert [(r.name, r.fields) for r in new] == [
        ("serve/chunk/h2d", {"chunk": 0, "bytes": 32})]
    assert NULL_PROFILER.records == [] and h2.record is None


def test_compile_split_matches_watcher():
    watcher = CompileWatcher().install()
    try:
        prof = StageProfiler(scope="t", recorder=_Recorder(),
                             watcher=watcher)
        x = jnp.ones(32)  # fill-program compile, BEFORE the baselines
        jax.block_until_ready(x)
        s0 = watcher.backend_compile_seconds
        n0 = watcher.backend_compile_count
        for name in ("a", "b"):
            with prof.stage(name) as h:
                h.sync(_fresh_jit()(x))
        got_n = sum(r["compile_count"] for r in prof.records)
        got_s = sum(r["compile_seconds"] for r in prof.records)
        assert got_n == watcher.backend_compile_count - n0 >= 2
        assert got_s == pytest.approx(
            watcher.backend_compile_seconds - s0, abs=1e-5)
    finally:
        watcher.uninstall()


def test_nested_stage_depth_excluded_from_summary():
    prof = StageProfiler(scope="t", recorder=_Recorder())
    with prof.stage("outer"):
        with prof.stage("inner"):
            time.sleep(0.01)
    prof.close()
    by = {r["stage"]: r for r in prof.records}
    assert by["inner"]["depth"] == 1 and by["outer"]["depth"] == 0
    # the inner stage's wall is already inside the outer's: only depth-0
    # stages count toward the attributed total
    summ = prof.summary(measured_wall=by["outer"]["wall_seconds"])
    assert [s["stage"] for s in summ["stages"]] == ["outer"]


def test_disabled_profiler_is_pure_noop():
    assert not NULL_PROFILER.enabled
    sentinel = object()  # block_until_ready would choke on this
    with NULL_PROFILER.stage("anything", lanes=8) as h:
        assert h.sync(sentinel) is sentinel
        h.annotate(ignored=1)
        assert h.record is None
    NULL_PROFILER.segment_tick()
    assert NULL_PROFILER.records == []
    assert NULL_PROFILER.watcher is None


def test_profiled_lowering_bit_identical(micro_workload):
    from fks_tpu.models import zoo
    from fks_tpu.sim import flat
    from fks_tpu.sim.engine import SimConfig, loop_tables

    cfg = SimConfig()
    ktable, max_steps = loop_tables(micro_workload, cfg)
    step = flat.build_step(micro_workload, zoo.first_fit(), cfg, ktable,
                           max_steps)
    s0 = flat.initial_state(micro_workload, cfg)
    base = str(jax.make_jaxpr(step)(s0))
    with StageProfiler(scope="t", recorder=_Recorder()) as prof:
        with prof.stage("pin"):
            inside = str(jax.make_jaxpr(step)(s0))
    assert inside == base


def test_manifest_pins_profiled_path():
    with open(FIXTURES / "jaxpr_pins.json") as f:
        pins = json.load(f)["pins"]
    assert "flat_step/profiled" in pins
    assert pins["flat_step/profiled"] == pins["flat_step/baseline"]


def test_occupancy_stats_folds_axes():
    s = occupancy_stats(3, 4)
    assert s["real_count"] == 3 and s["padded_count"] == 4
    assert s["pad_waste_fraction"] == pytest.approx(0.25)
    assert s["launched_lane_steps"] == 4 and s["real_lane_steps"] == 3
    s = occupancy_stats(3, 4, scenarios=2, segments=5)
    assert s["launched_lane_steps"] == 40 and s["real_lane_steps"] == 30
    # degenerate inputs clamp instead of exploding
    assert occupancy_stats(0, 4)["pad_waste_fraction"] == 0.0
    assert occupancy_stats(4, 4, scenarios=0)["scenarios"] == 1
    # base keys come straight from pad_stats
    assert set(pad_stats(3, 4)) <= set(s)


def test_utilization_from_occupancy_and_flops():
    prof = StageProfiler(scope="t", recorder=_Recorder())
    f = _fresh_jit()
    x = jnp.ones(16)
    h0 = h = None
    with prof.stage("eval", **occupancy_stats(3, 4)) as h:
        h.sync(f(x))  # compile inside: utilization must discount it
        h.annotate(cost_flops=1e6)
    with prof.stage("eval2", pad_waste_fraction=0.0) as h0:
        h0.sync(f(x))
    prof.close()
    r, r0 = h.record, h0.record
    assert r["occupancy"] == pytest.approx(0.75)
    # occupancy * compute/wall * 100 — compile time can't be utilized
    assert r["utilization_pct"] == pytest.approx(
        100.0 * 0.75 * r["compute_seconds"] / r["wall_seconds"], abs=0.01)
    assert r["est_flops_per_sec"] == pytest.approx(
        1e6 / r["compute_seconds"], rel=1e-3)
    assert r0["utilization_pct"] == pytest.approx(100.0, abs=0.1)


def test_summary_attribution_and_emit():
    rec = _Recorder()
    prof = StageProfiler(scope="t", recorder=rec)
    for name, secs in (("a", 0.03), ("b", 0.01)):
        with prof.stage(name, pad_waste_fraction=0.5):
            time.sleep(secs)
    prof.close()
    summ = prof.summary(measured_wall=0.05, emit=True)
    assert [s["stage"] for s in summ["stages"]] == ["a", "b"]
    assert summ["attributed_fraction"] >= 0.75
    assert summ["attributed_fraction"] + summ["idle_fraction"] == \
        pytest.approx(1.0, abs=1e-3)
    # annotated utilization survives aggregation (wall-weighted mean)
    assert all("utilization_pct" in s for s in summ["stages"])
    total = [r for r in rec.rows if r.get("stage") == "__total__"]
    assert len(total) == 1
    assert total[0]["attributed_fraction"] == summ["attributed_fraction"]


def test_evolve_profile_attribution_ge_95pct():
    """The tentpole acceptance number: a profiled flat-CPU evolve smoke
    attributes >= 95% of its wall clock to named pipeline stages."""
    from fks_tpu.data.synthetic import synthetic_workload
    from fks_tpu.funsearch import evolution

    wl = synthetic_workload(8, 12, seed=0)
    cfg = evolution.EvolutionConfig(
        population_size=6, generations=2, candidates_per_generation=3,
        early_stop_threshold=10.0, max_workers=2)
    t0 = time.perf_counter()
    fs = evolution.run(wl, cfg, engine="flat", log=lambda *_: None,
                       profile=True)
    wall = time.perf_counter() - t0
    assert fs.profiler.enabled and fs.profiler.records
    summ = fs.profiler.summary(measured_wall=wall)
    assert summ["attributed_fraction"] >= 0.95, summ
    stages = {s["stage"] for s in summ["stages"]}
    assert {"setup", "seed", "codegen", "rank", "ledger"} <= stages
    # backend stages run at depth 0 during generations (the evolution
    # spans don't nest profiler stages around evaluate())
    assert "device-eval" in {r["stage"] for r in fs.profiler.records}
    # profile=off leaves the same run un-instrumented
    fs2 = evolution.run(wl, cfg, engine="flat", log=lambda *_: None)
    assert not fs2.profiler.enabled and fs2.profiler.records == []
