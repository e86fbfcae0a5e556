"""Flight recorder contract: run-dir layout, null-path zero writes, span
nesting/sync, jax.monitoring compile capture, device/mesh snapshots, the
evolution ledger, and the report renderer. (The recorder is the evidence
surface for every ROADMAP claim, so these tests pin its schema.)"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fks_tpu import obs
from fks_tpu.obs import recorder as recorder_mod
from fks_tpu.obs.report import render_report, sparkline


# --------------------------------------------------------------- recorder

def test_flight_recorder_run_dir_layout(tmp_path):
    d = tmp_path / "run"
    with obs.FlightRecorder(str(d), meta={"command": "test"}) as rec:
        rec.event("span", label="x", seconds=0.1)
        rec.metric("generation", {"generation": 1, "best_score": 0.5})
        rec.annotate_meta(note="hello")
    meta = json.loads((d / "meta.json").read_text())
    assert meta["run_id"] == rec.run_id
    assert meta["command"] == "test"
    assert meta["note"] == "hello"
    assert meta["status"] == "ok"
    assert "wall_seconds" in meta
    events = [json.loads(l) for l in (d / "events.jsonl").read_text()
              .splitlines()]
    assert events[0]["kind"] == "span" and events[0]["seq"] == 0
    assert "ts" in events[0]
    metrics = [json.loads(l) for l in (d / "metrics.jsonl").read_text()
               .splitlines()]
    assert metrics[0]["kind"] == "generation"
    assert metrics[0]["best_score"] == 0.5
    beat = json.loads((d / "heartbeat").read_text())
    assert beat["run_id"] == rec.run_id


def test_flight_recorder_error_status(tmp_path):
    d = tmp_path / "run"
    with pytest.raises(RuntimeError):
        with obs.recording(obs.FlightRecorder(str(d))):
            raise RuntimeError("boom")
    assert json.loads((d / "meta.json").read_text())["status"] == "error"
    assert obs.get_recorder() is obs.NULL  # restored


def test_recorder_coerces_numpy_and_jax_scalars(tmp_path):
    d = tmp_path / "run"
    with obs.FlightRecorder(str(d)) as rec:
        rec.metric("scale", score=np.float32(0.25), n=np.int64(3),
                   arr=jnp.arange(2), dev=jnp.float32(1.5))
    row = json.loads((d / "metrics.jsonl").read_text().splitlines()[0])
    assert row["score"] == 0.25 and row["n"] == 3
    assert row["arr"] == [0, 1] and row["dev"] == 1.5


def test_null_recorder_writes_nothing(tmp_path, monkeypatch):
    """The disabled path's contract: zero filesystem writes."""
    monkeypatch.chdir(tmp_path)
    rec = obs.NullRecorder()
    rec.event("span", label="x")
    rec.metric("generation", {"g": 1})
    rec.heartbeat()
    rec.annotate_meta(a=1)
    rec.finish()
    rec.close()
    assert list(tmp_path.iterdir()) == []
    assert rec.enabled is False


def test_recording_installs_and_restores(tmp_path):
    assert obs.get_recorder() is obs.NULL
    rec = obs.FlightRecorder(str(tmp_path / "r"))
    with obs.recording(rec) as got:
        assert got is rec
        assert obs.get_recorder() is rec
    assert obs.get_recorder() is obs.NULL
    assert json.loads((tmp_path / "r" / "meta.json").read_text())[
        "status"] == "ok"


# ------------------------------------------------------------------ spans

def test_span_nesting_paths_and_fields(tmp_path):
    with obs.FlightRecorder(str(tmp_path / "r")) as rec:
        with obs.span("outer", recorder=rec):
            assert obs.span_path() == "outer"
            with obs.span("inner", recorder=rec, generation=3):
                assert obs.span_path() == "outer/inner"
        assert obs.span_path() == ""
    events = [json.loads(l) for l in
              (tmp_path / "r" / "events.jsonl").read_text().splitlines()]
    by_label = {e["label"]: e for e in events if e["kind"] == "span"}
    assert by_label["inner"]["path"] == "outer/inner"
    assert by_label["inner"]["depth"] == 1
    assert by_label["inner"]["generation"] == 3
    assert by_label["outer"]["path"] == "outer"
    assert by_label["outer"]["depth"] == 0
    # inner exits (and records) before outer
    assert by_label["inner"]["seq"] < by_label["outer"]["seq"]
    assert by_label["outer"]["seconds"] >= by_label["inner"]["seconds"]


def test_span_syncs_device_value_before_stopping_clock(monkeypatch):
    from fks_tpu.utils import profiling

    synced = []
    monkeypatch.setattr(profiling.jax, "block_until_ready",
                        lambda v: synced.append(v))
    sentinel = object()
    with obs.span("eval") as t:
        got = t.sync(sentinel)
    assert got is sentinel and synced == [sentinel]
    assert t.seconds >= 0


def test_span_stack_unwinds_on_exception():
    with pytest.raises(ValueError):
        with obs.span("broken"):
            raise ValueError("x")
    assert obs.span_path() == ""


def test_span_records_to_the_ring_without_a_recorder():
    """The default state: no run directory, and the span is still kept,
    with its stamps, ids, thread and fields, children before parents."""
    import threading
    import time

    from fks_tpu.obs import spans

    before = time.perf_counter()
    with obs.span("outer", lanes=4) as outer:
        with obs.span("inner") as inner:
            inner.set(bytes=128)
    after = time.perf_counter()
    got = {r.name: r for r in spans.LOG.snapshot()[-2:]}
    o, i = got["outer"], got["inner"]
    assert o is outer.record and i is inner.record
    assert before <= o.t0 <= i.t0 <= i.t1 <= o.t1 <= after
    assert i.seq < o.seq
    assert o.parent_id is None and o.trace_id == o.span_id
    assert i.parent_id == o.span_id and i.trace_id == o.trace_id
    assert o.fields == {"lanes": 4} and i.fields == {"bytes": 128}
    assert o.thread == i.thread == threading.get_ident()
    assert outer.seconds == o.t1 - o.t0 == o.seconds


def test_span_lands_on_the_profiler_timeline_and_names_no_ops(monkeypatch):
    """Every span enters ``TraceAnnotation("fks/<name>")``; the host span
    no longer enters ``jax.named_scope`` (it named nothing: the programs
    it calls are already compiled)."""
    import jax

    seen = []

    class Ann:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            seen.append(("enter", self.name))

        def __exit__(self, *exc):
            seen.append(("exit", self.name))

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Ann)
    monkeypatch.setattr(jax, "named_scope", lambda name: seen.append(
        ("named_scope", name)))
    with obs.span("serve/chunk/h2d"):
        seen.append("body")
    assert seen == [("enter", "fks/serve/chunk/h2d"), "body",
                    ("exit", "fks/serve/chunk/h2d")]


def test_recorded_span_event_carries_t0(tmp_path):
    import time

    with obs.FlightRecorder(str(tmp_path / "r")) as rec:
        t_before = time.perf_counter()
        with obs.span("stage", recorder=rec, chunk=2) as t:
            pass
    (row,) = [json.loads(l) for l in
              (tmp_path / "r" / "events.jsonl").read_text().splitlines()
              if json.loads(l)["kind"] == "span"]
    assert row["chunk"] == 2 and row["depth"] == 0
    assert row["t0"] == pytest.approx(t.t0, abs=1e-6) and t.t0 >= t_before
    assert row["seconds"] == pytest.approx(t.seconds, abs=1e-6)


# -------------------------------------------------------------- telemetry

def test_compile_watcher_captures_compile_events(tmp_path):
    """Acceptance: the jax.monitoring listener captures >= 1 compile event
    when a fresh program is jit-compiled inside the watch scope."""
    with obs.FlightRecorder(str(tmp_path / "r")) as rec:
        with obs.CompileWatcher(rec) as w:
            # fresh shape+closure => cannot hit jit cache from other tests
            @jax.jit
            def _fresh(x):
                return (x * 3.14159).sum() + 41.0

            _fresh(jnp.arange(17.0)).block_until_ready()
        assert len(w.events) >= 1
        assert w.backend_compile_count >= 1
        assert w.backend_compile_seconds > 0
        # one name per backend compile, as jax gives it: what a "zero
        # compiles" assertion prints when it fails
        assert len(w.programs) == w.backend_compile_count
        assert any("_fresh" in name for name in w.programs), w.programs
        summary = w.summary()
        assert any(k.startswith("/jax/core/compile") for k in summary)
    events = [json.loads(l) for l in
              (tmp_path / "r" / "events.jsonl").read_text().splitlines()]
    compiles = [e for e in events if e["kind"] == "compile"]
    assert compiles and all("seconds" in e for e in compiles)


def test_compile_watcher_uninstall_stops_capture():
    w = obs.CompileWatcher(obs.NULL).install()
    w.uninstall()
    n0 = len(w.events)

    @jax.jit
    def _after(x):
        return x - 2.71828

    _after(jnp.arange(5.0)).block_until_ready()
    assert len(w.events) == n0


def test_watch_compiles_null_when_disabled():
    with obs.watch_compiles(obs.NULL) as w:
        assert w is None


def test_device_snapshot_cpu_guarded():
    snap = obs.device_snapshot()
    assert len(snap) == len(jax.devices())
    for d in snap:
        assert d["platform"] == "cpu"
        assert "memory_stats" in d  # None on CPU is fine; key must exist


def test_normalize_memory_stats_aliases_and_partials():
    norm = obs.normalize_memory_stats
    assert norm(None) is None
    assert norm({}) is None
    assert norm({"weird": 1}) is None
    full = {"bytes_in_use": 10, "peak_bytes_in_use": 20, "bytes_limit": 30}
    assert norm(full) == full
    # another backend's spellings land on the canonical keys
    assert norm({"bytes_used": 7, "bytes_reservable_limit": 9}) == {
        "bytes_in_use": 7, "bytes_limit": 9}
    # partial dicts keep what they can answer
    assert norm({"bytes_in_use": 7}) == {"bytes_in_use": 7}


def test_mesh_snapshot_pad_waste(tmp_path):
    from fks_tpu.parallel import population_mesh
    from fks_tpu.parallel.mesh import num_shards, pad_stats

    mesh = population_mesh(jax.devices())
    shards = num_shards(mesh)
    assert shards == 8  # conftest's virtual 8-device mesh
    snap = obs.mesh_snapshot(mesh, real_count=5)
    assert snap["shards"] == shards
    assert snap["real_count"] == 5
    assert snap["padded_count"] == 8
    assert snap["pad_lanes"] == 3
    assert snap["pad_waste_fraction"] == pytest.approx(3 / 8)
    assert pad_stats(8, 8)["pad_waste_fraction"] == 0.0
    assert pad_stats(0, 8)["padded_count"] == 0
    with obs.FlightRecorder(str(tmp_path / "r")) as rec:
        obs.record_mesh(mesh, real_count=5, recorder=rec)
    ev = [json.loads(l) for l in
          (tmp_path / "r" / "events.jsonl").read_text().splitlines()]
    assert ev[0]["kind"] == "mesh" and ev[0]["pad_lanes"] == 3


# ----------------------------------------------------------------- ledger

class _FakeEvaluator:
    compile_count = 2
    vm_count = 0
    vm_batch_count = 1
    segments_dispatched = 10


def test_ledger_counter_deltas_and_throughput(tmp_path):
    from fks_tpu.funsearch.evolution import GenerationStats

    ev = _FakeEvaluator()
    with obs.FlightRecorder(str(tmp_path / "r")) as rec:
        ledger = obs.EvolutionLedger(rec, ev)
        ledger.begin_generation()
        ev.compile_count = 5
        ev.segments_dispatched = 16
        stats = GenerationStats(
            generation=1, best_score=0.5, mean_score=0.4, new_candidates=8,
            accepted=6, rejected_similar=2, eval_seconds=2.0, compile_count=5,
            median_score=0.45, p10_score=0.3, sandbox_failed=1,
            transpile_failed=1, rescore_fallbacks=0, llm_seconds=0.7)
        row = ledger.commit(stats)
    assert row["programs_compiled"] == 3  # 5 - 2
    assert row["vm_segments"] == 6  # 16 - 10
    assert row["vm_batches"] == 0
    assert row["evals_per_sec"] == 4.0
    assert row["sandbox_failed"] == 1 and row["transpile_failed"] == 1
    disk = json.loads((tmp_path / "r" / "metrics.jsonl").read_text()
                      .splitlines()[0])
    assert disk["kind"] == "generation" and disk["generation"] == 1
    assert (tmp_path / "r" / "heartbeat").exists()


def test_ledger_null_recorder_no_writes(tmp_path, monkeypatch):
    from fks_tpu.funsearch.evolution import GenerationStats

    monkeypatch.chdir(tmp_path)
    ledger = obs.EvolutionLedger(obs.NULL, _FakeEvaluator())
    ledger.begin_generation()
    row = ledger.commit(GenerationStats(
        generation=1, best_score=0.1, mean_score=0.1, new_candidates=1,
        accepted=1, rejected_similar=0, eval_seconds=0.0, compile_count=0))
    assert row["generation"] == 1
    assert "evals_per_sec" not in row  # zero eval time -> no rate
    assert list(tmp_path.iterdir()) == []


# ------------------------------------------------- evolve null-path contract

def test_evolve_generation_without_recorder_writes_nothing(tmp_path,
                                                           monkeypatch,
                                                           micro_workload):
    """Acceptance: with no recorder configured, evolve_generation makes
    zero filesystem writes (relative to the cwd it runs in)."""
    from fks_tpu.funsearch import EvolutionConfig, FakeLLM
    from fks_tpu.funsearch.backend import CodeEvaluator
    from fks_tpu.funsearch.evolution import FunSearch

    fs = FunSearch(
        CodeEvaluator(micro_workload, engine="exact"),
        EvolutionConfig(generations=1, population_size=4, elite_size=1,
                        candidates_per_generation=2, max_workers=2),
        backend=FakeLLM(seed=0), log=lambda s: None)
    assert fs.recorder is obs.NULL
    fs.initialize_population()
    monkeypatch.chdir(tmp_path)  # any relative write would land here
    stats = fs.evolve_generation()
    assert list(tmp_path.iterdir()) == []
    assert stats.generation == 1
    assert stats.median_score <= stats.best_score
    assert stats.p10_score <= stats.median_score <= stats.best_score


# ----------------------------------------------------------------- report

def test_percentiles_nearest_rank():
    from fks_tpu.funsearch.evolution import _percentile

    desc = [5.0, 4.0, 3.0, 2.0, 1.0]
    assert _percentile(desc, 0.5) == 3.0
    assert _percentile(desc, 0.10) == 1.0
    assert _percentile(desc, 1.0) == 5.0
    assert _percentile([2.5], 0.5) == 2.5
    assert _percentile([], 0.5) == 0.0


def test_sparkline():
    assert sparkline([]) == ""
    assert sparkline([1.0, 1.0]) == "▄▄"
    s = sparkline([0.0, 0.5, 1.0])
    assert s[0] == "▁" and s[-1] == "█" and len(s) == 3


def test_render_report_from_jsonl_alone(tmp_path):
    """The report is a pure function of the run dir's files."""
    d = str(tmp_path / "r")
    with obs.FlightRecorder(d, meta={"command": "evolve"}) as rec:
        rec.event("device", platform="cpu", id=0, memory_stats=None)
        rec.event("span", label="llm", path="llm", depth=0, seconds=0.5)
        rec.event("compile",
                  key="/jax/core/compile/backend_compile_duration",
                  seconds=1.25)
        for g, best in ((1, 0.3), (2, 0.45)):
            rec.metric("generation", {
                "generation": g, "best_score": best, "median_score": best / 2,
                "p10_score": best / 4, "new_candidates": 8, "accepted": 6,
                "rejected_similar": 2, "sandbox_failed": 1,
                "transpile_failed": 0, "rescore_fallbacks": 0,
                "llm_seconds": 0.5, "eval_seconds": 2.0,
                "evals_per_sec": 4.0, "vm_segments": 3})
        rec.metric("bench_stage", {"stage": "throughput",
                                   "evals_per_sec": 100.0,
                                   "compile_seconds": 9.5,
                                   "steady_state_seconds": 5.0})
        rec.annotate_meta(best_score=0.45)
    out = render_report(d)
    assert "status ok" in out
    assert "[evolve]" in out
    assert "generations: 2" in out
    assert "0.45" in out
    assert "backend_compile_duration: 1x 1.250s total" in out
    assert "llm: 1x 0.500s" in out
    assert "bench stage throughput:" in out
    assert "compile_seconds=9.5" in out
    assert "devices: 1x cpu" in out
    # the sparkline line tracks best fitness across generations
    assert "fitness best 0.3000 -> 0.4500" in out


def test_render_report_tolerates_torn_tail_and_missing_files(tmp_path):
    d = tmp_path / "r"
    d.mkdir()
    (d / "meta.json").write_text(json.dumps(
        {"run_id": "x", "started": "now", "status": "running"}))
    (d / "metrics.jsonl").write_text(
        json.dumps({"ts": 1, "kind": "generation", "generation": 1,
                    "best_score": 0.2}) + "\n" + '{"ts": 2, "kind": "gen')
    out = render_report(str(d))
    assert "generations: 1" in out
    assert "status running" in out
    with pytest.raises(FileNotFoundError):
        render_report(str(tmp_path / "nope"))


def test_read_jsonl_rejects_mid_file_corruption(tmp_path):
    from fks_tpu.obs.report import read_jsonl

    p = tmp_path / "bad.jsonl"
    p.write_text('{"ok": 1}\n{broken\n{"ok": 2}\n')
    with pytest.raises(ValueError, match="bad.jsonl:2"):
        read_jsonl(str(p))


# ---------------------------------------------------------- schema checker

def test_check_jsonl_schema_tool(tmp_path):
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools"))
    try:
        import check_jsonl_schema as cjs
    finally:
        sys.path.pop(0)

    good = tmp_path / "good.jsonl"
    good.write_text(json.dumps({"ts": 1, "kind": "a"}) + "\n"
                    + json.dumps({"ts": 2, "kind": "b"}) + "\n")
    assert len(cjs.check_jsonl(str(good), required=("ts", "kind"))) == 2

    missing = tmp_path / "missing.jsonl"
    missing.write_text(json.dumps({"ts": 1}) + "\n")
    with pytest.raises(cjs.SchemaError, match="missing"):
        cjs.check_jsonl(str(missing), required=("kind",))

    torn = tmp_path / "torn.jsonl"
    torn.write_text(json.dumps({"ts": 1, "kind": "a"}) + "\n" + '{"half')
    assert len(cjs.check_jsonl(str(torn), required=("ts",))) == 1

    with obs.FlightRecorder(str(tmp_path / "run")) as rec:
        rec.event("span", label="x", seconds=0.0)
        # known kinds must carry their required keys (watchdog schema)
        rec.metric("generation", {"generation": 1, "best_score": 0.5})
    counts = cjs.check_run_dir(str(tmp_path / "run"))
    assert counts["events.jsonl"] == 1
    assert counts["metrics.jsonl"] == 1
    assert counts["heartbeat"] == 1
    assert cjs.main([str(good), "--require", "ts,kind"]) == 0
    assert cjs.main(["--run-dir", str(tmp_path / "run")]) == 0
    assert cjs.main([str(missing), "--require", "kind"]) == 1
