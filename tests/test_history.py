"""Cross-run history, trend regression flagging, SLO burn, stale fallback.

The contracts this file holds: a synthetic 10-run history with one
injected 30% throughput drop raises EXACTLY one trend alert (the change
point) while +/-2% noise raises none; ``cli compare --baseline auto``
resolves a non-0.0 healthy baseline; a record carrying another run's
headline under ``stale_from_run`` is never a baseline; SLO burn rates
price p99/qps windows against the error
budget; and the exporter/watch/schema layers speak the three new metric
kinds (``device_profile`` / ``trend_report`` / ``slo_burn``).
"""
import json
import os
import pathlib
import sys
import time

import pytest

from fks_tpu import cli
from fks_tpu.obs.history import RunHistory, resolve_auto_baseline
from fks_tpu.serve.accounting import SLOConfig, record_slo_burn, slo_burn

REPO = pathlib.Path(__file__).parent.parent
GOLDEN = str(pathlib.Path(__file__).parent / "fixtures" / "golden_run")

CLEAN = [100.0, 101.5, 99.2, 100.8, 98.9, 101.1, 99.7, 100.4, 99.9, 100.6]
REGRESSED = CLEAN[:7] + [70.0, 69.5, 70.3]


def _write_history(root, values, start=None):
    """Bench headline files with 1h-spaced mtimes (newest = last)."""
    root = pathlib.Path(root)
    root.mkdir(parents=True, exist_ok=True)
    start = time.time() - 3600 * len(values) if start is None else start
    paths = []
    for i, v in enumerate(values):
        p = root / f"BENCH_r{i:02d}.json"
        p.write_text(json.dumps(
            {"metric": "evals/s", "value": v, "unit": "evals/s",
             "vs_baseline": round(v / 40.0, 3)}) + "\n")
        ts = start + i * 3600
        os.utime(p, (ts, ts))
        paths.append(str(p))
    return paths


# ------------------------------------------------------------------ trends


def test_trends_flag_injected_regression_exactly_once(tmp_path):
    _write_history(tmp_path, REGRESSED)
    reports = RunHistory(str(tmp_path)).trends(["evals_per_sec"])
    assert len(reports) == 1
    rep = reports[0]
    assert rep["metric"] == "evals_per_sec" and rep["runs"] == 10
    # the 70.0/69.5/70.3 level shift collapses to ONE alert at the
    # change point, not one per post-shift run
    assert len(rep["alerts"]) == 1
    alert = rep["alerts"][0]
    assert alert["run"] == "BENCH_r07.json"
    assert alert["direction"] == "drop" and alert["z"] < -3.5


def test_trends_quiet_on_noise(tmp_path):
    _write_history(tmp_path, CLEAN)
    reports = RunHistory(str(tmp_path)).trends(["evals_per_sec"])
    assert reports[0]["alerts"] == []


def test_trends_direction_for_lower_is_better(tmp_path):
    # compile_seconds regresses UPWARD; a drop must not alert
    root = tmp_path / "r"
    root.mkdir()
    vals = [10.0, 10.2, 9.9, 10.1, 10.0, 10.1, 9.8, 30.0, 29.5, 30.2]
    for i, v in enumerate(vals):
        p = root / f"BENCH_r{i:02d}.json"
        p.write_text(json.dumps({"value": 100.0, "unit": "evals/s",
                                 "compile_seconds": v}) + "\n")
        ts = time.time() - 3600 * (len(vals) - i)
        os.utime(p, (ts, ts))
    rep = RunHistory(str(root)).trends(["compile_seconds"])[0]
    assert len(rep["alerts"]) == 1
    assert rep["alerts"][0]["direction"] == "rise"


def test_write_index_is_tailable_jsonl(tmp_path):
    _write_history(tmp_path, CLEAN[:4])
    hist = RunHistory(str(tmp_path))
    path = hist.write_index()
    lines = [json.loads(ln) for ln in
             pathlib.Path(path).read_text().splitlines()]
    assert len(lines) == 4
    assert all(e["metrics"]["evals_per_sec"] > 0 for e in lines)
    # a rescan must not index the index file itself
    assert len(RunHistory(str(tmp_path)).scan()) == 4


# --------------------------------------------------- baselines & staleness


def test_best_healthy_and_auto_baseline(tmp_path):
    paths = _write_history(tmp_path, [95.0, 101.5, 99.0])
    # an unmeasured (0.0) newest run must never win
    bad = tmp_path / "BENCH_r99.json"
    bad.write_text(json.dumps({"value": 0.0, "unit": "evals/s",
                               "error": "probe failed"}) + "\n")
    hist = RunHistory(str(tmp_path))
    best = hist.best_healthy("evals_per_sec")
    assert best["path"] == paths[1]
    assert resolve_auto_baseline(str(tmp_path)) == paths[1]
    assert resolve_auto_baseline(str(tmp_path / "nothing_here")) is None


def test_stale_record_is_indexed_but_never_a_baseline(tmp_path):
    """Records written by the retired bench fallback carried another
    run's headline under ``stale_from_run``. Nothing writes them any
    more, but history still refuses to treat one found on disk as a
    measurement."""
    paths = _write_history(tmp_path, [95.0, 101.5])
    stale = tmp_path / "BENCH_r50.json"
    stale.write_text(json.dumps(
        {"value": 101.5, "unit": "evals/s", "error": "probe failed",
         "stale_from_run": {"run": "BENCH_r01.json"}}) + "\n")
    hist = RunHistory(str(tmp_path))
    hist.scan()
    by_run = {e["run"]: e for e in hist.entries}
    assert by_run["BENCH_r50.json"]["stale"]
    assert not by_run["BENCH_r50.json"]["healthy"]
    assert hist.best_healthy("evals_per_sec")["path"] == paths[1]
    assert resolve_auto_baseline(str(tmp_path)) == paths[1]


def test_compare_refuses_stale_candidate_allows_stale_baseline(tmp_path):
    from fks_tpu.obs.compare import extract_metrics

    p = tmp_path / "stale.json"
    p.write_text(json.dumps(
        {"value": 101.5, "unit": "evals/s",
         "stale_from_run": {"run": "BENCH_r01.json"}}) + "\n")
    assert "evals_per_sec" not in extract_metrics(str(p))
    assert extract_metrics(str(p), allow_stale=True)[
        "evals_per_sec"] == 101.5


def test_cli_compare_auto_baseline(tmp_path, capsys):
    _write_history(tmp_path, [95.0, 101.5, 99.0])
    cand = tmp_path / "candidate.json"
    cand.write_text(json.dumps({"value": 60.0, "unit": "evals/s"}) + "\n")
    rc = cli.main(["compare", "auto", str(cand),
                   "--history-root", str(tmp_path)])
    err = capsys.readouterr().err
    # auto resolved the non-0.0 best healthy run, and the 41% drop
    # against it is a regression
    assert "BENCH_r01.json" in err
    assert rc == 1
    # no history -> unresolvable, not silently green
    empty = tmp_path / "empty"
    empty.mkdir()
    assert cli.main(["compare", "auto", str(cand),
                     "--history-root", str(empty)]) == 2


def test_cli_trends_exit_codes(tmp_path, capsys):
    regressed = tmp_path / "reg"
    _write_history(regressed, REGRESSED)
    clean = tmp_path / "clean"
    _write_history(clean, CLEAN)
    assert cli.main(["trends", str(tmp_path / "missing")]) == 2
    empty = tmp_path / "empty"
    empty.mkdir()
    assert cli.main(["trends", str(empty)]) == 2
    capsys.readouterr()
    assert cli.main(["trends", str(clean), "--fail-on-alert"]) == 0
    assert "ALERT" not in capsys.readouterr().out
    rc = cli.main(["trends", str(regressed), "--fail-on-alert",
                   "--write-index"])
    out = capsys.readouterr().out
    assert rc == 1
    assert out.count("ALERT") == 1 and "BENCH_r07.json" in out
    assert (regressed / "history.jsonl").exists()
    # without --fail-on-alert the same alerts render but exit 0
    assert cli.main(["trends", str(regressed)]) == 0


# ---------------------------------------------------------------- SLO burn


def test_slo_burn_math():
    slo = SLOConfig(p99_ms=50.0, qps=100.0, error_budget=0.01)
    assert slo.enabled and not SLOConfig().enabled
    lat = [10.0] * 95 + [60.0] * 5
    recs = {r["slo"]: r for r in slo_burn(slo, lat, elapsed_s=2.0)}
    # 5% of requests over the 50ms target / 1% budget = 5x burn
    assert recs["p99_ms"]["burn_rate"] == pytest.approx(5.0)
    assert recs["p99_ms"]["target"] == 50.0
    assert recs["p99_ms"]["observed"] >= 50.0
    # 100 requests in 2s = 50 qps observed vs 100 target: 50% shortfall
    assert recs["qps"]["observed"] == pytest.approx(50.0)
    assert recs["qps"]["burn_rate"] == pytest.approx(50.0)
    # within budget -> burn below 1
    calm = slo_burn(SLOConfig(p99_ms=50.0), [10.0] * 200, 1.0)
    assert calm[0]["burn_rate"] == 0.0
    assert slo_burn(SLOConfig(), lat, 1.0) == []


def test_record_slo_burn_emits_metrics():
    class Rec:
        def __init__(self):
            self.rows = []

        def metric(self, kind, *dicts, **fields):
            row = {"kind": kind}
            for d in dicts:
                row.update(d)
            row.update(fields)
            self.rows.append(row)

    rec = Rec()
    out = record_slo_burn(SLOConfig(p99_ms=5.0), [1.0, 9.0], 1.0,
                          recorder=rec)
    assert len(out) == 1 and len(rec.rows) == 1
    row = rec.rows[0]
    assert row["kind"] == "slo_burn"
    for key in ("slo", "target", "observed", "burn_rate"):
        assert key in row


def test_serve_service_summary_prices_slo(micro_workload):
    from fks_tpu.serve.artifact import ChampionSpec, ServeEngine, \
        ShapeEnvelope
    from fks_tpu.serve.service import ServeService

    code = ('def priority_function(pod, node):\n'
            '    return 1000\n')
    eng = ServeEngine(ChampionSpec(code=code), micro_workload,
                      envelope=ShapeEnvelope(max_pods=8, max_batch=2,
                                             min_pod_bucket=8),
                      engine="exact")
    svc = ServeService(eng, slo=SLOConfig(p99_ms=0.001), max_wait_s=0.0)
    futs = [svc.submit({"pods": [{"cpu_milli": 100, "memory_mib": 100,
                                  "creation_time": 0, "duration_time": 5}]})
            for _ in range(3)]
    for f in futs:
        f.result(timeout=60.0)
    svc.close()
    out = svc.summary(record=False)
    assert out["requests"] == 3
    # a 1us p99 target is unmeetable: the budget must be burning
    slo_recs = {r["slo"]: r for r in out["slo"]}
    assert slo_recs["p99_ms"]["burn_rate"] > 1.0


# ------------------------------------------------- exporter / watch / schema


def _mini_run_dir(tmp_path, metrics):
    d = tmp_path / "run"
    d.mkdir()
    (d / "meta.json").write_text(json.dumps(
        {"run_id": "t1", "status": "ok", "started_ts": 1.0}))
    with open(d / "metrics.jsonl", "w") as f:
        for i, m in enumerate(metrics):
            f.write(json.dumps({"ts": 1.0 + i, **m}) + "\n")
    return str(d)


def test_openmetrics_profile_and_slo_gauges(tmp_path):
    from fks_tpu.obs.exporter import to_openmetrics

    d = _mini_run_dir(tmp_path, [
        {"kind": "device_profile", "scope": "evolve", "stage": "device-eval",
         "depth": 0, "wall_seconds": 2.0, "compile_seconds": 0.5,
         "compute_seconds": 1.5, "compile_count": 1,
         "utilization_pct": 71.2},
        {"kind": "device_profile", "stage": "__total__", "scope": "evolve",
         "wall_seconds": 2.0, "measured_wall_seconds": 2.1,
         "attributed_fraction": 0.952, "idle_fraction": 0.048,
         "compile_seconds": 0.5, "segments": 0},
        {"kind": "slo_burn", "slo": "p99_ms", "target": 50.0,
         "observed": 80.0, "over_fraction": 0.05, "burn_rate": 5.0,
         "requests": 100},
    ])
    text = to_openmetrics(d)
    assert ('fks_profile_attributed_fraction'
            '{run_id="t1",scope="evolve"} 0.952') in text
    assert 'stage="device-eval"' in text
    assert "fks_profile_stage_wall_seconds" in text
    assert 'fks_slo_burn_rate{run_id="t1",slo="p99_ms"} 5' in text
    assert "fks_slo_target" in text and "fks_slo_observed" in text


def test_watch_prints_slo_alert(tmp_path, capsys):
    from fks_tpu.obs.exporter import watch

    d = _mini_run_dir(tmp_path, [
        {"kind": "slo_burn", "slo": "p99_ms", "target": 50.0,
         "observed": 80.0, "burn_rate": 5.0},
        {"kind": "slo_burn", "slo": "qps", "target": 10.0,
         "observed": 12.0, "burn_rate": 0.0},
    ])
    watch(d, once=True)
    out = capsys.readouterr().out
    assert "SLO ALERT slo p99_ms: burn 5.00x" in out
    # an in-budget objective reports without the alert prefix
    assert "SLO ALERT slo qps" not in out


def test_schema_checker_knows_new_kinds(tmp_path):
    import shutil

    sys.path.insert(0, str(REPO / "tools"))
    try:
        import check_jsonl_schema as cjs
    finally:
        sys.path.pop(0)
    for kind in ("device_profile", "trend_report", "slo_burn"):
        assert kind in cjs.METRIC_KIND_REQUIRED
    # the refreshed golden fixture carries all three new kinds
    golden = [json.loads(ln) for ln in
              (pathlib.Path(GOLDEN) / "metrics.jsonl").read_text()
              .splitlines()]
    kinds = {m["kind"] for m in golden}
    assert {"device_profile", "trend_report", "slo_burn"} <= kinds
    assert cjs.main(["--run-dir", GOLDEN]) == 0
    # a field-less record of a known kind still fails the run-dir check
    bad = tmp_path / "run"
    shutil.copytree(GOLDEN, bad)
    with open(bad / "metrics.jsonl", "a") as f:
        f.write(json.dumps({"ts": 2e9, "kind": "slo_burn",
                            "slo": "p99_ms"}) + "\n")
    assert cjs.main(["--run-dir", str(bad)]) == 1


def test_report_renders_attribution_and_slo(capsys):
    assert cli.main(["report", GOLDEN]) == 0
    out = capsys.readouterr().out
    assert "device-time attribution" in out
    assert "device-eval" in out
    assert "attributed" in out
    assert "slo" in out.lower()
