"""Cross-run regression gating + OpenMetrics export.

Pins the issue's acceptance bar directly: ``cli compare`` exits nonzero
when a candidate run carries an injected regression (throughput -20% or
parity drift above 1e-5) and zero on identical runs; the OpenMetrics
exposition round-trips through the schema checker's validator; heartbeat
liveness classifies FINISHED/HEALTHY/STALE/DEAD from the run's own
cadence. The golden run-dir fixture is tests/fixtures/golden_run.
"""
import json
import os
import pathlib
import shutil
import sys
import time

import pytest

from fks_tpu import cli, obs
from fks_tpu.obs.compare import (
    DEFAULT_THRESHOLDS, Threshold, compare_runs, extract_metrics,
    format_comparison, has_regression, parse_threshold_overrides,
)
from fks_tpu.obs.exporter import run_health, to_openmetrics, watch

GOLDEN = str(pathlib.Path(__file__).parent / "fixtures" / "golden_run")


def _schema_tool():
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools"))
    try:
        import check_jsonl_schema as cjs
    finally:
        sys.path.pop(0)
    return cjs


def _regressed_copy(tmp_path, *, perf_factor=1.0, drift=None):
    """Copy the golden run dir, scaling bench throughput and/or injecting
    parity drift into the candidate's metrics stream."""
    dst = str(tmp_path / "candidate")
    shutil.copytree(GOLDEN, dst)
    rows = []
    with open(os.path.join(dst, "metrics.jsonl")) as f:
        rows = [json.loads(l) for l in f if l.strip()]
    for r in rows:
        if r["kind"] == "bench_stage" and "evals_per_sec" in r:
            r["evals_per_sec"] *= perf_factor
        if drift is not None and r["kind"] == "parity":
            r["max_drift"] = drift
    with open(os.path.join(dst, "metrics.jsonl"), "w") as f:
        f.writelines(json.dumps(r) + "\n" for r in rows)
    return dst


# ------------------------------------------------------------- comparator

def test_identical_runs_no_regression():
    rows = compare_runs(GOLDEN, GOLDEN)
    assert rows and not has_regression(rows)
    assert all(r["status"] == "OK" for r in rows)


def test_injected_perf_regression_gates(tmp_path):
    cand = _regressed_copy(tmp_path, perf_factor=0.8)  # the issue's -20%
    rows = compare_runs(GOLDEN, cand)
    assert has_regression(rows)
    by = {r["metric"]: r["status"] for r in rows}
    assert by["evals_per_sec"] == "REGRESSION"
    assert "REGRESSION: " in format_comparison(rows, GOLDEN, cand)


def test_injected_parity_drift_gates(tmp_path):
    cand = _regressed_copy(tmp_path, drift=0.01)  # > 1e-5 tolerance
    by = {r["metric"]: r["status"] for r in compare_runs(GOLDEN, cand)}
    assert by["parity_max_drift"] == "REGRESSION"


def test_small_perf_noise_rides_out(tmp_path):
    cand = _regressed_copy(tmp_path, perf_factor=0.95)  # within 10% rel
    by = {r["metric"]: r["status"] for r in compare_runs(GOLDEN, cand)}
    assert by["evals_per_sec"] == "OK"


def test_improvement_is_not_a_regression(tmp_path):
    cand = _regressed_copy(tmp_path, perf_factor=1.5)
    rows = compare_runs(GOLDEN, cand)
    assert not has_regression(rows)
    by = {r["metric"]: r["status"] for r in rows}
    assert by["evals_per_sec"] == "IMPROVED"


def test_metric_in_one_run_never_gates(tmp_path):
    base = tmp_path / "base.jsonl"
    cand = tmp_path / "cand.jsonl"
    base.write_text(json.dumps({"value": 100.0, "unit": "evals/s",
                                "best_score": 0.5}) + "\n")
    cand.write_text(json.dumps({"value": 100.0, "unit": "evals/s"}) + "\n")
    rows = compare_runs(str(base), str(cand))
    assert not has_regression(rows)
    by = {r["metric"]: r["status"] for r in rows}
    assert by["best_score"] == "BASELINE-ONLY"


def test_bench_fallback_value_contributes_nothing(tmp_path):
    """The 0.0-with-banked_from headline means 'nothing measured' and must
    not enter the throughput vocabulary (a later honest 0.0 baseline would
    otherwise make every candidate an infinite improvement)."""
    p = tmp_path / "fallback.jsonl"
    p.write_text(json.dumps({
        "benchmark": "fks_tpu", "value": 0.0, "unit": "evals/s",
        "error": "tpu timeout", "banked_from": "round6_tpu.jsonl"}) + "\n")
    assert "evals_per_sec" not in extract_metrics(str(p))


def test_bench_headline_and_session_log_extraction(tmp_path):
    p = tmp_path / "bench.jsonl"
    p.write_text(
        "prose line survives\n"
        + json.dumps({"ok": True, "stage": "throughput",
                      "result": {"evals_per_sec": 1200.0,
                                 "compile_seconds": 4.0}}) + "\n"
        + json.dumps({"value": 1500.0, "unit": "evals/s",
                      "compile_seconds": 3.5}) + "\n")
    m = extract_metrics(str(p))
    assert m["evals_per_sec"] == 1500.0  # best across rows
    assert m["compile_seconds"] == 3.5   # min: best measured compile


def test_threshold_overrides():
    th = parse_threshold_overrides("evals_per_sec=rel:0.5,best_score=abs:0.2")
    assert th["evals_per_sec"] == Threshold(higher_is_better=True, rel=0.5)
    assert th["best_score"].abs_tol == 0.2 and th["best_score"].rel is None
    # untouched metrics keep the defaults
    assert th["parity_max_drift"] == DEFAULT_THRESHOLDS["parity_max_drift"]
    with pytest.raises(ValueError, match="bad threshold"):
        parse_threshold_overrides("evals_per_sec=0.5")


def test_watchdog_and_alert_counts_gate(tmp_path):
    cand = str(tmp_path / "candidate")
    shutil.copytree(GOLDEN, cand)
    with open(os.path.join(cand, "events.jsonl"), "a") as f:
        f.write(json.dumps({"ts": 1785585691.0, "kind": "watchdog",
                            "seq": 6, "flags": 2, "kinds": ["inf"]}) + "\n")
    by = {r["metric"]: r["status"] for r in compare_runs(GOLDEN, cand)}
    assert by["watchdog_violations"] == "REGRESSION"  # any increase gates


# ------------------------------------------------------ openmetrics export

def test_openmetrics_round_trips_schema_checker():
    text = to_openmetrics(GOLDEN)
    assert text.endswith("# EOF\n")
    n = _schema_tool().check_openmetrics(text, "<golden>")
    assert n > 0


def test_openmetrics_families_and_labels():
    text = to_openmetrics(GOLDEN)
    assert '# TYPE fks_generation_best_score gauge' in text
    assert 'fks_run_info{run_id="20260801-120000-abc123"' in text
    assert 'fks_events_total{run_id="20260801-120000-abc123",kind="watchdog"} 1' in text
    assert "fks_parity_max_drift" in text
    assert "fks_bench_evals_per_sec" in text
    # finished golden run: healthy regardless of heartbeat age
    assert "fks_run_healthy" in text


def test_openmetrics_checker_rejects_malformed():
    cjs = _schema_tool()
    with pytest.raises(cjs.SchemaError, match="EOF"):
        cjs.check_openmetrics("fks_x 1\n", "<t>")
    with pytest.raises(cjs.SchemaError):
        # sample for an undeclared family
        cjs.check_openmetrics("fks_x{a=\"b\"} 1\n# EOF\n", "<t>")


def test_schema_checker_validates_watchdog_event_kinds(tmp_path):
    cjs = _schema_tool()
    assert cjs.main(["--run-dir", GOLDEN]) == 0
    bad = tmp_path / "run"
    shutil.copytree(GOLDEN, bad)
    with open(bad / "events.jsonl", "a") as f:
        # watchdog event missing its required flags/kinds payload
        f.write(json.dumps({"ts": 1.0, "kind": "watchdog", "seq": 9}) + "\n")
    assert cjs.main(["--run-dir", str(bad)]) == 1


#: kinds of PR 48's retired memory ledger; the golden run holds one row each
RETIRED_KINDS = {"memory_footprint", "memory_watermark", "leak_check"}


@pytest.mark.parametrize("reader",
                         ["report", "export-metrics", "watch", "schema"])
def test_rows_of_a_retired_kind_are_ignored(reader, capsys):
    """A run directory recorded before the memory ledger went is still a
    run directory: every reader accepts it and says nothing of the rows
    it no longer knows."""
    with open(os.path.join(GOLDEN, "metrics.jsonl")) as f:
        assert RETIRED_KINDS <= {json.loads(l)["kind"] for l in f}
    if reader == "schema":
        cjs = _schema_tool()
        assert cjs.main(["--run-dir", GOLDEN]) == 0
        assert not RETIRED_KINDS & set(cjs.METRIC_KIND_REQUIRED)
    else:
        argv = [reader, GOLDEN] + (["--once"] if reader == "watch" else [])
        assert cli.main(argv) == 0
    out = capsys.readouterr().out
    for word in ("fks_mem_", "memory (", "leak", "footprint", "watermark"):
        assert word not in out, (reader, word)


# -------------------------------------------------------------- liveness

def _live_run(tmp_path, heartbeat_age, gap=10.0):
    """Unfinished run whose metrics tick every ``gap`` seconds and whose
    last heartbeat is ``heartbeat_age`` seconds old."""
    d = tmp_path / f"live-{heartbeat_age}"
    d.mkdir()
    now = time.time()
    (d / "meta.json").write_text(json.dumps(
        {"run_id": "live", "status": "running", "command": "evolve"}))
    rows = [{"ts": now - 100 + i * gap, "kind": "generation",
             "generation": i, "best_score": 0.1} for i in range(5)]
    (d / "metrics.jsonl").write_text(
        "".join(json.dumps(r) + "\n" for r in rows))
    (d / "heartbeat").write_text(json.dumps(
        {"ts": now - heartbeat_age, "run_id": "live"}))
    # a genuinely stale heartbeat is old on BOTH signals: the embedded
    # ts and the file mtime (run_health takes the fresher of the two so
    # writer/reader clock skew cannot flap a live run to STALE)
    os.utime(d / "heartbeat", (now - heartbeat_age, now - heartbeat_age))
    return str(d)


def test_run_health_states(tmp_path):
    assert run_health(GOLDEN)["state"] == "FINISHED"
    assert run_health(_live_run(tmp_path, 5.0))["state"] == "HEALTHY"
    # cadence is ~10s: STALE beyond 2x, DEAD beyond 10x
    assert run_health(_live_run(tmp_path, 45.0))["state"] == "STALE"
    assert run_health(_live_run(tmp_path, 900.0))["state"] == "DEAD"
    # unfinished run with no heartbeat file at all: DEAD
    no_beat = _live_run(tmp_path, 1.0, gap=10.0)
    os.remove(os.path.join(no_beat, "heartbeat"))
    assert run_health(no_beat)["state"] == "DEAD"


def test_run_health_monotonic_skew_guard(tmp_path):
    """A heartbeat whose embedded ts looks old but whose file was just
    modified (writer/reader clock skew, shared-filesystem lag) must NOT
    flap to STALE/DEAD — the fresher of the two signals wins."""
    d = _live_run(tmp_path, 60.0)
    os.utime(os.path.join(d, "heartbeat"), None)  # mtime = now
    assert run_health(d)["state"] == "HEALTHY"


def test_report_flags_stale_run(tmp_path):
    from fks_tpu.obs.report import render_report

    stale = _live_run(tmp_path, 60.0)
    head = render_report(stale).splitlines()[0]
    assert "STALE" in head
    assert "STALE" not in render_report(GOLDEN).splitlines()[0]


def test_watch_once_finished_run(capsys):
    rc = watch(GOLDEN, once=True)
    out = capsys.readouterr().out
    assert rc == 0
    assert "[FINISHED]" in out
    assert "gen 3" in out and "parity gen 3" in out


def test_watch_dead_run_exits_nonzero(tmp_path, capsys):
    rc = watch(_live_run(tmp_path, 900.0), once=True)
    assert rc == 1
    assert "[DEAD]" in capsys.readouterr().out


# ------------------------------------------------------------ cli surface

def test_cli_compare_exit_codes(tmp_path, capsys):
    assert cli.main(["compare", GOLDEN, GOLDEN]) == 0
    assert "no regressions" in capsys.readouterr().out
    cand = _regressed_copy(tmp_path, perf_factor=0.8)
    assert cli.main(["compare", GOLDEN, cand]) == 1
    assert "REGRESSION" in capsys.readouterr().out
    assert cli.main(["compare", GOLDEN, str(tmp_path / "nope")]) == 2


def test_cli_compare_threshold_override(tmp_path, capsys):
    cand = _regressed_copy(tmp_path, perf_factor=0.8)
    rc = cli.main(["compare", GOLDEN, cand,
                   "--threshold", "evals_per_sec=rel:0.5,"
                   "parity_max_drift=abs:0.1"])
    capsys.readouterr()
    assert rc == 0  # widened gate rides out the -20%


def test_cli_export_metrics(tmp_path, capsys):
    out = tmp_path / "metrics.prom"
    assert cli.main(["export-metrics", GOLDEN, "--out", str(out)]) == 0
    capsys.readouterr()
    text = out.read_text()
    assert text.endswith("# EOF\n")
    assert _schema_tool().check_openmetrics(text, str(out)) > 0
    # stdout mode
    assert cli.main(["export-metrics", GOLDEN]) == 0
    assert "# EOF" in capsys.readouterr().out


def test_cli_watch_once(capsys):
    assert cli.main(["watch", GOLDEN, "--once"]) == 0
    assert "[FINISHED]" in capsys.readouterr().out
