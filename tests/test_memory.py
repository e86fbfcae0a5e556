"""Memory observability layer (fks_tpu.obs.memory).

The ISSUE-17 acceptance criteria, as tests:

- footprint ledger: ``footprint_of`` prices a compiled executable from
  ``memory_analysis()`` (None when the backend can't), ``record_footprint``
  lands one tagged ``memory_footprint`` record in both the process LEDGER
  and the recorder, ``rollup`` aggregates per (component, mesh_layout);
- watermark sampler: disabled is a true no-op ({} samples, no records —
  the Python-static contract the ``flat_step/mem_sampled`` jaxpr pin
  proves); enabled records host RSS + per-device rows;
- leak sentinel: drift math against live ``jax.Array`` allocations, the
  zero-tolerance default, and the fence-before-check contract;
- closed vocabularies pinned against tools/check_jsonl_schema.py's
  stdlib-only copies;
- gated memory budgets: ``cli compare`` flags an injected
  ``peak_device_bytes`` regression, rides out sub-page noise, and skips
  stale-fallback donor values on the candidate side;
- ``cli mem`` smoke over the golden fixture.

The deterministic drills themselves run here at reduced scale; ``cli mem
--drill`` runs the full 50-swap/200-batch criterion.
"""
import json
import os
import pathlib
import shutil
import sys

import jax.numpy as jnp
import pytest

from fks_tpu import cli
from fks_tpu.obs import memory as mem
from fks_tpu.obs.compare import compare_runs, extract_metrics, has_regression
from fks_tpu.obs.memory import (
    FOOTPRINT_KEYS, LEAK_LOOPS, LEDGER, MEMORY_COMPONENTS, LeakSentinel,
    NULL_SAMPLER, WatermarkSampler, footprint_of, leak_fence,
    live_array_stats, mesh_layout_label, record_footprint, rollup, run_drill,
)
from fks_tpu.obs.telemetry import normalize_memory_stats

GOLDEN = str(pathlib.Path(__file__).parent / "fixtures" / "golden_run")


class RecStub:
    enabled = True

    def __init__(self):
        self.metrics = []

    def metric(self, kind, *a, **fields):
        rec = dict(a[0]) if a and isinstance(a[0], dict) else {}
        rec.update(fields)
        self.metrics.append({"kind": kind, **rec})


class FakeAnalysis:
    temp_size_in_bytes = 1000
    argument_size_in_bytes = 200
    output_size_in_bytes = 50
    generated_code_size_in_bytes = 4096
    alias_size_in_bytes = 0


class FakeCompiled:
    def memory_analysis(self):
        return FakeAnalysis()


# --------------------------------------------------------------- ledger

def test_footprint_of_fake_compiled():
    fp = footprint_of(FakeCompiled())
    assert fp == {"temp_bytes": 1000, "argument_bytes": 200,
                  "output_bytes": 50, "generated_code_bytes": 4096,
                  "alias_bytes": 0, "total_bytes": 5346}


def test_footprint_of_unpriceable_returns_none():
    assert footprint_of(object()) is None

    class Raises:
        def memory_analysis(self):
            raise RuntimeError("no backend")

    class Empty:
        def memory_analysis(self):
            return object()  # none of the byte attrs

    assert footprint_of(Raises()) is None
    assert footprint_of(Empty()) is None


def test_record_footprint_lands_in_ledger_and_recorder():
    rec = RecStub()
    LEDGER.clear()
    out = record_footprint("serve_vm", "lanes=2,cap=64", FakeCompiled(),
                           recorder=rec, engine="flat")
    assert out is not None and out["component"] == "serve_vm"
    assert out["exe_key"] == "lanes=2,cap=64"
    assert out["engine"] == "flat"
    assert [r["exe_key"] for r in LEDGER.records()] == ["lanes=2,cap=64"]
    assert rec.metrics[0]["kind"] == "memory_footprint"
    assert rec.metrics[0]["total_bytes"] == 5346


def test_record_footprint_rejects_unknown_component():
    with pytest.raises(ValueError):
        record_footprint("gpu_tier", "x", FakeCompiled(), recorder=RecStub())


def test_record_footprint_unpriceable_records_nothing():
    rec = RecStub()
    LEDGER.clear()
    assert record_footprint("bench", "k", object(), recorder=rec) is None
    assert not LEDGER.records() and not rec.metrics


def test_rollup_aggregates_per_component_and_layout():
    rows = [
        {"component": "serve_aot", "mesh_layout": "", "temp_bytes": 100,
         "argument_bytes": 10, "output_bytes": 1,
         "generated_code_bytes": 5, "total_bytes": 116},
        {"component": "serve_aot", "mesh_layout": "", "temp_bytes": 300,
         "argument_bytes": 10, "output_bytes": 1,
         "generated_code_bytes": 5, "total_bytes": 316},
        {"component": "evolve", "mesh_layout": "pop=4", "temp_bytes": 9000,
         "argument_bytes": 0, "output_bytes": 0,
         "generated_code_bytes": 0},  # total derived from the byte keys
    ]
    agg = rollup(rows)
    assert [a["component"] for a in agg] == ["evolve", "serve_aot"]
    aot = agg[1]
    assert aot["executables"] == 2
    assert aot["predicted_hbm_bytes"] == 432
    assert aot["peak_temp_bytes"] == 300
    assert agg[0]["predicted_hbm_bytes"] == 9000


def test_rollup_defaults_to_process_ledger():
    LEDGER.clear()
    record_footprint("bench", "probe", FakeCompiled(), recorder=RecStub())
    agg = rollup()
    assert len(agg) == 1 and agg[0]["component"] == "bench"
    LEDGER.clear()


def test_mesh_layout_label_none_is_empty():
    assert mesh_layout_label(None) == ""


# ----------------------------------------------------- stats + sampler

def test_normalize_memory_stats_aliases_and_partials():
    assert normalize_memory_stats(None) is None
    assert normalize_memory_stats({}) is None
    assert normalize_memory_stats({"weird": 1}) is None
    out = normalize_memory_stats({"bytes_in_use": 10,
                                  "peak_bytes_in_use": 20,
                                  "bytes_limit": 30})
    assert out == {"bytes_in_use": 10, "peak_bytes_in_use": 20,
                   "bytes_limit": 30}
    # partial dicts keep what they can answer
    assert normalize_memory_stats({"bytes_in_use": 7}) == {"bytes_in_use": 7}


def test_disabled_sampler_is_a_true_noop():
    rec = RecStub()
    s = WatermarkSampler(enabled=False, recorder=rec)
    with s:
        assert s.sample(stage="x") == {}
    assert not s.samples and not rec.metrics
    assert NULL_SAMPLER.sample() == {}


def test_enabled_sampler_records_watermarks():
    rec = RecStub()
    with WatermarkSampler(enabled=True, recorder=rec) as s:
        out = s.sample(stage="unit")
    assert out["stage"] == "unit"
    assert out["host_rss_kb"] > 0
    assert isinstance(out["devices"], list) and out["devices"]
    row = out["devices"][0]
    assert "id" in row and "platform" in row  # identity even on CPU
    assert rec.metrics and rec.metrics[0]["kind"] == "memory_watermark"


def test_sampler_interval_thread_lifecycle():
    rec = RecStub()
    s = WatermarkSampler(enabled=True, interval_s=0.01, recorder=rec)
    s.start()
    import time
    deadline = time.time() + 5.0
    while not s.samples and time.time() < deadline:
        time.sleep(0.01)
    s.stop()
    assert s.samples and s.samples[0]["stage"] == "interval"
    assert s._thread is None


# --------------------------------------------------------- leak sentinel

def test_leak_sentinel_flags_real_growth_and_clears_on_free():
    rec = RecStub()
    held = []
    s = LeakSentinel("serve_batch", recorder=rec)
    s.fence()
    held.append(jnp.zeros(1024, dtype=jnp.float32) + 1.0)
    verdict = s.check(iterations=1)
    assert not verdict["ok"]
    assert verdict["drift_count"] >= 1
    assert verdict["drift_bytes"] >= 4096
    held.clear()
    s2 = LeakSentinel("serve_batch", recorder=rec)
    s2.fence()
    tmp = jnp.ones(1024, dtype=jnp.float32) * 2.0
    del tmp
    assert s2.check(iterations=1)["ok"]
    kinds = {m["kind"] for m in rec.metrics}
    assert kinds == {"leak_check"}


def test_leak_fence_context_manager_sets_result():
    with leak_fence("promotion", iterations=3, recorder=RecStub()) as s:
        pass
    assert s.result is not None and s.result["iterations"] == 3


def test_leak_sentinel_contracts():
    with pytest.raises(ValueError):
        LeakSentinel("not_a_loop", recorder=RecStub())
    s = LeakSentinel("vm_swap", recorder=RecStub())
    with pytest.raises(RuntimeError):
        s.check(1)
    stats = live_array_stats()
    assert stats["count"] >= 0 and stats["bytes"] >= 0


# ------------------------------------------------- vocabulary pinning

def _schema_tool():
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools"))
    try:
        import check_jsonl_schema as cjs
    finally:
        sys.path.pop(0)
    return cjs


def test_vocabularies_pinned_against_schema_tool():
    cjs = _schema_tool()
    assert set(MEMORY_COMPONENTS) == cjs.MEMORY_COMPONENTS
    assert set(LEAK_LOOPS) == cjs.LEAK_LOOPS
    assert set(FOOTPRINT_KEYS) < set(
        cjs.METRIC_KIND_REQUIRED["memory_footprint"])


# -------------------------------------------------------------- drills

def test_unknown_drill_raises():
    with pytest.raises(KeyError):
        run_drill("coffee_leak")


def test_drill_vm_swap_leak_reduced_scale():
    rec = RecStub()
    out = run_drill("vm_swap_leak", swaps=3, batches=6, recorder=rec)
    assert out["ok"], out
    assert out["drift_count"] == 0 and out["drift_bytes"] == 0
    assert out["batches"] == 6 and "seconds" in out
    assert any(m["kind"] == "leak_check" for m in rec.metrics)


def test_drill_snapshot_cache_bound():
    out = run_drill("snapshot_cache_bound", recorder=RecStub())
    assert out["ok"], out
    assert out["over_cap_observations"] == 0
    assert out["evicted"] and out["recent_rehit"]


# ------------------------------------------------- gated memory budgets

def _with_memory_budget(tmp_path, name, peak):
    """Copy the golden run dir, stamping ``peak_device_bytes`` onto its
    bench_stage rows (the gate reads the high-water mark across rows)."""
    dst = str(tmp_path / name)
    shutil.copytree(GOLDEN, dst)
    p = os.path.join(dst, "metrics.jsonl")
    with open(p) as f:
        rows = [json.loads(l) for l in f if l.strip()]
    for r in rows:
        if r["kind"] == "bench_stage":
            r["peak_device_bytes"] = peak
    with open(p, "w") as f:
        f.writelines(json.dumps(r) + "\n" for r in rows)
    return dst


def test_injected_memory_regression_gates(tmp_path):
    base = _with_memory_budget(tmp_path, "base", 1_000_000)
    cand = _with_memory_budget(tmp_path, "cand", 1_000_000 + 65536)
    rows = compare_runs(base, cand)
    assert has_regression(rows)
    by = {r["metric"]: r["status"] for r in rows}
    assert by["peak_device_bytes"] == "REGRESSION"


def test_memory_noise_below_one_page_rides_out(tmp_path):
    base = _with_memory_budget(tmp_path, "base", 1_000_000)
    cand = _with_memory_budget(tmp_path, "cand", 1_000_000 + 4096)
    by = {r["metric"]: r["status"] for r in compare_runs(base, cand)}
    assert by["peak_device_bytes"] == "OK"


def test_memory_improvement_is_not_a_regression(tmp_path):
    base = _with_memory_budget(tmp_path, "base", 1_000_000)
    cand = _with_memory_budget(tmp_path, "cand", 500_000)
    rows = compare_runs(base, cand)
    assert not has_regression(rows)
    by = {r["metric"]: r["status"] for r in rows}
    assert by["peak_device_bytes"] == "IMPROVED"


def test_stale_fallback_memory_counts_for_baseline_only(tmp_path):
    p = tmp_path / "stale.jsonl"
    p.write_text(json.dumps({
        "benchmark": "fks_tpu", "value": 0.0, "unit": "evals/s",
        "stale_from_run": "round19.jsonl", "peak_device_bytes": 123456,
        "exe_temp_bytes": 789}) + "\n")
    assert "peak_device_bytes" not in extract_metrics(str(p))
    donor = extract_metrics(str(p), allow_stale=True)
    assert donor["peak_device_bytes"] == 123456.0
    assert donor["exe_temp_bytes"] == 789.0


# ------------------------------------------------------------ cli mem

def test_cli_mem_view_golden(capsys):
    assert cli.main(["mem", "--run-dir", GOLDEN]) == 0
    out = capsys.readouterr().out
    assert "memory" in out
    assert "lanes=2,pods=8" in out
    assert "leak sentinel" in out


def test_cli_mem_requires_a_mode(capsys):
    assert cli.main(["mem"]) == 2


def test_cli_mem_sample(capsys):
    assert cli.main(["mem", "--sample"]) == 0
    rec = json.loads(capsys.readouterr().out)
    assert rec["host_rss_kb"] > 0 and rec["devices"]
