"""Serve accounting (fks_tpu.serve.accounting) and the load generator
(fks_tpu.obs.workload).

The ISSUE-18 acceptance criteria, as tests:

- query fingerprints: ``classify`` is order-independent (pod permutation
  and dict key order change nothing), deterministic ACROSS PROCESSES
  (a fresh interpreter computes the same class), splits on pod-count
  bucket and resource decade while clustering within a decade, and the
  windowed mix resets on ``record_mix``;
- fairness/burn math, hand-computed: Jain of [10, 10] is 1.0, of
  [10, 0] is 0.5; 10 of 100 requests over a 50 ms target with a 1%
  error budget burns at exactly 10x;
- tenant accounting: shed/expired/degraded counters, per-row global
  fairness, and ``record`` rows carrying every key the stdlib schema
  checker requires of ``tenant_stats``;
- ``parse_tenant_spec`` round trips and rejects malformed specs;
- ``run_loadgen`` drives a fake client and summarizes into the four
  compare-gated keys, recording one ``loadgen_summary`` metric;
- closed vocabularies pinned against tools/check_jsonl_schema.py's
  stdlib-only copies, and the golden fixture carries schema-complete
  exemplar rows for all three new metric kinds.

The end-to-end two-tenant run through the real HTTP front is ``cli
loadgen --http``; here the drivers run against fakes.
"""
import json
import os
import pathlib
import subprocess
import sys
import threading
import time

import pytest

from fks_tpu.obs.workload import (
    LOADGEN_MODES, TenantLoad, default_make_pods, parse_tenant_spec,
    run_loadgen,
)
from fks_tpu.serve.accounting import (
    DEFAULT_TENANT, QueryFingerprinter, SLOConfig, TenantAccountant,
    jain_fairness, tenant_of,
)

REPO = pathlib.Path(__file__).parent.parent
GOLDEN = str(REPO / "tests" / "fixtures" / "golden_run")

PODS = [
    {"cpu_milli": 120, "memory_mib": 512, "creation_time": 0,
     "duration_time": 40},
    {"cpu_milli": 55, "memory_mib": 1024, "creation_time": 1,
     "duration_time": 40},
    {"cpu_milli": 700, "memory_mib": 256, "creation_time": 2,
     "duration_time": 80},
]


class RecStub:
    enabled = True

    def __init__(self):
        self.metrics = []

    def metric(self, kind, *a, **fields):
        rec = dict(a[0]) if a and isinstance(a[0], dict) else {}
        rec.update(fields)
        self.metrics.append({"kind": kind, **rec})


def _schema_tool():
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools"))
    try:
        import check_jsonl_schema as cjs
    finally:
        sys.path.pop(0)
    return cjs


# ---------------------------------------------------------- fingerprints

def test_tenant_of():
    assert tenant_of({"tenant": "acme"}) == "acme"
    assert tenant_of({"tenant": 7}) == "7"
    assert tenant_of({}) == DEFAULT_TENANT
    assert tenant_of({"tenant": ""}) == DEFAULT_TENANT
    assert tenant_of(None) == DEFAULT_TENANT


def test_fingerprint_order_independent():
    fp = QueryFingerprinter()
    base = fp.classify(PODS)
    # pod permutation
    assert fp.classify(list(reversed(PODS))) == base
    # dict key order (JSON round trip preserves values, reorders keys)
    reordered = [dict(sorted(p.items(), reverse=True)) for p in PODS]
    assert fp.classify(reordered) == base
    assert base.startswith("p4:")  # 3 pods -> pow2 bucket 4


def test_fingerprint_splits_and_clusters():
    fp = QueryFingerprinter()
    base = fp.classify(PODS)
    # same decade clusters: 120 -> 160 is still +e3
    tweak = [dict(PODS[0], cpu_milli=160)] + PODS[1:]
    assert fp.classify(tweak) == base
    # decade jump splits: 120 -> 12000
    jump = [dict(PODS[0], cpu_milli=12000)] + PODS[1:]
    assert fp.classify(jump) != base
    # pod-count bucket splits: 3 pods (bucket 4) vs 5 pods (bucket 8)
    five = PODS + [dict(PODS[0]), dict(PODS[1])]
    assert fp.classify(five).startswith("p8:")
    assert fp.classify(five) != base


def test_fingerprint_cross_process():
    fp = QueryFingerprinter()
    local = fp.classify(PODS)
    code = (
        "import json,sys\n"
        "from fks_tpu.serve.accounting import QueryFingerprinter\n"
        "pods=json.loads(sys.argv[1])\n"
        "print(QueryFingerprinter().classify(pods))\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-c", code, json.dumps(PODS)],
        capture_output=True, text=True, cwd=str(REPO), env=env,
        timeout=300)
    assert proc.returncode == 0, proc.stderr[-500:]
    assert proc.stdout.strip() == local


def test_fingerprint_window_and_record_mix():
    fp = QueryFingerprinter()
    for _ in range(3):
        fp.observe(PODS)
    fp.observe(PODS[:1])
    mix = fp.mix()
    assert sum(mix.values()) == 4 and len(mix) == 2
    rec = RecStub()
    out = fp.record_mix(rec)
    assert out["window"] == 4 and out["distinct"] == 2
    assert sum(out["classes"].values()) == 4
    assert rec.metrics[0]["kind"] == "workload_mix"
    # reset=True started a fresh window; an empty window records nothing
    assert fp.mix() == {}
    assert fp.record_mix(rec) == {}
    assert len(rec.metrics) == 1


# --------------------------------------------------- fairness/burn math

def test_jain_fairness_hand_computed():
    assert jain_fairness([10, 10]) == pytest.approx(1.0)
    assert jain_fairness([10, 0]) == pytest.approx(0.5)
    assert jain_fairness([1, 1, 1, 1]) == pytest.approx(1.0)
    # one of n tenants has everything -> 1/n
    assert jain_fairness([5, 0, 0, 0]) == pytest.approx(0.25)
    # idle reads as fair
    assert jain_fairness([]) == 1.0
    assert jain_fairness([0, 0]) == 1.0


def test_slo_burn_hand_computed():
    acct = TenantAccountant(slo=SLOConfig(p99_ms=50.0, error_budget=0.01))
    for _ in range(90):
        acct.note_request("a", 10.0)
    for _ in range(10):
        acct.note_request("a", 60.0)
    row = acct.stats()["a"]
    # 10% of requests over target / 1% budget = burning at exactly 10x
    assert row["burn_rate"] == pytest.approx(10.0)
    assert row["requests"] == 100


def test_accountant_counters_and_record():
    acct = TenantAccountant()
    acct.note_request("a", 10.0)
    acct.note_request("a", 20.0, degraded=True)
    acct.note_request("b", 10.0)
    acct.note_shed("b")
    acct.note_expired("b")
    acct.note_shed("c")  # shed-only tenant still gets a row
    rec = RecStub()
    stats = acct.record(rec)
    assert stats["a"]["requests"] == 2 and stats["a"]["degraded"] == 1
    assert stats["b"]["shed"] == 1 and stats["b"]["expired"] == 1
    assert stats["c"]["requests"] == 0 and stats["c"]["shed"] == 1
    # EWMA: first sample seeds, second blends at alpha=0.2
    assert stats["a"]["ewma_ms"] == pytest.approx(0.2 * 20 + 0.8 * 10)
    # every row carries the same GLOBAL fairness index
    fair = {row["fairness_index"] for row in stats.values()}
    assert fair == {round(jain_fairness([2, 1, 0]), 4)}
    cjs = _schema_tool()
    required = set(cjs.METRIC_KIND_REQUIRED["tenant_stats"])
    for row in rec.metrics:
        assert row["kind"] == "tenant_stats"
        assert required <= set(row)


# ------------------------------------------------------------- tenant spec

def test_parse_tenant_spec():
    plan = parse_tenant_spec("a:closed:2, b:open:25, c:closed:1:5")
    assert [ld.tenant for ld in plan] == ["a", "b", "c"]
    assert plan[0].mode == "closed" and plan[0].concurrency == 2
    assert plan[1].mode == "open" and plan[1].rate_qps == 25.0
    assert plan[2].pods_per_query == 5


@pytest.mark.parametrize("bad", [
    "", "a:closed", "a:open:0", "a:closed:0", "a:zigzag:3",
])
def test_parse_tenant_spec_rejects(bad):
    with pytest.raises(ValueError):
        parse_tenant_spec(bad)


def test_default_make_pods_deterministic():
    load = TenantLoad("a", "closed", concurrency=1, pods_per_query=3)
    assert default_make_pods(load, 7) == default_make_pods(load, 7)
    assert len(default_make_pods(load, 0)) == 3


# ---------------------------------------------------------------- loadgen

def test_run_loadgen_fake_send():
    calls = []
    lock = threading.Lock()

    def send(query):
        with lock:
            calls.append(query)
            n = len(calls)
        time.sleep(0.001)
        return {"outcome": "shed"} if n % 5 == 0 else {"outcome": "ok"}

    plan = parse_tenant_spec("a:closed:2,b:closed:2")
    rec = RecStub()
    out = run_loadgen(send, plan, duration_s=0.25, recorder=rec)
    assert out["mode"] == "closed" and out["tenant_count"] == 2
    assert out["requests"] == out["completed"] + out["shed"] + out["errors"]
    assert out["requests"] > 0 and out["errors"] == 0
    assert out["loadgen_qps"] > 0
    assert 0.0 < out["loadgen_shed_rate"] < 1.0
    assert 0.0 < out["loadgen_fairness_index"] <= 1.0
    assert set(out["tenants"]) == {"a", "b"}
    # queries carried tenant identity and deterministic pods
    assert all(tenant_of(q) in ("a", "b") for q in calls)
    assert all(len(q["pods"]) == 2 for q in calls)
    summary = [m for m in rec.metrics if m["kind"] == "loadgen_summary"]
    assert len(summary) == 1 and summary[0]["mode"] == "closed"


def test_run_loadgen_mixed_mode():
    def send(query):
        time.sleep(0.001)
        return {"outcome": "ok"}

    plan = parse_tenant_spec("a:closed:1,b:open:80")
    out = run_loadgen(send, plan, duration_s=0.25, seed=3)
    assert out["mode"] == "mixed"
    assert out["tenants"]["b"]["sent"] > 0  # Poisson arrivals fired


# ------------------------------------------------- vocabulary pinning

def test_loadgen_modes_pinned_against_schema_tool():
    cjs = _schema_tool()
    assert set(LOADGEN_MODES) == cjs.LOADGEN_MODES


def test_golden_fixture_has_workload_rows():
    cjs = _schema_tool()
    rows = [json.loads(line) for line in
            open(os.path.join(GOLDEN, "metrics.jsonl"))]
    by_kind = {}
    for r in rows:
        by_kind.setdefault(r.get("kind"), []).append(r)
    assert len(by_kind["tenant_stats"]) >= 2
    assert by_kind["workload_mix"] and by_kind["loadgen_summary"]
    for kind in ("workload_mix", "tenant_stats", "loadgen_summary"):
        required = set(cjs.METRIC_KIND_REQUIRED[kind])
        for r in by_kind[kind]:
            assert required <= set(r), (kind, sorted(required - set(r)))
    for r in by_kind["loadgen_summary"]:
        assert r["mode"] in cjs.LOADGEN_MODES
