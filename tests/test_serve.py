"""Champion-serving subsystem tests (fks_tpu.serve).

The ISSUE-8 acceptance criteria, as tests:

- batched serving parity: every lane of a coalesced batch matches the
  UNBATCHED exact-engine answer (score <= 1e-5, placements identical) —
  scatter-back isolation means lane i sees only query i;
- zero-recompile warm path: repeated same-bucket queries after a warm
  call compile zero new XLA programs (CompileWatcher delta == 0);
- artifact round-trip: a saved+reloaded engine answers identically;
- plus units for bucket/lane routing, the prefilter auto-heuristic,
  the request coalescer's flush policy, the served-answer parity audit,
  and a CLI smoke over the real champion ledger.
"""
import json

import jax
import numpy as np
import pytest

from fks_tpu.data.synthetic import synthetic_workload
from fks_tpu.funsearch import template
from fks_tpu.serve import (
    ChampionSpec, RequestBatcher, ServeEngine, ServeService, ShapeEnvelope,
    load_champion, selftest,
)


@pytest.fixture(scope="module")
def engine():
    """One warm ServeEngine for the module: tiny synthetic cluster, the
    first_fit seed champion, a 2-rung bucket ladder."""
    wl = synthetic_workload(8, 16, seed=0)
    champ = ChampionSpec(code=template.fill_template("score = 1000"),
                         score=0.5)
    env = ShapeEnvelope(max_pods=16, min_pod_bucket=4, max_batch=4,
                        max_gpu_milli=1000)
    return ServeEngine(champ, wl, envelope=env)


def _query(i, n=2):
    return [{"cpu_milli": 10 + 7 * i + j, "memory_mib": 50 + 11 * j,
             "creation_time": j, "duration_time": 40}
            for j in range(n)]


# ----------------------------------------------------------- envelope units


def test_bucket_ladder_and_routing():
    env = ShapeEnvelope(max_pods=1024, min_pod_bucket=16,
                        pod_bucket_growth=4, max_batch=8)
    assert env.pod_buckets() == (16, 64, 256, 1024)
    assert env.pod_bucket_for(1) == 16
    assert env.pod_bucket_for(16) == 16
    assert env.pod_bucket_for(17) == 64
    assert env.pod_bucket_for(1024) == 1024
    with pytest.raises(ValueError):
        env.pod_bucket_for(1025)
    # min_real_pods: the routing guarantee the snapshot-table width
    # leans on — no query below this count lands in the bucket
    assert env.min_real_pods(16) == 1
    assert env.min_real_pods(64) == 17
    assert env.lane_buckets() == (1, 2, 4, 8)
    assert env.lanes_for(3) == 4
    with pytest.raises(ValueError):
        env.lanes_for(9)


def test_envelope_ladder_not_hitting_max():
    env = ShapeEnvelope(max_pods=100, min_pod_bucket=16,
                        pod_bucket_growth=4, max_batch=3)
    assert env.pod_buckets() == (16, 64, 100)
    assert env.lane_buckets() == (1, 2, 3)


# -------------------------------------------------------- champion loading


def test_load_champion_single_and_list(tmp_path):
    single = {"code": "def f(): pass", "score": 0.4, "generation": 3}
    top = [{"code": "a", "score": 0.1}, {"code": "b", "score": 0.9},
           {"code": "c", "score": 0.5}]
    p1 = tmp_path / "one.json"
    p1.write_text(json.dumps(single))
    p2 = tmp_path / "top.json"
    p2.write_text(json.dumps(top))
    c1 = load_champion(str(p1))
    assert c1.score == 0.4 and c1.generation == 3
    assert load_champion(str(p2)).code == "b"  # best of the list wins
    (tmp_path / "bad.json").write_text("{\"notcode\": 1}")
    with pytest.raises(ValueError):
        load_champion(str(tmp_path / "bad.json"))


# ------------------------------------------------------ snapshot cache


def test_snapshot_cache_stays_under_its_byte_bound():
    """The device-resident snapshot-table cache honours a ceiling in
    BYTES, not only an entry count: eight queries with distinct tables
    (the table is a function of the real pod count) through a cache that
    holds two never take it over the bound, the oldest are evicted, and
    the newest still hits."""
    wl = synthetic_workload(8, 16, seed=0)
    champ = ChampionSpec(code=template.fill_template("score = 1000"),
                         score=0.4)
    env = ShapeEnvelope(max_pods=8, min_pod_bucket=8, max_batch=2,
                        max_gpu_milli=1000)
    distinct = [_query(0, n) for n in range(1, 9)]
    probe = ServeEngine(champ, wl, envelope=env, engine="flat")
    probe.answer_batch(distinct[:1])
    cap = 2 * probe.snapshot_cache_bytes
    assert cap > 0
    eng = ServeEngine(champ, wl, envelope=env, engine="flat",
                      snapshot_cache_max_bytes=cap)
    for q in distinct:
        eng.answer_batch([q])
        assert eng.snapshot_cache_bytes <= cap
    stats = eng.snapshot_cache_stats()
    assert stats["misses"] == len(distinct) > stats["entries"]
    eng.answer_batch([distinct[-1]])
    assert eng.snapshot_cache_stats()["hits"] == stats["hits"] + 1


# ------------------------------------------------- prefilter auto-heuristic


def test_auto_prefilter_k_units():
    from fks_tpu.sim.engine import auto_prefilter_k

    # override always wins, probe or not
    assert auto_prefilter_k(4096, 1e-2, override=0) == 0
    assert auto_prefilter_k(64, None, override=32) == 32
    # small node parks never prefilter (the dense sweep is already cheap)
    assert auto_prefilter_k(128, 1e-2) == 0
    # big park + expensive policy -> on; cheap policy -> off
    assert auto_prefilter_k(4096, 1e-2) == 64
    assert auto_prefilter_k(4096, 1e-6) == 0
    assert auto_prefilter_k(4096, None) == 0  # probe failed -> stay dense


# --------------------------------------------------------- serving parity


def test_batch_parity_and_scatterback_isolation(engine):
    """Three DISTINCT queries batched together: each lane's answer equals
    its own unbatched exact answer — a lane leak (query j's pods bleeding
    into lane i) would break score or placements immediately."""
    queries = [_query(0, 1), _query(1, 2), _query(2, 3)]
    batched = engine.answer_batch(queries)
    for q, ans in zip(queries, batched):
        ref = engine.reference_answer(q)
        assert abs(ans["score"] - ref["score"]) <= 1e-5
        assert ans["placements"] == ref["placements"]
        assert ans["scheduled"] == ref["scheduled"]
    # distinct queries should produce at least two distinct answers here
    assert len({a["score"] for a in batched}) > 1


def test_batch_order_preserved(engine):
    queries = [_query(3, 2), _query(4, 2)]
    fwd = engine.answer_batch(queries)
    rev = engine.answer_batch(queries[::-1])
    assert fwd[0]["score"] == rev[1]["score"]
    assert fwd[0]["placements"] == rev[1]["placements"]


def test_selftest_green(engine):
    result = selftest(engine, count=4, pods_per_query=3)
    assert result["ok"], result
    assert result["max_drift"] <= 1e-5 and result["placements_match"]


def test_oversized_and_malformed_queries_rejected(engine):
    with pytest.raises(ValueError):
        engine.answer_batch([[]])
    with pytest.raises(ValueError):
        engine.answer_batch([_query(0, 17)])  # > max_pods
    with pytest.raises(ValueError):
        engine.answer_batch([[{"cpu_milli": -1}]])


# ----------------------------------------------------------- zero recompile


def test_warm_path_zero_recompile(engine):
    from fks_tpu.obs import CompileWatcher

    queries = [_query(5, 2), _query(6, 3)]
    engine.answer_batch(queries)  # warm: AOT + eager stacking programs
    watcher = CompileWatcher().install()
    try:
        for i in range(3):
            engine.answer_batch([_query(7 + i, 3), _query(9 + i, 2)])
        delta = watcher.backend_compile_count
    finally:
        watcher.uninstall()
    assert delta == 0, (
        f"{delta} XLA programs compiled on the warm path — the AOT "
        "bucket cache leaked a shape")


# --------------------------------------------------------- artifact I/O


def test_artifact_round_trip(tmp_path, engine):
    q = _query(10, 2)
    before = engine.answer_batch([q])[0]
    d = str(tmp_path / "artifact")
    cache_before = jax.config.jax_compilation_cache_dir
    engine.save(d)
    loaded = ServeEngine.load(d)
    # an artifact is its artifact.json: neither direction repoints the
    # process-wide compile cache (it is placed once, by the entry point)
    assert jax.config.jax_compilation_cache_dir == cache_before
    assert sorted(p.name for p in (tmp_path / "artifact").iterdir()) == [
        "artifact.json"]
    after = loaded.answer_batch([q])[0]
    assert before["score"] == after["score"]
    assert before["placements"] == after["placements"]
    assert loaded.envelope == engine.envelope
    assert loaded.prefilter_k == engine.prefilter_k
    assert loaded.base_pods == engine.base_pods
    # version guard: a future-format artifact must refuse to half-load
    doc = json.loads((tmp_path / "artifact" / "artifact.json").read_text())
    doc["version"] = 999
    (tmp_path / "artifact" / "artifact.json").write_text(json.dumps(doc))
    with pytest.raises(ValueError):
        ServeEngine.load(d)


# ------------------------------------------------------- request coalescer


def test_batcher_coalesces_and_scatters():
    seen_batches = []

    def handler(queries, _enq):
        seen_batches.append(list(queries))
        return [q * 10 for q in queries]

    b = RequestBatcher(handler, max_batch=3, max_wait_s=0.2)
    futs = [b.submit(i) for i in (1, 2, 3)]
    assert [f.result(timeout=5) for f in futs] == [10, 20, 30]
    assert len(seen_batches) == 1  # full batch flushed as one call
    b.close()
    assert b.submitted == 3 and b.batches == 1
    assert b.mean_occupancy == 1.0


def test_batcher_max_wait_flush_and_errors():
    def handler(queries, _enq):
        if any(q == "boom" for q in queries):
            raise RuntimeError("bad batch")
        return queries

    b = RequestBatcher(handler, max_batch=8, max_wait_s=0.01)
    f = b.submit("lonely")
    assert f.result(timeout=5) == "lonely"  # flushed by max_wait, not size
    g = b.submit("boom")
    with pytest.raises(RuntimeError):
        g.result(timeout=5)
    b.close()
    with pytest.raises(RuntimeError):
        b.submit("after close")


# ---------------------------------------------------------- service + audit


def test_service_answers_and_audits(engine):
    service = ServeService(engine, max_wait_s=0.005, audit_every=1)
    try:
        futs = [service.submit({"id": f"q{i}", "pods": _query(i, 2)})
                for i in range(3)]
        answers = [f.result(timeout=60) for f in futs]
    finally:
        service.close()
    assert [a["id"] for a in answers] == ["q0", "q1", "q2"]
    assert all(a["latency_ms"] > 0 for a in answers)
    summary = service.summary(record=False)
    assert summary["requests"] == 3
    assert summary["audits"] == 3 and summary["audit_failures"] == 0
    with pytest.raises(ValueError):
        service.resolve_query({"nope": 1})


def test_service_tenant_accounting(engine):
    """accounting=True threads tenant identity end to end: the batcher
    item carries it, serve_request rows are labelled, the accountant
    aggregates per tenant, and every ``workload_every`` requests one
    tenant_stats row per tenant plus a workload_mix row land on the
    recorder."""
    class Rec:
        enabled = True

        def __init__(self):
            self.metrics, self.events = [], []

        def metric(self, kind, record=None, **f):
            self.metrics.append({"kind": kind, **(record or f)})

        def event(self, kind, **f):
            self.events.append((kind, f))

    rec = Rec()
    service = ServeService(engine, recorder=rec, max_wait_s=0.002,
                           accounting=True, workload_every=2)
    try:
        futs = [service.submit({"id": f"q{i}", "tenant": t,
                                "pods": _query(i, 2)})
                for i, t in enumerate(("acme", "acme", "zoo"))]
        for f in futs:
            f.result(timeout=60)
        summary = service.summary(record=False)
    finally:
        service.close()
    stats = service.accountant.stats()
    assert stats["acme"]["requests"] == 2 and stats["zoo"]["requests"] == 1
    assert stats["acme"]["ewma_ms"] > 0
    reqs = [m for m in rec.metrics if m["kind"] == "serve_request"]
    assert [m["tenant"] for m in reqs] == ["acme", "acme", "zoo"]
    assert all(m["workload_class"].startswith("p2:") for m in reqs)
    # windowed accounting fired after crossing workload_every
    tstats = [m for m in rec.metrics if m["kind"] == "tenant_stats"]
    assert {m["tenant"] for m in tstats} == {"acme", "zoo"}
    mixes = [m for m in rec.metrics if m["kind"] == "workload_mix"]
    # the windowed record saw all 3 requests, then reset the window
    assert mixes and mixes[0]["window"] == 3
    assert 0.0 < summary["fairness_index"] <= 1.0
    assert set(summary["tenants"]) == {"acme", "zoo"}


def test_service_accounting_disabled_is_inert(engine):
    """The disabled path allocates no accountant and labels rows with
    the default tenant only — no workload_class field at all."""
    class Rec:
        enabled = False

        def __init__(self):
            self.metrics = []

        def metric(self, kind, record=None, **f):
            self.metrics.append({"kind": kind, **(record or f)})

    rec = Rec()
    service = ServeService(engine, recorder=rec, max_wait_s=0.002)
    try:
        service.submit({"pods": _query(0, 2)}).result(timeout=60)
    finally:
        service.close()
    assert service.accountant is None and service.fingerprinter is None
    row = [m for m in rec.metrics if m["kind"] == "serve_request"][0]
    assert row["tenant"] == "default"
    assert "workload_class" not in row


# -------------------------------------------------------------- HTTP front


def test_http_front_concurrent_clients_share_a_batch(engine):
    """Two clients POSTing at once must land in ONE coalesced batch: with
    max_batch=2 and a 5s flush wait, a serialized (single-threaded) front
    would make each request wait out the full window alone — both
    answering well under the window proves the handlers genuinely
    overlap."""
    import threading
    import time

    from fks_tpu.obs.workload import http_client
    from fks_tpu.serve.service import make_http_server

    service = ServeService(engine, max_batch=2, max_wait_s=5.0)
    server = make_http_server(service, 0)
    port = server.server_address[1]
    t = threading.Thread(target=server.serve_forever, daemon=True)
    t.start()
    send = http_client(port)
    outcomes = [None, None]

    def client(k):
        outcomes[k] = send({"id": f"c{k}", "pods": _query(k, 2)})

    try:
        t0 = time.perf_counter()
        c0 = threading.Thread(target=client, args=(0,))
        c1 = threading.Thread(target=client, args=(1,))
        c0.start()
        c1.start()
        c0.join(timeout=30)
        c1.join(timeout=30)
        elapsed = time.perf_counter() - t0
    finally:
        server.shutdown()
        server.server_close()
        service.close()
    assert [o["outcome"] for o in outcomes] == ["ok", "ok"]
    assert elapsed < 4.0, (
        f"two concurrent POSTs took {elapsed:.1f}s — they waited out the "
        "flush window instead of coalescing into one batch")
    assert service.summary(record=False)["batches"] == 1


def test_http_front_routes_and_errors(engine):
    """GET /stats and /healthz answer; a malformed POST answers a
    structured 400 instead of wedging the socket."""
    import json as _json
    import threading
    import urllib.error
    import urllib.request

    from fks_tpu.serve.service import make_http_server

    service = ServeService(engine, max_wait_s=0.002)
    server = make_http_server(service, 0)
    port = server.server_address[1]
    threading.Thread(target=server.serve_forever, daemon=True).start()
    base = f"http://127.0.0.1:{port}"
    try:
        with urllib.request.urlopen(f"{base}/healthz", timeout=30) as r:
            assert _json.loads(r.read())["ok"]
        with urllib.request.urlopen(f"{base}/stats", timeout=30) as r:
            assert "requests" in _json.loads(r.read())
        bad = urllib.request.Request(
            f"{base}/query", data=b'{"nope": 1}',
            headers={"Content-Type": "application/json"})
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(bad, timeout=30)
        assert ei.value.code == 400
    finally:
        server.shutdown()
        server.server_close()
        service.close()


def test_audit_served_alerts_on_drift():
    from fks_tpu.funsearch.parity import ParitySentinel

    class Rec:
        def __init__(self):
            self.metrics, self.events = [], []
            self.enabled = True

        def metric(self, kind, record=None, **f):
            self.metrics.append((kind, record or f))

        def event(self, kind, **f):
            self.events.append((kind, f))

    rec = Rec()
    s = ParitySentinel(None, tol=1e-5, recorder=rec)
    assert s.audit_served("r1", 0.5, 0.5)
    assert s.alerts == 0
    assert not s.audit_served("r2", 0.5, 0.6)  # drift
    assert not s.audit_served("r3", 0.5, 0.5, placements_match=False)
    assert s.alerts == 2 and s.checked == 3
    assert [k for k, _ in rec.metrics] == ["parity"] * 3
    alert_kinds = [f["source"] for k, f in rec.events if k == "alert"]
    assert alert_kinds == ["serve_parity", "serve_parity"]


# ----------------------------------------------------------------- CLI


def test_cli_serve_jsonl_smoke(tmp_path, capsys):
    from fks_tpu import cli

    qfile = tmp_path / "q.jsonl"
    qfile.write_text(
        json.dumps({"id": "a", "pods": _query(0, 2)}) + "\n"
        + json.dumps({"id": "b", "pods": _query(1, 1)}) + "\n")
    rc = cli.main(["serve", "--cpu", "--max-pods", "16", "--max-batch", "2",
                   "--queries", str(qfile), "--audit-every", "2",
                   "--run-dir", str(tmp_path / "run")])
    out = capsys.readouterr().out
    assert rc == 0
    answers = [json.loads(line) for line in out.strip().splitlines()]
    assert [a["id"] for a in answers] == ["a", "b"]
    assert all("score" in a and "placements" in a for a in answers)
    # the run dir passes the schema checker, serve_request kind included
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools"))
    try:
        import check_jsonl_schema as cjs
    finally:
        sys.path.pop(0)
    assert cjs.main(["--run-dir", str(tmp_path / "run")]) == 0
    metrics = [json.loads(ln) for ln in
               (tmp_path / "run" / "metrics.jsonl").read_text().splitlines()]
    assert sum(m["kind"] == "serve_request" for m in metrics) == 2
    assert any(m["kind"] == "parity" and m.get("source") == "serve"
               for m in metrics)


def test_cli_serve_selftest_smoke(tmp_path):
    from fks_tpu import cli

    rc = cli.main(["serve", "--cpu", "--max-pods", "8", "--max-batch", "2",
                   "--selftest", "2", "--pods-per-query", "2",
                   "--save-artifact", str(tmp_path / "art")])
    assert rc == 0
    assert (tmp_path / "art" / "artifact.json").exists()
