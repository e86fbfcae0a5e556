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


# ------------------------------------------- gpu_spec in the query schema
#
# A query pod may carry ``gpu_spec``: GPU model names joined by ``|`` (a
# list of strings is the same set). A pod with a non-empty one is placed
# only on a node whose model is in the set; an engine whose workload is
# not typed refuses it by name. The typed serve paths against the plain
# reference are tests/test_vm_serve.py and tests/test_serve_fork.py.

TYPED_NODES = [
    {"node_id": "n0", "cpu_milli": 32000, "memory_mib": 65536,
     "gpu_count": 0},
    {"node_id": "n1", "cpu_milli": 32000, "memory_mib": 65536,
     "gpu_count": 2, "model": "T4"},
    {"node_id": "n2", "cpu_milli": 32000, "memory_mib": 65536,
     "gpu_count": 2, "model": "G2"},
    {"node_id": "n3", "cpu_milli": 32000, "memory_mib": 65536,
     "gpu_count": 4, "model": "V100M16"},
]


def _typed_pod(i, spec=None, **kw):
    pod = {"cpu_milli": 1000, "memory_mib": 1024, "num_gpu": 1,
           "gpu_milli": 500, "creation_time": i, "duration_time": 50, **kw}
    if spec is not None:
        pod["gpu_spec"] = spec
    return pod


@pytest.fixture(scope="module")
def typed_engine():
    """A first-fit champion on four nodes of three GPU models, the
    workload typed (``gpu_spec="honor"``)."""
    from fks_tpu.data.build import make_workload

    base = [{"pod_id": f"p{i}", **_typed_pod(i, s)}
            for i, s in enumerate(["", "T4", "G2|T4"])]
    wl = make_workload(TYPED_NODES, base, gpu_spec="honor")
    assert wl.typed
    champ = ChampionSpec(code=template.seed_policies()["first_fit"],
                         score=0.4)
    env = ShapeEnvelope(max_pods=8, min_pod_bucket=8, max_batch=2,
                        max_gpu_milli=1000)
    return ServeEngine(champ, wl, envelope=env, prefilter_k=0)


@pytest.mark.parametrize("spec,word", [
    (None, 0), ("", 0), ([], 0),                    # absent or empty: any
    ("T4", 0b010), (["T4"], 0b010),
    ("G2|T4", 0b011), (["T4", "G2"], 0b011),        # a set, in any order
    ("G2|G2|T4", 0b011),                            # a repeat means nothing
    ("A100", -2 ** 31),                             # no node's model
    ("A100|V100M16", -2 ** 31 | 0b100),
])
def test_a_query_pods_gpu_spec_becomes_its_word(typed_engine, spec, word):
    from fks_tpu.serve.batcher import build_query_workload, query_gpu_spec

    pod = _typed_pod(0, spec)
    assert isinstance(query_gpu_spec(pod), str)
    typed_engine.validate_query([pod])
    wl = build_query_workload(typed_engine.cluster, [pod, _typed_pod(1)], 8)
    assert wl.typed and wl.cluster.gpu_models == ("G2", "T4", "V100M16")
    got = np.asarray(wl.pods.gpu_spec)
    assert got.dtype == np.int32 and got.tolist() == [word] + [0] * 7


@pytest.mark.parametrize("spec", [7, 1.5, {"T4": 1}, ["T4", 3], [["T4"]],
                                  True])
def test_a_malformed_gpu_spec_is_a_4xx_before_any_device_work(
        typed_engine, engine, spec):
    for eng in (typed_engine, engine):      # typed or not: refused alike
        with pytest.raises(ValueError, match="pod 1 gpu_spec .* neither a "
                           "string of GPU model names"):
            eng.validate_query([_typed_pod(0), _typed_pod(1, spec)])
    with pytest.raises(ValueError, match="neither a string"):
        ServeService(typed_engine).submit({"pods": [_typed_pod(0, spec)]})


def test_an_untyped_engine_refuses_a_constrained_query_by_name(engine):
    """A silent drop would be a wrong answer: the request is this
    request's 4xx at submit and never reaches a batch; a pod with an
    empty ``gpu_spec`` asks nothing and is served."""
    assert not engine.typed and engine.cluster.gpu_model is None
    with pytest.raises(ValueError, match="pod 1 names the GPU models it "
                       "accepts .*'T4'.* parsed without GPU models"):
        engine.answer_batch([[_query(0, 1)[0],
                              {**_query(0, 1)[0], "gpu_spec": "T4"}]])
    service = ServeService(engine, max_wait_s=0.002)
    try:
        before = service.summary(record=False)["batches"]
        with pytest.raises(ValueError, match="parsed without GPU models"):
            service.submit({"pods": [{**_query(0, 1)[0],
                                      "gpu_spec": ["T4"]}]})
        ans = service.submit({"pods": [{**_query(0, 1)[0],
                                        "gpu_spec": ""}]}).result(60)
        assert ans["placements"][0]["node"] >= 0
        assert service.summary(record=False)["batches"] == before + 1
    finally:
        service.close()


def test_a_typed_engine_places_a_pod_only_where_its_gpu_spec_allows(
        typed_engine):
    """first_fit takes the first node that fits: n1 (T4) for a GPU pod
    that names nothing; the named model's node for one that does; no
    node for a model the cluster does not have."""
    pods = [_typed_pod(0), _typed_pod(1, "G2"), _typed_pod(2, "V100M16|G3"),
            _typed_pod(3, ["G2", "T4"]), _typed_pod(4, "A100"),
            _typed_pod(5, "T4", num_gpu=0, gpu_milli=0)]
    a = typed_engine.answer_batch([pods])[0]
    assert [r["node"] for r in a["placements"]] == [1, 2, 3, 1, -1, 1]
    ref = typed_engine.reference_answer(pods)
    assert ref["placements"] == a["placements"]
    assert ref["score"] == a["score"]


def test_pods_to_dicts_writes_the_gpu_spec_back(typed_engine):
    from fks_tpu.data.entities import gpu_spec_bits, gpu_spec_names
    from fks_tpu.serve.batcher import build_query_workload, pods_to_dicts

    assert [p.get("gpu_spec") for p in typed_engine.base_pods] \
        == [None, "T4", "G2|T4"]
    sent = [_typed_pod(0), _typed_pod(1, "T4|G2|T4"), _typed_pod(2, "A100"),
            _typed_pod(3, ["V100M16", "A100"])]
    vocab = typed_engine.cluster.gpu_models
    wl = build_query_workload(typed_engine.cluster, sent, 8)
    back = pods_to_dicts(wl.pods, gpu_models=vocab)
    assert [p.get("gpu_spec") for p in back] \
        == [None, "G2|T4", "<no-node>", "V100M16|<no-node>"]
    again = build_query_workload(typed_engine.cluster, back, 8)
    assert np.array_equal(again.pods.gpu_spec, wl.pods.gpu_spec)
    for tree_a, tree_b in zip(jax.tree_util.tree_leaves(again.pods),
                              jax.tree_util.tree_leaves(wl.pods)):
        assert np.array_equal(tree_a, tree_b)
    for word in (0, 0b101, -2 ** 31, -2 ** 31 | 0b010):
        assert gpu_spec_bits(gpu_spec_names(word, vocab), vocab) == word
    # an untyped workload's pods have no such key, as before the field
    assert all("gpu_spec" not in p for p in pods_to_dicts(
        synthetic_workload(8, 16, seed=0).pods))


def test_a_typed_artifact_round_trips_with_its_models(tmp_path,
                                                      typed_engine):
    q = [_typed_pod(0, "G2"), _typed_pod(1, "V100M16"), _typed_pod(2)]
    before = typed_engine.answer_batch([q])[0]
    d = str(tmp_path / "typed_artifact")
    typed_engine.save(d)
    loaded = ServeEngine.load(d)
    assert loaded.typed and loaded.base_pods == typed_engine.base_pods
    assert loaded.cluster.gpu_models == typed_engine.cluster.gpu_models
    assert np.array_equal(loaded.cluster.gpu_model,
                          typed_engine.cluster.gpu_model)
    after = loaded.answer_batch([q])[0]
    assert after["placements"] == before["placements"]
    assert [r["node"] for r in after["placements"]] == [2, 3, 1]


def test_queries_that_differ_only_in_gpu_spec_are_not_one_class():
    from fks_tpu.serve.accounting import QueryFingerprinter

    fp = QueryFingerprinter()
    plain = [_typed_pod(0), _typed_pod(1)]
    t4 = [_typed_pod(0, "T4"), _typed_pod(1)]
    assert fp.classify(plain) != fp.classify(t4)
    assert fp.classify(t4) != fp.classify([_typed_pod(0, "G2"),
                                           _typed_pod(1)])
    # a set: order, repeats and the list form do not matter; nor does
    # the order of the pods
    assert fp.classify([_typed_pod(0, "G2|T4"), _typed_pod(1)]) \
        == fp.classify([_typed_pod(1), _typed_pod(0, ["T4", "G2", "T4"])])
    # a pod that names nothing keeps the class it always had
    assert fp.classify(plain) == fp.classify(
        [_typed_pod(0, ""), _typed_pod(1, [])])


def test_http_front_passes_the_gpu_spec_through(typed_engine, engine):
    """The field rides the JSON body to the engine: a typed engine
    honours it, an untyped one answers 400 with the reason."""
    import json as _json
    import threading
    import urllib.error
    import urllib.request

    from fks_tpu.serve.service import make_http_server

    def post(port, pods):
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/query",
            data=_json.dumps({"pods": pods}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=60) as r:
            return _json.loads(r.read())

    pods = [_typed_pod(0, "V100M16"), _typed_pod(1, ["G2"]), _typed_pod(2)]
    for eng in (typed_engine, engine):
        service = ServeService(eng, max_wait_s=0.002)
        server = make_http_server(service, 0)
        port = server.server_address[1]
        threading.Thread(target=server.serve_forever, daemon=True).start()
        try:
            if eng.typed:
                ans = post(port, pods)
                assert [r["node"] for r in ans["placements"]] == [3, 2, 1]
            else:
                with pytest.raises(urllib.error.HTTPError) as ei:
                    post(port, pods)
                assert ei.value.code == 400
                assert "parsed without GPU models" in _json.loads(
                    ei.value.read())["error"]
            with pytest.raises(urllib.error.HTTPError) as ei:
                post(port, [_typed_pod(0, 7)])
            assert ei.value.code == 400
        finally:
            server.shutdown()
            server.server_close()
            service.close()
