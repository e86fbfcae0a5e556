"""Causal-tracing tests (fks_tpu.obs.trace_ctx + the instrumented serve
path).

The PR-15 acceptance criteria, as tests:

- context mechanics: preallocated root span id, explicit cross-thread
  activation, nesting restores the previous context;
- ``obs.span`` dual emission: ``kind="span"`` with no active context,
  ``kind="trace_span"`` (with parent linkage + child context active in
  the body) under one;
- reconstruction: tree building, waterfall completeness, critical-path
  attribution — including torn-trail tolerance;
- end-to-end: every request served through a recorded ``ServeService``
  yields ONE complete causally-linked waterfall whose components sum to
  the root wall; a degraded-mode retry stays on the SAME trace with a
  ``primary_attempt`` child carrying the fault class;
- typed resilience errors carry the request's trace id in ``to_json``;
- schema/CI surface: the ``trace_span`` kind and the OpenMetrics
  exemplar syntax are accepted by tools/check_jsonl_schema.py.
"""
import json
import threading

import pytest

from fks_tpu.obs import FlightRecorder, trace_ctx
from fks_tpu.obs.report import read_jsonl


# ----------------------------------------------------- context mechanics


def test_new_trace_preallocates_root_span_id():
    ctx = trace_ctx.new_trace()
    assert ctx.trace_id.startswith("req-")
    assert len(ctx.span_id) == 16
    gen = trace_ctx.new_trace(prefix="gen")
    assert gen.trace_id.startswith("gen-")
    assert gen.trace_id != ctx.trace_id


def test_activate_nesting_restores_previous():
    assert trace_ctx.current() is None
    a, b = trace_ctx.new_trace(), trace_ctx.new_trace()
    with trace_ctx.activate(a):
        assert trace_ctx.current() is a
        with trace_ctx.activate(b):
            assert trace_ctx.current() is b
        assert trace_ctx.current() is a
    assert trace_ctx.current() is None


def test_activate_none_is_noop():
    with trace_ctx.activate(None) as got:
        assert got is None
        assert trace_ctx.current() is None


def test_context_object_crosses_threads():
    """The propagation contract: the context OBJECT is handed over and
    re-activated on the consuming thread — no ambient inheritance."""
    ctx = trace_ctx.new_trace()
    seen = {}

    def worker():
        seen["before"] = trace_ctx.current()
        with trace_ctx.activate(ctx):
            seen["during"] = trace_ctx.current()

    with trace_ctx.activate(ctx):
        t = threading.Thread(target=worker)
        t.start()
        t.join()
    assert seen["before"] is None  # thread-locals do not leak across
    assert seen["during"] is ctx


def test_emit_without_recorder_reaches_the_ring_only(tmp_path):
    """No recorder: the span still lands in the in-memory ring, with the
    explicit stamps; no context: nothing is written anywhere."""
    from fks_tpu.obs import NULL, spans

    ctx = trace_ctx.new_trace()
    sid = trace_ctx.emit(NULL, "x", t0=10.0, t1=10.25, ctx=ctx, lanes=2)
    got = [r for r in spans.LOG.snapshot() if r.span_id == sid]
    assert len(got) == 1
    assert (got[0].name, got[0].t0, got[0].t1) == ("x", 10.0, 10.25)
    assert got[0].trace_id == ctx.trace_id
    assert got[0].parent_id == ctx.span_id and got[0].fields == {"lanes": 2}
    rec = FlightRecorder(str(tmp_path / "r"))
    try:
        assert trace_ctx.emit(rec, "x", 0.1) is None  # no active ctx
    finally:
        rec.close()
    ep = tmp_path / "r" / "events.jsonl"
    rows = read_jsonl(str(ep)) if ep.exists() else []
    assert trace_ctx.trace_spans(rows) == []


def test_ids_are_cheap_unique_and_sixteen_hex():
    ids = [trace_ctx.new_span_id() for _ in range(1000)]
    assert len(set(ids)) == 1000
    assert all(len(i) == 16 and int(i, 16) >= 0 for i in ids)
    a, b = trace_ctx.new_trace(), trace_ctx.new_trace("gen")
    assert a.trace_id.startswith("req-") and b.trace_id.startswith("gen-")
    assert len({a.trace_id, b.trace_id, a.span_id, b.span_id}) == 4
    assert a.carries == ()


def test_emit_copy_mode_writes_the_event_but_not_the_ring(tmp_path):
    """``ring=False``: the run directory's copy of a span the ring already
    holds (the per-request view of a chunk span)."""
    from fks_tpu.obs import spans

    ctx = trace_ctx.new_trace()
    rec = FlightRecorder(str(tmp_path / "r"))
    n0 = len(spans.LOG.snapshot())
    sid = trace_ctx.emit(rec, "serve/request/stack", t0=5.0, t1=5.5,
                         ctx=ctx, ring=False, chunk=1)
    rec.close()
    assert len(spans.LOG.snapshot()) == n0
    (row,) = trace_ctx.trace_spans(
        read_jsonl(str(tmp_path / "r" / "events.jsonl")))
    assert row["span_id"] == sid and row["parent_id"] == ctx.span_id
    assert (row["t0"], row["seconds"], row["chunk"]) == (5.0, 0.5, 1)


def test_span_under_a_parentless_context_is_that_traces_root():
    """The batcher's flush context: a trace id with no open parent. The
    span opened under it is a root of that trace, and parents the rest."""
    from fks_tpu import obs

    flush = trace_ctx.TraceContext("batch-x", None, carries=("req-1",))
    with trace_ctx.activate(flush):
        with obs.span("serve/batch") as root:
            assert trace_ctx.current().span_id == root.span_id
            with obs.span("serve/chunk/stack") as kid:
                pass
        assert trace_ctx.current() is flush
    assert root.record.parent_id is None
    assert root.record.trace_id == kid.record.trace_id == "batch-x"
    assert kid.record.parent_id == root.record.span_id


def test_emit_root_and_child_linkage(tmp_path):
    rec = FlightRecorder(str(tmp_path / "r"))
    ctx = trace_ctx.new_trace()
    with trace_ctx.activate(ctx):
        child_sid = trace_ctx.emit(rec, "serve/request/queue_wait", 0.002)
    root_sid = trace_ctx.emit(rec, "serve/request", 0.01, ctx=ctx,
                              root=True)
    rec.close()
    rows = read_jsonl(str(tmp_path / "r" / "events.jsonl"))
    spans = trace_ctx.trace_spans(rows)
    assert len(spans) == 2
    by_sid = {s["span_id"]: s for s in spans}
    # root reuses the preallocated id with an explicit null parent;
    # the child (emitted BEFORE the root event existed) links to it
    assert root_sid == ctx.span_id
    assert by_sid[root_sid]["parent_id"] is None
    assert by_sid[child_sid]["parent_id"] == root_sid
    assert all(s["trace_id"] == ctx.trace_id for s in spans)


def test_obs_span_dual_emission(tmp_path):
    """Same call site, two vocabularies: plain ``span`` without a trace
    context, ``trace_span`` (parented, child ctx active inside) with one."""
    from fks_tpu import obs

    rec = FlightRecorder(str(tmp_path / "r"))
    with obs.recording(rec):
        with obs.span("outer"):
            pass
        ctx = trace_ctx.new_trace()
        with trace_ctx.activate(ctx):
            with obs.span("outer"):
                inner_ctx = trace_ctx.current()
                assert inner_ctx is not ctx  # child active in the body
                assert inner_ctx.trace_id == ctx.trace_id
                with obs.span("inner"):
                    pass
    rec.close()
    rows = read_jsonl(str(tmp_path / "r" / "events.jsonl"))
    plain = [r for r in rows if r.get("kind") == "span"]
    traced = [r for r in rows if r.get("kind") == "trace_span"]
    assert [s["path"] for s in plain] == ["outer"]
    assert "trace_id" not in plain[0]
    outer = next(s for s in traced if s["path"] == "outer")
    inner = next(s for s in traced if s["path"] == "outer/inner")
    assert outer["parent_id"] == ctx.span_id
    assert inner["parent_id"] == outer["span_id"]


# -------------------------------------------------------- reconstruction


def _span(trace_id, span_id, parent_id, path, seconds, ts):
    return {"kind": "trace_span", "trace_id": trace_id, "span_id": span_id,
            "parent_id": parent_id, "path": path, "seconds": seconds,
            "ts": ts}


def _serve_trace(tid="req-x"):
    rows = [_span(tid, "root", None, "serve/request", 0.01, 10.01)]
    t = 10.0
    for i, comp in enumerate(trace_ctx.SERVE_COMPONENTS):
        rows.append(_span(tid, f"c{i}", "root", f"serve/request/{comp}",
                          0.002, t + 0.002 * (i + 1)))
    return rows


def test_build_tree_and_orphans():
    rows = _serve_trace()
    roots = trace_ctx.build_tree(rows)
    assert len(roots) == 1
    assert len(roots[0]["children"]) == len(trace_ctx.SERVE_COMPONENTS)
    # a torn parent link surfaces as an extra root, not a lost span
    rows.append(_span("req-x", "orphan", "missing", "stray", 0.001, 10.0))
    assert len(trace_ctx.build_tree(rows)) == 2


def test_waterfall_complete_requires_every_component():
    rows = _serve_trace()
    assert trace_ctx.waterfall_complete(rows)
    assert not trace_ctx.waterfall_complete(rows[:-1])  # extract gone
    assert not trace_ctx.waterfall_complete([])
    two_roots = rows + [_span("req-x", "r2", None, "serve/request",
                              0.01, 10.01)]
    assert not trace_ctx.waterfall_complete(two_roots)
    torn = rows + [_span("req-x", "t", "missing", "extra", 0.001, 10.0)]
    assert not trace_ctx.waterfall_complete(torn)


def test_render_waterfall_orders_and_labels():
    out = trace_ctx.render_waterfall(_serve_trace())
    lines = out.splitlines()
    n = 1 + len(trace_ctx.SERVE_COMPONENTS)
    assert "req-x" in lines[0] and f"{n} spans" in lines[0]
    assert "serve/request" in lines[1]
    # components render indented under the root, in start order
    for comp, line in zip(trace_ctx.SERVE_COMPONENTS, lines[2:]):
        assert comp in line and "|" in line


def test_critical_path_attribution():
    tid = "gen-y"
    rows = [_span(tid, "root", None, "generation", 10.0, 110.0),
            _span(tid, "a", "root", "llm", 6.0, 106.0),
            _span(tid, "b", "root", "evaluate", 3.0, 109.0),
            _span(tid, "c", "root", "rank", 0.5, 109.5),
            # grandchildren must NOT double-count into the attribution
            _span(tid, "d", "b", "evaluate/candidate", 0.0, 109.0)]
    cp = trace_ctx.critical_path(rows)
    assert cp["ok"] and cp["wall_seconds"] == 10.0
    assert cp["bounding_stage"] == "llm"
    assert cp["attributed_fraction"] == pytest.approx(0.95)
    # the device idles while the LLM drafts; the LLM idles the rest
    assert cp["device_idle_seconds"] == 6.0
    assert cp["llm_idle_seconds"] == pytest.approx(3.5)
    assert trace_ctx.critical_path([rows[1]]) == {
        "ok": False, "reason": "no root span"}


# ------------------------------------------------- end-to-end serve path


@pytest.fixture(scope="module")
def stack():
    """Warm incumbent + exact fallback (same shape as test_resilience)."""
    import dataclasses

    from fks_tpu.data.synthetic import synthetic_workload
    from fks_tpu.funsearch import template
    from fks_tpu.serve import ChampionSpec, ServeEngine, ShapeEnvelope

    wl = synthetic_workload(8, 16, seed=0)
    champ = ChampionSpec(code=template.fill_template("score = 1000"),
                         score=0.5, source="<test-seed>")
    env = ShapeEnvelope(max_pods=8, min_pod_bucket=8, max_batch=2)
    incumbent = ServeEngine(champ, wl, envelope=env, engine="flat")
    incumbent.warmup()
    fallback = ServeEngine(champ, wl,
                           envelope=dataclasses.replace(env, max_batch=1),
                           engine="exact")
    fallback.warmup()
    return {"incumbent": incumbent, "fallback": fallback}


def _pods(stack, i, n=3):
    base = stack["incumbent"].base_pods
    return [dict(base[(i + j) % len(base)]) for j in range(n)]


def _run_traced_service(tmp_path, stack, n, flaky=False):
    """Serve ``n`` requests through a recorded service; returns
    (answers, trace groups, serve_request metrics)."""
    from fks_tpu.serve import ServeService

    engine = stack["incumbent"]
    if flaky:
        from fks_tpu.pipeline.faults import FlakyEngineProxy
        from fks_tpu.resilience.degrade import DegradeConfig

        engine = FlakyEngineProxy(engine, failures=1)
    rec = FlightRecorder(str(tmp_path / "run"))
    service = ServeService(engine, max_wait_s=0.002, recorder=rec)
    if flaky:
        service.enable_degraded_mode(
            lambda: stack["fallback"],
            config=DegradeConfig(background_rebuild=False))
    try:
        answers = [service.submit({"id": f"q{i}",
                                   "pods": _pods(stack, i)}).result(300)
                   for i in range(n)]
    finally:
        service.close()
        rec.finish("ok")
        rec.close()
    events = read_jsonl(str(tmp_path / "run" / "events.jsonl"))
    metrics = read_jsonl(str(tmp_path / "run" / "metrics.jsonl"))
    by = trace_ctx.traces_by_id(trace_ctx.trace_spans(events))
    served = [m for m in metrics if m.get("kind") == "serve_request"]
    return answers, by, served


def test_served_requests_reconstruct_complete_waterfalls(tmp_path, stack):
    answers, by, served = _run_traced_service(tmp_path, stack, 3)
    assert len(served) == 3
    for ans, m in zip(answers, served):
        tid = m["trace_id"]
        assert ans["trace_id"] == tid  # answer and metric agree
        spans = by[tid]
        assert trace_ctx.waterfall_complete(spans)
        root = next(s for s in spans if s["parent_id"] is None)
        assert root["path"] == trace_ctx.SERVE_ROOT
        # the children are the chunk's REAL spans with their own stamps:
        # each lies inside the root, they do not overlap, and nothing is
        # made up to fill the root (what is left is bookkeeping between
        # the spans)
        kids = sorted((s for s in spans
                       if s["parent_id"] == root["span_id"]),
                      key=lambda s: s["t0"])
        lo, hi = root["t0"], root["t0"] + root["seconds"]
        for a, b in zip(kids, kids[1:]):
            assert a["t0"] + a["seconds"] <= b["t0"] + 2e-6
        assert lo - 2e-6 <= kids[0]["t0"]
        assert kids[-1]["t0"] + kids[-1]["seconds"] <= hi + 2e-6
        assert sum(s["seconds"] for s in kids) <= root["seconds"] + 1e-5


def test_degraded_retry_stays_on_one_trace(tmp_path, stack):
    """Primary-fail -> fallback-retry is ONE connected trace: the faulted
    request's waterfall carries a ``primary_attempt`` child with the
    fault class, and later requests (already degraded) carry none."""
    answers, by, served = _run_traced_service(tmp_path, stack, 3,
                                              flaky=True)
    assert [m["trace_id"] for m in served] == \
        [a["trace_id"] for a in answers]
    retried = []
    for m in served:
        spans = by[m["trace_id"]]
        assert trace_ctx.waterfall_complete(spans)
        attempts = [s for s in spans
                    if s["path"] == "serve/request/primary_attempt"]
        if attempts:
            retried.append(m["trace_id"])
            assert attempts[0]["fault"] == "DeviceFault"
            root = next(s for s in spans if s["parent_id"] is None)
            assert attempts[0]["parent_id"] == root["span_id"]
    assert retried == [served[0]["trace_id"]]  # only the faulted batch


def test_resilience_errors_carry_trace_id():
    from fks_tpu.resilience.deadline import (
        DeadlineExceeded, ResilienceError, ShedError,
    )

    e = ShedError("full", retry_after_s=0.5, trace_id="req-abc")
    assert e.to_json()["trace_id"] == "req-abc"
    assert json.loads(json.dumps(e.to_json()))["kind"] == "shed"
    assert "trace_id" not in ResilienceError("plain").to_json()
    d = DeadlineExceeded("late", trace_id="req-def")
    assert d.to_json() == {"error": "late", "kind": "deadline",
                           "trace_id": "req-def"}


def test_batcher_shed_error_carries_trace_id(stack):
    """An in-queue expiry surfaces the request's OWN trace id on the
    typed error — the client can join its failure to the trace."""
    from fks_tpu.resilience.deadline import Deadline, ResilienceError
    from fks_tpu.serve.batcher import RequestBatcher

    gate, entered = threading.Event(), threading.Event()

    def blocked(queries, enq):
        entered.set()
        gate.wait(30)
        return list(queries)

    import time

    from fks_tpu.resilience.deadline import ShedError

    b = RequestBatcher(blocked, max_batch=1, max_wait_s=0.0)
    ctx = trace_ctx.new_trace()
    try:
        first = b.submit("a")
        assert entered.wait(30)
        # generous enough to pass admission's projected-wait check, short
        # enough to expire while the worker is provably still blocked
        try:
            doomed = b.submit("b",
                              deadline=Deadline(time.perf_counter() + 0.2),
                              ctx=ctx)
        except ShedError as e:
            # admission refused it up front — the shed path must carry
            # the trace id too
            assert e.trace_id == ctx.trace_id
            doomed = None
        if doomed is not None:
            time.sleep(0.25)  # worker still gated: the budget expires
        gate.set()
        first.result(30)
        if doomed is not None:
            with pytest.raises(ResilienceError) as ei:
                doomed.result(30)
            assert ei.value.trace_id == ctx.trace_id
            assert ei.value.to_json()["trace_id"] == ctx.trace_id
    finally:
        gate.set()
        b.close()


# ------------------------------------------------------ schema/CI surface


def test_schema_accepts_trace_span_and_exemplars(tmp_path):
    import sys

    sys.path.insert(0, "tools")
    try:
        import check_jsonl_schema as cjs
    finally:
        sys.path.pop(0)
    row = {"ts": 1.0, "kind": "trace_span", "trace_id": "req-a",
           "span_id": "s1", "parent_id": None, "path": "serve/request",
           "seconds": 0.01}
    p = tmp_path / "events.jsonl"
    p.write_text(json.dumps(row) + "\n")
    recs = cjs.check_jsonl(str(p), required=("ts", "kind"))
    cjs.check_kinds(str(p), recs, cjs.EVENT_KIND_REQUIRED)  # no raise
    bad = dict(row)
    del bad["span_id"]
    p.write_text(json.dumps(bad) + "\n")
    recs = cjs.check_jsonl(str(p), required=("ts", "kind"))
    with pytest.raises(cjs.SchemaError, match="span_id"):
        cjs.check_kinds(str(p), recs, cjs.EVENT_KIND_REQUIRED)
    # exemplar'd histogram buckets are legal OpenMetrics samples
    text = "\n".join([
        "# TYPE fks_serve_latency_seconds histogram",
        'fks_serve_latency_seconds_bucket{le="0.5"} 3 '
        '# {trace_id="req-a"} 0.41',
        'fks_serve_latency_seconds_bucket{le="+Inf"} 3',
        "fks_serve_latency_seconds_sum 1.2",
        "fks_serve_latency_seconds_count 3",
        "# EOF", ""])
    assert cjs.check_openmetrics(text) == 4
    with pytest.raises(cjs.SchemaError, match="malformed"):
        cjs.check_openmetrics(text.replace('} 0.41', '} nope extra'))
