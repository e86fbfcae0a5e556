"""A call that runs long names its stage (``fks_tpu.obs.spans``, ISSUE 40).

The ring compares every closed root of a call (``tier/evaluate``,
``serve/batch``) with the median of its like and keeps, counts, writes and
says the ones that ran long, each with the span whose self time grew; the
collector's pauses are ``host/gc`` records on the same clock. Rings here
are fed by hand with explicit stamps, so nothing sleeps and no length
hangs on this machine's load.
"""
import gc
import logging
import types

import pytest

from fks_tpu import obs
from fks_tpu.obs import spans, telemetry, trace_ctx

STAGES = (("tier/preflight", 0.05), ("tier/transpile", 0.15),
          ("tier/vm_batch/wait_device", 2.0), ("tier/record", 0.3))


@pytest.fixture
def ring(monkeypatch):
    """A ring of its own in the process's place (the hooks write to
    ``spans.LOG``), and what its warnings said."""
    log = spans.SpanLog()
    monkeypatch.setattr(spans, "LOG", log)
    said = []

    class Keep(logging.Handler):
        def emit(self, record):
            said.append(record.getMessage())

    handler = Keep(level=logging.WARNING)
    logger = logging.getLogger("fks_tpu.obs.spans")
    logger.addHandler(handler)
    logger.propagate = False      # kept here, not printed
    log.said = said
    yield log
    logger.propagate = True
    logger.removeHandler(handler)


@pytest.fixture
def clock(monkeypatch):
    """The clock of the span module alone, moved by the test."""
    now = [50.0]
    monkeypatch.setattr(spans, "time", types.SimpleNamespace(
        perf_counter=lambda: now[0]))
    return now


def feed(log, t, stretch=None, factor=1.0, name="tier/evaluate",
         stages=STAGES, **fields):
    """One call into ``log`` from ``t`` on: the stages back to back under
    a root of ``fields``, ``stretch`` made ``factor`` times as long as the
    WHOLE call is normally (so the call is that much longer). Returns what
    ``root_closed`` returned and the call's end."""
    fields = fields or {"candidates": 8, "start_event": 0}
    whole = sum(s for _, s in stages) + 0.01
    root_id, at = trace_ctx.new_span_id(), t + 0.005
    for stage, seconds in stages:
        if stage == stretch:
            seconds += (factor - 1.0) * whole
        log.append(stage, at, at + seconds, trace_ctx.new_span_id(),
                   root_id, root_id)
        at += seconds
    root = log.append(name, t, at + 0.005, root_id, None, root_id, fields)
    return log.root_closed(root), root.t1 + 0.001


def feed_like(log, n, t=100.0, **kw):
    for _ in range(n):
        got, t = feed(log, t, **kw)
        assert got is None
    return t


# ----------------------------------------------------------- slow roots

@pytest.mark.parametrize("stage", ["tier/transpile",
                                   "tier/vm_batch/wait_device"],
                         ids=("host_stage", "wait_device"))
def test_a_root_half_again_as_long_is_kept_with_the_stage_that_grew(
        ring, stage):
    t = feed_like(ring, 12)
    slow, t = feed(ring, t, stretch=stage, factor=1.5)
    after, _ = feed(ring, t)
    assert after is None and ring.slow_count == 1
    assert spans.slow_roots() == [slow] == list(ring.slow)
    assert slow["root"] == "tier/evaluate" and slow["grew"] == stage
    assert slow["fields"] == {"candidates": 8, "start_event": 0}
    assert slow["seconds"] == pytest.approx(1.5 * slow["median"], rel=1e-6)
    assert slow["like"] == 12
    # the stage's excess is the call's, the others have none
    assert slow["excess_s"] == pytest.approx(slow["seconds"]
                                             - slow["median"])
    table = slow["stages"]
    assert set(table) == {s for s, _ in STAGES} | {"tier/evaluate"}
    for name, row in table.items():
        assert row["excess_s"] == pytest.approx(
            slow["excess_s"] if name == stage else 0.0, abs=1e-9)
        assert row["seconds"] - row["median"] == pytest.approx(
            row["excess_s"])
    assert slow["ranked"][0] == stage
    assert (slow["gc_s"], slow["gc_pauses"], slow["compiles"]) \
        == (0.0, 0, None)
    # in the ring: one record over the call's extent, a root of its own
    (rec,) = [r for r in ring.snapshot() if r.name == "obs/slow_root"]
    assert (rec.t0, rec.t1 - rec.t0) == (slow["t0"], slow["seconds"])
    assert rec.parent_id is None and rec.trace_id != slow["trace_id"]
    assert rec.fields == {k: slow[k] for k in (
        "root", "seconds", "median", "grew", "excess_s", "gc_s")}
    # said once, on one line
    (line,) = ring.said
    assert "\n" not in line
    assert line.startswith("slow call: tier/evaluate (candidates=8 "
                           "start_event=0) took ")
    assert f"grew: {stage} +" in line and "; gc 0.000 s" in line


def test_the_kept_record_renders_as_a_waterfall(ring):
    t = feed_like(ring, 8)
    slow, _ = feed(ring, t, stretch="tier/record", factor=2.0)
    rows = slow["spans"]
    assert len(rows) == 1 + len(STAGES)
    (root,) = trace_ctx.build_tree(rows)
    assert root["span"]["path"] == "tier/evaluate"
    assert [c["span"]["path"] for c in root["children"]] \
        == [s for s, _ in STAGES]
    text = trace_ctx.render_waterfall(rows)
    assert "tier/record" in text and f"({len(rows)} spans)" in text


def test_nested_growth_is_named_once_at_the_span_that_holds_it(ring):
    """A child that grew makes its parent longer too: self time names the
    child, and a parent whose own part grew is named itself."""
    def call(t, lower=0.1, around=0.05):
        rid, sid = trace_ctx.new_span_id(), trace_ctx.new_span_id()
        a = t + 0.01
        # two sources lowered side by side, then the parent's own part
        for _ in range(2):
            ring.append("tier/transpile/lower", a, a + lower,
                        trace_ctx.new_span_id(), sid, rid)
        ring.append("tier/transpile", a, a + lower + around, sid, rid, rid)
        end = a + lower + around + 1.0
        ring.append("tier/vm_batch/wait_device", end - 1.0, end,
                    trace_ctx.new_span_id(), rid, rid)
        root = ring.append("tier/evaluate", t, end, rid, None, rid,
                           {"candidates": 2, "start_event": 0})
        return ring.root_closed(root), end + 0.001

    t = 10.0
    for _ in range(9):
        got, t = call(t)
        assert got is None
    slow, t = call(t, lower=0.6)
    assert slow["grew"] == "tier/transpile/lower"
    # both workers' spans grew by 0.5 s each; the stage's own part did not
    assert slow["stages"]["tier/transpile/lower"]["excess_s"] \
        == pytest.approx(1.0)
    assert slow["stages"]["tier/transpile"]["excess_s"] \
        == pytest.approx(0.0, abs=1e-9)
    slow, t = call(t, around=0.55)
    assert slow["grew"] == "tier/transpile"


@pytest.mark.parametrize("case", ["another_shape", "first_seven",
                                  "a_fifth_longer", "another_root_name",
                                  "a_root_that_raised"])
def test_what_is_not_a_slow_root(ring, clock, case):
    if case == "another_shape":
        t = feed_like(ring, 12)
        # 1.5 x as long, but nothing of ITS shape is known yet
        got, t = feed(ring, t, stretch="tier/record", factor=1.5,
                      candidates=16, start_event=0)
        assert got is None
        got, t = feed(ring, t, stretch="tier/record", factor=1.5,
                      candidates=8, start_event=5888)
    elif case == "first_seven":
        t = feed_like(ring, spans.MIN_REFERENCE - 1)
        got, t = feed(ring, t, stretch="tier/record", factor=3.0)
    elif case == "a_fifth_longer":
        t = feed_like(ring, 12)
        got, t = feed(ring, t, stretch="tier/record", factor=1.2)
    elif case == "another_root_name":
        # serve/request is no root of a call (CALL_ROOTS): never judged
        for i in range(12):
            with obs.span("serve/request", request=f"c{i}"):
                pass
        assert "serve/request" not in spans.CALL_ROOTS
        got = None
    else:
        clock[0] = feed_like(ring, 12)
        with pytest.raises(ValueError):
            with obs.span("tier/evaluate", candidates=8, start_event=0):
                with obs.span("tier/record"):
                    clock[0] += 50.0
                raise ValueError("the generation failed")
        assert ring.snapshot()[-1].seconds == 50.0
        got = None
    assert got is None
    assert ring.slow_count == 0 and not ring.slow and not ring.said
    assert not [r for r in ring.snapshot() if r.name == "obs/slow_root"]


def test_a_list_field_counts_by_its_length(ring):
    """``serve/batch`` lists the request traces it carries: two batches of
    four requests are alike whatever the ids."""
    stages = (("serve/chunk/stack", 0.01), ("serve/chunk/wait_device", 0.1))
    t = 5.0
    for i in range(10):
        got, t = feed(ring, t, name="serve/batch", stages=stages, queries=4,
                      requests=[f"req-{i}-{j}" for j in range(4)])
        assert got is None
    slow, t = feed(ring, t, name="serve/batch", stages=stages,
                   stretch="serve/chunk/wait_device", factor=1.6, queries=4,
                   requests=["a", "b", "c", "d"])
    assert slow["grew"] == "serve/chunk/wait_device"
    assert "(queries=4 requests=4)" in ring.said[0]
    got, _ = feed(ring, t, name="serve/batch", stages=stages,
                  stretch="serve/chunk/wait_device", factor=1.6, queries=2,
                  requests=["a", "b"])
    assert got is None


def test_the_median_follows_a_regime_that_changed_for_good(ring):
    t = feed_like(ring, spans.REFERENCE_CALLS)
    slow = 0
    for _ in range(2 * spans.REFERENCE_CALLS):
        got, t = feed(ring, t, stretch="tier/record", factor=1.5)
        slow += got is not None
    # slow until half of the reference calls are of the new regime
    assert slow == spans.REFERENCE_CALLS // 2 == ring.slow_count


def test_slow_keeps_the_newest_64_and_counts_them_all(ring):
    t = 1.0
    for k in range(spans.SLOW_KEPT + 6):
        t = feed_like(ring, spans.MIN_REFERENCE, t, candidates=k,
                      start_event=0)
        got, t = feed(ring, t, stretch="tier/record", factor=2.0,
                      candidates=k, start_event=0)
        assert got is not None
    assert ring.slow_count == spans.SLOW_KEPT + 6
    assert len(ring.slow) == len(spans.slow_roots()) == spans.SLOW_KEPT == 64
    assert [r["fields"]["candidates"] for r in ring.slow] \
        == list(range(6, spans.SLOW_KEPT + 6))
    assert len(ring.said) == spans.SLOW_KEPT + 6
    ring.clear()
    assert ring.slow_count == 0 and not ring.slow
    got, _ = feed(ring, t, stretch="tier/record", factor=2.0, candidates=0,
                  start_event=0)
    assert got is None         # the reference calls went with the ring


def test_roots_closing_on_many_threads_lose_no_count(ring):
    """Sixteen threads under a short switch interval, each ten shapes of
    its own in turn, five times over: nine like roots and a slow one (the
    160 shapes fit the table, so no thread's reference calls are evicted
    under it; each thread's stamps are its own, so only counts are
    asserted: another thread's record may end a walk early)."""
    import sys
    import threading

    def work(k):
        t = 1000.0 * k
        for i in range(50):       # 48,800 records in all: the ring holds
            for _ in range(spans.MIN_REFERENCE + 1):
                _, t = feed(ring, t, candidates=k, start_event=i % 10)
            _, t = feed(ring, t, stretch="tier/record", factor=30.0,
                        candidates=k, start_event=i % 10)

    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work, args=(k,))
                   for k in range(16)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=120)
    finally:
        sys.setswitchinterval(was)
    assert not any(th.is_alive() for th in threads)
    assert ring.slow_count == 16 * 50 == len(ring.said)
    assert len(ring.slow) == spans.SLOW_KEPT
    assert len(ring._like) == 16 * 10
    assert ring.dropped == 0
    assert len([r for r in ring.snapshot() if r.name == "obs/slow_root"]) \
        == 16 * 50


def test_unlike_shapes_do_not_grow_the_table(ring):
    t = 1.0
    for k in range(spans.REFERENCE_SHAPES + 40):
        _, t = feed(ring, t, candidates=k, start_event=0)
    assert len(ring._like) == spans.REFERENCE_SHAPES


def test_real_spans_are_judged_when_their_root_closes(ring, clock):
    """Through ``obs.span`` itself, on a clock the test moves."""
    now = clock

    def call(wait):
        with obs.span("serve/batch", queries=3) as root:
            root.set(requests=["a", "b", "c"])
            with obs.span("serve/chunk/stack"):
                now[0] += 0.01
            with obs.span("serve/chunk/wait_device"):
                now[0] += wait
        now[0] += 0.001

    for _ in range(9):
        call(0.1)
    assert ring.slow_count == 0
    call(0.3)
    (slow,) = spans.slow_roots()
    assert (slow["root"], slow["grew"]) == ("serve/batch",
                                            "serve/chunk/wait_device")
    assert slow["excess_s"] == pytest.approx(0.2)
    assert slow["fields"] == {"queries": 3, "requests": ["a", "b", "c"]}
    assert spans.SLOW_FACTOR == 1.25 and spans.MIN_REFERENCE == 8 \
        and spans.REFERENCE_CALLS == 32 and spans.GC_MIN_PAUSE_S == 1e-3


def test_a_slow_root_goes_to_an_open_run_directory(ring, tmp_path):
    from fks_tpu.obs.report import load_run

    with obs.recording(obs.FlightRecorder(str(tmp_path / "run"))):
        t = feed_like(ring, 9)
        feed(ring, t, stretch="tier/record", factor=1.7)
    rows = [e for e in load_run(str(tmp_path / "run"))[1]
            if e.get("kind") == "span"
            and e.get("label") == "obs/slow_root"]
    (row,) = rows
    assert row["grew"] == "tier/record" and row["root"] == "tier/evaluate"
    assert row["seconds"] == pytest.approx(1.7 * row["median"], rel=1e-4)


def test_compiles_inside_a_slow_root_are_counted_where_a_watcher_is(ring):
    watcher = telemetry.CompileWatcher().install()
    try:
        t = feed_like(ring, 9)
        # a backend compile that ended inside the slow call, one after it
        key = telemetry.COMPILE_PREFIX + "/" + telemetry.BACKEND_COMPILE
        watcher._listen(key, 0.5, fun_name="f")
        watcher._listen(key, 0.5, fun_name="g")
        watcher.compiled_at[:] = [t + 1.0, t + 100.0]
        slow, _ = feed(ring, t, stretch="tier/record", factor=1.5)
    finally:
        watcher.uninstall()
    assert slow["compiles"] == 1 and "; compiles 1" in ring.said[0]
    assert telemetry.compiles_between(0.0, 1e9) is None


# ---------------------------------------------------------------- host/gc

def gc_records(log):
    return [r for r in log.snapshot() if r.name == "host/gc"]


def test_a_full_collection_inside_a_span_is_one_record_inside_it(ring):
    assert any(getattr(cb, "__name__", "") == "_gc_pause"
               for cb in gc.callbacks)
    with obs.span("tier/transpile") as t:
        gc.collect()
    (rec,) = gc_records(ring)
    assert t.t0 <= rec.t0 <= rec.t1 <= t.t1
    assert rec.fields["generation"] == 2 and rec.fields["collected"] >= 0
    # a root of its own: no span's self time moves
    assert rec.parent_id is None and rec.trace_id == rec.span_id \
        != t.trace_id


def test_a_short_young_collection_writes_nothing(ring):
    for _ in range(50):
        gc.collect(0)
    kept = gc_records(ring)
    # generation 0 over a fresh nursery takes microseconds; one that a
    # loaded machine stretched past the floor may be there, as itself
    assert all(r.fields["generation"] == 0
               and r.t1 - r.t0 >= spans.GC_MIN_PAUSE_S for r in kept)
    assert len(kept) < 50


def test_a_long_young_collection_is_written(ring, clock):
    clock[0] = 7.0
    spans._gc_pause("start", {})
    clock[0] += 2 * spans.GC_MIN_PAUSE_S
    spans._gc_pause("stop", {"generation": 0, "collected": 3})
    (rec,) = gc_records(ring)
    assert (rec.t0, rec.fields) == (7.0, {"generation": 0, "collected": 3})


def test_pauses_inside_a_slow_root_are_its_gc_seconds(ring):
    t = feed_like(ring, 9)
    sid = trace_ctx.new_span_id()
    ring.append("host/gc", t + 0.2, t + 0.9, sid, None, sid,
                {"generation": 2, "collected": 0})
    slow, _ = feed(ring, t, stretch="tier/transpile", factor=1.5)
    assert slow["grew"] == "tier/transpile"
    assert (slow["gc_s"], slow["gc_pauses"]) == (pytest.approx(0.7), 1)
    assert "; gc 0.700 s in 1 pauses" in ring.said[0]
