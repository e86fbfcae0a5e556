"""Spans: the one timing mechanism, from a request down to a device wait.

``span(name)`` is a context manager around one piece of HOST work at a
call, chunk or stage boundary (never per lockstep event, per pod or per
lane). What one span records (``SpanRecord``): its name, ``t0``/``t1`` on
``time.perf_counter`` (the clock of the benchmark's window and drivers),
its own id, the id of the span that caused it (``parent_id``: the span
open around it on this thread, or the active ``trace_ctx`` context), the
id its whole request / batch / generation shares (``trace_id``), the
thread, and a few small fields (chunk index, bucket, lanes, bytes).

Where records go:

- **the ring** (``LOG``): ONE process-wide bounded in-memory log, always
  on. It holds the newest ``CAPACITY`` = 65,536 records (a traced whatif8
  benchmark run makes about 1,500, an open-loop serving window tens of
  thousands; at ~300 bytes a record the full ring is under 20 MB) and
  counts what it drops. ``LOG.snapshot()`` is what ``chipbench``'s
  per-layer readers, ``StageProfiler`` and the serve waterfall read.
  Spans only known after the fact are written to the same ring with
  explicit stamps: a request's queue wait by ``trace_ctx.emit``, a child
  whose stamps another process of this host took (a lowering worker:
  ``time.perf_counter`` is ``CLOCK_MONOTONIC`` on Linux, one clock for
  all of them) by ``span.child`` of the open span it belongs under.
- **the profiler's timeline**: every span enters
  ``jax.profiler.TraceAnnotation("fks/<name>")``, so in any profiler
  session the program's spans lie in the xplane on the device trace's
  clock. Without a session that is a no-op.
- **an open run directory**: the active ``FlightRecorder`` additionally
  gets one ``kind="span"`` event (label/path/depth/seconds/t0 + fields)
  per span, or ``kind="trace_span"`` (plus trace_id/span_id/parent_id)
  when a ``trace_ctx`` context is active, in which case a child context
  is active for the body so nested spans and cross-thread hand-offs
  chain to this one.

On and off. The default state, which production and the benchmark's
``--trace 0`` run, is two clock reads, one ring append and the no-op
annotation: no fence, no file, no lock. "On" is an open run directory
(events to disk) and an enabled ``StageProfiler`` (fences). A span adds a
device fence only where the caller asks for one with ``t.sync(value)``
(the clock then stops after ``value`` is ready), which the ``wait_device``
spans do on a value the next line fetches anyway.

Span names are a contract (PERF.md section 3 lists each with the metric
that reads it): ``serve/request`` (+ ``/queue_wait``, ``/batch_wait``),
``serve/batch`` (+ ``/swap_wait``), ``serve/chunk/{stack,pack,h2d,
enqueue,wait_device,d2h,extract}`` (+ ``serve/chunk/stack/heap_replay``,
a forked chunk's per-query heap replays), ``tier/evaluate``, ``tier/preflight``,
``tier/transpile``, ``tier/vm_batch/{stack_programs,launch,wait_device,
d2h}``, ``tier/record``, ``tier/fallback``, ``mesh/shard_put``,
``mesh/segment`` (+ ``/wait``), ``mesh/finish``. A ``wait_device`` span
holds nothing but the blocking call, so their union is "the host waited
for the chip". ``tier/transpile/lower`` (one a unique source, stamped by
the process that lowered it) and ``tier/transpile/pack`` are the transpile
stage's children (``funsearch/backend.py``, ``lower_pool.py``).

Two names are the ring's OWN, written here and never opened by a call
site:

- ``host/gc`` (``generation``, ``collected``): one pause of CPython's
  collector, from the ``gc.callbacks`` entry this module installs with the
  ring. Written for a generation-2 collection or any pause of
  ``GC_MIN_PAUSE_S`` or more; everything else costs two clock reads. A root
  with a trace id of its own: found by time containment, so no self time
  moves.
- ``obs/slow_root`` (``root``, ``seconds``, ``median``, ``grew``,
  ``excess_s``, ``gc_s``): a call that ran long. When a root of
  ``CALL_ROOTS`` closes (``tier/evaluate``: a generation; ``serve/batch``:
  a coalesced serving call) the ring compares it with the median of the
  last ``REFERENCE_CALLS`` roots of the same name and shape
  (``SpanLog.root_closed``). One ``SLOW_FACTOR`` times longer is kept
  (``SLOW``, ``slow_roots()``: per span name the self seconds in this call
  against the median over the reference calls, the name that ``grew``, the
  collector's seconds and the compiles inside, the call's own records for
  ``trace_ctx.render_waterfall``), counted (``LOG.slow_count``), written
  as this record over the call's extent and logged as ONE warning line.
  Cost: on a root's close only, a walk over that call's own records.
"""
from __future__ import annotations

import collections
import gc
import itertools
import operator
import statistics
import threading
import time
from typing import Any, Dict, List, NamedTuple, Optional, Sequence

import jax

from fks_tpu.obs import trace_ctx
from fks_tpu.obs.recorder import get_recorder

#: records the ring holds before it drops the oldest (module docstring)
CAPACITY = 65536
#: the roots of a call, each with the fields it is opened with that make
#: two calls alike (its shape; a list counts by its length).
#: ``serve/request`` is left out: its shape is the query's size, and the
#: open-loop cell that wants it will say so
CALL_ROOTS = {"tier/evaluate": ("candidates", "start_event"),
              "serve/batch": ("queries", "requests")}
#: a root this many times the median of its like is slow: like calls lie
#: within 5 % of each other (2.64-2.78 s) and the stalls seen were
#: 1.4-2.9 times as long (ledger and chip runs, PRs 33 and 35)
SLOW_FACTOR = 1.25
#: like roots the median is taken over: minutes of generations, so a
#: regime that changed for good stops reading as slow
REFERENCE_CALLS = 32
#: like roots known before any is judged: under these a compile or a cold
#: cache is still most of the median
MIN_REFERENCE = 8
#: slow roots kept after the ring has wrapped (a server's ring holds
#: minutes; these are a few KB each)
SLOW_KEPT = 64
#: what of a slow root's record its ``obs/slow_root`` ring record carries
SLOW_FIELDS = ("root", "seconds", "median", "grew", "excess_s", "gc_s")
#: shapes a process keeps reference calls for (a server sees a few batch
#: sizes; a stream of unlike shapes must not grow the table)
REFERENCE_SHAPES = 256
#: a collection shorter than this is written only if it is a full one
#: (generation 2): generation 0 runs every few hundred allocations and
#: takes tens of microseconds, a record each would be the ring's noise
GC_MIN_PAUSE_S = 1e-3


class SpanRecord(NamedTuple):
    """One finished span, as the ring holds it."""

    seq: int                    # append order, process-wide
    name: str
    t0: float                   # time.perf_counter
    t1: float
    span_id: str
    parent_id: Optional[str]
    trace_id: str               # shared by one request / batch / generation
    thread: int
    fields: Optional[Dict[str, Any]]

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


_new_record = tuple.__new__    # skips NamedTuple's Python-level __new__


class SpanLog:
    """The bounded ring. ``append`` is one ``deque.append`` (atomic under
    the GIL, so the batcher thread and a caller need no lock) and drops
    the oldest record when full. It also keeps what a closed call root is
    compared with (``root_closed``) and the slow roots it found."""

    def __init__(self, capacity: int = CAPACITY):
        self.capacity = int(capacity)
        #: the newest ``SLOW_KEPT`` slow roots (``slow_roots()``)
        self.slow: "collections.deque[dict]" = collections.deque(
            maxlen=SLOW_KEPT)
        # roots close on the batcher's threads and on callers': what
        # root_closed keeps is read, judged and updated under this
        self._judging = threading.Lock()
        self.clear()

    def clear(self) -> None:
        self._ring: "collections.deque[SpanRecord]" = collections.deque(
            maxlen=self.capacity)
        self._seq = itertools.count()
        # (root name, shape) -> the last REFERENCE_CALLS like roots, each
        # (seconds, {span name: self seconds})
        self._like: Dict[tuple, collections.deque] = {}
        self.slow.clear()
        self.slow_count = 0

    def append(self, name: str, t0: float, t1: float, span_id: str,
               parent_id: Optional[str], trace_id: str,
               fields: Optional[Dict[str, Any]] = None) -> SpanRecord:
        rec = _new_record(SpanRecord, (
            next(self._seq), name, t0, t1, span_id, parent_id, trace_id,
            threading.get_ident(), fields or None))
        self._ring.append(rec)
        return rec

    def snapshot(self) -> List[SpanRecord]:
        """The held records, oldest first."""
        return list(self._ring.copy())

    def since(self, t: float) -> List[SpanRecord]:
        """The newest records, oldest first, back to the first one that
        ended before ``t``: a walk from the ring's end, never a copy of
        it. Another thread's append ends the walk; it is then made
        again."""
        for _ in range(4):
            out = []
            try:
                for rec in reversed(self._ring):
                    if rec.t1 < t:
                        break
                    out.append(rec)
            except RuntimeError:      # the deque changed under the walk
                continue
            return out[::-1]
        return [rec for rec in self._ring.copy() if rec.t1 >= t]

    @property
    def dropped(self) -> int:
        """Records the ring has let go of since it was made or cleared."""
        snap = self._ring.copy()
        return max(r.seq for r in snap) + 1 - len(snap) if snap else 0

    def root_closed(self, root: SpanRecord) -> Optional[dict]:
        """Compare a closed root of ``CALL_ROOTS`` with its like and keep
        it as a reference; returns the slow record if it is one (module
        docstring)."""
        fields = root.fields or {}
        shape = tuple(_sized(fields.get(k)) for k in CALL_ROOTS[root.name])
        inside = self.since(root.t0)
        own = [r for r in inside
               if r.trace_id == root.trace_id and r.t0 >= root.t0]
        stages = self_seconds(own)
        with self._judging:
            like = self._like.get((root.name, shape))
            if like is None:
                while len(self._like) >= REFERENCE_SHAPES:
                    del self._like[next(iter(self._like))]
                like = self._like[root.name, shape] = collections.deque(
                    maxlen=REFERENCE_CALLS)
            slow = None
            if len(like) >= MIN_REFERENCE:
                median = statistics.median(s for s, _ in like)
                if root.t1 - root.t0 > SLOW_FACTOR * median:
                    slow = self._keep_slow(root, inside, own, stages,
                                           like, median)
            like.append((root.t1 - root.t0, stages))
        if slow is not None:     # said outside the lock: both may block
            recorder = get_recorder()
            if getattr(recorder, "enabled", False):
                recorder.event(
                    "span", label="obs/slow_root", depth=0,
                    path="obs/slow_root", t0=round(root.t0, 6),
                    **{k: round(slow[k], 6) if isinstance(slow[k], float)
                       else slow[k] for k in SLOW_FIELDS})
            from fks_tpu.utils import get_logger
            get_logger("fks_tpu.obs.spans").warning("%s", slow_line(slow))
        return slow

    def _keep_slow(self, root: SpanRecord, inside, own, stages, like,
                   median: float) -> dict:
        names = set(stages).union(*(st for _, st in like))
        table = {}
        for name in names:
            ref = statistics.median(st.get(name, 0.0) for _, st in like)
            table[name] = {"seconds": stages.get(name, 0.0), "median": ref,
                           "excess_s": stages.get(name, 0.0) - ref}
        ranked = sorted(table, key=lambda n: -table[n]["excess_s"])
        pauses = [r for r in inside if r.name == "host/gc"
                  and r.t0 >= root.t0 and r.t1 <= root.t1]
        from fks_tpu.obs import telemetry
        rec = {
            "root": root.name, "fields": dict(root.fields or {}),
            "trace_id": root.trace_id, "t0": root.t0,
            "seconds": root.t1 - root.t0, "median": median,
            "like": len(like), "stages": table, "ranked": ranked,
            "grew": ranked[0], "excess_s": table[ranked[0]]["excess_s"],
            "gc_s": float(sum(r.t1 - r.t0 for r in pauses)),
            "gc_pauses": len(pauses),
            # None: no CompileWatcher is installed, nobody counted
            "compiles": telemetry.compiles_between(root.t0, root.t1),
            # the call's own records as ``trace_span`` rows:
            # trace_ctx.build_tree / render_waterfall take them as they are
            "spans": [span_row(r) for r in own],
        }
        self.slow.append(rec)
        self.slow_count += 1
        # over the call's extent, a root of its own: readers find it by
        # time containment and no self time moves
        sid = trace_ctx.new_span_id()
        self.append("obs/slow_root", root.t0, root.t1, sid, None, sid,
                    {k: rec[k] for k in SLOW_FIELDS})
        return rec


def _sized(value):
    """A field as part of a root's shape: a list counts by its length
    (``serve/batch`` lists the request traces it carries)."""
    return len(value) if isinstance(value, (list, tuple)) else value


def self_seconds(records: Sequence[SpanRecord]) -> Dict[str, float]:
    """Per span name, the seconds of ``records`` (one call's: its root and
    what lies under it) that no child span covers. A stage that grew is
    so named once, and not its parents with it; children that ran side by
    side (the lowering workers') each count their own."""
    kids: Dict[Optional[str], list] = {}
    for r in records:
        kids.setdefault(r.parent_id, []).append(r)
    out: Dict[str, float] = {}
    for r in records:
        covered, end = 0.0, r.t0
        for k in sorted(kids.get(r.span_id, ()), key=_T0):
            a, b = max(k.t0, end), min(k.t1, r.t1)
            if b > a:
                covered += b - a
                end = b
        out[r.name] = out.get(r.name, 0.0) + (r.t1 - r.t0) - covered
    return out


_T0 = operator.attrgetter("t0")


def span_row(r: SpanRecord) -> dict:
    """A ring record in the shape of a run directory's ``trace_span`` row
    (``ts`` is the span's end, here on the ring's clock)."""
    return {"trace_id": r.trace_id, "span_id": r.span_id,
            "parent_id": r.parent_id, "path": r.name,
            "seconds": r.t1 - r.t0, "t0": r.t0, "ts": r.t1,
            **(r.fields or {})}


def slow_line(rec: dict) -> str:
    """The one warning line of a slow root: root and shape, seconds
    against the median, the name that grew with its excess, the next two,
    the collector, the compiles."""
    shape = " ".join(f"{k}={_sized(v)}" for k, v in rec["fields"].items())
    first, *rest = rec["ranked"][:3]
    t = rec["stages"]
    line = (f"slow call: {rec['root']} ({shape}) took {rec['seconds']:.3f} s"
            f" against a median of {rec['median']:.3f} s over "
            f"{rec['like']} like calls; grew: {first} "
            f"+{t[first]['excess_s']:.3f} s ({t[first]['median']:.3f} -> "
            f"{t[first]['seconds']:.3f} s)")
    if rest:
        line += ", then " + ", ".join(
            f"{n} {t[n]['excess_s']:+.3f} s" for n in rest)
    line += f"; gc {rec['gc_s']:.3f} s in {rec['gc_pauses']} pauses"
    if rec["compiles"] is not None:
        line += f"; compiles {rec['compiles']}"
    return line


#: the process-wide span log
LOG = SpanLog()
#: its kept slow roots, the newest ``SLOW_KEPT``
SLOW = LOG.slow


def slow_roots() -> List[dict]:
    """The kept slow roots, oldest first (module docstring): each with
    ``stages``, ``grew``, ``gc_s``, ``compiles`` and its ``spans``, which
    ``trace_ctx.render_waterfall`` renders."""
    return list(LOG.slow)


_gc_began = 0.0


def _gc_pause(phase: str, info: dict) -> None:
    """The ``gc.callbacks`` entry: a collection's start and stop on the
    ring's clock, written as one ``host/gc`` root when it is a full
    collection or paused the process for ``GC_MIN_PAUSE_S``. The collector
    does not nest, so one stamp does."""
    global _gc_began
    if phase == "start":
        _gc_began = time.perf_counter()
        return
    t1 = time.perf_counter()
    if info["generation"] == 2 or t1 - _gc_began >= GC_MIN_PAUSE_S:
        sid = trace_ctx.new_span_id()
        LOG.append("host/gc", _gc_began, t1, sid, None, sid,
                   {"generation": info["generation"],
                    "collected": info["collected"]})


# installed with the ring, once a process (a module imported twice under
# two names must not stamp every collection twice)
if not any(getattr(cb, "__name__", "") == "_gc_pause"
           and getattr(cb, "__module__", "") == __name__
           for cb in gc.callbacks):
    gc.callbacks.append(_gc_pause)

_nesting = threading.local()


def _stack() -> list:
    stack = getattr(_nesting, "stack", None)
    if stack is None:
        stack = _nesting.stack = []
    return stack


def span_path() -> str:
    """The current thread's open-span path ("" outside any span)."""
    return "/".join(s.label for s in getattr(_nesting, "stack", ()))


class span:
    """One recorded scope (module docstring). ``with span(name) as t``:
    ``t.sync(value)`` registers a device value the clock waits for at
    exit, ``t.set(**fields)`` adds fields known only inside the body,
    ``t.child(name, t0, t1, **fields)`` writes a child whose stamps were
    taken elsewhere, and after the block ``t.t0``/``t.t1``/``t.seconds``
    and ``t.record`` (the ring's ``SpanRecord``) are valid. Keyword fields
    ride along on the record and on the run directory's event."""

    __slots__ = ("label", "fields", "t0", "t1", "span_id", "parent_id",
                 "trace_id", "record", "_sync", "_recorder", "_ann",
                 "_ctx", "_prev")

    def __init__(self, label: str, sync: Any = None, recorder=None,
                 **fields):
        self.label = label
        self.fields = fields
        self.t0 = self.t1 = 0.0
        self.record: Optional[SpanRecord] = None
        self._sync = sync
        self._recorder = recorder

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    def sync(self, value):
        """Stop the clock only after ``value`` (any pytree of jax arrays)
        is ready on the device. Returns the value for inline use."""
        self._sync = value
        return value

    def set(self, **fields) -> None:
        self.fields.update(fields)

    def __enter__(self) -> "span":
        stack = _stack()
        self.span_id = trace_ctx.new_span_id()
        self._ctx = ctx = trace_ctx.current()
        if ctx is not None:
            # an explicit causal context: chain to it, and make this span
            # the parent of whatever the body opens or hands to a thread
            self.parent_id, self.trace_id = ctx.span_id, ctx.trace_id
            self._prev = trace_ctx.swap(
                trace_ctx.TraceContext(ctx.trace_id, self.span_id))
        elif stack:
            self.parent_id = stack[-1].span_id
            self.trace_id = stack[-1].trace_id
        else:
            self.parent_id, self.trace_id = None, self.span_id
        stack.append(self)
        self._ann = jax.profiler.TraceAnnotation("fks/" + self.label)
        self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        if self._sync is not None:
            jax.block_until_ready(self._sync)
        self.t1 = time.perf_counter()
        self._ann.__exit__(*exc)
        _stack().pop()
        if self._ctx is not None:
            trace_ctx.swap(self._prev)
        self.record = LOG.append(self.label, self.t0, self.t1, self.span_id,
                                 self.parent_id, self.trace_id, self.fields)
        self._event(self.label, self.span_id, self.parent_id, self.t0,
                    self.t1, self.fields)
        if self.label in CALL_ROOTS and exc[0] is None:
            try:
                LOG.root_closed(self.record)
            except Exception:  # noqa: BLE001 — a call keeps its answer
                from fks_tpu.utils import get_logger
                get_logger("fks_tpu.obs.spans").debug(
                    "slow-root check failed", exc_info=True)
        return False

    def child(self, label: str, t0: float, t1: float,
              **fields) -> SpanRecord:
        """One child of this OPEN span that is only known after the fact,
        with explicit stamps on ``time.perf_counter`` (module docstring):
        to the ring and an open run directory like a span that closed
        here. It enters no ``TraceAnnotation``, so the profiler's timeline
        does not hold it."""
        span_id = trace_ctx.new_span_id()
        record = LOG.append(label, t0, t1, span_id, self.span_id,
                            self.trace_id, fields)
        self._event(label, span_id, self.span_id, t0, t1, fields)
        return record

    def _event(self, label: str, span_id: str, parent_id: Optional[str],
               t0: float, t1: float, fields: Dict[str, Any]) -> None:
        """The run directory's copy of a record: the open spans of this
        thread are its path."""
        rec = self._recorder if self._recorder is not None \
            else get_recorder()
        if not getattr(rec, "enabled", False):
            return
        stack = _stack()
        row = dict(label=label, depth=len(stack),
                   path="/".join([s.label for s in stack] + [label]),
                   seconds=round(t1 - t0, 6), t0=round(t0, 6), **fields)
        if self._ctx is not None:
            rec.event("trace_span", trace_id=self.trace_id, span_id=span_id,
                      parent_id=parent_id, **row)
        else:
            rec.event("span", **row)
