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
  Spans only known after the fact (a request's queue wait) are written
  to the same ring with explicit stamps by ``trace_ctx.emit``.
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
enqueue,wait_device,d2h,extract}``, ``tier/evaluate``, ``tier/preflight``,
``tier/transpile``, ``tier/vm_batch/{stack_programs,launch,wait_device,
d2h}``, ``tier/record``, ``tier/fallback``, ``mesh/shard_put``,
``mesh/segment`` (+ ``/wait``), ``mesh/finish``. A ``wait_device`` span
holds nothing but the blocking call, so their union is "the host waited
for the chip".
"""
from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import Any, Dict, List, NamedTuple, Optional

import jax

from fks_tpu.obs import trace_ctx
from fks_tpu.obs.recorder import get_recorder

#: records the ring holds before it drops the oldest (module docstring)
CAPACITY = 65536


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
    the oldest record when full."""

    def __init__(self, capacity: int = CAPACITY):
        self.capacity = int(capacity)
        self.clear()

    def clear(self) -> None:
        self._ring: "collections.deque[SpanRecord]" = collections.deque(
            maxlen=self.capacity)
        self._seq = itertools.count()

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

    @property
    def dropped(self) -> int:
        """Records the ring has let go of since it was made or cleared."""
        snap = self._ring.copy()
        return max(r.seq for r in snap) + 1 - len(snap) if snap else 0


#: the process-wide span log
LOG = SpanLog()

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
    and after the block ``t.t0``/``t.t1``/``t.seconds`` and ``t.record``
    (the ring's ``SpanRecord``) are valid. Keyword fields ride along on
    the record and on the run directory's event."""

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
        stack = _stack()
        stack.pop()
        if self._ctx is not None:
            trace_ctx.swap(self._prev)
        self.record = LOG.append(self.label, self.t0, self.t1, self.span_id,
                                 self.parent_id, self.trace_id, self.fields)
        rec = self._recorder if self._recorder is not None \
            else get_recorder()
        if getattr(rec, "enabled", False):
            row = dict(label=self.label, depth=len(stack),
                       path="/".join([s.label for s in stack]
                                     + [self.label]),
                       seconds=round(self.t1 - self.t0, 6),
                       t0=round(self.t0, 6), **self.fields)
            if self._ctx is not None:
                rec.event("trace_span", trace_id=self.trace_id,
                          span_id=self.span_id, parent_id=self.parent_id,
                          **row)
            else:
                rec.event("span", **row)
        return False
