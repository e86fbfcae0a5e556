"""Reduce the program's own span log to per-call numbers.

The program (``fks_tpu.obs.spans``) keeps every span of its host stages in
one bounded in-memory ring, always on: name, ``t0``/``t1`` on
``time.perf_counter`` (the clock of ``chipbench.window`` and of the
drivers), own / parent / trace ids and a few fields. The per-layer readers
of ``chipbench/metrics/`` take the ring's snapshot here, keep the spans of
the window's whole calls and reduce them: unions, sums, self times.

Choosing the window's spans. A whatif call's requests carry the driver's
ids (``c<i>-<j>``: ``i >= 0`` in the window, negative in warm-up and in
the traced slice), so call ``i`` is the extent of its ``serve/request``
roots (first submit to last answer). Generations are the ``tier/evaluate``
roots in order after the one warm-up call. Either way the choice CHECKS
itself: the chosen calls' summed extent must agree with the driver's
``call_seconds`` within ``TOLERANCE`` and the ring must have dropped
nothing since the window began, else there is no selection and every
metric that reads it is missing from the line, which shows.

A program without the ring (a parent older than the spans) gives None
everywhere; nothing here raises for it.
"""
from __future__ import annotations

import re
import statistics
from typing import Dict, Iterable, List, NamedTuple, Optional, Sequence

#: the chosen calls' summed extent against the driver's ``call_seconds``
TOLERANCE = 0.005
#: spans that only group others (``StageProfiler`` stages without a name
#: of their own): a self time looks through them to their children
TRANSPARENT = ("stage/",)
REQUEST_ID = re.compile(r"^c(-?\d+)-\d+$")


class Call(NamedTuple):
    t0: float
    t1: float
    spans: list        # the ring's records that lie inside [t0, t1]


def ring():
    """(records oldest first, dropped) or None without a ring."""
    try:
        from fks_tpu.obs import spans
        return spans.LOG.snapshot(), int(spans.LOG.dropped)
    except (ImportError, AttributeError):
        return None


def union(intervals: Iterable) -> float:
    """Seconds covered by at least one of the ``(t0, t1)`` intervals."""
    total, end = 0.0, None
    for t0, t1 in sorted(intervals):
        if end is None or t0 > end:
            total += t1 - t0
            end = t1
        elif t1 > end:
            total += t1 - end
            end = t1
    return total


def children(records) -> Dict[Optional[str], list]:
    out: Dict[Optional[str], list] = {}
    for r in records:
        out.setdefault(r.parent_id, []).append(r)
    return out


def self_time(root, kids: Dict[Optional[str], list]) -> float:
    """Seconds of ``root`` that none of its children cover (``TRANSPARENT``
    children count as their own children)."""
    covered, todo = [], list(kids.get(root.span_id, ()))
    while todo:
        r = todo.pop()
        if r.name.startswith(TRANSPARENT):
            todo += kids.get(r.span_id, ())
        else:
            covered.append((max(r.t0, root.t0), min(r.t1, root.t1)))
    return (root.t1 - root.t0) - union(c for c in covered if c[1] > c[0])


def _checked(extents: List[tuple], records, dropped: int,
             call_seconds) -> Optional[List[Call]]:
    if not extents or not call_seconds:
        return None
    got = sum(t1 - t0 for t0, t1 in extents)
    if abs(got - float(call_seconds)) > TOLERANCE * float(call_seconds):
        return None
    start = min(t0 for t0, _ in extents)
    # records leave the ring oldest first: one that ended before the window
    # began and is still held proves that nothing of the window has left
    if dropped and not (records and records[0].t1 <= start):
        return None
    return _calls(extents, records)


def _calls(extents, records) -> List[Call]:
    return [Call(t0, t1, [r for r in records if r.t0 >= t0 and r.t1 <= t1])
            for t0, t1 in extents]


def select_whatif(records, dropped: int, calls: int, queries: int,
                  call_seconds) -> Optional[List[Call]]:
    """The window's whatif calls: ``calls`` of them, ``queries`` request
    roots in all, every call index from 0 present."""
    by_call: Dict[int, list] = {}
    for r in records:
        if r.name != "serve/request" or not r.fields:
            continue
        m = REQUEST_ID.match(str(r.fields.get("request", "")))
        if m and int(m.group(1)) >= 0:
            by_call.setdefault(int(m.group(1)), []).append(r)
    if not calls or sorted(by_call) != list(range(int(calls))):
        return None
    if sum(len(v) for v in by_call.values()) != int(queries):
        return None
    extents = [(min(r.t0 for r in v), max(r.t1 for r in v))
               for _, v in sorted(by_call.items())]
    return _checked(extents, records, dropped, call_seconds)


def select_generations(records, dropped: int, calls: int,
                       call_seconds) -> Optional[List[Call]]:
    """The window's generations: the ``calls`` ``tier/evaluate`` roots
    that follow the one warm-up call."""
    roots = sorted((r for r in records if r.name == "tier/evaluate"),
                   key=lambda r: r.t0)
    if not calls or len(roots) < 1 + int(calls):
        return None
    extents = [(r.t0, r.t1) for r in roots[1:1 + int(calls)]]
    return _checked(extents, records, dropped, call_seconds)


def window_calls(ctx: dict) -> Optional[List[Call]]:
    """The selection for this run's driver, made once per run and kept on
    the context: whatif where the driver counts ``queries``, generations
    elsewhere."""
    if "_span_calls" not in ctx:
        got = ring()
        if got is None:
            ctx["_span_calls"] = None
        elif "queries" in ctx:
            ctx["_span_calls"] = select_whatif(
                *got, ctx.get("calls"), ctx.get("queries"),
                ctx.get("call_seconds"))
        else:
            ctx["_span_calls"] = select_generations(
                *got, len(ctx.get("rows", ())), ctx.get("call_seconds"))
    return ctx["_span_calls"]


def calls_between(extents: Sequence[tuple]) -> List[Call]:
    """Calls whose ``(t0, t1)`` the caller stamped itself (the traced
    slice's own calls), each with the ring's records inside it; without a
    ring, without spans."""
    got = ring()
    return _calls(extents, got[0] if got else [])


def named(calls: Sequence[Call], names: Sequence[str]) -> list:
    return [r for c in calls for r in c.spans if r.name in names]


def extent_s(calls: Sequence[Call]) -> float:
    return sum(c.t1 - c.t0 for c in calls)


def union_s(calls: Sequence[Call], names: Sequence[str]) -> float:
    return sum(union((r.t0, r.t1) for r in c.spans if r.name in names)
               for c in calls)


def sum_s(calls: Sequence[Call], names: Sequence[str]) -> float:
    return sum(r.t1 - r.t0 for r in named(calls, names))


def self_s(calls: Sequence[Call], names: Sequence[str]) -> float:
    total = 0.0
    for c in calls:
        kids = children(c.spans)
        total += sum(self_time(r, kids) for r in c.spans if r.name in names)
    return total


def field_sum(calls: Sequence[Call], names: Sequence[str],
              field: str) -> float:
    return float(sum((r.fields or {}).get(field, 0)
                     for r in named(calls, names)))


# ----- what the readers of chipbench/metrics/ call

def calls_with(ctx: dict, names: Sequence[str]) -> Optional[List[Call]]:
    """The window's calls, or None when there is no selection or none of
    the ``names`` spans in it (the metric is then left out)."""
    calls = window_calls(ctx)
    return calls if calls and named(calls, names) else None


def sum_ms_per_call(ctx: dict, *names: str) -> Optional[float]:
    calls = calls_with(ctx, names)
    return calls and sum_s(calls, names) / len(calls) * 1e3


def union_ms_per_call(ctx: dict, *names: str) -> Optional[float]:
    calls = calls_with(ctx, names)
    return calls and union_s(calls, names) / len(calls) * 1e3


def exposed_ms_per_call(ctx: dict, *waits: str) -> Optional[float]:
    """The calls' extent minus the union of the ``waits`` spans inside:
    host time the device did not hide."""
    calls = calls_with(ctx, waits)
    return calls and (extent_s(calls)
                      - union_s(calls, waits)) / len(calls) * 1e3


def kb_per_call(ctx: dict, *names: str) -> Optional[float]:
    calls = calls_with(ctx, names)
    return calls and field_sum(calls, names, "bytes") / len(calls) / 1e3


def unattributed_share(ctx: dict, root: str) -> Optional[float]:
    """Self time of the ``root`` spans over their length, in percent: what
    no child span covers."""
    calls = calls_with(ctx, (root,))
    return calls and 100.0 * self_s(calls, (root,)) / sum_s(calls, (root,))


def request_wait_p50_ms(ctx: dict, *names: str) -> Optional[float]:
    """Median over the window's requests of their ``names`` spans' sum."""
    calls = window_calls(ctx)
    per: Dict[str, float] = {}
    for r in named(calls or (), names):
        per[r.trace_id] = per.get(r.trace_id, 0.0) + (r.t1 - r.t0)
    return statistics.median(per.values()) * 1e3 if per else None
