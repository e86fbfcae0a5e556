"""Where in a call the traced slice goes, worked out from calls that were
measured and never from a constant.

A traced run makes the cell's normal window first, so when the slice is
taken the run holds the window's whole calls with the program's spans
(``chipbench.reduce.spans.window_calls``). The cell's driver says where in
ONE call its device stage lies (``Driver.device_stage(call, for_s)``:
absolute ``(t0, t1)`` on the spans' clock, or None when the call shows no
such stage); ``stage_of`` turns the window's calls into offsets from a
call's start, the median of each end. ``place`` puts the slice at
``trace_phase`` of that stage's length, clipped to lie inside it. So a
program whose host stages or device loop got shorter moves the slice with
them: nothing in a traffic file is a wall-clock offset.

A program without the span ring, or a call in which the driver finds no
stage, gives the call's extent (the window's own rows), and ``Stage.source``
says so.
"""
from __future__ import annotations

import statistics
from typing import Callable, List, NamedTuple, Optional, Sequence

from chipbench.reduce.spans import Call

FROM_DRIVER = "driver"            # every call showed the driver's stage
FROM_EXTENT = "call_extent"       # no ring, no selection or no such span


class Stage(NamedTuple):
    d0: float          # seconds from a call's start to the stage's start
    d1: float          # ... to its end
    call_s: float      # a call's length
    source: str        # FROM_DRIVER or FROM_EXTENT


def calls_of_rows(rows: Sequence[dict]) -> List[Call]:
    """The window's rows as calls without spans (their clock starts at the
    window; only offsets inside a call are read from them)."""
    return [Call(r["t0"], r["t1"], []) for r in rows]


def between(call: Call, first: str, last: str) -> Optional[tuple]:
    """From the start of the call's first ``first`` span to the end of its
    last ``last`` span."""
    lo = [r.t0 for r in call.spans if r.name == first]
    hi = [r.t1 for r in call.spans if r.name == last]
    return (min(lo), max(hi)) if lo and hi and max(hi) > min(lo) else None


def stage_of(calls: Sequence[Call], device_stage: Callable,
             for_s: float) -> Stage:
    """The device stage of a typical call of ``calls``: the median offset
    of each end. One call without a stage and the whole answer is the
    call's extent."""
    if not calls:
        raise ValueError("no call to place the traced slice by")
    call_s = statistics.median(c.t1 - c.t0 for c in calls)
    found = [device_stage(c, for_s) for c in calls]
    if any(f is None for f in found):
        return Stage(0.0, call_s, call_s, FROM_EXTENT)
    d0, d1 = (statistics.median(f[end] - c.t0 for f, c in zip(found, calls))
              for end in (0, 1))
    return Stage(d0, d1, call_s, FROM_DRIVER)


def place(stage: Stage, for_s: float, phase: float) -> float:
    """Seconds after a call's start at which a slice of ``for_s`` begins:
    its middle at ``phase`` of the stage, moved so that it ends before the
    stage does and does not begin before it (a stage shorter than the
    slice is covered from its start)."""
    begin = stage.d0 + phase * (stage.d1 - stage.d0) - for_s / 2
    return max(stage.d0, min(begin, stage.d1 - for_s))


def innermost(records, t: float) -> Optional[str]:
    """Name of the shortest span of ``records`` that is open at ``t``."""
    open_ = [r for r in records if r.t0 <= t < r.t1]
    return min(open_, key=lambda r: r.t1 - r.t0).name if open_ else None
