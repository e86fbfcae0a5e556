"""Readers of what the program's ring holds beside the call sites' spans.

Since PR 40 the ring (``fks_tpu.obs.spans``) is written from three more
places: the transpile stage's children (``tier/transpile/lower``, one a
unique source, on the stamps of the process that lowered it, a worker of
``funsearch/lower_pool.py`` or this one; ``tier/transpile/pack`` around the
uploads), the collector's pauses (``host/gc``, roots found by time
containment) and the ring's own verdict on a call that ran long
(``obs/slow_root``, a root over the slow call's extent). The seven metrics
``tier.lower_ms_per_source``, ``tier.pack_ms_per_call``,
``tier.pool_overhead_ms_per_call``, ``tier.gc_ms_per_call``,
``serve.gc_ms_per_call``, ``tier.slow_call_share`` and
``serve.slow_call_share`` read them here, over the window's calls as
``chipbench/reduce/spans.py`` selects and checks them.

A window without a pause or a slow call reads 0, which is a reading; a
program that has no such mechanism (older than PR 40) reads as nothing,
and the two are told apart by what the program's module defines. Nothing
here raises for a program without it.
"""
from __future__ import annotations

from typing import Optional

from chipbench.reduce import spans

TRANSPILE = ("tier/transpile",)
LOWER = ("tier/transpile/lower",)
PACK = ("tier/transpile/pack",)
GC = ("host/gc",)
SLOW = ("obs/slow_root",)


def program_has(name: str) -> bool:
    """Whether the program's span module defines ``name``."""
    try:
        from fks_tpu.obs import spans as program
    except ImportError:
        return False
    return hasattr(program, name)


def lower_ms_per_source(ctx: dict) -> Optional[float]:
    """Mean length of the window's ``tier/transpile/lower`` spans."""
    calls = spans.calls_with(ctx, LOWER)
    return calls and spans.sum_s(calls, LOWER) \
        / len(spans.named(calls, LOWER)) * 1e3


def pack_ms_per_call(ctx: dict) -> Optional[float]:
    """The ``tier/transpile/pack`` spans' seconds, a call."""
    return spans.sum_ms_per_call(ctx, *PACK)


def pool_overhead_ms_per_call(ctx: dict) -> Optional[float]:
    """``tier/transpile``'s length less the union of its ``lower`` and
    ``pack`` children, a call: dispatch, pickling, the wait for a worker,
    bookkeeping. Nothing where a generation's lower spans were refused
    (``clock_misfit``): the stage would then read as all overhead."""
    calls = spans.calls_with(ctx, LOWER)
    if not calls or spans.field_sum(calls, TRANSPILE, "clock_misfit"):
        return None
    total = 0.0
    for c in calls:
        for stage in (r for r in c.spans if r.name in TRANSPILE):
            total += (stage.t1 - stage.t0) - spans.union(
                (max(r.t0, stage.t0), min(r.t1, stage.t1)) for r in c.spans
                if r.parent_id == stage.span_id and r.name in LOWER + PACK)
    return total / len(calls) * 1e3


def gc_ms_per_call(ctx: dict, root: str) -> Optional[float]:
    """Seconds the collector paused the process inside the window's calls
    (those that hold a ``root`` span), a call."""
    calls = spans.calls_with(ctx, (root,))
    if not calls or not program_has("GC_MIN_PAUSE_S"):
        return None
    return spans.union_s(calls, GC) / len(calls) * 1e3


def slow_call_share(ctx: dict, root: str) -> Optional[float]:
    """The window's calls the program's ring found slow (``obs/slow_root``
    records of this ``root`` inside them), in percent of its calls. The
    ring judges a call against the median of the up to 32 like calls before
    it, once it knows 8."""
    calls = spans.calls_with(ctx, (root,))
    if not calls or not program_has("slow_roots"):
        return None
    slow = [r for r in spans.named(calls, SLOW)
            if (r.fields or {}).get("root") == root]
    return 100.0 * len(slow) / len(calls)
