"""Timing one-liners and throughput counters.

The reference's only instrumentation is ad-hoc ``time.time()`` deltas around
runs (reference: tests/test_scheduler.py:266-269, test_integration.py:130-137,
funsearch/funsearch_integration.py:586-589) — no profiler hooks at all
(SURVEY.md §5). Scoped timing lives in ONE place, ``fks_tpu.obs.span`` (it
blocks on a registered device value before stopping the clock, records to
the in-memory span ring and lands on any profiler session's timeline); what
stays here is ``block_timed``, the call-and-materialize one-liner, and the
``ThroughputMeter``.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import List, Optional

import jax


def block_timed(fn, *args, **kwargs):
    """Call ``fn`` and return (result, seconds) with the result fully
    materialized.

    The result must be a pytree of jax arrays (or plain scalars):
    ``jax.block_until_ready`` treats unregistered custom objects as opaque
    leaves and silently skips them, so wrapping a function that hides its
    arrays inside plain dataclasses would time only the enqueue."""
    t0 = time.perf_counter()
    result = fn(*args, **kwargs)
    jax.block_until_ready(result)
    return result, time.perf_counter() - t0


@dataclass
class ThroughputMeter:
    """Accumulate (count, seconds) batches; report rates.

    ``cli scale`` feeds it timed repetitions. ``rate`` is total
    count over total seconds (not a mean of rates, which would overweight
    small batches).
    """

    counts: List[float] = field(default_factory=list)
    seconds: List[float] = field(default_factory=list)

    def add(self, count: float, seconds: float) -> None:
        self.counts.append(float(count))
        self.seconds.append(float(seconds))

    @property
    def total_count(self) -> float:
        return sum(self.counts)

    @property
    def total_seconds(self) -> float:
        return sum(self.seconds)

    @property
    def rate(self) -> Optional[float]:
        """Items per second over everything recorded; None if no time."""
        if self.total_seconds <= 0:
            return None
        return self.total_count / self.total_seconds

    def summary(self) -> str:
        r = self.rate
        return (f"{self.total_count:.0f} in {self.total_seconds:.2f}s"
                + (f" = {r:.1f}/s" if r is not None else ""))
