"""The measured window and its arithmetic: whole calls only.

A call is the unit of work (one population, one generation, one coalesced
batch of queries) and ends in ``block_until_ready`` or a fetched result.
Calls are made until ``seconds`` have passed; the clock then stops at the
END OF THE LAST WHOLE CALL, so a rate is work in whole calls over the time
those calls took and never depends on where the window's edge fell.
"""
from __future__ import annotations

import time
from typing import Callable, Dict, List, Tuple


def run_window(call: Callable[[int], Dict[str, float]], seconds: float,
               clock=time.perf_counter) -> Tuple[List[dict], float]:
    """``call(i)`` makes whole call ``i`` and returns its work counts.
    Returns (per-call rows with ``t0``/``t1`` relative to the window's
    start, seconds from the first call's start to the last call's end)."""
    rows: List[dict] = []
    start = clock()
    while True:
        t0 = clock()
        if rows and t0 - start >= seconds:
            break
        work = call(len(rows))
        rows.append({"t0": t0 - start, "t1": clock() - start, **work})
    return rows, rows[-1]["t1"]


def rate(rows: List[dict], key: str, elapsed: float) -> float:
    """Work of kind ``key`` in the whole calls, per second of them."""
    return sum(r[key] for r in rows) / elapsed
