"""The cell ``openb1523-loaded.whatif8`` at a tiny size on the CPU:

    python3 -m chipbench.selftest.whatif_loaded

``run_tiny`` drives everything of ``chipbench.run.run_cell`` but the look
for a chip: the real 1,523-node cluster under the first 264 arrivals,
forked after 200 of them, four queries of 4-64 pods a call (the 64 are the
whole backlog), two chunks of two lanes, 64 + 128 lockstep events from the
fork. ``tests/test_chipbench_whatif_loaded.py`` runs it in tier-1, so the
driver's ``check`` runs against the plain reference on every change. It
prints no number under the name of a device metric.
"""
from __future__ import annotations

import contextlib
import io
import json
import os
import sys

CELL = "openb1523-loaded.whatif8"
#: the first 264 arrivals, forked after 200: the backlog is 64 pods
TINY = {"config": {"pod_limit": 264, "start_event": 200},
        "traffic": {"sizes": [4, 12, 40, 64], "max_batch": 4,
                    "max_wait_s": 2.0, "trace_for_s": 0.05}}
#: lockstep events of a tiny call: budgets of the buckets 16 and 64
EVENTS = 64 + 128


def run_tiny(seed: int = 2 ** 31 + 7, trace: bool = False,
             seconds: float = 0.5):
    """(result line, the rows printed before it)."""
    from chipbench import run

    with contextlib.redirect_stdout(io.StringIO()) as out:
        res = run.run_cell(CELL, seed, seconds, trace, require_tpu=False,
                           overrides=TINY)
    return res, [json.loads(line) for line in out.getvalue().splitlines()]


def main() -> int:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    bad = 0
    for trace in (False, True):
        res, rows = run_tiny(trace=trace)
        calls = [r for r in rows if r["row"] == "call"]
        ok = (res["correct"] is True and res["failed"] == 0
              and res["device"]["platform"] == "cpu" and calls
              and all(r["lockstep_events"] == EVENTS for r in calls))
        bad += not ok
        print(f"{'PASS' if ok else 'FAIL'} whatif_loaded trace={int(trace)}",
              flush=True)
    print(f"{'FAILED' if bad else 'ok'}: {bad} failed")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
