"""The seven readers of ``chipbench/reduce/hostspans.py`` on the CPU:

    python3 -m chipbench.selftest.hostspans

At tiny sizes, on four virtual CPU devices, through everything of
``chipbench.run.run_cell`` but the look for a chip: the traced line of two
code cells (one chip and four) and of both whatif cells holds every one of
the metrics its cell lists (PR 40: the transpile stage's children, the
collector's pauses, the ring's own verdict on a slow call); and a
generation that is made to wait inside one stage (a ``time.sleep`` put
there by this file, not by a switch in the program) is counted by
``tier.slow_call_share``, kept by the program's ring and named with that
stage. It prints pass/fail and NO number under the name of a device
metric. ``tests/test_chipbench_hostspans.py`` runs it in tier-1, one case
a test.
"""
from __future__ import annotations

import os
import sys
import time
import traceback
from unittest import mock

if __name__ == "__main__":     # a process of its own picks its platform
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=4")

CODE = ("openb16.codegen8", "openb16.codegen8x4")
TIER = ("tier.lower_ms_per_source", "tier.pack_ms_per_call",
        "tier.pool_overhead_ms_per_call", "tier.gc_ms_per_call",
        "tier.slow_call_share")
SERVE = ("serve.gc_ms_per_call", "serve.slow_call_share")


def _traced(name: str, seconds: float = 0.5) -> dict:
    """The cell's traced result at the selftest's tiny size, from a ring
    that starts empty as a fresh process's does."""
    from chipbench.reduce import spans as rs
    from chipbench.selftest import tests, whatif_loaded
    from fks_tpu.obs import spans

    spans.LOG.clear()
    # the reducer holds the spans' extent to the driver's clock within
    # 0.5 %: of a 1.6 s call on the chip, not of a 50 ms call here
    with mock.patch.object(rs, "TOLERANCE", 0.05):
        if name == whatif_loaded.CELL:
            return whatif_loaded.run_tiny(trace=True, seconds=seconds)[0]
        return tests._run(name, trace=True, seconds=seconds)


def _holds(name: str, want) -> None:
    from chipbench import cells

    res = _traced(name)
    assert res["correct"] is True and res["failed"] == 0, res
    listed = {m["name"] for m in cells.load_cell(name).per_layer}
    for m in want:
        assert m in listed, (name, m)
        assert m in res["metrics"], (name, m, sorted(res["metrics"]))
        assert res["metrics"][m]["value"] >= 0, (name, m)
    # a 50 ms call on a loaded CPU is easily a quarter longer than its
    # like: the share is a share, and 0 only on a quiet machine
    assert res["metrics"][want[-1]]["value"] <= 100.0


def test_code_cells_report_the_five_tier_metrics():
    for name in CODE:
        _holds(name, TIER)


def test_whatif_cells_report_the_two_serve_metrics():
    from chipbench.selftest import whatif_loaded

    for name in ("openb1523.whatif8", whatif_loaded.CELL):
        _holds(name, SERVE)


#: the generation of the process that is made to wait (the warm-up is the
#: first; the ring judges from its ninth on), and for how long
SLOW_GENERATION = 11
SLEEP_S = 1.5


def test_a_stage_made_to_wait_is_counted_kept_and_named():
    """``lower_pool.lower_all`` runs inside ``tier/transpile`` and outside
    its children: a sleep there is the stage's own time."""
    from fks_tpu.funsearch import lower_pool
    from fks_tpu.obs import spans

    real, seen = lower_pool.lower_all, []

    def waits_once(codes, n, g):
        seen.append(len(codes))
        if len(seen) == SLOW_GENERATION:
            time.sleep(SLEEP_S)
        return real(codes, n, g)

    for seconds in (2.0, 8.0, 30.0):   # until the window holds that call
        del seen[:]
        with mock.patch.object(lower_pool, "lower_all", waits_once):
            res = _traced("openb16.codegen8", seconds)
        if res["attempted"] >= SLOW_GENERATION * 4:
            break
    assert res["correct"] is True and res["failed"] == 0, res
    assert res["metrics"]["tier.slow_call_share"]["value"] > 0, res
    slow = [r for r in spans.slow_roots() if r["grew"] == "tier/transpile"]
    assert slow, spans.slow_roots()
    rec = slow[0]
    assert rec["root"] == "tier/evaluate" and rec["fields"]["candidates"] == 4
    assert rec["excess_s"] > 0.8 * SLEEP_S
    assert rec["seconds"] > spans.SLOW_FACTOR * rec["median"]
    assert any(r["path"] == "tier/transpile" for r in rec["spans"])
    assert [r for r in spans.LOG.snapshot() if r.name == "obs/slow_root"
            and r.fields["grew"] == "tier/transpile"]


def main() -> int:
    names = [n for n in sorted(globals()) if n.startswith("test_")]
    want, bad = sys.argv[1:], 0
    for n in names:
        if want and not any(w in n for w in want):
            continue
        try:
            globals()[n]()
            print(f"PASS {n}", flush=True)
        except Exception:  # noqa: BLE001 — report and go on
            bad += 1
            traceback.print_exc()
            print(f"FAIL {n}", flush=True)
    print(f"{'FAILED' if bad else 'ok'}: {bad} failed")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
