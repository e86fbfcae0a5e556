"""The control of ``correct`` for the ``whatif_loaded`` driver, in
``control_loaded``'s way:

    python3 -m chipbench.selftest.control_whatif_loaded --seeds 1,2,3

For each seed it builds the cell's driver, makes one whole call and prints
the sound run's compared numbers; then the plain reference forked from the
same snapshot with bfloat16 SCORES alone (``scores``) and with bfloat16
scores and bfloat16 evaluator sums (``scores+sums``) stands in for each
answer (the VM engine has no lower-precision switch), and the same
comparison has to say "not correct". On the empty snapshot of
``openb1523.whatif8`` the deciding comparison is among identical nodes, so
bfloat16 scores pass identity there and only the sums fail; from the
loaded cluster the candidates are unequal, and the scores alone have to
move placements. It prints how many queries fail under each and by how
many placements. The last line is ``{"control_failed_everywhere": ...,
"scores_fail_identity_everywhere": ..., "sound_ok": ...}`` ("everywhere":
every seed). The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys

import ml_dtypes
import numpy as np

from chipbench import cells
from chipbench.reference import policies
from chipbench.selftest.control_cluster import _largest

CELL = "openb1523-loaded.whatif8"
IDENTITY = ("placements_differ", "gpu_picks_differ", "scheduled_diff",
            "events_diff", "flags_differ", "waiting_differ")


def control_answers(d, sums: bool) -> None:
    """Replace the driver's last answers by the forked reference's in
    bfloat16 scores (and, with ``sums``, bfloat16 evaluator sums)."""
    queries, answers = d.last
    policy = policies.source_policy(
        d.champion.code, True,
        dtype=d.cell.config["guarantees"]["score_dtype"])
    acc = ml_dtypes.bfloat16 if sums else np.float32
    for (s, rows), a in zip(queries, answers):
        low, waiting = d.simulate(s, len(rows), policy, acc_dtype=acc)
        a.update(
            score=low.policy_score, scheduled=low.scheduled_pods,
            events=low.events_processed, failed=low.failed,
            truncated=low.truncated, frag_events=low.num_frag_events,
            snapshots=low.num_snapshots, max_nodes=low.max_nodes,
            utilization=[float(x) for x in low.avg_util],
            fragmentation=float(low.frag_mean), waiting=waiting)
        a["placements"] = [
            {"pod": i, "node": int(nd),
             "gpus": [b for b in range(16) if int(g) >> b & 1]}
            for i, (nd, g) in enumerate(zip(low.assigned_node[d.e0:],
                                            low.assigned_gpus[d.e0:]))]


def _report(seed: int, run: str, numbers: list) -> dict:
    by_query: dict = {}
    for n in numbers:
        by_query.setdefault(n.name.split(".", 1)[0], []).append(n)
    failing = [q for q, ns in by_query.items()
               if not all(n.ok for n in ns)]
    identity = [q for q, ns in by_query.items() if any(
        not n.ok and n.name.split(".", 1)[1] in IDENTITY for n in ns)]
    return {"seed": seed, "run": run, "queries": len(by_query),
            "queries_failing": len(failing),
            "queries_failing_identity": len(identity),
            "placements_moved": [int(n.value) for n in numbers
                                 if n.name.endswith("placements_differ")],
            "largest": _largest(numbers),
            "failed_numbers": sorted({n.name.split(".", 1)[1]
                                      for n in numbers if not n.ok})}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", default=CELL)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--cpu-tiny", action="store_true",
                    help="selftest sizes on the CPU (no chip needed)")
    a = ap.parse_args(argv)
    overrides = None
    if a.cpu_tiny:
        from chipbench.selftest.whatif_loaded import TINY
        overrides = TINY
    else:
        import jax
        if jax.devices()[0].platform != "tpu":
            print("control: no TPU", file=sys.stderr)
            return 3
    sound_ok, control_failed, scores_identity = [], [], []
    for seed in (int(s) for s in a.seeds.split(",")):
        cell = cells.load_cell(a.workload, overrides)
        files = cells.verify_files(cell.config)
        d = cells.load_driver(cell.traffic["driver"]).Driver(
            cell, seed, files, None, False)
        try:
            d.setup()
            d.call(0)
            numbers = d.check()
            sound_ok.append(all(n.ok for n in numbers)
                            and d.failed == 0)
            print(json.dumps({"seed": seed, "run": "sound",
                              "checked_items": len(d.sizes),
                              "failed_operations": d.failed,
                              "largest": _largest(numbers)}), flush=True)
            for sums in (False, True):
                control_answers(d, sums)
                got = _report(seed, "scores+sums" if sums else "scores",
                              d.check())
                print(json.dumps(got), flush=True)
                control_failed.append(got["queries_failing"] > 0)
                if not sums:
                    scores_identity.append(
                        got["queries_failing_identity"] > 0)
        finally:
            d.close()
    print(json.dumps({
        "control_failed_everywhere": all(control_failed),
        "scores_fail_identity_everywhere": all(scores_identity),
        "sound_ok": all(sound_ok)}), flush=True)
    return 0 if all(control_failed) and all(scores_identity) \
        and all(sound_ok) else 1


if __name__ == "__main__":
    sys.exit(main())
