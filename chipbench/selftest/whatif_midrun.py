"""The cell ``openb16-cpu250-midrun.whatif8`` at a tiny size on the CPU,
and the controls of its ``correct``:

    python3 -m chipbench.selftest.whatif_midrun
    python3 -m chipbench.selftest.whatif_midrun --control --cpu-tiny --seeds 5
    python3 -m chipbench.selftest.whatif_midrun --control --seeds 1,2

The tiny size is ``selftest/midrun.py``'s deployment (a moment of a run
has no prefix by pod count): six nodes of the 16 under the first 500
arrivals of cpu250, forked after 320 events of ``first_fit``'s run (139
departures, 9 refused placements, 33 residents, 1 pod waiting with its
retry queued; 173 pods in the base, 327 not arrived). A call is four
queries of ``SIZES`` pods in two chunks of two lanes (buckets 16 and 64,
budgets 64 and 128 events from the fork); by the plain reference the
4-pod and the 24-pod query FINISH inside their budgets for every offset
(49-56 and 89-105 events) and the 16-pod and the 64-pod one are cut (at
least 71 and 169), so a call is ``EVENTS`` lockstep events and two
finished lanes for every seed. ``run_tiny`` drives everything of
``chipbench.run.run_cell`` but the look for a chip;
``tests/test_chipbench_whatif_midrun.py`` runs it in tier-1.

``--control`` makes, per seed, one whole call of the cell's driver and
prints the sound run's compared numbers; then the cell's comparison has to
say "not correct" four times: of the plain reference in bfloat16 scores
(``scores``) and in bfloat16 scores and evaluator sums (``scores+sums``)
standing in for each answer (the VM engine has no lower-precision
switch), and of THE PROGRAM handed a fork that lost what only the general
fork builds: the waiting pod dropped (``waiting_lost``: its queued retry
taken out of the heap, its flag and its entry in the waiting histogram
cleared, so it never comes back: a lane that finishes schedules one pod
fewer in fewer events) and the prefix's failed placements forgotten
(``frag_lost``: ``frag_count`` and ``frag_sum`` 0). Clearing the waiting
pod's ``COL_WAIT`` alone is NOT such a control: the pod's next retry
fails again and sets the flag anew, and no compared number moves (tried
at the tiny size, PR 52). Without ``--cpu-tiny`` it wants the
chip and runs at the cell's own size (the builder's chip runs). Nothing
here prints a number under the name of a device metric.
"""
from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import sys
from unittest import mock

CELL = "openb16-cpu250-midrun.whatif8"
SIZES = [4, 16, 24, 64]
#: lockstep events of a tiny call: budgets of the buckets 16 and 64
EVENTS = 64 + 128
#: what the tiny prefix holds (``chipbench/selftest/midrun.py``)
FORK_COUNTS = {"events": 320, "arrived": 173, "departed": 139,
               "residents": 33, "waiting": 1, "refused": 9,
               "not_arrived": 327}
RUNS = ("scores", "scores+sums", "waiting_lost", "frag_lost")


def tiny_deployment(directory: str) -> dict:
    """``selftest/midrun.py``'s files under ``directory`` and the
    ``overrides`` of ``cells.load_cell`` that run THIS cell on them."""
    from chipbench.selftest import midrun

    config = midrun.tiny_deployment(directory)["config"]
    config.pop("code_eval_max_steps")
    config["fork_counts"] = dict(FORK_COUNTS)
    return {"config": config,
            "traffic": {"sizes": SIZES, "s_range": {}, "max_batch": 4,
                        "max_wait_s": 2.0, "trace_for_s": 0.05}}


def run_tiny(directory: str, seed: int = 2 ** 31 + 7, trace: bool = False,
             seconds: float = 0.5, overrides=None):
    """(result line, the rows printed before it)."""
    from chipbench import run

    overrides = overrides or tiny_deployment(directory)
    with contextlib.redirect_stdout(io.StringIO()) as out:
        res = run.run_cell(CELL, seed, seconds, trace, require_tpu=False,
                           overrides=overrides)
    return res, [json.loads(line) for line in out.getvalue().splitlines()]


def control_answers(d, sums: bool) -> None:
    """Replace the driver's last answers by the forked reference's in
    bfloat16 scores (and, with ``sums``, bfloat16 evaluator sums)."""
    import ml_dtypes
    import numpy as np

    from chipbench.reference import forked_query_midrun as fq
    from chipbench.reference import policies

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
            truncated=low.truncated, finished=fq.finished(low),
            frag_events=low.num_frag_events, snapshots=low.num_snapshots,
            max_nodes=low.max_nodes,
            utilization=[float(x) for x in low.avg_util],
            fragmentation=float(low.frag_mean), waiting=waiting)
        a["placements"] = [
            {"pod": i, "node": int(nd),
             "gpus": [b for b in range(16) if int(g) >> b & 1]}
            for i, (nd, g) in enumerate(zip(low.assigned_node[d.base:],
                                            low.assigned_gpus[d.base:]))]


@contextlib.contextmanager
def fork_lost(what: str):
    """The program's ``forked_state`` with the part of the carry that only
    the general fork builds taken out again (module docstring)."""
    import heapq

    from fks_tpu.sim import engine
    from fks_tpu.sim.types import SimState

    real = engine.forked_state

    def damaged(workload, cfg, prefix, ktable=None):
        s = real(workload, cfg, prefix, ktable)
        if what == "frag_lost":
            return s._replace(frag_count=s.frag_count * 0,
                              frag_sum=s.frag_sum * 0)
        # a base pod's pending CREATE is a queued retry: out of the heap
        # (a valid heap of the rest), and the pod waits no more
        base, size = len(prefix.node), int(s.heap.size)
        items = [tuple(r) for r in s.heap.data[:size].tolist()
                 if not (r[2] == 0 and r[3] < base)]
        heapq.heapify(items)
        data = s.heap.data * 0
        data[:len(items)] = items
        pod_state = s.pod_state.copy()
        pod_state[:, SimState.COL_WAIT] = 0
        return s._replace(
            heap=s.heap._replace(data=data,
                                 size=s.heap.size * 0 + len(items)),
            pod_state=pod_state, wait_hist=s.wait_hist * 0)

    with mock.patch.object(engine, "forked_state", damaged):
        yield


def control(seeds, overrides=None) -> int:
    """Module docstring, ``--control``. The last line is
    ``{"sums_fail_everywhere", "scores_fail_identity_everywhere",
    "fork_lost_fails_everywhere", "sound_ok"}`` ("everywhere": every
    seed). bfloat16 SCORES alone have to move placements at the cell's
    own size (16 nodes under pressure, unequal candidates); on the tiny
    deployment's six nodes they move none, so ``--cpu-tiny`` reports that
    line and does not ask for it."""
    from chipbench import cells
    from chipbench.selftest.control_cluster import _largest
    from chipbench.selftest.control_whatif_loaded import _report

    sound, failed, scores_identity, lost = [], [], [], []
    for seed in seeds:
        cell = cells.load_cell(CELL, overrides)
        files = cells.verify_files(cell.config)
        d = cells.load_driver(cell.traffic["driver"]).Driver(
            cell, seed, files, None, False)
        try:
            d.setup()
            d.call(0)
            numbers = d.check()
            sound.append(all(n.ok for n in numbers) and d.failed == 0)
            print(json.dumps({
                "seed": seed, "run": "sound", "checked_items": len(d.sizes),
                "failed_operations": d.failed,
                "finished": [len(q[1]) for q, a in zip(*d.last)
                             if a["finished"]],
                "largest": _largest(numbers)}), flush=True)
            kept = d.last
            for sums in (False, True):
                d.last = (kept[0], [dict(a) for a in kept[1]])
                control_answers(d, sums)
                got = _report(seed, RUNS[sums], d.check())
                print(json.dumps(got), flush=True)
                if sums:
                    failed.append(got["queries_failing"] > 0)
                else:
                    scores_identity.append(
                        got["queries_failing_identity"] > 0)
            # the program itself on a fork that lost the general part
            for what in RUNS[2:]:
                with fork_lost(what):
                    d.call(1)
                got = _report(seed, what, d.check())
                print(json.dumps(got), flush=True)
                lost.append(got["queries_failing"] > 0)
        finally:
            d.close()
    verdict = {"sums_fail_everywhere": all(failed),
               "scores_fail_identity_everywhere": all(scores_identity),
               "fork_lost_fails_everywhere": all(lost),
               "sound_ok": all(sound)}
    print(json.dumps(verdict), flush=True)
    asked = [v for k, v in verdict.items()
             if overrides is None or k != "scores_fail_identity_everywhere"]
    return 0 if all(asked) else 1


def main(argv=None) -> int:
    import tempfile

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--cpu-tiny", action="store_true",
                    help="selftest sizes on the CPU (no chip needed)")
    ap.add_argument("--seeds", default="1")
    a = ap.parse_args(argv)
    seeds = [int(s) for s in a.seeds.split(",")]
    if a.control and not a.cpu_tiny:
        import jax
        if jax.devices()[0].platform != "tpu":
            print("control: no TPU", file=sys.stderr)
            return 3
        return control(seeds)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    with tempfile.TemporaryDirectory() as d:
        overrides = tiny_deployment(d)
        if a.control:
            return control(seeds, overrides)
        bad = 0
        for trace in (False, True):
            res, rows = run_tiny(d, trace=trace, overrides=overrides)
            calls = [r for r in rows if r["row"] == "call"]
            ok = (res["correct"] is True and res["failed"] == 0
                  and res["device"]["platform"] == "cpu" and calls
                  and all(r["lockstep_events"] == EVENTS
                          and r["finished_lanes"] == 2 for r in calls))
            bad += not ok
            print(f"{'PASS' if ok else 'FAIL'} whatif_midrun "
                  f"trace={int(trace)}", flush=True)
    print(f"{'FAILED' if bad else 'ok'}: {bad} failed")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
