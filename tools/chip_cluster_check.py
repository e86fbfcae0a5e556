"""Builder's readings for the real-cluster deployment (``openb1523-
inflated``), on the machine with the chip. Outside the benchmark: nothing
here is a cell, and nothing is measured on another backend (exit 3).

    python tools/chip_cluster_check.py whole --seed N
    python tools/chip_cluster_check.py forked --seed N
    python tools/chip_cluster_check.py midrun --seed N
    python tools/chip_cluster_check.py gpuspec --seed N
    python tools/chip_cluster_check.py dense --seed N [--events 32]

``whole``: ONE whole evaluation (no step cap: fill, pressure, drain, about
14,000 lockstep events) of the cell's 8 sources of one seed through
``CodeEvaluator.evaluate``, built as ``cli evolve`` builds it (default
``SimConfig``, the flat engine; ``fp_dedup`` off so that 8 jittered
sources stay 8 lanes), compared with the plain reference's whole free run
under the same rule, fitness included.

``forked``: ``whole`` from the pinned snapshot of ``openb1523-loaded``:
every lane starts after the snapshot's 5,888 arrivals and runs to the
queue's end (7,500-8,750 further events), compared with
``plain_sim_loaded.simulate_from``; the first fitness a code cell's
configuration compares on the chip.

``midrun``: ``forked`` on upstream's 16 nodes from the pinned moment of
``openb16-cpu250-midrun`` (event 12,288 of cpu250: departed, resident and
waiting pods): every lane runs to the end of the trace (12,370-17,836
further events by the plain reference) or to the 8 x pods cap, compared
as the cell compares
(``plain_sim_midrun.simulate_from``, float32 sources, ``nearties.admit``);
a lane that ends at the cap scores 0 on both sides, so one finished lane
at least is asked for, not all.

``gpuspec``: ``midrun``'s comparison on the production cluster under
GPU-type constraints, from the pinned snapshot of
``openb1523-gpuspec25-loaded`` (event 4,864; 8,900-13,100 further events
by the plain reference), against ``plain_sim_gpuspec.simulate_from``.

``dense``: the same 8 lanes for ``--events`` events under the rule the
program chooses (64) and DENSE (an explicit ``node_prefilter_k`` of the
padded node count, which ``SimConfig.resolve_prefilter_k`` turns into the
sweep of every node), ms per lockstep event each, from the second call's
``tier/vm_batch/launch`` + ``wait_device`` spans.

Prints JSON lines; the last is the summary.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import cells  # noqa: E402
from chipbench.drivers import common  # noqa: E402

CELL = "openb1523-inflated.codegen8"
LOADED = "openb1523-loaded.codegen8"
MIDRUN = "openb16-cpu250-midrun.codegen8"
GPUSPEC = "openb1523-gpuspec25-loaded.codegen8"
#: cells compared as their drivers compare: float32 sources, near ties
#: admitted, one finished lane at least asked for
AS_THE_CELL = (MIDRUN, GPUSPEC)
DEVICE = ("tier/vm_batch/launch", "tier/vm_batch/wait_device")


def say(**row) -> None:
    print(json.dumps(row), flush=True)


def _inputs(seed: int, name: str = CELL):
    """(cell, files, workload, sources, reference run): the workload is
    the driver's own (with the snapshot where the configuration pins one)
    and the reference is ``simulate`` or, forked, ``simulate_from`` on the
    snapshot's rows."""
    cell = cells.load_cell(name)
    files = cells.verify_files(cell.config)
    driver = cells.load_driver(cell.traffic["driver"]).Driver(
        cell, seed, files, None, False)
    if "snapshot" in files:
        import functools

        driver.e0 = int(cell.config["start_event"])
        wl = driver._workload()
        if name == GPUSPEC:
            from chipbench.reference.plain_sim_gpuspec import simulate_from
            reference = functools.partial(
                simulate_from, allowed=driver.allowed(), log=driver.rows())
        elif name == MIDRUN:
            from chipbench.reference.plain_sim_midrun import simulate_from
            reference = functools.partial(simulate_from, log=driver.rows())
        else:
            from chipbench.reference.plain_sim_loaded import simulate_from
            reference = functools.partial(simulate_from, rows=driver.rows())
    else:
        from chipbench.reference.plain_sim import simulate as reference
        wl = common.parse_workload(cell.config, files)
    return cell, files, wl, driver._sources(), reference


def _device_seconds(since: int) -> float:
    from fks_tpu.obs import spans
    return sum(r.t1 - r.t0 for r in spans.LOG.snapshot()
               if r.seq >= since and r.name in DEVICE)


def _next_seq() -> int:
    from fks_tpu.obs import spans
    snap = spans.LOG.snapshot()
    return snap[-1].seq + 1 if snap else 0


def whole(seed: int, name: str = CELL) -> bool:
    from chipbench.reference import policies
    from chipbench.reference.compare import Output, compare
    from fks_tpu.funsearch.backend import CodeEvaluator
    from fks_tpu.sim.engine import SimConfig

    cell, files, wl, sources, simulate = _inputs(seed, name)
    ev = CodeEvaluator(wl, SimConfig(), engine="flat", fp_dedup=False)
    # when each segment's dispatch returned: the double-buffered runner
    # syncs on the segment before, so the gaps are segment lengths
    ticks, count = [], ev._count_segment
    ev._count_segment = lambda: (ticks.append(time.perf_counter()), count())
    seq, t0 = _next_seq(), time.perf_counter()
    recs = ev.evaluate(sources)
    wall = time.perf_counter() - t0
    if len(ticks) > 2:
        say(row="segments", seg_steps=ev.vm_seg_steps,
            ms_per_event=[round((b - a) / ev.vm_seg_steps * 1e3, 4)
                          for a, b in zip(ticks[1:], ticks[2:])])
    stats = ev.last_eval_stats
    start = int(stats.get("start_event", 0))
    events = max(int(r.result.events_processed) for r in recs) - start
    say(row="evaluated", seed=seed, wall_s=wall, start_event=start,
        lockstep_events=events, frag_events=stats.get("frag_events"),
        device_ms_per_event=_device_seconds(seq) / events * 1e3,
        prefilter_k=stats["prefilter_k"], segments=stats["segments"],
        vm_batch_lanes=stats["vm_batch_lanes"],
        fallback_lanes=stats["fallback_lanes"])
    cluster, pods = common.reference_inputs(cell.config, files)
    ok = stats["vm_batch_lanes"] == len(sources)
    kw = dict(retry=cell.config["retry_rule"],
              prefilter_k=int(cell.config["node_prefilter_k"]))
    finished = 0
    for lane, (rec, code) in enumerate(zip(recs, sources)):
        t0 = time.perf_counter()
        got = Output.of_lane(rec.result, pods.p)
        if name in AS_THE_CELL:
            from chipbench.reference.nearties import admit
            policy = policies.source_policy(
                code, dtype=cell.config["guarantees"]["score_dtype"])
            ref, ties = admit(
                lambda decide: simulate(cluster, pods, policy=policy,
                                        decide=decide, **kw),
                got.assigned_node, cell.config["guarantees"], f"lane{lane}")
            numbers = [ties]
        else:
            ref = simulate(cluster, pods,
                           policy=policies.source_policy(code), **kw)
            numbers = []
        numbers += compare(f"lane{lane}", ref, got,
                           cell.config["guarantees"])
        finished += ref.policy_score > 0
        ok &= all(n.ok for n in numbers)
        say(row="lane", lane=lane, fitness=rec.score,
            reference_fitness=ref.policy_score,
            events=int(rec.result.events_processed),
            scheduled=int(rec.result.scheduled_pods),
            frag_events=int(rec.result.num_fragmentation_events),
            reference_s=time.perf_counter() - t0,
            compared={n.name.split(".", 1)[1]: n.value for n in numbers},
            ok=all(n.ok for n in numbers))
    ok &= finished > 0 if name in AS_THE_CELL \
        else finished == len(sources)
    say(row="whole", seed=seed, lanes=len(sources), finished=finished,
        all_equal=bool(ok))
    return bool(ok)


def dense(seed: int, events: int) -> bool:
    import numpy as np
    from fks_tpu.funsearch.backend import CodeEvaluator
    from fks_tpu.sim.engine import SimConfig

    _, _, wl, sources, _ = _inputs(seed)
    n = wl.cluster.n_padded
    out, placed = {}, {}
    for name, k in (("rule", 0), ("dense", n)):
        ev = CodeEvaluator(wl, SimConfig(max_steps=events,
                                         node_prefilter_k=k),
                           engine="flat", fp_dedup=False)
        ev.evaluate(sources)                 # compiles
        seq = _next_seq()
        recs = ev.evaluate(sources)
        out[name] = _device_seconds(seq) / events * 1e3
        placed[name] = np.stack([np.asarray(r.result.assigned_node)
                                 for r in recs])
        say(row="reading", view=name,
            prefilter_k=ev.last_eval_stats["prefilter_k"], events=events,
            device_ms_per_event=out[name])
    say(row="dense", seed=seed, events=events, nodes_padded=n,
        rule_ms_per_event=out["rule"], dense_ms_per_event=out["dense"],
        ratio=out["dense"] / out["rule"],
        placements_differ=int((placed["rule"] != placed["dense"]).sum()))
    return True


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("what", choices=("whole", "forked", "midrun", "gpuspec",
                                     "dense"))
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--events", type=int, default=32)
    a = ap.parse_args(argv)
    import jax
    from fks_tpu.utils import place_compile_cache

    if jax.devices()[0].platform != "tpu":
        print("chip_cluster_check: no TPU", file=sys.stderr)
        return 3
    place_compile_cache()
    ok = (dense(a.seed, a.events) if a.what == "dense"
          else whole(a.seed, {"forked": LOADED, "midrun": MIDRUN,
                              "gpuspec": GPUSPEC}.get(a.what, CELL)))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
