"""Driver ``codegen_loaded``: ``codegen_cluster``'s generation forked from
a pinned snapshot of the loaded cluster.

The workload is parsed with the configuration's ``snapshot`` file
(``TraceParser.parse_workload(..., snapshot_file=...)``), so the
evaluator, built exactly as ``codegen`` builds it, starts every lane after
the snapshot's ``start_event`` arrivals with no further option: the fork
is data on the workload. Three things differ from ``codegen_cluster``.
The step cap is absolute in the program, so the driver passes
``start_event + code_eval_max_steps`` and counts a lane's work from the
fork: lane-events and lockstep events of a call are ``events_processed -
start_event`` (the per-event metrics divide by the window's events, not by
the prefix the snapshot decided). Before the warm-up call, the run's first
device program, it ends the run unless the program took the fork (a
program without the snapshot path, as the parent commit is, never gets
that far: it has no snapshot file to verify). And the comparison is with
``plain_sim_loaded.simulate_from``, which is handed the snapshot's rows
from its own parse of the file and runs free after the fork.

A lane that does not run exactly to the cap is a failed operation: no
lane can finish inside the window (6,400 and more deletes stay queued).
So ``compare``'s fitness number is never emitted here, and
``compare_whole`` adds what a cut run still says of the evaluator the
fork built: snapshot count, peak of active nodes and failed placements
(exact), and the fitness the run would report if it ended at the cap
(the four utilization averages less the fragmentation penalty, without
the gate on unplaced pods), within the configuration's ``fitness_rtol``.
The driver's ``frag_events`` counter, which ``sim.retry_share`` divides,
is held to the reference's count over the same call.

``pod_limit`` (selftest only) keeps the first pods, and ``start_event``
then keeps the first rows of the snapshot: placements only take, so every
prefix of a valid snapshot is one.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from chipbench.drivers import codegen, codegen_cluster, common
from chipbench.reference.compare import Number, Output, compare
from chipbench.reference.nearties import admit

F = np.float32


def _evaluator_of(res, lane=None) -> tuple:
    """(snapshots, failed placements, peak active nodes, fitness at the
    cap) of a lane, from the program's ``SimResult`` or the reference's
    ``Result``."""
    def pick(x):
        return np.asarray(x) if lane is None else np.asarray(x)[lane]

    if hasattr(res, "avg_util"):                   # the reference's
        avg, frag_mean = res.avg_util, res.frag_mean
        frag = res.num_frag_events
    else:
        avg = [pick(x) for x in (
            res.avg_cpu_utilization, res.avg_memory_utilization,
            res.avg_gpu_count_utilization, res.avg_gpu_memory_utilization)]
        frag_mean = pick(res.gpu_fragmentation_score)
        frag = pick(res.num_fragmentation_events)
    avg = [F(a) for a in avg]
    overall = F(F(F(F(avg[0] + avg[1]) + avg[2]) + avg[3]) / F(4))
    raw = F(min(max(overall - min(F(0.1), F(frag_mean)), F(0)), F(1)))
    return (int(pick(res.num_snapshots)), int(frag),
            int(pick(res.max_nodes)), float(raw))


def compare_whole(tag: str, ref, res, pods: int, guarantees: dict,
                  lane=None) -> list:
    """``compare`` plus the evaluator's state at the cap (module
    docstring). ``res``: a ``SimResult`` or a reference ``Result``."""
    out = compare(tag, ref, Output.of_lane(res, pods, lane), guarantees)
    want, got = _evaluator_of(ref), _evaluator_of(res, lane)
    out += [Number(f"{tag}.{name}_diff", float(abs(w - g)), 0.0)
            for name, w, g in zip(("snapshots", "frag_events", "max_nodes"),
                                  want, got)]
    out.append(Number(f"{tag}.fitness_at_cap_rel_err",
                      abs(got[3] - want[3]) / max(want[3], 1e-30),
                      float(guarantees["fitness_rtol"])))
    return out


class Driver(codegen_cluster.Driver):
    def _workload(self):
        """The program's own parse, snapshot included."""
        try:
            from fks_tpu.data import snapshot
        except ImportError:
            raise SystemExit(
                f"chipbench: {self.cell.name} needs a program that can "
                "start from a snapshot (fks_tpu.data.snapshot); this one "
                "cannot, so nothing is run") from None
        wl = common.parse_workload(self.cell.config, self.files)
        full = snapshot.load_snapshot(
            self.files["snapshot"].removesuffix(".gz"),
            wl if not self.cell.config.get("pod_limit")
            else common.parse_workload(
                {**self.cell.config, "pod_limit": None}, self.files))
        return dataclasses.replace(
            wl, snapshot=snapshot.head(full, self.e0))

    def setup(self) -> dict:
        from fks_tpu.funsearch.backend import CodeEvaluator
        from fks_tpu.sim.engine import SimConfig

        t0 = common.now()
        self.e0 = int(self.cell.config["start_event"])
        self.wl = self._workload()
        t1 = common.now()
        self.sources = self._sources()
        # absolute, as SimConfig.max_steps is: the prefix counts
        self.k = self.e0 + int(self.cell.config["code_eval_max_steps"])
        self.profiler = None
        if self.traced:   # fences: per-layer runs only
            from fks_tpu import obs
            self.profiler = obs.StageProfiler(enabled=True, scope="bench")
        self.ev = CodeEvaluator(
            self.wl, cfg=SimConfig(max_steps=self.k),
            engine=self.cell.config["engine"], fp_dedup=False,
            mesh=self.mesh, profiler=self.profiler)
        t2 = common.now()
        self.call(-1)                      # warm-up: compiles, not counted
        self._reset()
        return {"parse_s": t1 - t0, "build_s": t2 - t1,
                "warm_call_s": common.now() - t2,
                "lanes": len(self.sources), "max_steps": self.k,
                "start_event": self.e0,
                "vm_seg_steps": self.ev.vm_seg_steps,
                "vm_batch": bool(self.ev.vm_batch),
                "program_capacity": self.ev.VM_CAPACITY,
                "shards": 1 if self.mesh is None else self.mesh.size,
                "nodes_padded": int(self.wl.cluster.n_padded),
                "node_prefilter_k": int(self.ev.cfg.node_prefilter_k)}

    def _reset(self) -> None:
        super()._reset()
        self.frag = self.lane_events = 0
        self.last_frag = None

    def _require_fork(self) -> None:
        got = int(getattr(self.ev, "start_event", 0))
        if got != self.e0:
            raise SystemExit(
                f"chipbench: {self.cell.name} needs a program whose "
                f"evaluator starts at event {self.e0} of the workload it "
                f"was given; this program's CodeEvaluator starts at {got}, "
                "so nothing is run")

    def call(self, i: int) -> dict:
        if i == -1:      # the warm-up: nothing has run on the device yet
            self._require_rule()
            self._require_fork()
        row = codegen.Driver.call(self, i)     # counts whole-run events
        # a lane's work is what it ran after the fork, and every lane has
        # to run exactly to the cap
        done = [int(r.result.events_processed) - self.e0 for r in self.last
                if r.result is not None]
        self.failed += sum(1 for n in done if n != self.k - self.e0)
        if done:
            self.events -= self.e0
        frag = self.ev.last_eval_stats.get("frag_events")
        self.last_frag = frag
        if frag is None:        # a program that does not count them
            self.frag = None
        elif self.frag is not None:
            self.frag += int(frag)
        self.lane_events += sum(done)
        return {**row, "lane_events": sum(done),
                "lockstep_events": max(done, default=0)}

    def counters(self) -> dict:
        out = super().counters()
        out["lane_events_total"] = self.lane_events
        if self.frag is not None:
            out["frag_events"] = self.frag
        return out

    def rows(self):
        """The snapshot's rows from the reference's own parse, cut to the
        fork as the program's are."""
        from chipbench.reference import data, plain_sim_loaded

        rows = plain_sim_loaded.load_rows(
            self.files["snapshot"], self.files["cluster"],
            self.files["trace"])
        if len(rows) == self.e0:
            return rows
        # selftest sizes: the first start_event arrivals' rows
        pods = data.load_pods(self.files["trace"])
        first = sorted(rows, key=lambda i: (int(pods.creation_time[i]),
                                            int(pods.rank[i])))[:self.e0]
        return {i: rows[i] for i in first}

    def check(self) -> list:
        from chipbench.reference.plain_sim_loaded import simulate_from

        cluster, pods = common.reference_inputs(self.cell.config, self.files)
        rows = self.rows()
        numbers, failed = [], 0
        for lane in range(len(self.sources)):
            ref, ties = admit(
                lambda decide, lane=lane: simulate_from(
                    cluster, pods, rows, self._policy(lane),
                    retry=self.cell.config["retry_rule"],
                    max_steps=self.k, prefilter_k=self._rule(),
                    decide=decide),
                np.asarray(self.last[lane].result.assigned_node)[:pods.p],
                self.cell.config["guarantees"], f"lane{lane}")
            numbers.append(ties)
            numbers += compare_whole(f"lane{lane}", ref,
                                     self.last[lane].result, pods.p,
                                     self.cell.config["guarantees"])
            failed += ref.num_frag_events
        if self.last_frag is not None:
            # the counter sim.retry_share divides, over the same call
            numbers.append(Number("call.frag_counter_diff",
                                  float(abs(failed - int(self.last_frag))),
                                  0.0))
        return numbers
