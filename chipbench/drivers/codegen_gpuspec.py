"""Driver ``codegen_gpuspec``: ``codegen_midrun``'s forked generation on
a workload whose pods name the GPU models they accept
(``openb1523-gpuspec25-loaded``).

``codegen_midrun``'s driver (absolute step cap ``start_event +
code_eval_max_steps``, a lane's events counted from the fork, a lane that
does not stop exactly at the cap a failed operation,
``call.frag_counter_diff``, ``compare_whole``'s cut-run numbers, the
refusal of a program that did not take the fork), with two differences.
The workload is the program's own parse WITH ``gpu_spec`` honoured
(``TraceParser.parse_workload(..., gpu_spec="honor")``; ``common.
parse_workload`` has no such argument): the constraint is data on the
workload, so the evaluator, built exactly as ``codegen`` builds it,
applies it with no further option. And the comparison is with
``plain_sim_gpuspec.simulate_from``, handed the reference's own read of
the two columns (``load_allowed``).

A program that parses the column away would run this cell WRONGLY, not
fail: it would time the unconstrained list. So before the warm-up call,
the run's first device program, the run ends unless the evaluator's
workload carries the type leaves with the configuration's ``typed_pods``
constrained pods (a program older than the argument ends at the parse;
the parent commit has no trace file to verify).
"""
from __future__ import annotations

import dataclasses

import numpy as np

from chipbench.drivers import codegen_midrun, common
from chipbench.drivers.codegen_loaded import compare_whole
from chipbench.reference.compare import Number
from chipbench.reference.nearties import admit


def typed_pods(workload) -> int:
    """Pods of the program's workload that carry a constraint; 0 where it
    has no such leaves (or the program no such notion)."""
    spec = getattr(workload.pods, "gpu_spec", None)
    if spec is None or getattr(workload.cluster, "gpu_model", None) is None:
        return 0
    return int(np.count_nonzero(
        np.asarray(spec)[np.asarray(workload.pods.pod_mask)]))


class Driver(codegen_midrun.Driver):
    #: what the parse is asked to do with the column; the selftest's
    #: mask-lost control (``chipbench/selftest/gpuspec.py``) says "ignore"
    gpu_spec = "honor"

    def _workload(self):
        """The program's own parse: constraints honoured, snapshot
        included."""
        import os

        from fks_tpu.data import TraceParser, snapshot

        directory = os.path.dirname(os.path.dirname(self.files["cluster"]))
        try:
            wl = TraceParser(directory).parse_workload(
                node_file=common._csv_name(self.files["cluster"]),
                pod_file=common._csv_name(self.files["trace"]),
                gpu_spec=self.gpu_spec)
        except TypeError:
            raise SystemExit(
                f"chipbench: {self.cell.name} needs a program whose parse "
                "can honour gpu_spec (TraceParser.parse_workload(..., "
                "gpu_spec='honor')); this one cannot, so nothing is run"
            ) from None
        full = snapshot.load_snapshot(
            self.files["snapshot"].removesuffix(".gz"), wl)
        return dataclasses.replace(wl, snapshot=snapshot.head(full, self.e0))

    def _require_types(self) -> None:
        want = int(self.cell.config["typed_pods"])
        got = typed_pods(self.ev.workload)
        if got != want:
            raise SystemExit(
                f"chipbench: {self.cell.name} needs a program whose "
                f"evaluator runs the workload it was given with its "
                f"{want} constrained pods (gpu_spec honoured); this "
                f"program's CodeEvaluator holds a workload with {got}, so "
                "nothing is run")

    def call(self, i: int) -> dict:
        if i == -1:      # the warm-up: nothing has run on the device yet
            self._require_types()
        return super().call(i)

    def counters(self) -> dict:
        out = super().counters()
        # what sim.typed_pod_share divides by: the pods the driver parsed
        out["workload_pods"] = int(self.wl.num_pods)
        return out

    def close(self) -> None:
        # the run may end at the parse, before the profiler is there
        if getattr(self, "profiler", None):
            self.profiler.close()

    def allowed(self):
        from chipbench.reference import plain_sim_gpuspec

        return plain_sim_gpuspec.load_allowed(self.files["cluster"],
                                              self.files["trace"])

    def check(self) -> list:
        from chipbench.reference.plain_sim_gpuspec import (
            simulate_from, validate)

        cluster, pods = common.reference_inputs(self.cell.config, self.files)
        log, allowed = self.rows(), self.allowed()
        retry = self.cell.config["retry_rule"]
        validate(cluster, pods, allowed, log, retry)
        refused = sum(1 for _, node, _ in log.attempts if node < 0)
        numbers, failed = [], 0
        for lane in range(len(self.sources)):
            ref, ties = admit(
                lambda decide, lane=lane: simulate_from(
                    cluster, pods, allowed, log, self._policy(lane),
                    retry=retry, max_steps=self.k,
                    prefilter_k=self._rule(), decide=decide),
                np.asarray(self.last[lane].result.assigned_node)[:pods.p],
                self.cell.config["guarantees"], f"lane{lane}")
            numbers.append(ties)
            numbers += compare_whole(f"lane{lane}", ref,
                                     self.last[lane].result, pods.p,
                                     self.cell.config["guarantees"])
            failed += ref.num_frag_events - refused
        if self.last_frag is not None:
            # the counter sim.retry_share divides, over the same call:
            # failed placements AFTER the fork
            numbers.append(Number("call.frag_counter_diff",
                                  float(abs(failed - int(self.last_frag))),
                                  0.0))
        return numbers
