"""Driver ``codegen_midrun``: ``codegen``'s generation forked from a
pinned moment of a real run (``openb16-cpu250-midrun``).

``codegen_loaded``'s driver, with what a prefix of arrivals alone did not
need. The workload is parsed with the configuration's ``snapshot`` file,
whose rows are the decisions of the CREATE attempts among the first
``start_event`` EVENTS of a run (placements, refusals), so the evaluator,
built exactly as ``codegen`` builds it, starts every lane after those
events with no further option: 5,618 pods have left, one waits with its
retry queued, the waiting histogram, the fragmentation sum and 26
utilization snapshots are in the carry. As there: the step cap is
absolute (``start_event + code_eval_max_steps``), a lane's events are
counted from the fork, a lane that does not stop exactly at the cap is a
failed operation (no lane can finish: 3,751 pods have not arrived), the
``frag_events`` counter is held to the reference's, ``compare_whole``
adds the cut run's evaluator numbers, and before the warm-up call, the
run's first device program, the run ends unless the program took the fork
(a program that forks from arrivals alone refuses the file when it parses
it; the parent commit has no file to verify). The comparison is with
``plain_sim_midrun.simulate_from``: the snapshot's log, from the
reference's own parse of the file and held once to end at ``start_event``
(``validate``), decides the prefix of ``plain_sim.simulate``'s own loop,
which runs free after the fork.

The selftest (``chipbench/selftest/midrun.py``) overrides the cluster,
the trace and the snapshot with a tiny deployment's files; no
``pod_limit`` here: a moment of a run has no prefix by pod count.
"""
from __future__ import annotations

import numpy as np

from chipbench.drivers import codegen_loaded, common
from chipbench.drivers.codegen_loaded import compare_whole
from chipbench.reference.compare import Number
from chipbench.reference.nearties import admit


class Driver(codegen_loaded.Driver):
    def rows(self):
        """The snapshot's log from the reference's own parse."""
        from chipbench.reference import plain_sim_midrun

        log = plain_sim_midrun.load_log(
            self.files["snapshot"], self.files["cluster"],
            self.files["trace"])
        if log.e0 != self.e0:
            raise SystemExit(
                f"chipbench: {self.cell.name} forks at event {self.e0}, "
                f"the snapshot's log ends at {log.e0}")
        return log

    def check(self) -> list:
        from chipbench.reference.plain_sim_midrun import (
            simulate_from, validate)

        cluster, pods = common.reference_inputs(self.cell.config, self.files)
        log = self.rows()
        retry = self.cell.config["retry_rule"]
        validate(cluster, pods, log, retry)
        refused = sum(1 for _, node, _ in log.attempts if node < 0)
        numbers, failed = [], 0
        for lane in range(len(self.sources)):
            ref, ties = admit(
                lambda decide, lane=lane: simulate_from(
                    cluster, pods, log, self._policy(lane), retry=retry,
                    max_steps=self.k, prefilter_k=self._rule(),
                    decide=decide),
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
