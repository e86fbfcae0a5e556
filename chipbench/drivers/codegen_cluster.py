"""Driver ``codegen_cluster``: ``codegen``'s generation on a cluster large
enough for the program's large-cluster rule.

The evaluator is built exactly as ``codegen`` builds it: no
``node_prefilter_k`` is passed, the program chooses. Two things differ.
Before the warm-up call, which is the run's first device program, the
driver reads the rule the evaluator resolved (``CodeEvaluator.cfg``) and
ends the run unless it is the configuration's ``node_prefilter_k``: a
program that leaves the rule at 0 would interpret over every node of the
cluster (registers of 219 MB a slot on 1,523 nodes x 8 lanes) until it is
killed. And the comparison hands the same rule to the plain reference.
"""
from __future__ import annotations

from chipbench.drivers import codegen, common
from chipbench.reference.compare import Output, compare
from chipbench.reference.nearties import admit
from chipbench.reference.plain_sim import simulate


class Driver(codegen.Driver):
    def _rule(self) -> int:
        return int(self.cell.config["node_prefilter_k"])

    def _require_rule(self) -> None:
        got = int(self.ev.cfg.node_prefilter_k)
        if got != self._rule():
            raise SystemExit(
                f"chipbench: {self.cell.name} needs a program whose "
                f"evaluation path chooses node_prefilter_k={self._rule()} "
                f"for a cluster of {self.wl.cluster.n_padded} padded nodes "
                f"from its shape; this program's CodeEvaluator resolved "
                f"{got}, so nothing is run")

    def setup(self) -> dict:
        shapes = super().setup()
        shapes["nodes_padded"] = int(self.wl.cluster.n_padded)
        shapes["node_prefilter_k"] = int(self.ev.cfg.node_prefilter_k)
        return shapes

    def call(self, i: int) -> dict:
        if i == -1:      # the warm-up: nothing has run on the device yet
            self._require_rule()
        return super().call(i)

    def check(self) -> list:
        cluster, pods = common.reference_inputs(self.cell.config, self.files)
        numbers = []
        for lane in range(len(self.sources)):
            got = Output.of_lane(self.last[lane].result, pods.p)
            ref, ties = admit(
                lambda decide, lane=lane: simulate(
                    cluster, pods, self._policy(lane),
                    retry=self.cell.config["retry_rule"],
                    max_steps=self.k, prefilter_k=self._rule(),
                    decide=decide),
                got.assigned_node, self.cell.config["guarantees"],
                f"lane{lane}")
            numbers.append(ties)
            numbers += compare(f"lane{lane}", ref, got,
                               self.cell.config["guarantees"])
        return numbers
