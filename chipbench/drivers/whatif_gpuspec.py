"""Driver ``whatif_gpuspec``: ``whatif_loaded``'s forked, coalesced call on
a cluster whose pods name the GPU models they accept
(``openb1523-gpuspec25-loaded-snapshot``): every query pod is sent with
its ``gpu_spec``.

``whatif_loaded``'s driver (the closed loop, the budgets counted from the
fork, a lane that does not stop exactly at its budget a failed operation,
the waiting set, the evaluator's three counts,
``fitness_at_cap_rel_err``), with three differences. The workload is the
program's own parse WITH ``gpu_spec`` honoured and with the
configuration's snapshot (``codegen_gpuspec``'s parse): the constraint is
data on the workload, so ``VMServeEngine``, built exactly as
``whatif_loaded`` builds it, honours it with no further option. A query
pod carries, beside its six numbers, the ``gpu_spec`` string of its row
of the pod list, read by the REFERENCE's CSV reader (a pod that names
nothing is sent without the key). And the comparison is with
``forked_query_gpuspec.simulate_query``, which makes a query pod's
allowed nodes from the string the service was SENT and the residents'
from its own read of the two columns.

A program whose serving has today's six-field schema would run this cell
WRONGLY, not fail: it would answer as if no pod named a GPU. So before
the warm-up call, the run's first device program, the run ends unless the
engine took the fork AND says that its queries carry the constraint
(``engine.typed``) with the configuration's ``typed_residents``
constrained residents in its fork. A program older than the parse's
argument ends at the parse; the parent commit has no snapshot file to
verify.
"""
from __future__ import annotations

import glob
import os

import numpy as np

from chipbench import cells
from chipbench.drivers import codegen_gpuspec, codegen_loaded, common
from chipbench.drivers import whatif_loaded
from chipbench.reference import forked_query_gpuspec as fq
from chipbench.reference.data import _rows

GPU_SPEC = "gpu_spec"


class Driver(whatif_loaded.Driver):
    #: what the parse is asked to do with the column
    gpu_spec = "honor"
    #: the selftest's field-lost control (``chipbench/selftest/
    #: whatif_gpuspec.py``) sends the same queries WITHOUT their gpu_spec
    send_spec = True

    def _queries(self, sizes) -> list:
        """``whatif_loaded``'s windows, each pod with the ``gpu_spec`` of
        its row of the pod list."""
        out = super()._queries(sizes)
        if self.send_spec:
            for s, rows in out:
                for j, row in enumerate(rows):
                    if self.specs[self.e0 + s + j]:
                        row[GPU_SPEC] = self.specs[self.e0 + s + j]
        return out

    def setup(self) -> dict:
        from fks_tpu.serve import (ServeService, ShapeEnvelope,
                                   VMServeEngine, load_champion)

        t0 = common.now()
        self.e0 = int(self.cell.config["start_event"])
        self.wl = codegen_gpuspec.Driver._workload(self)   # typed, forked
        self.cluster, self.pods = common.reference_inputs(
            self.cell.config, self.files)
        self.rows = codegen_loaded.Driver.rows(self)
        self.backlog = self.pods.p - self.e0
        # the reference's own read of the two columns
        self.allowed = codegen_gpuspec.Driver.allowed(self)
        self.models = fq.node_models(self.files["cluster"])
        self.specs = [r.get(GPU_SPEC) or ""
                      for r in _rows(self.files["trace"])]
        keyed = fq.inputs(self.pods, self.rows, self.allowed, (), (),
                          self.models)[1]
        self.resident_nodes = [keyed[i][0] for i in range(self.e0)]
        t1 = common.now()
        ledger = sorted(glob.glob(os.path.join(cells.ROOT,
                                               self.t["ledger_glob"])))
        self.champion = max((load_champion(p) for p in ledger),
                            key=lambda c: c.score)
        self.rng = np.random.default_rng(self.seed)
        self.sizes = [int(n) for n in self.t["sizes"]]
        self.k = int(self.cell.config["node_prefilter_k"])
        self.k_ref = self.k if 0 < self.k < self.wl.cluster.n_padded else 0
        self.engine = VMServeEngine(
            self.champion, self.wl, engine=self.cell.config["engine"],
            envelope=ShapeEnvelope(max_batch=int(self.t["max_batch"])),
            prefilter_k=self.k,
            max_steps_factor=int(self.cell.config["max_steps_factor"]),
            mesh=self.mesh)
        self.service = ServeService(
            self.engine, max_batch=int(self.t["max_batch"]),
            max_wait_s=float(self.t["max_wait_s"]))
        t2 = common.now()
        self._require_fork()
        self._require_types()
        self.call(-1)                      # warm-up: compiles, not counted
        self._reset()
        self.batches0 = self.service.summary(record=False)["batches"]
        budgets = {}
        for n in self.sizes:
            budgets[self.engine.envelope.pod_bucket_for(n)] = self.budget(n)
        return {"parse_s": t1 - t0, "build_s": t2 - t1,
                "warm_call_s": common.now() - t2,
                "sizes": self.sizes, "pods_per_call": sum(self.sizes),
                "start_event": self.e0, "backlog": self.backlog,
                "budgets": budgets,
                "lockstep_events_per_call": sum(budgets.values()),
                "program_capacity": self.engine.program_capacity,
                "node_prefilter_k": self.engine.prefilter_k,
                "champion_score": self.champion.score,
                "nodes": self.wl.num_nodes,
                "typed_residents": self._typed_residents(),
                "typed_backlog": sum(1 for s in self.specs[self.e0:] if s)}

    def _typed_residents(self) -> int:
        fork = getattr(self.engine, "fork", None)
        return int(getattr(fork, "typed_residents", 0) or 0)

    def _require_types(self) -> None:
        want = int(self.cell.config["typed_residents"])
        got = self._typed_residents() \
            if getattr(self.engine, "typed", False) else 0
        if got != want:
            raise SystemExit(
                f"chipbench: {self.cell.name} needs a program whose serve "
                f"engine carries a query pod's gpu_spec and forks from the "
                f"snapshot's {want} constrained residents; this program's "
                f"VMServeEngine holds {got} (it would answer as if no pod "
                "named a GPU model), so nothing is run")

    def simulate(self, s: int, n: int, policy, **kw):
        """The plain reference's run of the query of ``n`` pods at offset
        ``s`` after the fork, each pod allowed what the string the
        service is sent for it allows (the field-lost control sends none
        and is still held to the column): ``(Result, waiting)``."""
        query = range(self.e0 + s, self.e0 + s + n)
        taken, keyed, allowed = fq.inputs(
            self.pods, self.rows, self.allowed, query,
            [self.specs[i] for i in query], self.models)
        return fq.simulate_query(
            self.cluster, taken, keyed, allowed, policy,
            max_steps=self.e0 + self.budget(n), prefilter_k=self.k_ref,
            retry=self.cell.config["retry_rule"], **kw)

    def check(self) -> list:
        fq.validate_snapshot(self.cluster, self.pods, self.rows,
                             self.allowed, self.cell.config["retry_rule"])
        return super().check()
