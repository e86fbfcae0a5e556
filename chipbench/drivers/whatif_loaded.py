"""Driver ``whatif_loaded``: ``whatif``'s coalesced call forked from a
pinned snapshot of the loaded cluster.

The workload is parsed with the configuration's ``snapshot`` file, as
``codegen_loaded`` parses it, and handed to ``VMServeEngine`` as
``whatif`` hands its own: the fork is data on the workload, so every query
submitted through ``ServeService.submit`` is answered from the loaded
cluster with no further option. What differs from ``whatif``:

- a query of ``n`` pods is ``n`` consecutive arrivals of the pod list
  AFTER the fork, rows ``start_event + s ..`` with their own creation
  times, durations and order; ``--seed`` picks ``s`` in ``[0, backlog -
  n]``, never how much, and the query that is the whole backlog is the
  same for every seed;
- the engine's step budget is the configuration's ``max_steps_factor``
  (``max(64, factor x bucket)`` events FROM THE FORK), and no forked run
  ends inside it (the residents' departures stay queued), so an answer cut
  exactly at its budget is the expected one: a lane that stops anywhere
  else, fails or errors is a failed operation. A lane's lockstep events
  are counted from the fork (``events - start_event``; an answer's
  ``events`` is the whole run's);
- before the warm-up call, the run's first device program, it ends the
  run unless the engine took the fork (a program whose serving cannot
  fork, as the parent commit is, would answer from an EMPTY cluster);
- the comparison is with ``plain_sim_loaded.simulate_from`` on
  ``residents ++ the query's rows`` (``chipbench/reference/
  forked_query.py``), free after the fork: the query's pods' nodes and GPU
  picks, scheduled, events, flags and the pods waiting at the cut exact;
  the evaluator's snapshot count, failed placements and peak of active
  nodes exact; the fitness the cut run would report within the
  configuration's ``fitness_rtol`` (``codegen_loaded``'s numbers for a
  cut run).

``pod_limit`` (selftest only) keeps the first pods, and ``start_event``
then the first rows of the snapshot.
"""
from __future__ import annotations

import glob
import os
import types

import numpy as np

from chipbench import cells
from chipbench.drivers import codegen_loaded, common, whatif
from chipbench.reference import forked_query, policies
from chipbench.reference.compare import Number, Output, compare
from chipbench.reference.nearties import admit


def _evaluator_of_answer(a: dict) -> tuple:
    """``codegen_loaded._evaluator_of`` of a forked answer."""
    cpu, mem, gpus, milli = a["utilization"]
    return codegen_loaded._evaluator_of(types.SimpleNamespace(
        avg_cpu_utilization=cpu, avg_memory_utilization=mem,
        avg_gpu_count_utilization=gpus, avg_gpu_memory_utilization=milli,
        gpu_fragmentation_score=a["fragmentation"],
        num_fragmentation_events=a["frag_events"],
        num_snapshots=a["snapshots"], max_nodes=a["max_nodes"]))


class Driver(whatif.Driver):
    def _reset(self) -> None:
        super()._reset()
        self.frag = self.lane_events = 0

    def budget(self, n: int) -> int:
        """Events from the fork that a query of ``n`` pods may run."""
        return max(64, int(self.cell.config["max_steps_factor"])
                   * self.engine.envelope.pod_bucket_for(n))

    def _queries(self, sizes) -> list:
        """(offset after the fork, pods) per query: a seeded window of
        the arrivals that follow the residents."""
        out = []
        for n in sizes:
            s = int(self.rng.integers(0, self.backlog - n + 1))
            rows = [{k: int(getattr(self.pods, a)[self.e0 + s + j])
                     for k, a in whatif.FIELDS} for j in range(n)]
            out.append((s, rows))
        return out

    def setup(self) -> dict:
        from fks_tpu.serve import (ServeService, ShapeEnvelope,
                                   VMServeEngine, load_champion)

        t0 = common.now()
        self.e0 = int(self.cell.config["start_event"])
        self.wl = codegen_loaded.Driver._workload(self)   # with the snapshot
        self.cluster, self.pods = common.reference_inputs(
            self.cell.config, self.files)
        self.rows = codegen_loaded.Driver.rows(self)
        self.backlog = self.pods.p - self.e0
        # the residents' nodes in arrival order: what every answer's pod
        # axis begins with (``nearties.admit`` is handed the whole axis)
        keyed = forked_query.inputs(self.pods, self.rows, ())[1]
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
                "nodes": self.wl.num_nodes}

    def _require_fork(self) -> None:
        got = int(getattr(self.engine, "start_event", 0))
        if got != self.e0:
            raise SystemExit(
                f"chipbench: {self.cell.name} needs a program whose serve "
                f"engine starts at event {self.e0} of the workload it was "
                f"given; this program's VMServeEngine starts at {got} (it "
                "would answer from an empty cluster), so nothing is run")

    def call(self, i: int) -> dict:
        queries = self._queries(self.sizes)
        t0 = common.now()
        with common.annotate(self.span):
            answers = self._submit(queries, f"c{i}")
        self.call_s += common.now() - t0
        # the expected answer is cut exactly at its budget from the fork
        ok = [a for (_, rows), a in zip(queries, answers)
              if "error" not in a and not a["failed"]
              and a["events"] - self.e0 == self.budget(len(rows))]
        self.failed += len(answers) - len(ok)
        self.queries += len(answers)
        # one chunk per pod bucket, each run to its slowest lane
        by_bucket = {}
        for a in ok:
            by_bucket[a["bucket_pods"]] = max(
                by_bucket.get(a["bucket_pods"], 0), a["events"] - self.e0)
        events = sum(by_bucket.values())
        self.events += events
        self.calls += 1
        self.host_s += float(self.engine.last_batch_timing["pack_h2d_s"])
        self.latencies += [a["latency_ms"] for a in ok]
        self.frag += sum(a["frag_events"] for a in ok)
        self.lane_events += sum(a["events"] - self.e0 for a in ok)
        self.last = (queries, answers)
        return {"queries": len(answers),
                "pods": sum(len(q[1]) for q, a in zip(queries, answers)
                            if "error" not in a),
                "lockstep_events": events,
                "chunks": len(by_bucket),
                "frag_events": sum(a["frag_events"] for a in ok)}

    def counters(self) -> dict:
        return {**super().counters(), "frag_events": self.frag,
                "lane_events_total": self.lane_events}

    def simulate(self, s: int, n: int, policy, **kw):
        """The plain reference's run of the query of ``n`` pods at offset
        ``s`` after the fork: ``(Result, waiting)``; ``kw`` to
        ``simulate_query``."""
        taken, keyed = forked_query.inputs(
            self.pods, self.rows, range(self.e0 + s, self.e0 + s + n))
        return forked_query.simulate_query(
            self.cluster, taken, keyed, policy,
            max_steps=self.e0 + self.budget(n), prefilter_k=self.k_ref,
            retry=self.cell.config["retry_rule"], **kw)

    def reference(self, s: int, n: int, policy, tag: str, got_nodes):
        """``simulate`` through ``nearties.admit``: ``(Result, waiting,
        near-tie count)``."""
        waiting = []

        def run(decide):
            ref, wait = self.simulate(s, n, policy, decide=decide)
            waiting[:] = wait
            return ref

        ref, ties = admit(run,
                          np.concatenate([self.resident_nodes, got_nodes]),
                          self.cell.config["guarantees"], tag)
        return ref, list(waiting), ties

    def check(self) -> list:
        queries, answers = self.last
        g = self.cell.config["guarantees"]
        policy = policies.source_policy(self.champion.code,
                                        dtype=g["score_dtype"])
        numbers = []
        for j, ((s, rows), a) in enumerate(zip(queries, answers)):
            n, tag = len(rows), f"query{j}n{len(rows)}"
            if "error" in a:
                raise SystemExit(f"query {j} failed: {a['error']}")
            nodes = np.array([r["node"] for r in a["placements"]], np.int64)
            gpus = np.array([sum(1 << b for b in r["gpus"])
                             for r in a["placements"]], np.int64)
            ref, waiting, ties = self.reference(s, n, policy, tag, nodes)
            numbers.append(ties)
            numbers += compare(
                tag, forked_query.of_query(ref, self.e0),
                Output(assigned_node=nodes, assigned_gpus=gpus,
                       scheduled=int(a["scheduled"]),
                       events=int(a["events"]), score=float(a["score"]),
                       failed=bool(a["failed"]),
                       truncated=bool(a["truncated"])), g)
            numbers.append(Number(
                f"{tag}.waiting_differ",
                float(len(set(waiting) ^ set(a.get("waiting", ())))
                      + ("waiting" not in a)), 0.0))
            want = codegen_loaded._evaluator_of(ref)
            got = _evaluator_of_answer(a)
            numbers += [
                Number(f"{tag}.{name}_diff", float(abs(w - v)), 0.0)
                for name, w, v in zip(
                    ("snapshots", "frag_events", "max_nodes"), want, got)]
            numbers.append(Number(
                f"{tag}.fitness_at_cap_rel_err",
                abs(got[3] - want[3]) / max(want[3], 1e-30),
                float(g["fitness_rtol"])))
        return numbers
