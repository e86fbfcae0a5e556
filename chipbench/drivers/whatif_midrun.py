"""Driver ``whatif_midrun``: ``whatif_loaded``'s coalesced call forked from
a pinned MOMENT of a real run (``openb16-cpu250-midrun-snapshot``).

The workload is parsed with the configuration's ``snapshot`` file, whose
rows are the decisions of the CREATE attempts among the first
``start_event`` EVENTS of a run (placements and refusals; pods have left,
one waits with its retry queued), and handed to ``VMServeEngine`` as
``whatif_loaded`` hands its own: the fork is data on the workload. What
differs from ``whatif_loaded``:

- the base of a forked query is every pod with an attempt in the log, and
  a query of ``n`` pods is ``n`` consecutive rows of the pods that have
  NOT arrived at the fork, in arrival order, with their own creation times
  and durations; ``--seed`` picks the offset ``s`` in the traffic file's
  range for that size (``s_range``; the largest query's is ``[0, 0]``),
  never how much;
- a lane is a good operation when it stops exactly at its budget OR
  finishes with an empty heap inside it (``finished``), and a failed one
  otherwise; which of the two a query does is the reference's to say
  (``check`` holds ``events``, the flags and ``finished`` to it, exactly);
- before the warm-up call, the run's first device program, it ends the
  run unless the serve engine took the fork AND its ``serve/fork_state``
  span counts what the reference's own run of the log counts: the
  departures, the refused placements and the waiting pods. A program that
  cannot fork from such a prefix refuses it by name when the engine is
  built (the parent commit: a ``ValueError`` from ``QueryFork``); one that
  dropped the waiting pod or the departures would run the cell WRONGLY,
  so the driver refuses that itself;
- the comparison is with ``plain_sim_fork.simulate`` around
  ``chipbench/reference/forked_query_midrun.py``: ``base ++ the query's
  rows``, the first ``start_event`` events as the log says under the rule
  it names, every later event FREE under the configuration's
  ``retry_rule``, through ``nearties.admit`` on the query's own pods:
  nodes, GPU picks, the waiting pods, counts and flags exact; a finished
  lane's gated fitness (``fitness_rel_err``) and every lane's
  ``fitness_at_cap_rel_err`` within ``fitness_rtol``.

No ``pod_limit`` here (a moment of a run has no prefix by pod count): the
selftest (``chipbench/selftest/whatif_midrun.py``) overrides the cluster,
the trace and the snapshot with a tiny deployment's files.
"""
from __future__ import annotations

import glob
import os

import numpy as np

from chipbench import cells
from chipbench.drivers import codegen_loaded, codegen_midrun, common
from chipbench.drivers import whatif, whatif_loaded
from chipbench.drivers.whatif_loaded import _evaluator_of_answer
from chipbench.reference import forked_query_midrun as fq
from chipbench.reference import plain_sim_fork, policies
from chipbench.reference.compare import Number, Output, compare
from chipbench.reference.nearties import admit

#: what the program's ``serve/fork_state`` span has to count as the
#: reference does before anything runs
FORK_FIELDS = ("events", "departed", "refused", "waiting", "residents")


def fork_counts(log) -> dict:
    """``FORK_FIELDS`` of a log, by the reference: a pod is resident when
    its last attempt placed it and its DELETE is not among the events, so
    residents = placed - departed; it waits when its last attempt was
    refused."""
    last = {}
    for i, node, _ in log.attempts:
        last[i] = node
    placed = sum(1 for node in last.values() if node >= 0)
    departed = log.e0 - len(log.attempts)
    return {"events": log.e0, "departed": departed,
            "refused": sum(1 for _, node, _ in log.attempts if node < 0),
            "waiting": len(last) - placed, "residents": placed - departed}


class Driver(whatif_loaded.Driver):
    def _reset(self) -> None:
        super()._reset()
        self.finished = self.lanes = 0

    def _queries(self, sizes) -> list:
        """(offset among the pods not yet arrived, pods) per query."""
        out = []
        for n in sizes:
            lo, hi = self.s_range.get(n, (0, len(self.rest) - n))
            s = int(self.rng.integers(lo, hi + 1))
            rows = [{k: int(getattr(self.pods, a)[i])
                     for k, a in whatif.FIELDS}
                    for i in self.rest[s:s + n]]
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
        self.log = codegen_midrun.Driver.rows(self)
        base = fq.base_of(self.log)
        self.base = len(base)
        arrived = set(base)
        self.rest = [i for i in sorted(
            range(self.pods.p), key=lambda i: (
                int(self.pods.creation_time[i]), int(self.pods.rank[i])))
            if i not in arrived]
        self.backlog = len(self.rest)
        # the log is a snapshot of its own base: held once to end at E0
        plain_sim_fork.validate(
            self.cluster, *fq.inputs(self.pods, self.log, ()))
        self.counts = fork_counts(self.log)
        t1 = common.now()
        ledger = sorted(glob.glob(os.path.join(cells.ROOT,
                                               self.t["ledger_glob"])))
        self.champion = max((load_champion(p) for p in ledger),
                            key=lambda c: c.score)
        self.rng = np.random.default_rng(self.seed)
        self.sizes = [int(n) for n in self.t["sizes"]]
        self.s_range = {int(n): (int(lo), int(hi)) for n, (lo, hi)
                        in self.t.get("s_range", {}).items()}
        self.k = int(self.cell.config["node_prefilter_k"])
        self.k_ref = self.k if 0 < self.k < self.wl.cluster.n_padded else 0
        self.engine = VMServeEngine(
            self.champion, self.wl, engine=self.cell.config["engine"],
            envelope=ShapeEnvelope(max_batch=int(self.t["max_batch"])),
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
                "start_event": self.e0, "base_pods": self.base,
                "backlog": self.backlog, "fork": self.counts,
                "budgets": budgets,
                "program_capacity": self.engine.program_capacity,
                "node_prefilter_k": self.engine.prefilter_k,
                "champion_score": self.champion.score,
                "nodes": self.wl.num_nodes}

    def _require_fork(self) -> None:
        from chipbench.reduce import spans

        super()._require_fork()                 # starts at start_event
        if self.engine.prefilter_k != self.k:
            raise SystemExit(
                f"chipbench: {self.cell.name} runs under node_prefilter_k "
                f"{self.k}, what the program must choose for "
                f"{self.wl.num_nodes} nodes from the cluster's shape; this "
                f"program resolved {self.engine.prefilter_k}, so nothing "
                "is run")
        got = spans.ring()
        forks = [r for r in (got[0] if got else ())
                 if r.name == "serve/fork_state"]
        said = dict(forks[-1].fields or {}) if forks else {}
        wrong = {k: (said.get(k), self.counts[k]) for k in FORK_FIELDS
                 if said.get(k) != self.counts[k]}
        if wrong:
            raise SystemExit(
                f"chipbench: {self.cell.name} forks from a moment of a "
                "run, and the program's serve/fork_state span does not "
                "count it as the reference's run of the log does "
                "((program, reference): " + ", ".join(
                    f"{k} {v}" for k, v in sorted(wrong.items()))
                + "): a fork that lost its departures or its waiting "
                "pods answers from another cluster, so nothing is run")

    def call(self, i: int) -> dict:
        queries = self._queries(self.sizes)
        t0 = common.now()
        with common.annotate(self.span):
            answers = self._submit(queries, f"c{i}")
        self.call_s += common.now() - t0
        # the expected answer is cut exactly at its budget from the fork,
        # or finished with an empty heap inside it
        ok = [a for (_, rows), a in zip(queries, answers)
              if "error" not in a and not a["failed"] and (
                  a["events"] - self.e0 == self.budget(len(rows))
                  if a["truncated"] else a["finished"]
                  and a["events"] - self.e0 <= self.budget(len(rows)))]
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
        # failed placements FROM THE FORK (an answer's are the whole run's)
        frag = sum(a["frag_events"] - self.counts["refused"] for a in ok)
        self.frag += frag
        self.lane_events += sum(a["events"] - self.e0 for a in ok)
        done = sum(a["finished"] for a in ok)
        self.finished += done
        self.lanes += len(answers)
        self.last = (queries, answers)
        return {"queries": len(answers),
                "pods": sum(len(q[1]) for q, a in zip(queries, answers)
                            if "error" not in a),
                "lockstep_events": events, "chunks": len(by_bucket),
                "frag_events": frag, "finished_lanes": done}

    def counters(self) -> dict:
        return {**super().counters(), "finished_lanes": self.finished,
                "lanes_total": self.lanes}

    def close(self) -> None:
        # a program that refuses the fork by name does so while the
        # engine is built, before there is a service to close: its
        # ValueError is the run's last word
        if hasattr(self, "service"):
            super().close()

    def simulate(self, s: int, n: int, policy, **kw):
        """The plain reference's run of the query of ``n`` pods at offset
        ``s`` among the pods not yet arrived: ``(Result, waiting)``;
        ``kw`` to ``simulate_query``."""
        taken, keyed = fq.inputs(self.pods, self.log, self.rest[s:s + n])
        return fq.simulate_query(
            self.cluster, taken, keyed, policy,
            max_steps=self.e0 + self.budget(n), prefilter_k=self.k_ref,
            retry=self.cell.config["retry_rule"], **kw)

    def reference(self, s: int, n: int, policy, tag: str, got_nodes):
        """``simulate`` through ``nearties.admit`` on the query's own
        pods: ``(Result, waiting, near-tie count)``."""
        waiting = []

        def run(decide):
            ref, wait = self.simulate(s, n, policy, decide=decide)
            waiting[:] = wait
            return ref

        ref, ties = admit(run, got_nodes, self.cell.config["guarantees"],
                          tag)
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
                tag, fq.of_query(ref, self.base),
                Output(assigned_node=nodes, assigned_gpus=gpus,
                       scheduled=int(a["scheduled"]),
                       events=int(a["events"]), score=float(a["score"]),
                       failed=bool(a["failed"]),
                       truncated=bool(a["truncated"])), g)
            numbers.append(Number(
                f"{tag}.waiting_differ",
                float(len(set(waiting) ^ set(a.get("waiting", ())))
                      + ("waiting" not in a)), 0.0))
            numbers.append(Number(
                f"{tag}.finished_differ",
                float(fq.finished(ref) != a["finished"]), 0.0))
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
