"""Driver ``whatif``: one call = ``len(sizes)`` what-if queries submitted
together through ``ServeService.submit`` (in process, closed loop): the
batcher flushes them as one coalesced batch and the next call goes when
every future is done.

Every call holds the traffic file's ``sizes`` in the listed order, so its
pods, its chunks (one per pod bucket) and its lockstep events are the same
for every seed; ``--seed`` picks each query's window of the pod list.
"""
from __future__ import annotations

import glob
import os

import numpy as np

from chipbench import cells
from chipbench.drivers import common
from chipbench.reference import policies
from chipbench.reference.compare import Output, compare
from chipbench.reference.nearties import admit
from chipbench.reference.plain_sim import simulate

FIELDS = (("cpu_milli", "cpu"), ("memory_mib", "mem"),
          ("num_gpu", "num_gpu"), ("gpu_milli", "gpu_milli"),
          ("creation_time", "creation_time"), ("duration_time", "duration"))


class Driver:
    span = "bench/whatif_call"

    def __init__(self, cell, seed: int, files: dict, mesh, traced: bool):
        self.cell, self.seed, self.files = cell, seed, files
        self.t = cell.traffic
        self.mesh = mesh
        self.last = None
        self._reset()

    def _reset(self) -> None:
        self.call_s = self.host_s = 0.0
        self.events = self.calls = self.failed = self.queries = 0
        self.latencies = []

    def _queries(self, sizes) -> list:
        """(start, pods) per query: a seeded window of the pod list."""
        out = []
        for n in sizes:
            start = int(self.rng.integers(0, self.pods.p - n + 1))
            rows = [{k: int(getattr(self.pods, a)[start + j])
                     for k, a in FIELDS} for j in range(n)]
            out.append((start, rows))
        return out

    def setup(self) -> dict:
        from fks_tpu.serve import (ServeService, ShapeEnvelope,
                                   VMServeEngine, load_champion)

        t0 = common.now()
        self.wl = common.parse_workload(self.cell.config, self.files)
        self.cluster, self.pods = common.reference_inputs(
            self.cell.config, self.files)
        t1 = common.now()
        ledger = sorted(glob.glob(os.path.join(cells.ROOT,
                                               self.t["ledger_glob"])))
        self.champion = max((load_champion(p) for p in ledger),
                            key=lambda c: c.score)
        self.rng = np.random.default_rng(self.seed)
        self.sizes = [int(n) for n in self.t["sizes"]]
        self.k = int(self.cell.config["node_prefilter_k"])
        # the program scores every node when k covers the (padded) cluster
        self.k_ref = self.k if 0 < self.k < self.wl.cluster.n_padded else 0
        self.engine = VMServeEngine(
            self.champion, self.wl, engine=self.cell.config["engine"],
            envelope=ShapeEnvelope(max_batch=int(self.t["max_batch"])),
            prefilter_k=self.k, mesh=self.mesh)
        self.service = ServeService(
            self.engine, max_batch=int(self.t["max_batch"]),
            max_wait_s=float(self.t["max_wait_s"]))
        t2 = common.now()
        self.call(-1)                      # warm-up: compiles, not counted
        self._reset()
        self.batches0 = self.service.summary(record=False)["batches"]
        return {"parse_s": t1 - t0, "build_s": t2 - t1,
                "warm_call_s": common.now() - t2,
                "sizes": self.sizes, "pods_per_call": sum(self.sizes),
                "program_capacity": self.engine.program_capacity,
                "node_prefilter_k": self.engine.prefilter_k,
                "champion_score": self.champion.score,
                "nodes": self.wl.num_nodes}

    def _submit(self, queries, tag) -> list:
        futs = [self.service.submit({"id": f"{tag}-{j}", "pods": rows})
                for j, (_, rows) in enumerate(queries)]
        answers = []
        for f in futs:
            try:
                answers.append(f.result(timeout=600))
            except Exception as e:  # noqa: BLE001 — a failed query, counted
                answers.append({"error": repr(e)})
        return answers

    def call(self, i: int) -> dict:
        queries = self._queries(self.sizes)
        t0 = common.now()
        with common.annotate(self.span):
            answers = self._submit(queries, f"c{i}")
        self.call_s += common.now() - t0
        ok = [a for a in answers if "error" not in a and not a["failed"]
              and not a["truncated"]]
        self.failed += len(answers) - len(ok)
        self.queries += len(answers)
        # one chunk per pod bucket, each run to its slowest lane
        by_bucket = {}
        for a in ok:
            by_bucket[a["bucket_pods"]] = max(
                by_bucket.get(a["bucket_pods"], 0), a["events"])
        events = sum(by_bucket.values())
        self.events += events
        self.calls += 1
        self.host_s += float(self.engine.last_batch_timing["pack_h2d_s"])
        self.latencies += [a["latency_ms"] for a in ok]
        self.last = (queries, answers)
        return {"queries": len(answers),
                "pods": sum(len(q[1]) for q, a in zip(queries, answers)
                            if "error" not in a),
                "lockstep_events": events,
                "chunks": len(by_bucket)}

    @staticmethod
    def device_stage(call, for_s: float):
        """Where the traced slice belongs in a coalesced call: the wait for
        the LARGEST chunk (bucket x lanes: most of the call's device time
        is its one op-slot loop), so that the slice reads a loop and not
        the seams between chunks; from that chunk's enqueue where the wait
        alone is shorter than the slice."""
        stacks = [r for r in call.spans
                  if r.name == "serve/chunk/stack" and r.fields]
        if not stacks:
            return None
        big = max(stacks, key=lambda r: (r.fields.get("bucket", 0)
                                         * r.fields.get("lanes", 0), r.t0))

        def of(name):
            return [r for r in call.spans if r.name == name
                    and r.trace_id == big.trace_id
                    and (r.fields or {}).get("chunk") == big.fields["chunk"]]

        wait, enqueue = of("serve/chunk/wait_device"), of("serve/chunk/enqueue")
        if not wait:
            return None
        if wait[0].t1 - wait[0].t0 >= for_s or not enqueue:
            return wait[0].t0, wait[0].t1
        return enqueue[0].t0, wait[0].t1

    def counters(self) -> dict:
        batches = self.service.summary(record=False)["batches"] \
            - self.batches0
        return {"call_seconds": self.call_s, "lockstep_events": self.events,
                "host_seconds": self.host_s, "calls": self.calls,
                "queries": self.queries,
                "flush_slots": batches * int(self.t["max_batch"]),
                "latency_p50_ms": (float(np.median(self.latencies))
                                   if self.latencies else None)}

    def attempted_failed(self, rows) -> tuple:
        return sum(r["queries"] for r in rows), self.failed

    def check(self) -> list:
        queries, answers = self.last
        env = self.engine.envelope
        numbers = []
        policy = policies.source_policy(
            self.champion.code,
            dtype=self.cell.config["guarantees"]["score_dtype"])
        for j, ((start, rows), a) in enumerate(zip(queries, answers)):
            n = len(rows)
            if "error" in a:
                raise SystemExit(f"query {j} failed: {a['error']}")
            nodes = np.array([r["node"] for r in a["placements"]], np.int64)
            gpus = np.array([sum(1 << b for b in r["gpus"])
                             for r in a["placements"]], np.int64)
            got = Output(assigned_node=nodes, assigned_gpus=gpus,
                         scheduled=int(a["scheduled"]),
                         events=int(a["events"]), score=float(a["score"]),
                         failed=bool(a["failed"]),
                         truncated=bool(a["truncated"]))
            bucket = env.pod_bucket_for(n)
            taken = self.pods.take(range(start, start + n), query=True)
            ref, ties = admit(
                lambda decide, taken=taken, bucket=bucket: simulate(
                    self.cluster, taken, policy,
                    retry=self.cell.config["retry_rule"],
                    max_steps=max(
                        64, int(self.cell.config["max_steps_factor"])
                        * bucket),
                    prefilter_k=self.k_ref, decide=decide),
                nodes, self.cell.config["guarantees"], f"query{j}n{n}")
            numbers.append(ties)
            numbers += compare(f"query{j}n{n}", ref, got,
                               self.cell.config["guarantees"])
        return numbers

    def close(self) -> None:
        self.service.close()
