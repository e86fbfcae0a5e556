"""Driver ``codegen``: one call = one generation of candidate sources
through ``fks_tpu.funsearch.backend.CodeEvaluator.evaluate``.

Every lane runs exactly ``code_eval_max_steps`` lockstep events (the
configuration's step cap), so a call is ``lanes x cap`` lane-events for
every seed; a generation is the traffic file's seed policies plus ledger
champions, and ``--seed`` picks which champions, jitters their weights and
orders the lanes (so the host stages see the same mix of sources too). With more than
one chip the generation is sharded over a population mesh.
"""
from __future__ import annotations

import glob
import json
import os
import re

import numpy as np

from chipbench import cells
from chipbench.drivers import common
from chipbench.reduce import anchor
from chipbench.reference import policies
from chipbench.reference.compare import Output, compare
from chipbench.reference.nearties import admit
from chipbench.reference.plain_sim import simulate

#: a parenthesised float literal, as the ledger's champions carry their
#: weights: ``(0.101951) * (...)``
WEIGHT = re.compile(r"\((-?\d+\.\d+(?:e-?\d+)?)\)")


def jitter(code: str, rng, scale: float) -> str:
    return WEIGHT.sub(
        lambda m: "(%.6g)" % (float(m.group(1))
                              * (1.0 + scale * rng.standard_normal())), code)


class Driver:
    span = "bench/evaluate"

    def __init__(self, cell, seed: int, files: dict, mesh, traced: bool):
        self.cell, self.seed, self.files = cell, seed, files
        self.t = cell.traffic
        self.mesh, self.traced = mesh, traced
        self.last = None
        self._reset()

    def _reset(self) -> None:
        self.call_s = self.device_s = 0.0
        self.events = self.failed = 0
        self.min_lanes = None

    def _sources(self) -> list:
        from fks_tpu.funsearch import template

        pool = []
        for path in sorted(glob.glob(os.path.join(cells.ROOT,
                                                  self.t["ledger_glob"]))):
            with open(path) as f:
                pool.append(json.load(f)["code"])
        fixed = [template.seed_policies()[k] for k in self.t["seed_policies"]]
        rng = np.random.default_rng(self.seed)
        lanes = int(self.t["lanes"])
        # the seed policies are short sources and cost the host stages less
        # than a champion, so every generation holds all of them: which
        # champions fill it, their jitter and the order are the seed's
        picks = rng.choice(len(pool), size=lanes - len(fixed), replace=False)
        out = fixed + [jitter(pool[i], rng, float(self.t["jitter"]))
                       for i in picks]
        return [out[i] for i in rng.permutation(lanes)]

    def setup(self) -> dict:
        from fks_tpu.funsearch.backend import CodeEvaluator
        from fks_tpu.sim.engine import SimConfig

        t0 = common.now()
        self.wl = common.parse_workload(self.cell.config, self.files)
        t1 = common.now()
        self.sources = self._sources()
        self.k = int(self.cell.config["code_eval_max_steps"])
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
                "vm_seg_steps": self.ev.vm_seg_steps,
                "vm_batch": bool(self.ev.vm_batch),
                "program_capacity": self.ev.VM_CAPACITY,
                "shards": 1 if self.mesh is None else self.mesh.size}

    def call(self, i: int) -> dict:
        n0 = len(self.profiler.records) if self.profiler else 0
        t0 = common.now()
        with common.annotate(self.span):
            recs = self.ev.evaluate(self.sources)
        self.call_s += common.now() - t0
        stats = self.ev.last_eval_stats
        lanes = len(self.sources)
        # a candidate the batched tier did not serve, or that came back
        # without a result, is a failed operation
        bad = sum(1 for r in recs if r.result is None
                  or bool(r.result.failed))
        bad = max(bad, lanes - int(stats["vm_batch_lanes"]),
                  int(stats["fallback_lanes"]))
        self.failed += bad
        ev = [int(r.result.events_processed) for r in recs
              if r.result is not None]
        if self.mesh is not None:
            held = self.ev.last_lanes_per_device
            low = min((held.get(d.id, 0) for d in self.mesh.devices.flat),
                      default=0)
            self.min_lanes = low if self.min_lanes is None \
                else min(self.min_lanes, low)
            if low == 0:
                self.failed += 1
        if self.profiler:
            self.device_s += sum(
                r["wall_seconds"] for r in self.profiler.records[n0:]
                if r["stage"] == "device-eval" and r.get("depth", 0) == 0)
        self.events += max(ev, default=0)
        self.last = recs
        return {"lanes": lanes, "lane_events": sum(ev),
                "lockstep_events": max(ev, default=0)}

    @staticmethod
    def device_stage(call, for_s: float):
        """Where the traced slice belongs in a generation: from the launch
        of the batched VM to the end of the wait for it (on four chips the
        segmented runner waits for its segments inside ``launch``)."""
        return anchor.between(call, "tier/vm_batch/launch",
                              "tier/vm_batch/wait_device")

    def counters(self) -> dict:
        out = {"call_seconds": self.call_s, "lockstep_events": self.events}
        if self.profiler:
            out["device_eval_seconds"] = self.device_s
        if self.min_lanes is not None:
            out["min_lanes_per_device"] = self.min_lanes
        return out

    def attempted_failed(self, rows) -> tuple:
        return sum(r["lanes"] for r in rows), self.failed

    def _policy(self, lane: int):
        """The lane's source for the plain reference, in the precision
        the configuration states (``guarantees.score_dtype``)."""
        return policies.source_policy(
            self.sources[lane],
            dtype=self.cell.config["guarantees"]["score_dtype"])

    def check(self) -> list:
        cluster, pods = common.reference_inputs(self.cell.config, self.files)
        numbers = []
        for lane in range(len(self.sources)):
            got = Output.of_lane(self.last[lane].result, pods.p)
            ref, ties = admit(
                lambda decide, lane=lane: simulate(
                    cluster, pods, self._policy(lane),
                    retry=self.cell.config["retry_rule"],
                    max_steps=self.k, decide=decide),
                got.assigned_node, self.cell.config["guarantees"],
                f"lane{lane}")
            numbers.append(ties)
            numbers += compare(f"lane{lane}", ref, got,
                               self.cell.config["guarantees"])
        return numbers

    def close(self) -> None:
        if self.profiler:
            self.profiler.close()
