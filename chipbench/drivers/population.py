"""Driver ``population``: one call = one parametric population through
``fks_tpu.parallel.make_population_eval``.

The population is the traffic file's pinned CSV (``population_file``),
so how many lanes complete and how many lockstep steps a call runs do not
depend on ``--seed``; the seed permutes the lanes and picks the checked
ones (one that completed, one that hit the step cap).
"""
from __future__ import annotations

import os

import numpy as np

from chipbench import cells
from chipbench.drivers import common
from chipbench.reference import policies
from chipbench.reference.compare import Output, compare
from chipbench.reference.nearties import admit
from chipbench.reference.plain_sim import simulate


class Driver:
    span = "bench/population_eval"

    def __init__(self, cell, seed: int, files: dict, mesh, traced: bool):
        self.cell, self.seed, self.files = cell, seed, files
        self.t = cell.traffic
        self.last = None
        self._reset()

    def _reset(self) -> None:
        self.call_s = 0.0
        self.steps = self.truncated = self.lanes_run = self.failed = 0

    def setup(self) -> dict:
        import jax
        from fks_tpu.parallel import make_population_eval
        from fks_tpu.sim.engine import SimConfig

        t0 = common.now()
        self.wl = common.parse_workload(self.cell.config, self.files)
        t1 = common.now()
        lanes = int(self.t["lanes"])
        pop = np.loadtxt(os.path.join(cells.ROOT, self.t["population_file"]),
                         delimiter=",", skiprows=1, dtype=np.float32)
        if len(pop) < lanes:
            raise SystemExit(f"{self.t['population_file']} holds {len(pop)} "
                             f"lanes, the traffic asks for {lanes}")
        rng = np.random.default_rng(self.seed)
        self.weights = pop[:lanes][rng.permutation(lanes)]
        self.rng = rng
        self.params = jax.device_put(self.weights)
        self.max_steps = (int(self.cell.config["param_eval_max_steps_factor"])
                          * self.wl.num_pods)
        self.eval = make_population_eval(
            self.wl, cfg=SimConfig(max_steps=self.max_steps,
                                   track_ctime=bool(self.t["track_ctime"])),
            engine=self.cell.config["engine"])
        t2 = common.now()
        self.call(-1)                      # warm-up: compiles, not counted
        self._reset()
        return {"parse_s": t1 - t0, "build_s": t2 - t1,
                "warm_call_s": common.now() - t2,
                "lanes": lanes, "max_steps": self.max_steps,
                "pods": self.wl.num_pods, "nodes": self.wl.num_nodes}

    def call(self, i: int) -> dict:
        import jax

        t0 = common.now()
        with common.annotate(self.span):
            res = self.eval(self.params)
            jax.block_until_ready(res)
            trunc = np.asarray(res.truncated)
            bad = np.asarray(res.failed)
            steps = int(np.asarray(res.events_processed).max())
        self.call_s += common.now() - t0
        self.last = res
        self.steps += steps
        self.truncated += int(trunc.sum())
        self.lanes_run += len(trunc)
        self.failed += int(bad.sum())
        return {"lanes": len(trunc), "evals": int((~trunc & ~bad).sum()),
                "lockstep_steps": steps}

    @staticmethod
    def device_stage(call, for_s: float):
        """The call is one device program: the traced slice may lie
        anywhere in it."""
        return call.t0, call.t1

    def counters(self) -> dict:
        return {"call_seconds": self.call_s, "lockstep_steps": self.steps,
                "truncated_lanes": self.truncated, "lanes": self.lanes_run}

    def attempted_failed(self, rows) -> tuple:
        return sum(r["lanes"] for r in rows), self.failed

    def check(self) -> list:
        import jax

        res = jax.device_get(self.last)
        trunc = np.asarray(res.truncated)
        p = self.wl.num_pods
        picks = []
        for want in (False, True):      # one completed, one truncated
            pool = np.nonzero(trunc == want)[0]
            if len(pool):
                picks.append(int(self.rng.choice(pool)))
        cluster, pods = common.reference_inputs(self.cell.config, self.files)
        if pods.p != p:
            raise SystemExit(f"reference parsed {pods.p} pods, program {p}")
        numbers = []
        for lane in picks:
            got = Output.of_lane(res, p, lane)
            tag = f"lane{lane}" + ("t" if got.truncated else "c")
            ref, ties = admit(
                lambda decide, lane=lane: simulate(
                    cluster, pods,
                    policies.parametric_policy(self.weights[lane]),
                    retry=self.cell.config["retry_rule"],
                    max_steps=self.max_steps, decide=decide),
                got.assigned_node, self.cell.config["guarantees"], tag)
            numbers.append(ties)
            numbers += compare(tag, ref, got,
                               self.cell.config["guarantees"])
        return numbers

    def close(self) -> None:
        pass
