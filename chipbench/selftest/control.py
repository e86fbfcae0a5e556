"""The control of ``correct``, at a cell's own size, on the machine with
the chip:

    python3 -m chipbench.selftest.control --workload <cell> --seeds 1,2,3

For each seed it builds the cell's driver, makes ``--calls`` whole calls
and prints the sound run's compared numbers after each; then it puts a
LOWER PRECISION in the program's place and prints what the same
comparison says, which has to be "not correct":

- ``population``: the program's own lower-precision paths,
  ``parametric.score(dtype=bfloat16)`` and ``SimConfig(score_dtype=
  bfloat16)`` (the evaluator's sums), run on the chip;
- ``codegen`` / ``whatif``: the VM tiers have no such switch, so the plain
  reference with bfloat16 scores and bfloat16 evaluator sums stands in for
  the program's output.

The last line is ``{"control_failed_everywhere": ..., "sound_ok": ...}``.
The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import functools
import json
import sys

import ml_dtypes

from chipbench import cells
from chipbench.reference import policies
from chipbench.reference.plain_sim import simulate

BF16 = ml_dtypes.bfloat16


def control_outputs(d, kind: str) -> None:
    """Replace the driver's last outputs by the lower-precision ones."""
    from chipbench.drivers import common

    if kind == "population":
        import jax
        import jax.numpy as jnp
        from fks_tpu.models import parametric
        from fks_tpu.parallel import make_population_eval
        from fks_tpu.sim.engine import SimConfig

        cfg = SimConfig(max_steps=d.max_steps, score_dtype=jnp.bfloat16,
                        track_ctime=bool(d.t["track_ctime"]))
        d.eval = make_population_eval(
            d.wl, functools.partial(parametric.score, dtype=jnp.bfloat16),
            cfg=cfg, engine=d.cell.config["engine"])
        d.call(0)
        jax.block_until_ready(d.last)
    elif kind == "codegen":
        cluster, pods = common.reference_inputs(d.cell.config, d.files)
        for lane, rec in enumerate(d.last):
            low = simulate(cluster, pods,
                           policies.source_policy(
                               d.sources[lane], True,
                               dtype=d.cell.config["guarantees"][
                                   "score_dtype"]),
                           retry=d.cell.config["retry_rule"], max_steps=d.k,
                           acc_dtype=BF16)
            rec.result = low
    else:
        queries, answers = d.last
        env = d.engine.envelope
        policy = policies.source_policy(
            d.champion.code, True,
            dtype=d.cell.config["guarantees"]["score_dtype"])
        for (start, rows), a in zip(queries, answers):
            n = len(rows)
            low = simulate(
                d.cluster, d.pods.take(range(start, start + n), query=True),
                policy, retry=d.cell.config["retry_rule"],
                max_steps=max(64, int(d.cell.config["max_steps_factor"])
                              * env.pod_bucket_for(n)), prefilter_k=d.k_ref,
                acc_dtype=BF16)
            a.update(score=low.policy_score, scheduled=low.scheduled_pods,
                     events=low.events_processed, failed=low.failed,
                     truncated=low.truncated)
            a["placements"] = [
                {"pod": i, "node": int(nd),
                 "gpus": [b for b in range(16) if int(g) >> b & 1]}
                for i, (nd, g) in enumerate(zip(low.assigned_node,
                                                low.assigned_gpus))]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--calls", type=int, default=1,
                    help="sound calls checked per seed (what-if queries "
                         "are drawn anew in every call)")
    ap.add_argument("--cpu-tiny", action="store_true",
                    help="selftest sizes on the CPU (no chip needed)")
    a = ap.parse_args(argv)
    overrides = None
    if a.cpu_tiny:
        from chipbench.selftest.tests import TINY, batched_vm_on_cpu
        overrides = TINY
        batched_vm_on_cpu().__enter__()     # for the life of the process
    else:
        import jax
        if jax.devices()[0].platform != "tpu":
            print("control: no TPU", file=sys.stderr)
            return 3
    sound_ok, control_failed = [], []
    for seed in (int(s) for s in a.seeds.split(",")):
        cell = cells.load_cell(a.workload, overrides)
        files = cells.verify_files(cell.config)
        mesh = None
        if cell.chips > 1:
            import jax
            from fks_tpu.parallel import population_mesh
            mesh = population_mesh(jax.devices()[:cell.chips])
        d = cells.load_driver(cell.traffic["driver"]).Driver(
            cell, seed, files, mesh, False)
        try:
            d.setup()
            top, items = {}, 0
            for i in range(a.calls):
                d.call(i)
                numbers = d.check()
                sound_ok.append(all(n.ok for n in numbers))
                items += len({n.name.split(".", 1)[0] for n in numbers})
                for n in numbers:
                    k = n.name.split(".", 1)[1]
                    top[k] = max(top.get(k, 0.0), n.value)
            print(json.dumps({"seed": seed, "run": "sound", "calls": a.calls,
                              "checked_items": items, "largest": top}),
                  flush=True)
            control_outputs(d, cell.traffic["driver"])
            numbers = d.check()
            control_failed.append(not all(n.ok for n in numbers))
            low = {}
            for n in numbers:    # the smallest the control gives, per lane
                k = n.name.split(".", 1)[1]
                low.setdefault(k, []).append(n.value)
            print(json.dumps({"seed": seed, "run": "control",
                              "per_checked_item": low,
                              "failed_numbers": sorted(
                                  {n.name.split(".", 1)[1]
                                   for n in numbers if not n.ok})}),
                  flush=True)
        finally:
            d.close()
    print(json.dumps({"control_failed_everywhere": all(control_failed),
                      "sound_ok": all(sound_ok)}), flush=True)
    return 0 if all(control_failed) and all(sound_ok) else 1


if __name__ == "__main__":
    sys.exit(main())
