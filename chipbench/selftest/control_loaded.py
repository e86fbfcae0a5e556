"""The control of ``correct`` for the ``codegen_loaded`` driver, in
``control_cluster``'s way (that module hands the reference no snapshot):

    python3 -m chipbench.selftest.control_loaded --seeds 1,2,3

For each seed it builds the cell's driver, makes one whole call and prints
the sound run's compared numbers; then the plain reference forked from the
same snapshot with bfloat16 scores and bfloat16 evaluator sums stands in
for the program's output (the VM tier has no lower-precision switch), and
the same comparison has to say "not correct". The snapshot's own
placements are data and move in no precision; what moves is every
placement a float decides after the fork, now among UNEQUAL nodes. It
prints how many lanes fail and by how many placements. The last line is
``{"control_failed_everywhere": ..., "sound_ok": ...}`` ("everywhere":
every seed). The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys

import ml_dtypes

from chipbench import cells
from chipbench.drivers import common
from chipbench.reference import policies
from chipbench.drivers.codegen_loaded import compare_whole
from chipbench.reference.nearties import admit
from chipbench.reference.plain_sim_loaded import simulate_from
from chipbench.selftest.control_cluster import _largest

CELL = "openb1523-loaded.codegen8"


def control_numbers(config: dict, files: dict, sources: list,
                    max_steps: int, rows: dict) -> list:
    """Per lane, the comparison of the forked reference with its bfloat16
    self; ``max_steps`` is absolute (the snapshot's events count)."""
    cluster, pods = common.reference_inputs(config, files)
    kw = dict(retry=config["retry_rule"], max_steps=max_steps,
              prefilter_k=int(config["node_prefilter_k"]))
    dtype = config["guarantees"]["score_dtype"]
    out = []
    for lane, code in enumerate(sources):
        low = simulate_from(cluster, pods, rows,
                            policies.source_policy(code, True, dtype=dtype),
                            acc_dtype=ml_dtypes.bfloat16, **kw)
        ref, ties = admit(
            lambda decide, code=code: simulate_from(
                cluster, pods, rows,
                policies.source_policy(code, dtype=dtype), decide=decide,
                **kw),
            low.assigned_node, config["guarantees"], f"lane{lane}")
        out.append([ties] + compare_whole(f"lane{lane}", ref, low, pods.p,
                                          config["guarantees"]))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", default=CELL)
    ap.add_argument("--seeds", required=True)
    a = ap.parse_args(argv)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("control: no TPU", file=sys.stderr)
        return 3
    sound_ok, control_failed = [], []
    for seed in (int(s) for s in a.seeds.split(",")):
        cell = cells.load_cell(a.workload)
        files = cells.verify_files(cell.config)
        d = cells.load_driver(cell.traffic["driver"]).Driver(
            cell, seed, files, None, False)
        try:
            d.setup()
            d.call(0)
            numbers = d.check()
        finally:
            d.close()
        sound_ok.append(all(n.ok for n in numbers))
        print(json.dumps({"seed": seed, "run": "sound",
                          "checked_items": len(d.sources),
                          "largest": _largest(numbers)}), flush=True)
        lanes = control_numbers(cell.config, files, d.sources, d.k, d.rows())
        failing = [i for i, ns in enumerate(lanes)
                   if not all(n.ok for n in ns)]
        control_failed.append(bool(failing))
        print(json.dumps({
            "seed": seed, "run": "control", "lanes": len(lanes),
            "lanes_failing": len(failing),
            "placements_moved": [int(n.value) for ns in lanes for n in ns
                                 if n.name.endswith("placements_differ")],
            "largest": _largest([n for ns in lanes for n in ns]),
            "failed_numbers": sorted({n.name.split(".", 1)[1]
                                      for ns in lanes for n in ns
                                      if not n.ok})}), flush=True)
    print(json.dumps({"control_failed_everywhere": all(control_failed),
                      "sound_ok": all(sound_ok)}), flush=True)
    return 0 if all(control_failed) and all(sound_ok) else 1


if __name__ == "__main__":
    sys.exit(main())
