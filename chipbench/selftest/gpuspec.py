"""The cell ``openb1523-gpuspec25-loaded.codegen8`` at a tiny size on the
CPU, and the controls of its ``correct`` at the cell's own size:

    python3 -m chipbench.selftest.gpuspec
    python3 -m chipbench.selftest.gpuspec --control --seeds 1,2,3
    python3 -m chipbench.selftest.gpuspec --mask-lost --seeds 1,2

The tiny size is a deployment of its own (``tiny_deployment``): every
sixth row of the configuration's node list (254 nodes, 256 padded, so the
program's large-cluster rule still engages; six of the seven GPU models),
the first ``PODS`` arrivals of its trace, and the snapshot of the first
``E0`` events of the program's ``best_fit`` run of them with the
constraints honoured (``flat.make_snapshot``; the reference reads the
file with its own reader). ``E0`` is chosen where the tiny prefix holds
what the real one holds, and more: refused placements, constrained pods
waiting with their retries queued. ``run_tiny`` then drives everything of
``chipbench.run.run_cell`` but the look for a chip;
``tests/test_chipbench_gpuspec_cell.py`` runs it in tier-1.

``--control`` needs no chip: per seed, at the cell's own size, the plain
reference forked from the committed snapshot stands in for the program's
output three times, and the cell's comparison has to say "not correct" of
each: with bfloat16 scores (``scores``), with bfloat16 evaluator sums as
well (``scores+sums``), and with the type constraints LOST after the fork
(``mask_lost``: every node allowed, what a program that parses the column
away computes). ``--mask-lost`` makes the third with the program itself,
on whatever backend JAX has (the builder's chip runs): the cell's driver
with the workload parsed WITHOUT the choice and its refusal of such a
program switched off, one whole call, compared with the constrained
reference: identity has to fail in every lane. Nothing here prints a
number under the name of a device metric.
"""
from __future__ import annotations

import argparse
import contextlib
import csv
import io
import json
import os
import shutil
import sys

from chipbench.selftest.midrun import _pin, _read

CELL = "openb1523-gpuspec25-loaded.codegen8"
#: the tiny deployment: every sixth node (254 of 1,523; 1,007 GPUs) under
#: the first 640 arrivals (138 of them constrained), forked after 512
#: events of best_fit's run (refusals and waiting constrained pods in the
#: prefix; the next 48 events hold more), 48-event generations of 4 lanes
NODE_STEP, PODS, E0, WINDOW, LANES = 6, 640, 512, 48, 4
RUNS = ("scores", "scores+sums", "mask_lost")


def tiny_deployment(directory: str) -> dict:
    """Write the tiny deployment's files under ``directory`` (a traces
    directory: the GPU map beside ``csv/``) and return the ``overrides``
    of ``cells.load_cell`` that run the cell on them."""
    import numpy as np

    from chipbench import cells
    from fks_tpu.data import TraceParser
    from fks_tpu.data.snapshot import write_snapshot_csv_gz
    from fks_tpu.models import zoo
    from fks_tpu.sim import flat
    from fks_tpu.sim.engine import SimConfig, shape_prefilter_k

    files = cells.verify_files(cells.load_cell(CELL).config)
    out = os.path.join(directory, "csv")
    os.makedirs(out, exist_ok=True)
    shutil.copy(files["gpu_mem_mapping"], directory)
    nodes = _read(files["cluster"])
    nodes = nodes[:1] + nodes[1::NODE_STEP]
    pods = _read(files["trace"])[:1 + PODS]      # in arrival order
    names = {"cluster": "nodes.csv", "trace": "pods.csv",
             "snapshot": "snapshot.csv.gz"}
    for key, rows in (("cluster", nodes), ("trace", pods)):
        with open(os.path.join(out, names[key]), "w", newline="") as f:
            csv.writer(f, lineterminator="\n").writerows(rows)
    wl = TraceParser(directory).parse_workload(
        names["cluster"], names["trace"], gpu_spec="honor")
    k = shape_prefilter_k(wl.cluster.n_padded)
    write_snapshot_csv_gz(
        wl, flat.make_snapshot(wl, zoo.best_fit(), E0,
                               SimConfig(node_prefilter_k=k)),
        os.path.join(out, names["snapshot"]))
    config = {key: _pin(os.path.join(out, n)) for key, n in names.items()}
    config["gpu_mem_mapping"] = _pin(
        os.path.join(directory, os.path.basename(files["gpu_mem_mapping"])))
    config.update(
        start_event=E0, code_eval_max_steps=WINDOW, node_prefilter_k=k,
        node_models=list(wl.cluster.gpu_models),
        typed_pods=int(np.count_nonzero(
            np.asarray(wl.pods.gpu_spec)[np.asarray(wl.pods.pod_mask)])))
    return {"config": config,
            "traffic": {"lanes": LANES, "trace_for_s": 0.05}}


def run_tiny(directory: str, seed: int = 2 ** 31 + 7, trace: bool = False,
             seconds: float = 0.5, overrides=None):
    """(result line, the rows printed before it); the batched VM tier is
    asked for, as the selftest's CPU has to."""
    from chipbench import run
    from chipbench.selftest.tests import batched_vm_on_cpu

    overrides = overrides or tiny_deployment(directory)
    with batched_vm_on_cpu(), contextlib.redirect_stdout(io.StringIO()) \
            as out:
        res = run.run_cell(CELL, seed, seconds, trace, require_tpu=False,
                           overrides=overrides)
    return res, [json.loads(line) for line in out.getvalue().splitlines()]


def control_numbers(config: dict, files: dict, sources: list,
                    max_steps: int, run: str) -> list:
    """Per lane, the comparison of the forked reference with a stand-in
    for a faulty program (``RUNS``): its bfloat16 self (scores, and the
    evaluator's sums too), or itself with every node allowed after the
    fork; ``max_steps`` is absolute."""
    import ml_dtypes
    import numpy as np

    from chipbench.drivers import common
    from chipbench.drivers.codegen_loaded import compare_whole
    from chipbench.reference import plain_sim_gpuspec as gs
    from chipbench.reference import policies
    from chipbench.reference.nearties import admit

    cluster, pods = common.reference_inputs(config, files)
    allowed = gs.load_allowed(files["cluster"], files["trace"])
    log = gs.load_log(files["snapshot"], files["cluster"], files["trace"])
    gs.validate(cluster, pods, allowed, log, config["retry_rule"])
    kw = dict(retry=config["retry_rule"], max_steps=max_steps,
              prefilter_k=int(config["node_prefilter_k"]))
    dtype = config["guarantees"]["score_dtype"]
    lost = run == "mask_lost"
    faulty = allowed
    if lost:
        # every decision AFTER the fork sees every node; the pods the log
        # placed keep their rows, so the prefix's candidate cut is the
        # log's own (a refused attempt is refused under any mask)
        faulty = np.ones_like(allowed)
        placed = [i for i, node, _ in log.attempts if node >= 0]
        faulty[placed] = allowed[placed]
    out = []
    for lane, code in enumerate(sources):
        low = gs.simulate_from(
            cluster, pods, faulty, log,
            policies.source_policy(code, not lost, dtype=dtype),
            acc_dtype=ml_dtypes.bfloat16 if run == "scores+sums"
            else np.float32, **kw)
        ref, ties = admit(
            lambda decide, code=code: gs.simulate_from(
                cluster, pods, allowed, log,
                policies.source_policy(code, dtype=dtype), decide=decide,
                **kw),
            low.assigned_node, config["guarantees"], f"lane{lane}")
        out.append([ties] + compare_whole(f"lane{lane}", ref, low, pods.p,
                                          config["guarantees"]))
    return out


def _report(seed, run, lanes) -> bool:
    from chipbench.selftest.control_cluster import _largest

    failing = [i for i, ns in enumerate(lanes)
               if not all(n.ok for n in ns)]
    print(json.dumps({
        "seed": seed, "run": run, "lanes": len(lanes),
        "lanes_failing": len(failing),
        "placements_moved": [
            int(n.value) for ns in lanes for n in ns
            if n.name.endswith("placements_differ")],
        "largest": _largest([n for ns in lanes for n in ns]),
        "failed_numbers": sorted({
            n.name.split(".", 1)[1] for ns in lanes for n in ns
            if not n.ok})}), flush=True)
    return len(failing) == len(lanes) if run.startswith("mask_lost") \
        else bool(failing)


def control(seeds) -> int:
    from chipbench import cells

    cell = cells.load_cell(CELL)
    files = cells.verify_files(cell.config)
    cap = cell.config["start_event"] + cell.config["code_eval_max_steps"]
    failed = []
    for seed in seeds:
        d = cells.load_driver(cell.traffic["driver"]).Driver(
            cell, seed, files, None, False)
        for run in RUNS:
            failed.append(_report(seed, run, control_numbers(
                cell.config, files, d._sources(), cap, run)))
    print(json.dumps({"control_failed_everywhere": all(failed)}), flush=True)
    return 0 if all(failed) else 1


def mask_lost_driver(cell, seed, files, mesh=None, traced=False):
    """The cell's driver over a program that lost the constraints: the
    workload parsed WITHOUT the choice, the driver's refusal of it off."""
    from chipbench import cells

    base = cells.load_driver(cell.traffic["driver"]).Driver

    class MaskLost(base):
        gpu_spec = "ignore"

        def _require_types(self) -> None:
            pass

    return MaskLost(cell, seed, files, mesh, traced)


def mask_lost(seeds, overrides=None) -> int:
    """One whole call of the program without the constraints, compared
    with the constrained reference, per seed: every lane has to fail."""
    from chipbench import cells

    failed = []
    for seed in seeds:
        cell = cells.load_cell(CELL, overrides)
        files = cells.verify_files(cell.config)
        d = mask_lost_driver(cell, seed, files)
        try:
            d.setup()
            d.call(0)
            numbers = d.check()
        finally:
            d.close()
        lanes = [[n for n in numbers if n.name.startswith(f"lane{i}.")]
                 for i in range(len(d.sources))]
        failed.append(_report(seed, "mask_lost_program", lanes))
    print(json.dumps({"mask_lost_failed_every_lane": all(failed)}),
          flush=True)
    return 0 if all(failed) else 1


def main(argv=None) -> int:
    import tempfile

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--mask-lost", action="store_true")
    ap.add_argument("--seeds", default="1")
    a = ap.parse_args(argv)
    seeds = [int(s) for s in a.seeds.split(",")]
    if a.mask_lost:
        return mask_lost(seeds)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    if a.control:
        return control(seeds)
    bad = 0
    with tempfile.TemporaryDirectory() as d:
        overrides = tiny_deployment(d)
        for trace in (False, True):
            res, rows = run_tiny(d, trace=trace, overrides=overrides)
            calls = [r for r in rows if r["row"] == "call"]
            ok = (res["correct"] is True and res["failed"] == 0
                  and res["device"]["platform"] == "cpu" and calls
                  and all(r["lane_events"] == LANES * WINDOW for r in calls))
            bad += not ok
            print(f"{'PASS' if ok else 'FAIL'} gpuspec trace={int(trace)}",
                  flush=True)
    print(f"{'FAILED' if bad else 'ok'}: {bad} failed")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
