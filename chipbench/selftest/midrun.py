"""The cell ``openb16-cpu250-midrun.codegen8`` at a tiny size on the CPU,
and the control of its ``correct`` at the cell's own size:

    python3 -m chipbench.selftest.midrun
    python3 -m chipbench.selftest.midrun --control --seeds 1,2,3

A moment of a run has no prefix by pod count (cpu250's first placement
fails at event 3,792), so the tiny size is a deployment of its own:
``tiny_deployment`` writes, under a directory it is given, rows
``NODES`` of the configuration's node list, the first ``PODS``
arrivals of its trace, and the snapshot of the first ``E0`` events of the
program's ``first_fit`` run of them (``flat.make_snapshot``, written with
``write_snapshot_csv_gz``; the reference reads the file with its own
reader), and returns the overrides that lay those files over the cell's.
``E0`` is chosen where the tiny prefix holds what the real one holds:
departures, refused placements and a pod waiting with its retry queued.
``run_tiny`` then drives everything of ``chipbench.run.run_cell`` but the
look for a chip. ``tests/test_chipbench_midrun_cell.py`` runs it in
tier-1, so the driver's ``check`` runs against the plain reference on
every change.

``--control`` needs no chip: per seed, the plain reference forked from
the committed snapshot with bfloat16 scores (``scores``), and with
bfloat16 evaluator sums as well (``scores+sums``), stands in for the
program's output at the cell's own size, and the cell's comparison has to
say "not correct" of each. It prints how many lanes fail and by what.
Nothing here prints a number under the name of a device metric.
"""
from __future__ import annotations

import argparse
import contextlib
import csv
import gzip
import hashlib
import io
import json
import os
import shutil
import sys

CELL = "openb16-cpu250-midrun.codegen8"
#: the tiny deployment: the six large nodes of the 16 (rows 10-15, 44 of
#: the 48 GPUs) under the first 500 arrivals, forked after 320 events
#: (139 departures, 9 refused placements, 1 pod waiting, 33 residents;
#: the next 48 events hold 13 more refusals under first_fit), 48-event
#: generations of 4 lanes
NODES, PODS, E0, WINDOW, LANES = range(10, 16), 500, 320, 48, 4


def _read(path: str) -> list:
    with io.TextIOWrapper(gzip.open(path, "rb"), newline="") as f:
        return list(csv.reader(f))


def _pin(path: str) -> dict:
    with open(path, "rb") as f:
        return {"file": path, "sha256": hashlib.sha256(f.read()).hexdigest()}


def tiny_deployment(directory: str) -> dict:
    """Write the tiny deployment's files under ``directory`` (a traces
    directory: the GPU map beside ``csv/``) and return the ``overrides``
    of ``cells.load_cell`` that run the cell on them."""
    from chipbench import cells
    from fks_tpu.data import TraceParser
    from fks_tpu.data.snapshot import write_snapshot_csv_gz
    from fks_tpu.models import zoo
    from fks_tpu.sim import flat

    files = cells.verify_files(cells.load_cell(CELL).config)
    out = os.path.join(directory, "csv")
    os.makedirs(out, exist_ok=True)
    shutil.copy(files["gpu_mem_mapping"], directory)
    nodes = _read(files["cluster"])
    nodes = nodes[:1] + [nodes[1 + i] for i in NODES]
    head, *pods = _read(files["trace"])
    at = head.index("creation_time")
    pods = sorted(pods, key=lambda r: (int(r[at]), r[0]))[:PODS]
    names = {"cluster": "nodes.csv", "trace": "pods.csv",
             "snapshot": "snapshot.csv.gz"}
    for key, rows in (("cluster", nodes), ("trace", [head] + pods)):
        with open(os.path.join(out, names[key]), "w", newline="") as f:
            csv.writer(f, lineterminator="\n").writerows(rows)
    wl = TraceParser(directory).parse_workload(names["cluster"],
                                               names["trace"])
    write_snapshot_csv_gz(
        wl, flat.make_snapshot(wl, zoo.first_fit(), E0),
        os.path.join(out, names["snapshot"]))
    config = {k: _pin(os.path.join(out, n)) for k, n in names.items()}
    config["gpu_mem_mapping"] = _pin(
        os.path.join(directory, os.path.basename(files["gpu_mem_mapping"])))
    config.update(start_event=E0, code_eval_max_steps=WINDOW)
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
                    max_steps: int, sums: bool) -> list:
    """Per lane, the comparison of the forked reference with its bfloat16
    self (scores, and the evaluator's sums too where ``sums``);
    ``max_steps`` is absolute."""
    import ml_dtypes
    import numpy as np

    from chipbench.drivers import common
    from chipbench.drivers.codegen_loaded import compare_whole
    from chipbench.reference import plain_sim_midrun as mid
    from chipbench.reference import policies
    from chipbench.reference.nearties import admit

    cluster, pods = common.reference_inputs(config, files)
    log = mid.load_log(files["snapshot"], files["cluster"], files["trace"])
    mid.validate(cluster, pods, log, config["retry_rule"])
    kw = dict(retry=config["retry_rule"], max_steps=max_steps)
    dtype = config["guarantees"]["score_dtype"]
    out = []
    for lane, code in enumerate(sources):
        low = mid.simulate_from(
            cluster, pods, log,
            policies.source_policy(code, True, dtype=dtype),
            acc_dtype=ml_dtypes.bfloat16 if sums else np.float32, **kw)
        ref, ties = admit(
            lambda decide, code=code: mid.simulate_from(
                cluster, pods, log,
                policies.source_policy(code, dtype=dtype), decide=decide,
                **kw),
            low.assigned_node, config["guarantees"], f"lane{lane}")
        out.append([ties] + compare_whole(f"lane{lane}", ref, low, pods.p,
                                          config["guarantees"]))
    return out


def control(seeds) -> int:
    from chipbench import cells
    from chipbench.selftest.control_cluster import _largest

    cell = cells.load_cell(CELL)
    files = cells.verify_files(cell.config)
    cap = cell.config["start_event"] + cell.config["code_eval_max_steps"]
    failed = []
    for seed in seeds:
        d = cells.load_driver(cell.traffic["driver"]).Driver(
            cell, seed, files, None, False)
        for sums in (False, True):
            lanes = control_numbers(cell.config, files, d._sources(), cap,
                                    sums)
            failing = [i for i, ns in enumerate(lanes)
                       if not all(n.ok for n in ns)]
            failed.append(bool(failing))
            print(json.dumps({
                "seed": seed, "run": "scores+sums" if sums else "scores",
                "lanes": len(lanes), "lanes_failing": len(failing),
                "placements_moved": [
                    int(n.value) for ns in lanes for n in ns
                    if n.name.endswith("placements_differ")],
                "largest": _largest([n for ns in lanes for n in ns]),
                "failed_numbers": sorted({
                    n.name.split(".", 1)[1] for ns in lanes for n in ns
                    if not n.ok})}), flush=True)
    print(json.dumps({"control_failed_everywhere": all(failed)}), flush=True)
    return 0 if all(failed) else 1


def main(argv=None) -> int:
    import tempfile

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--seeds", default="1")
    a = ap.parse_args(argv)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    if a.control:
        return control([int(s) for s in a.seeds.split(",")])
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
            print(f"{'PASS' if ok else 'FAIL'} midrun trace={int(trace)}",
                  flush=True)
    print(f"{'FAILED' if bad else 'ok'}: {bad} failed")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
