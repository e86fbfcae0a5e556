"""The cell ``openb1523-gpuspec25-loaded.whatif8`` at a tiny size on the
CPU, and the controls of its ``correct``:

    python3 -m chipbench.selftest.whatif_gpuspec
    python3 -m chipbench.selftest.whatif_gpuspec --control --cpu-tiny --seeds 5
    python3 -m chipbench.selftest.whatif_gpuspec --control --seeds 1,2

The tiny size is a deployment of its own (``tiny_deployment``, as
``selftest/gpuspec.py`` makes one): every sixth row of the configuration's
node list (254 nodes, 256 padded, six of the seven GPU models), the first
``PODS`` arrivals of its trace, and the snapshot of the first ``E0``
arrivals as the program's ``first_fit`` places them with the constraints
honoured (no refusal before arrival 724 there). From ``E0`` the ledger's
champion fails placements for want of a TYPE within the 64-bucket's 128
events, and a pod still waits at the cut. ``run_tiny`` drives everything
of ``chipbench.run.run_cell`` but the look for a chip;
``tests/test_chipbench_whatif_gpuspec.py`` runs it in tier-1.

``--control`` makes, per seed, one whole call of the cell's driver and
prints the sound run's compared numbers; then the cell's comparison has to
say "not correct" three times: of the plain reference in bfloat16 scores
(``scores``) and in bfloat16 scores and evaluator sums (``scores+sums``)
standing in for each answer (``control_whatif_loaded``'s stand-ins), and
of THE PROGRAM sent the same queries WITHOUT their ``gpu_spec``
(``field_lost``: what a serving schema without the field computes),
compared with the typed reference: identity has to fail in the whole
backlog's lane. Without ``--cpu-tiny`` it wants the chip and runs at the
cell's own size (the builder's chip runs). Nothing here prints a number
under the name of a device metric.
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

CELL = "openb1523-gpuspec25-loaded.whatif8"
#: every sixth node (254 of 1,523) under the first 736 arrivals, forked
#: after 672 of them (first_fit's first refusal there is arrival 724): the
#: backlog is 64 pods, 13 of them constrained
NODE_STEP, PODS, E0 = 6, 736, 672
SIZES = [4, 12, 40, 64]
#: lockstep events of a tiny call: budgets of the buckets 16 and 64
EVENTS = 64 + 128
RUNS = ("scores", "scores+sums", "field_lost")


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
    snap = flat.make_snapshot(wl, zoo.first_fit(), E0,
                              SimConfig(node_prefilter_k=k))
    write_snapshot_csv_gz(wl, snap, os.path.join(out, names["snapshot"]))
    config = {key: _pin(os.path.join(out, n)) for key, n in names.items()}
    config["gpu_mem_mapping"] = _pin(
        os.path.join(directory, os.path.basename(files["gpu_mem_mapping"])))
    spec = np.asarray(wl.pods.gpu_spec)[np.asarray(wl.pods.pod_mask)]
    config.update(
        start_event=E0, node_prefilter_k=k,
        node_models=list(wl.cluster.gpu_models),
        typed_pods=int(np.count_nonzero(spec)),
        typed_residents=int(np.count_nonzero(spec[np.asarray(snap.pod)])),
        typed_backlog=int(np.count_nonzero(spec[E0:])))
    return {"config": config,
            "traffic": {"sizes": SIZES, "max_batch": 4, "max_wait_s": 2.0,
                        "trace_for_s": 0.05}}


def run_tiny(directory: str, seed: int = 2 ** 31 + 7, trace: bool = False,
             seconds: float = 0.5, overrides=None):
    """(result line, the rows printed before it)."""
    from chipbench import run

    overrides = overrides or tiny_deployment(directory)
    with contextlib.redirect_stdout(io.StringIO()) as out:
        res = run.run_cell(CELL, seed, seconds, trace, require_tpu=False,
                           overrides=overrides)
    return res, [json.loads(line) for line in out.getvalue().splitlines()]


def control(seeds, overrides=None) -> int:
    """Module docstring, ``--control``. The last line is
    ``{"control_failed_everywhere", "scores_fail_identity_everywhere",
    "field_lost_fails_the_backlog_everywhere", "sound_ok"}``
    ("everywhere": every seed)."""
    from chipbench import cells
    from chipbench.selftest.control_cluster import _largest
    from chipbench.selftest.control_whatif_loaded import (
        _report, control_answers)

    sound, failed, scores_identity, lost_backlog = [], [], [], []
    for seed in seeds:
        cell = cells.load_cell(CELL, overrides)
        files = cells.verify_files(cell.config)
        d = cells.load_driver(cell.traffic["driver"]).Driver(
            cell, seed, files, None, False)
        try:
            d.setup()
            d.call(0)
            numbers = d.check()
            sound.append(all(n.ok for n in numbers) and d.failed == 0)
            print(json.dumps({"seed": seed, "run": "sound",
                              "checked_items": len(d.sizes),
                              "failed_operations": d.failed,
                              "largest": _largest(numbers)}), flush=True)
            kept = d.last
            for sums in (False, True):
                d.last = (kept[0], [dict(a) for a in kept[1]])
                control_answers(d, sums)
                got = _report(seed, RUNS[sums], d.check())
                print(json.dumps(got), flush=True)
                failed.append(got["queries_failing"] > 0)
                if not sums:
                    scores_identity.append(
                        got["queries_failing_identity"] > 0)
            # the program itself, the field lost on the way in: the same
            # driver sends its next call's queries without gpu_spec
            d.send_spec = False
            d.call(1)
            numbers = d.check()
            got = _report(seed, RUNS[2], numbers)
            got["backlog_placements_moved"] = [
                int(n.value) for n in numbers if n.name.endswith(
                    f"n{max(d.sizes)}.placements_differ")]
            print(json.dumps(got), flush=True)
            failed.append(got["queries_failing"] > 0)
            lost_backlog.append(any(got["backlog_placements_moved"]))
        finally:
            d.close()
    verdict = {"control_failed_everywhere": all(failed),
               "scores_fail_identity_everywhere": all(scores_identity),
               "field_lost_fails_the_backlog_everywhere": all(lost_backlog),
               "sound_ok": all(sound)}
    print(json.dumps(verdict), flush=True)
    return 0 if all(verdict.values()) else 1


def main(argv=None) -> int:
    import tempfile

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--cpu-tiny", action="store_true",
                    help="selftest sizes on the CPU (no chip needed)")
    ap.add_argument("--seeds", default="1")
    a = ap.parse_args(argv)
    seeds = [int(s) for s in a.seeds.split(",")]
    if a.control and not a.cpu_tiny:
        import jax
        if jax.devices()[0].platform != "tpu":
            print("control: no TPU", file=sys.stderr)
            return 3
        return control(seeds)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    with tempfile.TemporaryDirectory() as d:
        overrides = tiny_deployment(d)
        if a.control:
            return control(seeds, overrides)
        bad = 0
        for trace in (False, True):
            res, rows = run_tiny(d, trace=trace, overrides=overrides)
            calls = [r for r in rows if r["row"] == "call"]
            ok = (res["correct"] is True and res["failed"] == 0
                  and res["device"]["platform"] == "cpu" and calls
                  and all(r["lockstep_events"] == EVENTS for r in calls))
            bad += not ok
            print(f"{'PASS' if ok else 'FAIL'} whatif_gpuspec "
                  f"trace={int(trace)}", flush=True)
    print(f"{'FAILED' if bad else 'ok'}: {bad} failed")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
