"""The cell ``openb1523-loaded.codegen8`` run: end to end at tiny size on
the CPU (work per call equal for every seed, counted from the fork), a
program that loses the fork or never took it, and the bfloat16 control of
its comparison at the cell's own size. Device-heavy, and a few items only:
the suite's scheduler queues the files with the most items first, so this
one runs in the tail, after the latency-gated promotion tests are through.
The declaration is ``tests/test_chipbench_loaded_decl.py``."""
import contextlib
import io
import json
import math
import time

import pytest

from chipbench import cells, run
from chipbench.reduce import spans as rs
from chipbench.selftest import control_loaded
from chipbench.selftest.tests import batched_vm_on_cpu
from tests.test_chipbench_loaded_decl import (CELL, COUNTER_METRICS,
                                              SPAN_METRICS)

#: the first 300 arrivals, forked after 200: 48-event generations of 4
TINY = {"config": {"pod_limit": 300, "start_event": 200,
                   "code_eval_max_steps": 48},
        "traffic": {"lanes": 4, "trace_for_s": 0.05}}
#: what ``check`` compares a lane by, and the call by
LANE_NUMBERS = {"placements_differ", "gpu_picks_differ", "scheduled_diff",
                "events_diff", "flags_differ", "snapshots_diff",
                "frag_events_diff", "max_nodes_diff",
                "fitness_at_cap_rel_err"}


def _run(monkeypatch, tmp_path, trace, seed=2 ** 31 + 5):
    from fks_tpu import utils
    from fks_tpu.obs import spans

    spans.LOG.clear()      # generations are counted from the warm-up call
    monkeypatch.setattr(utils, "place_compile_cache", lambda: str(tmp_path))
    monkeypatch.setenv("FKS_VM_SEG_STEPS", "16")
    monkeypatch.setattr(rs, "TOLERANCE", 0.05)     # a loaded test worker
    with batched_vm_on_cpu(), contextlib.redirect_stdout(io.StringIO()) \
            as out:
        res = run.run_cell(CELL, seed, 0.5, trace, require_tpu=False,
                           overrides=TINY)
    return res, [json.loads(line) for line in out.getvalue().splitlines()]


def test_cell_runs_end_to_end_and_counts_from_the_fork(monkeypatch,
                                                       tmp_path):
    res, rows = _run(monkeypatch, tmp_path, trace=True)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0 and res["device"]["platform"] == "cpu"
    setup = next(r for r in rows if r["row"] == "setup")
    assert (setup["node_prefilter_k"], setup["nodes_padded"]) == (64, 1528)
    assert (setup["lanes"], setup["start_event"], setup["max_steps"]) \
        == (4, 200, 248)
    compared = [r for r in rows if r["row"] == "compared"]
    assert all(r["ok"] for r in compared)
    assert {r["name"] for r in compared} == {
        f"lane{i}.{n}" for i in range(4) for n in LANE_NUMBERS} | {
            "call.frag_counter_diff"}
    # the evaluator's state at the cap is compared on something: 10 of
    # the run's 12 snapshots lie before the fork, in the carry it built
    at_cap = [r for r in compared
              if r["name"].endswith("fitness_at_cap_rel_err")]
    assert all(0 <= r["value"] <= r["limit"] == 16 * 2.0 ** -23
               for r in at_cap)
    calls = [r for r in rows if r["row"] == "call"]
    assert calls and all((r["lane_events"], r["lockstep_events"])
                         == (4 * 48, 48) for r in calls)
    for m in SPAN_METRICS + COUNTER_METRICS:
        assert m in res["metrics"], m
        assert math.isfinite(res["metrics"][m]["value"]), m
    v = {m: res["metrics"][m]["value"] for m in res["metrics"]}
    assert v["vm.register_mb"] == 4 * 305 * 64 * 8 * 8 / 1e6
    assert v["sim.fork_state_ms"] > 0
    # the first 300 arrivals do not fill the cluster: no placement fails
    assert v["sim.retry_share"] == 0.0
    # per-event metrics divide by the window's events, not by the prefix
    slots = v["vm.live_slot_share"] / 100 * 256     # a champion's bucket
    assert v["vm.us_per_slot"] == pytest.approx(
        v["vm.device_ms_per_event"] * 1e3 / slots, rel=1e-6)
    call_ms = sum(r["t1"] - r["t0"] for r in calls) / len(calls) * 1e3
    assert v["vm.device_ms_per_event"] * 48 <= call_ms \
        < v["vm.device_ms_per_event"] * 248


@pytest.mark.parametrize("seed", [7, 2 ** 31 + 11])
def test_work_per_call_is_the_same_for_every_seed(monkeypatch, tmp_path,
                                                  seed):
    res, rows = _run(monkeypatch, tmp_path, trace=False, seed=seed)
    assert res["correct"] is True and res["failed"] == 0
    assert set(res["metrics"]) == {"lane_events_per_s", "setup_s"}
    calls = [r for r in rows if r["row"] == "call"]
    assert calls and all(r["lane_events"] == 4 * 48 for r in calls)


def test_a_fork_that_drops_the_residents_is_not_correct(monkeypatch,
                                                        tmp_path):
    """A program that starts at the fork's counters on an EMPTY cluster
    (the residents dropped): every lane places the window's pods
    elsewhere and the residents nowhere, and the comparison says so."""
    from fks_tpu.sim import flat

    real = flat._loaded_leaves
    counters = ("events_processed", "steps", "snap_idx", "snap_sums",
                "max_nodes")

    def dropped(*a, **kw):
        return {k: v for k, v in real(*a, **kw).items() if k in counters}

    monkeypatch.setattr(flat, "_loaded_leaves", dropped)
    res, rows = _run(monkeypatch, tmp_path, trace=False)
    assert res["correct"] is False
    bad = {r["name"].split(".", 1)[1] for r in rows
           if r["row"] == "compared" and not r["ok"]}
    assert {"placements_differ", "scheduled_diff"} <= bad


def test_a_fork_with_other_evaluator_sums_is_not_correct(monkeypatch,
                                                         tmp_path):
    """The residents in place, the evaluator's part of the carry wrong
    (one utilization sum a thousandth high): no placement moves and no
    lane finishes, and the comparison still says so, by the number that
    reads the evaluator at the cap."""
    import jax.numpy as jnp

    from fks_tpu.sim import flat

    real = flat._loaded_leaves

    def skewed(*a, **kw):
        out = real(*a, **kw)
        sums = out["snap_sums"]
        out["snap_sums"] = sums.at[0].multiply(jnp.asarray(1.001,
                                                           sums.dtype))
        return out

    monkeypatch.setattr(flat, "_loaded_leaves", skewed)
    res, rows = _run(monkeypatch, tmp_path, trace=False)
    assert res["correct"] is False and res["failed"] == 0
    bad = {r["name"].split(".", 1)[1] for r in rows
           if r["row"] == "compared" and not r["ok"]}
    assert bad == {"fitness_at_cap_rel_err"}


def test_a_program_that_did_not_fork_is_refused_at_once(monkeypatch,
                                                        tmp_path):
    """An evaluator that starts at event 0 (it ignored the snapshot): the
    run ends before the first device program, with the reason."""
    from fks_tpu.funsearch.backend import CodeEvaluator

    real = CodeEvaluator.__init__

    def init(self, workload, *a, **kw):
        import dataclasses
        real(self, dataclasses.replace(workload, snapshot=None), *a, **kw)

    monkeypatch.setattr(CodeEvaluator, "__init__", init)
    t0 = time.perf_counter()
    with pytest.raises(SystemExit) as e:
        _run(monkeypatch, tmp_path, trace=False)
    assert "starts at event 200" in str(e.value)
    assert "starts at 0" in str(e.value)
    assert time.perf_counter() - t0 < 20


def test_bfloat16_control_is_not_correct_at_the_cells_size():
    """The forked reference against its bfloat16 self, under the rule, to
    the cell's own cap (event 6,912): the comparison fails the generation
    by its identity limit, among UNEQUAL nodes now; the snapshot's own
    placements are data and move in no precision."""
    cell = cells.load_cell(CELL)
    files = cells.verify_files(cell.config)
    d = cells.load_driver("codegen_loaded").Driver(cell, 2 ** 31 + 7, files,
                                                   None, False)
    d.e0 = cell.config["start_event"]
    rows = d.rows()
    assert len(rows) == 5888
    lanes = control_loaded.control_numbers(
        cell.config, files, d._sources(),
        d.e0 + cell.config["code_eval_max_steps"], rows)
    assert len(lanes) == 8

    def failing(what):
        return [ns for ns in lanes if any(
            n.name.endswith(what) and not n.ok for n in ns)]

    assert len(failing("placements_differ")) >= 4
    # bfloat16 evaluator sums: the fitness at the cap is off in every
    # lane, also where integer scores decide and no placement moves
    assert len(failing("fitness_at_cap_rel_err")) == 8
