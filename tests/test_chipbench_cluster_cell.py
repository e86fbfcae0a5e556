"""The cell ``openb1523-inflated.codegen8``: end to end at tiny size on
the CPU, its refusal of a program that does not choose the large-cluster
rule, and the bfloat16 control of its comparison."""
import contextlib
import io
import json
import math
import os
import time

import pytest

from chipbench import cells, run
from chipbench.reduce import spans as rs
from chipbench.selftest import control_cluster
from chipbench.selftest.tests import batched_vm_on_cpu

CELL = "openb1523-inflated.codegen8"
#: the first 150 arrivals, 48-event generations of 4 lanes
TINY = {"config": {"pod_limit": 150, "code_eval_max_steps": 48},
        "traffic": {"lanes": 4, "trace_for_s": 0.05}}
SPAN_METRICS = ("tier.preflight_ms_per_call", "tier.transpile_ms_per_call",
                "tier.harvest_ms_per_call", "tier.unattributed_share",
                "vm.device_ms_per_event", "vm.live_slot_share",
                "vm.us_per_slot", "vm.register_mb",
                "tier.traces_per_source", "vm.ops_kept_share",
                "vm.scatter_write_share", "tier.pooled_source_share",
                "vm.merged_read_share", "vm.slots_per_turn",
                "vm.narrow_turn_share",
                # the ring's other writers (PR 40; chipbench/reduce/hostspans.py)
                "tier.lower_ms_per_source", "tier.pack_ms_per_call",
                "tier.pool_overhead_ms_per_call", "tier.gc_ms_per_call",
                "tier.slow_call_share",
                # where the checks ran and how the programs went up (PR 51)
                "tier.check_ms_per_source", "tier.uploads_per_call")


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


def test_the_cell_is_declared_with_its_files():
    cell = cells.load_cell(CELL)
    assert cell.chips == 1 and cell.traffic["driver"] == "codegen_cluster"
    cfg = cell.config
    assert (cfg["engine"], cfg["retry_rule"]) == ("flat", "earliest_delete")
    assert (cfg["node_prefilter_k"], cfg["code_eval_max_steps"]) == (64, 1024)
    assert cfg["reduced"] == ["code_eval_max_steps"]
    assert set(cfg["assumed"]) == {"share", "seed", "duration",
                                   "arrival_order"}
    assert set(cells.verify_files(cfg)) == {"cluster", "trace",
                                            "gpu_mem_mapping"}
    assert [m["name"] for m in cell.end_to_end] == ["lane_events_per_s",
                                                    "setup_s"]
    reported = {m["name"] for m in cell.per_layer}
    assert reported == set(SPAN_METRICS) | {"compile.setup_programs",
                                            "device.idle_share.code"}
    # codegen8's mix, parameter for parameter
    a, b = (dict(cells.load_cell(n).traffic)
            for n in ("openb16.codegen8", CELL))
    for t in (a, b):
        for k in ("driver", "seed_picks", "traced", "checked", "same_as"):
            t.pop(k, None)
    assert a == b


def test_cell_runs_end_to_end_and_reports_its_span_metrics(monkeypatch,
                                                           tmp_path):
    res, rows = _run(monkeypatch, tmp_path, trace=True)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0 and res["device"]["platform"] == "cpu"
    setup = next(r for r in rows if r["row"] == "setup")
    assert (setup["node_prefilter_k"], setup["nodes_padded"]) == (64, 1528)
    assert (setup["lanes"], setup["max_steps"]) == (4, 48)
    compared = [r for r in rows if r["row"] == "compared"]
    assert len(compared) >= 4 * 5 and all(r["ok"] for r in compared)
    calls = [r for r in rows if r["row"] == "call"]
    assert calls and all(r["lane_events"] == 4 * 48 for r in calls)
    for m in SPAN_METRICS:
        assert m in res["metrics"], m
        assert math.isfinite(res["metrics"][m]["value"]), m
    v = {m: res["metrics"][m]["value"] for m in SPAN_METRICS}
    # 4 lanes x 305 rows x 64 nodes x 8 GPUs x 8 bytes (x64 in the tests):
    # a champion's 238 live ops fill the 256 bucket (561 rows of the 512
    # bucket, and 57 %, until PR 53)
    assert v["vm.register_mb"] == 4 * 305 * 64 * 8 * 8 / 1e6
    assert 90 < v["vm.live_slot_share"] <= 100
    assert v["tier.traces_per_source"] == 1.0   # no dry trace before it
    assert 0 < v["vm.ops_kept_share"] < 100     # the simplifier engaged
    assert v["vm.scatter_write_share"] == 0.0   # every write stayed a slice
    assert v["vm.merged_read_share"] == 100.0   # every fetch one gather
    assert 0.0 <= v["tier.pooled_source_share"] <= 100.0
    assert v["tier.uploads_per_call"] == 8.0    # one put of eight leaves
    slots = v["vm.live_slot_share"] / 100 * 256     # a champion's bucket
    assert v["vm.us_per_slot"] == pytest.approx(
        v["vm.device_ms_per_event"] * 1e3 / slots, rel=1e-6)


def test_untraced_run_reports_the_end_to_end_metrics(monkeypatch, tmp_path):
    res, _ = _run(monkeypatch, tmp_path, trace=False, seed=7)
    assert res["correct"] is True and res["failed"] == 0
    assert set(res["metrics"]) == {"lane_events_per_s", "setup_s"}


def test_a_program_without_the_rule_is_refused_at_once(monkeypatch,
                                                       tmp_path):
    """What the parent commit does: its evaluator leaves the rule at 0.
    The run ends before the first device program, with the reason."""
    from fks_tpu.funsearch import backend

    monkeypatch.setattr(backend, "shape_prefilter_k",
                        lambda n_padded, override=None: 0)
    t0 = time.perf_counter()
    with pytest.raises(SystemExit) as e:
        _run(monkeypatch, tmp_path, trace=False)
    assert "node_prefilter_k=64" in str(e.value)
    assert "resolved 0" in str(e.value)
    assert time.perf_counter() - t0 < 20


def test_new_readers_find_nothing_in_a_program_without_the_fields():
    """The parent's launch spans have ``slots`` and no ``register_bytes``:
    ``vm.register_mb`` is then left out and nothing raises."""
    from fks_tpu.obs.spans import SpanRecord

    def rec(name, t0, t1, sid, parent=None, **fields):
        return SpanRecord(seq=0, name=name, t0=t0, t1=t1, span_id=sid,
                          parent_id=parent, trace_id="t", thread=0,
                          fields=fields or None)

    records = [rec("tier/evaluate", 0.0, 1.0, "w"),      # the warm-up
               rec("tier/evaluate", 1.0, 3.0, "g"),
               rec("tier/vm_batch/launch", 1.2, 2.8, "l", "g", lanes=8,
                   slots=292, capacity=512),
               rec("tier/vm_batch/wait_device", 2.8, 2.9, "d", "g")]
    ctx = {"rows": [{}], "call_seconds": 2.0, "lockstep_events": 1000,
           "_span_calls": rs.select_generations(records, 0, 1, 2.0)}
    assert cells.metric_reader("vm.register_mb")(ctx) is None
    assert cells.metric_reader("vm.us_per_slot")(ctx) == pytest.approx(
        1.7 / (1000 * 292) * 1e6)
    for name in ("vm.register_mb", "vm.us_per_slot"):
        assert cells.metric_reader(name)({}) is None


def test_bfloat16_control_is_not_correct_at_the_cells_size():
    """The reference against its bfloat16 self, under the rule, to the
    cell's own cap of 1,024 events: the comparison fails the generation
    by its identity limit (the float-free seed policies pass in any
    precision, the champions do not)."""
    cell = cells.load_cell(CELL)
    files = cells.verify_files(cell.config)
    d = cells.load_driver("codegen_cluster").Driver(cell, 2 ** 31 + 7, files,
                                                    None, False)
    lanes = control_cluster.control_numbers(
        cell.config, files, d._sources(), cell.config["code_eval_max_steps"])
    assert len(lanes) == 8
    failing = [ns for ns in lanes if not all(n.ok for n in ns)]
    assert len(failing) >= 4
    assert all(any(n.name.endswith("placements_differ") and not n.ok
                   for n in ns) for ns in failing)


def test_control_needs_the_chip(capsys):
    assert control_cluster.main(["--seeds", "1"]) == 3
    assert "no TPU" in capsys.readouterr().err
    assert os.path.exists(os.path.join(cells.HERE, "selftest",
                                       "control_cluster.py"))
