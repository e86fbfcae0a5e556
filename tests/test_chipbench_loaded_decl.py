"""The cell ``openb1523-loaded.codegen8`` as ``BENCHMARK.json`` and its
files declare it, and what a program without the fork does with it. The
cell's runs are ``tests/test_chipbench_loaded_cell.py``."""
import json
import os

import pytest

from chipbench import cells
from chipbench.selftest import control_loaded

CELL = "openb1523-loaded.codegen8"
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
                "sim.fork_state_ms",
                # where the checks ran and how the programs went up (PR 51)
                "tier.check_ms_per_source", "tier.uploads_per_call")
#: read from the driver's counters: the profiler's device-eval stage
#: against the calls' seconds and the window's lockstep events
COUNTER_METRICS = ("tier.host_share", "vm.ms_per_event", "sim.retry_share")


def test_the_cell_is_declared_with_its_files():
    cell = cells.load_cell(CELL)
    assert cell.chips == 1 and cell.traffic["driver"] == "codegen_loaded"
    cfg = cell.config
    assert (cfg["engine"], cfg["retry_rule"]) == ("flat", "earliest_delete")
    assert (cfg["node_prefilter_k"], cfg["code_eval_max_steps"],
            cfg["start_event"]) == (64, 1024, 5888)
    assert cfg["reduced"] == ["code_eval_max_steps"]
    assert cfg["architecture"] is None
    assert set(cells.verify_files(cfg)) == {"cluster", "trace",
                                            "gpu_mem_mapping", "snapshot"}
    # cluster, trace, GPU map, limits and every assumption of the
    # configuration it forks
    base = cells.load_cell("openb1523-inflated.codegen8").config
    for k in ("cluster", "trace", "gpu_mem_mapping", "shape"):
        assert cfg[k] == base[k], k
    assert cfg["guarantees"]["fitness_rtol"] \
        == base["guarantees"]["fitness_rtol"] == 16 * 2.0 ** -23
    assert set(cfg["assumed"]) == set(base["assumed"]) | {"start_event",
                                                          "placing_policy"}
    # the pinned file lies outside chipbench/: a parent checkout, which
    # gets only chipbench/ laid over it, ends the run in verify_files
    assert not cfg["snapshot"]["file"].startswith("chipbench/")
    assert [m["name"] for m in cell.end_to_end] == ["lane_events_per_s",
                                                    "setup_s"]
    reported = {m["name"] for m in cell.per_layer}
    assert reported == set(SPAN_METRICS) | set(COUNTER_METRICS) | {
        "compile.setup_programs", "device.idle_share.code"}
    # every list that holds the fifth cell, the two older readings of the
    # same counters (this driver emits them too), and the two new ones
    fifth = {m["name"] for m in cells.load_cell(
        "openb1523-inflated.codegen8").per_layer}
    assert reported - fifth == {"tier.host_share", "vm.ms_per_event",
                                "sim.retry_share", "sim.fork_state_ms"}
    # codegen8-cluster's mix, parameter for parameter
    a, b = (dict(cells.load_cell(n).traffic)
            for n in ("openb1523-inflated.codegen8", CELL))
    for t in (a, b):
        for k in ("driver", "seed_picks", "traced", "checked", "same_as"):
            t.pop(k, None)
    assert a == b


def test_benchmark_json_only_gained_entries():
    """Entries at the end of their lists, the cell appended to the lists
    that held the fifth cell, nothing else."""
    with open(os.path.join(cells.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # in the place PR 31 gave them (later PRs append after them)
    assert bench["configs"][3]["name"] == "openb1523-loaded"
    assert bench["workloads"][5]["name"] == CELL
    # the cell's own two metrics, in the place PR 31 gave them (later PRs
    # append after them)
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index("sim.retry_share")
    assert names[at:at + 2] == ["sim.retry_share", "sim.fork_state_ms"]
    own = bench["per_layer"][at:at + 2]
    others = bench["per_layer"][:at] + bench["per_layer"][at + 2:]
    later = ("openb1523-loaded.whatif8", "openb16-cpu250-midrun.codegen8",
             "openb1523-gpuspec25-loaded.codegen8",
             "openb1523-gpuspec25-loaded.whatif8",
             "openb16-cpu250-midrun.whatif8")
    for m in bench["end_to_end"] + others:
        lists = m.get("workloads", [])
        assert (CELL in lists) == (
            "openb1523-inflated.codegen8" in lists
            or m["name"] in ("tier.host_share", "vm.ms_per_event"))
        if CELL in lists:      # last of the cells there were at PR 31
            assert [w for w in lists if w not in later][-1] == CELL
    for m in own:       # PR 42's and PR 45's forked cells read both too
        assert m["workloads"] == [CELL, later[1], later[2]]
        assert m["layer"] == "engines sim/flat.py"


def test_a_checkout_without_the_snapshot_file_ends_in_verify_files(
        monkeypatch):
    """What the parent commit does: ``chipbench/`` is laid over it, the
    pinned snapshot under ``benchmarks/`` is not, and the run ends where
    the configuration's files are checked, before any driver is built."""
    cell = cells.load_cell(CELL)
    cfg = dict(cell.config, snapshot=dict(
        cell.config["snapshot"],
        file="benchmarks/traces/csv/not_in_this_checkout.csv.gz"))
    with pytest.raises(FileNotFoundError):
        cells.verify_files(cfg)


def test_new_readers_find_nothing_in_a_program_without_them():
    """The parent has no ``tier/fork_state`` span and no ``frag_events``:
    both readers then return None and nothing raises."""
    from fks_tpu.obs import spans

    spans.LOG.clear()
    assert cells.metric_reader("sim.fork_state_ms")({}) is None
    assert cells.metric_reader("sim.retry_share")(
        {"lane_events_total": 8192}) is None
    assert cells.metric_reader("sim.retry_share")(
        {"lane_events_total": 8192, "frag_events": 512}) == 6.25


def test_control_needs_the_chip(capsys):
    assert control_loaded.main(["--seeds", "1"]) == 3
    assert "no TPU" in capsys.readouterr().err
