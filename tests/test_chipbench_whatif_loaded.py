"""The cell ``openb1523-loaded.whatif8``: as ``BENCHMARK.json`` and its
files declare it, run end to end at a tiny size on the CPU through
``chipbench/selftest/whatif_loaded.py`` (the driver's ``check`` against
the plain reference), a program whose serving cannot fork, an answer that
does not end at its budget, and the bfloat16 control at the tiny size.
The forked serve path itself is ``tests/test_serve_fork.py``."""
import json
import math
import os

import pytest

from chipbench import cells
from chipbench.reduce import spans as rs
from chipbench.selftest import control_whatif_loaded, whatif_loaded

CELL = whatif_loaded.CELL
SIBLING = "openb1523.whatif8"
NEW = ("serve.fork_state_ms", "serve.retry_share")
QUERY_NUMBERS = {"placements_differ", "gpu_picks_differ", "scheduled_diff",
                 "events_diff", "flags_differ", "waiting_differ",
                 "snapshots_diff", "frag_events_diff", "max_nodes_diff",
                 "fitness_at_cap_rel_err"}


# ----------------------------------------------------------- declaration

def test_the_cell_is_declared_with_its_files():
    cell = cells.load_cell(CELL)
    assert cell.chips == 1 and cell.traffic["driver"] == "whatif_loaded"
    cfg, t = cell.config, cell.traffic
    assert (cfg["engine"], cfg["retry_rule"], cfg["node_prefilter_k"],
            cfg["max_steps_factor"], cfg["start_event"]) \
        == ("exact", "heap_array", 64, 2, 5888)
    assert cfg["reduced"] == ["max_steps_factor"] \
        and "max_steps_factor" in cfg["reduced_why"]
    assert cfg["architecture"] is None and len(cfg["source"]) < 200
    assert cfg["shape"]["pod_buckets"] == [16, 64, 256, 1024]
    assert set(cells.verify_files(cfg)) == {"cluster", "trace",
                                            "gpu_mem_mapping", "snapshot"}
    # the files, sha256 and assumptions of the configuration whose
    # snapshot it serves from
    loaded = cells.load_cell("openb1523-loaded.codegen8").config
    for k in ("cluster", "trace", "gpu_mem_mapping", "snapshot"):
        assert cfg[k] == loaded[k], k
    assert set(cfg["assumed"]) == set(loaded["assumed"]) | {
        "node_prefilter_k"}
    # no limit is new and none is loosened: the sibling's, number for
    # number
    snap = cells.load_cell(SIBLING).config["guarantees"]
    for k in ("fitness_rtol", "score_dtype", "score_near_tie_units",
              "near_ties_per_run"):
        assert cfg["guarantees"][k] == snap[k], k
    assert "leave when their duration ends" in cfg["guarantees"]["semantics"]
    assert (t["sizes"], t["max_batch"], t["max_wait_s"], t["trace_for_s"]) \
        == ([8, 16, 32, 64, 128, 256, 512, 807], 8, 0.25, 0.25)
    assert sum(t["sizes"]) == 1823
    assert [m["name"] for m in cell.end_to_end] == ["whatif_pods_per_s",
                                                    "setup_s"]
    mine = {m["name"] for m in cell.per_layer}
    # PR 52's heap replay is on this cell's stacking path too
    assert mine == {m["name"] for m in cells.load_cell(SIBLING).per_layer} \
        | set(NEW) | {"serve.heap_replay_ms_per_call"}


def test_benchmark_json_only_gained_entries():
    with open(os.path.join(cells.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # in the place PR 37 gave them (PR 42 appended after them)
    assert bench["configs"][4]["name"] == "openb1523-loaded-snapshot"
    assert bench["configs"][4]["reduced"] == ["max_steps_factor"]
    assert bench["workloads"][6] == {
        "name": CELL, "config": "openb1523-loaded-snapshot",
        "traffic": "whatif8-loaded", "chips": 1,
        "why": bench["workloads"][6]["why"]}
    assert len(bench["workloads"][6]["why"]) <= 200
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index(NEW[0])
    assert names[at:at + 2] == list(NEW)
    # appended since, at the end: PR 39's metric of the code cells and
    # PR 40's seven readers of the ring's other writers
    assert names[at + 2:] == [
        "tier.pooled_source_share", "tier.lower_ms_per_source",
        "tier.pack_ms_per_call", "tier.pool_overhead_ms_per_call",
        "tier.gc_ms_per_call", "serve.gc_ms_per_call",
        "tier.slow_call_share", "serve.slow_call_share",
        # PR 42's two of the mid-run fork
        "sim.fork_replay_us_per_event", "sim.fork_waiting_pods",
        # PR 44's merged-read share of the code cells
        "vm.merged_read_share",
        # PR 45's share of pods that name their GPU models
        "sim.typed_pod_share",
        # PR 46's slots a turn of the interpreter's loop
        "vm.slots_per_turn",
        # PR 47's share of its turns that ran the narrow opcode table
        "vm.narrow_turn_share",
        # PR 49's share of query pods that name their GPU models
        "serve.typed_pod_share",
        # PR 51's check a source and uploads a call of the code cells
        "tier.check_ms_per_source", "tier.uploads_per_call",
        # PR 52's three of the what-if cell forked mid-run
        "serve.heap_replay_ms_per_call", "serve.fork_waiting_pods",
        "serve.finished_lane_share"]
    new = bench["per_layer"][at:at + 2]
    # PR 49's and PR 52's forked cells read both too
    typed = "openb1523-gpuspec25-loaded.whatif8"
    midrun = "openb16-cpu250-midrun.whatif8"
    for m in new:
        assert m["workloads"] == [CELL, typed, midrun]
        assert m["layer"] == "serving serve/"
    assert [m["moves"] for m in new] == ["setup_s", "whatif_pods_per_s"]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m in new or m["name"] == "serve.heap_replay_ms_per_call":
            continue
        lists = m.get("workloads", [])
        assert (CELL in lists) == (SIBLING in lists), m["name"]
        if CELL in lists:   # last of the cells there were at PR 37
            assert [w for w in lists if w not in (
                "openb16-cpu250-midrun.codegen8",
                "openb1523-gpuspec25-loaded.codegen8", typed,
                midrun)][-1] == CELL
    assert len(bench["workloads"]) == 11
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1


def test_new_readers_find_nothing_in_a_program_without_them():
    from fks_tpu.obs import spans

    spans.LOG.clear()
    for name in NEW:
        assert cells.metric_reader(name)({}) is None


# ------------------------------------------------------------- tiny runs

@pytest.fixture()
def tiny(monkeypatch, tmp_path_factory):
    from fks_tpu import utils
    from fks_tpu.obs import spans

    cache = str(tmp_path_factory.getbasetemp() / "whatif_loaded_cache")
    spans.LOG.clear()
    monkeypatch.setattr(utils, "place_compile_cache", lambda: cache)
    monkeypatch.setattr(rs, "TOLERANCE", 0.05)     # a loaded test worker


def test_cell_runs_end_to_end_and_counts_from_the_fork(tiny):
    res, rows = whatif_loaded.run_tiny(trace=True)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0 and res["device"]["platform"] == "cpu"
    setup = next(r for r in rows if r["row"] == "setup")
    assert (setup["start_event"], setup["backlog"], setup["nodes"],
            setup["node_prefilter_k"]) == (200, 64, 1523, 64)
    assert setup["budgets"] == {"16": 64, "64": 128}
    assert setup["reduced"] == {"max_steps_factor": 2}
    calls = [r for r in rows if r["row"] == "call"]
    assert calls and all(
        (r["lockstep_events"], r["pods"], r["chunks"], r["queries"])
        == (whatif_loaded.EVENTS, 120, 2, 4) for r in calls)
    compared = [r for r in rows if r["row"] == "compared"]
    assert all(r["ok"] for r in compared)
    assert {r["name"].split(".", 1)[1] for r in compared} == QUERY_NUMBERS
    assert len(compared) == 4 * len(QUERY_NUMBERS)
    want = {m["name"] for m in cells.load_cell(CELL).per_layer} \
        - {"device.idle_share.serve"}       # no device trace on the CPU
    assert set(res["metrics"]) == want
    v = {m: res["metrics"][m]["value"] for m in res["metrics"]}
    assert all(math.isfinite(x) for x in v.values())
    assert v["serve.fork_state_ms"] > 0
    # the first 264 arrivals do not fill the cluster: no placement fails
    assert v["serve.retry_share"] == 0.0
    assert v["serve.batch_occupancy"] == 100.0
    # what a forked call ships: the residents' part with every batch
    assert v["serve.h2d_kb_per_call"] > 4 * 200 * 61 / 1e3


@pytest.mark.parametrize("seed", [7, 2 ** 31 + 11])
def test_work_per_call_is_the_same_for_every_seed(tiny, seed):
    res, rows = whatif_loaded.run_tiny(seed=seed)
    assert res["correct"] is True and res["failed"] == 0
    assert set(res["metrics"]) == {"whatif_pods_per_s", "setup_s"}
    calls = [r for r in rows if r["row"] == "call"]
    assert calls and all(r["lockstep_events"] == whatif_loaded.EVENTS
                         and r["pods"] == 120 for r in calls)


def test_a_program_whose_serving_cannot_fork_is_refused_at_once(
        tiny, monkeypatch):
    """What the parent commit does: its engine takes the workload, never
    looks at the snapshot and would answer from an empty cluster. The
    driver ends the run before the warm-up call."""
    from fks_tpu.serve import VMServeEngine

    monkeypatch.setattr(VMServeEngine, "start_event",
                        property(lambda self: 0))
    with pytest.raises(SystemExit, match="starts at 0"):
        whatif_loaded.run_tiny()


def test_an_answer_that_ends_elsewhere_is_a_failed_operation(
        tiny, monkeypatch):
    from chipbench.drivers import whatif_loaded as driver

    real = driver.Driver.budget
    monkeypatch.setattr(driver.Driver, "budget",
                        lambda self, n: real(self, n) + 1)
    res, _ = whatif_loaded.run_tiny()
    assert res["correct"] is False and res["failed"] == res["attempted"]


def test_bfloat16_scores_fail_identity_from_a_loaded_cluster(tiny, capsys):
    """The control at the tiny size: from the loaded cluster the
    candidates are unequal, so bfloat16 SCORES alone move placements
    (on the sibling's empty snapshot they do not)."""
    assert control_whatif_loaded.main(["--cpu-tiny", "--seeds", "5"]) == 0
    lines = [json.loads(line) for line in
             capsys.readouterr().out.splitlines() if line.startswith("{")]
    scores = next(r for r in lines if r.get("run") == "scores")
    assert scores["queries_failing_identity"] >= 1
    assert "placements_differ" in scores["failed_numbers"]
    assert lines[-1] == {"control_failed_everywhere": True,
                         "scores_fail_identity_everywhere": True,
                         "sound_ok": True}


def test_control_needs_the_chip(capsys):
    assert control_whatif_loaded.main(["--seeds", "1"]) == 3
    assert "no TPU" in capsys.readouterr().err
