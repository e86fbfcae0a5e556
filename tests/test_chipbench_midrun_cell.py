"""The cell ``openb16-cpu250-midrun.codegen8``: as ``BENCHMARK.json`` and
its files declare it, run end to end at a tiny size on the CPU through
``chipbench/selftest/midrun.py`` (the driver's ``check`` against the plain
reference), a program that did not take the fork or lost the waiting pod,
the bfloat16 control at the cell's own size, and the two new readers on a
program without their fields. Device-heavy, and a few items only: the
suite's scheduler queues the files with the most items first, so this one
runs in the tail. The snapshot itself is ``tests/test_snapshot_midrun.py``."""
import json
import math
import os
import time

import pytest

from chipbench import cells
from chipbench.reduce import spans as rs
from chipbench.selftest import midrun
from tests.test_chipbench_loaded_decl import COUNTER_METRICS, SPAN_METRICS

CELL = midrun.CELL
CONTROL = "openb16.codegen8"
FORKED = "openb1523-loaded.codegen8"
NEW = ("sim.fork_replay_us_per_event", "sim.fork_waiting_pods")
LANE_NUMBERS = {"near_ties_admitted", "placements_differ",
                "gpu_picks_differ", "scheduled_diff", "events_diff",
                "flags_differ", "snapshots_diff", "frag_events_diff",
                "max_nodes_diff", "fitness_at_cap_rel_err"}


# ----------------------------------------------------------- declaration

def test_the_cell_is_declared_with_its_files():
    cell = cells.load_cell(CELL)
    assert cell.chips == 1 and cell.traffic["driver"] == "codegen_midrun"
    cfg = cell.config
    assert (cfg["engine"], cfg["retry_rule"], cfg["node_prefilter_k"],
            cfg["code_eval_max_steps"], cfg["start_event"]) \
        == ("flat", "earliest_delete", 0, 2048, 12288)
    assert cfg["reduced"] == ["code_eval_max_steps"] \
        and "code_eval_max_steps" in cfg["reduced_why"]
    assert cfg["architecture"] is None and len(cfg["source"]) <= 200
    assert set(cells.verify_files(cfg)) == {"cluster", "trace",
                                            "gpu_mem_mapping", "snapshot"}
    # cluster, GPU map, limits and assumptions of the control's
    # configuration; another trace, so another queue
    base = cells.load_cell(CONTROL).config
    for k in ("cluster", "gpu_mem_mapping"):
        assert cfg[k] == base[k], k
    assert cfg["trace"]["file"].endswith("openb_pod_list_cpu250.csv.gz")
    assert {k: v for k, v in cfg["shape"].items()
            if k not in ("pods", "queue_width")} \
        == {k: v for k, v in base["shape"].items()
            if k not in ("pods", "queue_width")}
    for k in ("fitness_rtol", "score_dtype", "score_near_tie_units",
              "near_ties_per_run"):
        assert cfg["guarantees"][k] == base["guarantees"][k], k
    assert set(cfg["assumed"]) == set(base["assumed"]) | {
        "start_event", "placing_policy"}
    # codegen8's mix, parameter for parameter
    a, b = (dict(cells.load_cell(n).traffic) for n in (CONTROL, CELL))
    for t in (a, b):
        for k in ("driver", "seed_picks", "traced", "checked", "same_as"):
            t.pop(k, None)
    assert a == b
    assert [m["name"] for m in cell.end_to_end] == ["lane_events_per_s",
                                                    "setup_s"]
    assert {m["name"] for m in cell.per_layer} == {
        m["name"] for m in cells.load_cell(FORKED).per_layer} | set(NEW)


def test_benchmark_json_only_gained_entries():
    with open(os.path.join(cells.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # in the place PR 42 gave them (PR 45 appended after them)
    assert bench["configs"][5] == {
        "name": "openb16-cpu250-midrun",
        "source": cells.load_cell(CELL).config["source"],
        "file": "chipbench/configs/openb16-cpu250-midrun.json",
        "reduced": ["code_eval_max_steps"],
        "why": bench["configs"][5]["why"]}
    assert bench["workloads"][7] == {
        "name": CELL, "config": "openb16-cpu250-midrun",
        "traffic": "codegen8-midrun", "chips": 1,
        "why": bench["workloads"][7]["why"]}
    for text in (bench["configs"][5]["why"], bench["configs"][5]["source"],
                 bench["workloads"][7]["why"]):
        assert len(text) <= 200
    assert len(bench["workloads"]) == 11 and len(bench["configs"]) == 9
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    at = [m["name"] for m in bench["per_layer"]].index(NEW[0])
    new = bench["per_layer"][at:at + 2]
    assert [m["name"] for m in new] == list(NEW)
    # appended since, at the end: PR 44's metric of the code cells,
    # PR 45's of the typed cell, PR 46's and PR 47's of the code cells,
    # PR 49's of the typed what-if cell, PR 51's two of the code cells
    # and PR 52's three of the what-if cell on this cell's snapshot
    assert [m["name"] for m in bench["per_layer"][at + 2:]] == [
        "vm.merged_read_share", "sim.typed_pod_share", "vm.slots_per_turn",
        "vm.narrow_turn_share", "serve.typed_pod_share",
        "tier.check_ms_per_source", "tier.uploads_per_call",
        "serve.heap_replay_ms_per_call", "serve.fork_waiting_pods",
        "serve.finished_lane_share"]
    later = "openb1523-gpuspec25-loaded.codegen8"   # PR 45's forked cell
    for m in new:
        assert m["workloads"] == [CELL, later]
        assert m["layer"] == "engines sim/flat.py"
        meta = json.load(open(os.path.join(cells.HERE, "metrics",
                                           m["name"] + ".json")))
        for key in ("unit", "better", "source", "layer", "moves"):
            assert meta[key] == m[key], (m["name"], key)
    assert [m["moves"] for m in new] == ["setup_s", "lane_events_per_s"]
    # appended to every list that held the forked cell, at its end
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m in new:
            continue
        lists = m.get("workloads", [])
        assert (CELL in lists) == (FORKED in lists), m["name"]
        if CELL in lists:   # last of the cells there were at PR 42
            assert [w for w in lists if w not in (
                later, "openb1523-gpuspec25-loaded.whatif8",
                "openb16-cpu250-midrun.whatif8")][-1] == CELL


def test_new_readers_find_nothing_in_a_program_without_their_fields():
    """The parent has no ``tier/fork_state`` span on this cell (it never
    gets that far), and its span on the loaded cell has neither
    ``departed`` nor ``waiting``: both readers return None, nothing
    raises; with the fields they read them."""
    from fks_tpu import obs
    from fks_tpu.obs import spans

    spans.LOG.clear()
    for name in NEW:
        assert cells.metric_reader(name)({}) is None
    with obs.span("tier/fork_state", start_event=5888, residents=5888):
        pass
    for name in NEW:
        assert cells.metric_reader(name)({}) is None
    with obs.span("tier/fork_state", start_event=1000, departed=7,
                  waiting=2):
        time.sleep(0.01)
    us, pods = (cells.metric_reader(name)({}) for name in NEW)
    assert 10 <= us < 1000 and pods == 2.0
    spans.LOG.clear()


# ------------------------------------------------------------- tiny runs

@pytest.fixture(scope="module")
def deployment(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("midrun_cell"))
    return d, midrun.tiny_deployment(d)


@pytest.fixture()
def tiny(monkeypatch, tmp_path_factory, deployment):
    from fks_tpu import utils
    from fks_tpu.obs import spans

    cache = str(tmp_path_factory.getbasetemp() / "midrun_cache")
    spans.LOG.clear()
    monkeypatch.setattr(utils, "place_compile_cache", lambda: cache)
    monkeypatch.setenv("FKS_VM_SEG_STEPS", "16")
    monkeypatch.setattr(rs, "TOLERANCE", 0.05)     # a loaded test worker
    d, overrides = deployment
    return lambda **kw: midrun.run_tiny(d, overrides=overrides, **kw)


def test_cell_runs_end_to_end_and_counts_from_the_fork(tiny):
    res, rows = tiny(trace=True)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0 and res["device"]["platform"] == "cpu"
    setup = next(r for r in rows if r["row"] == "setup")
    assert (setup["lanes"], setup["start_event"], setup["max_steps"],
            setup["node_prefilter_k"]) \
        == (midrun.LANES, midrun.E0, midrun.E0 + midrun.WINDOW, 0)
    compared = [r for r in rows if r["row"] in ("compared", "admitted")]
    assert all(r["ok"] for r in compared)
    assert {r["name"] for r in compared} == {
        f"lane{i}.{n}" for i in range(midrun.LANES)
        for n in LANE_NUMBERS} | {"call.frag_counter_diff"}
    calls = [r for r in rows if r["row"] == "call"]
    assert calls and all(
        (r["lane_events"], r["lockstep_events"])
        == (midrun.LANES * midrun.WINDOW, midrun.WINDOW) for r in calls)
    for m in SPAN_METRICS + COUNTER_METRICS + NEW:
        assert m in res["metrics"], m
        assert math.isfinite(res["metrics"][m]["value"]), m
    v = {m: res["metrics"][m]["value"] for m in res["metrics"]}
    # the regime: a pod waits at the fork, and the window's lanes fail
    # placements of their own (the prefix's 9 are not counted again)
    assert v["sim.fork_waiting_pods"] == 1.0
    assert 5.0 < v["sim.retry_share"] < 60.0
    assert v["sim.fork_replay_us_per_event"] * midrun.E0 \
        == pytest.approx(v["sim.fork_state_ms"] * 1e3, rel=1e-6)
    from fks_tpu.obs import spans
    (fork,) = [r for r in spans.LOG.snapshot()
               if r.name == "tier/fork_state"]
    assert {k: fork.fields[k] for k in (
        "start_event", "departed", "waiting", "prefix_failed", "residents",
        "rule")} == {"start_event": 320, "departed": 139, "waiting": 1,
                     "prefix_failed": 9, "residents": 33,
                     "rule": "earliest_delete"}


@pytest.mark.parametrize("seed", [7, 2 ** 31 + 11])
def test_work_per_call_is_the_same_for_every_seed(tiny, seed):
    res, rows = tiny(trace=False, seed=seed)
    assert res["correct"] is True and res["failed"] == 0
    assert set(res["metrics"]) == {"lane_events_per_s", "setup_s"}
    calls = [r for r in rows if r["row"] == "call"]
    assert calls and all(
        r["lane_events"] == midrun.LANES * midrun.WINDOW for r in calls)


def test_a_fork_that_drops_the_waiting_pod_is_not_correct(tiny,
                                                           monkeypatch):
    """The carry as the fork builds it, but for the waiting pod's queued
    retry: the pod never comes back, every lane leaves it unplaced where
    the reference places it, and the comparison says so."""
    import jax.numpy as jnp

    from fks_tpu.sim import flat

    real = flat._loaded_leaves

    def dropped(*a, **kw):
        out = real(*a, **kw)
        gone = (out["aux"] == flat.AUX_WAITING) & (out["ev_time"] < flat.INF)
        assert int(gone.sum()) == 1
        out["ev_time"] = jnp.where(gone, flat.INF, out["ev_time"])
        out["pending"] = out["pending"] - 1
        return out

    monkeypatch.setattr(flat, "_loaded_leaves", dropped)
    res, rows = tiny(trace=True)
    assert res["correct"] is False
    bad = {r["name"].split(".", 1)[1] for r in rows
           if r["row"] == "compared" and not r["ok"]}
    assert {"placements_differ", "scheduled_diff"} <= bad
    assert res["metrics"]["sim.fork_waiting_pods"]["value"] == 0.0


def test_a_program_that_did_not_fork_is_refused_at_once(tiny, monkeypatch):
    """An evaluator that starts at event 0 (it ignored the snapshot): the
    run ends before the first device program, with the reason."""
    import dataclasses

    from fks_tpu.funsearch.backend import CodeEvaluator

    real = CodeEvaluator.__init__

    def init(self, workload, *a, **kw):
        real(self, dataclasses.replace(workload, snapshot=None), *a, **kw)

    monkeypatch.setattr(CodeEvaluator, "__init__", init)
    t0 = time.perf_counter()
    with pytest.raises(SystemExit) as e:
        tiny(trace=False)
    assert "starts at event 320" in str(e.value)
    assert "starts at 0" in str(e.value)
    assert time.perf_counter() - t0 < 20


@pytest.mark.parametrize("sums", [False, True])
def test_bfloat16_control_is_not_correct_at_the_cells_size(sums):
    """The forked reference against its bfloat16 self, to the cell's own
    cap (event 14,336): bfloat16 SCORES move placements in most lanes
    (integer scores decide in upstream's first_fit), so the comparison
    fails the generation by its identity limit; bfloat16 evaluator sums
    fail every lane by the fitness at the cap besides."""
    cell = cells.load_cell(CELL)
    files = cells.verify_files(cell.config)
    d = cells.load_driver("codegen_midrun").Driver(cell, 2 ** 31 + 7, files,
                                                   None, False)
    lanes = midrun.control_numbers(
        cell.config, files, d._sources(),
        cell.config["start_event"] + cell.config["code_eval_max_steps"],
        sums)
    assert len(lanes) == 8

    def failing(what):
        return [ns for ns in lanes if any(
            n.name.endswith(what) and not n.ok for n in ns)]

    assert len(failing("placements_differ")) >= 4
    assert len(failing("fitness_at_cap_rel_err")) >= (8 if sums else 4)
