"""The cell ``openb16-cpu250-midrun.whatif8`` (PR 52): as ``BENCHMARK.json``
and its files declare it, the configuration's numbers of the state at the
fork against the program's replay and the reference's parse, the cell run
end to end at a tiny size on the CPU through
``chipbench/selftest/whatif_midrun.py`` (the driver's ``check`` against
the plain reference whose retry rule changes at the fork), a program that
lost the waiting pod, an answer that ends elsewhere, and the controls at
the tiny size. The forked path itself is
``tests/test_serve_fork_midrun.py``."""
import dataclasses
import json
import math
import os
import time

import numpy as np
import pytest

from chipbench import cells
from chipbench.drivers import common, whatif_midrun
from chipbench.reduce import spans as rs
from chipbench.reference import forked_query_midrun as fq
from chipbench.reference import plain_sim_fork
from chipbench.reference import plain_sim_midrun as mid
from chipbench.selftest import whatif_midrun as selftest

CELL = selftest.CELL
CONTROL = "openb1523-loaded.whatif8"
CODE_CELL = "openb16-cpu250-midrun.codegen8"
NEW = ("serve.heap_replay_ms_per_call", "serve.fork_waiting_pods",
       "serve.finished_lane_share")
QUERY_NUMBERS = {"placements_differ", "gpu_picks_differ", "scheduled_diff",
                 "events_diff", "flags_differ", "waiting_differ",
                 "finished_differ", "snapshots_diff", "frag_events_diff",
                 "max_nodes_diff", "fitness_at_cap_rel_err"}


# ----------------------------------------------------------- declaration

def test_the_cell_is_declared_with_its_files():
    cell = cells.load_cell(CELL)
    assert cell.chips == 1 and cell.traffic["driver"] == "whatif_midrun"
    cfg, t = cell.config, cell.traffic
    assert (cfg["engine"], cfg["retry_rule"], cfg["snapshot_rule"],
            cfg["node_prefilter_k"], cfg["max_steps_factor"],
            cfg["start_event"]) \
        == ("exact", "heap_array", "earliest_delete", 0, 2, 12288)
    assert cfg["reduced"] == ["max_steps_factor"] \
        and "max_steps_factor" in cfg["reduced_why"]
    assert cfg["architecture"] is None and len(cfg["source"]) <= 200
    assert cfg["shape"]["pod_buckets"] == [16, 64, 256, 1024]
    assert cfg["shape"]["base_pods"] == 5669
    for width in ("5,685", "5,733", "5,925", "6,693"):
        assert width in cfg["shape"]["pod_axis"]
    assert set(cells.verify_files(cfg)) == {"cluster", "trace",
                                            "gpu_mem_mapping", "snapshot"}
    # the four pinned files of the code cell's configuration: the code
    # cell and the serving cell ask from one pinned moment
    code = cells.load_cell(CODE_CELL).config
    for k in ("cluster", "trace", "gpu_mem_mapping", "snapshot"):
        assert cfg[k] == code[k], k
    assert cfg["state_at_fork"] == code["state_at_fork"]
    assert cfg["node_prefilter_k_is"] == code["node_prefilter_k_is"]
    assert set(cfg["assumed"]) == {"start_event", "placing_policy",
                                   "query_schema"}
    for k in ("start_event", "placing_policy"):
        assert cfg["assumed"][k] == code["assumed"][k]
    # no limit is new and none is loosened: the control's, number for
    # number
    loaded = cells.load_cell(CONTROL).config
    for k in ("fitness_rtol", "score_dtype", "score_near_tie_units",
              "near_ties_per_run"):
        assert cfg["guarantees"][k] == loaded["guarantees"][k], k
    assert cfg["max_steps_factor_is"].replace("12,288", "5,888") \
        == loaded["max_steps_factor_is"]
    says = cfg["guarantees"]["semantics"]
    assert "FINISHES inside its budget" in says and "16 f32 ulps" in says
    assert "what HAPPENED" in cfg["fork"] and "slot for slot" in cfg["fork"]
    # whatif8-loaded, parameter for parameter, but the driver and the
    # windows
    control = cells.load_cell(CONTROL).traffic
    for k in ("sizes", "max_batch", "max_wait_s", "ledger_glob",
              "trace_for_s"):
        assert t[k] == control[k], k
    assert set(t) == set(control) | {"s_range"}
    assert t["s_range"] == {"807": [0, 0]} and sum(t["sizes"]) == 1823
    assert [m["name"] for m in cell.end_to_end] == ["whatif_pods_per_s",
                                                    "setup_s"]
    mine = {m["name"] for m in cell.per_layer}
    assert mine == {m["name"] for m in cells.load_cell(CONTROL).per_layer} \
        | set(NEW)


def test_benchmark_json_only_gained_entries():
    with open(os.path.join(cells.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert bench["configs"][-1] == {
        "name": "openb16-cpu250-midrun-snapshot",
        "source": cells.load_cell(CELL).config["source"],
        "file": "chipbench/configs/openb16-cpu250-midrun-snapshot.json",
        "reduced": ["max_steps_factor"],
        "why": bench["configs"][-1]["why"]}
    assert bench["workloads"][-1] == {
        "name": CELL, "config": "openb16-cpu250-midrun-snapshot",
        "traffic": "whatif8-midrun", "chips": 1,
        "why": bench["workloads"][-1]["why"]}
    assert len(bench["workloads"][-1]["why"]) <= 200
    assert len(bench["workloads"]) == 11 and len(bench["configs"]) == 9
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    names = [m["name"] for m in bench["per_layer"]]
    assert tuple(names[-3:]) == NEW
    loaded = [CONTROL, "openb1523-gpuspec25-loaded.whatif8"]
    new = bench["per_layer"][-3:]
    assert [m["workloads"] for m in new] == [[CELL] + loaded, [CELL], [CELL]]
    assert all(m["layer"] == "serving serve/"
               and m["moves"] == "whatif_pods_per_s" for m in new)
    assert [(m["unit"], m["better"], m["source"]) for m in new] == [
        ("ms", "lower", "program_span"),
        ("pods", "higher", "program_counter"),
        ("%", "higher", "program_counter")]
    # everywhere else the cell stands where its control stands, last
    for m in bench["end_to_end"] + bench["per_layer"][:-3]:
        lists = m.get("workloads", [])
        if m["name"] == "serve.typed_pod_share":
            assert CELL not in lists
            continue
        assert (CELL in lists) == (CONTROL in lists), m["name"]
        if CELL in lists:
            assert lists[-1] == CELL, m["name"]


def test_new_readers_find_nothing_in_a_program_without_them():
    from fks_tpu.obs import spans

    spans.LOG.clear()
    for name in NEW:
        assert cells.metric_reader(name)({}) is None
    # a parent's spans: a fork_state without the field, a batch root
    # without the count, no heap_replay span
    with spans.span("serve/fork_state", start_event=5888, residents=5888):
        pass
    with spans.span("serve/batch", queries=8):
        pass
    ctx = {"calls": 0, "queries": 0, "call_seconds": 0.0}
    for name in NEW:
        assert cells.metric_reader(name)(dict(ctx)) is None
    spans.LOG.clear()


# ------------------------------------- the state at the fork, three ways

def test_what_the_configuration_says_of_the_fork_is_what_both_sides_count():
    """5,669 / 5,618 / 50 / 1 / 1,002: the configuration's ``fork_counts``
    against the program's replay of the committed snapshot (the serve
    engine's ``QueryFork``) and against the reference's own parse of the
    file; and the per-query heap replay stays far from the whole replay's
    cost."""
    from fks_tpu.data import snapshot
    from fks_tpu.serve.batcher import (QueryFork, build_query_workload,
                                       pods_to_dicts)
    from fks_tpu.sim import engine as exact
    from fks_tpu.sim.engine import SimConfig

    cfg = cells.load_cell(CELL).config
    files = cells.verify_files(cfg)
    want = cfg["fork_counts"]
    assert want == {"events": 12288, "arrived": 5669, "departed": 5618,
                    "residents": 50, "waiting": 1, "refused": 1002,
                    "not_arrived": 3751}
    for n in ("5,669", "5,618", "50 are resident", "1 GPU pod waits",
              "1,002", "3,751"):
        assert n in cfg["state_at_fork"], n
    # the reference's parse
    cluster, pods = common.reference_inputs(cfg, files)
    log = mid.load_log(files["snapshot"], files["cluster"], files["trace"])
    counts = whatif_midrun.fork_counts(log)
    assert counts == {k: want[k] for k in whatif_midrun.FORK_FIELDS}
    assert (len(fq.base_of(log)), pods.p - len(fq.base_of(log))) \
        == (want["arrived"], want["not_arrived"])
    at = plain_sim_fork.validate(cluster, *fq.inputs(pods, log, ()))
    assert (at.steps, at.num_frag_events, at.scheduled_pods) \
        == (12288, 1002, 5668)
    # the program's replay
    wl = common.parse_workload(cfg, files)
    wl = dataclasses.replace(wl, snapshot=snapshot.load_snapshot(
        files["snapshot"].removesuffix(".gz"), wl))
    fork = QueryFork(wl)
    assert {"events": fork.e0, "arrived": fork.base,
            "departed": fork.prefix.departed, "residents": fork.residents,
            "waiting": fork.waiting, "refused": fork.prefix.refused,
            "not_arrived": wl.num_pods - fork.base} == want
    assert fork.nodes_loaded == cfg["shape"]["nodes_loaded"] == 15
    assert len(fork.prefix.heap) == 51      # 50 DELETEs and the retry
    assert str(fork.not_before) in cfg["fork"].replace(",", "")
    # one query's heap replay (the whole of forked_state): 9 ms for the
    # 12,288 events on this sandbox's CPU (PR 52), the whole replay 75
    dicts = pods_to_dicts(wl.pods)
    arrived = set(np.asarray(wl.snapshot.pod).tolist())
    query = [dicts[i] for i in snapshot.event_order(wl.pods).tolist()
             if i not in arrived][:807]
    qwl = build_query_workload(wl.cluster, query, 1024, fork)
    cfg16 = SimConfig(max_steps=fork.e0 + 2048, wait_hist_size=1001)
    best = math.inf
    for _ in range(3):
        t0 = time.perf_counter()
        state = exact.forked_state(qwl, cfg16, fork.prefix)
        best = min(best, time.perf_counter() - t0)
    assert int(state.heap.size) == 51 + len(query)
    assert best < 0.1, best


# ------------------------------------------------------------- tiny runs

@pytest.fixture()
def tiny(monkeypatch, tmp_path_factory):
    from fks_tpu import utils
    from fks_tpu.obs import spans

    cache = str(tmp_path_factory.getbasetemp() / "whatif_midrun_cache")
    spans.LOG.clear()
    monkeypatch.setattr(utils, "place_compile_cache", lambda: cache)
    monkeypatch.setattr(rs, "TOLERANCE", 0.05)     # a loaded test worker
    d = str(tmp_path_factory.mktemp("whatif_midrun"))
    return d, selftest.tiny_deployment(d)


def test_cell_runs_end_to_end_and_two_lanes_finish(tiny):
    d, overrides = tiny
    res, rows = selftest.run_tiny(d, trace=True, overrides=overrides)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0 and res["device"]["platform"] == "cpu"
    setup = next(r for r in rows if r["row"] == "setup")
    assert (setup["start_event"], setup["base_pods"], setup["backlog"],
            setup["nodes"], setup["node_prefilter_k"]) \
        == (320, 173, 327, 6, 0)
    assert setup["fork"] == {k: selftest.FORK_COUNTS[k]
                             for k in whatif_midrun.FORK_FIELDS}
    assert setup["budgets"] == {"16": 64, "64": 128}
    assert setup["reduced"] == {"max_steps_factor": 2}
    calls = [r for r in rows if r["row"] == "call"]
    assert calls and all(
        (r["lockstep_events"], r["pods"], r["chunks"], r["queries"],
         r["finished_lanes"]) == (selftest.EVENTS, 108, 2, 4, 2)
        for r in calls)
    assert all(r["frag_events"] > 0 for r in calls)
    compared = [r for r in rows if r["row"] == "compared"]
    assert all(r["ok"] for r in compared)
    kinds = [r["name"].split(".", 1)[1] for r in compared]
    assert set(kinds) == QUERY_NUMBERS | {"fitness_rel_err"}
    # the gated fitness of a finished run: the two lanes that finished
    assert kinds.count("fitness_rel_err") == 2
    assert len(compared) == 4 * len(QUERY_NUMBERS) + 2
    want = {m["name"] for m in cells.load_cell(CELL).per_layer} \
        - {"device.idle_share.serve"}       # no device trace on the CPU
    assert set(res["metrics"]) == want
    v = {m: res["metrics"][m]["value"] for m in res["metrics"]}
    assert all(math.isfinite(x) for x in v.values())
    assert v["serve.finished_lane_share"] == 50.0
    assert v["serve.fork_waiting_pods"] == 1.0
    assert 0 < v["serve.heap_replay_ms_per_call"] \
        < v["serve.stack_ms_per_call"]
    assert v["serve.fork_state_ms"] > 0
    # failed placements FROM THE FORK over the lanes' events after it:
    # the prefix's nine are not in it
    events = sum(r["lockstep_events"] for r in calls)
    assert 0 < v["serve.retry_share"] < 100.0 * sum(
        r["frag_events"] for r in calls) / events + 1e-9
    assert v["serve.batch_occupancy"] == 100.0
    # what a forked call ships: the base's rows with every batch
    assert v["serve.h2d_kb_per_call"] > 4 * 173 * 61 / 1e3


@pytest.mark.parametrize("seed", [7, 2 ** 31 + 11])
def test_work_per_call_is_the_same_for_every_seed(tiny, seed):
    d, overrides = tiny
    res, rows = selftest.run_tiny(d, seed=seed, overrides=overrides)
    assert res["correct"] is True and res["failed"] == 0
    assert set(res["metrics"]) == {"whatif_pods_per_s", "setup_s"}
    calls = [r for r in rows if r["row"] == "call"]
    assert calls and all(
        (r["lockstep_events"], r["pods"], r["finished_lanes"])
        == (selftest.EVENTS, 108, 2) for r in calls)


def test_a_program_that_lost_the_waiting_pod_is_refused_at_once(
        tiny, monkeypatch):
    """A serve engine that took the fork and dropped the waiting pod would
    run the cell wrongly: the driver reads the ``serve/fork_state`` span
    and ends the run before the warm-up call."""
    from fks_tpu.serve import batcher

    d, overrides = tiny
    real = batcher.QueryFork.__init__

    def init(self, workload):
        real(self, workload)
        self.waiting = 0

    monkeypatch.setattr(batcher.QueryFork, "__init__", init)
    with pytest.raises(SystemExit, match=r"waiting \(0, 1\)") as e:
        selftest.run_tiny(d, overrides=overrides)
    assert "nothing is run" in str(e.value)


def test_a_program_whose_serving_cannot_fork_is_refused_at_once(
        tiny, monkeypatch):
    from fks_tpu.serve import VMServeEngine

    d, overrides = tiny
    monkeypatch.setattr(VMServeEngine, "start_event",
                        property(lambda self: 0))
    with pytest.raises(SystemExit, match="starts at 0"):
        selftest.run_tiny(d, overrides=overrides)


def test_an_answer_that_ends_elsewhere_is_a_failed_operation(
        tiny, monkeypatch):
    """A cut lane has to stop exactly at its budget; the two lanes that
    finish inside theirs stay good operations."""
    d, overrides = tiny
    real = whatif_midrun.Driver.budget
    monkeypatch.setattr(whatif_midrun.Driver, "budget",
                        lambda self, n: real(self, n) + 1)
    res, _ = selftest.run_tiny(d, overrides=overrides)
    assert res["correct"] is False
    assert res["failed"] * 2 == res["attempted"] > 0


def test_the_controls_fail_at_the_tiny_size(tiny, capsys):
    """bfloat16 evaluator sums fail the fitness of every query (bfloat16
    scores alone move no placement among the tiny deployment's six nodes:
    that line is asked for at the cell's own size only), and the program
    handed a fork without the waiting pod, or without the prefix's failed
    placements, is not correct."""
    d, overrides = tiny
    assert selftest.control([5], overrides) == 0
    lines = [json.loads(line) for line in
             capsys.readouterr().out.splitlines() if line.startswith("{")]
    runs = {r["run"]: r for r in lines if "run" in r}
    assert runs["sound"]["finished"] == [4, 24]
    assert runs["scores+sums"]["queries_failing"] == 4
    assert "fitness_rel_err" in runs["scores+sums"]["failed_numbers"]
    lost = runs["waiting_lost"]
    assert lost["queries_failing"] == 4
    assert {"scheduled_diff", "events_diff"} <= set(lost["failed_numbers"])
    assert runs["frag_lost"]["queries_failing"] == 4
    assert runs["frag_lost"]["largest"]["frag_events_diff"] == 9.0
    assert lines[-1] == {"sums_fail_everywhere": True,
                         "scores_fail_identity_everywhere": False,
                         "fork_lost_fails_everywhere": True,
                         "sound_ok": True}


def test_control_needs_the_chip(capsys):
    assert selftest.main(["--control", "--seeds", "1"]) == 3
    assert "no TPU" in capsys.readouterr().err
