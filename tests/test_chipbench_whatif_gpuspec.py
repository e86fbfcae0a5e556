"""The cell ``openb1523-gpuspec25-loaded.whatif8``: as ``BENCHMARK.json``
and its files declare it, run end to end at a tiny size on the CPU through
``chipbench/selftest/whatif_gpuspec.py`` (the driver's ``check`` against
the plain reference ``forked_query_gpuspec``), a program whose serving
lost the field (refused at once), the bfloat16 and the field-lost
controls, and the new reader on spans without its field. The serve path
itself is ``tests/test_serve_fork.py`` and ``tests/test_vm_serve.py``."""
import json
import math
import os

import pytest

from chipbench import cells
from chipbench.reduce import spans as rs
from chipbench.selftest import whatif_gpuspec

CELL = whatif_gpuspec.CELL
CONTROL = "openb1523-loaded.whatif8"
TYPED_CODE = "openb1523-gpuspec25-loaded.codegen8"
NEW = "serve.typed_pod_share"
QUERY_NUMBERS = {"near_ties_admitted", "placements_differ",
                 "gpu_picks_differ", "scheduled_diff", "events_diff",
                 "flags_differ", "waiting_differ", "snapshots_diff",
                 "frag_events_diff", "max_nodes_diff",
                 "fitness_at_cap_rel_err"}


# ----------------------------------------------------------- declaration

def test_the_cell_is_declared_with_its_files():
    cell = cells.load_cell(CELL)
    assert cell.chips == 1 and cell.traffic["driver"] == "whatif_gpuspec"
    cfg, base = cell.config, cells.load_cell(CONTROL).config
    assert cfg["architecture"] is None and len(cfg["source"]) <= 200
    assert (cfg["gpu_spec"], cfg["typed_pods"], cfg["typed_residents"],
            cfg["typed_backlog"], len(cfg["node_models"])) \
        == ("honored", 1375, 1216, 159, 7)
    assert set(cells.verify_files(cfg)) == {"cluster", "trace",
                                            "gpu_mem_mapping", "snapshot"}
    # outside chipbench/: a parent checkout ends in verify_files
    assert not cfg["snapshot"]["file"].startswith("chipbench/")
    # the control's cluster, GPU map, shape but for the nodes the
    # residents sit on, fork event, engine, rule, budget and limits,
    # number for number
    for k in ("cluster", "gpu_mem_mapping", "start_event", "engine",
              "retry_rule", "max_steps_factor", "max_steps_factor_is",
              "node_prefilter_k", "reduced"):
        assert cfg[k] == base[k], k
    assert {**cfg["shape"], "nodes_loaded": 1223} == base["shape"]
    assert cfg["shape"]["nodes_loaded"] == 1245
    for k in ("fitness_rtol", "score_dtype", "score_near_tie_units",
              "near_ties_per_run"):
        assert cfg["guarantees"][k] == base["guarantees"][k], k
    for k in ("share", "seed", "duration", "arrival_order",
              "node_prefilter_k"):
        assert cfg["assumed"][k] == base["assumed"][k], k
    typed = cells.load_cell(TYPED_CODE).config
    for k in ("trace", "node_models", "trace_from"):
        assert cfg[k] == typed[k], k
    assert cfg["assumed"]["list"] == typed["assumed"]["list"]
    assert "first_fit" in cfg["assumed"]["placing_policy"] \
        and "3,569" in cfg["assumed"]["placing_policy"]
    assert "may be placed only on a node whose model is in the set" \
        in cfg["guarantees"]["semantics"]
    assert "as a cordoned node is" in cfg["guarantees"]["semantics"]
    # whatif8-loaded's mix, parameter for parameter
    a, b = (dict(cells.load_cell(n).traffic) for n in (CONTROL, CELL))
    for t in (a, b):
        for k in ("driver", "seed_picks", "checked", "same_as"):
            t.pop(k)
    assert a == b and sum(b["sizes"]) == 1823
    assert [m["name"] for m in cell.end_to_end] == ["whatif_pods_per_s",
                                                    "setup_s"]
    assert {m["name"] for m in cell.per_layer} == {
        m["name"] for m in cells.load_cell(CONTROL).per_layer} | {NEW}


def test_benchmark_json_only_gained_entries():
    with open(os.path.join(cells.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert bench["configs"][-2] == {
        "name": "openb1523-gpuspec25-loaded-snapshot",
        "source": cells.load_cell(CELL).config["source"],
        "file": "chipbench/configs/openb1523-gpuspec25-loaded-snapshot.json",
        "reduced": ["max_steps_factor"],
        "why": bench["configs"][-2]["why"]}
    assert bench["workloads"][-2] == {
        "name": CELL, "config": "openb1523-gpuspec25-loaded-snapshot",
        "traffic": "whatif8-gpuspec", "chips": 1,
        "why": bench["workloads"][-2]["why"]}
    for text in (bench["configs"][-2]["why"], bench["configs"][-2]["source"],
                 bench["workloads"][-2]["why"]):
        assert len(text) <= 200
    # a source of its own among the configurations
    assert len({c["source"] for c in bench["configs"]}) == 9
    assert len(bench["workloads"]) == 11 and len(bench["configs"]) == 9
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    # at the end as PR 49 left it; PR 51 appended the code cells' check
    # a source and uploads a call after, PR 52 the mid-run what-if
    # cell's three
    assert [m["name"] for m in bench["per_layer"][-6:]] == [
        NEW, "tier.check_ms_per_source", "tier.uploads_per_call",
        "serve.heap_replay_ms_per_call", "serve.fork_waiting_pods",
        "serve.finished_lane_share"]
    new = bench["per_layer"][-6]
    meta = json.load(open(os.path.join(cells.HERE, "metrics",
                                       NEW + ".json")))
    assert new == {**{k: meta[k] for k in (
        "name", "unit", "better", "source", "layer", "moves")},
        "workloads": [CELL]}
    assert (new["name"], new["layer"], new["moves"]) \
        == (NEW, "serving serve/", "whatif_pods_per_s")
    # appended to every list that held the control, at its end
    for m in bench["end_to_end"] + bench["per_layer"][:-6] \
            + bench["per_layer"][-5:]:
        lists = m.get("workloads", [])
        # PR 52's waiting pods and finished lanes are its own cell's
        assert (CELL in lists) == (CONTROL in lists), m["name"]
        if CELL in lists:   # last of the cells there were at PR 49
            assert [w for w in lists if w != "openb16-cpu250-midrun.whatif8"][-1] == CELL


def test_the_new_reader_finds_nothing_on_spans_without_its_field():
    """The parent's ``serve/chunk/stack`` spans, and an untyped engine's,
    carry no ``pods`` / ``typed_pods``: the reader returns None and
    nothing raises; with the fields it reads them."""
    from fks_tpu import obs
    from fks_tpu.obs import spans

    read = cells.metric_reader(NEW)
    spans.LOG.clear()
    assert read({}) is None

    def ctx(**fields):
        spans.LOG.clear()
        with obs.span("serve/chunk/stack", chunk=0, bucket=16, lanes=2,
                      real=2, **fields):
            pass
        recs = spans.LOG.snapshot()
        # the window's selection, as ``spans.window_calls`` keeps it
        return {"_span_calls": [rs.Call(recs[0].t0, recs[-1].t1, recs)]}

    assert read(ctx()) is None
    assert read(ctx(pods=40, typed_pods=10)) == 25.0
    assert read(ctx(pods=40, typed_pods=0)) == 0.0   # the field lost


# ------------------------------------------------------------- tiny runs

@pytest.fixture(scope="module")
def deployment(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("whatif_gpuspec"))
    return d, whatif_gpuspec.tiny_deployment(d)


@pytest.fixture()
def tiny(monkeypatch, tmp_path_factory):
    from fks_tpu import utils
    from fks_tpu.obs import spans

    cache = str(tmp_path_factory.getbasetemp() / "whatif_gpuspec_cache")
    spans.LOG.clear()
    monkeypatch.setattr(utils, "place_compile_cache", lambda: cache)
    monkeypatch.setattr(rs, "TOLERANCE", 0.05)     # a loaded test worker


def test_the_tiny_deployment_is_typed_and_forks_from_placed_creates(
        deployment):
    _, over = deployment
    cfg = over["config"]
    assert (cfg["start_event"], cfg["node_prefilter_k"]) \
        == (whatif_gpuspec.E0, 64)
    assert (cfg["typed_pods"], cfg["typed_residents"],
            cfg["typed_backlog"]) == (158, 145, 13)
    assert len(cfg["node_models"]) == 6


def test_cell_runs_end_to_end_with_the_field(tiny, deployment):
    d, over = deployment
    res, rows = whatif_gpuspec.run_tiny(d, trace=True, overrides=over)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0 and res["device"]["platform"] == "cpu"
    setup = next(r for r in rows if r["row"] == "setup")
    assert (setup["start_event"], setup["backlog"], setup["nodes"],
            setup["node_prefilter_k"], setup["typed_residents"],
            setup["typed_backlog"]) == (672, 64, 254, 64, 145, 13)
    assert setup["budgets"] == {"16": 64, "64": 128}
    calls = [r for r in rows if r["row"] == "call"]
    assert calls and all(
        (r["lockstep_events"], r["pods"], r["chunks"], r["queries"])
        == (whatif_gpuspec.EVENTS, 120, 2, 4) for r in calls)
    # the whole backlog's lane fails placements for want of a type
    assert all(r["frag_events"] >= 28 for r in calls)
    compared = [r for r in rows if r["row"] in ("compared", "admitted")]
    assert all(r["ok"] for r in compared)
    assert {r["name"].split(".", 1)[1] for r in compared} == QUERY_NUMBERS
    want = {m["name"] for m in cells.load_cell(CELL).per_layer} \
        - {"device.idle_share.serve"}       # no device trace on the CPU
    assert set(res["metrics"]) == want
    v = {m: res["metrics"][m]["value"] for m in res["metrics"]}
    assert all(math.isfinite(x) for x in v.values())
    # 13 of the backlog's 64 pods name their GPUs; the windows by seed
    assert 100 * 13 / 120 <= v[NEW] <= 100 * (13 + 3 * 13) / 120
    assert v["serve.retry_share"] >= 5.0
    assert v["serve.fork_state_ms"] > 0
    # what a forked call ships: the residents' words with every lane
    assert v["serve.h2d_kb_per_call"] > 4 * 672 * 65 / 1e3


def test_a_program_whose_serving_lost_the_field_is_refused_at_once(
        tiny, deployment, monkeypatch):
    """What the parent commit does with the snapshot copied in: its
    engine forks, and builds every query without ``gpu_spec``. The driver
    ends the run before the warm-up call, the first device program."""
    from fks_tpu.serve import VMServeEngine

    d, over = deployment
    monkeypatch.setattr(VMServeEngine, "typed", property(lambda self: False))
    compiled = []
    monkeypatch.setattr(VMServeEngine, "compiled_for",
                        lambda self, *a: compiled.append(a))
    with pytest.raises(SystemExit, match="carries a query pod's gpu_spec "
                       "and forks from the snapshot's 145 constrained"):
        whatif_gpuspec.run_tiny(d, overrides=over)
    assert not compiled


def test_a_program_whose_serving_cannot_fork_is_refused_at_once(
        tiny, deployment, monkeypatch):
    from fks_tpu.serve import VMServeEngine

    d, over = deployment
    monkeypatch.setattr(VMServeEngine, "start_event",
                        property(lambda self: 0))
    with pytest.raises(SystemExit, match="starts at 0"):
        whatif_gpuspec.run_tiny(d, overrides=over)


def test_the_controls_fail_correct_at_the_tiny_size(tiny, deployment,
                                                    capsys):
    """bfloat16 scores move placements from the loaded cluster; the
    program sent the same queries WITHOUT ``gpu_spec`` fails identity in
    the whole backlog's lane; the sound run passes."""
    _, over = deployment
    assert whatif_gpuspec.control([5], over) == 0
    lines = [json.loads(line) for line in
             capsys.readouterr().out.splitlines() if line.startswith("{")]
    by_run = {r["run"]: r for r in lines if "run" in r}
    assert by_run["scores"]["queries_failing_identity"] >= 1
    assert "placements_differ" in by_run["scores"]["failed_numbers"]
    assert by_run["scores+sums"]["queries_failing"] >= 1
    lost = by_run["field_lost"]
    assert lost["backlog_placements_moved"][0] >= 10
    assert {"placements_differ", "frag_events_diff"} \
        <= set(lost["failed_numbers"])
    assert lines[-1] == {"control_failed_everywhere": True,
                         "scores_fail_identity_everywhere": True,
                         "field_lost_fails_the_backlog_everywhere": True,
                         "sound_ok": True}


def test_control_at_the_cells_own_size_needs_the_chip(capsys):
    assert whatif_gpuspec.main(["--control", "--seeds", "1"]) == 3
    assert "no TPU" in capsys.readouterr().err
