"""The cell ``openb1523-gpuspec25-loaded.codegen8``: as ``BENCHMARK.json``
and its files declare it, run end to end at a tiny size on the CPU through
``chipbench/selftest/gpuspec.py`` (the driver's ``check`` against the
plain reference ``plain_sim_gpuspec``), a program whose workload lost the
constraints (refused at once; and, the refusal off, not correct), the
bfloat16 control, and the new reader on a program without its field.
Device-heavy, and a few items only: the suite's scheduler queues the
files with the most items first, so this one runs in the tail. The
mechanism itself is ``tests/test_gpu_spec.py``."""
import json
import math
import os
import time

import pytest

from chipbench import cells
from chipbench.reduce import spans as rs
from chipbench.selftest import gpuspec
from tests.test_chipbench_loaded_decl import COUNTER_METRICS, SPAN_METRICS

CELL = gpuspec.CELL
CONTROL = "openb1523-loaded.codegen8"
MIDRUN = "openb16-cpu250-midrun.codegen8"
NEW = "sim.typed_pod_share"
WHATIF = "openb1523-gpuspec25-loaded.whatif8"     # PR 49's, on this list
#: constrained pods among the tiny deployment's 640
TYPED = 138
LANE_NUMBERS = {"near_ties_admitted", "placements_differ",
                "gpu_picks_differ", "scheduled_diff", "events_diff",
                "flags_differ", "snapshots_diff", "frag_events_diff",
                "max_nodes_diff", "fitness_at_cap_rel_err"}


# ----------------------------------------------------------- declaration

def test_the_cell_is_declared_with_its_files():
    cell = cells.load_cell(CELL)
    assert cell.chips == 1 and cell.traffic["driver"] == "codegen_gpuspec"
    cfg = cell.config
    assert (cfg["engine"], cfg["retry_rule"], cfg["node_prefilter_k"],
            cfg["code_eval_max_steps"], cfg["start_event"]) \
        == ("flat", "earliest_delete", 64, 2048, 4864)
    assert cfg["reduced"] == ["code_eval_max_steps"] \
        and "code_eval_max_steps" in cfg["reduced_why"]
    assert cfg["architecture"] is None and len(cfg["source"]) <= 200
    assert (cfg["gpu_spec"], cfg["typed_pods"], len(cfg["node_models"])) \
        == ("honored", 1375, 7)
    assert set(cells.verify_files(cfg)) == {"cluster", "trace",
                                            "gpu_mem_mapping", "snapshot"}
    # the control's cluster, GPU map, shape, rule and limits, file for
    # file and number for number; both windows end at the same event
    base = cells.load_cell(CONTROL).config
    for k in ("cluster", "gpu_mem_mapping", "shape", "engine", "retry_rule",
              "node_prefilter_k", "node_prefilter_k_is"):
        assert cfg[k] == base[k], k
    for k in ("fitness_rtol", "score_dtype", "score_near_tie_units",
              "near_ties_per_run"):
        assert cfg["guarantees"][k] == base["guarantees"][k], k
    assert cfg["start_event"] + cfg["code_eval_max_steps"] \
        == base["start_event"] + base["code_eval_max_steps"] == 6912
    for k in ("share", "seed", "duration", "arrival_order"):
        assert cfg["assumed"][k] == base["assumed"][k], k
    assert "may be placed only on a node whose model is in the set" \
        in cfg["guarantees"]["semantics"]
    # codegen8-loaded's mix, parameter for parameter
    a, b = (dict(cells.load_cell(n).traffic) for n in (CONTROL, CELL))
    for t in (a, b):
        for k in ("driver", "seed_picks", "traced", "checked", "same_as"):
            t.pop(k, None)
    assert a == b
    assert [m["name"] for m in cell.end_to_end] == ["lane_events_per_s",
                                                    "setup_s"]
    assert {m["name"] for m in cell.per_layer} == {
        m["name"] for m in cells.load_cell(MIDRUN).per_layer} | {NEW}


def test_benchmark_json_only_gained_entries():
    with open(os.path.join(cells.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # in the place PR 45 gave them (PR 49 appended after them)
    assert bench["configs"][6] == {
        "name": "openb1523-gpuspec25-loaded",
        "source": cells.load_cell(CELL).config["source"],
        "file": "chipbench/configs/openb1523-gpuspec25-loaded.json",
        "reduced": ["code_eval_max_steps"],
        "why": bench["configs"][6]["why"]}
    assert bench["workloads"][8] == {
        "name": CELL, "config": "openb1523-gpuspec25-loaded",
        "traffic": "codegen8-gpuspec", "chips": 1,
        "why": bench["workloads"][8]["why"]}
    for text in (bench["configs"][6]["why"], bench["configs"][6]["source"],
                 bench["workloads"][8]["why"]):
        assert len(text) <= 200
    # a source of its own among the configurations
    assert len({c["source"] for c in bench["configs"]}) == 9
    assert len(bench["workloads"]) == 11 and len(bench["configs"]) == 9
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    # at the end as PR 45 left it; PR 46 appended the interpreter's
    # slots a turn after, PR 47 its narrow turns' share, PR 49 the typed
    # query pods' share, PR 51 the check a source and the uploads a call,
    # PR 52 the mid-run what-if cell's three
    assert [m["name"] for m in bench["per_layer"][-9:]] == [
        NEW, "vm.slots_per_turn", "vm.narrow_turn_share",
        "serve.typed_pod_share", "tier.check_ms_per_source",
        "tier.uploads_per_call", "serve.heap_replay_ms_per_call",
        "serve.fork_waiting_pods", "serve.finished_lane_share"]
    new = bench["per_layer"][-9]
    meta = json.load(open(os.path.join(cells.HERE, "metrics",
                                       NEW + ".json")))
    assert new == {**{k: meta[k] for k in (
        "name", "unit", "better", "source", "layer", "moves")},
        "workloads": [CELL]}
    assert (new["name"], new["layer"], new["moves"]) \
        == (NEW, "engines sim/flat.py", "lane_events_per_s")
    # appended to every list that held the mid-run forked cell, at its end
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m is new:
            continue
        lists = m.get("workloads", [])
        assert (CELL in lists) == (MIDRUN in lists), m["name"]
        if CELL in lists:    # last of the cells there were at PR 45
            assert [w for w in lists
                    if w not in (WHATIF, "openb16-cpu250-midrun.whatif8")][-1] == CELL


def test_the_new_reader_finds_nothing_in_a_program_without_its_field():
    """The parent's ``tier/evaluate`` roots carry no ``typed_pods``: the
    reader returns None and nothing raises; with the field it reads it."""
    from fks_tpu import obs
    from fks_tpu.obs import spans

    read = cells.metric_reader(NEW)
    spans.LOG.clear()
    assert read({}) is None

    def ring(**fields):
        spans.LOG.clear()
        for _ in range(3):      # a warm-up call and a window of two
            with obs.span("tier/evaluate", candidates=8, **fields):
                time.sleep(0.002)
        roots = spans.LOG.snapshot()
        return {"rows": [{}, {}], "workload_pods": 640,
                "call_seconds": sum(r.t1 - r.t0 for r in roots[1:])}

    assert read(ring()) is None
    ctx = ring(typed_pods=TYPED, node_models=6)
    assert rs.window_calls(ctx) is not None
    assert read(ctx) == pytest.approx(100.0 * TYPED / 640)
    ctx.pop("workload_pods")
    ctx.pop("_span_calls")
    assert read(ctx) is None
    spans.LOG.clear()


# ------------------------------------------------------------- tiny runs

@pytest.fixture(scope="module")
def deployment(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("gpuspec_cell"))
    return d, gpuspec.tiny_deployment(d)


@pytest.fixture()
def tiny(monkeypatch, tmp_path_factory, deployment):
    from fks_tpu import utils
    from fks_tpu.obs import spans

    cache = str(tmp_path_factory.getbasetemp() / "gpuspec_cache")
    spans.LOG.clear()
    monkeypatch.setattr(utils, "place_compile_cache", lambda: cache)
    monkeypatch.setenv("FKS_VM_SEG_STEPS", "16")
    monkeypatch.setattr(rs, "TOLERANCE", 0.05)     # a loaded test worker
    d, overrides = deployment
    return lambda **kw: gpuspec.run_tiny(d, overrides=overrides, **kw)


def test_the_tiny_deployment_holds_what_the_real_one_holds(deployment):
    _, overrides = deployment
    cfg = overrides["config"]
    assert (cfg["start_event"], cfg["code_eval_max_steps"],
            cfg["node_prefilter_k"]) == (gpuspec.E0, gpuspec.WINDOW, 64)
    assert cfg["typed_pods"] == TYPED and len(cfg["node_models"]) == 6


def test_cell_runs_end_to_end_under_the_constraints(tiny, deployment):
    res, rows = tiny(trace=True)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0 and res["device"]["platform"] == "cpu"
    setup = next(r for r in rows if r["row"] == "setup")
    assert (setup["lanes"], setup["start_event"], setup["max_steps"],
            setup["node_prefilter_k"], setup["nodes_padded"]) \
        == (gpuspec.LANES, gpuspec.E0, gpuspec.E0 + gpuspec.WINDOW, 64, 256)
    compared = [r for r in rows if r["row"] in ("compared", "admitted")]
    assert all(r["ok"] for r in compared)
    assert {r["name"] for r in compared} == {
        f"lane{i}.{n}" for i in range(gpuspec.LANES)
        for n in LANE_NUMBERS} | {"call.frag_counter_diff"}
    calls = [r for r in rows if r["row"] == "call"]
    assert calls and all(
        (r["lane_events"], r["lockstep_events"])
        == (gpuspec.LANES * gpuspec.WINDOW, gpuspec.WINDOW) for r in calls)
    for m in SPAN_METRICS + COUNTER_METRICS + (
            NEW, "sim.fork_waiting_pods", "sim.fork_replay_us_per_event"):
        assert m in res["metrics"], m
        assert math.isfinite(res["metrics"][m]["value"]), m
    v = {m: res["metrics"][m]["value"] for m in res["metrics"]}
    # the regime: constrained pods wait at the fork, and the window's
    # lanes fail placements of their own
    assert v[NEW] == pytest.approx(100.0 * TYPED / gpuspec.PODS)
    assert v["sim.fork_waiting_pods"] >= 1.0
    assert 2.0 < v["sim.retry_share"] < 60.0
    from fks_tpu.obs import spans
    (fork,) = [r for r in spans.LOG.snapshot()
               if r.name == "tier/fork_state"]
    assert fork.fields["typed_pods"] == TYPED
    assert fork.fields["node_models"] == 6
    assert 1 <= fork.fields["typed_waiting"] <= fork.fields["waiting"]
    assert fork.fields["prefix_failed"] >= fork.fields["waiting"]


@pytest.mark.parametrize("seed", [7, 2 ** 31 + 11])
def test_work_per_call_is_the_same_for_every_seed(tiny, seed):
    res, rows = tiny(trace=False, seed=seed)
    assert res["correct"] is True and res["failed"] == 0
    assert set(res["metrics"]) == {"lane_events_per_s", "setup_s"}
    calls = [r for r in rows if r["row"] == "call"]
    assert calls and all(
        r["lane_events"] == gpuspec.LANES * gpuspec.WINDOW for r in calls)


def test_a_program_that_lost_the_constraints_is_refused_at_once(
        tiny, monkeypatch):
    """A parse that takes the choice and drops the column (the leaves are
    not there): the run ends before the first device program, with the
    reason. A program that runs this cell without them would time the
    unconstrained list, not fail."""
    from fks_tpu.data import TraceParser

    real = TraceParser.parse_workload

    def parse(self, *a, gpu_spec="ignore", **kw):
        return real(self, *a, **kw)

    monkeypatch.setattr(TraceParser, "parse_workload", parse)
    t0 = time.perf_counter()
    with pytest.raises(SystemExit) as e:
        tiny(trace=False)
    assert f"its {TYPED} constrained pods" in str(e.value)
    assert "holds a workload with 0" in str(e.value)
    assert time.perf_counter() - t0 < 20


def test_a_program_older_than_the_choice_is_refused_at_the_parse(
        tiny, monkeypatch):
    from fks_tpu.data import TraceParser

    real = TraceParser.parse_workload

    def parse(self, node_file, pod_file, snapshot_file=None):
        return real(self, node_file, pod_file, snapshot_file=snapshot_file)

    monkeypatch.setattr(TraceParser, "parse_workload", parse)
    with pytest.raises(SystemExit) as e:
        tiny(trace=False)
    assert "whose parse can honour gpu_spec" in str(e.value)


def test_the_program_without_the_mask_is_not_correct(deployment, tiny,
                                                     capsys):
    """The mask-lost control with the program itself: the workload parsed
    WITHOUT the choice, the driver's refusal off, one whole call against
    the constrained reference: identity fails in every lane."""
    from chipbench.selftest.tests import batched_vm_on_cpu

    _, overrides = deployment
    with batched_vm_on_cpu():
        assert gpuspec.mask_lost([2 ** 31 + 7], overrides) == 0
    lines = [json.loads(line) for line in capsys.readouterr().out
             .splitlines() if line.startswith("{")]
    assert lines[-1] == {"mask_lost_failed_every_lane": True}
    (row,) = lines[:-1]
    assert row["run"] == "mask_lost_program"
    assert row["lanes_failing"] == row["lanes"] == gpuspec.LANES
    assert all(n > 0 for n in row["placements_moved"])
    assert "placements_differ" in row["failed_numbers"]


@pytest.mark.parametrize("run", gpuspec.RUNS)
def test_controls_are_not_correct_on_the_tiny_deployment(deployment, run):
    """The forked reference against a faulty stand-in of itself, to the
    tiny cell's cap: bfloat16 scores move placements, bfloat16 sums fail
    the fitness at the cap besides, and a run that lost the constraints
    after the fork fails identity in every lane."""
    d, overrides = deployment
    cell = cells.load_cell(CELL, overrides)
    files = cells.verify_files(cell.config)
    drv = cells.load_driver("codegen_gpuspec").Driver(
        cell, 2 ** 31 + 7, files, None, False)
    lanes = gpuspec.control_numbers(
        cell.config, files, drv._sources(),
        cell.config["start_event"] + cell.config["code_eval_max_steps"], run)
    assert len(lanes) == gpuspec.LANES

    def failing(what):
        return [ns for ns in lanes if any(
            n.name.endswith(what) and not n.ok for n in ns)]

    if run == "mask_lost":
        assert len(failing("placements_differ")) == gpuspec.LANES
    elif run == "scores+sums":
        assert len(failing("fitness_at_cap_rel_err")) == gpuspec.LANES
    else:
        assert failing("placements_differ")
