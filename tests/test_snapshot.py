"""A pinned snapshot of a loaded cluster (``fks_tpu.data.snapshot``): what
an invalid snapshot raises and who refuses one by name, the file format,
the plain reference's fork (``simulate_from``) against its unedited
``simulate`` on the real configuration, and the committed snapshot's
bytes. The loaded carry leaf by leaf is ``tests/test_snapshot_carry.py``,
the forked runners ``tests/test_snapshot_tiers.py``."""
import dataclasses
import gzip
import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import cells
from chipbench.drivers import common
from chipbench.reference import data as ref_data
from chipbench.reference import plain_sim, plain_sim_loaded, policies
from fks_tpu.data import snapshot as snap_mod
from fks_tpu.data.build import make_workload
from fks_tpu.data.snapshot import Snapshot
from fks_tpu.funsearch.backend import CodeEvaluator
from fks_tpu.sim import engine as exact
from fks_tpu.sim import flat
from fks_tpu.sim.engine import SimConfig
from tests import pressure_traces as pt

CONFIG = json.load(open(os.path.join(
    cells.HERE, "configs", "openb1523-loaded.json")))
E0, RULE = CONFIG["start_event"], CONFIG["node_prefilter_k"]
SNAPSHOT_FILE = "openb_snapshot_inflated080_e5888.csv"
CSV = os.path.join(cells.ROOT, "benchmarks", "traces", "csv")


# -------- (3) the reference's fork against its unedited simulate

@pytest.fixture(scope="module")
def real():
    files = cells.verify_files(CONFIG)
    cluster, pods = common.reference_inputs(CONFIG, files)
    rows = plain_sim_loaded.load_rows(files["snapshot"], files["cluster"],
                                      files["trace"])
    return files, cluster, pods, rows


def _switched(after):
    """best_fit for its first E0 calls, ``after`` from then on: the
    unedited ``simulate`` then runs the forked run by itself."""
    calls = [0]

    def policy(pod, state, cand):
        calls[0] += 1
        return (policies.best_fit if calls[0] <= E0 else after)(
            pod, state, cand)

    return policy


def _champion():
    return policies.source_policy(pt.policy_sources()[2])


@pytest.mark.parametrize("name,cap", [("first_fit", None),
                                      ("best_fit", None),
                                      ("champion", E0 + 1024)])
def test_simulate_from_is_the_plain_run_with_the_prefix_decided(real, name,
                                                                cap):
    _, cluster, pods, rows = real
    p = {"first_fit": policies.first_fit, "best_fit": policies.best_fit,
         "champion": _champion()}[name]
    kw = dict(retry="earliest_delete", max_steps=cap, prefilter_k=RULE)
    whole = plain_sim.simulate(cluster, pods, _switched(p), **kw)
    forked = plain_sim_loaded.simulate_from(cluster, pods, rows, p, **kw)
    for f in dataclasses.fields(whole):
        assert np.array_equal(getattr(whole, f.name),
                              getattr(forked, f.name)), f.name
    assert forked.events_processed > E0
    if name == "first_fit":     # ISSUE 31's readings of the regime
        assert forked.num_frag_events == 1194
        assert forked.policy_score == pytest.approx(0.2841, abs=5e-5)
    if name == "best_fit":
        assert forked.num_frag_events == 0
        assert forked.policy_score == pytest.approx(0.3512, abs=5e-5)
    if name == "champion":
        assert forked.num_frag_events > 0 and forked.truncated


def test_the_reference_checks_the_snapshots_rows_itself(real):
    _, cluster, pods, rows = real
    first = min(rows)
    for bad in ({**rows, first: (rows[first][0], 1 << 9)},      # no such GPU
                {i: v for i, v in rows.items() if i != first}):  # not a row
        with pytest.raises(ValueError):
            plain_sim_loaded.simulate_from(
                cluster, pods, bad, policies.first_fit,
                retry="earliest_delete", max_steps=64, prefilter_k=RULE)


# ----------------------------------- (4) invalid snapshots, refusals

def _tiny(durations=(50, 50, 50, 50)):
    nodes = [{"node_id": f"n{i}", "cpu_milli": 4000, "memory_mib": 4096,
              "gpus": [1000, 1000]} for i in range(3)]
    pods = [{"pod_id": f"p{i}", "cpu_milli": 1000, "memory_mib": 1024,
             "num_gpu": 1, "gpu_milli": 600, "creation_time": i,
             "duration_time": d} for i, d in enumerate(durations)]
    return make_workload(nodes, pods)


def _snap(pod, node, gpus, event=None, e0=None, rule=""):
    """A log of attempts; by default one placed CREATE an event."""
    if event is None:
        return snap_mod.placed_creates(pod, node, gpus)
    return Snapshot(pod=np.asarray(pod, np.int32),
                    node=np.asarray(node, np.int32),
                    gpus=np.asarray(gpus, np.uint32),
                    event=np.asarray(event, np.int32), e0=e0, rule=rule)


def test_a_valid_tiny_snapshot_loads():
    wl = _tiny()
    s = flat.initial_state(
        dataclasses.replace(wl, snapshot=_snap([0, 1], [0, 0], [1, 2])),
        SimConfig())
    assert int(s.steps) == 2 and int(s.cpu_left[0]) == 2000
    assert np.asarray(s.gpu_milli_left[0]).tolist() == [400, 400]


WRONG_POD = "the log is not this workload's run"
#: pod 0 leaves at t=1, before pod 1 arrives: events C0, D0, C1, C2
LEAVES = (1, 50, 50, 50)
RETRY = snap_mod.RETRY_RULE


@pytest.mark.parametrize("why,snap,durations", [
    (WRONG_POD, ([0, 2], [0, 0], [1, 2]), None),          # skips an arrival
    (WRONG_POD, ([0, 0], [0, 1], [1, 1]), None),          # a pod twice
    ("not a pod of the workload", ([0, 7], [0, 0], [1, 2]), None),
    ("a node the cluster does not have", ([0, 1], [0, 5], [1, 1]), None),
    ("holds a GPU that node", ([0, 1], [0, 0], [1, 4]), None),
    ("asks for 1 GPUs and holds 2", ([0, 1], [0, 0], [1, 3]), None),
    ("over-committed", ([0, 1], [0, 0], [1, 1]), None),
    # what was "the prefix is not 3 CREATEs": the third of the first
    # three events is pod 0's DELETE, so the log is an attempt too long
    ("attempt 1 is logged at event 1, which is pod p0's DELETE",
     ([0, 1, 2], [0, 1, 2], [1, 1, 1]), LEAVES),
    # ... and an attempt too short when it ends after the DELETE
    ("event 2 of the run is a CREATE attempt of pod p1, and the log has "
     "no attempt left", ([0], [0], [1], [0], 3), LEAVES),
    ("its next attempt at event 3",
     ([0, 2], [0, 1], [1, 1], [0, 3], 4), LEAVES),
    # the same prefix, logged as it is: valid events, wrong pod there
    (WRONG_POD, ([0, 2], [0, 1], [1, 1], [0, 2], 3), LEAVES),
    # a refused pod that holds GPUs, events out of order or past the end
    ("is refused at attempt 1 and holds GPUs",
     ([0, 1], [0, -1], [1, 1], [0, 1], 2),
     None),
    ("not at rising events below 2", ([0, 1], [0, 0], [1, 2], [1, 0], 2),
     None),
    ("not at rising events below 2", ([0, 1], [0, 0], [1, 2], [0, 2], 2),
     None),
    # the run is over before the log is: 4 pods placed and gone by 8
    ("the run ends after 8 events, before event 9",
     ([0, 1, 2, 3], [0, 0, 1, 1], [1, 2, 1, 2], [0, 2, 4, 6], 9),
     (1, 1, 1, 1)),
    # made under a rule the flat engine does not re-queue by
    ("made under the retry rule 'heap_array'",
     ([0, 1], [0, -1], [1, 0], [0, 1], 2, "heap_array"), None),
])
def test_an_invalid_snapshot_raises_on_the_host(why, snap, durations,
                                                monkeypatch):
    wl = _tiny(durations) if durations else _tiny()
    bad = dataclasses.replace(wl, snapshot=_snap(*snap))
    monkeypatch.setattr(jax, "jit", None)   # nothing may reach a program
    with pytest.raises(ValueError, match="snapshot:") as e:
        flat.initial_state(bad, SimConfig())
    assert why in str(e.value)


@pytest.mark.parametrize("row,match", [
    ("nobody,n0,0", "unknown pod 'nobody'"),
    ("p1,nowhere,0", "unknown node 'nowhere'"),
    ("p1,n0,9", "GPU slot 9"),
    ("p1,n0,0", "over-committed"),
    ("p3,n0,1", "the log is not this workload's run"),
])
def test_an_invalid_snapshot_file_raises_when_parsed(tmp_path, row, match):
    wl = _tiny()
    path = tmp_path / "snap.csv"
    path.write_text("name,node_sn,gpus\np0,n0,0\n" + row + "\n")
    with pytest.raises(ValueError, match="snapshot:") as e:
        snap_mod.load_snapshot(path, wl)
    assert match in str(e.value)


def test_row_order_of_the_file_is_free(tmp_path):
    wl = _tiny()
    path = tmp_path / "snap.csv"
    path.write_text("name,node_sn,gpus\np1,n2,1\np0,n0,0|1\n")
    with pytest.raises(ValueError, match="asks for 1 GPUs and holds 2"):
        snap_mod.load_snapshot(path, wl)
    path.write_text("name,node_sn,gpus\np1,n2,1\np0,n0,0\n")
    s = snap_mod.load_snapshot(path, wl)
    assert np.asarray(s.pod).tolist() == [0, 1]
    assert np.asarray(s.node).tolist() == [0, 2]
    assert np.asarray(s.gpus).tolist() == [1, 2]
    assert snap_mod.snapshot_csv(wl, s) \
        == "name,node_sn,gpus\np0,n0,0\np1,n2,1\n"


def test_a_placement_that_fails_is_in_the_snapshot():
    """What ``make_snapshot`` refused before PR 42: a prefix with failed
    placements is a snapshot like any other (two refusals, nobody to wait
    for, so both pods are dropped); only a run that is over before the
    fork cannot be forked."""
    wl = _tiny()
    refuse = lambda pod, nodes: jnp.zeros_like(nodes.cpu_milli_left)  # noqa: E731
    snap = flat.make_snapshot(wl, refuse, 2)
    assert (snap.e0, snap.rule) == (2, RETRY)
    assert np.asarray(snap.pod).tolist() == [0, 1]
    assert np.asarray(snap.node).tolist() == [-1, -1]
    s = flat.initial_state(dataclasses.replace(wl, snapshot=snap),
                           SimConfig())
    assert (int(s.steps), int(s.frag_count), int(s.pending)) == (2, 2, 2)
    assert np.asarray(s.aux).tolist()[:2] == [flat.AUX_WAITING] * 2
    with pytest.raises(ValueError, match="cannot be forked at event 9: it "
                                         "ended after 4 events"):
        flat.make_snapshot(wl, refuse, 9)


def test_engines_and_runners_that_cannot_fork_refuse_by_name():
    wl = dataclasses.replace(_tiny(), snapshot=_snap([0], [0], [1]))
    # the exact engine forks since PR 37 (tests/test_serve_fork.py); the
    # candidate tiers stay on the flat engine's fork
    assert int(exact.initial_state(wl, SimConfig()).steps) == 1
    with pytest.raises(ValueError, match="snapshot: flat engine only"):
        CodeEvaluator(wl, engine="exact")
    from fks_tpu.parallel import make_population_eval
    with pytest.raises(ValueError, match="snapshot: flat engine only"):
        make_population_eval(wl, engine="fused_interpret")
    from fks_tpu.parallel.traces import strip_ids
    with pytest.raises(ValueError, match="snapshot: trace batching "
                                         "starts"):
        strip_ids(wl)
    from fks_tpu.scenarios.generator import ScenarioSpec, perturb_workload
    with pytest.raises(ValueError, match="snapshot: a scenario"):
        perturb_workload(wl, ScenarioSpec(name="s", seed=0))
    with pytest.raises(ValueError, match="fault events or a decision trace"):
        flat.initial_state(wl, SimConfig(decision_trace=True))


def test_the_cli_takes_a_snapshot_on_the_flat_engine_only(capsys):
    from fks_tpu import cli

    ap = cli.build_parser()
    for cmd in ("simulate", "bench", "evolve"):
        a = ap.parse_args([cmd, "--engine", "flat", "--snapshot", "s.csv"])
        assert a.snapshot == "s.csv"
    with pytest.raises(SystemExit):
        ap.parse_args(["serve", "--snapshot", "s.csv"])
    with pytest.raises(SystemExit):
        cli.main(["simulate", "--snapshot", "s.csv"])
    assert "flat engine only" in capsys.readouterr().err
    with pytest.raises(SystemExit):
        cli.main(["evolve", "--engine", "flat", "--snapshot", "s.csv",
                  "--parity-sample", "2"])
    assert "parity sentinel" in capsys.readouterr().err


def test_without_a_snapshot_the_spans_say_event_zero():
    from fks_tpu.obs import spans

    spans.LOG.clear()
    ev = CodeEvaluator(_tiny(), cfg=SimConfig(max_steps=8),
                       engine="flat", vm_batch=True)
    ev.evaluate(pt.policy_sources()[:2])
    log = spans.LOG.snapshot()
    assert not [r for r in log if r.name == "tier/fork_state"]
    for name in ("tier/evaluate", "tier/vm_batch/launch"):
        (r,) = [r for r in log if r.name == name]
        assert r.fields["start_event"] == 0
    assert ev.last_eval_stats["start_event"] == 0
    assert ev.last_eval_stats["frag_events"] == 0


def test_evolution_does_not_rescore_a_forked_search_and_says_so():
    """The exact engine cannot fork, so a forked search keeps its own
    fitness: said once when the search is built, and on every saved
    entry, whose ``score`` is then not an exact one."""
    from fks_tpu.funsearch import EvolutionConfig, FakeLLM
    from fks_tpu.funsearch.evolution import FunSearch

    forked = dataclasses.replace(_tiny(), snapshot=_snap([0], [0], [1]))
    ev = CodeEvaluator(forked, engine="flat")
    said = []
    fs = FunSearch(ev, EvolutionConfig(), backend=FakeLLM(seed=0),
                   log=said.append)
    assert fs._search_fitness_is_final
    (line,) = [m for m in said if "no exact re-rank" in m]
    assert "[flat] fitness from event 1" in line
    code = "def priority_function(pod, node):\n    return 1\n"
    assert fs._exact_score(code, 0.25) == 0.25
    assert fs.rescore_fallbacks == 0 and fs._exact_eval is None
    assert fs._champion_fields(code, 0.25) == {
        "score": 0.25, "search_score": 0.25, "search_engine": "flat",
        "score_engine": "flat", "start_event": 1}
    fs._admit(code, 0.25)
    assert said[-1].lstrip().startswith("NEW BEST 0.2500 (gen")


# ------------------------------------------ (5) the committed snapshot

def test_committed_snapshot_is_what_the_command_writes(tmp_path):
    from fks_tpu import cli

    out = tmp_path / "snap.csv.gz"
    path, snap = cli.write_snapshot(out)
    committed = os.path.join(CSV, SNAPSHOT_FILE + ".gz")
    with open(path, "rb") as a, open(committed, "rb") as b:
        got, want = a.read(), b.read()
    assert got == want
    assert hashlib.sha256(want).hexdigest() == CONFIG["snapshot"]["sha256"]
    assert CONFIG["snapshot"]["file"].endswith(SNAPSHOT_FILE + ".gz")
    assert snap.e0 == E0 == CONFIG["start_event"]
    assert len(np.unique(snap.node)) == 1223
    text = gzip.decompress(want).decode().splitlines()
    assert text[0] == "name,node_sn,gpus" and len(text) == 1 + E0
    assert text[1] == "inflated-pod-0000,openb-node-0259,0"


def test_the_references_own_best_fit_prefix_gives_the_same_rows(real):
    """The program placed the residents in float32, upstream's best_fit
    runs in float64: the file does not hang on the precision."""
    _, cluster, pods, rows = real
    ref = plain_sim.simulate(cluster, pods, policies.best_fit,
                             retry="earliest_delete", max_steps=E0,
                             prefilter_k=RULE)
    assert (ref.scheduled_pods, ref.num_frag_events) == (E0, 0)
    mine = {i: (int(ref.assigned_node[i]), int(ref.assigned_gpus[i]))
            for i in np.flatnonzero(ref.assigned_node >= 0)}
    assert mine == rows


def test_what_the_configuration_says_of_the_snapshot(real):
    """70 % of the GPUs, the pods that share one, no early leaver."""
    _, cluster, pods, rows = real
    idx = np.asarray(sorted(rows))
    assert idx.tolist() == list(range(E0))      # the first E0 arrivals
    asked = (pods.num_gpu[idx] * pods.gpu_milli[idx]).sum()
    assert round(100 * asked / cluster.gpu_milli_total.sum(), 1) == 69.9
    assert round(100 * pods.cpu[idx].sum() / cluster.cpu_total.sum(),
                 1) == 48.8
    gpu = pods.num_gpu[idx] > 0
    assert int(gpu.sum()) == 5084
    assert int((gpu & (pods.gpu_milli[idx] < 1000)).sum()) == 2233
    assert (pods.creation_time[idx] + pods.duration[idx]).min() > E0
    assert ref_data.load_pods(real[0]["trace"]).p == 6695


# ------------- (6) the typed cluster's snapshot for what-if serving (PR 49)

TYPED_CONFIG = json.load(open(os.path.join(
    cells.HERE, "configs", "openb1523-gpuspec25-loaded-snapshot.json")))
TYPED_SNAPSHOT = "openb_snapshot_gpuspec25_inflated080_firstfit_e5888.csv.gz"
COMMITTED_SHA256 = {
    "openb_snapshot_inflated080_e5888.csv.gz":
        "093c73f70d4260889cc95c434ca77a494294e7405ad1969b9d19e552ff8c4f32",
    "openb_snapshot_cpu250_firstfit_e12288.csv.gz":
        "bb59eba3054930ec773f3bbc4eaf1068fdde8c9757042319146777723fa7b07c",
    "openb_snapshot_gpuspec25_inflated080_e4864.csv.gz":
        "38f35dcb3daef1d1c55b1c1c9ee041b15c1822654bfc5df95525e89ead10205b",
    TYPED_SNAPSHOT:
        "46626075d2ae4b66319d3d5816b2d2e6cd4b5683d6fdec770419f4cdc257c362",
}


@pytest.mark.parametrize("name", sorted(COMMITTED_SHA256))
def test_every_committed_snapshot_keeps_its_sha256(name):
    """The three that stood before PR 49 are the bytes they were, and the
    fourth is pinned with them; ``cli.COMMITTED_SNAPSHOTS`` names no
    other."""
    from fks_tpu import cli

    assert sorted(cli.COMMITTED_SNAPSHOTS) == sorted(COMMITTED_SHA256)
    with open(os.path.join(CSV, name), "rb") as f:
        assert hashlib.sha256(f.read()).hexdigest() == COMMITTED_SHA256[name]


def test_the_typed_serving_snapshot_is_what_the_command_writes(tmp_path):
    from fks_tpu import cli

    assert cli.COMMITTED_SNAPSHOTS[TYPED_SNAPSHOT] == (
        "openb_node_list_all_node.csv",
        "openb_pod_list_gpuspec25_inflated080.csv", "first_fit", 5888, 64,
        "honor")
    path, snap = cli.write_snapshot(tmp_path / "snap.csv.gz",
                                    name=TYPED_SNAPSHOT)
    with open(path, "rb") as a, open(os.path.join(CSV, TYPED_SNAPSHOT),
                                     "rb") as b:
        got, want = a.read(), b.read()
    assert got == want
    assert hashlib.sha256(want).hexdigest() \
        == TYPED_CONFIG["snapshot"]["sha256"] \
        == COMMITTED_SHA256[TYPED_SNAPSHOT]
    assert TYPED_CONFIG["snapshot"]["file"].endswith(TYPED_SNAPSHOT)
    node = np.asarray(snap.node)
    assert (snap.e0, snap.rule, len(node), int((node < 0).sum()),
            len(np.unique(node))) == (5888, "", 5888, 0, 1245)
    assert snap.e0 == TYPED_CONFIG["start_event"] == E0
    assert TYPED_CONFIG["shape"]["nodes_loaded"] == 1245
    text = gzip.decompress(want).decode().splitlines()
    assert text[0] == "name,node_sn,gpus" and len(text) == 1 + 5888


def test_the_references_own_first_fit_prefix_gives_the_typed_rows():
    """first_fit's score has no arithmetic in it, so the file hangs on no
    precision: the plain reference's own first_fit under the type rule
    places the first 5,888 arrivals on the same nodes and GPUs, refuses
    none of them, and the configuration's counts are the reference's."""
    from chipbench.reference import forked_query_gpuspec as fq
    from chipbench.reference import plain_sim_gpuspec as gs

    files = cells.verify_files(TYPED_CONFIG)
    cluster, pods = common.reference_inputs(TYPED_CONFIG, files)
    allowed = gs.load_allowed(files["cluster"], files["trace"])
    rows = plain_sim_loaded.load_rows(files["snapshot"], files["cluster"],
                                      files["trace"])
    ref = gs.simulate(cluster, pods, allowed, policies.first_fit,
                      retry="heap_array", max_steps=5888, prefilter_k=RULE)
    assert (ref.scheduled_pods, ref.num_frag_events,
            ref.num_snapshots) == (5888, 0, 17)
    mine = {i: (int(ref.assigned_node[i]), int(ref.assigned_gpus[i]))
            for i in np.flatnonzero(ref.assigned_node >= 0)}
    assert mine == rows and sorted(rows) == list(range(5888))
    typed = ~allowed.all(axis=1)
    assert (int(typed.sum()), int(typed[:5888].sum()),
            int(typed[5888:].sum())) \
        == (TYPED_CONFIG["typed_pods"], TYPED_CONFIG["typed_residents"],
            TYPED_CONFIG["typed_backlog"]) == (1375, 1216, 159)
    idx = np.arange(5888)
    asked = (pods.num_gpu[idx] * pods.gpu_milli[idx]).sum()
    assert round(100 * asked / cluster.gpu_milli_total.sum(), 1) == 69.9
    assert fq.validate_snapshot(cluster, pods, rows, allowed,
                                "heap_array").max_nodes == 1245
    assert fq.node_models(files["cluster"]).count("") == 310
    assert sorted(set(fq.node_models(files["cluster"])) - {""}) \
        == TYPED_CONFIG["node_models"]
