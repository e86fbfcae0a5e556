"""A snapshot of a moment of a real run (PR 42; ``fks_tpu.data.snapshot``):
the committed file of ``openb16-cpu250-midrun`` and its bytes, the file
format with its two further columns, the plain reference's fork
(``chipbench/reference/plain_sim_midrun.py``) against its unedited
``simulate`` and what it refuses, and that every engine that forks
forks from a prefix with a departure or a refusal (the exact engine and
serving since PR 52: ``tests/test_serve_fork_midrun.py``). The forked
carry leaf by leaf is ``tests/test_snapshot_carry.py``, the forked runners
``tests/test_snapshot_tiers.py``, the invalid logs
``tests/test_snapshot.py``."""
import dataclasses
import gzip
import hashlib
import json
import os

import numpy as np
import pytest

from chipbench import cells
from chipbench.drivers import common
from chipbench.reference import plain_sim, policies
from chipbench.reference import plain_sim_midrun as mid
from fks_tpu.data import snapshot as snap_mod
from fks_tpu.data.build import make_workload
from fks_tpu.sim import engine as exact
from fks_tpu.sim import flat
from fks_tpu.sim.engine import SimConfig

CONFIG = json.load(open(os.path.join(
    cells.HERE, "configs", "openb16-cpu250-midrun.json")))
E0 = CONFIG["start_event"]
CAP = E0 + CONFIG["code_eval_max_steps"]
SNAPSHOT_FILE = "openb_snapshot_cpu250_firstfit_e12288.csv"
CSV = os.path.join(cells.ROOT, "benchmarks", "traces", "csv")
RETRY = snap_mod.RETRY_RULE


@pytest.fixture(scope="module")
def real():
    files = cells.verify_files(CONFIG)
    cluster, pods = common.reference_inputs(CONFIG, files)
    log = mid.load_log(files["snapshot"], files["cluster"], files["trace"])
    return files, cluster, pods, log


# ------------------------------------------- the committed snapshot

def test_committed_snapshot_is_what_the_command_writes(tmp_path):
    from fks_tpu import cli

    out = tmp_path / "snap.csv.gz"
    path, snap = cli.write_snapshot(out, name=SNAPSHOT_FILE + ".gz")
    committed = os.path.join(CSV, SNAPSHOT_FILE + ".gz")
    with open(path, "rb") as a, open(committed, "rb") as b:
        got, want = a.read(), b.read()
    assert got == want
    assert hashlib.sha256(want).hexdigest() == CONFIG["snapshot"]["sha256"]
    assert CONFIG["snapshot"]["file"].endswith(SNAPSHOT_FILE + ".gz")
    # outside chipbench/: a parent checkout ends in verify_files
    assert not CONFIG["snapshot"]["file"].startswith("chipbench/")
    assert (snap.e0, snap.rule) == (E0, RETRY) == (
        12288, CONFIG["retry_rule"])
    node = np.asarray(snap.node)
    assert (len(node), int((node < 0).sum())) == (6670, 1002)
    text = gzip.decompress(want).decode().splitlines()
    assert text[0] == "name,node_sn,gpus,event,rule"
    assert len(text) == 1 + 6670 + 1
    assert text[1] == "openb-pod-2356,openb-node-0000,0,0,"
    assert text[-1] == ",,,12288,earliest_delete"
    assert set(cli.COMMITTED_SNAPSHOTS) == {
        SNAPSHOT_FILE + ".gz", "openb_snapshot_inflated080_e5888.csv.gz",
        "openb_snapshot_gpuspec25_inflated080_e4864.csv.gz",
        # PR 49's, the typed cluster's for what-if serving
        "openb_snapshot_gpuspec25_inflated080_firstfit_e5888.csv.gz"}


def test_the_old_file_reads_as_it_did_and_writes_its_own_bytes():
    """The all-CREATE file of the two loaded configurations has no new
    column: it reads to one placed CREATE an event under no rule, and the
    writer gives back its bytes."""
    loaded = json.load(open(os.path.join(
        cells.HERE, "configs", "openb1523-loaded.json")))
    files = cells.verify_files(loaded)
    wl = common.parse_workload(loaded, files)
    snap = snap_mod.load_snapshot(files["snapshot"].removesuffix(".gz"), wl)
    assert (snap.e0, snap.rule) == (5888, "")
    assert np.array_equal(snap.event, np.arange(5888))
    assert np.array_equal(snap.pod, snap_mod.event_order(wl.pods)[:5888])
    assert (np.asarray(snap.node) >= 0).all()
    with gzip.open(files["snapshot"], "rt") as f:
        assert snap_mod.snapshot_csv(wl, snap) == f.read()


def test_what_the_configuration_says_of_the_state_at_the_fork(real):
    """By the reference's own run of the log: arrived, departed, waiting,
    the failed placements and snapshots of the prefix."""
    _, cluster, pods, log = real
    at = mid.validate(cluster, pods, log, RETRY)
    placed = {i for i, node, _ in log.attempts if node >= 0}
    arrived = {i for i, _, _ in log.attempts}
    assert (len(arrived), len(placed), pods.p - len(arrived)) \
        == (5669, 5668, 3751)
    assert (at.steps, at.num_frag_events, at.num_snapshots) \
        == (E0, 1002, 26)
    assert round(at.frag_mean, 4) == 0.0617
    assert at.scheduled_pods == 5668 and len(arrived - placed) == 1
    says = CONFIG["state_at_fork"]
    for n in ("5,669", "5,618", "50 are resident", "1,002", "0.0617",
              "26 utilization", "3,751"):
        assert n in says, n
    assert CONFIG["shape"] == {**json.load(open(os.path.join(
        cells.HERE, "configs", "openb16-default.json")))["shape"],
        "pods": 9420, "queue_width": 9472}
    assert CONFIG["shape"]["queue_width"] == -(-pods.p // 128) * 128


def test_the_references_own_first_fit_makes_the_same_log(real):
    """The program logged its float32 first_fit; upstream's runs in
    float64 and integers decide: the file does not hang on a precision.
    The reference's run of its own first_fit to event E0 places what the
    log places and refuses as often."""
    _, cluster, pods, log = real
    ref = plain_sim.simulate(cluster, pods, policies.first_fit,
                             retry=RETRY, max_steps=E0)
    mine = {i: (int(ref.assigned_node[i]), int(ref.assigned_gpus[i]))
            for i in np.flatnonzero(ref.assigned_node >= 0)}
    assert mine == {i: (node, bits) for i, node, bits in log.attempts
                    if node >= 0}
    assert ref.num_frag_events == sum(
        1 for _, node, _ in log.attempts if node < 0) == 1002


# ---------------- the reference's fork against its unedited simulate

@pytest.mark.parametrize("cap", [CAP, None])
def test_simulate_from_is_the_plain_run_with_the_prefix_decided(real, cap):
    """The same policy before and after the fork gives the unforked
    ``Result``, to the cell's cap and to the end of the trace."""
    _, cluster, pods, log = real
    whole = plain_sim.simulate(cluster, pods, policies.first_fit,
                               retry=RETRY, max_steps=cap)
    forked = mid.simulate_from(cluster, pods, log, policies.first_fit,
                               retry=RETRY, max_steps=cap)
    for f in dataclasses.fields(whole):
        assert np.array_equal(getattr(whole, f.name),
                              getattr(forked, f.name)), f.name
    if cap:
        assert forked.truncated and forked.events_processed == CAP
        assert forked.num_frag_events == 1537      # 535 after the fork
    else:       # ISSUE 42's reading of first_fit's whole run
        assert (forked.events_processed, forked.num_frag_events) \
            == (24720, 5880)
        assert forked.policy_score > 0


def _tiny(durations=(50, 50, 50, 50)):
    nodes = [{"node_id": f"n{i}", "cpu_milli": 4000, "memory_mib": 4096,
              "gpus": [1000, 1000]} for i in range(2)]
    pods = [{"pod_id": f"p{i}", "cpu_milli": 1000, "memory_mib": 1024,
             "num_gpu": 2, "gpu_milli": 600, "creation_time": i,
             "duration_time": d} for i, d in enumerate(durations)]
    return make_workload(nodes, pods)


def _reference(wl):
    c, p = wl.cluster, wl.pods
    n, q = c.num_nodes, p.num_pods
    take = lambda x, k: np.asarray(x, np.int64)[:k]  # noqa: E731
    cluster = plain_sim.Cluster(
        take(c.cpu_total, n), take(c.mem_total, n), take(c.gpu_declared, n),
        take(c.num_gpus, n), take(c.gpu_milli_total, n),
        np.asarray(c.gpu_mask)[:n])
    pods = plain_sim.Pods(*(take(x, q) for x in (
        p.cpu, p.mem, p.num_gpu, p.gpu_milli, p.creation_time, p.duration,
        p.tie_rank)))
    return cluster, pods


#: two nodes of two GPUs, pods of two GPUs: p0 and p1 take a node each,
#: p2 is refused and re-queued at 51, p3 too; events C0 C1 R2 R3
LOG = [(0, 0, 3), (1, 1, 3), (2, -1, 0), (3, -1, 0)]


@pytest.mark.parametrize("why,attempts,e0,rule", [
    (None, LOG, 4, RETRY),
    ("and the run meets pod 2 there", [LOG[0], LOG[1], LOG[3], LOG[2]], 4,
     RETRY),
    ("which cannot hold it", [LOG[0], (1, 0, 3)] + LOG[2:], 4, RETRY),
    ("best-fit picks", [(0, 0, 3), (1, 1, 3)], 2, ""),
    ("is not in the snapshot's log", LOG[:3], 4, RETRY),
    ("used 3 of 4 attempts", LOG, 3, RETRY),
    ("made under the retry rule 'heap_array'", LOG, 4, "heap_array"),
    ("made under the retry rule ''", LOG, 4, ""),
])
def test_the_reference_checks_the_log_itself(why, attempts, e0, rule):
    wl = _tiny()
    if why == "best-fit picks":     # one GPU a pod: best-fit takes GPU 0
        wl = dataclasses.replace(wl, pods=dataclasses.replace(
            wl.pods, num_gpu=np.where(wl.pods.pod_mask, 1, 0)))
        attempts = [(0, 0, 2), (1, 0, 1)]
    cluster, pods = _reference(wl)
    log = mid.Log(list(attempts), e0, rule)
    if why is None:
        at = mid.validate(cluster, pods, log, RETRY)
        assert (at.steps, at.num_frag_events, at.scheduled_pods) == (4, 2, 2)
        return
    with pytest.raises(ValueError) as e:
        mid.validate(cluster, pods, log, RETRY)
    assert why in str(e.value)


# ------------------------------------------------- the file format

def test_the_file_round_trips_and_its_row_order_is_free(tmp_path):
    wl = _tiny()
    refuse_third = flat.make_snapshot(wl, _first_fit(), 4)
    assert np.asarray(refuse_third.node).tolist() == [0, 1, -1, -1]
    text = snap_mod.snapshot_csv(wl, refuse_third)
    assert text == ("name,node_sn,gpus,event,rule\np0,n0,0|1,0,\n"
                    "p1,n1,0|1,1,\np2,,,2,\np3,,,3,\n"
                    ",,,4,earliest_delete\n")
    rows = text.splitlines()
    path = tmp_path / "snap.csv"
    path.write_text("\n".join([rows[0]] + rows[:0:-1]) + "\n")
    back = snap_mod.load_snapshot(path, wl)
    assert (back.e0, back.rule) == (4, RETRY)
    for f in ("pod", "node", "gpus", "event"):
        assert np.array_equal(getattr(back, f), getattr(refuse_third, f))
    # without its last row the file does not say where the log ends
    path.write_text("\n".join(rows[:-1]) + "\n")
    with pytest.raises(ValueError, match="no last row"):
        snap_mod.load_snapshot(path, wl)
    names = tmp_path / "names.csv"      # the reference reads names only
    names.write_text("name,sn\np0,n0\np1,n1\np2,\np3,\n")
    with pytest.raises(ValueError, match="no last row"):
        mid.load_log(str(path), str(names), str(names))
    path.write_text(text)
    log = mid.load_log(str(path), str(names), str(names))
    assert (log.attempts, log.e0, log.rule) == (LOG, 4, RETRY)


def _first_fit():
    from fks_tpu.models import zoo

    return zoo.first_fit()


def test_a_prefix_of_a_midrun_snapshot_is_one():
    wl = _tiny((1, 50, 50, 50))     # events C0 D0 C1 C2 C3(refused)
    full = flat.make_snapshot(wl, _first_fit(), 5)
    assert np.asarray(full.event).tolist() == [0, 2, 3, 4]
    assert np.asarray(full.node).tolist() == [0, 0, 1, -1]
    for e0 in range(6):
        want = flat.make_snapshot(wl, _first_fit(), e0)
        got = snap_mod.head(full, e0)
        assert (got.e0, got.rule) == (want.e0, want.rule) == (
            e0, RETRY if e0 == 5 else "")
        for f in ("pod", "node", "gpus", "event"):
            assert np.array_equal(getattr(got, f), getattr(want, f)), e0


# ------------------------------- every engine that forks, forks from it

def test_the_exact_engine_and_serving_fork_from_a_departure_or_a_refusal():
    """The two prefixes that the exact engine and serving refused by name
    until PR 52 (C0 D0, and C0 D0 C1 C2 C3 with the last refused and its
    retry queued): ``initial_state`` and ``QueryFork`` now take them, and
    the exact engine's run from each to the end is the plain reference's
    (``plain_sim_fork``: the log's events as logged, then free under
    ``heap_array``). The fused engine goes on refusing every snapshot."""
    from chipbench.reference import plain_sim_fork
    from fks_tpu.serve.batcher import QueryFork
    from fks_tpu.sim import fused

    wl = _tiny((1, 50, 50, 50))
    full = flat.make_snapshot(wl, _first_fit(), 5)
    cluster, pods = _reference(wl)
    for e0, held in ((2, (1, 0, 0, 0)), (5, (1, 1, 2, 1))):
        snap = snap_mod.head(full, e0)
        forked = dataclasses.replace(wl, snapshot=snap)
        state = exact.initial_state(forked, SimConfig())
        assert int(state.steps) == int(state.events_processed) == e0
        assert int(flat.initial_state(forked, SimConfig()).steps) == e0
        fork = QueryFork(forked)
        assert (fork.prefix.departed, fork.prefix.refused, fork.residents,
                fork.waiting) == held
        assert (fork.e0, fork.base) == (e0, 1 if e0 == 2 else 4)
        log = mid.Log([(int(i), int(nd), int(g)) for i, nd, g in zip(
            snap.pod, snap.node, snap.gpus)], e0, snap.rule)
        ref, waiting = plain_sim_fork.simulate(
            cluster, pods, log, policies.first_fit, retry="heap_array")
        res = exact.simulate(forked, _first_fit(), SimConfig())
        assert np.array_equal(np.asarray(res.assigned_node)[:4],
                              ref.assigned_node)
        assert np.array_equal(np.asarray(res.assigned_gpus)[:4],
                              ref.assigned_gpus)
        assert (int(res.events_processed), int(res.scheduled_pods),
                int(res.num_fragmentation_events), bool(res.truncated)) \
            == (ref.events_processed, ref.scheduled_pods,
                ref.num_frag_events, ref.truncated) == (9, 4, 1, False)
        assert not waiting.any()
        np.testing.assert_allclose(float(res.policy_score),
                                   ref.policy_score, rtol=2e-6)
        with pytest.raises(ValueError, match="snapshot: flat engine only"):
            fused._build_plan(forked, SimConfig())
