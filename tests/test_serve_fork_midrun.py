"""The exact engine and what-if serving forked from a MOMENT of a run (PR
52): a prefix with departures, refused placements and a waiting pod whose
retry is queued (``chipbench/selftest/midrun.py::tiny_deployment``: six
nodes of upstream's 16 under the first 500 arrivals of cpu250, the first
320 events of ``first_fit``'s flat run logged under ``earliest_delete``:
139 departures, 9 refusals, 33 residents, 1 waiting), against the plain
reference whose retry rule changes at the fork
(``chipbench/reference/plain_sim_fork.py`` under
``forked_query_midrun.py``: the log's events as logged, then FREE under
``heap_array``). One definition for every engine (``fks_tpu/data/
snapshot.py``): a snapshot says what HAPPENED, and every later event is
the engine's own. The placed-CREATE fork is ``tests/test_serve_fork.py``;
the benchmark's cell on this path ``tests/test_chipbench_whatif_midrun.py``.
"""
import dataclasses

import jax
import numpy as np
import pytest

from chipbench.drivers.whatif import FIELDS
from chipbench.reference import data, plain_sim_fork, policies
from chipbench.reference import forked_query_midrun as fq
from chipbench.reference import plain_sim_midrun as mid
from chipbench.selftest import midrun
from fks_tpu import obs
from fks_tpu.data import TraceParser
from fks_tpu.data import snapshot as snap_mod
from fks_tpu.funsearch import transpiler
from fks_tpu.models import zoo
from fks_tpu.serve import ServeService, ShapeEnvelope, VMServeEngine
from fks_tpu.serve.batcher import QueryFork, build_query_workload
from fks_tpu.sim import engine as exact
from fks_tpu.sim.engine import SimConfig, loop_tables
from tests.test_serve_fork import _ask, _champion

E0 = midrun.E0
RTOL = 16 * 2.0 ** -23      # the configurations' fitness_rtol: 16 f32 ulps


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """(forked workload, reference cluster, pods, log, the pods that have
    not arrived at the fork in arrival order)."""
    d = str(tmp_path_factory.mktemp("midrun_fork"))
    files = {k: v["file"] for k, v in
             midrun.tiny_deployment(d)["config"].items()
             if isinstance(v, dict)}
    wl = TraceParser(d).parse_workload("nodes.csv", "pods.csv",
                                       snapshot_file="snapshot.csv")
    cluster = data.load_cluster(files["cluster"], files["gpu_mem_mapping"])
    pods = data.load_pods(files["trace"])
    log = mid.load_log(files["snapshot"], files["cluster"], files["trace"])
    arrived = set(fq.base_of(log))
    rest = [i for i in sorted(range(pods.p), key=lambda i: (
        int(pods.creation_time[i]), int(pods.rank[i]))) if i not in arrived]
    return wl, cluster, pods, log, rest


def _rows(pods, idx):
    return [{k: int(getattr(pods, a)[i]) for k, a in FIELDS} for i in idx]


def test_the_prefix_is_a_moment_of_a_run(tiny):
    """What the tiny prefix holds, by the program's replay and by the
    reference's own run of the log."""
    wl, cluster, pods, log, rest = tiny
    prefix = exact.fork_prefix(wl)
    fork = QueryFork(wl)
    assert (prefix.e0, prefix.departed, prefix.refused) == (E0, 139, 9)
    assert (fork.base, fork.residents, fork.waiting, len(rest)) \
        == (173, 33, 1, 327) == (len(fq.base_of(log)), 33, 1, pods.p - 173)
    assert wl.snapshot.rule == log.rule == snap_mod.RETRY_RULE
    taken, keyed = fq.inputs(pods, log, ())
    at = plain_sim_fork.validate(cluster, taken, keyed)
    assert (at.steps, at.num_frag_events, at.scheduled_pods) == (E0, 9, 172)
    # the base's own heap: the residents' DELETEs and the queued retry
    assert len(fork.prefix.heap) == 34 == fork.residents + fork.waiting
    # the ops of the whole workload's replay are the base's, re-keyed
    assert len(prefix.pushes) == len(fork.prefix.pushes) == E0
    assert [p is None for p in prefix.pushes] \
        == [p is None for p in fork.prefix.pushes]
    assert sum(p is None for p in prefix.pushes) == prefix.departed


# -------- (b) the exact engine forked from it, run to the end

def _policies():
    code = _champion().code
    return {"first_fit": (zoo.first_fit(), policies.first_fit),
            "best_fit": (zoo.best_fit(), policies.best_fit),
            "champion": (transpiler.transpile(code),
                         policies.source_policy(code, dtype="float32"))}


@pytest.mark.parametrize("name", ["first_fit", "best_fit", "champion"])
def test_the_exact_engine_forked_mid_run_is_the_plain_references_run(
        tiny, name):
    """``initial_state`` on the workload as parsed, with no further
    argument: the heap at the fork is the reference's ``heapq`` list slot
    for slot, and the run to the end (every later event the policy's and
    the heap-array rule's) places, counts and scores as the reference."""
    wl, cluster, pods, log, _ = tiny
    program, plain = _policies()[name]
    cfg = SimConfig()
    ktable, max_steps = loop_tables(wl, cfg)
    step = exact.build_step(wl, program, cfg, ktable, max_steps)
    state0 = exact.initial_state(wl, cfg)
    heaps = []
    ref, waiting = plain_sim_fork.simulate(
        cluster, pods, log, plain, retry="heap_array", max_steps=max_steps,
        at_fork=heaps.append)
    size = int(state0.heap.size)
    assert size == len(heaps[0]) == exact.fork_prefix(wl).pending
    assert np.asarray(state0.heap.data)[:size].tolist() \
        == [list(item) for item in heaps[0]]
    assert (int(state0.steps), int(state0.frag_count)) == (E0, 9)
    final = jax.jit(lambda s: jax.lax.while_loop(
        lambda s: exact.lane_active(s, max_steps), step, s))(state0)
    res = jax.jit(lambda s: exact.finalize(wl, cfg, s))(final)
    p = pods.p
    assert np.array_equal(np.asarray(res.assigned_node)[:p],
                          ref.assigned_node)
    assert np.array_equal(np.asarray(res.assigned_gpus)[:p].astype(np.int64),
                          ref.assigned_gpus)
    assert np.array_equal(np.asarray(final.waiting)[:p], waiting)
    assert (int(res.events_processed), int(res.scheduled_pods),
            int(res.num_snapshots), int(res.num_fragmentation_events),
            int(res.max_nodes), bool(res.failed), bool(res.truncated)) \
        == (ref.events_processed, ref.scheduled_pods, ref.num_snapshots,
            ref.num_frag_events, ref.max_nodes, ref.failed, ref.truncated)
    assert not ref.truncated and ref.num_frag_events > 40
    assert ref.policy_score > 0.3
    np.testing.assert_allclose(float(res.policy_score), ref.policy_score,
                               rtol=RTOL)


def test_where_the_two_rules_agree_the_carry_is_the_steps_own():
    """Two nodes of two GPUs under four two-GPU pods: C0 D0 C1 C2 C3, the
    last refused with its retry queued behind the one DELETE that comes
    first in the array AND in time, so this engine's own run reaches the
    fork under its own rule: the forked carry (``COL_WAIT``, the moved
    ``COL_CTIME``, the departed pod's row, ``wait_hist``, ``frag_sum``,
    the heap with the retry in it) is the stepped one leaf by leaf."""
    from fks_tpu.sim import flat
    from tests.test_snapshot_midrun import _first_fit, _tiny

    wl = _tiny((1, 50, 50, 50))
    cfg = SimConfig()
    ktable, max_steps = loop_tables(wl, cfg)
    step = exact.build_step(wl, _first_fit(), cfg, ktable, max_steps)
    full = flat.make_snapshot(wl, _first_fit(), 5)
    for e0 in (2, 5):
        stepped = jax.jit(lambda s, e0=e0: jax.lax.while_loop(
            lambda s: s.steps < e0, step, s))(exact.initial_state(wl, cfg))
        loaded = exact.initial_state(dataclasses.replace(
            wl, snapshot=snap_mod.head(full, e0)), cfg)
        live = int(stepped.heap.size)
        for (path, a), (_, b) in zip(
                jax.tree_util.tree_leaves_with_path(stepped),
                jax.tree_util.tree_leaves_with_path(loaded)):
            a, b = np.asarray(a), np.asarray(b)
            assert a.dtype == b.dtype and a.shape == b.shape, (e0, path)
            if a.shape == stepped.heap.data.shape:
                a, b = a[:live], b[:live]
            assert np.array_equal(a, b), (e0, path)
    assert int(loaded.frag_count) == 1
    assert float(loaded.frag_sum) == pytest.approx(0.4)    # 4 x 400 of 4,000
    assert np.asarray(loaded.pod_state).tolist()[:4] == [
        [0, 3, 0, 0], [0, 3, 1, 0], [1, 3, 2, 0], [-1, 0, 52, 1]]
    assert int(np.asarray(loaded.wait_hist)[600]) == 1


# -------- (c) forked serving from it

@pytest.fixture(scope="module")
def engine(tiny):
    return VMServeEngine(_champion(), tiny[0], engine="exact",
                         max_steps_factor=2,
                         envelope=ShapeEnvelope(max_batch=4, max_pods=256))


def _check(engine, tiny, idx, a, heap=None):
    _, cluster, pods, log, _ = tiny
    policy = policies.source_policy(_champion().code, dtype="float32")
    n, base = len(idx), len(fq.base_of(log))
    budget = max(64, 2 * engine.envelope.pod_bucket_for(n))
    taken, keyed = fq.inputs(pods, log, idx)
    kw = {} if heap is None else {"at_fork": heap.append}
    ref, waiting = fq.simulate_query(cluster, taken, keyed, policy,
                                     max_steps=E0 + budget, **kw)
    assert [r["node"] for r in a["placements"]] \
        == ref.assigned_node[base:].tolist()
    assert [sum(1 << b for b in r["gpus"]) for r in a["placements"]] \
        == ref.assigned_gpus[base:].tolist()
    assert len(a["placements"]) == n
    assert (a["scheduled"], a["events"], a["failed"], a["truncated"],
            a["finished"]) == (ref.scheduled_pods, ref.events_processed,
                               ref.failed, ref.truncated, fq.finished(ref))
    assert a["waiting"] == waiting
    assert (a["snapshots"], a["frag_events"], a["max_nodes"]) \
        == (ref.num_snapshots, ref.num_frag_events, ref.max_nodes)
    assert a["start_event"] == E0
    np.testing.assert_allclose(a["utilization"], ref.avg_util, rtol=RTOL)
    np.testing.assert_allclose(a["fragmentation"], ref.frag_mean,
                               rtol=RTOL, atol=1e-9)
    np.testing.assert_allclose(a["score"], ref.policy_score, rtol=RTOL)
    return ref


def test_forked_mid_run_serving_answers_are_the_plain_references(
        tiny, engine):
    """Five sizes in ONE coalesced call through ``ServeService``: three
    lanes FINISH inside their budget and report the gated fitness of the
    finished run, two are cut, one of them with three pods waiting; queries of different
    sizes from one engine all agree with a reference that heapifies each
    query's CREATEs among the base's (the heap's layout, and with it
    every heap-array retry time after the fork, depends on the size of
    the query asked: the log's own events do not, which is why one log
    serves them all); and the spans say what the fork holds."""
    wl, _, pods, _, rest = tiny
    obs.spans.LOG.clear()
    picks = [rest[3:7], rest[10:26], rest[50:74], rest[140:204],
             rest[20:120]]
    service = ServeService(engine, max_batch=5, max_wait_s=0.25)
    try:
        answers = _ask(service, [_rows(pods, q) for q in picks], "m")
    finally:
        service.close()
    refs = [_check(engine, tiny, q, a) for q, a in zip(picks, answers)]
    assert [a["finished"] for a in answers] == [True, False, True, False,
                                                True]
    for ref, a in zip(refs, answers):
        if a["finished"]:
            assert a["score"] == pytest.approx(ref.policy_score, rel=RTOL) \
                and a["score"] > 0.5 and not a["waiting"]
        else:
            assert a["score"] == 0.0 and a["events"] - E0 == max(
                64, 2 * a["bucket_pods"])
    # at the cut three of the fourth query's pods wait for a node
    assert [a["waiting"] for a in answers] == [[], [], [], [51, 53, 54],
                                               []]
    # the whole run's failed placements: the prefix's nine among them
    assert all(a["frag_events"] > 9 for a in answers)
    log = obs.spans.LOG.snapshot()
    batch = [r for r in log if r.name == "serve/batch"][-1]
    assert batch.fields["finished_lanes"] == 3
    stacks = {r.span_id: r for r in log if r.name == "serve/chunk/stack"}
    # one a chunk, inside its stack span (a first call's compiles stack an
    # example batch each, outside any)
    replays = [r for r in log if r.name == "serve/chunk/stack/heap_replay"
               and r.parent_id in stacks]
    assert len(replays) == 3
    assert sorted(r.fields["queries"] for r in replays) == [1, 2, 2]
    assert all(r.fields["events"] == E0 for r in replays)
    # failed placements FROM THE FORK (what serve.retry_share divides)
    extracts = [r for r in log if r.name == "serve/chunk/extract"]
    assert sum(r.fields["frag_events"] for r in extracts) \
        == sum(a["frag_events"] - 9 for a in answers)
    assert sum(r.fields["lane_events"] for r in extracts) \
        == sum(a["events"] - E0 for a in answers)


def test_the_heap_at_the_fork_depends_on_the_query_and_is_cpythons(
        tiny, engine):
    """Slot for slot, for two queries of different sizes, against the
    reference's own ``heapq`` list before event ``E0``; and the two
    layouts differ in the slots they share."""
    _, cluster, pods, log, rest = tiny
    fork, cfg = engine.fork, engine.bucket_config(64)
    seen = []
    for q in (rest[:4], rest[:40]):
        taken, keyed = fq.inputs(pods, log, q)
        heaps = []
        plain_sim_fork.simulate(cluster, taken, keyed,
                                lambda pod, s, cand: [0] * len(cand),
                                max_steps=E0 + 1, at_fork=heaps.append)
        wl = build_query_workload(engine.cluster, _rows(pods, q), 64, fork)
        state = exact.forked_state(wl, cfg, fork.prefix)
        size = int(state.heap.size)
        assert size == len(heaps[0]) == 34 + len(q)
        assert np.asarray(state.heap.data)[:size].tolist() \
            == [list(item) for item in heaps[0]]
        seen.append(np.asarray(state.heap.data)[:34 + 4].tolist())
    assert seen[0] != seen[1]


def test_the_serve_engine_says_what_it_forked_from(tiny, engine):
    fork_span = [r for r in obs.spans.LOG.snapshot()
                 if r.name == "serve/fork_state"]
    fresh = QueryFork(tiny[0])
    assert (fresh.e0, fresh.base, fresh.residents, fresh.waiting,
            fresh.nodes_loaded) == (E0, 173, 33, 1, 6)
    # the base's rows on the pod axis, not the events
    assert fresh.lane_bytes == 173 * (7 * 4 + 1 + 16 + 16) \
        + 4 * tiny[0].cluster.n_padded * (3 + tiny[0].cluster.g_padded)
    assert engine.start_event == E0 and engine.base_pods_on_axis == 173
    assert engine.bucket_config(16).max_steps == E0 + 64
    if fork_span:       # the engine's own, where the ring still holds it
        f = fork_span[-1].fields
        assert (f["start_event"], f["events"], f["departed"], f["refused"],
                f["waiting"], f["residents"], f["heap_size"]) \
            == (E0, E0, 139, 9, 1, 33, 34)


# -------- (e) a query pod before the last prefix event is a 4xx

def test_a_query_pod_created_before_the_last_prefix_event_is_a_4xx(
        tiny, engine):
    wl = tiny[0]
    last = engine.fork.not_before
    # the time of event E0 - 1: later than the last base pod's arrival,
    # because the prefix ends on retries and departures
    arrivals = np.asarray(wl.pods.creation_time)[np.asarray(
        wl.snapshot.pod)]
    assert last == exact.fork_prefix(wl).last_time > int(arrivals.max())
    service = ServeService(engine, max_batch=2, max_wait_s=0.01)
    pod = {"cpu_milli": 1, "memory_mib": 1, "creation_time": last - 1}
    try:
        with pytest.raises(ValueError, match="lies before the fork") as e:
            service.submit({"pods": [pod]})
        assert f"last event is at {last}" in str(e.value)
    finally:
        service.close()
    # why: such a pod would be met among the first E0 events, which have
    # happened without it; at the last event's own time it sorts after
    early = build_query_workload(engine.cluster, [pod], 16, engine.fork)
    with pytest.raises(ValueError, match="snapshot: "):
        exact.initial_state(early, engine.bucket_config(16))
    on_time = build_query_workload(
        engine.cluster, [{**pod, "creation_time": last}], 16, engine.fork)
    state = exact.initial_state(on_time, engine.bucket_config(16))
    assert int(state.steps) == E0 and int(state.heap.size) == 35


def test_a_forked_mid_run_engine_survives_save_and_load(tiny, engine,
                                                        tmp_path):
    engine.save(str(tmp_path))
    again = VMServeEngine.load(str(tmp_path))
    a, b = engine.fork, again.fork
    assert (b.e0, b.base, b.residents, b.waiting, b.not_before) \
        == (a.e0, a.base, a.residents, a.waiting, a.not_before)
    assert b.snapshot.rule == snap_mod.RETRY_RULE
    assert b.prefix.pushes == a.prefix.pushes
    assert np.array_equal(b.rank, a.rank)
