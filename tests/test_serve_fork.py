"""What-if serving forked from a loaded cluster: the exact engine's carry
after a snapshot (``sim.engine.initial_state`` on a workload that carries
one), its heap against CPython's own slot for slot, and forked queries
through ``ServeService`` against the plain reference
(``chipbench/reference/forked_query.py`` around ``simulate_from``,
``retry="heap_array"``). The flat engine's fork is
``tests/test_snapshot_carry.py``; the benchmark's cell on this path is
``tests/test_chipbench_whatif_loaded.py``. The fork from a MOMENT of a
run (departures, refusals, a waiting pod in the prefix) against
``chipbench/reference/forked_query_midrun.py`` is
``tests/test_serve_fork_midrun.py``."""
import dataclasses
import hashlib
import heapq
import os

import jax
import numpy as np
import pytest

from chipbench.reference import forked_query, plain_sim, policies
from fks_tpu import obs
from fks_tpu.data import TraceParser, default_traces_dir
from fks_tpu.data.build import make_pods
from fks_tpu.data.entities import Workload
from fks_tpu.data.snapshot import from_placements
from fks_tpu.funsearch import transpiler
from fks_tpu.models import zoo
from fks_tpu.serve import (ServeService, ShapeEnvelope, VMServeEngine,
                           load_champion)
from fks_tpu.serve.batcher import (QueryFork, build_query_workload,
                                   pods_to_dicts, stack_query_tables)
from fks_tpu.sim import engine as exact
from fks_tpu.sim.engine import SimConfig, loop_tables
from tests import pressure_traces as pt

RULE = 64
SEED, E0 = 5, 250      # the 60 arrivals after event 250 retry 11-20 times


def _champion():
    root = default_traces_dir().parent.parent / "policies" / "discovered"
    return load_champion(str(root / pt.CHAMPIONS[0]))


@pytest.fixture(scope="module")
def pressure(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("fork"))
    wl = pt.write_traces(d, SEED).parse_workload(pt.NODE_FILE, pt.POD_FILE)
    return d, wl


def _snapshot_of(wl, policy, e0, cfg):
    """The exact engine's own first ``e0`` placements under ``policy``."""
    res = exact.simulate(wl, policy,
                         dataclasses.replace(cfg, max_steps=int(e0)))
    assert int(res.scheduled_pods) == int(res.events_processed) == e0
    return from_placements(wl, e0, res.assigned_node, res.assigned_gpus)


# -------- the heap at the fork is CPython's, slot for slot

def _cpython_heap(times, ranks, durations, e0):
    """``heapify`` of every CREATE in pod-list order, then ``e0`` rounds
    of pop-CREATE / push-DELETE: the plain reference's loop."""
    h = [(int(t), int(r), 0, i) for i, (t, r) in enumerate(zip(times, ranks))]
    heapq.heapify(h)
    for _ in range(e0):
        t, r, kind, i = heapq.heappop(h)
        assert kind == 0
        heapq.heappush(h, (t + int(durations[i]), r, 1, i))
    return np.asarray(h, np.int64)


@pytest.mark.parametrize("e0", [1, 7, 64, 129, 300])
def test_fork_heap_is_cpythons_slot_for_slot_on_16_nodes(e0):
    """A 16-node cluster under 400 small pods whose arrival order, name
    order and list order all differ (so heapify has work to do and equal
    times are broken by rank): the heap array of ``initial_state`` on the
    forked workload equals ``heapq``'s list after the prefix."""
    rng = np.random.default_rng(e0)
    n = 400
    ctime = rng.integers(0, 120, n)              # many equal times
    names = rng.permutation(n)
    rows = [{"pod_id": f"p{names[i]:04d}", "cpu_milli": 10,
             "memory_mib": 10, "num_gpu": 0, "gpu_milli": 0,
             "creation_time": int(ctime[i]),
             "duration_time": int(rng.integers(200, 900))}
            for i in range(n)]
    cluster = TraceParser().parse_workload().cluster
    assert cluster.num_nodes == 16
    wl = Workload(cluster=cluster, pods=make_pods(rows, pad_pods_to=512))
    cfg = SimConfig()
    forked = dataclasses.replace(
        wl, snapshot=_snapshot_of(wl, zoo.first_fit(), e0, cfg))
    state = exact.initial_state(forked, cfg)
    p = wl.pods
    want = _cpython_heap(np.asarray(p.creation_time)[:n],
                         np.asarray(p.tie_rank)[:n],
                         np.asarray(p.duration)[:n], e0)
    size = int(state.heap.size)
    assert size == n == len(want)
    assert np.array_equal(np.asarray(state.heap.data)[:size], want)
    # and it is the array the engine's own heap ops leave after e0 steps
    ktable, max_steps = loop_tables(wl, cfg)
    step = exact.build_step(wl, zoo.first_fit(), cfg, ktable, max_steps)
    stepped = jax.jit(lambda s: jax.lax.while_loop(
        lambda s: s.steps < e0, step, s))(exact.initial_state(wl, cfg))
    assert np.array_equal(np.asarray(stepped.heap.data)[:size], want)


def test_a_prefix_that_pops_a_delete_forks_with_cpythons_heap():
    """The inputs the placed-CREATE replay refused until PR 52 (two pods
    at 0 and 5 that hold for 1: the prefix of 2 events is C0 D0) now
    fork: the one replay re-runs whatever the prefix pushed, and the
    exact engine's heap after it is ``heapq``'s list."""
    from fks_tpu.data import snapshot as snap_mod
    from fks_tpu.data.build import make_workload
    from fks_tpu.data.snapshot import replay
    from fks_tpu.sim import flat

    wl = make_workload(
        [{"node_id": "n0", "cpu_milli": 4000, "memory_mib": 4096,
          "gpus": []}],
        [{"pod_id": f"p{i}", "cpu_milli": 10, "memory_mib": 10,
          "num_gpu": 0, "gpu_milli": 0, "creation_time": t,
          "duration_time": 1} for i, t in enumerate((0, 5))])
    snap = flat.make_snapshot(wl, zoo.first_fit(), 2)
    prefix = replay(wl, snap)
    key = snap_mod.heap_key
    # event 0 pops C0 and pushes D0 at 1, event 1 pops it and pushes none
    assert prefix.pushes == [key(1, 0, 1), None] and prefix.departed == 1
    want = [(0, 0, 0, 0), (5, 1, 0, 1)]         # (time, rank, kind, pod)
    heapq.heapify(want)
    for item in ((1, 0, 1, 0), None):
        heapq.heappop(want)
        if item:
            heapq.heappush(want, item)
    assert want == [(5, 1, 0, 1)] and prefix.heap == [key(5, 1, 0)]
    rows, size = snap_mod.heap_after(
        [key(0, 0, 0), key(5, 1, 0)], prefix.pushes, [0, 1], capacity=4)
    assert size == 1 and rows[:1].tolist() == [[5, 1, 0, 1]]
    state = exact.initial_state(dataclasses.replace(wl, snapshot=snap),
                                SimConfig())
    assert int(state.heap.size) == 1 and int(state.steps) == 2
    assert np.asarray(state.heap.data)[0].tolist() == [5, 1, 0, 1]
    # the departed pod's row is what the step leaves: its node kept
    assert np.asarray(state.pod_state)[0].tolist() == [0, 0, 0, 0]


# -------- exact-engine fork identity

def _policies():
    return {"first_fit": zoo.first_fit(), "best_fit": zoo.best_fit(),
            "champion": transpiler.transpile(_champion().code)}


@pytest.fixture(scope="module")
def churn(pressure):
    """The pressured deployment with short lives: the pods hold for 5-60
    seconds, so the first 200 events of a run hold departures, and the
    cluster never fills before the fork: no refusal, and the prefix is
    the same run under every retry rule (``rule`` "")."""
    _, wl = pressure
    p = wl.pods
    rng = np.random.default_rng(11)
    return dataclasses.replace(wl, pods=dataclasses.replace(
        p, duration=np.where(p.pod_mask, rng.integers(5, 60, p.p_padded),
                             0).astype(np.asarray(p.duration).dtype)))


@pytest.mark.parametrize("name,trace", [
    ("first_fit", "pressure"), ("best_fit", "pressure"),
    ("champion", "pressure"), ("first_fit", "churn"),
    ("champion", "churn")])
def test_exact_fork_is_the_whole_run(pressure, churn, name, trace):
    """Policy ``p`` run whole == the snapshot of ``p``'s first ``e0``
    events, then ``p`` from the snapshot: the carry at the fork leaf
    by leaf (the live heap slot for slot) and the ``SimResult`` at the
    end bit for bit, on a trace whose retries all lie after the fork;
    ``churn``: on one whose prefix holds DEPARTURES and no refusal (the
    exact engine's own run of the same decisions reaches the fork, so the
    departed pods' rows, the refunds and the heap after DELETE pops are
    held to the step's own)."""
    from fks_tpu.sim import flat

    wl = churn if trace == "churn" else pressure[1]
    pol = _policies()[name]
    e0 = 200
    cfg = SimConfig(node_prefilter_k=RULE)
    ktable, max_steps = loop_tables(wl, cfg)
    step = exact.build_step(wl, pol, cfg, ktable, max_steps)

    @jax.jit
    def advance(s, bound):
        return jax.lax.while_loop(
            lambda s: exact.lane_active(s, max_steps) & (s.steps < bound),
            step, s)

    finish = jax.jit(lambda s: exact.finalize(wl, cfg, s))
    stepped = advance(exact.initial_state(wl, cfg), e0)
    assert int(stepped.frag_count) == 0
    if trace == "churn":
        snap = flat.make_snapshot(wl, pol, e0, cfg)
        assert snap.rule == "" and 40 < len(snap.pod) < e0   # departures
    else:
        snap = from_placements(wl, e0, stepped.assigned_node,
                               stepped.assigned_gpus)
    forked = dataclasses.replace(wl, snapshot=snap)
    loaded = exact.initial_state(forked, cfg)
    live = int(stepped.heap.size)
    for (path, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path(stepped),
            jax.tree_util.tree_leaves_with_path(loaded)):
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype and a.shape == b.shape, (name, path)
        if a.shape == stepped.heap.data.shape:      # slots past size: stale
            a, b = a[:live], b[:live]
        assert np.array_equal(a, b), (name, path)
    whole = finish(advance(stepped, 2 ** 30))
    from_fork = finish(advance(loaded, 2 ** 30))
    for a, b in zip(jax.tree_util.tree_leaves(whole),
                    jax.tree_util.tree_leaves(from_fork)):
        assert np.array_equal(np.asarray(a), np.asarray(b)), name
    if trace == "pressure":
        assert int(whole.num_fragmentation_events) > 0  # a retry cascade
    assert float(whole.policy_score) > 0


# -------- forked serving against the plain reference

@pytest.fixture(scope="module")
def forked(pressure):
    """(forked workload, reference cluster, reference pods, rows): the
    first ``E0`` arrivals as best_fit places them."""
    d, wl = pressure
    cfg = SimConfig(node_prefilter_k=RULE)
    fwl = dataclasses.replace(
        wl, snapshot=_snapshot_of(wl, zoo.best_fit(), E0, cfg))
    cluster, pods = pt.reference_inputs(d)
    snap = fwl.snapshot
    rows = {int(i): (int(nd), int(g)) for i, nd, g in zip(
        np.asarray(snap.pod), np.asarray(snap.node), np.asarray(snap.gpus))}
    return fwl, cluster, pods, rows


def _engine(fwl, factor, **kw):
    return VMServeEngine(_champion(), fwl, engine="exact",
                         max_steps_factor=factor, prefilter_k=RULE,
                         envelope=ShapeEnvelope(max_batch=4, max_pods=256),
                         **kw)


def _ask(service, queries, tag):
    futs = [service.submit({"id": f"{tag}-{j}", "pods": q})
            for j, q in enumerate(queries)]
    return [f.result(timeout=600) for f in futs]


def _check(engine, forked, query_idx, answer):
    fwl, cluster, pods, rows = forked
    policy = policies.source_policy(_champion().code, dtype="float32")
    n = len(query_idx)
    bucket = engine.envelope.pod_bucket_for(n)
    budget = max(64, engine.max_steps_factor * bucket)
    taken, keyed = forked_query.inputs(pods, rows, query_idx)
    ref, waiting = forked_query.simulate_query(
        cluster, taken, keyed, policy, max_steps=E0 + budget,
        prefilter_k=RULE, retry="heap_array")
    a = answer
    assert [r["node"] for r in a["placements"]] \
        == ref.assigned_node[E0:].tolist()
    assert [sum(1 << b for b in r["gpus"]) for r in a["placements"]] \
        == ref.assigned_gpus[E0:].tolist()
    assert len(a["placements"]) == n
    assert (a["scheduled"], a["events"], a["failed"], a["truncated"]) \
        == (ref.scheduled_pods, ref.events_processed, ref.failed,
            ref.truncated)
    assert a["waiting"] == waiting
    assert (a["snapshots"], a["frag_events"], a["max_nodes"]) \
        == (ref.num_snapshots, ref.num_frag_events, ref.max_nodes)
    assert a["start_event"] == E0
    np.testing.assert_allclose(a["utilization"], ref.avg_util, rtol=2e-6)
    np.testing.assert_allclose(a["fragmentation"], ref.frag_mean,
                               rtol=2e-6, atol=1e-9)
    np.testing.assert_allclose(a["score"], ref.policy_score, rtol=2e-6)
    return ref, waiting


def test_forked_serving_answers_are_the_plain_references(forked):
    """Mixed sizes in ONE coalesced batch through ``ServeService``, cut at
    the bucket's budget from the fork (factor 1: 64 events): the query
    that is the whole backlog is cut with nine of its pods waiting after
    eleven failed placements; and a second call compiles nothing."""
    fwl, _, pods, rows = forked
    rest = [i for i in range(pods.p) if i not in rows]
    dicts = pods_to_dicts(fwl.pods)
    engine = _engine(fwl, 1)
    service = ServeService(engine, max_batch=4, max_wait_s=0.25)
    picks = [rest[:60], rest[3:11], rest[20:40], rest[5:6]]
    try:
        answers = _ask(service, [[dicts[i] for i in q] for q in picks], "a")
        with obs.CompileWatcher(obs.NULL) as second:
            again = _ask(service, [[dicts[i] for i in q] for q in picks],
                         "b")
    finally:
        service.close()
    assert second.backend_compile_count == 0, second.programs
    for q, a, b in zip(picks, answers, again):
        ref, waiting = _check(engine, forked, q, a)
        assert ref.truncated and ref.events_processed == E0 + 64
        drop = ("id", "latency_ms", "trace_id")
        assert {k: v for k, v in a.items() if k not in drop} \
            == {k: v for k, v in b.items() if k not in drop}
    assert len(answers[0]["waiting"]) == 9
    assert answers[0]["frag_events"] == 11
    # the spans of a forked call say so
    log = obs.spans.LOG.snapshot()
    fork_spans = [r for r in log if r.name == "serve/fork_state"]
    assert fork_spans and fork_spans[-1].fields["residents"] == E0
    assert fork_spans[-1].fields["heap_size"] == E0
    stacks = [r for r in log if r.name == "serve/chunk/stack"
              and (r.fields or {}).get("start_event") == E0]
    assert stacks and all(r.fields["resident_bytes"] > 0 for r in stacks)
    extracts = [r for r in log if r.name == "serve/chunk/extract"
                and "lane_events" in (r.fields or {})]
    assert sum(r.fields["frag_events"] for r in extracts[-2:]) == 11


def test_forked_serving_uncut_finishes_with_the_references_fitness(forked):
    """Factor 8: the whole backlog's run drains (every resident leaves)
    and reports the whole run's fitness; the unbatched reference answer of
    the engine agrees."""
    fwl, _, pods, rows = forked
    rest = [i for i in range(pods.p) if i not in rows]
    dicts = pods_to_dicts(fwl.pods)
    engine = _engine(fwl, 8)
    query = [dicts[i] for i in rest[:60]]
    answer = engine.answer_batch([query, query[:30]])[0]
    ref, waiting = _check(engine, forked, rest[:60], answer)
    assert not ref.truncated and ref.policy_score > 0 and not waiting
    assert ref.num_frag_events > 0
    unbatched = engine.reference_answer(query)
    assert unbatched["placements"] == answer["placements"]
    assert unbatched["score"] == answer["score"]


def test_a_query_pod_created_before_the_fork_is_a_4xx(forked):
    fwl = forked[0]
    engine = _engine(fwl, 1)
    last = engine.fork.not_before
    assert last == int(np.asarray(fwl.pods.creation_time)[
        np.asarray(fwl.snapshot.pod)[-1]])      # the last prefix event
    service = ServeService(engine, max_batch=2, max_wait_s=0.01)
    try:
        with pytest.raises(ValueError, match="lies before the fork"):
            service.submit({"pods": [{"cpu_milli": 1, "memory_mib": 1,
                                      "creation_time": last - 1}]})
        with pytest.raises(ValueError, match="lies before the fork"):
            engine.answer_batch([[{"cpu_milli": 1, "memory_mib": 1}]])
    finally:
        service.close()
    # at the last arrival itself a query pod sorts after the residents
    wl = build_query_workload(
        fwl.cluster, [{"cpu_milli": 1, "memory_mib": 1,
                       "creation_time": last}], 16, engine.fork)
    assert int(exact.initial_state(wl, engine.bucket_config(16)).steps) == E0


def test_the_forked_stack_is_initial_state_of_each_query(forked):
    """What ``QueryFork.stack`` stages in NumPy for a chunk is, lane for
    lane and leaf for leaf, ``initial_state`` of that query's forked
    workload (the generic path, which validates and sums the residents
    anew)."""
    fwl = forked[0]
    fork = QueryFork(fwl)
    dicts = pods_to_dicts(fwl.pods)
    queries = [dicts[E0:E0 + 5], dicts[E0 + 9:E0 + 25]]
    cfg = SimConfig(max_steps=E0 + 64, wait_hist_size=1001,
                    node_prefilter_k=RULE)
    pods, kt, s0 = stack_query_tables(exact, fwl.cluster, queries, 16, cfg,
                                      40, fork)
    assert pods.cpu.shape == (2, E0 + 16) and kt.shape == (2, 40)
    # a prefix of placed CREATEs through the general fork (PR 52: any
    # valid prefix, one heap replay): the bytes of every leaf as PR 52's
    # parent commit (0ff8270) stacks them, and of ``forked_state``
    assert _digest((pods, kt, s0)) == (
        "ad5d89764c07ef25176955a9c82f6b8ac0f5fd07a741adf242cb98ad1cce0b39")
    assert _digest(exact.forked_state(
        fwl, SimConfig(node_prefilter_k=RULE))) == (
        "0645dd2d1af1f689557c1f5419046272445ea10ea22b4a5b7604feb8fded4c51")
    assert (fork.base, fork.residents, fork.waiting, fork.lane_bytes) \
        == (E0, E0, 0, 29330)
    for lane, q in enumerate(queries):
        wl = build_query_workload(fwl.cluster, q, 16, fork)
        assert wl.num_pods == E0 + len(q)
        want = exact.initial_state(wl, cfg)
        got = jax.tree_util.tree_map(lambda x: x[lane], s0)
        for a, b in zip(jax.tree_util.tree_leaves(want),
                        jax.tree_util.tree_leaves(got)):
            assert np.asarray(a).dtype == np.asarray(b).dtype
            assert np.array_equal(np.asarray(a), np.asarray(b))
        assert np.array_equal(kt[lane][:len(loop_tables(wl, cfg)[0])],
                              loop_tables(wl, cfg)[0])


def _digest(tree) -> str:
    h = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves(tree):
        a = np.asarray(leaf)
        h.update(str((a.dtype, a.shape)).encode())
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


def test_a_forked_engine_survives_save_and_load(forked, tmp_path):
    fwl = forked[0]
    engine = _engine(fwl, 1)
    engine.save(str(tmp_path))
    again = VMServeEngine.load(str(tmp_path))
    assert again.fork is not None and again.fork.e0 == E0
    assert again.fork.not_before == engine.fork.not_before
    assert np.array_equal(again.fork.rank, engine.fork.rank)
    assert again.bucket_config(64).max_steps == E0 + 64


def test_engines_that_cannot_fork_say_so(forked):
    fwl = forked[0]
    with pytest.raises(ValueError, match="snapshot: serving forks on the "
                                         "exact engine"):
        VMServeEngine(_champion(), fwl, engine="flat")
    from fks_tpu.portfolio.engine import PortfolioEngine
    with pytest.raises(ValueError, match="snapshot: serving forks one "
                                         "champion"):
        PortfolioEngine([_champion()], fwl, engine="exact")


# -------- without a snapshot nothing moves

def test_without_a_snapshot_the_stacked_tables_are_todays_bytes():
    """``stack_query_tables`` on a workload without a snapshot: the bytes
    of every leaf as the parent commit (f985578) stacks them."""
    wl = TraceParser().parse_workload()
    rows = pods_to_dicts(wl.pods, limit=64)
    queries = [rows[:5], rows[7:23], rows[30:33]]
    cfg = SimConfig(max_steps=128, wait_hist_size=1001)
    assert _digest(stack_query_tables(
        exact, wl.cluster, queries, 16, cfg, 900)) == (
        "e81c60f356716b9437252de1365d7e3b540a5a420ec0b18fdcf4b5a1f1ef71ce")


def test_without_a_snapshot_an_answer_has_todays_keys():
    wl = TraceParser().parse_workload()
    engine = VMServeEngine(_champion(), wl, engine="exact",
                           envelope=ShapeEnvelope(max_batch=2, max_pods=16))
    assert engine.fork is None and engine.start_event == 0
    assert engine.bucket_config(16).max_steps == 128
    a = engine.answer_batch([pods_to_dicts(wl.pods, limit=4)])[0]
    assert sorted(a) == ["bucket_lanes", "bucket_pods", "events", "failed",
                         "placements", "scheduled", "score", "truncated"]


# -------- the fork on a typed workload: a query pod carries its gpu_spec
#
# *A query pod with a non-empty ``gpu_spec`` may be placed only on a node
# whose ``model`` is in the set; every other node is to it as a cordoned
# node is. The residents keep the words the trace gave them; they stay
# where the snapshot put them.* Both plain engines, forked, against
# ``chipbench/reference/forked_query_gpuspec.py`` (a query pod's allowed
# nodes from the string it was SENT).

T_E0 = 200      # first_fit places the first 200 arrivals of the typed
                # draw; the 100 after them fail 52 placements for a type


@pytest.fixture(scope="module")
def typed_fork(tmp_path_factory):
    """(forked typed workload, reference cluster / pods / rows / allowed /
    node models, the pod list's gpu_spec column as the reference reads
    it)."""
    from chipbench.reference import forked_query_gpuspec as fq
    from chipbench.reference import plain_sim_gpuspec as gs
    from chipbench.reference.data import _rows

    d = str(tmp_path_factory.mktemp("typed_fork"))
    wl = pt.write_typed_traces(d, SEED).parse_workload(
        pt.NODE_FILE, pt.POD_FILE, gpu_spec="honor")
    fwl = dataclasses.replace(wl, snapshot=_snapshot_of(
        wl, zoo.first_fit(), T_E0, SimConfig(node_prefilter_k=RULE)))
    cluster, pods = pt.reference_inputs(d)
    nodes_csv = os.path.join(d, "csv", pt.NODE_FILE)
    pods_csv = os.path.join(d, "csv", pt.POD_FILE)
    snap = fwl.snapshot
    rows = {int(i): (int(nd), int(g)) for i, nd, g in zip(
        np.asarray(snap.pod), np.asarray(snap.node), np.asarray(snap.gpus))}
    return (fwl, cluster, pods, rows, gs.load_allowed(nodes_csv, pods_csv),
            fq.node_models(nodes_csv),
            [r.get("gpu_spec") or "" for r in _rows(pods_csv)])


def _typed_engine(cls, fwl, factor=2):
    return cls(_champion(), fwl, engine="exact", max_steps_factor=factor,
               prefilter_k=RULE,
               envelope=ShapeEnvelope(max_batch=2, max_pods=256))


def _sent(typed_fork, idx, with_spec=True):
    """The query of the pod list's rows ``idx`` as the service is sent
    it: six numbers a pod and, where the row names GPU models, their
    string."""
    _, _, pods, _, _, _, specs = typed_fork
    out = []
    for i in idx:
        pod = {"cpu_milli": int(pods.cpu[i]), "memory_mib": int(pods.mem[i]),
               "num_gpu": int(pods.num_gpu[i]),
               "gpu_milli": int(pods.gpu_milli[i]),
               "creation_time": int(pods.creation_time[i]),
               "duration_time": int(pods.duration[i])}
        if with_spec and specs[i]:
            pod["gpu_spec"] = specs[i]
        out.append(pod)
    return out


def _typed_reference(typed_fork, engine, idx, specs):
    from chipbench.reference import forked_query_gpuspec as fq

    _, cluster, pods, rows, allowed, models, _ = typed_fork
    budget = max(64, engine.max_steps_factor
                 * engine.envelope.pod_bucket_for(len(idx)))
    taken, keyed, ok = fq.inputs(pods, rows, allowed, idx, specs, models)
    return fq.simulate_query(
        cluster, taken, keyed, ok,
        policies.source_policy(_champion().code, dtype="float32"),
        max_steps=T_E0 + budget, prefilter_k=RULE, retry="heap_array")


@pytest.mark.parametrize("kind", ["vm", "aot"])
def test_forked_typed_serving_answers_are_the_plain_references(typed_fork,
                                                               kind):
    """Two queries of one coalesced call through ``ServeService``, each
    pod sent with its ``gpu_spec``: placements, GPU picks, the waiting
    set, the counts and the fitness at the cut are the reference's, and
    the spans say what the fork and the chunks carried."""
    from fks_tpu.serve import ServeEngine

    fwl, _, pods, rows, _, _, specs = typed_fork
    obs.spans.LOG.clear()
    engine = _typed_engine(VMServeEngine if kind == "vm" else ServeEngine,
                           fwl)
    assert engine.typed and engine.fork.typed_residents == 37
    rest = [i for i in range(pods.p) if i not in rows]
    picks = [rest[:100], rest[10:22]]
    service = ServeService(engine, max_batch=2, max_wait_s=0.25)
    try:
        answers = _ask(service, [_sent(typed_fork, q) for q in picks], "t")
    finally:
        service.close()
    for q, a in zip(picks, answers):
        ref, waiting = _typed_reference(typed_fork, engine, q,
                                        [specs[i] for i in q])
        assert [r["node"] for r in a["placements"]] \
            == ref.assigned_node[T_E0:].tolist()
        assert [sum(1 << b for b in r["gpus"]) for r in a["placements"]] \
            == ref.assigned_gpus[T_E0:].tolist()
        assert (a["scheduled"], a["events"], a["failed"], a["truncated"]) \
            == (ref.scheduled_pods, ref.events_processed, ref.failed,
                ref.truncated)
        assert a["waiting"] == waiting
        assert (a["snapshots"], a["frag_events"], a["max_nodes"]) \
            == (ref.num_snapshots, ref.num_frag_events, ref.max_nodes)
        np.testing.assert_allclose(a["utilization"], ref.avg_util,
                                   rtol=2e-6)
        np.testing.assert_allclose(a["fragmentation"], ref.frag_mean,
                                   rtol=2e-6, atol=1e-9)
    # the regime: a type's scarcity fails placements in the larger query
    assert answers[0]["frag_events"] == 52
    log = obs.spans.LOG.snapshot()
    fork_span = [r for r in log if r.name == "serve/fork_state"][-1]
    assert (fork_span.fields["typed_residents"],
            fork_span.fields["node_models"]) == (37, 6)
    stacks = {r.fields["bucket"]: r.fields for r in log
              if r.name == "serve/chunk/stack"}
    assert (stacks[256]["pods"], stacks[256]["typed_pods"]) \
        == (100, sum(1 for i in picks[0] if specs[i])) == (100, 32)
    assert (stacks[16]["pods"], stacks[16]["typed_pods"]) \
        == (12, sum(1 for i in picks[1] if specs[i]))


def test_a_query_sent_without_its_gpu_spec_is_another_answer(typed_fork):
    """The field-lost control in small: the same pods sent WITHOUT their
    strings land elsewhere, and that answer is the reference's for pods
    that name nothing."""
    fwl, _, pods, rows, _, _, specs = typed_fork
    engine = _typed_engine(VMServeEngine, fwl)
    q = [i for i in range(pods.p) if i not in rows][:100]
    with_spec, without = engine.answer_batch(
        [_sent(typed_fork, q), _sent(typed_fork, q, with_spec=False)])
    moved = sum(a["node"] != b["node"] for a, b in zip(
        with_spec["placements"], without["placements"]))
    assert moved == 78
    ref, _ = _typed_reference(typed_fork, engine, q, [""] * len(q))
    assert [r["node"] for r in without["placements"]] \
        == ref.assigned_node[T_E0:].tolist()


def test_the_typed_reference_without_constraints_is_the_untyped_one(
        typed_fork):
    """``forked_query_gpuspec`` with every ``gpu_spec`` empty, residents'
    included, equals ``forked_query.simulate_query`` field for field."""
    from chipbench.reference import forked_query_gpuspec as fq

    _, cluster, pods, rows, allowed, models, _ = typed_fork
    q = [i for i in range(pods.p) if i not in rows][:100]
    policy = policies.source_policy(_champion().code, dtype="float32")
    kw = dict(max_steps=T_E0 + 512, prefilter_k=RULE, retry="heap_array")
    taken, keyed = forked_query.inputs(pods, rows, q)
    plain, plain_waiting = forked_query.simulate_query(
        cluster, taken, keyed, policy, **kw)
    taken2, keyed2, ok = fq.inputs(pods, rows, np.ones_like(allowed), q,
                                   [""] * len(q), models)
    assert ok.all() and keyed2 == keyed
    typed, typed_waiting = fq.simulate_query(cluster, taken2, keyed2, ok,
                                             policy, **kw)
    assert typed_waiting == plain_waiting
    for f in dataclasses.fields(plain):
        a, b = getattr(plain, f.name), getattr(typed, f.name)
        assert np.array_equal(a, b), f.name
    # and the strings decide: list or string, order and repeats alike
    assert np.array_equal(fq.allowed_row("T4|G2|T4", models),
                          fq.allowed_row(["G2", "T4"], models))
    assert fq.allowed_row("", models).all() \
        and fq.allowed_row(None, models).all()
    assert not fq.allowed_row("A100", models).any()
    # the snapshot is the typed workload's run and breaks no constraint
    assert fq.validate_snapshot(cluster, pods, rows, allowed,
                                "heap_array").scheduled_pods == T_E0


def test_the_typed_fork_holds_the_residents_words_and_counts_them(
        typed_fork):
    fwl = typed_fork[0]
    fork = QueryFork(fwl)
    untyped = QueryFork(dataclasses.replace(
        fwl, cluster=dataclasses.replace(fwl.cluster, gpu_model=None,
                                         gpu_models=()),
        pods=dataclasses.replace(fwl.pods, gpu_spec=None)))
    order = np.asarray(fwl.snapshot.pod)
    assert np.array_equal(fork.spec, np.asarray(fwl.pods.gpu_spec)[order])
    assert fork.spec.dtype == np.int32 and untyped.spec is None
    assert (fork.typed_residents, untyped.typed_residents) == (37, 0)
    # the new column is counted: 4 bytes a resident a lane
    assert fork.lane_bytes - untyped.lane_bytes == 4 * T_E0
    wl = build_query_workload(fwl.cluster, [{"cpu_milli": 1, "gpu_spec": "T4",
                                             "creation_time": 10 ** 6}],
                              16, fork)
    assert wl.typed and wl.pods.gpu_spec.shape == (T_E0 + 16,)
    assert np.array_equal(wl.pods.gpu_spec[:T_E0], fork.spec)
    assert wl.pods.gpu_spec[T_E0] == 1 << fwl.cluster.gpu_models.index("T4")
    assert not wl.pods.gpu_spec[T_E0 + 1:].any()


def test_a_snapshot_that_breaks_a_constraint_is_no_fork(typed_fork):
    """The residents stay where the snapshot put them only if they may be
    there: ``QueryFork`` goes on refusing a row the rule forbids."""
    fwl = typed_fork[0]
    snap = fwl.snapshot
    spec = np.asarray(fwl.pods.gpu_spec)[np.asarray(snap.pod)]
    k = int(np.flatnonzero(spec)[0])           # a constrained resident
    model = np.asarray(fwl.cluster.gpu_model)
    allowed = (spec[k] >> np.maximum(model, 0)) & 1
    wrong = int(np.flatnonzero((model >= 0) & (allowed == 0))[0])
    node = np.asarray(snap.node).copy()
    node[k] = wrong
    bad = dataclasses.replace(fwl, snapshot=dataclasses.replace(
        snap, node=node))
    with pytest.raises(ValueError, match="whose GPU model its gpu_spec "
                                         "does not name"):
        QueryFork(bad)
    with pytest.raises(ValueError, match="does not name"):
        _typed_engine(VMServeEngine, bad)
