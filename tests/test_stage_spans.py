"""Spans where the work happens: the serve batch, a generation of the code
tier and the sharded runner each leave their tree in the in-memory ring
(``fks_tpu.obs.spans.LOG``) in the DEFAULT state: no recorder, no
profiler, no run directory.
"""
import threading

import jax
import pytest

from chipbench.reduce.spans import union as _union
from fks_tpu import obs
from fks_tpu.obs import spans
from fks_tpu.obs.spans import SpanLog


def _since(mark):
    return [r for r in spans.LOG.snapshot() if r.seq >= mark]


def _mark():
    with obs.span("test/mark") as t:
        pass
    return t.record.seq + 1


# ------------------------------------------------------------------ ring

def test_ring_is_bounded_and_counts_what_it_drops():
    log = SpanLog(capacity=8)
    for i in range(20):
        log.append(f"s{i}", float(i), i + 0.5, f"id{i}", None, "t")
    snap = log.snapshot()
    assert len(snap) == 8 and log.dropped == 12
    assert [r.name for r in snap] == [f"s{i}" for i in range(12, 20)]
    assert [r.seq for r in snap] == list(range(12, 20))
    log.clear()
    assert log.snapshot() == [] and log.dropped == 0


def test_ring_takes_appends_from_two_threads_without_a_lock():
    """The batcher thread and a caller append at once: nothing is lost,
    nothing is duplicated, every record is whole."""
    log = SpanLog(capacity=50_000)
    n = 10_000

    def work(tag):
        for i in range(n):
            log.append(tag, float(i), float(i) + 1.0, f"{tag}{i}", None, tag,
                       {"i": i})

    threads = [threading.Thread(target=work, args=(t,)) for t in "ab"]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    snap = log.snapshot()
    assert len(snap) == 2 * n and log.dropped == 0
    assert sorted(r.seq for r in snap) == list(range(2 * n))
    for tag in "ab":
        mine = [r for r in snap if r.name == tag]
        assert [r.fields["i"] for r in mine] == list(range(n))
        assert len({r.thread for r in mine}) == 1


def test_process_ring_holds_tens_of_thousands():
    assert spans.LOG.capacity == spans.CAPACITY == 65536


# ----------------------------------------------------------------- serve

@pytest.fixture(scope="module")
def vm_engine():
    from fks_tpu.data.synthetic import synthetic_workload
    from fks_tpu.funsearch import template
    from fks_tpu.serve import ChampionSpec, ShapeEnvelope, VMServeEngine

    wl = synthetic_workload(8, 16, seed=0)
    champ = ChampionSpec(code=template.seed_policies()["best_fit"],
                         score=0.5, source="<test>")
    env = ShapeEnvelope(max_pods=64, min_pod_bucket=8, max_batch=4)
    eng = VMServeEngine(champ, wl, envelope=env, engine="flat")
    return eng


def _pods(engine, i, n):
    base = engine.base_pods
    return [dict(base[(i + j) % len(base)]) for j in range(n)]


def _two_bucket_queries(engine):
    # three queries in the 8-pod bucket, two in the 64-pod bucket
    return [_pods(engine, i, n) for i, n in enumerate([5, 6, 40, 7, 33])]


CHUNK_STAGES = ["stack", "pack", "h2d", "enqueue", "wait_device", "d2h",
                "extract"]


def test_serve_batch_of_two_buckets_leaves_its_tree(vm_engine):
    queries = _two_bucket_queries(vm_engine)
    vm_engine.answer_batch(queries)          # warm: compiles both buckets
    best = 0.0
    for _ in range(3):                       # a loaded CI box may preempt
        mark = _mark()
        answers = vm_engine.answer_batch(queries)
        got = _since(mark)
        roots = [r for r in got if r.name == "serve/batch"]
        assert len(roots) == 1 and roots[0].parent_id is None
        root = roots[0]
        kids = [r for r in got if r.parent_id == root.span_id]
        assert all(r.trace_id == root.trace_id for r in kids)
        names = [r.name for r in sorted(kids, key=lambda r: r.t0)]
        assert names[0] == "serve/batch/swap_wait"
        # two chunks, double-buffered: chunk 1 is staged and enqueued
        # before chunk 0 is waited for
        stage = lambda s: f"serve/chunk/{s}"  # noqa: E731
        assert names[1:] == [stage(s) for s in CHUNK_STAGES[:4]] * 2 + \
            [stage(s) for s in CHUNK_STAGES[4:]] * 2
        for s in CHUNK_STAGES:
            assert sorted(r.fields["chunk"] for r in kids
                          if r.name == stage(s)) == [0, 1]
        waits = sorted((r for r in kids if r.name == stage("wait_device")),
                       key=lambda r: r.t0)
        assert waits[0].t1 <= waits[1].t0            # they do not overlap
        h2d = [r for r in kids if r.name == stage("h2d")]
        d2h = [r for r in kids if r.name == stage("d2h")]
        assert all(r.fields["bytes"] > 0 for r in h2d + d2h)
        best = max(best, _union((r.t0, r.t1) for r in kids)
                   / (root.t1 - root.t0))
    assert len(answers) == 5 and best >= 0.98


def test_last_batch_timing_keeps_its_keys_and_reads_the_spans(vm_engine):
    queries = _two_bucket_queries(vm_engine)
    vm_engine.answer_batch(queries)
    mark = _mark()
    vm_engine.answer_batch(queries)
    assert set(vm_engine.last_batch_timing) == {"pack_h2d_s", "dispatch_s"}
    by = {}
    for r in _since(mark):
        if r.name.startswith("serve/chunk/"):
            by.setdefault(r.fields["chunk"], {})[r.name[12:]] = r
    want_pack = sum(c["enqueue"].t1 - c["stack"].t0 for c in by.values())
    want_disp = sum(c["d2h"].t1 - c["wait_device"].t0 for c in by.values())
    assert vm_engine.last_batch_timing["pack_h2d_s"] == pytest.approx(
        want_pack, abs=1e-9)
    assert vm_engine.last_batch_timing["dispatch_s"] == pytest.approx(
        want_disp, abs=1e-9)
    # the per-batch views the service's waterfall reads
    assert len(vm_engine.last_batch_spans) == 14
    assert vm_engine.last_batch_chunks == [[0, 1, 3], [2, 4]]


def test_requests_share_their_batch_id(vm_engine):
    from fks_tpu.serve import ServeService

    service = ServeService(vm_engine, max_batch=5, max_wait_s=2.0)
    mark = _mark()
    try:
        futs = [service.submit({"id": f"c0-{j}", "pods": q})
                for j, q in enumerate(_two_bucket_queries(vm_engine))]
        answers = [f.result(300) for f in futs]
    finally:
        service.close()
    assert "trace_id" not in answers[0]      # no recorder: answers as before
    got = _since(mark)
    batch = [r for r in got if r.name == "serve/batch"]
    assert len(batch) == 1
    roots = [r for r in got if r.name == "serve/request"]
    assert sorted(r.fields["request"] for r in roots) == \
        [f"c0-{j}" for j in range(5)]
    assert {r.fields["batch"] for r in roots} == {batch[0].trace_id}
    assert sorted(batch[0].fields["requests"]) == \
        sorted(r.trace_id for r in roots)
    assert batch[0].fields["queries"] == 5
    for root in roots:
        assert root.parent_id is None
        mine = {r.name: r for r in got
                if r.trace_id == root.trace_id and r is not root}
        assert set(mine) == {"serve/request/queue_wait",
                             "serve/request/batch_wait"}
        q, b = mine["serve/request/queue_wait"], \
            mine["serve/request/batch_wait"]
        assert q.parent_id == b.parent_id == root.span_id
        # submit -> dequeue -> batch start -> answer, on one clock
        assert root.t0 == q.t0 <= q.t1 == b.t0 <= b.t1 <= batch[0].t0
        assert batch[0].t1 <= root.t1
    # the batcher thread stamped the requests, the engine ran there too
    assert {r.thread for r in roots} == {batch[0].thread}
    assert batch[0].thread != threading.get_ident()


# ------------------------------------------------------------- code tier

def _codes():
    from tests.test_vm import _corpus
    return _corpus()[:4]


TIER_SPANS = {"tier/preflight", "tier/transpile",
              "tier/vm_batch/stack_programs", "tier/vm_batch/launch",
              "tier/vm_batch/wait_device", "tier/vm_batch/d2h", "tier/record"}


def test_generation_leaves_the_tier_tree_without_a_profiler(micro_workload):
    from fks_tpu.funsearch import backend

    ev = backend.CodeEvaluator(micro_workload, vm_batch=True)
    ev.evaluate(_codes())                                    # warm
    mark = _mark()
    recs = ev.evaluate(_codes())
    got = _since(mark)
    assert all(r.ok for r in recs)
    roots = [r for r in got if r.name == "tier/evaluate"]
    # typed_pods / node_models (PR 45): 0 on a workload parsed without
    # gpu_spec="honor"
    assert len(roots) == 1 and roots[0].fields == {
        "candidates": 4, "start_event": 0, "typed_pods": 0,
        "node_models": 0}
    root = roots[0]
    kids = [r for r in got if r.parent_id == root.span_id]
    assert {r.name for r in kids} == TIER_SPANS      # no fallback ran
    assert all(r.trace_id == root.trace_id for r in kids)
    assert not any(r.name.startswith("stage/") for r in got)
    order = [r.name for r in sorted(kids, key=lambda r: r.t0)]
    assert order[:2] == ["tier/preflight", "tier/transpile"]
    assert order.index("tier/vm_batch/launch") \
        < order.index("tier/vm_batch/wait_device") \
        < order.index("tier/vm_batch/d2h")
    assert _union((r.t0, r.t1) for r in kids) >= 0.9 * (root.t1 - root.t0)


def test_device_eval_stage_is_a_view_of_its_child_spans(micro_workload):
    """An enabled ``StageProfiler``: its records keep their schema, the
    device-eval stage is one span whose children are the tier's spans,
    and its ``wall_seconds`` are that span's own length."""
    from fks_tpu.funsearch import backend

    with obs.StageProfiler(enabled=True, scope="t") as prof:
        ev = backend.CodeEvaluator(micro_workload, vm_batch=True,
                                   profiler=prof)
        ev.evaluate(_codes())                                # warm
        gap = None
        for _ in range(3):                   # a loaded CI box may preempt
            n0, mark = len(prof.records), _mark()
            ev.evaluate(_codes())
            got = _since(mark)
            new = prof.records[n0:]
            assert [r["stage"] for r in new] == [
                "sandbox+preflight", "transpile", "device-eval"]
            for r in new:
                assert {"scope", "stage", "depth", "wall_seconds",
                        "compile_seconds", "compile_count",
                        "compute_seconds"} <= set(r)
                assert r["depth"] == 0 and r["scope"] == "t"
            assert new[0]["candidates"] == 4 and new[0]["unique"] == 4
            assert new[1]["vm_lanes"] == 4
            stage = next(r for r in got if r.name == "stage/device-eval")
            root = next(r for r in got if r.name == "tier/evaluate")
            assert stage.parent_id == root.span_id
            assert new[2]["wall_seconds"] == pytest.approx(
                stage.t1 - stage.t0, abs=1e-6)
            kids = [r for r in got if r.parent_id == stage.span_id]
            assert {r.name for r in kids} == TIER_SPANS - {
                "tier/preflight", "tier/transpile"}
            covered = _union((r.t0, r.t1) for r in kids)
            assert covered <= new[2]["wall_seconds"] + 1e-6
            g = new[2]["wall_seconds"] - covered
            gap = g if gap is None else min(gap, g)
    # the stage IS the union of its children, bookkeeping between them
    # aside (tens of microseconds)
    assert gap < 1e-3
    # the named stages ARE their spans: one record each, same length
    for rec, name in zip(new[:2], ["tier/preflight", "tier/transpile"]):
        sp = [r for r in got if r.name == name]
        assert len(sp) == 1 and sp[0].parent_id == root.span_id
        assert rec["wall_seconds"] == pytest.approx(sp[0].t1 - sp[0].t0,
                                                    abs=1e-6)


def test_fallback_tier_gets_its_span(micro_workload):
    from fks_tpu.funsearch import backend, template

    hard = template.fill_template(
        "gpus = sorted(g.gpu_milli_left for g in node.gpus)\n"
        "return max(1, gpus[0]) if pod.num_gpu == 0 else 1")
    ev = backend.CodeEvaluator(micro_workload, vm_batch=True)
    mark = _mark()
    ev.evaluate(_codes()[:2] + [hard])
    fb = [r for r in _since(mark) if r.name == "tier/fallback"]
    assert len(fb) == 1 and fb[0].fields == {"lanes": 1}


# ------------------------------------------------------------------ mesh

def test_four_device_generation_leaves_the_mesh_spans(micro_workload,
                                                      monkeypatch):
    """The sharded segmented runner (what a TPU host picks): shard_put,
    one span per segment with the host's wait for the device as its child,
    finish; all inside the tier's launch span."""
    from fks_tpu.funsearch import backend, vm
    from fks_tpu.parallel import population_mesh

    monkeypatch.setenv("FKS_VM_SEG_STEPS", "8")
    mesh = population_mesh(jax.devices()[:4])
    ev = backend.CodeEvaluator(micro_workload, mesh=mesh, engine="flat")
    assert ev.vm_seg_steps == 8
    ev.evaluate(_codes())                                    # warm
    mark = _mark()
    recs = ev.evaluate(_codes())
    got = _since(mark)
    assert all(r.ok for r in recs)
    launch = next(r for r in got if r.name == "tier/vm_batch/launch")
    # slots / capacity: the op-slot loop runs to the longest live program
    # of the generation, in the stack's power-of-two bucket
    c = micro_workload.cluster
    longest = max(int(vm.compile_policy(code, c.n_padded, c.g_padded).n_ops)
                  for code in _codes())
    # nodes / view / register_bytes: the node axis, what of it the policy
    # sees, and the register file one device (2 lanes here) carries
    cap = vm.capacity_bucket(longest)
    assert launch.fields == {
        "lanes": 8, "shards": 4, "start_event": 0, "slots": longest,
        "capacity": cap, "nodes": c.n_padded, "view": c.n_padded,
        "register_bytes": 2 * vm.register_rows(cap) * c.n_padded
        * c.g_padded * 8,
        # how the warm call's trace lowered the register write, kept with
        # the (lanes, capacity) bucket: every run of the rule a slice
        "slice_writes": launch.fields["slice_writes"], "scatter_writes": 0,
        # and its operand fetch: every run of that rule one gather
        "merged_reads": launch.fields["merged_reads"], "split_reads": 0,
        # and its trip structure: a block of slots a turn on every device
        "blocked_loops": launch.fields["blocked_loops"], "plain_loops": 0,
        "turns": -(-longest // vm.SLOT_BLOCK),
        # none of them on the whole opcode table: no lane holds an opcode
        # of ``vm.WIDE``
        "wide_turns": 0}
    assert launch.fields["slice_writes"] >= 1
    assert launch.fields["merged_reads"] >= 1
    assert launch.fields["blocked_loops"] >= 1
    assert longest < launch.fields["capacity"]
    mesh_spans = [r for r in got if r.name.startswith("mesh/")]
    top = sorted((r for r in mesh_spans if r.parent_id == launch.span_id),
                 key=lambda r: r.t0)
    assert top[0].name == "mesh/shard_put" and top[-1].name == "mesh/finish"
    segs = top[1:-1]
    assert len(segs) >= 2 and {r.name for r in segs} == {"mesh/segment"}
    assert [r.fields["segment"] for r in segs] == list(range(len(segs)))
    waits = [r for r in mesh_spans if r.name == "mesh/segment/wait"]
    # double-buffered: every segment but the first waits for the one before
    assert [w.parent_id for w in waits] == [s.span_id for s in segs[1:]]
    assert len(mesh_spans) == len(top) + len(waits)
    assert all(r.trace_id == launch.trace_id for r in mesh_spans)
