"""VM-native serving tests (fks_tpu.serve.vm_engine + the controller's
zero-rebuild promotion fast path).

The ISSUE-16 acceptance criteria, as tests:

- VM-vs-AOT parity: the champion-as-data engine answers every query
  with the same score/placements as the AOT closure engine — exact on
  the integer contract, <= 1e-5 otherwise;
- zero-rebuild hot swap: TWO consecutive promotions through the live
  PromotionController perform ZERO XLA compiles on the serving process
  (CompileWatcher delta == 0) — the swap is transpile + pack + H2D;
- AOT fallback: a VM-unlowerable candidate promotes through the
  closure-engine slow path with a recorded ``vm_swap`` fallback event;
- per-lane isolation on the 8-virtual-device mesh: a lane's answer is
  independent of its batch neighbours, and matches the plain engine.

Plus units for the capacity bucket, the packed program wire format,
artifact round-trip (engine_kind dispatch), the service summary
surface, and the evolution ledger's ``vm_coverage`` stat.
"""
import os

import jax
import numpy as np
import pytest

from fks_tpu.data.synthetic import synthetic_workload
from fks_tpu.funsearch import backend, template, vm
from fks_tpu.obs import CompileWatcher
from fks_tpu.parallel.mesh import num_shards, population_mesh
from fks_tpu.pipeline import (
    PromotionConfig, PromotionController, write_champion,
)
from fks_tpu.serve import (
    ChampionSpec, ServeEngine, ServeService, ShapeEnvelope, VMServeEngine,
    pack_program_tables, unpack_program_tables,
)

# 1000 on every feasible node. The two zero terms are op slots and nothing
# else: they keep the seed in BETTER_LOGIC's capacity bucket (130 live ops
# each at (16, 8); a bare ``score = 1000`` is 126 since ``vm.simplify_ops``
# and would be served from the 128 bucket), and the hot swaps below are
# swaps INSIDE one bucket
SEED_LOGIC = "score = 1000 + 0 * node.gpu_left - 0 * pod.num_gpu"
BETTER_LOGIC = ("score = 1000 + (node.cpu_milli_left - pod.cpu_milli) "
                "/ max(1, node.cpu_milli_total)")
EVEN_BETTER_LOGIC = ("score = 2000 + (node.memory_mib_left - "
                     "pod.memory_mib) / max(1, node.memory_mib_total)")
UNSUPPORTED_LOGIC = ("gpus = sorted(g.gpu_milli_left for g in node.gpus)\n"
                     "return max(1, gpus[0]) if pod.num_gpu == 0 else 1")


def _champ(logic, score=0.5, source="<test>"):
    return ChampionSpec(code=template.fill_template(logic), score=score,
                        source=source)


def _query(i, n=3):
    return [{"cpu_milli": 10 + 7 * i + j, "memory_mib": 50 + 11 * j,
             "creation_time": j, "duration_time": 40}
            for j in range(n)]


def _traffic(service, n=3, pods=3):
    base = service.engine.base_pods
    futs = [service.submit(
        {"pods": [dict(base[(i + j) % len(base)]) for j in range(pods)]})
        for i in range(n)]
    return [f.result(timeout=300) for f in futs]


class RecStub:
    """Recorder double: keeps every event/metric for assertions. The
    ``metric`` signature must absorb positional record payloads."""

    enabled = True

    def __init__(self):
        self.events = []
        self.metrics = []

    def event(self, kind, **fields):
        self.events.append({"kind": kind, **fields})

    def metric(self, kind, *a, **fields):
        self.metrics.append({"kind": kind, **fields})


@pytest.fixture(scope="module")
def wl():
    return synthetic_workload(8, 16, seed=0)


@pytest.fixture(scope="module")
def envelope():
    return ShapeEnvelope(max_pods=8, min_pod_bucket=8, max_batch=2,
                         max_gpu_milli=1000)


@pytest.fixture(scope="module")
def aot(wl, envelope):
    return ServeEngine(_champ(BETTER_LOGIC), wl, envelope=envelope,
                       engine="flat")


@pytest.fixture(scope="module")
def vm_engine(wl, envelope):
    return VMServeEngine(_champ(BETTER_LOGIC), wl, envelope=envelope,
                        engine="flat")


# ------------------------------------------------------------- units


def test_capacity_bucket():
    assert vm.capacity_bucket(0) == 64
    assert vm.capacity_bucket(1) == 64
    assert vm.capacity_bucket(64) == 64
    assert vm.capacity_bucket(65) == 128
    assert vm.capacity_bucket(128) == 128
    assert vm.capacity_bucket(200) == 256


def test_pack_program_tables_round_trip():
    prog = vm.compile_policy(template.fill_template(BETTER_LOGIC), 8, 2)
    packed = pack_program_tables(prog)
    tables = packed[0]
    assert tables.shape == (4, prog.capacity)  # ONE op-table buffer
    assert tables.dtype == np.int32
    back = unpack_program_tables(packed)
    for a, b in zip(prog, back):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_vm_engine_binds_champion_as_data(vm_engine):
    assert vm_engine.engine_kind == "vm"
    assert vm_engine.policy_tier == "vm"
    assert vm_engine.program_capacity >= int(vm_engine.params.n_ops)
    # capacity is a pow2 bucket floored at 64 — shared across champions
    cap = vm_engine.program_capacity
    assert cap >= 64 and cap & (cap - 1) == 0


def test_vm_unsupported_champion_raises_at_construction(wl, envelope):
    with pytest.raises(vm.VMUnsupported):
        VMServeEngine(_champ(UNSUPPORTED_LOGIC), wl, envelope=envelope,
                      engine="flat")


# ------------------------------------------------------------- parity


def test_vm_matches_aot_on_batches(aot, vm_engine):
    queries = [_query(i) for i in range(4)]
    a = aot.answer_batch(queries)
    b = vm_engine.answer_batch(queries)
    for i, (pa, pb) in enumerate(zip(a, b)):
        # float arithmetic champion: x64 tests evaluate both tiers in
        # f64, so the contract is <= 1e-5 (observed: exact)
        assert abs(pa["score"] - pb["score"]) <= 1e-5, f"lane {i} score"
        assert pa["placements"] == pb["placements"], f"lane {i} placements"


def test_vm_matches_aot_exactly_on_integer_contract(wl, envelope):
    logic = "score = 3 * node.cpu_milli_left - 2 * pod.cpu_milli"
    a = ServeEngine(_champ(logic), wl, envelope=envelope, engine="flat")
    b = VMServeEngine(_champ(logic), wl, envelope=envelope, engine="flat")
    queries = [_query(10 + i) for i in range(3)]
    for pa, pb in zip(a.answer_batch(queries), b.answer_batch(queries)):
        assert pa["score"] == pb["score"]  # integer contract: exact
        assert pa["placements"] == pb["placements"]


# ------------------------------------------------ zero-rebuild hot swap


def test_double_hot_swap_zero_recompiles(wl, envelope, tmp_path):
    """TWO consecutive promotions through the live controller: every
    swap is a table upload into the warm executables — zero XLA
    compiles across shadow eval, swap, and post-swap traffic."""
    rec = RecStub()
    incumbent = VMServeEngine(_champ(SEED_LOGIC, 0.4), wl,
                              envelope=envelope, engine="flat",
                              recorder=rec)
    incumbent.warmup()
    service = ServeService(incumbent, max_wait_s=0.002)
    try:
        _traffic(service, 4)  # replay buffer for the shadow eval
        ctrl = PromotionController(
            service, wl, ledger_dir=str(tmp_path),
            log_path=os.path.join(str(tmp_path), "promotion.jsonl"),
            config=PromotionConfig(shadow_queries=2), recorder=rec)
        watcher = CompileWatcher().install()
        try:
            write_champion(str(tmp_path),
                           template.fill_template(BETTER_LOGIC), 0.9)
            v1 = ctrl.poll_once()
            _traffic(service, 3)
            write_champion(str(tmp_path),
                           template.fill_template(EVEN_BETTER_LOGIC), 1.3)
            v2 = ctrl.poll_once()
            _traffic(service, 3)
            compiles = watcher.backend_compile_count
            programs = list(watcher.programs)
        finally:
            watcher.uninstall()
        assert v1.get("action") == "promoted" and \
            v1.get("engine_kind") == "vm", v1
        assert v2.get("action") == "promoted" and \
            v2.get("engine_kind") == "vm", v2
        assert compiles == 0, (
            f"{compiles} XLA programs compiled across two VM hot-swaps "
            f"({', '.join(programs)}) — promotion must be transpile + "
            "pack + H2D only")
        # the swap was IN PLACE: same engine object, new champion tables
        assert service.engine is incumbent
        assert incumbent.vm_swaps == 2
        assert incumbent.vm_swap_h2d_bytes > 0
        bd = incumbent.last_swap_breakdown
        assert bd["h2d_bytes"] > 0 and bd["swap_ms"] >= 0.0
        assert bd["capacity"] == incumbent.program_capacity
        swaps = [e for e in rec.events if e["kind"] == "vm_swap"]
        assert [e["outcome"] for e in swaps] == ["swapped", "swapped"]
    finally:
        service.close()


def test_swap_program_returns_rollback_handle(wl, envelope):
    eng = VMServeEngine(_champ(SEED_LOGIC, 0.4, source="<old>"), wl,
                        envelope=envelope, engine="flat")
    queries = [_query(40)]
    before = eng.answer_batch(queries)
    old = eng.swap_program(_champ(BETTER_LOGIC, 0.9, source="<new>"))
    assert old.source == "<old>"
    assert eng.champion.source == "<new>"
    # the swapped-in tables serve EXACTLY like an engine built on the
    # new champion from scratch
    fresh = VMServeEngine(_champ(BETTER_LOGIC, 0.9), wl,
                          envelope=envelope, engine="flat")
    swapped = eng.answer_batch(queries)
    target = fresh.answer_batch(queries)
    assert swapped[0]["score"] == target[0]["score"]
    assert swapped[0]["placements"] == target[0]["placements"]
    eng.swap_program(old)  # rolling back is another swap_program
    rolled = eng.answer_batch(queries)
    assert rolled[0]["score"] == before[0]["score"]
    assert rolled[0]["placements"] == before[0]["placements"]


def test_swaps_and_batches_leave_no_array_behind(wl, envelope):
    """A serving process swaps champions for as long as it lives: after
    the warm-up (the bucket's program, the snapshot cache and both
    champions' first use are residency, not leaks) six swaps with twelve
    batches between them leave ``jax.live_arrays()`` where it was. Every
    swap frees the tables it displaces, every batch's buffers are donated
    or cache hits."""
    import gc

    champs = [_champ(SEED_LOGIC, 0.4, source="<a>"),
              _champ(BETTER_LOGIC, 0.9, source="<b>")]
    eng = VMServeEngine(champs[0], wl, envelope=envelope, engine="flat")
    queries = [_query(0), _query(1)]
    for c in (champs[1], champs[0]):
        eng.swap_program(c)
        eng.answer_batch(queries)
    gc.collect()
    before = len(jax.live_arrays())
    for i in range(6):
        eng.swap_program(champs[(i + 1) % 2])
        eng.answer_batch(queries)
        eng.answer_batch(queries)
    gc.collect()
    assert len(jax.live_arrays()) == before


def _ledger_champion(score=0.9):
    """The pinned ledger champion: 238 live ops, the 256 bucket."""
    from tests.test_vm_batch import _champion_code

    return ChampionSpec(code=_champion_code(), score=score,
                        source="<ledger>")


def _enqueue_fields(eng):
    return [(r.fields["slots"], r.fields["capacity"])
            for r in eng.last_batch_spans if r.name == "serve/chunk/enqueue"]


def test_swap_between_lengths_in_one_bucket_compiles_nothing(wl, envelope):
    """The op-slot loop's bound is data, not shape: two champions of
    different live lengths in ONE capacity bucket share the executables,
    each swap is a table upload (zero XLA compiles), the ``enqueue`` span
    says how far the loop runs, and every answer equals the unbatched
    reference's."""
    short, long_ = _champ(SEED_LOGIC, 0.4, "<short>"), _ledger_champion()
    eng = VMServeEngine(short, wl, envelope=envelope, engine="flat",
                        program_capacity=256)
    eng.warmup()
    n_short = int(eng.params.n_ops)
    queries = [_query(3), _query(9, 5)]
    eng.answer_batch(queries)  # the eager stacking ops of this batch shape
    # compile_policy's own eager dtype cast compiles once per capacity it
    # lowers AT (256, the bucket of both sources): not the swap's, and
    # not the serve executables'
    c = wl.cluster
    vm.compile_policy(long_.code, c.n_padded, c.g_padded)
    watcher = CompileWatcher().install()
    try:
        a_short = eng.answer_batch(queries)
        f_short = _enqueue_fields(eng)
        eng.swap_program(long_)
        a_long = eng.answer_batch(queries)
        f_long = _enqueue_fields(eng)
        eng.swap_program(short)
        a_back = eng.answer_batch(queries)
        compiles = watcher.backend_compile_count
    finally:
        watcher.uninstall()
    assert compiles == 0, f"{compiles} programs compiled across the swaps"
    assert n_short < 238 <= eng.program_capacity == 256
    assert f_short and set(f_short) == {(n_short, 256)}
    assert f_long and set(f_long) == {(238, 256)}
    for q, a, b in zip(queries, a_short, a_back):
        ref = eng.reference_answer(q)
        assert a["score"] == b["score"]
        assert abs(a["score"] - ref["score"]) <= 1e-5
        assert a["placements"] == b["placements"] == ref["placements"]
    eng.swap_program(long_)
    for q, a in zip(queries, a_long):
        ref = eng.reference_answer(q)
        assert abs(a["score"] - ref["score"]) <= 1e-5
        assert a["placements"] == ref["placements"]
        assert a["scheduled"] == ref["scheduled"]


def test_serve_program_loops_over_the_champions_live_slots(
        vm_engine, monkeypatch):
    """Serving maps ONE program over the lanes (``in_axes=None``): the
    op-slot loop's bound is the resident champion's ``n_ops``, read from
    the uploaded tables: a scalar predicate, nothing selected, and as
    many slot iterations per lockstep event as the champion has ops."""
    from tests.test_vm_batch import (
        _assert_unbatched_op_slot_loop, _count_slot_iterations,
    )

    eng = vm_engine
    fn = eng._make_serve_fn(8)
    batch = eng._example_batch(2, 8)
    _assert_unbatched_op_slot_loop(
        jax.make_jaxpr(fn)(eng._prog_dev, *batch), eng.program_capacity)
    res, fired = _count_slot_iterations(monkeypatch, fn, eng._prog_dev,
                                        *batch)
    events = int(np.max(np.asarray(res.events_processed)))
    live = int(eng.params.n_ops)
    assert live < eng.program_capacity
    assert events > 0 and fired == live * events


def test_serve_program_writes_a_register_as_one_slice(vm_engine):
    """The serve batch (one program, ``in_axes=None``, lanes of queries):
    the op-slot loop's body holds one ``dynamic_update_slice`` of the
    register file and no scatter of it, the executables say so on the
    ``enqueue`` span (``slice_writes`` / ``scatter_writes``: how the rule
    ran while each was traced), and a swap changes neither. The loop turns
    once a SLOT there (``blocked_loops`` / ``plain_loops`` both 0: the
    program is never per lane, so the rule of ``vm._slot_loop`` that
    chooses between them is never reached)."""
    from tests.test_vm_batch import _assert_one_slice_write_a_slot

    eng = vm_engine
    fn = eng._make_serve_fn(8)
    batch = eng._example_batch(2, 8)
    assert _assert_one_slice_write_a_slot(
        jax.make_jaxpr(fn)(eng._prog_dev, *batch),
        eng.program_capacity, block=1) == 1
    eng.answer_batch([_query(3), _query(9, 5)])
    enqueued = [r.fields for r in eng.last_batch_spans
                if r.name == "serve/chunk/enqueue"]
    got = [(f["slice_writes"], f["scatter_writes"]) for f in enqueued]
    assert got and all(s >= 1 and c == 0 for s, c in got), got
    assert all((f["blocked_loops"], f["plain_loops"]) == (0, 0)
               for f in enqueued), enqueued


def test_transpile_cache_makes_reswap_warm(wl, envelope):
    """Host-side transpile cache (ISSUE-18): re-promoting a champion the
    engine already lowered must skip ``compile_policy`` entirely — the
    breakdown says "hit", the counters move, and the warm transpile leg
    costs no more than the cold one. Keyed on the EXACT source hash, so
    two different champions never alias; seeded at construction, so a
    rollback to the original champion is warm from swap one."""
    rec = RecStub()
    eng = VMServeEngine(_champ(SEED_LOGIC, 0.4, source="<seed>"), wl,
                        envelope=envelope, engine="flat", recorder=rec)
    assert eng.transpile_cache_hits == 0
    eng.swap_program(_champ(BETTER_LOGIC, 0.9, source="<new>"))
    cold = dict(eng.last_swap_breakdown)
    assert cold["transpile_cache"] == "miss"
    assert cold["transpile_cache_misses"] == 1
    # same source again (a rollback / A-B flip): pure cache lookup
    eng.swap_program(_champ(BETTER_LOGIC, 0.9, source="<again>"))
    warm = dict(eng.last_swap_breakdown)
    assert warm["transpile_cache"] == "hit"
    assert warm["transpile_cache_hits"] == 1
    assert warm["transpile_ms"] <= cold["transpile_ms"]
    # construction champion was seeded into the cache: rollback is warm
    eng.swap_program(_champ(SEED_LOGIC, 0.4))
    assert eng.last_swap_breakdown["transpile_cache"] == "hit"
    swaps = [e for e in rec.events if e["kind"] == "vm_swap"]
    assert [e["transpile_cache"] for e in swaps] == ["miss", "hit", "hit"]
    # warm-swapped tables still serve exactly like a fresh build
    eng.swap_program(_champ(BETTER_LOGIC, 0.9))
    fresh = VMServeEngine(_champ(BETTER_LOGIC, 0.9), wl, envelope=envelope,
                          engine="flat")
    q = [_query(90)]
    assert eng.answer_batch(q)[0]["score"] == \
        fresh.answer_batch(q)[0]["score"]


def test_transpile_cache_shared_with_shadow(wl, envelope):
    """``shadow_for`` lowers through the incumbent's cache, so the
    shadow-then-promote flow promotes WARM: the controller's real swap
    is H2D only."""
    eng = VMServeEngine(_champ(SEED_LOGIC, 0.4), wl, envelope=envelope,
                        engine="flat")
    cand = _champ(BETTER_LOGIC, 0.9, source="<cand>")
    eng.shadow_for(cand)
    eng.swap_program(cand)
    assert eng.last_swap_breakdown["transpile_cache"] == "hit"


def test_transpile_cache_never_caches_unsupported(wl, envelope):
    """A VM-unlowerable champion must raise on EVERY attempt — a cached
    rejection (or worse, a cached bogus program) would break the AOT
    fallback's retry semantics."""
    eng = VMServeEngine(_champ(SEED_LOGIC, 0.4), wl, envelope=envelope,
                        engine="flat")
    bad = _champ(UNSUPPORTED_LOGIC, 0.9)
    misses_before = eng.transpile_cache_misses
    for _ in range(2):
        with pytest.raises(vm.VMUnsupported):
            eng.swap_program(bad)
    assert eng.transpile_cache_misses == misses_before
    assert eng.transpile_cache_hits == 0


def test_service_swap_engine_routes_championspec(wl, envelope):
    eng = VMServeEngine(_champ(SEED_LOGIC, 0.4, source="<old>"), wl,
                        envelope=envelope, engine="flat")
    service = ServeService(eng, max_wait_s=0.002)
    try:
        old = service.swap_engine(_champ(BETTER_LOGIC, 0.9))
        assert isinstance(old, ChampionSpec) and old.source == "<old>"
        assert service.engine is eng  # in-place: no engine flip
        assert service.swaps == 1
        summary = service.summary()
        assert summary["engine_kind"] == "vm"
        assert summary["program_capacity"] == eng.program_capacity
        assert summary["vm_swaps"] == 1
        assert summary["vm_swap_h2d_bytes"] > 0
        # an AOT engine has no swap_program: ChampionSpec must be refused
        plain = ServeEngine(_champ(SEED_LOGIC), wl, envelope=envelope,
                            engine="flat")
        service.swap_engine(plain)
        with pytest.raises(TypeError):
            service.swap_engine(_champ(BETTER_LOGIC, 0.9))
    finally:
        service.close()


# --------------------------------------------------------- AOT fallback


def test_vm_unsupported_candidate_falls_back_to_aot(wl, envelope,
                                                    tmp_path):
    """A candidate outside the VM vocabulary still promotes — through
    the AOT closure factory — and the fallback is a recorded event."""
    rec = RecStub()
    incumbent = VMServeEngine(_champ(SEED_LOGIC, 0.4), wl,
                              envelope=envelope, engine="flat")
    incumbent.warmup()
    service = ServeService(incumbent, max_wait_s=0.002)
    try:
        _traffic(service, 4)
        ctrl = PromotionController(
            service, wl, ledger_dir=str(tmp_path),
            log_path=os.path.join(str(tmp_path), "promotion.jsonl"),
            config=PromotionConfig(shadow_queries=2), recorder=rec)
        write_champion(str(tmp_path),
                       template.fill_template(UNSUPPORTED_LOGIC), 0.9)
        verdict = ctrl.poll_once()
        assert verdict.get("action") == "promoted", verdict
        assert verdict.get("engine_kind") == "aot"
        # the service flipped to a NEW closure engine — the VM incumbent
        # could not serve this champion in place
        assert service.engine is not incumbent
        assert service.engine.engine_kind == "aot"
        falls = [e for e in rec.events
                 if e["kind"] == "vm_swap" and e["outcome"] == "fallback"]
        assert len(falls) == 1
        assert "sort" in falls[0]["detail"]
        _traffic(service, 2)  # the promoted AOT engine serves
    finally:
        service.close()


# ------------------------------------------------------- mesh sharding


def test_mesh_per_lane_isolation_and_parity(wl):
    """8-virtual-device mesh: each lane of a full batch answers exactly
    as the plain single-device VM engine, alone or together — and the
    program tables replicate while the lanes shard."""
    assert num_shards(population_mesh(jax.devices())) >= 8
    env = ShapeEnvelope(max_pods=8, min_pod_bucket=8, max_batch=8,
                        max_gpu_milli=1000)
    plain = VMServeEngine(_champ(BETTER_LOGIC), wl, envelope=env,
                          engine="flat")
    sharded = VMServeEngine(_champ(BETTER_LOGIC), wl, envelope=env,
                            engine="flat",
                            mesh=population_mesh(jax.devices()))
    queries = [_query(60 + i) for i in range(8)]
    together = sharded.answer_batch(queries)
    baseline = plain.answer_batch(queries)
    for i, (t, b) in enumerate(zip(together, baseline)):
        assert t["score"] == b["score"], f"lane {i} score"
        assert t["placements"] == b["placements"], f"lane {i} placements"
    alone = [sharded.answer_batch([q])[0] for q in queries[:3]]
    for t, s in zip(together, alone):
        assert t["score"] == s["score"]
        assert t["placements"] == s["placements"]


def test_mesh_swap_keeps_parity(wl):
    env = ShapeEnvelope(max_pods=8, min_pod_bucket=8, max_batch=4,
                        max_gpu_milli=1000)
    sharded = VMServeEngine(_champ(SEED_LOGIC), wl, envelope=env,
                            engine="flat",
                            mesh=population_mesh(jax.devices()))
    sharded.swap_program(_champ(BETTER_LOGIC, 0.9))
    fresh = VMServeEngine(_champ(BETTER_LOGIC, 0.9), wl, envelope=env,
                          engine="flat")
    queries = [_query(70 + i) for i in range(4)]
    for a, b in zip(sharded.answer_batch(queries),
                    fresh.answer_batch(queries)):
        assert a["score"] == b["score"]
        assert a["placements"] == b["placements"]


# ----------------------------------------------------- artifact + ledger


def test_vm_artifact_round_trip(tmp_path, wl, envelope):
    eng = VMServeEngine(_champ(BETTER_LOGIC), wl, envelope=envelope,
                        engine="flat")
    queries = [_query(80), _query(81)]
    before = eng.answer_batch(queries)
    d = str(tmp_path / "artifact")
    eng.save(d)
    loaded = ServeEngine.load(d)  # engine_kind dispatch in load()
    assert isinstance(loaded, VMServeEngine)
    assert loaded.engine_kind == "vm"
    assert loaded.program_capacity == eng.program_capacity
    after = loaded.answer_batch(queries)
    for a, b in zip(before, after):
        assert a["score"] == b["score"]
        assert a["placements"] == b["placements"]


def test_vm_coverage_stat(micro_workload):
    """The ledger's vm_coverage: fraction of the batch's unique
    candidates served by the VM tier."""
    from tests.test_vm import _corpus

    ev = backend.CodeEvaluator(micro_workload, vm_batch=True)
    vmable = _corpus()[:3]
    hard = template.fill_template(UNSUPPORTED_LOGIC)
    ev.evaluate(vmable + [hard])
    assert ev.last_eval_stats["vm_coverage"] == pytest.approx(3 / 4)
    ev.evaluate(vmable)
    assert ev.last_eval_stats["vm_coverage"] == 1.0


def test_generation_stats_carries_vm_coverage():
    from fks_tpu.funsearch.evolution import GenerationStats

    stats = GenerationStats(generation=1, best_score=1.0, mean_score=1.0,
                            new_candidates=4, accepted=2,
                            rejected_similar=0, eval_seconds=0.1,
                            compile_count=0, vm_coverage=0.75)
    assert stats.vm_coverage == 0.75
    # exporter surface: the gauge rides the standard generation table
    from fks_tpu.obs.exporter import GENERATION_GAUGES
    assert any(key == "vm_coverage" for _, key, _ in GENERATION_GAUGES)


def test_concurrent_swap_never_tears_a_batch(wl, envelope):
    """ISSUE-17 thread-race criterion: ``swap_program`` racing in-flight
    ``answer_batch`` calls must be atomic per batch — every answer set
    matches ONE of the two champions exactly (the engine's swap lock
    holds across a batch), never a torn mix of old tables and new
    params, and the race must not leak or recompile."""
    import threading

    # behaviorally OPPOSED champions (worst-fit vs best-fit) so the two
    # programs place differently — a torn swap has something to tear
    champs = [_champ("score = node.cpu_milli_left - pod.cpu_milli",
                     0.4, source="<a>"),
              _champ("score = pod.cpu_milli - node.cpu_milli_left",
                     0.9, source="<b>")]
    eng = VMServeEngine(champs[0], wl, envelope=envelope, engine="flat")
    queries = [_query(7), _query(11)]

    def key(answers):
        return tuple((round(float(a["score"]), 9), tuple(a["placements"]))
                     for a in answers)

    # one reference answer set per champion, from the same engine while
    # it is single-threaded (VM answers are deterministic per program)
    legal = {}
    for i, c in enumerate(champs):
        eng.swap_program(c)
        legal[i] = key(eng.answer_batch(queries))
    assert legal[0] != legal[1]  # the race has something to tear

    watcher = CompileWatcher().install()
    errors, torn = [], []
    stop = threading.Event()

    def hammer():
        try:
            while not stop.is_set():
                got = key(eng.answer_batch(queries))
                if got not in (legal[0], legal[1]):
                    torn.append(got)
        except Exception as e:  # pragma: no cover - failure diagnostics
            errors.append(repr(e))

    threads = [threading.Thread(target=hammer) for _ in range(3)]
    for t in threads:
        t.start()
    try:
        for i in range(30):
            eng.swap_program(champs[(i + 1) % 2])
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=60)
        watcher.uninstall()
    assert not errors, errors
    assert not torn, f"{len(torn)} torn batches, first: {torn[:1]}"
    assert watcher.backend_compile_count == 0  # swaps never rebuild


# ------------------------------------------ a query pod carries gpu_spec
#
# Unforked, on a tiny typed deployment (320 nodes of six GPU models,
# ``pressure_traces.write_typed_traces``): both engines against the plain
# reference ``plain_sim_gpuspec.simulate``, a typed engine whose queries
# name nothing against the untyped engine bit for bit, and an untyped
# engine's programs as they always were. The forked path is
# tests/test_serve_fork.py, the schema tests/test_serve.py.

@pytest.fixture(scope="module")
def typed_deployment(tmp_path_factory):
    from chipbench.reference import forked_query_gpuspec as fq
    from chipbench.reference.data import _rows
    from tests import pressure_traces as pt

    d = str(tmp_path_factory.mktemp("typed_serve"))
    parser = pt.write_typed_traces(d, 5)
    typed = parser.parse_workload(pt.NODE_FILE, pt.POD_FILE,
                                  gpu_spec="honor")
    untyped = parser.parse_workload(pt.NODE_FILE, pt.POD_FILE)
    cluster, pods = pt.reference_inputs(d)
    models = fq.node_models(os.path.join(d, "csv", pt.NODE_FILE))
    specs = [r.get("gpu_spec") or ""
             for r in _rows(os.path.join(d, "csv", pt.POD_FILE))]
    return typed, untyped, cluster, pods, models, specs


def _ledger_champion():
    from fks_tpu.data import default_traces_dir
    from fks_tpu.serve import load_champion
    from tests import pressure_traces as pt

    root = default_traces_dir().parent.parent / "policies" / "discovered"
    return load_champion(str(root / pt.CHAMPIONS[0]))


def _typed_serve_engine(cls, wl):
    return cls(_ledger_champion(), wl, engine="exact", prefilter_k=64,
               max_steps_factor=8,
               envelope=ShapeEnvelope(max_batch=2, max_pods=320,
                                      min_pod_bucket=64))


def _rows_as_sent(pods, specs, idx, with_spec=True):
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


@pytest.mark.parametrize("kind", ["vm", "aot"])
def test_typed_serving_unforked_is_the_plain_references(typed_deployment,
                                                        kind):
    """The whole 310-pod list as ONE query (72 pods name their GPUs) and
    a window of it, each pod sent with its ``gpu_spec``: every placement,
    GPU pick, count and the fitness are ``plain_sim_gpuspec.simulate``'s
    on the allowed rows made from the strings that were sent."""
    from chipbench.reference import forked_query_gpuspec as fq
    from chipbench.reference import plain_sim_gpuspec as gs
    from chipbench.reference import policies

    typed, _, cluster, pods, models, specs = typed_deployment
    engine = _typed_serve_engine(
        VMServeEngine if kind == "vm" else ServeEngine, typed)
    assert engine.typed and engine.fork is None
    picks = [list(range(pods.p)), list(range(40, 100))]
    answers = engine.answer_batch([_rows_as_sent(pods, specs, q)
                                   for q in picks])
    policy = policies.source_policy(_ledger_champion().code, dtype="float32")
    for q, a in zip(picks, answers):
        allowed = np.array([fq.allowed_row(specs[i], models) for i in q])
        bucket = engine.envelope.pod_bucket_for(len(q))
        ref = gs.simulate(cluster, pods.take(q, query=True), allowed,
                          policy, retry="heap_array", prefilter_k=64,
                          max_steps=max(64, 8 * bucket))
        assert [r["node"] for r in a["placements"]] \
            == ref.assigned_node.tolist()
        assert [sum(1 << b for b in r["gpus"]) for r in a["placements"]] \
            == ref.assigned_gpus.tolist()
        assert (a["scheduled"], a["events"], a["failed"], a["truncated"]) \
            == (ref.scheduled_pods, ref.events_processed, ref.failed,
                ref.truncated)
        np.testing.assert_allclose(a["score"], ref.policy_score, rtol=2e-6)
        placed = ref.assigned_node >= 0
        assert allowed[np.flatnonzero(placed),
                       ref.assigned_node[placed]].all()
    # the whole list is under a type's pressure and still finishes
    assert answers[0]["score"] > 0 and not answers[0]["truncated"]
    assert answers[0]["events"] > 2 * pods.p


def test_a_typed_engine_asked_nothing_answers_as_the_untyped_engine(
        typed_deployment):
    """Every ``gpu_spec`` empty: the typed engine's answer (its program
    carries the type term, every word 0) equals the untyped engine's,
    key for key and bit for bit."""
    typed, untyped, _, pods, _, specs = typed_deployment
    q = _rows_as_sent(pods, specs, range(120), with_spec=False)
    a = _typed_serve_engine(VMServeEngine, typed).answer_batch([q, q[:50]])
    b = _typed_serve_engine(VMServeEngine, untyped).answer_batch(
        [q, q[:50]])
    assert a == b
    # and with the strings the answer is another one
    c = _typed_serve_engine(VMServeEngine, typed).answer_batch(
        [_rows_as_sent(pods, specs, range(120))])
    assert c[0]["placements"] != a[0]["placements"]


def test_an_untyped_engines_programs_are_what_they_were(typed_deployment,
                                                        wl, envelope):
    """Data decides, no switch: an engine whose workload is not typed
    (parsed without the choice, or holding only half of the leaves) builds
    queries without the ``gpu_spec`` leaf, lowers each bucket to the text
    it lowered to before the field existed, and the typed engine's text
    differs from it by the type term alone. (The optimized modules of the
    benchmark's own buckets against the parent commit:
    ``tools/describe_compile.py whatif --hlo``, PERF.md section 6, PR 49;
    ``serve_bucket/exact_l1_p16`` in the lint pins is an untyped one.)"""
    import dataclasses

    typed, untyped, _, _, _, _ = typed_deployment
    half = dataclasses.replace(untyped, cluster=typed.cluster)
    assert half.cluster.gpu_model is not None and not half.typed

    def lowered(workload):
        eng = _typed_serve_engine(VMServeEngine, workload)
        example = (eng._prog_dev,) + eng._example_batch(2, 64)
        leaves = jax.tree_util.tree_leaves(example[1])
        text = jax.jit(eng._make_serve_fn(64)).lower(*example).as_text()
        return eng, len(leaves), text

    e_untyped, n_untyped, t_untyped = lowered(untyped)
    e_half, n_half, t_half = lowered(half)
    e_typed, n_typed, t_typed = lowered(typed)
    assert (e_untyped.typed, e_half.typed, e_typed.typed) \
        == (False, False, True)
    assert e_half.cluster.gpu_model is None
    assert n_untyped == n_half == 8 and n_typed == 9
    assert t_untyped == t_half != t_typed
    assert len(t_typed.splitlines()) > len(t_untyped.splitlines())
    # the module's older fixtures are untyped engines too
    eng = VMServeEngine(_champ(BETTER_LOGIC), wl, envelope=envelope,
                        engine="flat")
    assert not eng.typed and "gpu_spec" not in eng.base_pods[0]
