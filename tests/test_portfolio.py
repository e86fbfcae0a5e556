"""Multi-tenant champion-portfolio serving (fks_tpu.portfolio).

The contract under test: N resident policies live in ONE slot-vmapped
VM executable; per-request slot selection is bit-identical to serving
each champion alone; promoting one slot under live traffic is a table
upload — zero XLA compiles — that never perturbs the other slots; and
the router's rule chain (pin / affinity / A-B / coverage fallback) is
deterministic and closed-vocabulary.
"""
import json
import os
import threading

import numpy as np
import pytest

from fks_tpu.data.synthetic import synthetic_workload
from fks_tpu.funsearch import template, vm
from fks_tpu.obs import CompileWatcher
from fks_tpu.pipeline import PromotionConfig, write_champion
from fks_tpu.portfolio import (
    FALLBACK, FleetController, PortfolioEngine, PortfolioService,
    ROUTE_REASONS, Router, portfolio_selftest, vm_coverage_split,
)
from fks_tpu.serve import (
    ChampionSpec, ServeEngine, ShapeEnvelope, VMServeEngine,
)
from fks_tpu.serve.accounting import QueryFingerprinter
from fks_tpu.serve.artifact import Workload
from fks_tpu.serve.batcher import (
    pack_portfolio_tables, unpack_portfolio_tables,
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
WORST_FIT_LOGIC = ("score = 1000 - (node.cpu_milli_left - pod.cpu_milli) "
                   "/ max(1, node.cpu_milli_total)")
UNSUPPORTED_LOGIC = ("gpus = sorted(g.gpu_milli_left for g in node.gpus)\n"
                     "return max(1, gpus[0]) if pod.num_gpu == 0 else 1")


def _champ(logic, score=0.5, source="<test>"):
    return ChampionSpec(code=template.fill_template(logic), score=score,
                        source=source)


class RecStub:
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
def champs():
    # raw-milli scores: genuinely distinct policies. The normalized
    # "+fit/total" logic variants collapse into all-tie constant
    # policies under the template's int() truncation — four identical
    # slots could never catch a cross-slot routing bug in the parity
    # checks below.
    return [_champ(SEED_LOGIC, 0.4, "<c0>"),
            _champ("score = node.cpu_milli_left - pod.cpu_milli",
                   0.5, "<c1>"),
            _champ("score = node.memory_mib_left - pod.memory_mib",
                   0.6, "<c2>"),
            _champ("score = pod.cpu_milli - node.cpu_milli_left",
                   0.7, "<c3>")]


@pytest.fixture(scope="module")
def portfolio(wl, envelope, champs):
    eng = PortfolioEngine(champs, wl, envelope=envelope, engine="flat",
                          n_slots=5)
    eng.warmup()
    return eng


def _query(base, i, n=3):
    return [dict(base[(i + j) % len(base)]) for j in range(n)]


# ------------------------------------------------------------- units


def test_pack_unpack_portfolio_tables(wl):
    n, g = wl.cluster.n_padded, wl.cluster.g_padded
    progs = [vm.pad_capacity(vm.compile_policy(
        template.fill_template(lg), n, g), 256)
        for lg in (SEED_LOGIC, BETTER_LOGIC)]
    packed = pack_portfolio_tables(progs)
    stacked = unpack_portfolio_tables(packed)
    for s, prog in enumerate(progs):
        one = vm.select_slot(stacked, s)
        for a, b in zip(one, prog):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_select_slot_capacity_is_shape_derived(wl):
    n, g = wl.cluster.n_padded, wl.cluster.g_padded
    progs = [vm.pad_capacity(vm.compile_policy(
        template.fill_template(lg), n, g), 256)
        for lg in (SEED_LOGIC, BETTER_LOGIC)]
    stacked = vm.stack_programs(progs)
    one = vm.select_slot(stacked, 1)
    assert one.capacity == 256  # shape-derived, not the slot axis


def test_n_slots_must_cover_champions(wl, envelope, champs):
    with pytest.raises(ValueError):
        PortfolioEngine(champs, wl, envelope=envelope, n_slots=2)


def test_shadow_for_is_not_the_portfolio_shadow_path(portfolio):
    with pytest.raises(TypeError):
        portfolio.shadow_for(_champ(BETTER_LOGIC))


# ----------------------------------------------------- slot parity


def test_per_slot_and_mixed_parity(portfolio):
    """The acceptance criterion: every resident slot's answers match a
    single-champion VM engine serving that champion alone, and a mixed
    batch matches the per-slot answers."""
    result = portfolio_selftest(portfolio, count=6, pods_per_query=3)
    assert result["ok"], result["failures"]
    assert result["max_drift"] == 0.0  # integer-scored VM: bit-identical
    assert result["mixed_max_drift"] == 0.0
    assert result["placements_match"]
    # guard against vacuous parity: the resident policies must actually
    # disagree somewhere, or slot-routing bugs would be invisible
    base = portfolio.base_pods
    queries = [_query(base, i) for i in range(6)]
    s1 = portfolio.answer_batch(queries, slots=[1] * 6)
    s3 = portfolio.answer_batch(queries, slots=[3] * 6)
    assert any(a["score"] != b["score"] or a["placements"] != b["placements"]
               for a, b in zip(s1, s3))


def test_slot_validation(portfolio):
    base = portfolio.base_pods
    with pytest.raises(ValueError):
        portfolio.answer_batch([_query(base, 0)], slots=[99])
    with pytest.raises(ValueError):
        portfolio.answer_batch([_query(base, 0)], slots=[0, 1])


def test_swap_slot_returns_rollback_handle(wl, envelope):
    # opposed raw-milli champions: their scores differ by hundreds, so
    # int() truncation in the template can't collapse them into ties
    a = _champ("score = node.cpu_milli_left - pod.cpu_milli", 0.4, "<a>")
    b = _champ("score = pod.cpu_milli - node.cpu_milli_left", 0.9, "<b>")
    eng = PortfolioEngine([a, b], wl, envelope=envelope, engine="flat",
                          n_slots=3)
    eng.warmup()
    base = eng.base_pods
    queries = [_query(base, 7), _query(base, 11)]

    def key(answers):
        return tuple((round(float(x["score"]), 9),
                      tuple(str(p) for p in x["placements"]))
                     for x in answers)

    before = key(eng.answer_batch(queries, slots=[0, 0]))
    other = key(eng.answer_batch(queries, slots=[1, 1]))
    assert before != other  # the pair is genuinely opposed on these
    old = eng.swap_slot(0, b)
    assert old.source == "<a>"  # the rollback handle
    changed = key(eng.answer_batch(queries, slots=[0, 0]))
    assert changed == other  # slot 0 now serves b, bit-identically
    eng.swap_slot(0, old)  # roll back
    after = key(eng.answer_batch(queries, slots=[0, 0]))
    assert after == before


def test_save_load_roundtrip(tmp_path, portfolio):
    portfolio.save(str(tmp_path))
    with open(os.path.join(str(tmp_path), "artifact.json")) as f:
        doc = json.load(f)
    assert doc["portfolio"]["n_slots"] == portfolio.n_slots
    loaded = ServeEngine.load(str(tmp_path))
    assert isinstance(loaded, PortfolioEngine)
    assert [c.source for c in loaded.slot_champions] == \
        [c.source for c in portfolio.slot_champions]
    q = _query(portfolio.base_pods, 1)
    for s in range(3):
        a = portfolio.answer_batch([q], slots=[s])[0]
        b = loaded.answer_batch([q], slots=[s])[0]
        assert a["score"] == b["score"]
        assert a["placements"] == b["placements"]


# ---------------------------------------------------------- router


def test_router_rule_precedence(wl):
    base_pods = [{"cpu_milli": 100, "memory_mib": 200}] * 3
    cls = QueryFingerprinter().classify(base_pods)
    r = Router(4, pins={"vip": 1}, affinity={cls: 2},
               ab_split={0: 0.5, 3: 0.5})
    assert r.route("r1", "vip", base_pods) == (1, "pin")
    assert r.route("r2", "other", base_pods) == (2, "affinity")
    slot, reason = r.route("r3", "other", [{"cpu_milli": 999999,
                                            "memory_mib": 1}] * 3)
    assert reason == "ab" and slot in (0, 3)


def test_router_ab_is_deterministic():
    r = Router(4, ab_split={0: 0.5, 3: 0.5})
    pods = [{"cpu_milli": 1, "memory_mib": 1}]
    first = [r.route(f"req-{i}", "t", pods)[0] for i in range(64)]
    again = [r.route(f"req-{i}", "t", pods)[0] for i in range(64)]
    assert first == again  # same request id -> same arm, always
    assert set(first) == {0, 3}  # both arms actually drawn


def test_router_fallback_reason_and_validation():
    r = Router(2, pins={"legacy": FALLBACK})
    slot, reason = r.route("r1", "legacy", [])
    assert slot == FALLBACK and reason == "fallback"
    with pytest.raises(ValueError):
        Router(2, pins={"bad": 7})
    with pytest.raises(ValueError):
        Router(2, ab_split={0: 0.0})


def test_vm_coverage_split(wl):
    n, g = wl.cluster.n_padded, wl.cluster.g_padded
    resident, fallback = vm_coverage_split(
        [_champ(SEED_LOGIC), _champ(UNSUPPORTED_LOGIC)], n, g)
    assert len(resident) == 1 and len(fallback) == 1
    assert fallback[0].code == template.fill_template(UNSUPPORTED_LOGIC)


def test_route_reasons_pins_schema_checker_vocabulary():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "check_jsonl_schema",
        os.path.join(os.path.dirname(__file__), os.pardir, "tools",
                     "check_jsonl_schema.py"))
    checker = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(checker)
    assert set(ROUTE_REASONS) == checker.ROUTE_REASONS
    assert "slot_swap" in checker.EVENT_KIND_REQUIRED
    assert "portfolio_route" in checker.METRIC_KIND_REQUIRED


# --------------------------------------------------------- service


def test_service_routes_and_records(portfolio):
    rec = RecStub()
    router = Router(portfolio.n_slots, pins={"vip": 1},
                    ab_split={0: 0.5, 2: 0.5})
    svc = PortfolioService(portfolio, router=router, recorder=rec,
                           max_wait_s=0.002)
    try:
        base = portfolio.base_pods
        futs = [svc.submit({"pods": _query(base, i),
                            "tenant": "vip" if i % 2 else "t"})
                for i in range(6)]
        answers = [f.result(timeout=300) for f in futs]
    finally:
        svc.close()
    assert all("slot" in a for a in answers)
    routes = [m for m in rec.metrics if m["kind"] == "portfolio_route"]
    assert len(routes) == 6
    assert all(m["reason"] in ROUTE_REASONS for m in routes)
    assert {m["reason"] for m in routes} == {"pin", "ab"}
    summ = svc.summary(record=False)
    assert summ["portfolio"]["n_slots"] == portfolio.n_slots
    assert sum(summ["portfolio"]["slot_requests"]) >= 6


def test_service_query_slot_override(portfolio):
    svc = PortfolioService(portfolio, max_wait_s=0.002)
    try:
        q = {"pods": _query(portfolio.base_pods, 0), "slot": 2}
        ans = svc.submit(q).result(timeout=300)
    finally:
        svc.close()
    assert ans["slot"] == 2
    assert svc.router.routed["query"] == 1


def test_service_fallback_engine(wl, envelope, portfolio):
    """FALLBACK-routed requests are answered on the kept-warm AOT
    engine and marked slot -1; portfolio lanes are unaffected."""
    fallback = ServeEngine(_champ(BETTER_LOGIC), wl, envelope=envelope,
                           engine="flat")
    router = Router(portfolio.n_slots, pins={"legacy": FALLBACK})
    svc = PortfolioService(portfolio, router=router,
                           fallback_engine=fallback, max_wait_s=0.002)
    try:
        base = portfolio.base_pods
        f1 = svc.submit({"pods": _query(base, 0), "tenant": "legacy"})
        f2 = svc.submit({"pods": _query(base, 1), "tenant": "normal"})
        a1, a2 = f1.result(timeout=300), f2.result(timeout=300)
    finally:
        svc.close()
    assert a1["slot"] == FALLBACK
    assert a2["slot"] == svc.router.default_slot
    assert svc.fallback_served == 1


# -------------------------------------------- swap under live fire


def test_concurrent_slot_swap_never_perturbs_other_slots(wl, envelope):
    """ISSUE-20 extension of the PR-17 race criterion: promoting slot
    UNDER's neighbour must be invisible to slot UNDER — its answers
    stay bit-identical across 30 swaps of slot SWAP, every future
    resolves exactly once, and the whole race performs zero compiles."""
    champs = [_champ("score = node.cpu_milli_left - pod.cpu_milli",
                     0.4, source="<a>"),
              _champ("score = pod.cpu_milli - node.cpu_milli_left",
                     0.9, source="<b>")]
    eng = PortfolioEngine(champs, wl, envelope=envelope, engine="flat",
                          n_slots=3)
    eng.warmup()
    SWAP, UNDER = 0, 1
    base = eng.base_pods
    queries = [_query(base, 7), _query(base, 11)]

    def key(answers):
        return tuple((round(float(a["score"]), 9), tuple(a["placements"]))
                     for a in answers)

    expected = key(eng.answer_batch(queries, slots=[UNDER, UNDER]))
    # the swap alternates programs whose slot-SWAP answers differ, so a
    # torn slot table would have something to tear
    legal_swap = {}
    for i, c in enumerate(champs):
        eng.swap_slot(SWAP, c)
        legal_swap[i] = key(eng.answer_batch(queries, slots=[SWAP, SWAP]))
    assert legal_swap[0] != legal_swap[1]

    watcher = CompileWatcher().install()
    errors, torn, served = [], [], []
    stop = threading.Event()

    def hammer():
        try:
            while not stop.is_set():
                got = key(eng.answer_batch(queries, slots=[UNDER, UNDER]))
                served.append(1)
                if got != expected:
                    torn.append(got)
        except Exception as e:  # pragma: no cover - failure diagnostics
            errors.append(repr(e))

    threads = [threading.Thread(target=hammer) for _ in range(3)]
    for t in threads:
        t.start()
    try:
        for i in range(30):
            eng.swap_slot(SWAP, champs[(i + 1) % 2])
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=60)
        watcher.uninstall()
    assert not errors, errors
    assert not torn, (f"{len(torn)} perturbed slot-{UNDER} batches "
                      f"across slot-{SWAP} swaps, first: {torn[:1]}")
    assert len(served) > 0
    assert eng.slot_swaps[SWAP] >= 30
    assert watcher.backend_compile_count == 0


def test_concurrent_service_futures_exactly_once(wl, envelope):
    """The routed front under the same race: every submitted future
    resolves exactly once with a well-formed answer while a neighbour
    slot is being promoted."""
    champs = [_champ(SEED_LOGIC, 0.4, "<a>"),
              _champ(BETTER_LOGIC, 0.9, "<b>")]
    eng = PortfolioEngine(champs, wl, envelope=envelope, engine="flat",
                          n_slots=3)
    eng.warmup()
    svc = PortfolioService(eng, router=Router(3, pins={"t": 1}),
                           max_wait_s=0.002)
    base = eng.base_pods
    try:
        futs = [svc.submit({"pods": _query(base, i), "tenant": "t"})
                for i in range(8)]
        for i in range(10):
            eng.swap_slot(0, champs[(i + 1) % 2])
        answers = [f.result(timeout=300) for f in futs]
    finally:
        svc.close()
    assert len(answers) == 8
    assert all(a["slot"] == 1 and a["score"] is not None
               for a in answers)


# ----------------------------------------------- fleet controller


def test_fleet_promotes_one_slot(tmp_path, wl, envelope, champs):
    rec = RecStub()
    eng = PortfolioEngine(champs[:3], wl, envelope=envelope,
                          engine="flat", n_slots=4, recorder=rec)
    eng.warmup()
    svc = PortfolioService(eng, router=Router(4), recorder=rec,
                           max_wait_s=0.002)
    base = eng.base_pods
    try:
        futs = [svc.submit({"pods": _query(base, i)}) for i in range(4)]
        [f.result(timeout=300) for f in futs]
        ctrl = FleetController(
            svc, wl, slot=1, shadow_slot=3, ledger_dir=str(tmp_path),
            log_path=os.path.join(str(tmp_path), "promotion.jsonl"),
            config=PromotionConfig(shadow_queries=2), recorder=rec)
        watcher = CompileWatcher().install()
        try:
            write_champion(str(tmp_path),
                           template.fill_template(
                               "score = 3000 + (node.cpu_milli_left - "
                               "pod.cpu_milli) / "
                               "max(1, node.cpu_milli_total)"), 0.9)
            verdict = ctrl.poll_once()
            compiles = watcher.backend_compile_count
        finally:
            watcher.uninstall()
    finally:
        svc.close()
    assert verdict.get("action") == "promoted", verdict
    assert verdict.get("engine_kind") == "vm"
    assert compiles == 0
    assert eng.slot_swaps[1] == 1  # commit into the target slot
    assert eng.slot_swaps[3] == 1  # shadow staging into the spare slot
    # every promotion record carries the slot
    promo = [m for m in rec.metrics if m["kind"] == "promotion_event"
             and "slot" in m]
    assert promo and all(m["slot"] == 1 for m in promo)
    swaps = [e for e in rec.events if e["kind"] == "slot_swap"]
    assert [e["slot"] for e in swaps] == [3, 1]
    assert all(e["outcome"] == "swapped" for e in swaps)


def test_fleet_slot_validation(wl, envelope, champs):
    eng = PortfolioEngine(champs[:3], wl, envelope=envelope,
                          engine="flat", n_slots=4)
    svc = PortfolioService(eng, max_wait_s=0.002)
    try:
        with pytest.raises(ValueError):
            FleetController(svc, wl, slot=1, shadow_slot=1)
        with pytest.raises(ValueError):
            FleetController(svc, wl, slot=9, shadow_slot=3)
    finally:
        svc.close()


def test_fleet_fitness_gate_compares_against_slot(tmp_path, wl, envelope):
    """The fitness gate prices the candidate against the TARGET SLOT's
    resident champion, not the engine default: a candidate above slot 0
    but below slot 1 is rejected when slot 1 is the target."""
    eng = PortfolioEngine([_champ(SEED_LOGIC, 0.1, "<weak>"),
                           _champ(BETTER_LOGIC, 2.0, "<strong>")],
                          wl, envelope=envelope, engine="flat", n_slots=3)
    eng.warmup()
    svc = PortfolioService(eng, max_wait_s=0.002)
    try:
        ctrl = FleetController(
            svc, wl, slot=1, shadow_slot=2, ledger_dir=str(tmp_path),
            log_path=os.path.join(str(tmp_path), "promotion.jsonl"),
            config=PromotionConfig(shadow_queries=2))
        write_champion(str(tmp_path),
                       template.fill_template(EVEN_BETTER_LOGIC), 0.5)
        verdict = ctrl.poll_once()
    finally:
        svc.close()
    assert verdict.get("action") == "rejected", verdict
    assert "fitness" in verdict.get("reason", "")


# ------------------------------------------------------ satellites


def test_per_tenant_retry_after(wl, envelope, portfolio):
    """Satellite 1: a shed request's Retry-After is priced at the
    SHEDDING tenant's observed EWMA service time when accounting is on,
    falling back to the global estimate for cold tenants."""
    from fks_tpu.resilience.admission import (
        AdmissionConfig, AdmissionController,
    )
    from fks_tpu.resilience.deadline import ShedError

    ctl = AdmissionController(AdmissionConfig(max_queue=1))
    ctl.note_batch(1, 0.010)  # global EWMA: 10ms
    ctl.service_time_for = {"slow": 0.500, "fast": 0.001,
                            "cold": None}.get
    ctl.admit(None)  # fills the queue
    hints = {}
    for tenant in ("slow", "fast", "cold", None):
        with pytest.raises(ShedError) as e:
            ctl.admit(None, tenant=tenant)
        hints[tenant] = e.value.retry_after_s
    assert hints["slow"] == pytest.approx(0.500)
    assert hints["fast"] > 0.0
    assert hints["slow"] > hints["fast"]
    assert hints["cold"] == hints[None]  # cold tenant -> global EWMA


def test_service_wires_accountant_into_admission(portfolio):
    from fks_tpu.serve.service import ServeService

    svc = ServeService(portfolio, max_wait_s=0.002, accounting=True)
    try:
        assert svc._batcher.admission.service_time_for is not None
        base = portfolio.base_pods
        svc.submit({"pods": _query(base, 0),
                    "tenant": "t0"}).result(timeout=300)
        est = svc._batcher.admission.service_time_for("t0")
        assert est is not None and est > 0.0
        assert svc._batcher.admission.service_time_for("never-seen") \
            is None
    finally:
        svc.close()


def test_transpile_overlap(wl, envelope):
    """Satellite 2: ``begin_overlapped_transpile`` (kicked at SHADOW
    entry) warms the transpile cache off the promotion path, and the
    following swap reports ``transpile_overlapped``."""
    eng = VMServeEngine(_champ(SEED_LOGIC, 0.4), wl, envelope=envelope,
                        engine="flat")
    champ = _champ(EVEN_BETTER_LOGIC, 0.9, "<overlap>")
    t = eng.begin_overlapped_transpile(champ)
    t.join(timeout=60)
    eng.swap_program(champ)
    assert eng.last_swap_breakdown["transpile_overlapped"] is True
    assert eng.last_swap_breakdown["transpile_cache"] == "hit"
    # the flag is consumed: a re-swap of the same champion is a plain
    # cache hit, not another overlap claim
    eng.swap_program(_champ(SEED_LOGIC))
    eng.swap_program(champ)
    assert eng.last_swap_breakdown["transpile_overlapped"] is False


def test_transpile_overlap_rides_fleet_promotion(tmp_path, wl, envelope,
                                                 champs):
    rec = RecStub()
    eng = PortfolioEngine(champs[:2], wl, envelope=envelope,
                          engine="flat", n_slots=3, recorder=rec)
    eng.warmup()
    svc = PortfolioService(eng, recorder=rec, max_wait_s=0.002)
    base = eng.base_pods
    try:
        futs = [svc.submit({"pods": _query(base, i)}) for i in range(4)]
        [f.result(timeout=300) for f in futs]
        ctrl = FleetController(
            svc, wl, slot=1, shadow_slot=2, ledger_dir=str(tmp_path),
            log_path=os.path.join(str(tmp_path), "promotion.jsonl"),
            config=PromotionConfig(shadow_queries=2), recorder=rec)
        write_champion(str(tmp_path),
                       template.fill_template(
                           "score = 4000 + (node.memory_mib_left - "
                           "pod.memory_mib) / "
                           "max(1, node.memory_mib_total)"), 5.0)
        verdict = ctrl.poll_once()
    finally:
        svc.close()
    assert verdict.get("action") == "promoted", verdict
    swaps = [e for e in rec.events if e["kind"] == "slot_swap"]
    assert [e["slot"] for e in swaps] == [2, 1]
    # the staging swap is the candidate's first sighting (miss); the
    # COMMIT swap lowers from a warm cache entry and carries the
    # overlapped-transpile claim kicked at SHADOW entry
    assert swaps[0]["transpile_cache"] == "miss"
    assert swaps[1]["transpile_cache"] == "hit"
    assert swaps[1]["transpile_overlapped"] is True


# ------------------------------------- the op-slot loop under slot dispatch


def test_mixed_slot_batch_runs_to_the_longest_selected_program(
        wl, envelope, monkeypatch):
    """Per-lane slot dispatch gathers a per-lane ``n_ops``; the shared
    op-slot loop runs to the longest program any lane of the batch
    SELECTED, rounded up to whole blocks of ``vm.SLOT_BLOCK`` slots (the
    program words are per lane here; never a block past the table's
    longest, and never the padded capacity), its predicate stays a
    scalar, and the ``enqueue`` span says the live number."""
    import jax

    from tests.test_vm_batch import (
        _assert_unbatched_op_slot_loop, _champion_code,
        _count_slot_iterations,
    )

    long_ = ChampionSpec(code=_champion_code(), score=0.9, source="<ledger>")
    eng = PortfolioEngine([_champ(SEED_LOGIC, 0.4, "<c0>"), long_], wl,
                          envelope=envelope, engine="flat", n_slots=3)
    n_short, n_long = (int(p.n_ops) for p in eng._slot_progs[:2])
    assert n_short < n_long == 238 < eng.program_capacity == 256
    lanes, bucket = 2, 8
    fn = eng._make_serve_fn(bucket)
    batch = eng._example_batch(lanes, bucket)

    def run(slots):
        return fn(eng._prog_dev, slots, *batch)

    _assert_unbatched_op_slot_loop(
        jax.make_jaxpr(run)(np.asarray([0, 1], np.int32)),
        eng.program_capacity)
    for slots, longest in (([0, 1], n_long), ([0, 0], n_short),
                           ([2, 0], n_short)):   # slot 2: a spare, = slot 0
        res, fired = _count_slot_iterations(
            monkeypatch, run, np.asarray(slots, np.int32))
        monkeypatch.undo()
        events = int(np.max(np.asarray(res.events_processed)))
        ran = -(-longest // vm.SLOT_BLOCK) * vm.SLOT_BLOCK
        assert events > 0 and fired == ran * events, (slots, fired)
    # the host's copy of the same number, on the span the benchmark reads
    q = [_query(eng.base_pods, 0), _query(eng.base_pods, 1)]
    for slots, longest in (([0, 1], n_long), ([0, 0], n_short)):
        eng.answer_batch(q, slots=slots)
        got = [(r.fields["slots"], r.fields["capacity"])
               for r in eng.last_batch_spans
               if r.name == "serve/chunk/enqueue"]
        assert got and set(got) == {(longest, 256)}


def test_slot_dispatch_writes_a_register_as_one_slice(wl, envelope):
    """Per-lane programs out of the slot tables (``vm.select_slot``): the
    row the loop writes is still ``op_base`` plus the loop counter, so the
    batch keeps one ``dynamic_update_slice`` of the register file a slot,
    no scatter, and the ``enqueue`` span carries the two counts."""
    import jax

    from tests.test_vm_batch import _assert_one_slice_write_a_slot

    eng = PortfolioEngine([_champ(SEED_LOGIC, 0.4, "<c0>"),
                           _champ(BETTER_LOGIC, 0.9, "<c1>")], wl,
                          envelope=envelope, engine="flat", n_slots=2)
    fn = eng._make_serve_fn(8)
    batch = eng._example_batch(2, 8)
    assert _assert_one_slice_write_a_slot(
        jax.make_jaxpr(lambda slots: fn(eng._prog_dev, slots, *batch))(
            np.asarray([0, 1], np.int32)), eng.program_capacity) == 1
    eng.answer_batch([_query(eng.base_pods, 0), _query(eng.base_pods, 1)],
                     slots=[0, 1])
    enqueued = [r.fields for r in eng.last_batch_spans
                if r.name == "serve/chunk/enqueue"]
    got = [(f["slice_writes"], f["scatter_writes"]) for f in enqueued]
    assert got and all(s >= 1 and c == 0 for s, c in got), got
    # each lane gathers ITS champion's words (``vm.select_slot``), so the
    # program is per lane here and the loop turns once a block of slots
    assert all(f["blocked_loops"] >= 1 and f["plain_loops"] == 0
               for f in enqueued), enqueued
