"""Eval-budget allocator tests (fks_tpu.funsearch.budget).

Coverage map:
- BudgetConfig validation + survivor arithmetic (ceil(n/eta), the
  min_survivors floor, never more than n)
- probe_sim_config (probe scoring on; max_steps replaced only when
  probe_steps is set)
- CodeEvaluator wiring: budget requires suite mode, rejects the fused
  engine with a pointer message, forces the batched VM tier on CPU
- fused kernel rejects probe-scored SimConfigs at build time
- unified FKS_VM_SEG_STEPS / seg_steps validation (one helper, one
  error vocabulary, backend.py and sim/flat.py both on it)
- the budgeted evaluate() path end-to-end: rung tagging, survivor
  count, pruned-score capping below the worst survivor, per-rung
  stats, champion invariance vs the unbudgeted full evaluation
- compile-once-per-bucket: a second generation of the same size must
  not trigger new XLA backend compiles
- ParitySentinel.check_champion: silent on a sound pruning, alert
  (source="budget_champion") when a pruned candidate's reference score
  beats the pruned champion
- evolution integration: budget_rung metrics + GenerationStats budget
  fields land in the run dir over a multi-generation stub-LLM run with
  zero sentinel alerts, and the schema checker accepts the run dir
"""
import json
import pathlib
import subprocess
import sys

import numpy as np
import pytest

from fks_tpu.funsearch import llm, template, transpiler, vm
from fks_tpu.funsearch.backend import CodeEvaluator, EvalRecord
from fks_tpu.funsearch.budget import (
    BudgetConfig, BudgetedSuiteEval, probe_sim_config,
)
from fks_tpu.scenarios import RobustConfig, get_suite
from fks_tpu.sim.engine import SimConfig

REPO = pathlib.Path(__file__).parent.parent


def micro_workload():
    from tests.test_engine_micro import micro_workload as mw
    return mw()


def _vm_codes(wl, need, seed=7):
    """``need`` UNIQUE (by canonical key) VM-lowerable candidate sources
    from the stub LLM — the same candidate stream the bench stages use."""
    fake = llm.FakeLLM(seed=seed, junk_rate=0.0)
    c = wl.cluster
    codes, seen = [], set()
    for _ in range(40 * need):
        if len(codes) >= need:
            break
        code = template.fill_template(fake.complete("x"))
        try:
            key = transpiler.canonical_key(code)
            vm.compile_policy(code, c.n_padded, c.g_padded)
        except Exception:  # noqa: BLE001 — outside the VM vocabulary
            continue
        if key in seen:
            continue
        seen.add(key)
        codes.append(code)
    assert len(codes) >= need, f"only {len(codes)} unique VM candidates"
    return codes


# ------------------------------------------------------------- config


def test_budget_config_validation():
    with pytest.raises(ValueError, match="unknown budget schedule"):
        BudgetConfig(schedule="bandit")
    with pytest.raises(ValueError, match="eta must be >= 2"):
        BudgetConfig(schedule="halving", eta=1)
    with pytest.raises(ValueError, match="probe_steps must be >= 0"):
        BudgetConfig(schedule="halving", probe_steps=-1)
    with pytest.raises(ValueError, match="min_survivors must be >= 1"):
        BudgetConfig(schedule="halving", min_survivors=0)
    assert not BudgetConfig().enabled
    assert BudgetConfig(schedule="halving").enabled
    d = BudgetConfig(schedule="halving", eta=3, probe_steps=64).describe()
    assert d["eta"] == 3 and d["probe_steps"] == 64


def test_budget_survivor_arithmetic():
    b = BudgetConfig(schedule="halving", eta=2)
    assert b.survivors(8) == 4
    assert b.survivors(7) == 4  # ceil(7/2)
    assert b.survivors(1) == 1
    assert BudgetConfig(schedule="halving", eta=4).survivors(64) == 16
    # the floor wins over the fraction, but never exceeds n
    b = BudgetConfig(schedule="halving", eta=4, min_survivors=3)
    assert b.survivors(8) == 3
    assert b.survivors(2) == 2


def test_probe_sim_config():
    cfg = SimConfig(max_steps=512, track_ctime=False)
    p = probe_sim_config(cfg, BudgetConfig(schedule="halving",
                                           probe_steps=128))
    assert p.probe_score and p.max_steps == 128
    assert not p.track_ctime  # everything else rides along
    # probe_steps=0: full trace on the probe, only the scoring changes
    p0 = probe_sim_config(cfg, BudgetConfig(schedule="halving"))
    assert p0.probe_score and p0.max_steps == 512
    assert not cfg.probe_score  # the input config is untouched


# ------------------------------------------------------------- wiring


def test_budget_requires_suite_mode():
    with pytest.raises(ValueError, match="requires suite mode"):
        CodeEvaluator(micro_workload(),
                      budget=BudgetConfig(schedule="halving"))


def test_budget_rejects_fused_engine():
    wl = micro_workload()
    with pytest.raises(ValueError, match="fused"):
        CodeEvaluator(wl, engine="fused", suite=get_suite("smoke3", wl),
                      budget=BudgetConfig(schedule="halving"))


def test_disabled_budget_is_inert():
    wl = micro_workload()
    ev = CodeEvaluator(wl, suite=get_suite("smoke3", wl),
                       budget=BudgetConfig(schedule="none"))
    assert ev.budget is None
    assert not ev._budget_active(8)


def test_budget_forces_batched_vm_tier_on_cpu():
    wl = micro_workload()
    suite = get_suite("smoke3", wl)
    assert not CodeEvaluator(wl, suite=suite).vm_batch  # CPU default
    assert CodeEvaluator(wl, suite=suite,
                         budget=BudgetConfig(schedule="halving")).vm_batch


def test_fused_kernel_rejects_probe_score():
    from fks_tpu.sim import fused

    with pytest.raises(ValueError, match="probe_score"):
        fused.make_fused_population_run(
            micro_workload(), SimConfig(probe_score=True))


def test_seg_steps_validation_unified():
    from fks_tpu.utils import validate_seg_steps

    assert validate_seg_steps("4096") == 4096
    assert validate_seg_steps(0) == 0
    with pytest.raises(ValueError, match="must be an integer"):
        validate_seg_steps("abc")
    with pytest.raises(ValueError, match="must be >= 0"):
        validate_seg_steps(-3)
    with pytest.raises(ValueError, match="make_population_run_fn"):
        validate_seg_steps(0, zero_disables=False)
    # both consumers speak the same vocabulary: the backend names its env
    # var, the flat runner points at the unsegmented entry point
    with pytest.raises(ValueError, match="FKS_VM_SEG_STEPS must be"):
        validate_seg_steps("nope", source="FKS_VM_SEG_STEPS")


def test_backend_env_seg_steps_uses_helper(monkeypatch):
    monkeypatch.setenv("FKS_VM_SEG_STEPS", "-7")
    with pytest.raises(ValueError, match="FKS_VM_SEG_STEPS must be >= 0"):
        CodeEvaluator(micro_workload())
    monkeypatch.setenv("FKS_VM_SEG_STEPS", "2048")
    assert CodeEvaluator(micro_workload()).vm_seg_steps == 2048


def test_flat_segmented_runner_uses_helper():
    from fks_tpu.sim import flat

    wl = micro_workload()
    with pytest.raises(ValueError, match="make_population_run_fn"):
        flat.make_segmented_population_run(wl, vm.score, SimConfig(),
                                           seg_steps=0)
    with pytest.raises(ValueError, match="must be an integer"):
        flat.make_segmented_population_run(wl, vm.score, SimConfig(),
                                           seg_steps="junk")


# ------------------------------------------------- budgeted evaluation


@pytest.fixture(scope="module")
def budget_eval_setup():
    wl = micro_workload()
    suite = get_suite("smoke3", wl)
    robust = RobustConfig("cvar", cvar_alpha=0.5)
    budget = BudgetConfig(schedule="halving", eta=2, probe_suite="smoke3",
                          probe_steps=6)
    codes = _vm_codes(wl, 6)
    return wl, suite, robust, budget, codes


def test_budgeted_evaluate_end_to_end(budget_eval_setup):
    wl, suite, robust, budget, codes = budget_eval_setup
    ev = CodeEvaluator(wl, suite=suite, robust=robust, budget=budget)
    recs = ev.evaluate(codes)
    assert [r.code for r in recs] == codes  # input order preserved
    survivors = [r for r in recs if r.budget_rung == 1]
    pruned = [r for r in recs if r.budget_rung == 0]
    assert len(survivors) == 3 and len(pruned) == 3
    # pruned probe scores are capped BELOW every survivor's full score
    floor = min(r.score for r in survivors)
    assert all(r.score <= floor for r in pruned)
    # per-rung ledger stats: probe saw everyone, full rung the survivors
    assert [(r["rung"], r["entered"], r["survived"])
            for r in ev.last_budget_stats] == [(0, 6, 3), (1, 3, 3)]
    assert all(r["device_seconds"] > 0 for r in ev.last_budget_stats)
    assert all(r["lanes"] >= r["entered"] for r in ev.last_budget_stats)
    assert ev.last_eval_stats["budget_pruned"] == 3
    assert ev.vm_batch_count == 2  # one launch per rung


def test_budget_champion_matches_full_eval(budget_eval_setup):
    wl, suite, robust, budget, codes = budget_eval_setup
    budgeted = CodeEvaluator(wl, suite=suite, robust=robust, budget=budget)
    full = CodeEvaluator(wl, suite=suite, robust=robust, vm_batch=True)
    b_recs = budgeted.evaluate(codes)
    f_recs = full.evaluate(codes)
    assert all(r.budget_rung is None for r in f_recs)
    b_champ = max(b_recs, key=lambda r: r.score)
    f_best = max(r.score for r in f_recs)
    # pruning may change WHO gets full fidelity, never who wins: the
    # budget champion's full-suite score equals the unbudgeted maximum
    assert b_champ.budget_rung == 1
    assert b_champ.score == pytest.approx(f_best, abs=1e-6)
    # survivors carry true full-suite records — identical to the
    # unbudgeted evaluation of the same code
    by_code = {r.code: r for r in f_recs}
    for r in b_recs:
        if r.budget_rung == 1:
            ref = by_code[r.code]
            assert r.score == pytest.approx(ref.score, abs=1e-6)
            np.testing.assert_allclose(r.scenario_scores,
                                       ref.scenario_scores, atol=1e-6)


def test_budget_compiles_once_per_bucket(budget_eval_setup):
    from fks_tpu.obs import CompileWatcher

    wl, suite, robust, budget, codes = budget_eval_setup
    ev = CodeEvaluator(wl, suite=suite, robust=robust, budget=budget)
    watcher = CompileWatcher().install()
    try:
        ev.evaluate(codes)
        warm = watcher.backend_compile_count
        # a fresh generation of the SAME size must hit both rungs'
        # compiled programs — bucketed lanes, stable probe shape
        ev.evaluate(_vm_codes(wl, 6, seed=11))
        assert watcher.backend_compile_count == warm
    finally:
        watcher.uninstall()


def test_budget_inactive_below_two_candidates(budget_eval_setup):
    wl, suite, robust, budget, codes = budget_eval_setup
    ev = CodeEvaluator(wl, suite=suite, robust=robust, budget=budget)
    recs = ev.evaluate(codes[:1])
    assert recs[0].budget_rung is None  # unbudgeted path served it
    assert ev.last_budget_stats == []


def test_budgeted_suite_eval_direct():
    """The ladder below the evaluator: BudgetedSuiteEval.run on lowered
    programs — survivor indices sorted, probe scores for everyone, rung
    stats consistent."""
    import jax

    wl = micro_workload()
    cfg = SimConfig()
    robust = RobustConfig("mean")
    budget = BudgetConfig(schedule="halving", eta=3, probe_steps=6)
    codes = _vm_codes(wl, 6)
    c = wl.cluster
    progs = [vm.compile_policy(s, c.n_padded, c.g_padded) for s in codes]

    from fks_tpu.scenarios.robust import make_suite_eval
    suite = get_suite("smoke3", wl)
    full_ev = make_suite_eval(suite, vm.score, cfg,
                              population=True, engine="exact")
    ladder = BudgetedSuiteEval(
        wl, cfg, budget, robust,
        full_runner=lambda stacked: full_ev(stacked))
    out = ladder.run(progs)
    assert len(out.results) == 6
    assert out.survivor_indices == sorted(out.survivor_indices)
    assert len(out.survivor_indices) == 2  # ceil(6/3)
    assert [r.rung for r in out.rungs] == [0, 1]
    assert out.rungs[0].entered == 6 and out.rungs[0].survived == 2
    assert out.rungs[1].entered == 2
    assert len(out.probe_scores) == 6
    # the survivors ARE the probe's top-2 (stable argsort)
    order = np.argsort(-np.asarray(out.probe_scores), kind="stable")
    assert set(out.survivor_indices) == set(int(i) for i in order[:2])
    # pruned flags complement the survivor set
    assert [not p for p in out.pruned] == [
        i in out.survivor_indices for i in range(6)]
    del jax  # imported for parity with other direct-ladder users


# ------------------------------------------------------------ sentinel


class _Recorder:
    def __init__(self):
        self.metrics, self.events = [], []

    def metric(self, kind, payload=None, **kw):
        rec = dict(payload or {})
        rec.update(kw)
        self.metrics.append((kind, rec))

    def event(self, kind, **kw):
        self.events.append((kind, kw))


def test_check_champion_silent_on_sound_pruning(budget_eval_setup):
    from fks_tpu.funsearch.parity import ParitySentinel

    wl, suite, robust, budget, codes = budget_eval_setup
    ev = CodeEvaluator(wl, suite=suite, robust=robust, budget=budget)
    recs = ev.evaluate(codes)
    rec = _Recorder()
    sentinel = ParitySentinel(ev, tol=1e-5, recorder=rec)
    stats = sentinel.check_champion(0, recs)
    assert stats["alerts"] == 0 and sentinel.alerts == 0
    assert stats["checked"] == 4  # 3 pruned + the champion
    kinds = [k for k, _ in rec.metrics]
    assert kinds == ["parity"]
    assert rec.metrics[0][1]["source"] == "budget_champion"
    assert not rec.events


def test_check_champion_alerts_on_wrong_prune():
    from fks_tpu.funsearch.parity import ParitySentinel

    wl = micro_workload()
    ev = CodeEvaluator(wl, suite=get_suite("smoke3", wl),
                       budget=BudgetConfig(schedule="halving"))
    rec = _Recorder()
    sentinel = ParitySentinel(ev, tol=1e-5, recorder=rec)

    class _Ref:
        def evaluate_one(self, code):
            # the pruned candidate's true score beats the champion's
            return EvalRecord(code, 0.9 if code == "pruned" else 0.4)

    sentinel._ref = _Ref()
    records = [EvalRecord("champ", 0.5, budget_rung=1),
               EvalRecord("pruned", 0.1, budget_rung=0)]
    stats = sentinel.check_champion(3, records)
    assert stats["alerts"] == 1 and sentinel.alerts == 1
    assert stats["max_gap"] == pytest.approx(0.5)
    alerts = [kw for k, kw in rec.events if k == "alert"]
    assert len(alerts) == 1
    assert alerts[0]["source"] == "budget_champion"
    assert alerts[0]["generation"] == 3


def test_check_champion_skips_without_budget_records():
    from fks_tpu.funsearch.parity import ParitySentinel

    wl = micro_workload()
    ev = CodeEvaluator(wl, suite=get_suite("smoke3", wl),
                       budget=BudgetConfig(schedule="halving"))
    rec = _Recorder()
    sentinel = ParitySentinel(ev, tol=1e-5, recorder=rec)
    stats = sentinel.check_champion(0, [EvalRecord("a", 0.5)])
    assert stats == {"generation": 0, "checked": 0, "max_gap": 0.0,
                     "alerts": 0}
    assert not rec.metrics and not rec.events


# ----------------------------------------------------------- evolution


def test_evolution_with_budget_ledger_and_zero_alerts(tmp_path):
    from fks_tpu import obs
    from fks_tpu.funsearch import EvolutionConfig, FakeLLM
    from fks_tpu.funsearch import evolution as evo

    run_dir = tmp_path / "run"
    recorder = obs.FlightRecorder(str(run_dir), meta={"command": "test"})
    cfg = EvolutionConfig(population_size=8, generations=5, elite_size=2,
                          candidates_per_generation=6, max_workers=1,
                          seed=7, early_stop_threshold=1.1,
                          scenario_suite="smoke3",
                          robust_aggregation="cvar", robust_cvar_alpha=0.5,
                          budget_schedule="halving", budget_eta=2,
                          probe_suite="smoke3", probe_steps=6)
    fs = evo.run(micro_workload(), cfg, backend=FakeLLM(seed=7),
                 log=lambda _m: None, recorder=recorder)
    recorder.finish("ok")
    recorder.close()
    assert fs.evaluator.budget is not None
    # the acceptance bar: pruning never changed a champion over >= 5
    # generations of the stub LLM
    assert fs.sentinel.alerts == 0
    budgeted = [s for s in fs.history if s.budget_pruned > 0]
    assert budgeted, "no generation engaged the budget ladder"
    assert all(s.budget_device_seconds > 0 for s in budgeted)

    metrics = [json.loads(line) for line in
               (run_dir / "metrics.jsonl").read_text().splitlines()]
    rungs = [m for m in metrics if m["kind"] == "budget_rung"]
    assert rungs, "no budget_rung records in the run dir"
    by_gen = {}
    for r in rungs:
        by_gen.setdefault(r["generation"], []).append(r)
    for gen_rungs in by_gen.values():
        gen_rungs.sort(key=lambda r: r["rung"])
        assert [r["rung"] for r in gen_rungs] == [0, 1]
        assert gen_rungs[0]["survived"] == gen_rungs[1]["entered"]
        assert gen_rungs[0]["entered"] > gen_rungs[0]["survived"]
    # the champion audit ran each budgeted generation
    audits = [m for m in metrics if m["kind"] == "parity"
              and m.get("source") == "budget_champion"]
    assert len(audits) == len(by_gen)
    # ledger rows carry the budget columns
    gens = [m for m in metrics if m["kind"] == "generation"]
    assert any(g.get("budget_pruned", 0) > 0 for g in gens)

    # the schema checker accepts the new kind in a REAL run dir
    proc = subprocess.run(
        [sys.executable, str(REPO / "tools" / "check_jsonl_schema.py"),
         "--run-dir", str(run_dir)],
        capture_output=True, text=True, cwd=str(REPO))
    assert proc.returncode == 0, proc.stderr + proc.stdout


def test_evolution_config_budget_from_json(tmp_path):
    from fks_tpu.funsearch.evolution import EvolutionConfig

    path = tmp_path / "cfg.json"
    path.write_text(json.dumps({"funsearch": {
        "budget_schedule": "halving", "budget_eta": 3,
        "probe_suite": "smoke3", "probe_steps": 99}}))
    cfg = EvolutionConfig.from_json(str(path))
    assert cfg.budget_schedule == "halving"
    assert cfg.budget_eta == 3
    assert cfg.probe_suite == "smoke3"
    assert cfg.probe_steps == 99
    bare = tmp_path / "bare.json"
    bare.write_text("{}")
    assert EvolutionConfig.from_json(str(bare)).budget_schedule == "none"
