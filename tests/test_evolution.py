"""Hermetic evolution-loop tests with the deterministic fake LLM — the
testability gap SURVEY.md §4 calls out in the reference (whose loop needs a
live OpenRouter key). Runs on the micro workload so a full multi-generation
evolution takes seconds."""
import json

import pytest

from fks_tpu.funsearch import (
    CodeEvaluator, EvolutionConfig, FakeLLM, FunSearch, seed_policies,
)
from fks_tpu.funsearch import evolution as evo
from tests.test_engine_micro import micro_workload


@pytest.fixture(scope="module")
def evaluator():
    return CodeEvaluator(micro_workload())


def quiet(_msg):
    pass


def make_fs(evaluator, **overrides):
    # max_workers=1: with a shared-RNG FakeLLM, >1 worker can permute which
    # draw lands on which future, breaking the bit-identical-resume checks
    cfg = EvolutionConfig(
        population_size=8, generations=2, elite_size=2,
        candidates_per_generation=4, max_workers=1, seed=7,
        early_stop_threshold=1.1,  # never early-stop in tests
        **overrides)
    return FunSearch(evaluator, cfg, backend=FakeLLM(seed=7), log=quiet)


def test_seeds_score_positive(evaluator):
    recs = evaluator.evaluate(list(seed_policies().values()))
    assert all(r.ok for r in recs)
    assert all(r.score > 0 for r in recs)


def test_failed_candidates_score_zero(evaluator):
    recs = evaluator.evaluate(["import os", "def priority_function(pod, node:"])
    assert [r.score for r in recs] == [0.0, 0.0]
    assert all(not r.ok for r in recs)


def test_compile_cache_hits_on_reformatted_code(evaluator0=None):
    ev = CodeEvaluator(micro_workload())
    code = list(seed_policies().values())[0]
    ev.evaluate([code])
    n = ev.compile_count
    ev.evaluate([code.replace("return max(1, int(score))",
                              "return max(1,  int(score))")])
    assert ev.compile_count == n  # same AST -> cached program


def test_evolution_runs_and_improves_or_holds(evaluator):
    fs = make_fs(evaluator)
    best_code, best_score = fs.run_evolution()
    assert best_score > 0
    assert "priority_function" in best_code
    assert fs.generation == 2
    assert len(fs.population) <= 8
    assert len(fs.history) == 2
    # population sorted desc, best tracks the top
    scores = [s for _, s in fs.population]
    assert scores == sorted(scores, reverse=True)
    assert best_score >= scores[0] - 1e-12


def test_evolution_deterministic(evaluator):
    a = make_fs(evaluator).run_evolution()
    b = make_fs(evaluator).run_evolution()
    assert a == b


def test_dedup_rejects_near_duplicates(evaluator):
    fs = make_fs(evaluator)
    fs.initialize_population()
    code, score = fs.population[0]
    assert fs._is_too_similar(code, score - 0.01)  # identical code, lower score
    assert not fs._is_too_similar("def priority_function(pod, node):\n"
                                  "    return 1\n", 0.0)


def test_early_stop(evaluator):
    fs = make_fs(evaluator)
    fs.cfg = EvolutionConfig(
        population_size=8, generations=5, elite_size=2,
        candidates_per_generation=4, max_workers=2, seed=7,
        early_stop_threshold=0.01)
    fs.run_evolution()
    assert fs.generation == 1  # seeds already beat 0.01 -> stop after gen 1


def test_checkpoint_resume_round_trip(evaluator, tmp_path):
    ck = str(tmp_path / "evo.json")
    fs = make_fs(evaluator)
    fs.initialize_population()
    fs.evolve_generation()
    fs.checkpoint(ck)
    mid_best = fs.best
    fs.evolve_generation()
    final = (fs.best, [s for _, s in fs.population], fs.generation)

    fs2 = make_fs(evaluator)
    fs2.restore(ck)
    assert fs2.generation == 1
    assert fs2.best == mid_best
    fs2.evolve_generation()
    resumed = (fs2.best, [s for _, s in fs2.population], fs2.generation)
    assert resumed == final  # bit-identical continuation (incl. RNG state)


def test_save_top_policies_schema(evaluator, tmp_path):
    fs = make_fs(evaluator)
    fs.initialize_population()
    path = fs.save_top_policies(str(tmp_path / "discovered"), k=2)
    with open(path) as f:
        payload = json.load(f)
    assert len(payload) == 2
    assert {"rank", "score", "generation", "code", "timestamp"} <= set(payload[0])
    assert payload[0]["rank"] == 1
    assert payload[0]["score"] >= payload[1]["score"]


def test_save_best_policy_schema(evaluator, tmp_path):
    """Single-champion JSON: reference filename pattern + {score,
    generation, code, timestamp} schema (funsearch_integration.py:606-633)."""
    fs = make_fs(evaluator)
    fs.initialize_population()
    path = fs.save_best_policy(str(tmp_path / "discovered"))
    assert "funsearch_" in path and "_score" in path
    with open(path) as f:
        payload = json.load(f)
    assert set(payload) == {"score", "generation", "code", "timestamp"}
    assert payload["score"] == fs.best[1]
    assert payload["code"] == fs.best[0]


def test_flat_engine_champions_rescored_on_exact(tmp_path):
    """Search on the fast (flat) engine, report on the exact engine: every
    persisted champion's ``score`` must be exact-engine fitness, with the
    raw search fitness alongside (round-2 verdict ask #3 — fast-engine
    fitness uses relaxed retry semantics and is not comparable to the
    reference's published table)."""
    from fks_tpu.sim.engine import simulate
    from fks_tpu.funsearch import transpiler

    wl = micro_workload()
    fs = make_fs(CodeEvaluator(wl, engine="flat"))
    fs.initialize_population()
    fs.evolve_generation()
    assert fs.best_exact is not None

    path = fs.save_best_policy(str(tmp_path / "discovered"))
    with open(path) as f:
        payload = json.load(f)
    assert {"score", "search_score", "search_engine"} <= set(payload)
    assert payload["search_engine"] == "flat"
    assert not {"score_engine", "start_event"} & set(payload)  # rescored
    assert payload["search_score"] == fs.best[1]
    # the persisted score really is the exact engine's verdict on this code
    want = float(simulate(wl, transpiler.transpile(payload["code"])).policy_score)
    assert payload["score"] == pytest.approx(want, abs=1e-9)
    # filename carries the exact score, not the search score
    assert f"_score{payload['score']:.4f}" in path

    top = fs.save_top_policies(str(tmp_path / "discovered"), k=2)
    with open(top) as f:
        ranked = json.load(f)
    assert all({"score", "search_score", "search_engine"} <= set(r)
               for r in ranked)


def test_exact_engine_champions_have_no_search_fields(evaluator, tmp_path):
    """engine="exact" searches stay single-score: no redundant
    search_score/search_engine fields (the reference schema untouched)."""
    fs = make_fs(evaluator)
    fs.initialize_population()
    path = fs.save_best_policy(str(tmp_path / "discovered"))
    with open(path) as f:
        payload = json.load(f)
    assert set(payload) == {"score", "generation", "code", "timestamp"}
    assert fs.best_exact == fs.best[1]


def test_interrupt_mid_evolution_saves_champions(tmp_path, monkeypatch):
    """A KeyboardInterrupt inside the generation loop still leaves top-K +
    best champion JSONs and a checkpoint on disk (reference saves top-5 on
    interrupt, funsearch_integration.py:698-702)."""
    out = tmp_path / "discovered"
    ck = str(tmp_path / "evo.json")
    cfg = EvolutionConfig(population_size=6, generations=3, elite_size=2,
                          candidates_per_generation=2, max_workers=1, seed=3,
                          early_stop_threshold=1.1)
    calls = {"n": 0}
    orig = FunSearch.evolve_generation

    def interrupting(self):
        calls["n"] += 1
        if calls["n"] == 2:
            raise KeyboardInterrupt
        return orig(self)

    monkeypatch.setattr(FunSearch, "evolve_generation", interrupting)
    fs = evo.run(micro_workload(), cfg, backend=FakeLLM(3),
                 checkpoint_path=ck, out_dir=str(out), log=quiet)
    assert fs.best is not None
    saved = sorted(p.name for p in out.iterdir())
    assert any(p.startswith("top_policies_") for p in saved)
    assert any(p.startswith("funsearch_") for p in saved)
    import os
    assert os.path.exists(ck)


def test_config_from_reference_json(tmp_path):
    p = tmp_path / "llm_config.json"
    p.write_text(json.dumps({
        "openrouter": {"api_key": "k", "base_url": "https://x/v1",
                       "model": "m", "max_tokens": 100, "temperature": 0.3,
                       "timeout": 12.5, "max_retries": 1},
        "funsearch": {"population_size": 9, "generations": 3,
                      "early_stop_threshold": 0.5, "elite_size": 4,
                      "max_workers": 2},
    }))
    cfg = EvolutionConfig.from_json(str(p))
    assert cfg.population_size == 9
    assert cfg.elite_size == 4
    assert cfg.llm.model == "m"
    assert cfg.llm.temperature == 0.3
    assert cfg.llm.timeout == 12.5
    assert cfg.llm.max_retries == 1


def test_run_entry_point_with_checkpoint(tmp_path):
    ck = str(tmp_path / "run.json")
    cfg = EvolutionConfig(population_size=6, generations=1, elite_size=2,
                          candidates_per_generation=2, max_workers=2, seed=3,
                          early_stop_threshold=1.1)
    fs = evo.run(micro_workload(), cfg, backend=FakeLLM(3),
                 checkpoint_path=ck, log=quiet)
    assert fs.best is not None
    # resume picks up where the checkpoint left off
    fs2 = evo.run(micro_workload(), cfg, backend=FakeLLM(3),
                  checkpoint_path=ck, log=quiet)
    assert fs2.generation == 1  # already at generation budget; no extra gens


# ---------------------------------------------------- ISSUE 2: observability

def test_generation_stats_failure_classification():
    """EvalRecord errors split into transpile-fail (static rejection) vs
    sandbox-fail (raised while running) by prefix."""
    from fks_tpu.funsearch.backend import EvalRecord
    from fks_tpu.funsearch.evolution import _failure_counts

    records = [
        EvalRecord("a", 0.5, None),
        EvalRecord("b", 0.0, "syntax: invalid syntax"),
        EvalRecord("c", 0.0, "transpile: unsupported node"),
        EvalRecord("d", 0.0, "runtime: ZeroDivisionError"),
        EvalRecord("e", 0.0, "gpu allocation aborted"),
    ]
    sandbox, transpile = _failure_counts(records)
    assert transpile == 2
    assert sandbox == 2


def test_generation_stats_extended_fields(evaluator):
    fs = make_fs(evaluator)
    fs.initialize_population()
    stats = fs.evolve_generation()
    assert stats.p10_score <= stats.median_score <= stats.best_score
    assert stats.median_score > 0  # seeds score positive on the micro trace
    assert stats.sandbox_failed >= 0 and stats.transpile_failed >= 0
    assert stats.rescore_fallbacks == 0  # exact engine: no rescoring at all
    assert stats.llm_seconds >= 0
    # the ledger row carries every dataclass field + evaluator deltas
    row = fs.ledger.generation_record(stats)
    import dataclasses
    for f in dataclasses.fields(stats):
        assert f.name in row
    assert "programs_compiled" in row and "vm_segments" in row


def test_rescore_fallback_counter(evaluator, monkeypatch):
    """A transiently failing exact rescore increments the counter (and the
    per-generation delta lands in stats)."""
    fs = make_fs(evaluator)
    fs.evaluator = type(fs.evaluator)(micro_workload(), engine="flat")
    monkeypatch.setattr(
        type(fs.evaluator), "evaluate_one",
        lambda self, code: (_ for _ in ()).throw(RuntimeError("wedged")),
        raising=False)
    before = fs.rescore_fallbacks
    got = fs._exact_score("def priority_function(pod, node):\n    return 1\n",
                          0.42)
    assert got == 0.42  # falls back to the search fitness
    assert fs.rescore_fallbacks == before + 1


def test_restore_rejects_config_drift(evaluator, tmp_path):
    """Resuming a checkpoint under a different suite/aggregation/population
    would mix incomparable fitness scales — restore must fail loudly,
    naming the drifted keys."""
    import dataclasses

    ck = str(tmp_path / "evo.json")
    fs = make_fs(evaluator)
    fs.initialize_population()
    fs.checkpoint(ck)

    for key, value in (("population_size", 16),
                       ("scenario_suite", "default8"),
                       ("robust_aggregation", "cvar")):
        cfg2 = dataclasses.replace(fs.cfg, **{key: value})
        fs2 = FunSearch(evaluator, cfg2, backend=FakeLLM(seed=7), log=quiet)
        with pytest.raises(ValueError, match=key):
            fs2.restore(ck)
    # the matching config still restores
    fs3 = make_fs(evaluator)
    fs3.restore(ck)
    assert fs3.generation == fs.generation


def test_restore_tolerates_checkpoint_without_config(evaluator, tmp_path):
    """Pre-drift-check checkpoints carry no config block; they must keep
    restoring (drift detection is best-effort on old files)."""
    ck = tmp_path / "evo.json"
    fs = make_fs(evaluator)
    fs.initialize_population()
    fs.checkpoint(str(ck))
    state = json.loads(ck.read_text())
    del state["config"]
    ck.write_text(json.dumps(state))
    fs2 = make_fs(evaluator)
    fs2.restore(str(ck))
    assert fs2.generation == fs.generation


def test_llm_outage_circuit_breaker(tmp_path):
    """A total LLM outage (every call raises) halts the loop after N
    consecutive empty generations with the llm_outage flag up, a ledger
    event recorded, and the checkpoint still written by run()."""
    import os

    class DeadBackend:
        calls = 0

        def complete(self, prompt):
            DeadBackend.calls += 1
            raise RuntimeError("endpoint down")

    class EventRec:
        def __init__(self):
            self.events = []

        def event(self, kind, **fields):
            self.events.append({"kind": kind, **fields})

        def metric(self, kind, record=None, **fields):
            pass

        def heartbeat(self):
            pass

    rec = EventRec()
    ck = str(tmp_path / "evo.json")
    cfg = EvolutionConfig(population_size=6, generations=6, elite_size=2,
                          candidates_per_generation=3, max_workers=1,
                          seed=3, early_stop_threshold=1.1,
                          llm_outage_generations=2)
    fs = evo.run(micro_workload(), cfg, backend=DeadBackend(),
                 checkpoint_path=ck, out_dir=str(tmp_path / "out"),
                 recorder=rec, log=quiet)
    assert fs.llm_outage
    assert fs.generation == 2  # halted, not the 6-generation budget
    assert fs.best is not None  # seeds still scored
    assert os.path.exists(ck)  # the shutdown path checkpointed first
    assert DeadBackend.calls > 0
    outage = [e for e in rec.events if e["kind"] == "llm_outage"]
    assert outage and outage[0]["consecutive"] == 2


def test_llm_failures_reset_on_success(evaluator):
    """A flaky endpoint (one empty generation, then drafts) must NOT trip
    the breaker: the consecutive-failure counter resets."""
    fs = make_fs(evaluator, llm_outage_generations=2)
    fs.initialize_population()
    real_complete = fs.generator.backend.complete
    fs.generator.backend.complete = lambda prompt: (_ for _ in ()).throw(
        RuntimeError("down"))
    fs.evolve_generation()
    assert fs.llm_failures == 1
    fs.generator.backend.complete = real_complete
    fs.evolve_generation()
    assert fs.llm_failures == 0
    assert not fs.llm_outage
