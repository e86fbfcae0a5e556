"""The FunSearch evolution controller.

TPU-native re-design of the reference driver (reference:
funsearch/funsearch_integration.py:124-604 ``SimpleFunSearch``): identical
population semantics — descending sort, top-``elite_size`` elites, at most
``min(8, population_size - elite_size)`` new candidates per generation,
difflib near-duplicate suppression against equal-or-better incumbents,
truncation to ``population_size``, early stop on threshold — but the fitness
stage is the on-device backend (one compiled XLA program per unique
candidate, trace parsed once) instead of a subprocess pool that re-parses
CSVs per candidate.

Additions over the reference, called for by SURVEY.md §5:
- full checkpoint/resume (population + RNG state + generation), which the
  reference lacks entirely (its champion JSONs are write-only);
- a hermetic fake-LLM mode so the loop is testable without network;
- per-generation metrics records for observability.
"""
from __future__ import annotations

import contextlib
import dataclasses
import difflib
import functools
import hashlib
import json
import os
import random
import time
from typing import Callable, List, Optional, Sequence, Tuple

import jax

from fks_tpu import obs
from fks_tpu.obs import trace_ctx
from fks_tpu.funsearch import llm as llm_mod
from fks_tpu.funsearch import template
from fks_tpu.funsearch.backend import CodeEvaluator, EvalRecord
from fks_tpu.funsearch.parity import ParitySentinel
from fks_tpu.resilience.wal import GenerationWAL
from fks_tpu.sim.engine import SimConfig
from fks_tpu.sim.guards import combined_flags, describe_flags


# ------------------------------------------------------------------ config

@dataclasses.dataclass
class LLMSettings:
    """Reference ``openrouter`` block (configs/llm_config.json:2-8)."""

    api_key: str = ""
    base_url: str = "https://openrouter.ai/api/v1"
    model: str = "deepseek/deepseek-chat-v3-0324"
    max_tokens: int = 500
    temperature: float = 0.7
    # reachable from llm_config.json (unlike the reference, which rides the
    # SDK's 600 s default and retries): one hung request must not stall a
    # generation's thread-pool slot for 10 minutes
    timeout: float = 60.0
    max_retries: int = 2


@dataclasses.dataclass
class EvolutionConfig:
    """Reference ``funsearch`` block defaults (configs/llm_config.json:19-25;
    ``similarity_threshold`` default 0.85 per funsearch_integration.py:156)."""

    population_size: int = 20
    generations: int = 5
    early_stop_threshold: float = 0.6
    elite_size: int = 5
    max_workers: int = 8
    similarity_threshold: float = 0.85
    candidates_per_generation: int = 8  # reference cap: min(8, pop - elite)
    seed: int = 0
    # device-resident parametric rounds interleaved between LLM rounds
    # (0 = off): each generation additionally advances this many compiled
    # weight-evolution steps on the mesh and admits the rendered champion
    # through the normal code path (fks_tpu.funsearch.device_evolution)
    parametric_rounds: int = 0
    parametric_pop: int = 32
    parametric_noise: float = 0.05
    # parity sentinel (fks_tpu.funsearch.parity.ParitySentinel): re-score this
    # many sampled population members per generation through the exact
    # reference evaluator on the JIT tier and alert when |Δfitness|
    # exceeds parity_tol (0 = off). NOTE: the default tol assumes an
    # exact-engine search; flat-engine runs need a tol above the trace's
    # measured divergence bound (tools/divergence_audit.py).
    parity_sample: int = 0
    parity_tol: float = 1e-5
    # scenario-suite robust fitness (fks_tpu.scenarios): name a registered
    # suite ("" = off, single-trace fitness as before) and candidates are
    # scored by the composite robust aggregate over every scenario —
    # fault-injected variants included — evaluated in one vmapped call
    scenario_suite: str = ""
    robust_aggregation: str = "mean"  # mean | min | cvar
    robust_cvar_alpha: float = 0.25
    # successive-halving eval-budget allocation (fks_tpu.funsearch.budget;
    # requires a scenario_suite): score the whole generation on a cheap
    # probe rung — the probe_suite and/or a probe_steps-truncated trace
    # prefix — and advance only the top 1/budget_eta fraction to the full
    # suite. "none" = full-fidelity evaluation for every candidate.
    budget_schedule: str = "none"  # none | halving
    budget_eta: int = 2
    probe_suite: str = "smoke3"
    probe_steps: int = 0  # probe event budget; 0 = full trace on the probe
    # LLM-outage circuit breaker: after this many CONSECUTIVE generations
    # where every LLM call failed (zero candidates drafted), stop the run
    # with an ``llm_outage`` ledger event instead of spinning through the
    # remaining generation budget on an endpoint that is down (0 = spin)
    llm_outage_generations: int = 3

    llm: LLMSettings = dataclasses.field(default_factory=LLMSettings)

    @classmethod
    def from_json(cls, path: str) -> "EvolutionConfig":
        """Load the reference's config file format
        (reference: funsearch_integration.py:127-141)."""
        with open(path) as f:
            raw = json.load(f)
        fs = raw.get("funsearch", {})
        lm = raw.get("openrouter", {})
        return cls(
            population_size=fs.get("population_size", 20),
            generations=fs.get("generations", 5),
            early_stop_threshold=fs.get("early_stop_threshold", 0.6),
            elite_size=fs.get("elite_size", 5),
            max_workers=fs.get("max_workers", 8),
            similarity_threshold=fs.get("similarity_threshold", 0.85),
            parametric_rounds=fs.get("parametric_rounds", 0),
            parametric_pop=fs.get("parametric_pop", 32),
            parametric_noise=fs.get("parametric_noise", 0.05),
            parity_sample=fs.get("parity_sample", 0),
            parity_tol=fs.get("parity_tol", 1e-5),
            scenario_suite=fs.get("scenario_suite", ""),
            robust_aggregation=fs.get("robust_aggregation", "mean"),
            robust_cvar_alpha=fs.get("robust_cvar_alpha", 0.25),
            budget_schedule=fs.get("budget_schedule", "none"),
            budget_eta=fs.get("budget_eta", 2),
            probe_suite=fs.get("probe_suite", "smoke3"),
            probe_steps=fs.get("probe_steps", 0),
            llm_outage_generations=fs.get("llm_outage_generations", 3),
            llm=LLMSettings(
                api_key=lm.get("api_key", ""),
                base_url=lm.get("base_url", LLMSettings.base_url),
                model=lm.get("model", LLMSettings.model),
                max_tokens=lm.get("max_tokens", 500),
                temperature=lm.get("temperature", 0.7),
                timeout=lm.get("timeout", LLMSettings.timeout),
                max_retries=lm.get("max_retries", LLMSettings.max_retries),
            ),
        )


Member = Tuple[str, float]  # (candidate source, fitness)


@dataclasses.dataclass
class GenerationStats:
    generation: int
    best_score: float
    mean_score: float
    new_candidates: int
    accepted: int
    rejected_similar: int  # dup-suppressed (difflib near-duplicate)
    eval_seconds: float
    compile_count: int
    # fitness distribution over the post-truncation population (best /
    # median / p10 is the trio population-based stacks track per
    # generation; PAPERS.md: evosax, Fast PBRL)
    median_score: float = 0.0
    p10_score: float = 0.0
    # reject/failure breakdown the loop already observes (EvalRecord
    # errors + exact-rescore fallbacks) — previously dropped on the floor
    sandbox_failed: int = 0  # candidate raised during sandboxed execution
    transpile_failed: int = 0  # syntax / transpile rejection
    rescore_fallbacks: int = 0  # exact rescore failed -> search fitness
    llm_seconds: float = 0.0  # wall time of the LLM candidate stage
    # numerics watchdog: OR of SimResult.numeric_flags across this
    # generation's evaluations (0 unless SimConfig.watchdog is on), and
    # the parity sentinel's per-generation verdict (0 checks unless
    # EvolutionConfig.parity_sample > 0)
    watchdog_flags: int = 0
    parity_checked: int = 0
    parity_max_drift: float = 0.0
    parity_alerts: int = 0
    # scenario-suite searches: which suite/aggregation scored this
    # generation, and the champion's per-scenario breakdown (empty lists /
    # "" on single-trace runs — the pre-scenario schema unchanged)
    scenario_suite: str = ""
    robust_aggregation: str = ""
    best_scenario_scores: List[float] = dataclasses.field(
        default_factory=list)
    # eval-budget allocation (fks_tpu.funsearch.budget): how many LLM
    # candidates the probe rung pruned away from the full suite this
    # generation, and the total device wall across all rungs (the
    # per-rung breakdown rides kind="budget_rung" metric records; 0/0.0
    # on unbudgeted runs — the pre-budget schema unchanged)
    budget_pruned: int = 0
    budget_device_seconds: float = 0.0
    # fraction of this generation's unique candidates that lowered to
    # the VM register tier (backend.last_eval_stats) — the population's
    # eligibility for the zero-rebuild VM serve fast path (0.0 on
    # evaluators without the stat — the pre-VM-serve schema unchanged)
    vm_coverage: float = 0.0


def _percentile(sorted_desc: Sequence[float], q: float) -> float:
    """Nearest-rank quantile (q in [0, 1], from the BOTTOM) of an already
    descending-sorted score list; 0.0 on empty."""
    if not sorted_desc:
        return 0.0
    idx = min(len(sorted_desc) - 1,
              max(0, int(round((1.0 - q) * (len(sorted_desc) - 1)))))
    return float(sorted_desc[idx])


def _code_sha(code: str) -> str:
    """Content address of a candidate's source — the key that links an
    evolve-generation candidate span to the promotion attempt serving
    it (fks_tpu.pipeline.controller stamps the same hash)."""
    return hashlib.sha1(code.encode()).hexdigest()[:12]


def _failure_counts(records) -> Tuple[int, int]:
    """(sandbox_failed, transpile_failed) breakdown of a generation's
    EvalRecords. Transpile-fail covers the static rejections ("syntax:",
    "transpile:", and the pre-flight analyzer's "preflight:" verdicts —
    fks_tpu.analysis rejects are transpile failures caught early);
    sandbox-fail covers everything that failed while actually running —
    candidate exceptions ("runtime:") and simulated aborts (gpu
    allocation aborted / event budget exceeded). Failed candidates still
    enter selection at score 0 (reference semantics); these counters are
    observational only."""
    sandbox = transpile = 0
    for r in records:
        if r.error is None:
            continue
        if r.error.startswith(("syntax", "transpile", "preflight")):
            transpile += 1
        else:
            sandbox += 1
    return sandbox, transpile


@functools.lru_cache(maxsize=4096)
def analysis_fingerprint(code: str) -> Optional[str]:
    """Memoized normalized-AST fingerprint (fks_tpu.analysis). Incumbents
    are fingerprinted once per process, not once per similarity check."""
    from fks_tpu.analysis import fingerprint
    return fingerprint(code)


# ------------------------------------------------------------------ driver

class FunSearch:
    """Population manager + generation loop (reference semantics throughout;
    see module docstring)."""

    def __init__(self, evaluator: CodeEvaluator,
                 config: EvolutionConfig = EvolutionConfig(),
                 backend: Optional[llm_mod.TextBackend] = None,
                 log: Callable[[str], None] = print,
                 on_generation: Optional[
                     Callable[["GenerationStats"], None]] = None,
                 recorder: Optional[obs.NullRecorder] = None,
                 profiler=None):
        self.cfg = config
        self.evaluator = evaluator
        # device-time attribution (fks_tpu.obs.profiler): defaults to the
        # evaluator's profiler so one StageProfiler wired through the
        # evaluator attributes the whole loop — codegen / rank / ledger
        # here, sandbox+preflight / transpile / device-eval in the backend
        self.profiler = (profiler if profiler is not None
                         else evaluator.profiler)
        self.rng = random.Random(config.seed)
        self.log = log
        if evaluator.engine != "exact" and self._search_fitness_is_final:
            log(f"snapshot: no exact rescore from a fork (the exact "
                f"engine forks for serving, and the "
                f"tiers are not wired to it), so elites "
                f"are ranked and champions saved by their "
                f"[{evaluator.engine}] fitness from event "
                f"{evaluator.start_event}, with no exact re-rank; saved "
                "entries say so (score_engine, start_event)")
        # flight recorder: explicit > process-wide active (cli --run-dir
        # installs one via obs.recording); defaults to the NullRecorder,
        # under which the ledger performs zero filesystem writes
        self.recorder = recorder if recorder is not None else obs.get_recorder()
        self.ledger = obs.EvolutionLedger(self.recorder, evaluator)
        # the parity sentinel is a no-op unless parity_sample > 0; its
        # lifetime ``alerts`` counter feeds the CLI's nonzero-exit policy
        self.sentinel = ParitySentinel(
            evaluator, sample=config.parity_sample, tol=config.parity_tol,
            seed=config.seed, recorder=self.recorder)
        self.rescore_fallbacks = 0  # lifetime count; per-gen delta in stats
        self.rescore_platform = ""  # where exact rescores ran ("" = none yet)
        if backend is None:
            if config.llm.api_key:
                backend = llm_mod.OpenAIBackend(
                    config.llm.api_key, config.llm.base_url, config.llm.model,
                    config.llm.max_tokens, config.llm.temperature,
                    timeout=config.llm.timeout,
                    max_retries=config.llm.max_retries)
            else:
                backend = llm_mod.FakeLLM(seed=config.seed)
        self.generator = llm_mod.CandidateGenerator(backend)
        self.on_generation = on_generation
        self.population: List[Member] = []
        self.generation = 0
        self.best: Optional[Member] = None
        self.history: List[GenerationStats] = []
        # LLM-outage circuit breaker: consecutive all-calls-failed
        # generations; run_evolution() trips after
        # cfg.llm_outage_generations of them and sets ``llm_outage``
        # (the CLI maps it to a distinct exit code)
        self.llm_failures = 0
        self.llm_outage = False
        # lazily built device-resident parametric searcher; its weight
        # population persists on device across generations (its state is
        # NOT checkpointed — rendered champions persist via the code
        # population instead)
        self._device_evo = None
        # fast-engine searches (flat/fused) report fitness under relaxed
        # retry semantics, which is NOT comparable to the reference's
        # published numbers. Every NEW BEST and every persisted champion
        # is therefore re-scored through the exact reference-replica
        # engine; both numbers are kept. (Round-2 verdict: search-on-fast
        # + rescore-on-exact must be the built-in default, not a tools/
        # afterthought.)
        self._exact_eval: Optional[CodeEvaluator] = None
        self._exact_memo: dict = {}  # canonical AST key -> exact score
        self._scenario_memo: dict = {}  # key -> per-scenario exact scores
        self.best_exact: Optional[float] = None
        # generation WAL (fks_tpu.resilience.wal): when attached (run()'s
        # ``wal_path``), drafted codes and eval outcomes are durably
        # logged mid-generation and the loop checkpoints at EVERY
        # generation boundary — a kill mid-generation resumes without
        # re-spending LLM calls or device evals
        self.wal: Optional[GenerationWAL] = None
        self.checkpoint_path: Optional[str] = None
        self.wal_replayed_codes = 0  # lifetime resume accounting
        self.wal_replayed_evals = 0

    # ----- population mechanics (reference funsearch_integration.py:174-215)

    def initialize_population(self) -> None:
        """Seed from the baseline policies (reference seeds first-fit +
        best-fit, funsearch_integration.py:179-186) and evaluate them."""
        seeds = list(template.seed_policies().values())
        records = self.evaluator.evaluate(seeds)
        for r in records:
            if r.ok:  # in-process baseline eval skips failures
                self._admit(r.code, r.score)
        self._sort()
        if self.population:
            self.best = self.population[0]

    def _sort(self) -> None:
        """Descending by search fitness, then the head window re-ranked by
        EXACT fitness. Fast-engine scores drift from the exact engine by up
        to ~0.05 on the default trace (tools/divergence_audit.py) while
        published champion gaps are ~0.01, so a ranking taken raw from the
        fast engine would aim selection pressure inside the noise band.
        Re-ranking the top ``2*elite_size`` members by exact-engine fitness
        (memoized; ≤window extra exact runs per generation, usually just
        the new head entrants) makes elite selection and parent sampling
        exact-ranked, as the reference's single-engine sort trivially is
        (reference: funsearch_integration.py:494-496)."""
        self.population.sort(key=lambda m: m[1], reverse=True)
        if self._search_fitness_is_final or self.cfg.elite_size <= 0:
            return
        window = min(len(self.population), 2 * self.cfg.elite_size)
        if window <= 1:
            return
        head = self.population[:window]
        # exact first, search fitness as the tie-break; a transiently
        # failed rescore falls back to the member's search fitness
        # (un-memoized), so an infrastructure hiccup cannot evict a true
        # champion from the head window
        head.sort(key=lambda m: (self._exact_score(m[0], m[1]), m[1]),
                  reverse=True)
        self.population[:window] = head

    def _is_too_similar(self, code: str, score: float) -> bool:
        """difflib ratio >= threshold against any incumbent with >= score
        => reject (reference: funsearch_integration.py:208-215). Compared on
        the evolved logic block, not the full source: every candidate shares
        the fixed template, which would dominate a full-string ratio."""
        logic = template.logic_of(code)
        # normalized-AST fast path (fks_tpu.analysis): an exact fingerprint
        # collision with any incumbent at >= score is a duplicate by
        # construction (alpha-renames and same-decade coefficient jitter
        # collide) — skip the quadratic difflib pass for it
        fp = analysis_fingerprint(code)
        for other_code, other_score in self.population:
            if other_score >= score:
                if fp is not None and fp == analysis_fingerprint(other_code):
                    return True
                ratio = difflib.SequenceMatcher(
                    None, logic, template.logic_of(other_code)).ratio()
                if ratio >= self.cfg.similarity_threshold:
                    return True
        return False

    @property
    def _search_fitness_is_final(self) -> bool:
        """No exact rescore: the search engine IS exact, or the workload
        forks from a snapshot, from which candidates are evaluated on the
        flat engine only (``CodeEvaluator`` refuses another by name; the
        exact engine's fork serves queries: ROADMAP R5)."""
        return (self.evaluator.engine == "exact"
                or self.evaluator.workload.snapshot is not None)

    def _exact_score(self, code: str, score: float) -> float:
        """Fitness under the exact reference-replica engine. Identity when
        the search engine already IS exact; otherwise one VM-tier (or
        cached-jit) run of fks_tpu.sim.engine, memoized per canonical AST
        so NEW-BEST logging and the save paths never re-simulate the same
        candidate. A transiently failed rescore falls back to ``score``
        (the member's search fitness, un-memoized, retried next call);
        only an unparseable candidate maps to 0.0 — the rule the
        reference applies to failed evaluations (reference:
        funsearch_integration.py:63-64)."""
        if self._search_fitness_is_final:
            return score
        from fks_tpu.funsearch import transpiler
        try:
            key = transpiler.canonical_key(code)
        except SyntaxError:
            return 0.0
        if key in self._exact_memo:
            return self._exact_memo[key]
        try:
            # pin rescoring to the host CPU: the exact engine's heap is a
            # chain of tiny dependent gathers, the shape an accelerator
            # is worst at (PROFILE.md), and the rescore would compete
            # with the search for the device. The exact engine is
            # integer/deterministic, so the score is backend-independent.
            with self._exact_device():
                exact = self._exact_evaluator().evaluate_one(code).score
        except Exception as e:  # noqa: BLE001 — a transient infrastructure
            # failure (evaluate_one catches candidate failures, but
            # evaluator construction itself can raise) must never kill the
            # evolve loop mid-generation. Fall back to the member's SEARCH
            # fitness: ranking on (exact if ok else search, search) keeps a
            # true champion inside the elite window, where a 0.0 would
            # evict it — and the head window would then aim selection
            # pressure away from the best member for the rest of the run.
            # NOT memoized: the failure is transient; the next _sort
            # retries the exact rescore.
            self.rescore_fallbacks += 1
            self.log(f"  exact rescore failed ({type(e).__name__}: {e}); "
                     f"falling back to search fitness {score:.4f}")
            return score
        self._exact_memo[key] = exact
        return exact

    def _exact_evaluator(self) -> CodeEvaluator:
        """The lazily built exact rescoring evaluator. A scenario-suite
        search rescores on the SAME suite (the persisted robust score must
        be the exact-engine fold of the same scenarios the search ranked
        on, not a single-trace number)."""
        if self._exact_eval is None:
            self._exact_eval = CodeEvaluator(
                self.evaluator.workload, self.evaluator.cfg,
                engine="exact", suite=self.evaluator.suite,
                robust=self.evaluator.robust)
        return self._exact_eval

    def _scenario_breakdown(self, code: str) -> Optional[List[float]]:
        """Per-scenario EXACT-engine scores for a champion (None without a
        suite; memoized per canonical AST so champion saves and NEW-BEST
        stats never re-simulate the same candidate)."""
        if self.evaluator.suite is None:
            return None
        from fks_tpu.funsearch import transpiler
        try:
            key = transpiler.canonical_key(code)
        except SyntaxError:
            return None
        if key not in self._scenario_memo:
            try:
                if self.evaluator.engine == "exact":
                    rec = self.evaluator.evaluate_one(code)
                else:
                    with self._exact_device():
                        rec = self._exact_evaluator().evaluate_one(code)
            except Exception:  # noqa: BLE001 — transient infra failure:
                # skip the breakdown this time, retry on the next call
                return None
            self._scenario_memo[key] = rec.scenario_scores
        return self._scenario_memo[key]

    def _exact_device(self):
        """Context manager pinning exact rescoring to the host CPU backend.
        When the process exposes no CPU backend (``JAX_PLATFORMS`` names
        only the accelerator) the rescore shares the default device;
        ``rescore_platform`` says which of the two happened."""
        try:
            dev = jax.devices("cpu")[0]
        except RuntimeError:
            self.rescore_platform = jax.default_backend()
            return contextlib.nullcontext()
        self.rescore_platform = "cpu"
        return jax.default_device(dev)

    def _admit(self, code: str, score: float) -> None:
        self.population.append((code, score))
        if self.best is None or score > self.best[1]:
            self.best = (code, score)
            self.best_exact = self._exact_score(code, score)
            if self._search_fitness_is_final:
                self.log(f"  NEW BEST {score:.4f} (gen {self.generation})")
            else:
                self.log(f"  NEW BEST {score:.4f} "
                         f"[{self.evaluator.engine}] = {self.best_exact:.4f} "
                         f"[exact] (gen {self.generation})")

    def _sample_parents(self) -> Sequence[Member]:
        """<=2 random elites as prompt parents (reference:
        funsearch_integration.py:466)."""
        elites = self.population[: self.cfg.elite_size]
        k = min(2, len(elites))
        return self.rng.sample(elites, k) if k else []

    # ----- the generation loop (reference funsearch_integration.py:487-597)

    def evolve_generation(self) -> GenerationStats:
        self.generation += 1
        # one causal trace per generation (fks_tpu.obs.trace_ctx): the
        # llm/evaluate/rank/commit spans become children of a root
        # ``generation`` span, so ``cli spans --critical-path`` can read
        # the device-idle (LLM-bound) vs LLM-idle split straight off the
        # trail; per-candidate marker spans carry a content hash linking
        # this generation to any promotion attempt its champion wins
        gen_ctx = (trace_ctx.new_trace(prefix="gen")
                   if getattr(self.recorder, "enabled", False) else None)
        t_gen0 = time.perf_counter()
        with trace_ctx.activate(gen_ctx):
            stats = self._evolve_generation_body()
            trace_ctx.emit(self.recorder, "generation",
                           time.perf_counter() - t_gen0, ctx=gen_ctx,
                           root=True, generation=self.generation,
                           candidates=stats.new_candidates)
        return stats

    def _evolve_generation_body(self) -> GenerationStats:
        cfg = self.cfg
        with self.profiler.stage("codegen", generation=self.generation):
            self.ledger.begin_generation()
            fallbacks0 = self.rescore_fallbacks
            self._sort()
            n_new = min(cfg.candidates_per_generation,
                        max(0, cfg.population_size - cfg.elite_size))
            feedback = ""
            if self.best:
                feedback = (
                    f"best fitness so far {self.best[1]:.4f}; higher "
                    "utilization with less GPU fragmentation wins")
            cached_codes = (self.wal.pending_codes(self.generation)
                            if self.wal is not None else None)
            with obs.span("llm", generation=self.generation,
                          candidates=n_new) as lt:
                if cached_codes is not None:
                    # WAL replay: the drafted candidates survived the
                    # kill; burn the parent draws generate_many would
                    # have made (exactly n_new, at submit time) so the
                    # RNG trajectory matches the original attempt, and
                    # issue ZERO LLM calls
                    for _ in range(n_new):
                        self._sample_parents()
                    codes = list(cached_codes)
                    self.wal_replayed_codes += len(codes)
                else:
                    codes = llm_mod.generate_many(
                        self.generator, n_new, self._sample_parents,
                        feedback, cfg.max_workers)
                    if self.wal is not None:
                        self.wal.record_codes(self.generation, codes)
        llm_s = lt.seconds
        # outage tracking: a generation that ASKED for candidates and got
        # none back means every LLM call failed (generate() returns None
        # on any failure and generate_many drops them)
        if n_new > 0 and not codes:
            self.llm_failures += 1
        else:
            self.llm_failures = 0

        # plain wall time: evaluate() returns host floats (each candidate's
        # score is materialized inside), so there is nothing left to sync —
        # and its EvalRecord dataclasses are opaque to block_until_ready
        with obs.span("evaluate", generation=self.generation,
                      candidates=len(codes)) as t:
            records = self._evaluate_with_wal(codes, cached_codes)
            if getattr(self.recorder, "enabled", False):
                # content-addressed candidate markers: code_sha is the
                # key the promotion controller stamps on its attempts,
                # so ledger -> shadow -> swap links back to the evolve
                # generation that produced the champion
                for r in records:
                    trace_ctx.emit(
                        self.recorder, "evaluate/candidate", 0.0,
                        code_sha=_code_sha(r.code),
                        score=round(float(r.score), 6),
                        generation=self.generation)
        eval_s = t.seconds
        sandbox_failed, transpile_failed = _failure_counts(records)

        with self.profiler.stage("rank", generation=self.generation) as hr, \
                obs.span("rank", generation=self.generation):
            # eval-budget ledger: one budget_rung metric per rung (entered
            # / survived / device-seconds / segment count), then the
            # champion audit — pruning may never change who wins a
            # generation, only how cheaply, and a violated audit alerts
            # into the same exit-3 policy as fitness-drift parity alerts
            budget_rungs = list(
                getattr(self.evaluator, "last_budget_stats", []) or [])
            budget_alerts = 0
            for rung in budget_rungs:
                self.recorder.metric(
                    "budget_rung", generation=self.generation, **rung)
            if budget_rungs:
                budget_alerts = self.sentinel.check_champion(
                    self.generation, records)["alerts"]

            # numerics watchdog: one event per generation carrying the OR
            # of every evaluation's flag mask (always 0 when
            # SimConfig.watchdog is off — the guards are compiled out)
            wd_flags = 0
            for r in records:
                if r.result is not None:
                    wd_flags |= combined_flags(
                        getattr(r.result, "numeric_flags", 0))
            if wd_flags:
                self.recorder.event(
                    "watchdog", flags=wd_flags,
                    kinds=describe_flags(wd_flags),
                    generation=self.generation, candidates=len(records))

            accepted = rejected = 0
            for r in records:
                # subprocess-path semantics: failures carry score 0 and
                # still enter selection (SURVEY.md §2 fine print 8)
                if self._is_too_similar(r.code, r.score):
                    rejected += 1
                    continue
                self._admit(r.code, r.score)
                accepted += 1

            if cfg.parametric_rounds > 0:
                r = self._parametric_round()
                if r is not None:
                    if self._is_too_similar(r.code, r.score):
                        rejected += 1
                    else:
                        self._admit(r.code, r.score)
                        accepted += 1
            self._sort()
            del self.population[cfg.population_size:]

            # parity sentinel: sample the post-truncation population
            # (those are the members whose fitness selection actually
            # trusts)
            parity = self.sentinel.check(self.generation, self.population)
            hr.annotate(accepted=accepted, rejected_similar=rejected)

        with self.profiler.stage("ledger", generation=self.generation), \
                obs.span("commit", generation=self.generation):
            stats = self._commit_generation(
                codes, eval_s, llm_s, sandbox_failed, transpile_failed,
                fallbacks0, wd_flags, parity, budget_alerts, budget_rungs,
                accepted, rejected)
        if self.wal is not None:
            # checkpoint BEFORE the WAL commit: a kill between the two
            # leaves stale uncommitted records for THIS generation, which
            # the next resume (restored to this generation) never reads —
            # whereas commit-before-checkpoint would lose the generation
            if self.checkpoint_path:
                self.checkpoint(self.checkpoint_path)
            self.wal.commit(self.generation)
        return stats

    def _evaluate_with_wal(self, codes: List[str],
                           cached_codes) -> List[EvalRecord]:
        """Evaluate, replaying WAL-cached outcomes on resume: candidates
        whose eval already landed in the WAL are reconstructed (zero
        device work); only the fresh remainder runs, and each fresh
        outcome is durably logged before ranking sees it."""
        if self.wal is None:
            return self.evaluator.evaluate(codes)
        cached = self.wal.cached_evals(self.generation)
        keys = [GenerationWAL.code_key(c) for c in codes]
        fresh_idx = [i for i, k in enumerate(keys) if k not in cached]
        fresh = (self.evaluator.evaluate([codes[i] for i in fresh_idx])
                 if fresh_idx else [])
        by_idx = {}
        for i, r in zip(fresh_idx, fresh):
            by_idx[i] = r
            self.wal.record_eval(self.generation, r)
        records: List[EvalRecord] = []
        replayed = 0
        for i, code in enumerate(codes):
            if i in by_idx:
                records.append(by_idx[i])
            else:
                e = cached[keys[i]]
                records.append(EvalRecord(
                    code=code, score=e["score"], error=e["error"],
                    scenario_scores=e["scenario_scores"],
                    aggregation=e["aggregation"],
                    budget_rung=e["budget_rung"]))
                replayed += 1
        self.wal_replayed_evals += replayed
        if cached_codes is not None or replayed:
            self.recorder.event(
                "resume_wal", generation=self.generation,
                cached_codes=len(cached_codes or []), cached_evals=replayed,
                fresh_evals=len(fresh_idx))
        return records

    def _commit_generation(self, codes, eval_s, llm_s, sandbox_failed,
                           transpile_failed, fallbacks0, wd_flags, parity,
                           budget_alerts, budget_rungs, accepted,
                           rejected) -> GenerationStats:
        """Stats assembly + flight-recorder commit for one generation
        (the ``ledger`` profiler stage of ``evolve_generation``)."""
        # scenario-suite bookkeeping: the champion's per-scenario breakdown
        # rides the stats/ledger, and one robust_fitness metric per
        # generation lands in the flight-recorder trail
        suite = self.evaluator.suite
        best_breakdown: List[float] = []
        if suite is not None and self.best is not None:
            best_breakdown = self._scenario_breakdown(self.best[0]) or []
            self.recorder.metric(
                "robust_fitness", generation=self.generation,
                suite=suite.name, version=suite.version,
                aggregation=self.evaluator.robust.aggregation,
                scores=best_breakdown)

        scores = [s for _, s in self.population]  # descending post-_sort
        stats = GenerationStats(
            generation=self.generation,
            best_score=self.best[1] if self.best else 0.0,
            mean_score=sum(scores) / len(scores) if scores else 0.0,
            new_candidates=len(codes), accepted=accepted,
            rejected_similar=rejected, eval_seconds=eval_s,
            compile_count=self.evaluator.compile_count,
            median_score=_percentile(scores, 0.5),
            p10_score=_percentile(scores, 0.10),
            sandbox_failed=sandbox_failed,
            transpile_failed=transpile_failed,
            rescore_fallbacks=self.rescore_fallbacks - fallbacks0,
            llm_seconds=llm_s,
            watchdog_flags=wd_flags,
            parity_checked=parity["checked"],
            parity_max_drift=parity["max_drift"],
            parity_alerts=parity["alerts"] + budget_alerts,
            scenario_suite=suite.name if suite is not None else "",
            robust_aggregation=(self.evaluator.robust.aggregation
                                if suite is not None else ""),
            best_scenario_scores=best_breakdown,
            budget_pruned=sum(r["entered"] - r["survived"]
                              for r in budget_rungs),
            budget_device_seconds=round(sum(r["device_seconds"]
                                            for r in budget_rungs), 6),
            vm_coverage=float(getattr(self.evaluator, "last_eval_stats",
                                      {}).get("vm_coverage", 0.0)))
        self.history.append(stats)
        # ledger first: the flight-recorder trail must be complete even if a
        # user on_generation callback raises
        self.ledger.commit(stats)
        if self.on_generation is not None:
            # streamed per generation so an interrupted run still leaves a
            # complete metric trail (fks_tpu.utils.logging contract)
            self.on_generation(stats)
        self.log(
            f"gen {stats.generation}: best {stats.best_score:.4f} "
            f"mean {stats.mean_score:.4f} new {stats.new_candidates} "
            f"accepted {stats.accepted} (dup-rejected {stats.rejected_similar}) "
            f"eval {eval_s:.2f}s programs {stats.compile_count}")
        return stats

    def _parametric_round(self):
        """Advance the device-resident weight search and feed its champion
        back into the code population through the normal evaluation path
        (the rendered source is re-scored by the evaluator, so the
        admission comparison is apples-to-apples with LLM candidates)."""
        from fks_tpu.funsearch.device_evolution import ParametricEvolution

        if self._device_evo is None:
            self._device_evo = ParametricEvolution(
                self.evaluator.workload, pop_size=self.cfg.parametric_pop,
                noise=self.cfg.parametric_noise, cfg=self.evaluator.cfg,
                engine=self.evaluator.engine, seed=self.cfg.seed)
        st = self._device_evo.run(self.cfg.parametric_rounds)
        self.log(f"  parametric: gen {st.generation} best {st.best_score:.4f} "
                 f"mean {st.mean_score:.4f} (device-resident)")
        code = self._device_evo.best_code()
        rec = self.evaluator.evaluate([code])[0]
        return rec

    def run_evolution(self) -> Tuple[str, float]:
        """Full loop -> (best_code, best_score) (reference:
        funsearch_integration.py:574-597)."""
        if not self.population:
            # a named stage (not codegen) so the backend's nested eval
            # stages stay attributed to seeding, not the first generation
            with self.profiler.stage("seed"):
                self.initialize_population()
        while self.generation < self.cfg.generations:
            stats = self.evolve_generation()
            if stats.best_score >= self.cfg.early_stop_threshold:
                self.log(f"early stop: {stats.best_score:.4f} >= "
                         f"{self.cfg.early_stop_threshold}")
                break
            if (self.cfg.llm_outage_generations > 0
                    and self.llm_failures >= self.cfg.llm_outage_generations):
                # the endpoint is down, not flaky: stop burning the
                # generation budget on empty rounds. The caller's normal
                # shutdown path still checkpoints and saves champions.
                self.llm_outage = True
                self.recorder.event(
                    "llm_outage", generation=self.generation,
                    consecutive=self.llm_failures,
                    detail=f"every LLM call failed for {self.llm_failures} "
                           "consecutive generations; halting evolution")
                self.log(f"LLM OUTAGE: {self.llm_failures} consecutive "
                         "generations with zero drafted candidates; "
                         "checkpointing and stopping")
                break
        if self.best is None:
            return "", 0.0
        return self.best

    # ----- persistence (reference funsearch_integration.py:606-679) + resume

    def _champion_fields(self, code: str, score: float) -> dict:
        """The persisted ``score`` is exact-engine fitness — the only
        number comparable to the reference's published table. When the
        search ran on a fast engine, the raw search fitness and the engine
        name ride along as ``search_score``/``search_engine``. The one
        exception marks itself: a search forked from a snapshot cannot be
        rescored (the exact engine refuses a snapshot), so ``score`` is
        the search engine's and the entry carries ``score_engine`` and
        ``start_event`` — a reader that ranks entries must not compare
        such a score with an exact one."""
        exact = self._exact_score(code, score)
        fields = {"score": exact}
        if self.evaluator.engine != "exact":
            fields["search_score"] = score
            fields["search_engine"] = self.evaluator.engine
            if self._search_fitness_is_final:
                fields["score_engine"] = self.evaluator.engine
                fields["start_event"] = self.evaluator.start_event
        suite = self.evaluator.suite
        if suite is not None:
            fields["scenario_suite"] = suite.name
            fields["suite_version"] = suite.version
            fields["aggregation"] = self.evaluator.robust.aggregation
            per = self._scenario_breakdown(code)
            if per is not None:
                fields["scenario_scores"] = dict(zip(suite.names, per))
        return fields

    def save_top_policies(self, directory: str, k: int = 5) -> str:
        """Champion JSON with rank/score/generation/code/timestamp schema
        (reference: funsearch_integration.py:635-679). Fast-engine
        searches take the top ``k`` by search fitness, then RANK the
        payload by exact-engine fitness — a consumer reading rank 1 gets
        the exact-engine best of the rescored set, and the listed scores
        are monotonic."""
        os.makedirs(directory, exist_ok=True)
        stamp = time.strftime("%Y%m%d_%H%M%S")
        path = os.path.join(directory, f"top_policies_{stamp}.json")
        self._sort()
        entries = [
            {**self._champion_fields(c, s), "generation": self.generation,
             "code": c, "timestamp": stamp}
            for c, s in self.population[:k]
        ]
        entries.sort(key=lambda e: e["score"], reverse=True)
        payload = [{"rank": i + 1, **e} for i, e in enumerate(entries)]
        with open(path, "w") as f:
            json.dump(payload, f, indent=2)
        return path

    def save_best_policy(self, directory: str = "policies/discovered") -> str:
        """Single-champion JSON, reference schema {score, generation, code,
        timestamp} and filename pattern ``funsearch_<stamp>_score<s>.json``
        (reference: funsearch_integration.py:606-633). The score in both
        the filename and the payload is exact-engine fitness; for
        fast-engine searches the saved champion is the exact-engine best
        among the rescored top-5 (search order and exact order can
        disagree, and the persisted 'best' must honor the persisted
        metric)."""
        if self.best is None:
            raise ValueError("no best policy to save")
        self._sort()
        candidates = list(self.population[:5])
        if self.best not in candidates:
            candidates.append(self.best)
        code, score = max(
            candidates, key=lambda m: self._exact_score(m[0], m[1]))
        fields = self._champion_fields(code, score)
        os.makedirs(directory, exist_ok=True)
        stamp = time.strftime("%Y%m%d_%H%M%S")
        path = os.path.join(
            directory, f"funsearch_{stamp}_score{fields['score']:.4f}.json")
        with open(path, "w") as f:
            json.dump({**fields, "generation": self.generation,
                       "code": code,
                       "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S")},
                      f, indent=2)
        return path

    def checkpoint(self, path: str) -> None:
        """Mid-evolution state: population, best, generation, RNG — enough
        to resume bit-identically (absent from the reference; SURVEY.md §5
        flags it as required for long mesh jobs)."""
        state = {
            "version": 1,
            "generation": self.generation,
            "population": [{"code": c, "score": s} for c, s in self.population],
            "best": ({"code": self.best[0], "score": self.best[1]}
                     if self.best else None),
            "best_exact": self.best_exact,
            "rng_state": _encode_rng(self.rng.getstate()),
            "config": dataclasses.asdict(self.cfg),
        }
        backend = self.generator.backend
        if hasattr(backend, "getstate"):
            state["backend_state"] = backend.getstate()
        parent = os.path.dirname(path)
        if parent:
            os.makedirs(parent, exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(state, f)
            # fsync BEFORE the atomic rename: without it a crash can
            # replace a good checkpoint with an empty/torn rename target
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)

    #: config fields that change what a fitness NUMBER means (or how the
    #: population evolves); resuming a checkpoint across a drift in any
    #: of them would silently mix incomparable scores in one population
    _DRIFT_KEYS = ("scenario_suite", "robust_aggregation",
                   "robust_cvar_alpha", "population_size")

    def restore(self, path: str) -> None:
        try:
            with open(path) as f:
                state = json.load(f)
        except json.JSONDecodeError as e:
            raise ValueError(
                f"{path}: torn checkpoint (invalid JSON: {e}); delete it "
                "or restore from a backup — resuming from half a state "
                "would corrupt the population") from e
        if state.get("version") != 1:
            raise ValueError(f"unknown checkpoint version {state.get('version')}")
        stored = state.get("config") or {}
        current = dataclasses.asdict(self.cfg)
        drifted = [k for k in self._DRIFT_KEYS
                   if k in stored and stored[k] != current[k]]
        if drifted:
            diff = ", ".join(f"{k}: checkpoint={stored[k]!r} "
                             f"current={current[k]!r}" for k in drifted)
            raise ValueError(
                f"{path}: checkpoint config drift — resuming would mix "
                f"incomparable fitness scales ({diff}). Re-run with the "
                "checkpoint's config or start a fresh checkpoint.")
        self.generation = state["generation"]
        self.population = [(m["code"], m["score"]) for m in state["population"]]
        self.best = ((state["best"]["code"], state["best"]["score"])
                     if state["best"] else None)
        self.best_exact = state.get("best_exact")
        self.rng.setstate(_decode_rng(state["rng_state"]))
        backend = self.generator.backend
        if "backend_state" in state and hasattr(backend, "setstate"):
            backend.setstate(state["backend_state"])


def _encode_rng(state):
    """random.Random state contains a tuple-of-ints; make it JSON-stable."""
    kind, internal, gauss = state
    return [kind, list(internal), gauss]


def _decode_rng(obj):
    kind, internal, gauss = obj
    return (kind, tuple(internal), gauss)


# ------------------------------------------------------------- entry point

def run(workload, config: Optional[EvolutionConfig] = None,
        backend: Optional[llm_mod.TextBackend] = None,
        sim_config: SimConfig = SimConfig(),
        checkpoint_path: Optional[str] = None,
        wal_path: Optional[str] = None,
        out_dir: Optional[str] = None,
        engine: str = "exact",
        log: Callable[[str], None] = print,
        on_generation: Optional[Callable[[GenerationStats], None]] = None,
        recorder: Optional[obs.NullRecorder] = None,
        profile: bool = False,
        mesh=None,
        ) -> FunSearch:
    """Assemble evaluator + driver, optionally resuming from a checkpoint,
    and run to completion. Returns the driver for inspection.

    ``profile=True`` attributes the run's wall time per pipeline stage
    (fks_tpu.obs.profiler.StageProfiler): device_profile metrics into the
    recorder trail plus a summary on the returned driver's
    ``profiler.records``. Off is the default and compiles bit-identical
    programs (the NULL profiler adds no fences — pinned by cli lint).
    ``mesh`` (a >1-device population mesh) shards every batched
    generation over its devices (``CodeEvaluator(mesh=...)``).

    A KeyboardInterrupt mid-evolution still persists champions (top-K +
    single best into ``out_dir``, reference: funsearch_integration.py:
    698-702) and the checkpoint — a long device run killed at the terminal
    must never lose its discoveries."""
    config = config or EvolutionConfig()
    profiler = (obs.StageProfiler(scope="evolve", recorder=recorder)
                if profile else obs.NULL_PROFILER)
    suite = robust = budget = None
    if config.scenario_suite:
        from fks_tpu.scenarios import RobustConfig, get_suite
        suite = get_suite(config.scenario_suite, workload)
        robust = RobustConfig(aggregation=config.robust_aggregation,
                              cvar_alpha=config.robust_cvar_alpha)
        log(f"scenario suite {suite.name} v{suite.version}: "
            f"{len(suite)} scenarios, robust={robust.aggregation}")
    if config.budget_schedule != "none":
        from fks_tpu.funsearch.budget import BudgetConfig
        budget = BudgetConfig(schedule=config.budget_schedule,
                              eta=config.budget_eta,
                              probe_suite=config.probe_suite,
                              probe_steps=config.probe_steps)
        log(f"eval budget {budget.schedule}: probe {budget.probe_suite}"
            + (f" @{budget.probe_steps} events" if budget.probe_steps
               else "")
            + f", top 1/{budget.eta} advance to the full suite")
    with profiler.stage("setup", engine=engine):
        fs = FunSearch(CodeEvaluator(workload, sim_config, engine=engine,
                                     suite=suite, robust=robust,
                                     budget=budget, profiler=profiler,
                                     mesh=mesh),
                       config, backend, log,
                       on_generation=on_generation, recorder=recorder)
    if fs.evaluator.prefilter_derived:
        c = workload.cluster
        log(f"note: {c.n_padded} padded nodes: candidates score the first "
            f"{fs.evaluator.cfg.node_prefilter_k} feasible nodes in node "
            "order, not every node (sim.engine.shape_prefilter_k); pass "
            f"SimConfig(node_prefilter_k={c.n_padded}) for the dense sweep")
    if checkpoint_path and os.path.exists(checkpoint_path):
        fs.restore(checkpoint_path)
        log(f"resumed from {checkpoint_path} at generation {fs.generation}")
    if wal_path:
        # preemption-safe mode: WAL + checkpoint-every-generation, so the
        # pending window is exactly one generation and a kill -9
        # mid-generation resumes without re-buying its LLM/device spend
        fs.wal = GenerationWAL(wal_path)
        fs.checkpoint_path = checkpoint_path
        summ = fs.wal.summary()
        if summ["records"]:
            log(f"generation WAL {wal_path}: {summ['records']} records, "
                f"{len(summ['committed'])} committed generations"
                + (f", {summ['skipped_lines']} torn lines skipped"
                   if summ["skipped_lines"] else ""))
    fs.interrupted = False  # callers: champions already persisted when True
    try:
        fs.run_evolution()
    except KeyboardInterrupt:
        fs.interrupted = True
        log("evolution interrupted; saving champions")
        if fs.population and out_dir:
            log(f"top policies saved to {fs.save_top_policies(out_dir, k=5)}")
        if fs.best and out_dir:
            log(f"best policy saved to {fs.save_best_policy(out_dir)}")
        if checkpoint_path:
            fs.checkpoint(checkpoint_path)
        return fs
    finally:
        if profile:
            # the __total__ device_profile record: per-stage attribution
            # aggregate + the idle (unattributed) remainder of the run
            summ = profiler.summary(emit=True)
            log("device-time attribution: "
                f"{summ['attributed_fraction'] * 100:.1f}% of "
                f"{summ['measured_wall_seconds']:.2f}s wall attributed "
                f"({summ['compile_seconds']:.2f}s compile); see cli report")
            profiler.close()
    if checkpoint_path:
        fs.checkpoint(checkpoint_path)
    return fs
