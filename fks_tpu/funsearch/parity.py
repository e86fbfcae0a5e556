"""The online parity sentinel: the search's fitness against the exact
reference, sampled every generation.

``ParitySentinel`` re-scores ``k`` sampled candidates per generation
through the exact reference evaluator on the jit tier (``use_vm=False``)
and records |Δfitness| into the ledger. Drift above ``tol`` (default
1e-5) means the VM lowering, the transpiler, or a fast engine disagrees
with the reference replica — an ``alert`` event fires and the CLI exit
policy turns it into a nonzero exit. Serving (``audit_served``) and the
promotion controller's shadow stage use the same tolerance policy and
the same ``parity`` metric / ``alert`` event plumbing. The in-graph
guards are ``fks_tpu.sim.guards``; host reporting of their flags and the
offline per-trace divergence audit are ``fks_tpu.obs.watchdog``.
"""
from __future__ import annotations

import contextlib
import random
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax

from fks_tpu.funsearch import tracing
from fks_tpu.funsearch.backend import CodeEvaluator
from fks_tpu.obs.recorder import get_recorder


class ParitySentinel:
    """Online drift detector: per generation, re-score ``sample``
    candidates through the exact reference evaluator on the jit tier and
    compare against the fitness the search assigned them.

    The evolution loop already rescores CHAMPIONS through the exact
    engine's VM tier; the sentinel instead samples the broad population
    and goes through ``use_vm=False`` (direct transpile + jit), so it
    catches VM-lowering and transpiler drift that champion rescoring —
    which rides the same VM — cannot see. Results land in the run dir as
    ``kind="parity"`` metrics; drift above ``tol`` raises an ``alert``
    event and increments ``self.alerts`` (the CLI exit policy). An alert
    additionally replays the worst offender through
    ``fks_tpu.funsearch.tracing.candidate_trace_diff`` and attaches the
    first divergent scheduling step to the alert event — best-effort,
    never fatal to the search.

    NOTE on tolerance: the default 1e-5 assumes the search engine is
    ``exact`` (integer/deterministic — any drift is a real lowering
    bug). The flat engine's documented retry-rule divergence reaches
    |d| <= 0.029 on published policies, so flat-engine runs should pass
    a tolerance above their measured per-trace bound (see
    ``fks_tpu.obs.watchdog.audit_trace``).
    """

    def __init__(self, evaluator, sample: int = 0, tol: float = 1e-5,
                 seed: int = 0, recorder=None):
        self.evaluator = evaluator
        self.sample = int(sample)
        self.tol = float(tol)
        self.rng = random.Random(seed)
        self.recorder = recorder if recorder is not None else get_recorder()
        self.alerts = 0
        self.checked = 0
        self.max_drift = 0.0
        self._ref = None  # lazily-built jit-tier exact evaluator

    def _reference(self):
        if self._ref is None:
            # suite/robust ride along: a scenario-suite search's fitness is
            # the robust aggregate, so the reference must fold the same
            # scenarios or every check would alert on an apples-to-oranges
            # comparison
            self._ref = CodeEvaluator(
                self.evaluator.workload, self.evaluator.cfg,
                engine="exact", use_vm=False,
                suite=getattr(self.evaluator, "suite", None),
                robust=getattr(self.evaluator, "robust", None))
        return self._ref

    @staticmethod
    def _cpu_device():
        """Pin reference rescoring to the host CPU (same rationale as
        ``FunSearch._exact_device``: never compete with the search for
        the accelerator; the exact engine is backend-independent)."""
        try:
            dev = jax.devices("cpu")[0]
        except RuntimeError:
            return contextlib.nullcontext()
        return jax.default_device(dev)

    def check(self, generation: int,
              population: Sequence[Tuple[str, float]]) -> Dict[str, Any]:
        """Sample up to ``self.sample`` members of ``population``
        (``(code, fitness)`` pairs), re-score each through the reference
        evaluator, and record the drift. Returns the generation's parity
        stats (also written as a ``parity`` metric)."""
        stats = {"generation": int(generation), "checked": 0,
                 "max_drift": 0.0, "alerts": 0}
        if self.sample <= 0 or not population:
            return stats
        picks = self.rng.sample(list(population),
                                min(self.sample, len(population)))
        drifts: List[float] = []
        failed = 0
        worst: Optional[Tuple[float, str]] = None  # (drift, code)
        with self._cpu_device():
            ref = self._reference()
            for code, fitness in picks:
                try:
                    rec = ref.evaluate_one(code)
                except Exception:  # noqa: BLE001 — a sentinel failure
                    failed += 1     # must never take down the search
                    continue
                if not rec.ok:
                    failed += 1
                    continue
                d = abs(float(rec.score) - float(fitness))
                drifts.append(d)
                if worst is None or d > worst[0]:
                    worst = (d, code)
        self.checked += len(drifts)
        gen_max = max(drifts) if drifts else 0.0
        self.max_drift = max(self.max_drift, gen_max)
        stats.update(checked=len(drifts), max_drift=round(gen_max, 8),
                     failed=failed)
        self.recorder.metric("parity", {
            "generation": int(generation), "checked": len(drifts),
            "failed": failed, "max_drift": round(gen_max, 8),
            "tol": self.tol})
        if gen_max > self.tol:
            self.alerts += 1
            stats["alerts"] = 1
            alert_fields = dict(
                source="parity", generation=int(generation),
                max_drift=round(gen_max, 8), tol=self.tol,
                detail=f"fitness drift {gen_max:.3g} exceeds "
                       f"tolerance {self.tol:.3g}")
            if worst is not None:
                div = self._diff_offender(worst[1], generation)
                if div is not None:
                    # the alert arrives with its root cause attached: the
                    # first scheduling step where the offender's search
                    # evaluation departed from the exact/jit reference
                    alert_fields["first_divergence"] = \
                        div.get("first_divergence")
                    alert_fields["diff_engines"] = div.get("engines")
            self.recorder.event("alert", **alert_fields)
        return stats

    def check_champion(self, generation: int, records) -> Dict[str, Any]:
        """Budget-pruning champion audit (fks_tpu.funsearch.budget):
        pruning may never change which candidate wins a generation, only
        how cheaply. The pruned run's champion is by construction a
        full-rung survivor; the only way it can be WRONG is a pruned
        candidate whose full-fidelity score would have beaten it. Rescore
        every pruned candidate plus the champion through the unpruned
        exact reference and alert (``source="budget_champion"``, feeding
        the CLI exit-3 policy) when any pruned candidate's reference
        score exceeds the champion's by more than ``tol``. Bounded work:
        at most candidates-per-generation exact rescores, memoized by
        the reference's own compile cache. Runs regardless of
        ``self.sample`` — the budget opt-in is the gate."""
        stats = {"generation": int(generation), "checked": 0,
                 "max_gap": 0.0, "alerts": 0}
        pruned = [r for r in records
                  if getattr(r, "budget_rung", None) == 0 and r.ok]
        survivors = [r for r in records
                     if getattr(r, "budget_rung", None) == 1 and r.ok]
        if not pruned or not survivors:
            return stats
        champion = max(survivors, key=lambda r: r.score)
        failed = 0
        gaps: List[Tuple[float, str]] = []
        with self._cpu_device():
            ref = self._reference()
            try:
                champ_ref = float(ref.evaluate_one(champion.code).score)
            except Exception:  # noqa: BLE001 — sentinel failures must
                return stats   # never take down the search
            for r in pruned:
                try:
                    rec = ref.evaluate_one(r.code)
                except Exception:  # noqa: BLE001
                    failed += 1
                    continue
                if not rec.ok:
                    failed += 1
                    continue
                gaps.append((float(rec.score) - champ_ref, r.code))
        self.checked += len(gaps) + 1
        worst = max(gaps, key=lambda g: g[0]) if gaps else (0.0, "")
        gap = max(0.0, worst[0])
        stats.update(checked=len(gaps) + 1, max_gap=round(gap, 8),
                     failed=failed)
        self.recorder.metric("parity", {
            "generation": int(generation), "checked": len(gaps) + 1,
            "failed": failed, "max_drift": round(gap, 8),
            "tol": self.tol, "source": "budget_champion"})
        if gap > self.tol:
            self.alerts += 1
            self.max_drift = max(self.max_drift, gap)
            stats["alerts"] = 1
            self.recorder.event(
                "alert", source="budget_champion",
                generation=int(generation), max_drift=round(gap, 8),
                tol=self.tol,
                detail=f"budget pruning dropped a candidate whose exact "
                       f"reference score beats the pruned run's champion "
                       f"by {gap:.3g} (tol {self.tol:.3g})")
        return stats

    def audit_served(self, request_id: str, served_score: float,
                     reference_score: float, placements_match: bool = True,
                     source: str = "serve") -> bool:
        """Audit one SERVED answer (fks_tpu.serve) against the unbatched
        exact-engine reference the serving engine computed for the same
        query. No evaluator needed (``ParitySentinel(None, ...)`` works):
        both scores arrive precomputed; the sentinel contributes the
        tolerance policy, the drift bookkeeping, and the shared
        ``parity`` metric / ``alert`` event plumbing so serving drift
        lands in the same dashboards as search drift. Returns True when
        the answer passes."""
        d = abs(float(served_score) - float(reference_score))
        ok = d <= self.tol and bool(placements_match)
        self.checked += 1
        self.max_drift = max(self.max_drift, d)
        self.recorder.metric("parity", {
            "generation": -1, "checked": 1, "failed": 0,
            "max_drift": round(d, 8), "tol": self.tol, "source": source,
            "request_id": str(request_id),
            "placements_match": bool(placements_match)})
        if not ok:
            self.alerts += 1
            why = (f"fitness drift {d:.3g} exceeds tolerance "
                   f"{self.tol:.3g}" if d > self.tol
                   else "placements diverge from the exact reference")
            self.recorder.event(
                "alert", source="serve_parity",
                request_id=str(request_id), max_drift=round(d, 8),
                tol=self.tol, detail=f"served answer {request_id}: {why}")
        return ok

    def _diff_offender(self, code: str, generation: int) -> Optional[dict]:
        """Best-effort root-cause localization for an alert: trace-diff
        the worst offender's search-tier evaluation against the exact
        reference (fks_tpu.funsearch.tracing.candidate_trace_diff). Never
        raises — the sentinel must not take down the search."""
        try:
            with self._cpu_device():
                return tracing.candidate_trace_diff(
                    self.evaluator, code, recorder=self.recorder,
                    label=f"parity_alert_gen{int(generation)}")
        except Exception as e:  # noqa: BLE001
            self.recorder.event("probe_failure", attempt="trace_diff",
                                error=f"{type(e).__name__}: {e}")
            return None
