"""Decision-trace extraction, alignment, and first-divergence localization.

The engines' ``SimConfig.decision_trace`` instrument (fks_tpu.sim.types
``TraceBuffer``) logs one row per processed event inside the jitted step:
event kind, pod, chosen node, winning score + second-best margin, pending
count, and post-step free aggregates. This module is the host-side half:

- ``extract_trace``  — TraceBuffer / SimResult -> list of row dicts
- ``align_traces``   — first divergent row between two extracted traces
- ``replay``         — re-run one engine with tracing forced on
- ``trace_diff``     — replay two (engine, policy) specs on the same
                       workload, align, record ``decision_trace`` +
                       ``trace_diff`` events into the run dir
- ``format_diff``    — human-readable table for ``cli trace-diff``
- ``candidate_trace_diff`` — the ParitySentinel hook: localize WHERE a
                       drifting candidate's search-tier evaluation first
                       departs from the exact/jit reference

Why step alignment instead of final-fitness comparison: the parity
sentinel and ``tools/divergence_audit`` say THAT two engines drifted;
replaying with traces says WHICH scheduling decision diverged first —
any later divergence is downstream snowball (the flat engine's documented
retry-rule delta works exactly like this), so only the first row is
root cause.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from fks_tpu.obs.recorder import get_recorder
from fks_tpu.sim.engine import SimConfig
from fks_tpu.sim.types import TRACE_KIND_NAMES, TraceBuffer

#: row fields compared exactly / within score_tol by align_traces
_EXACT_FIELDS = ("kind", "pod", "node", "pending",
                 "free_cpu", "free_mem", "free_gpu", "free_gpu_milli")
_SCORE_FIELDS = ("score", "margin")


def extract_trace(result_or_buffer) -> List[Dict[str, Any]]:
    """Written rows of a decision trace as a list of plain dicts (one per
    processed event, in step order). Accepts a ``SimResult`` (or any object
    with a ``.trace``) or a ``TraceBuffer`` directly."""
    buf = getattr(result_or_buffer, "trace", result_or_buffer)
    if buf is None:
        raise ValueError(
            "no decision trace recorded — run with SimConfig(decision_trace"
            "=True) (the fused kernel does not support tracing)")
    if not isinstance(buf, TraceBuffer):
        buf = TraceBuffer(*buf)
    data = np.asarray(buf.data)
    scores = np.asarray(buf.scores)
    if data.ndim != 2:
        raise ValueError(
            f"batched trace (data shape {data.shape}); index one lane first")
    count = int(np.asarray(buf.count))
    rows = []
    for i in range(min(count, data.shape[0])):
        d = data[i]
        rows.append({
            "step": i,
            "kind": TRACE_KIND_NAMES[int(d[TraceBuffer.COL_KIND])],
            "pod": int(d[TraceBuffer.COL_POD]),
            "node": int(d[TraceBuffer.COL_NODE]),
            "pending": int(d[TraceBuffer.COL_PENDING]),
            "free_cpu": int(d[TraceBuffer.COL_FREE_CPU]),
            "free_mem": int(d[TraceBuffer.COL_FREE_MEM]),
            "free_gpu": int(d[TraceBuffer.COL_FREE_GPU]),
            "free_gpu_milli": int(d[TraceBuffer.COL_FREE_GPU_MILLI]),
            "score": float(scores[i, 0]),
            "margin": float(scores[i, 1]),
        })
    return rows


def align_traces(a: Sequence[Dict[str, Any]], b: Sequence[Dict[str, Any]],
                 score_tol: float = 1e-5) -> Optional[Dict[str, Any]]:
    """First divergent step between two extracted traces, or None when they
    agree. Integer fields compare exactly; score/margin within
    ``score_tol``. A strict-prefix match diverges at the first missing row
    (field "length", the shorter side's row None)."""
    for i in range(min(len(a), len(b))):
        ra, rb = a[i], b[i]
        for field in _EXACT_FIELDS:
            if ra[field] != rb[field]:
                return {"step": i, "field": field, "a": ra, "b": rb}
        for field in _SCORE_FIELDS:
            if abs(ra[field] - rb[field]) > score_tol:
                return {"step": i, "field": field, "a": ra, "b": rb}
    if len(a) != len(b):
        i = min(len(a), len(b))
        return {"step": i, "field": "length",
                "a": a[i] if i < len(a) else None,
                "b": b[i] if i < len(b) else None}
    return None


def replay(workload, engine: str, param_policy, params,
           cfg: SimConfig = SimConfig()):
    """Re-run ``engine`` ("exact" | "flat") on ``workload`` with the
    decision trace forced on; returns the SimResult (``.trace`` set)."""
    import jax

    from fks_tpu.sim import get_engine

    cfg = dataclasses.replace(cfg, decision_trace=True)
    mod = get_engine(engine)  # rejects "fused" with an explanation
    run = jax.jit(mod.make_param_run_fn(workload, param_policy, cfg))
    return run(params, mod.initial_state(workload, cfg))


def trace_diff(workload, specs, cfg: Optional[SimConfig] = None,
               score_tol: float = 1e-5, recorder=None, label: str = "",
               max_trace_events: int = 64) -> Dict[str, Any]:
    """Replay exactly two ``(name, engine, param_policy, params)`` specs on
    the same workload, align their decision logs, and return the
    ``trace_diff`` record (also written to the active run dir, alongside
    one bounded ``decision_trace`` event per engine)."""
    if len(specs) != 2:
        raise ValueError(f"trace_diff compares exactly 2 specs, got {len(specs)}")
    if cfg is None:
        # cond_policy: replays are single-lane, where skipping the policy
        # on deletes is both the fast path and the sentinel's config
        cfg = SimConfig(cond_policy=True)
    rec = recorder if recorder is not None else get_recorder()
    names, traces, scores = [], [], {}
    for name, engine, param_policy, params in specs:
        res = replay(workload, engine, param_policy, params, cfg)
        rows = extract_trace(res)
        names.append(name)
        traces.append(rows)
        scores[name] = float(np.asarray(res.policy_score))
        rec.event("decision_trace", engine=name, label=label,
                  steps=len(rows), events=rows[:max_trace_events])
    div = align_traces(traces[0], traces[1], score_tol=score_tol)
    record = {
        "engines": names,
        "label": label,
        "steps": {names[0]: len(traces[0]), names[1]: len(traces[1])},
        "scores": scores,
        "score_tol": score_tol,
        "divergent": div is not None,
        "first_divergence": div,
    }
    rec.event("trace_diff", **record)
    return record


def format_diff(record: Dict[str, Any]) -> str:
    """Human-readable rendering of a ``trace_diff`` record."""
    na, nb = record["engines"]
    lines = [f"trace-diff: {na} vs {nb}"
             + (f"  [{record['label']}]" if record.get("label") else "")]
    for n in (na, nb):
        lines.append(f"  {n}: {record['steps'][n]} steps, "
                     f"fitness {record['scores'][n]:.6f}")
    div = record.get("first_divergence")
    if div is None:
        steps = record["steps"][na]
        lines.append(f"  no divergence ({steps} steps compared)")
        return "\n".join(lines)
    lines.append(f"  FIRST DIVERGENCE at step {div['step']} "
                 f"(field: {div['field']})")
    hdr = f"    {'engine':<24} {'kind':<7} {'pod':>4} {'node':>4} " \
          f"{'score':>12} {'margin':>12} {'pending':>7}"
    lines.append(hdr)
    for n, row in ((na, div.get("a")), (nb, div.get("b"))):
        if row is None:
            lines.append(f"    {n:<24} <trace ended>")
            continue
        lines.append(
            f"    {n:<24} {row['kind']:<7} {row['pod']:>4} {row['node']:>4} "
            f"{row['score']:>12.6f} {row['margin']:>12.6f} "
            f"{row['pending']:>7}")
    return "\n".join(lines)


def policy_params(workload, policy_name: str = "", code: str = "",
                  capacity: int = 512) -> Tuple[Any, Any]:
    """(param_policy, params) for ``cli trace-diff``: candidate source
    ``code`` runs on the funsearch VM; otherwise ``policy_name`` picks a
    zoo policy (params None)."""
    if code:
        from fks_tpu.funsearch import vm
        return vm.score, vm.compile_for_workload(code, workload,
                                                 capacity=capacity)
    from fks_tpu.models import zoo
    if policy_name not in zoo.ZOO:
        raise ValueError(f"unknown policy {policy_name!r}; "
                         f"available: {', '.join(sorted(zoo.ZOO))}")
    pol = zoo.ZOO[policy_name]()
    return (lambda _p, pod, nodes: pol(pod, nodes)), None


def candidate_trace_diff(evaluator, code: str, recorder=None,
                         score_tol: float = 1e-5,
                         label: str = "") -> Dict[str, Any]:
    """Trace-diff a candidate's SEARCH-tier evaluation (the evaluator's
    engine + VM program when eligible) against the exact/jit reference —
    the same two numbers the ParitySentinel compares, so the returned
    first divergence is the root-cause step of a parity alert."""
    from fks_tpu.funsearch import transpiler, vm

    wl = evaluator.workload
    cfg = dataclasses.replace(evaluator.cfg, cond_policy=True)
    engine = evaluator.engine if evaluator.engine in ("exact", "flat") else "flat"
    policy = transpiler.transpile(code)

    def jit_policy(_p, pod, nodes):
        return policy(pod, nodes)

    search_policy, search_params, search_tier = jit_policy, None, "jit"
    if getattr(evaluator, "use_vm", True):
        try:
            search_params = vm.compile_for_workload(code, wl)
            search_policy, search_tier = vm.score, "vm"
        except Exception:  # noqa: BLE001 — VM-ineligible -> jit tier
            pass
    specs = [
        (f"search:{engine}/{search_tier}", engine, search_policy, search_params),
        ("reference:exact/jit", "exact", jit_policy, None),
    ]
    return trace_diff(wl, specs, cfg=cfg, score_tol=score_tol,
                      recorder=recorder, label=label)
