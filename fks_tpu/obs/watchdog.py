"""Numerics watchdog, host side: flag reporting and the offline
divergence audit.

One failure model — a candidate policy (or an engine bug) produces a
score that is NaN, Inf, or outside the fitness range, and the search
silently ranks garbage:

1. **In-graph guards** live in ``fks_tpu.sim.guards``. They are
   mask-and-flag, not checkify: non-finite policy scores are masked to 0
   ("refuse placement") and a sticky ``i32`` bitmask rides the loop
   carry into ``SimResult.numeric_flags``. Gated on the Python-static
   ``SimConfig.watchdog`` flag, so the disabled path compiles to the
   identical program — zero cost when off.
2. **Host reporting**: ``check_result`` OR-reduces a result's flag
   mask (scalar or per-lane, ``sim.guards.combined_flags``) and emits a
   ``kind="watchdog"`` event on the flight recorder when any lane
   tripped.
3. **The parity sentinel** (``fks_tpu.funsearch.parity``) re-scores
   sampled candidates through the exact reference every generation. The
   offline per-trace divergence audit (``audit_trace``/``panel_sources``,
   formerly ``tools/divergence_audit.py``) lives here.
"""
from __future__ import annotations

import glob
import json
import os
import sys
import time
from typing import Dict, Iterable, List, Optional, Sequence

from fks_tpu.obs.recorder import get_recorder
from fks_tpu.sim.guards import combined_flags, describe_flags

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def check_result(result, recorder=None, **context) -> int:
    """Inspect ``result.numeric_flags`` (any ``SimResult``-shaped object;
    objects without the field read as clean) and emit a ``watchdog``
    event when any lane tripped. Returns the combined bitmask."""
    rec = recorder if recorder is not None else get_recorder()
    flags = getattr(result, "numeric_flags", None)
    if flags is None:
        return 0
    mask = combined_flags(flags)
    if mask:
        rec.event("watchdog", flags=mask, kinds=describe_flags(mask),
                  **context)
    return mask


# ---------------------------------------------------------------------------
# Offline divergence audit (folded in from tools/divergence_audit.py —
# the tool is now a thin wrapper over these functions).
# ---------------------------------------------------------------------------

def panel_sources(top_k: int = 3) -> Dict[str, str]:
    """Seed policies + the top-k discovered champion sources by score."""
    from fks_tpu.funsearch import template

    sources = dict(template.seed_policies())
    champs = []
    for path in glob.glob(os.path.join(REPO, "policies", "discovered",
                                       "funsearch_*_score*.json")):
        try:
            with open(path) as f:
                doc = json.load(f)
            champs.append((float(doc["score"]), os.path.basename(path),
                           doc["code"]))
        except (KeyError, ValueError, OSError, json.JSONDecodeError):
            continue  # skip-and-continue: one bad file must not end it
    champs.sort(reverse=True)
    for score, name, code in champs[:top_k]:
        sources[f"champion_{score:.4f}"] = code
    return sources


def audit_trace(pod_file: str, sources: Dict[str, str],
                cfg_kw: Optional[dict] = None) -> dict:
    """Run a policy panel through BOTH engines on one trace; one JSONL
    row: per-policy exact/flat scores, |d|, and retry-cascade marks."""
    import jax

    from fks_tpu.data import TraceParser
    from fks_tpu.funsearch import vm
    from fks_tpu.sim import flat
    from fks_tpu.sim import engine as exact
    from fks_tpu.sim.engine import SimConfig

    wl = TraceParser().parse_workload(pod_file=pod_file)
    n, g = wl.cluster.n_padded, wl.cluster.g_padded
    cfg = SimConfig(cond_policy=True, **(cfg_kw or {}))
    runs = {
        "exact": (jax.jit(exact.make_param_run_fn(wl, vm.score, cfg)),
                  exact.initial_state(wl, cfg)),
        "flat": (jax.jit(flat.make_param_run_fn(wl, vm.score, cfg)),
                 flat.initial_state(wl, cfg)),
    }
    per_policy = {}
    events = scheduled = 0
    for name, code in sources.items():
        try:
            prog = vm.compile_policy(code, n, g, capacity=512)
        except Exception as e:  # noqa: BLE001 — skip, keep the audit going
            per_policy[name] = {"skipped": f"{type(e).__name__}"}
            continue
        scores, trunc, ev = {}, {}, {}
        for eng, (run, s0) in runs.items():
            res = run(prog, s0)
            scores[eng] = float(res.policy_score)
            trunc[eng] = bool(res.truncated) or bool(res.failed)
            ev[eng] = int(res.events_processed)
            if eng == "exact":
                events = max(events, ev[eng])
                scheduled = max(scheduled, int(res.scheduled_pods))
        per_policy[name] = {
            "exact": round(scores["exact"], 6),
            "flat": round(scores["flat"], 6),
            "flat_events": ev["flat"],  # cascade magnitude is visible here
            "abs_d": round(abs(scores["exact"] - scores["flat"]), 6),
            # truncated-on-flat-only marks a RETRY CASCADE: the flat
            # retry-time rule re-queues enough extra creations to blow the
            # event budget, zeroing the score. Distinct from arithmetic
            # drift — conservative for search (the candidate is culled,
            # never over-promoted), but it under-ranks a true champion.
            "flat_cascade": trunc["flat"] and not trunc["exact"],
        }
    ds = [p["abs_d"] for p in per_policy.values() if "abs_d" in p]
    drift = [p["abs_d"] for p in per_policy.values()
             if "abs_d" in p and not p["flat_cascade"]]
    return {
        "trace": pod_file, "num_pods": wl.num_pods,
        "num_nodes": wl.num_nodes,
        "max_events_processed": events, "max_scheduled": scheduled,
        "max_abs_d": max(ds) if ds else None,
        "mean_abs_d": round(sum(ds) / len(ds), 6) if ds else None,
        "max_drift": max(drift) if drift else None,  # cascades excluded
        "flat_cascades": sum(p.get("flat_cascade", False)
                             for p in per_policy.values()),
        "policies": per_policy,
    }


def run_audit(out: str, traces: Optional[Iterable[str]] = None,
              top_champions: int = 3, log=print) -> List[dict]:
    """Audit every trace (default: all shipped pod CSVs), appending one
    JSONL row per trace to ``out``. Returns the rows."""
    from fks_tpu.data import TraceParser

    traces = list(traces) if traces else TraceParser().get_available_pod_files()
    sources = panel_sources(top_champions)
    log(f"panel: {list(sources)}")
    os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
    rows = []
    for pod_file in traces:
        t0 = time.time()
        try:
            row = audit_trace(pod_file, sources, {})
        except Exception as e:  # noqa: BLE001 — a bad trace must not end
            row = {"trace": pod_file, "error": f"{type(e).__name__}: {e}"}
        row["wall_s"] = round(time.time() - t0, 1)
        rows.append(row)
        with open(out, "a") as f:
            f.write(json.dumps({"ts": round(time.time(), 1), **row}) + "\n")
        log(f"{pod_file}: max|d|={row.get('max_abs_d')} "
            f"({row['wall_s']}s)")
    return rows


def format_audit_table(rows: Sequence[dict]) -> str:
    """The audit summary table (worst trace first)."""
    if not rows:
        return "(no traces audited)"
    width = max(len(r["trace"]) for r in rows)
    lines = [f"{'trace':<{width}}  {'pods':>6}  {'events':>7}  "
             f"{'max|d|':>8}  {'drift':>8}  {'cascades':>8}"]
    for r in sorted(rows, key=lambda r: -(r.get("max_abs_d") or 0)):
        if "error" in r:
            lines.append(f"{r['trace']:<{width}}  ERROR {r['error']}")
        else:
            lines.append(f"{r['trace']:<{width}}  {r['num_pods']:>6}  "
                         f"{r['max_events_processed']:>7}  "
                         f"{r['max_abs_d']:>8}  {r['max_drift']:>8}  "
                         f"{r['flat_cascades']:>8}")
    return "\n".join(lines)


def audit_main(argv=None) -> int:
    """CLI entry shared with ``tools/divergence_audit.py``."""
    import argparse

    ap = argparse.ArgumentParser(
        description="per-trace flat-vs-exact divergence audit")
    ap.add_argument("--out", default=os.path.join(
        REPO, "benchmarks", "results", "divergence_audit.jsonl"))
    ap.add_argument("--traces", default="",
                    help="comma-separated pod CSVs (default: all)")
    ap.add_argument("--top-champions", type=int, default=3)
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args(argv)

    import jax
    if args.cpu:
        jax.config.update("jax_platforms", "cpu")

    traces = args.traces.split(",") if args.traces else None
    rows = run_audit(args.out, traces, args.top_champions,
                     log=lambda m: print(m, file=sys.stderr))
    print(format_audit_table(rows))
    return 0
