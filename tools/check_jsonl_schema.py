"""JSONL schema checker for flight-recorder run dirs and results files.

Two jobs, one helper:

- ``check_jsonl(path, required=...)`` — every line must parse as a JSON
  object carrying the required keys. A torn FINAL line (a writer killed
  mid-append) is tolerated by default, matching ``obs.report.read_jsonl``;
  a torn line anywhere else is corruption and fails.
- ``check_run_dir(run_dir)`` — validate a ``fks_tpu.obs.FlightRecorder``
  directory: ``meta.json`` (run_id/started/status), ``events.jsonl`` and
  ``metrics.jsonl`` (ts/kind per line), ``heartbeat`` when present.

Beyond the generic ts/kind floor, records of KNOWN kinds (the watchdog /
alert / parity / probe_failure vocabulary added with the numerics
watchdog, plus the evolution ledger's generation records, plus the
``decision_trace``/``trace_diff`` records from fks_tpu.funsearch.tracing —
whose embedded trace rows must carry a known CREATE/DELETE/RETRY/
NODE_DOWN/NODE_UP event kind, and the scenario-suite records from
fks_tpu.scenarios) are checked for their kind-specific required keys — a watchdog
event without a flag mask is as corrupt as a line without a timestamp.

``check_openmetrics(text)`` validates the ``cli export-metrics`` output:
every exposition line is a comment, a ``# TYPE``/``# HELP`` header, or a
``name{labels} value`` sample whose family was declared first, and the
exposition ends with ``# EOF``.

Usage:
    python tools/check_jsonl_schema.py --run-dir runs/evolve1
    python tools/check_jsonl_schema.py --openmetrics metrics.prom
    python tools/check_jsonl_schema.py benchmarks/results/divergence_audit.jsonl

The last form checks arbitrary JSONL evidence files (they have no
fixed keys, so they are checked for parseability only unless --require
is given). Exit code 0 = clean, 1 = violations (printed one per line).
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys
from typing import Dict, List, Sequence, Tuple

#: per-line required keys for the recorder's JSONL surfaces
RUN_DIR_REQUIRED: Dict[str, Tuple[str, ...]] = {
    "events.jsonl": ("ts", "kind"),
    "metrics.jsonl": ("ts", "kind"),
}
#: required keys in a run dir's meta.json
META_REQUIRED: Tuple[str, ...] = ("run_id", "started", "status")

#: kind-specific required keys, per surface. Unknown kinds pass (the
#: recorder is an open vocabulary); known kinds must be well-formed.
EVENT_KIND_REQUIRED: Dict[str, Tuple[str, ...]] = {
    "watchdog": ("flags", "kinds"),
    "alert": ("source",),
    "probe_failure": ("attempt",),
    "span": ("seconds",),
    "compile": ("seconds",),
    "decision_trace": ("engine", "events"),
    "trace_diff": ("engines", "divergent"),
    # static pre-flight analyzer (fks_tpu.analysis): one event per
    # candidate rejected before the sandbox/transpile/compile pipeline —
    # the taxonomy label is machine-readable and closed-vocabulary
    "candidate_rejected": ("taxonomy", "stage"),
    # promotion pipeline (fks_tpu.pipeline): a post-promotion SLO burn
    # swapped the last-good engine back
    "rollback": ("attempt", "reason"),
    # evolve circuit breaker: N consecutive all-failed-LLM generations
    # tripped the loop (cli evolve exits 4 after checkpointing)
    "llm_outage": ("generation", "consecutive"),
    # resilience layer (fks_tpu.resilience): admission control refused a
    # request (reason: queue_full / deadline_budget / draining)
    "shed": ("reason",),
    # degraded-mode state machine transition (state: degraded /
    # probation / normal / dead)
    "degraded": ("fault", "state"),
    # SIGTERM drain completed: every in-flight Future completed or shed
    "drain": ("pending",),
    # evolve WAL replay: a resumed generation reused persisted
    # candidates/evals instead of re-spending LLM calls / device evals
    "resume_wal": ("generation",),
    # VM-native serving (fks_tpu.serve.vm_engine + cli serve /
    # promotion controller): one event per champion table hot-swap
    # (outcome="swapped") or per AOT fallback when a champion is outside
    # the VM vocabulary (outcome="fallback")
    "vm_swap": ("outcome", "champion"),
    # portfolio serving (fks_tpu.portfolio.engine): one event per slot
    # promotion in the shared slot-vmapped executable — which slot's
    # tables were re-uploaded (always outcome="swapped"; a champion
    # outside the VM vocabulary never reaches a slot)
    "slot_swap": ("slot", "outcome", "champion"),
    # causal tracing (fks_tpu.obs.trace_ctx): one span of a request /
    # generation / promotion trace. parent_id is intentionally NOT
    # required: the root span carries an explicit JSON null there, and
    # key-presence is what this checker tests
    "trace_span": ("trace_id", "span_id", "path", "seconds"),
}

#: legal ``taxonomy`` values on a candidate_rejected event. This tool is
#: stdlib-only by design, so the vocabulary is duplicated from
#: fks_tpu.analysis.REJECT_TAXONOMY; tests/test_analysis.py pins the two
#: copies against each other.
CANDIDATE_REJECT_TAXONOMY = {
    "syntax", "forbidden_construct", "bad_signature", "unsupported_syntax",
    "unsupported_call", "bad_arity", "unknown_attribute", "loop_too_long",
    "duplicate_fingerprint",
}

#: legal event kinds inside an embedded decision-trace row (must match
#: fks_tpu.sim.types.TRACE_KIND_NAMES)
TRACE_EVENT_KINDS = {"CREATE", "DELETE", "RETRY", "NODE_DOWN", "NODE_UP"}

#: legal ``outcome`` values on a vm_swap event, and legal ``engine_kind``
#: values wherever the field appears (promotion_event / vm_swap /
#: serve meta) — which champion-binding strategy served the swap
VM_SWAP_OUTCOMES = {"swapped", "fallback"}
ENGINE_KINDS = {"aot", "vm"}

#: legal ``mode`` values on a loadgen_summary record (duplicated from
#: fks_tpu.obs.workload.LOADGEN_MODES; tests/test_workload.py pins the
#: two copies) — the arrival process that produced the numbers
LOADGEN_MODES = {"open", "closed", "mixed"}
#: legal ``reason`` values on a portfolio_route metric (duplicated from
#: fks_tpu.portfolio.router.ROUTE_REASONS; tests/test_portfolio.py pins
#: the two copies) — which routing rule placed the request
ROUTE_REASONS = {"pin", "affinity", "ab", "default", "fallback", "query"}
METRIC_KIND_REQUIRED: Dict[str, Tuple[str, ...]] = {
    "generation": ("generation", "best_score"),
    "parity": ("generation", "checked", "max_drift"),
    # scenario-suite vocabulary (fks_tpu.scenarios): the materialized
    # suite summary (cli scenarios --run-dir) and the per-generation
    # robust-fitness breakdown the evolution loop records
    "scenario_suite": ("suite", "version", "scenarios"),
    "robust_fitness": ("generation", "suite", "aggregation", "scores"),
    # eval-budget allocation (fks_tpu.funsearch.budget): one record per
    # rung per generation — who entered, who survived to the next rung,
    # and what the rung cost in device wall seconds
    "budget_rung": ("generation", "rung", "entered", "survived",
                    "device_seconds"),
    # large-cluster scale tier (cli scale): the
    # completion-run throughput record must say what shape ran and which
    # scale knobs (prefilter / packed dtypes) produced the number
    "scale_tier": ("nodes", "pods", "events_per_sec",
                   "node_prefilter_k", "state_pack"),
    # champion serving (fks_tpu.serve): one record per served request —
    # what it cost (latency), how well the coalescer packed the batch
    # (occupancy), and which compiled shape bucket answered it
    "serve_request": ("request_id", "latency_ms", "batch_size",
                      "batch_occupancy", "bucket_pods", "bucket_lanes"),
    # repo lint gate (cli lint --run-dir): the AST findings + jaxpr-pin
    # drift messages and the overall verdict
    "lint_report": ("paths", "findings", "pin_drift", "ok"),
    # device-time attribution (fks_tpu.obs.profiler): one record per
    # completed stage (wall/compile/compute split, occupancy) plus the
    # stage="__total__" aggregate with the attributed fraction
    "device_profile": ("stage", "wall_seconds"),
    # cross-run history (cli trends): per-metric timeline + robust-z
    # regression alerts over the bench-results archive
    "trend_report": ("metric", "runs", "alerts"),
    # serve-tier SLO pricing (fks_tpu.serve.accounting.slo_burn): one record
    # per objective; burn_rate > 1 means the error budget is burning
    "slo_burn": ("slo", "target", "observed", "burn_rate"),
    # promotion pipeline (fks_tpu.pipeline.state): one record per
    # state-machine transition in promotion.jsonl, mirrored to the
    # flight recorder so a run dir tells the whole promotion story
    "promotion_event": ("attempt", "state", "champion"),
    # device-resident snapshot cache (fks_tpu.serve.artifact): ktable
    # reuse vs upload economics of the (sharded) serve path — the
    # exporter renders these as fks_serve_snapshot_cache_* gauges
    "snapshot_cache": ("hits", "misses", "entries", "hit_rate",
                       "h2d_bytes_per_query"),
    # workload fingerprinting (fks_tpu.serve.accounting): the windowed
    # distribution of query classes the serve path observed
    "workload_mix": ("window", "distinct", "classes"),
    # per-tenant accounting (fks_tpu.serve.accounting): one row per
    # tenant — counters, latency, goodput, SLO burn, global fairness index
    "tenant_stats": ("tenant", "requests", "shed", "expired", "ewma_ms",
                     "p99_ms", "goodput_qps", "burn_rate",
                     "fairness_index"),
    # load generator (fks_tpu.obs.workload.run_loadgen): the sustained
    # multi-tenant run summary carrying the four compare-gated keys
    "loadgen_summary": ("mode", "requests", "loadgen_qps",
                        "loadgen_p99_ms", "loadgen_shed_rate",
                        "loadgen_fairness_index"),
    # portfolio routing (fks_tpu.portfolio.service): one row per routed
    # request — which slot answered it and which rule decided (slot -1
    # means the AOT coverage-fallback engine served it)
    "portfolio_route": ("request_id", "tenant", "slot", "reason"),
}

#: an OpenMetrics sample line: name, optional {labels}, value, optional
#: ts, optional exemplar (`# {labels} value [ts]` — carried on histogram
#: buckets by the exporter's latency family to link hot buckets back to
#: a trace id)
_LABELSET = (r'\{[a-zA-Z_][a-zA-Z0-9_]*="(\\.|[^"\\])*"'
             r'(,[a-zA-Z_][a-zA-Z0-9_]*="(\\.|[^"\\])*")*\}')
_SAMPLE_RE = re.compile(
    r'^[a-zA-Z_:][a-zA-Z0-9_:]*'                 # metric name
    rf'({_LABELSET})?'                           # labels
    r' -?[0-9.eE+-]+( [0-9.eE+-]+)?'             # value, optional ts
    rf'( # {_LABELSET} -?[0-9.eE+-]+( [0-9.eE+-]+)?)?$')  # exemplar


class SchemaError(ValueError):
    """A JSONL file violated the schema; ``str(e)`` says where and why."""


def check_jsonl(path: str, required: Sequence[str] = (),
                allow_empty: bool = True,
                tolerate_torn_tail: bool = True) -> List[dict]:
    """Parse ``path`` line by line, requiring each record to be a JSON
    object with every key in ``required``. Returns the parsed records.
    Raises ``SchemaError`` on the first violation (with line number)."""
    try:
        with open(path) as f:
            lines = f.readlines()
    except OSError as e:
        raise SchemaError(f"{path}: unreadable ({e})") from e
    if not lines and not allow_empty:
        raise SchemaError(f"{path}: empty")
    records: List[dict] = []
    last = len(lines) - 1
    for i, line in enumerate(lines):
        if not line.strip():
            if i == last:
                continue  # trailing newline
            raise SchemaError(f"{path}:{i + 1}: blank line mid-file")
        try:
            rec = json.loads(line)
        except json.JSONDecodeError as e:
            if i == last and tolerate_torn_tail:
                break  # writer killed mid-append; the prefix is valid
            raise SchemaError(f"{path}:{i + 1}: unparsable ({e})") from e
        if not isinstance(rec, dict):
            raise SchemaError(f"{path}:{i + 1}: not a JSON object "
                              f"({type(rec).__name__})")
        missing = [k for k in required if k not in rec]
        if missing:
            raise SchemaError(f"{path}:{i + 1}: missing {missing} "
                              f"(has {sorted(rec)[:8]})")
        records.append(rec)
    return records


def check_kinds(path: str, records: List[dict],
                kind_required: Dict[str, Tuple[str, ...]]) -> None:
    """Per-kind key validation over parsed records: every record whose
    ``kind`` is in the known vocabulary must carry that kind's required
    keys. Raises ``SchemaError`` naming the record index."""
    for i, rec in enumerate(records):
        # engine_kind is optional everywhere it appears (promotion_event,
        # vm_swap, serve summaries), but when present it must name a real
        # champion-binding strategy
        if "engine_kind" in rec and rec["engine_kind"] not in ENGINE_KINDS:
            raise SchemaError(
                f"{path}: record {i + 1}: unknown engine_kind "
                f"{rec['engine_kind']!r} (expect one of "
                f"{sorted(ENGINE_KINDS)})")
        required = kind_required.get(rec.get("kind", ""))
        if not required:
            continue
        missing = [k for k in required if k not in rec]
        if missing:
            raise SchemaError(
                f"{path}: record {i + 1} (kind={rec.get('kind')!r}): "
                f"missing {missing}")
        if rec.get("kind") == "candidate_rejected":
            tax = rec.get("taxonomy")
            if tax not in CANDIDATE_REJECT_TAXONOMY:
                raise SchemaError(
                    f"{path}: record {i + 1}: unknown rejection taxonomy "
                    f"{tax!r} (expect one of "
                    f"{sorted(CANDIDATE_REJECT_TAXONOMY)})")
        elif rec.get("kind") in ("vm_swap", "slot_swap"):
            out = rec.get("outcome")
            if out not in VM_SWAP_OUTCOMES:
                raise SchemaError(
                    f"{path}: record {i + 1}: unknown {rec['kind']} "
                    f"outcome {out!r} (expect one of "
                    f"{sorted(VM_SWAP_OUTCOMES)})")
        elif rec.get("kind") == "portfolio_route":
            reason = rec.get("reason")
            if reason not in ROUTE_REASONS:
                raise SchemaError(
                    f"{path}: record {i + 1}: unknown route reason "
                    f"{reason!r} (expect one of {sorted(ROUTE_REASONS)})")
        elif rec.get("kind") == "loadgen_summary":
            mode = rec.get("mode")
            if mode not in LOADGEN_MODES:
                raise SchemaError(
                    f"{path}: record {i + 1}: unknown loadgen mode "
                    f"{mode!r} (expect one of {sorted(LOADGEN_MODES)})")
        elif rec.get("kind") == "decision_trace":
            _check_embedded_events(path, i, rec.get("events", []))
        elif rec.get("kind") == "trace_diff":
            div = rec.get("first_divergence") or {}
            _check_embedded_events(
                path, i, [r for r in (div.get("a"), div.get("b")) if r])


def _check_embedded_events(path: str, idx: int, rows) -> None:
    """Decision-trace rows embedded in a record must be dicts whose
    ``kind`` is in the engine's event vocabulary — an unknown kind means
    a corrupt trace (or a vocabulary drift between writer and checker)."""
    if not isinstance(rows, (list, tuple)):
        raise SchemaError(
            f"{path}: record {idx + 1}: embedded events not a list "
            f"({type(rows).__name__})")
    for j, row in enumerate(rows):
        if not isinstance(row, dict):
            raise SchemaError(
                f"{path}: record {idx + 1}: trace row {j + 1} not an "
                f"object ({type(row).__name__})")
        if row.get("kind") not in TRACE_EVENT_KINDS:
            raise SchemaError(
                f"{path}: record {idx + 1}: trace row {j + 1} has unknown "
                f"event kind {row.get('kind')!r} "
                f"(expect one of {sorted(TRACE_EVENT_KINDS)})")


def check_openmetrics(text: str, path: str = "<openmetrics>") -> int:
    """Validate OpenMetrics text exposition (``cli export-metrics``):
    declared-before-sampled families, well-formed sample lines, terminal
    ``# EOF``. Returns the sample count; raises ``SchemaError``."""
    lines = text.splitlines()
    stripped = [ln for ln in lines if ln.strip()]
    if not stripped or stripped[-1] != "# EOF":
        raise SchemaError(f"{path}: missing terminal '# EOF'")
    declared = set()
    samples = 0
    for i, line in enumerate(lines, 1):
        if not line.strip() or line == "# EOF":
            continue
        if line.startswith("# TYPE ") or line.startswith("# HELP "):
            parts = line.split(None, 3)
            if len(parts) < 4:
                raise SchemaError(f"{path}:{i}: malformed header {line!r}")
            declared.add(parts[2])
            continue
        if line.startswith("#"):
            continue  # free-form comment
        if not _SAMPLE_RE.match(line):
            raise SchemaError(f"{path}:{i}: malformed sample {line!r}")
        name = re.split(r"[{ ]", line, 1)[0]
        # suffixed samples (_total, _bucket, ...) belong to the base family
        base = {name} | {name[: -len(sfx)]
                         for sfx in ("_total", "_sum", "_count", "_bucket")
                         if name.endswith(sfx)}
        if not (base & declared):
            raise SchemaError(f"{path}:{i}: sample for undeclared family "
                              f"{name!r} (no preceding # TYPE)")
        samples += 1
    if samples == 0:
        raise SchemaError(f"{path}: no samples")
    return samples


def check_run_dir(run_dir: str) -> Dict[str, int]:
    """Validate a FlightRecorder run directory; returns per-file record
    counts. Raises ``SchemaError`` on the first violation."""
    meta_path = os.path.join(run_dir, "meta.json")
    try:
        with open(meta_path) as f:
            meta = json.load(f)
    except OSError as e:
        raise SchemaError(f"{meta_path}: unreadable ({e})") from e
    except json.JSONDecodeError as e:
        raise SchemaError(f"{meta_path}: unparsable ({e})") from e
    missing = [k for k in META_REQUIRED if k not in meta]
    if missing:
        raise SchemaError(f"{meta_path}: missing {missing}")
    counts = {"meta.json": 1}
    for name, required in RUN_DIR_REQUIRED.items():
        path = os.path.join(run_dir, name)
        if not os.path.exists(path):
            counts[name] = 0  # a run may legitimately record no metrics
            continue
        records = check_jsonl(path, required=required)
        check_kinds(path, records,
                    EVENT_KIND_REQUIRED if name == "events.jsonl"
                    else METRIC_KIND_REQUIRED)
        counts[name] = len(records)
    hb = os.path.join(run_dir, "heartbeat")
    if os.path.exists(hb):
        try:
            with open(hb) as f:
                beat = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            raise SchemaError(f"{hb}: unparsable ({e})") from e
        if "ts" not in beat:
            raise SchemaError(f"{hb}: missing ['ts']")
        counts["heartbeat"] = 1
    return counts


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("paths", nargs="*",
                    help="JSONL files to check (e.g. benchmarks/results/"
                         "divergence_audit.jsonl)")
    ap.add_argument("--run-dir", default="",
                    help="validate a flight-recorder run directory instead")
    ap.add_argument("--require", default="",
                    help="comma-separated keys every record must carry")
    ap.add_argument("--openmetrics", default="",
                    help="validate an OpenMetrics text file "
                         "(cli export-metrics output)")
    args = ap.parse_args(argv)
    if not args.run_dir and not args.paths and not args.openmetrics:
        ap.error("give JSONL paths, --run-dir, or --openmetrics")
    required = [k for k in args.require.split(",") if k]
    rc = 0
    if args.run_dir:
        try:
            counts = check_run_dir(args.run_dir)
            print(f"{args.run_dir}: ok "
                  + " ".join(f"{k}={v}" for k, v in sorted(counts.items())))
        except SchemaError as e:
            print(f"SCHEMA: {e}", file=sys.stderr)
            rc = 1
    if args.openmetrics:
        try:
            with open(args.openmetrics) as f:
                n = check_openmetrics(f.read(), args.openmetrics)
            print(f"{args.openmetrics}: ok ({n} samples)")
        except OSError as e:
            print(f"SCHEMA: {args.openmetrics}: unreadable ({e})",
                  file=sys.stderr)
            rc = 1
        except SchemaError as e:
            print(f"SCHEMA: {e}", file=sys.stderr)
            rc = 1
    for path in args.paths:
        try:
            records = check_jsonl(path, required=required)
            print(f"{path}: ok ({len(records)} records)")
        except SchemaError as e:
            print(f"SCHEMA: {e}", file=sys.stderr)
            rc = 1
    return rc


if __name__ == "__main__":
    sys.exit(main())
