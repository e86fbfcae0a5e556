"""fks_tpu.obs — the flight recorder: run directories, spans, compile/
device telemetry, the per-generation evolution ledger, and the
watchdog / export / gating layer built on top of them.

Every ROADMAP evidence gap is an observability gap; this package records
what a run actually did, into a run directory that ``cli report`` renders
back without any in-process state (fks_tpu.obs.report). The disabled path
is a shared NullRecorder — zero filesystem writes, no conditionals in
jitted code.

- ``recorder``  — FlightRecorder/NullRecorder + the process-wide active
                  recorder (``get_recorder``/``recording``)
- ``spans``     — THE timing mechanism: ``span(name)`` records name,
                  ``t0``/``t1`` (perf_counter), own / parent / trace id,
                  thread and a few fields into one process-wide bounded
                  ring (``spans.LOG``, 65,536 records, counts its drops),
                  enters ``TraceAnnotation("fks/<name>")`` for the
                  profiler's timeline, and mirrors into an open run
                  directory. Always on: two clock reads, one append, no
                  fence, no file, no lock. The span names (``serve/...``,
                  ``tier/...``, ``mesh/...``) are listed in its docstring.
                  Two prefixes are the ring's own, never opened by a call
                  site: ``host/gc`` (the collector's pauses, from the
                  ``gc.callbacks`` entry installed with the ring) and
                  ``obs/slow_root`` (a ``tier/evaluate`` or ``serve/batch``
                  root 1.25 times the median of its like: kept with its
                  stage sums in ``spans.SLOW`` / ``spans.slow_roots()``,
                  counted, and said in ONE warning line that names the
                  span that grew); ``span.child`` writes a child whose
                  stamps another process of the host took (the lowering
                  workers' ``tier/transpile/lower``)
- ``trace_ctx`` — causal trace contexts (trace_id/span_id/parent_id)
                  propagated explicitly across thread boundaries, spans
                  known after the fact (``emit``, into the same ring),
                  plus waterfall/critical-path reconstruction
                  (``cli spans``)
- ``telemetry`` — jax.monitoring compile listener, device memory_stats,
                  mesh/pad-waste snapshots
- ``ledger``    — per-generation evolution records
- ``report``    — run-dir summary rendering (``cli report``)
- ``watchdog``  — numeric guards (re-exported from sim.guards), host
                  reporting, the online parity sentinel, and the offline
                  divergence audit (``cli``/tools entry points)
- ``tracing``   — decision-trace extraction + first-divergence
                  localization across engines (``cli trace-diff``)
- ``exporter``  — OpenMetrics text export + heartbeat liveness
                  (``cli export-metrics`` / ``cli watch``)
- ``compare``   — cross-run regression gating (``cli compare``)
- ``profiler``  — per-stage device-time attribution, a view of the
                  spans: each stage opens one span and adds the compile
                  split + occupancy (``device_profile`` metrics); enabled,
                  it is what fences
- ``history``   — cross-run index, trend/regression flagging, auto
                  baselines, SLO burn rates (``cli trends``)
- ``memory``    — executable-footprint ledger, watermark sampler, leak
                  sentinel + drills (``cli mem``, ``fks_mem_*`` gauges)
- ``workload``  — query fingerprinting, per-tenant accounting with SLO
                  burn + fairness, and the multi-tenant load generator
                  (``cli loadgen``, ``fks_tenant_*`` gauges)
"""
from fks_tpu.obs.compare import (
    DEFAULT_THRESHOLDS, Threshold, compare_runs, extract_metrics,
    format_comparison, has_regression, parse_threshold_overrides,
)
from fks_tpu.obs.exporter import (
    health_line, run_health, to_openmetrics, watch,
)
from fks_tpu.obs.history import (
    RunHistory, SLOConfig, record_slo_burn, resolve_auto_baseline, slo_burn,
)
from fks_tpu.obs.ledger import EvolutionLedger
from fks_tpu.obs.memory import (
    LEAK_LOOPS, MEMORY_COMPONENTS, NULL_SAMPLER, FootprintLedger,
    LeakSentinel, WatermarkSampler, footprint_of, leak_fence,
    live_array_stats, record_footprint, rollup, run_drill,
)
from fks_tpu.obs.profiler import (
    NULL_PROFILER, StageProfiler,
)
from fks_tpu.obs.recorder import (
    NULL, FlightRecorder, NullRecorder, get_recorder, recording,
)
from fks_tpu.obs.report import render_report, sparkline
from fks_tpu.obs.spans import SpanLog, SpanRecord, span, span_path
from fks_tpu.obs import trace_ctx
from fks_tpu.obs.trace_ctx import (
    TraceContext, activate_trace, critical_path, current_trace, emit_span,
    new_trace, render_waterfall,
)
from fks_tpu.obs.tracing import (
    align_traces, candidate_trace_diff, extract_trace, format_diff,
    trace_diff,
)
from fks_tpu.obs.telemetry import (
    CompileWatcher, device_snapshot, mesh_snapshot, normalize_memory_stats,
    record_devices, record_mesh, watch_compiles,
)
from fks_tpu.obs.watchdog import (
    FLAG_INF, FLAG_NAN, FLAG_RANGE, ParitySentinel, check_result,
    combined_flags, describe_flags,
)
from fks_tpu.obs.workload import (
    DEFAULT_TENANT, LOADGEN_MODES, QueryFingerprinter, TenantAccountant,
    TenantLoad, default_make_pods, http_client, jain_fairness,
    parse_tenant_spec, run_loadgen, service_client, tenant_of,
)

__all__ = [
    "DEFAULT_TENANT", "DEFAULT_THRESHOLDS", "FLAG_INF", "FLAG_NAN",
    "FLAG_RANGE", "LEAK_LOOPS", "LOADGEN_MODES", "MEMORY_COMPONENTS",
    "NULL", "NULL_PROFILER", "NULL_SAMPLER", "CompileWatcher",
    "EvolutionLedger", "FlightRecorder", "FootprintLedger", "LeakSentinel",
    "NullRecorder", "ParitySentinel", "QueryFingerprinter", "RunHistory",
    "SLOConfig", "SpanLog", "SpanRecord", "StageProfiler",
    "TenantAccountant", "TenantLoad",
    "Threshold", "WatermarkSampler", "align_traces", "candidate_trace_diff",
    "check_result", "combined_flags", "compare_runs", "default_make_pods",
    "describe_flags", "device_snapshot", "extract_metrics",
    "extract_trace", "footprint_of", "format_comparison", "format_diff",
    "get_recorder", "has_regression", "health_line", "http_client",
    "jain_fairness", "leak_fence", "live_array_stats", "mesh_snapshot",
    "normalize_memory_stats", "parse_tenant_spec",
    "parse_threshold_overrides", "record_devices",
    "record_footprint", "record_mesh", "record_slo_burn", "recording",
    "render_report", "resolve_auto_baseline", "rollup", "run_drill",
    "run_health", "run_loadgen", "service_client", "slo_burn", "span",
    "span_path", "sparkline", "tenant_of", "to_openmetrics", "trace_diff",
    "watch", "watch_compiles",
    "TraceContext", "activate_trace", "critical_path", "current_trace",
    "emit_span", "new_trace", "render_waterfall", "trace_ctx",
]
