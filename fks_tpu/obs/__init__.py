"""fks_tpu.obs — the flight recorder: run directories, spans, compile/
device telemetry, the per-generation evolution ledger; and, on top of
them, the tools that read a run back.

Every ROADMAP evidence gap is an observability gap; this package records
what a run actually did, into a run directory that ``cli report`` renders
back without any in-process state (fks_tpu.obs.report). The disabled path
is a shared NullRecorder — zero filesystem writes, no conditionals in
jitted code.

**The instrumentation core** is what the program is instrumented with,
what this namespace imports and re-exports, and all of ``obs`` that a
program package may import (``tests/test_layering.py::OBS_CORE``):

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
- ``profiler``  — per-stage device-time attribution, a view of the
                  spans: each stage opens one span and adds the compile
                  split + occupancy (``device_profile`` metrics); enabled,
                  it is what fences
- ``ledger``    — per-generation evolution records

**The tools on top** read run directories and drive services; they rank
above the program, nothing here loads them, and whoever wants one imports
it by its module path (``from fks_tpu.obs.exporter import ...``):

- ``report``    — run-dir summary rendering (``cli report``)
- ``exporter``  — OpenMetrics text export + heartbeat liveness
                  (``cli export-metrics`` / ``cli watch``)
- ``compare``   — cross-run regression gating (``cli compare``)
- ``history``   — cross-run index, trend/regression flagging, auto
                  baselines (``cli trends``)
- ``watchdog``  — host reporting of the numeric guards' flags
                  (``check_result``) and the offline divergence audit
                  (``tools/divergence_audit.py``)
- ``workload``  — the multi-tenant load generator (``cli loadgen``)
"""
from fks_tpu.obs import trace_ctx
from fks_tpu.obs.ledger import EvolutionLedger
from fks_tpu.obs.profiler import NULL_PROFILER, StageProfiler
from fks_tpu.obs.recorder import (
    NULL, FlightRecorder, NullRecorder, get_recorder, recording,
)
from fks_tpu.obs.spans import SpanLog, SpanRecord, span, span_path
from fks_tpu.obs.telemetry import (
    CompileWatcher, device_snapshot, mesh_snapshot, normalize_memory_stats,
    record_devices, record_mesh, watch_compiles,
)
from fks_tpu.obs.trace_ctx import (
    TraceContext, activate_trace, critical_path, current_trace, emit_span,
    new_trace, render_waterfall,
)

__all__ = [
    "NULL", "NULL_PROFILER", "CompileWatcher", "EvolutionLedger",
    "FlightRecorder", "NullRecorder", "SpanLog", "SpanRecord",
    "StageProfiler", "TraceContext", "activate_trace", "critical_path",
    "current_trace", "device_snapshot", "emit_span", "get_recorder",
    "mesh_snapshot", "new_trace", "normalize_memory_stats",
    "record_devices", "record_mesh", "recording", "render_waterfall",
    "span", "span_path", "trace_ctx", "watch_compiles",
]
