"""Device-time attribution: per-stage wall / compile / compute split.

A stage is a VIEW of a span: ``StageProfiler.stage`` opens one
``obs.span`` (the one timing mechanism, ``fks_tpu.obs.spans``) and, when
the profiler is enabled, adds what a span does not carry: the compile
delta, the occupancy arithmetic and the ``device_profile`` metric. A call
site that names its span (``stage("h2d", span="serve/chunk/h2d")``) gets
that span in the in-memory ring whether the profiler is enabled or not;
one that does not gets ``stage/<name>`` and only when enabled. The
stage's ``wall_seconds`` are the span's own ``t1 - t0``.

The spans say how long a scope took; this module says
where the time WENT. A ``StageProfiler`` owns a ``CompileWatcher``
(fks_tpu.obs.telemetry) and carves a run into named stages — codegen /
sandbox+preflight / transpile / device-eval / rank / ledger for the
evolution loop, per-bucket compile and steady for serving — each fenced
with explicit ``block_until_ready`` so a stage's wall clock includes the
device work it dispatched, not just the Python that enqueued it. Per
stage it reports:

- ``wall_seconds``: fenced wall time of the scope;
- ``compile_seconds`` / ``compile_count``: the XLA backend-compile share,
  read as a before/after delta off the compile watcher (host-side
  ``jax.monitoring`` telemetry — zero instrumentation in jitted code);
- ``compute_seconds``: the dispatch+compute remainder;
- occupancy, when the caller annotates the launch shape: pad-lane waste
  from ``parallel.mesh.pad_stats`` plus the scenario and trace-segment
  batch axes fold into ``utilization_pct`` — the share of launched
  lane-time spent on real candidates actually computing — and an
  attached XLA ``cost_analysis`` FLOP count yields ``est_flops_per_sec``.

Each stage lands as one ``device_profile`` metric on the active flight
recorder; ``summary()`` aggregates by stage name and reports the
attributed fraction of a measured wall interval (the ≥95% acceptance
bar) with the rest called idle. ``cli report`` renders the aggregate as
an attribution table.

The module follows the repo's Python-static-flag convention: a disabled
profiler (``NULL_PROFILER``, or ``StageProfiler(enabled=False)``) is
pure host-side scaffolding — no record, no fence, and only the spans
its call sites name — and it never touches tracing, so any
program lowered inside a stage is bit-identical with the profiler on or
off (pinned as ``flat_step/profiled`` in the jaxpr manifest).
"""
from __future__ import annotations

import contextlib
import time
from typing import Any, Dict, Iterator, List, Optional

import jax

from fks_tpu.obs.recorder import get_recorder
from fks_tpu.obs.spans import span as _span
from fks_tpu.obs.telemetry import CompileWatcher


class StageHandle:
    """What an enabled ``stage(...)`` scope yields: annotate launch-shape
    fields onto the stage record, fence device values into its clock."""

    __slots__ = ("fields", "record", "span")

    def __init__(self, span, **fields) -> None:
        self.fields: Dict[str, Any] = dict(fields)
        self.record: Optional[Dict[str, Any]] = None  # set at stage exit
        self.span = span    # the stage's open obs.span

    def annotate(self, **fields) -> None:
        """Attach occupancy/cost fields (e.g. ``parallel.mesh.pad_stats``
        output, ``cost_flops``) to the stage's device_profile record."""
        self.fields.update(fields)

    def sync(self, value: Any) -> Any:
        """Block until ``value`` is device-ready, so the dispatched work
        lands inside this stage's wall clock. Returns ``value``."""
        jax.block_until_ready(value)
        return value


class _NullHandle:
    """The disabled handle: annotate drops fields, sync is identity (the
    unprofiled path must not grow extra device fences). ``span`` is the
    open span of a stage that names one, else None."""

    __slots__ = ("span",)
    record = None

    def __init__(self, span=None) -> None:
        self.span = span

    def annotate(self, **fields) -> None:
        pass

    def sync(self, value: Any) -> Any:
        return value


_NULL_HANDLE = _NullHandle()


class StageProfiler:
    """Attribute wall time to named pipeline stages (module docstring).

    ``enabled=False`` is the Python-static off path: ``stage()`` yields a
    shared no-op handle and records nothing — same code shape for
    callers, zero filesystem writes, zero effect on lowering. The
    ``recorder`` (default: the process-wide active flight recorder)
    receives one ``device_profile`` metric per completed stage; in-memory
    ``records`` accumulate regardless, so recorder-less callers can
    read the attribution directly.
    """

    def __init__(self, enabled: bool = True, scope: str = "evolve",
                 recorder=None, watcher: Optional[CompileWatcher] = None):
        self.enabled = bool(enabled)
        self.scope = scope
        self.recorder = recorder if recorder is not None else get_recorder()
        self.records: List[Dict[str, Any]] = []
        self._depth = 0
        self._segments = 0
        self._t_start = time.perf_counter()
        self.watcher: Optional[CompileWatcher] = None
        self._own_watcher = False
        if self.enabled:
            if watcher is None:
                # NullRecorder-backed watcher: compile deltas accumulate
                # in-process without requiring an open run dir
                watcher = CompileWatcher(recorder=self.recorder).install()
                self._own_watcher = True
            self.watcher = watcher

    def close(self) -> None:
        """Uninstall the owned compile listener (borrowed watchers are the
        caller's to manage)."""
        if self._own_watcher and self.watcher is not None:
            self.watcher.uninstall()

    def __enter__(self) -> "StageProfiler":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # ----- stages

    @contextlib.contextmanager
    def stage(self, name: str, span: Optional[str] = None,
              **fields) -> Iterator[Any]:
        """A named attribution scope over one ``obs.span``: ``span`` names
        it (and it is then opened by a disabled profiler too), else it is
        ``stage/<name>``. ``fields`` go to the span and to the record,
        ``handle.annotate`` to the record alone. Nested stages record with
        their ``depth``; only depth-0 stages count toward the summary
        totals (an inner stage's wall is already inside its parent's)."""
        if not self.enabled:
            if span is None:
                yield _NULL_HANDLE
            else:
                with _span(span, **fields) as sp:
                    yield _NullHandle(sp)
            return
        sp = _span(span or "stage/" + name, **fields)
        handle = StageHandle(sp, **fields)
        depth = self._depth
        self._depth += 1
        seg0 = self._segments
        c_s0 = self.watcher.backend_compile_seconds
        c_n0 = self.watcher.backend_compile_count
        try:
            with sp:
                yield handle
        finally:
            wall = sp.seconds
            self._depth -= 1
            compile_s = self.watcher.backend_compile_seconds - c_s0
            compile_n = self.watcher.backend_compile_count - c_n0
            segs = self._segments - seg0
            rec: Dict[str, Any] = {
                "scope": self.scope, "stage": name, "depth": depth,
                "wall_seconds": round(wall, 6),
                "compile_seconds": round(min(compile_s, wall), 6),
                "compile_count": int(compile_n),
                "compute_seconds": round(max(0.0, wall - compile_s), 6),
            }
            if segs:
                rec["segments"] = int(segs)
            rec.update(handle.fields)
            _finish_utilization(rec)
            handle.record = rec
            self.records.append(rec)
            self.recorder.metric("device_profile", dict(rec))

    def segment_tick(self, n: int = 1) -> None:
        """Count a dispatched trace segment against the open stage (wired
        as the segmented runner's ``on_segment`` host callback)."""
        self._segments += int(n)

    # ----- summaries

    def summary(self, measured_wall: Optional[float] = None,
                emit: bool = False) -> Dict[str, Any]:
        """Aggregate depth-0 stages by name (wall/compile/compute sums,
        occurrence counts, per-stage share of the attributed total) and
        judge coverage against ``measured_wall`` (default: time since
        construction): ``attributed_fraction`` is the ≥95% acceptance
        number, the remainder is ``idle_fraction``. ``emit=True``
        additionally lands the aggregate as a ``stage="__total__"``
        device_profile metric."""
        top = [r for r in self.records if r.get("depth", 0) == 0]
        by: Dict[str, Dict[str, Any]] = {}
        for r in top:
            a = by.setdefault(r["stage"], {
                "stage": r["stage"], "count": 0, "wall_seconds": 0.0,
                "compile_seconds": 0.0, "compute_seconds": 0.0,
                "compile_count": 0, "segments": 0})
            a["count"] += 1
            a["wall_seconds"] += float(r["wall_seconds"])
            a["compile_seconds"] += float(r["compile_seconds"])
            a["compute_seconds"] += float(r["compute_seconds"])
            a["compile_count"] += int(r["compile_count"])
            a["segments"] += int(r.get("segments", 0))
            if "utilization_pct" in r:
                a["_uw"] = a.get("_uw", 0.0) + float(r["wall_seconds"])
                a["_us"] = a.get("_us", 0.0) + (
                    float(r["utilization_pct"]) * float(r["wall_seconds"]))
        total = sum(a["wall_seconds"] for a in by.values())
        stages = sorted(by.values(), key=lambda a: -a["wall_seconds"])
        for a in stages:
            a["pct_of_attributed"] = round(
                100.0 * a["wall_seconds"] / total, 2) if total else 0.0
            for k in ("wall_seconds", "compile_seconds", "compute_seconds"):
                a[k] = round(a[k], 6)
            uw, us = a.pop("_uw", 0.0), a.pop("_us", 0.0)
            if uw:  # wall-weighted mean of the annotated occurrences
                a["utilization_pct"] = round(us / uw, 2)
        if measured_wall is None:
            measured_wall = time.perf_counter() - self._t_start
        frac = total / measured_wall if measured_wall > 0 else 0.0
        out = {
            "scope": self.scope,
            "stages": stages,
            "wall_seconds": round(total, 6),
            "measured_wall_seconds": round(measured_wall, 6),
            "attributed_fraction": round(min(frac, 1.0), 4),
            "idle_fraction": round(max(0.0, 1.0 - frac), 4),
            "compile_seconds": round(
                sum(a["compile_seconds"] for a in stages), 6),
            "segments": int(self._segments),
        }
        if emit and self.enabled:
            self.recorder.metric(
                "device_profile", stage="__total__", scope=self.scope,
                wall_seconds=out["wall_seconds"],
                measured_wall_seconds=out["measured_wall_seconds"],
                attributed_fraction=out["attributed_fraction"],
                idle_fraction=out["idle_fraction"],
                compile_seconds=out["compile_seconds"],
                segments=out["segments"])
        return out


def _finish_utilization(rec: Dict[str, Any]) -> None:
    """Fold annotated occupancy/cost fields into derived numbers: pad-lane
    waste (and the scenario/segment axes, already multiplicative in lane
    count) discounts the compute share of the stage wall into
    ``utilization_pct``; an attached static FLOP count prices the compute
    seconds into ``est_flops_per_sec``."""
    wall = float(rec.get("wall_seconds", 0.0))
    waste = rec.get("pad_waste_fraction")
    if waste is not None and wall > 0:
        occ = max(0.0, 1.0 - float(waste))
        rec["occupancy"] = round(occ, 4)
        rec["utilization_pct"] = round(
            100.0 * occ * float(rec["compute_seconds"]) / wall, 2)
    flops = rec.get("cost_flops")
    if flops and float(rec.get("compute_seconds", 0.0)) > 0:
        rec["est_flops_per_sec"] = round(
            float(flops) / float(rec["compute_seconds"]), 1)


#: shared disabled profiler — instrumented paths default to this, so
#: profiling never needs an ``if profiler:`` guard (same pattern as
#: ``obs.recorder.NULL``)
NULL_PROFILER = StageProfiler(enabled=False, scope="null")
