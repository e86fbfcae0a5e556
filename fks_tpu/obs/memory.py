"""Memory observability: footprint ledger, watermark sampler, leak sentinel.

The simulator's real scaling wall is HBM, not FLOPs — yet until this
module the repo had no memory accounting: ``memory_stats()`` was an
opaque blob, ``memory_analysis()`` a discarded bench log line, and
nothing said whether 1,000 champion hot-swaps leak device buffers.
Three pillars, all host-side (zero effect on lowered programs — the
Python-static-flag convention, pinned as ``flat_step/mem_sampled``):

- **Executable-footprint ledger** — ``record_footprint`` captures a
  compiled program's ``memory_analysis()`` (temp / argument / output /
  generated-code bytes) as one ``memory_footprint`` metric per
  executable, tagged with its component (serve AOT ladder, VM capacity
  bucket, evolve tier, bench probe) and mesh layout. ``rollup``
  aggregates the ledger per (component, mesh_layout) into predicted-HBM
  totals, so ``parallel.mesh`` layouts become comparable by bytes
  before a single batch runs — the layout-autotuner's cost signal.
- **Watermark sampler** — ``WatermarkSampler`` records per-device
  ``memory_stats()`` watermarks (normalized keys, deltas vs the start
  fence), host RSS via ``resource.getrusage``, and optional
  ``tracemalloc`` top-N host attribution, as ``memory_watermark``
  metrics — interval-driven from a background thread, or per
  StageProfiler stage via the profiler's ``sampler=`` hook. Off by
  default; the disabled sampler is a shared no-op.
- **Leak sentinel** — ``LeakSentinel`` fences ``jax.live_arrays()``
  count/bytes around N iterations of a hot loop (serve batches, VM
  ``swap_program``, promotion cycles, evolve generations) and records a
  ``leak_check`` verdict against a drift tolerance. Two deterministic
  drills (``vm_swap_leak``, ``snapshot_cache_bound``) run under
  ``cli mem --drill``.

Read back by ``cli mem`` (footprint ladder + watermark table), the
``cli report`` memory section, and the ``fks_mem_*`` OpenMetrics gauges.
"""
from __future__ import annotations

import contextlib
import gc
import threading
import time
from typing import Any, Callable, Dict, Iterator, List, Optional, Tuple

from fks_tpu.obs.recorder import get_recorder
from fks_tpu.obs.telemetry import normalize_memory_stats

#: closed vocabulary for memory_footprint.component — which tier compiled
#: the executable (duplicated stdlib-only in tools/check_jsonl_schema.py;
#: tests pin the two copies against each other)
MEMORY_COMPONENTS = ("serve_aot", "serve_vm", "evolve", "bench")

#: closed vocabulary for leak_check.loop — which hot loop was fenced
LEAK_LOOPS = ("serve_batch", "vm_swap", "promotion", "evolve_generation",
              "drill")

#: canonical footprint byte keys, in ladder-rendering order
FOOTPRINT_KEYS = ("temp_bytes", "argument_bytes", "output_bytes",
                  "generated_code_bytes")

#: memory_analysis() attribute -> canonical ledger key
_ANALYSIS_ATTRS = (
    ("temp_size_in_bytes", "temp_bytes"),
    ("argument_size_in_bytes", "argument_bytes"),
    ("output_size_in_bytes", "output_bytes"),
    ("generated_code_size_in_bytes", "generated_code_bytes"),
    ("alias_size_in_bytes", "alias_bytes"),
)


# ------------------------------------------------------ footprint ledger


def footprint_of(compiled: Any) -> Optional[Dict[str, int]]:
    """The canonical byte footprint of a ``jax`` ``Compiled`` executable
    (or anything exposing ``memory_analysis()``): temp / argument /
    output / generated-code bytes plus their ``total_bytes`` sum — the
    executable's predicted steady-state HBM claim. None when the backend
    cannot price the program (the caller records nothing rather than a
    row of zeros)."""
    ma = getattr(compiled, "memory_analysis", None)
    if ma is None:
        return None
    try:
        stats = ma() if callable(ma) else ma
    except Exception:
        return None
    if stats is None:
        return None
    out: Dict[str, int] = {}
    for attr, key in _ANALYSIS_ATTRS:
        v = getattr(stats, attr, None) if not isinstance(stats, dict) \
            else stats.get(key, stats.get(attr))
        if isinstance(v, (int, float)) and not isinstance(v, bool):
            out[key] = int(v)
    if not any(k in out for k in FOOTPRINT_KEYS):
        return None
    for k in FOOTPRINT_KEYS:
        out.setdefault(k, 0)
    out["total_bytes"] = sum(out[k] for k in FOOTPRINT_KEYS)
    return out


def mesh_layout_label(mesh: Any) -> str:
    """A mesh's layout as a stable comparison key: ``"dcn=2,pop=4"``
    from its axis shape (empty for single-device / no mesh)."""
    if mesh is None:
        return ""
    try:
        shape = mesh.shape
        return ",".join(f"{k}={int(v)}" for k, v in shape.items())
    except Exception:
        return ""


class FootprintLedger:
    """Bounded in-process ledger of recorded executable footprints —
    the roll-up source when no run dir is open. Thread-safe appends
    (serve compiles happen under batcher threads)."""

    def __init__(self, cap: int = 512):
        self.cap = int(cap)
        self._lock = threading.Lock()
        self._records: List[Dict[str, Any]] = []

    def add(self, record: Dict[str, Any]) -> None:
        with self._lock:
            self._records.append(dict(record))
            if len(self._records) > self.cap:
                del self._records[: len(self._records) - self.cap]

    def records(self) -> List[Dict[str, Any]]:
        with self._lock:
            return list(self._records)

    def clear(self) -> None:
        with self._lock:
            self._records.clear()


#: the process-wide ledger every ``record_footprint`` lands in (alongside
#: the active flight recorder, when one is enabled)
LEDGER = FootprintLedger()


def record_footprint(component: str, exe_key: Any, compiled: Any = None, *,
                     footprint: Optional[Dict[str, int]] = None,
                     mesh: Any = None, recorder=None,
                     **fields) -> Optional[Dict[str, Any]]:
    """One ``memory_footprint`` record for a compiled executable: the
    ``footprint_of`` bytes tagged with ``component`` (closed vocabulary),
    a stable ``exe_key`` (e.g. ``"lanes=2,pods=8"``), and the mesh
    layout. Lands in the in-process ``LEDGER`` and, when recording, on
    the flight recorder. Returns the record, or None when the backend
    cannot price the program — callers never branch on it."""
    if component not in MEMORY_COMPONENTS:
        raise ValueError(f"unknown memory component {component!r} "
                         f"(expect one of {sorted(MEMORY_COMPONENTS)})")
    fp = footprint if footprint is not None else footprint_of(compiled)
    if fp is None:
        return None
    rec: Dict[str, Any] = {
        "component": component,
        "exe_key": str(exe_key),
        "mesh_layout": mesh_layout_label(mesh),
        **fp,
        **fields,
    }
    LEDGER.add(rec)
    r = recorder if recorder is not None else get_recorder()
    r.metric("memory_footprint", dict(rec))
    return rec


def rollup(records: Optional[List[Dict[str, Any]]] = None
           ) -> List[Dict[str, Any]]:
    """Per-(component, mesh_layout) aggregate over footprint records
    (default: the process ledger): executable count, per-key byte sums,
    the ``predicted_hbm_bytes`` total, and the single largest
    executable's temp claim — what makes two mesh layouts comparable by
    predicted HBM before either runs. Sorted largest-first."""
    recs = LEDGER.records() if records is None else records
    by: Dict[Tuple[str, str], Dict[str, Any]] = {}
    for r in recs:
        key = (str(r.get("component", "")), str(r.get("mesh_layout", "")))
        a = by.setdefault(key, {
            "component": key[0], "mesh_layout": key[1], "executables": 0,
            "predicted_hbm_bytes": 0, "peak_temp_bytes": 0,
            **{k: 0 for k in FOOTPRINT_KEYS}})
        a["executables"] += 1
        for k in FOOTPRINT_KEYS:
            a[k] += int(r.get(k, 0))
        total = int(r.get("total_bytes",
                          sum(int(r.get(k, 0)) for k in FOOTPRINT_KEYS)))
        a["predicted_hbm_bytes"] += total
        a["peak_temp_bytes"] = max(a["peak_temp_bytes"],
                                   int(r.get("temp_bytes", 0)))
    return sorted(by.values(), key=lambda a: -a["predicted_hbm_bytes"])


# ----------------------------------------------------- watermark sampler


def host_rss_kb() -> int:
    """Peak resident set size of this process in KB (``ru_maxrss`` is KB
    on Linux, bytes on macOS — normalized to KB). 0 where the resource
    module is unavailable."""
    try:
        import resource
        import sys
        rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        return int(rss // 1024) if sys.platform == "darwin" else int(rss)
    except Exception:  # pragma: no cover - platform without getrusage
        return 0


def _device_watermarks(base: Dict[int, Dict[str, int]]
                       ) -> List[Dict[str, Any]]:
    """Per-device normalized memory stats plus the delta vs the sampler's
    start fence. Non-reporting backends (CPU) contribute identity-only
    rows — present, so the table says 'this backend does not report'."""
    import jax

    out: List[Dict[str, Any]] = []
    for d in jax.devices():
        try:
            stats = normalize_memory_stats(d.memory_stats())
        except Exception:
            stats = None
        row: Dict[str, Any] = {"id": int(d.id), "platform": d.platform}
        if stats:
            row.update(stats)
            b = base.get(int(d.id), {})
            if "bytes_in_use" in stats and "bytes_in_use" in b:
                row["delta_bytes"] = (stats["bytes_in_use"]
                                      - b["bytes_in_use"])
        out.append(row)
    return out


class WatermarkSampler:
    """Low-overhead memory watermark recorder (module docstring).

    ``enabled=False`` (the default construction for instrumented paths)
    is the Python-static off path: ``start``/``stop``/``sample`` are
    no-ops, no thread exists, nothing is recorded — and because the
    sampler never touches tracing, programs lowered while a sampler runs
    are bit-identical (``flat_step/mem_sampled`` pin). Enabled, each
    ``sample(stage=...)`` lands one ``memory_watermark`` metric: host
    RSS, per-device normalized watermarks with deltas vs the start
    fence, and — when ``tracemalloc`` tracing is active or
    ``trace_host=True`` started it — the top-N allocation sites.

    ``interval_s > 0`` + ``start()`` runs a daemon thread sampling on
    that cadence (``stage="interval"``); ``sample`` stays callable
    inline (the StageProfiler ``sampler=`` hook calls it per stage).
    """

    def __init__(self, enabled: bool = True, interval_s: float = 0.0,
                 top_n: int = 5, trace_host: bool = False, recorder=None,
                 cap: int = 1024):
        self.enabled = bool(enabled)
        self.interval_s = float(interval_s)
        self.top_n = int(top_n)
        self.trace_host = bool(trace_host)
        self.recorder = recorder if recorder is not None else get_recorder()
        self.cap = int(cap)
        self.samples: List[Dict[str, Any]] = []
        self._base_rss_kb = 0
        self._base_dev: Dict[int, Dict[str, int]] = {}
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        self._own_tracemalloc = False

    # ----- lifecycle

    def start(self) -> "WatermarkSampler":
        """Fence the baselines (RSS + per-device bytes_in_use) and, with
        an interval, launch the daemon sampling thread."""
        if not self.enabled:
            return self
        import jax

        self._base_rss_kb = host_rss_kb()
        self._base_dev = {}
        for d in jax.devices():
            try:
                stats = normalize_memory_stats(d.memory_stats())
            except Exception:
                stats = None
            if stats:
                self._base_dev[int(d.id)] = stats
        if self.trace_host:
            import tracemalloc
            if not tracemalloc.is_tracing():
                tracemalloc.start()
                self._own_tracemalloc = True
        if self.interval_s > 0 and self._thread is None:
            self._stop.clear()
            self._thread = threading.Thread(
                target=self._loop, name="fks-mem-sampler", daemon=True)
            self._thread.start()
        return self

    def stop(self) -> None:
        if self._thread is not None:
            self._stop.set()
            self._thread.join(timeout=max(1.0, 2 * self.interval_s))
            self._thread = None
        if self._own_tracemalloc:
            import tracemalloc
            tracemalloc.stop()
            self._own_tracemalloc = False

    def __enter__(self) -> "WatermarkSampler":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    def _loop(self) -> None:
        while not self._stop.wait(self.interval_s):
            self.sample(stage="interval")

    # ----- sampling

    def _top_allocs(self) -> List[Dict[str, Any]]:
        import tracemalloc

        if not tracemalloc.is_tracing() or self.top_n <= 0:
            return []
        try:
            stats = tracemalloc.take_snapshot().statistics("lineno")
        except Exception:  # pragma: no cover - snapshot raced a stop()
            return []
        return [{"site": f"{s.traceback[0].filename}:"
                         f"{s.traceback[0].lineno}",
                 "kb": round(s.size / 1024.0, 1), "count": int(s.count)}
                for s in stats[: self.top_n]]

    def sample(self, stage: str = "") -> Dict[str, Any]:
        """One ``memory_watermark`` record for the current instant (empty
        dict when disabled — the no-op contract instrumented paths rely
        on)."""
        if not self.enabled:
            return {}
        rss = host_rss_kb()
        rec: Dict[str, Any] = {
            "stage": stage or "manual",
            "host_rss_kb": rss,
            "host_rss_delta_kb": rss - self._base_rss_kb,
            "devices": _device_watermarks(self._base_dev),
        }
        top = self._top_allocs()
        if top:
            rec["top_allocs"] = top
        self.samples.append(rec)
        if len(self.samples) > self.cap:
            del self.samples[: len(self.samples) - self.cap]
        self.recorder.metric("memory_watermark", dict(rec))
        return rec


#: shared disabled sampler — instrumented paths default to this, so
#: watermark sampling never needs an ``if sampler:`` guard (the
#: ``NULL_PROFILER`` pattern)
NULL_SAMPLER = WatermarkSampler(enabled=False)


# -------------------------------------------------------- leak sentinel


def live_array_stats() -> Dict[str, int]:
    """Count and total bytes of every live ``jax.Array`` in the process —
    the leak sentinel's fence reading. Arrays deleted mid-walk are
    skipped rather than raising."""
    import jax

    count = 0
    total = 0
    for a in jax.live_arrays():
        try:
            nb = int(a.nbytes)
        except Exception:
            continue
        count += 1
        total += nb
    return {"count": count, "bytes": total}


class LeakSentinel:
    """Fence ``live_arrays()`` around N iterations of a hot loop and
    record the drift verdict.

    Usage: ``fence()`` before the loop (after warmup — caches and
    constants allocated on first use are residency, not leaks), run the
    loop, then ``check(iterations)``: one ``leak_check`` metric with the
    count/byte drift and ``ok`` judged against the tolerances (default:
    ZERO net growth — the steady-state contract of donated batch buffers
    and content-hash caches). Both fences ``gc.collect()`` first so
    Python-side garbage holding device buffers can't masquerade as a
    device leak."""

    def __init__(self, loop: str, tolerance_count: int = 0,
                 tolerance_bytes: int = 0, recorder=None):
        if loop not in LEAK_LOOPS:
            raise ValueError(f"unknown leak loop {loop!r} "
                             f"(expect one of {sorted(LEAK_LOOPS)})")
        self.loop = loop
        self.tolerance_count = int(tolerance_count)
        self.tolerance_bytes = int(tolerance_bytes)
        self.recorder = recorder if recorder is not None else get_recorder()
        self.baseline: Optional[Dict[str, int]] = None
        self.result: Optional[Dict[str, Any]] = None

    def fence(self) -> Dict[str, int]:
        gc.collect()
        self.baseline = live_array_stats()
        return self.baseline

    def check(self, iterations: int) -> Dict[str, Any]:
        if self.baseline is None:
            raise RuntimeError("fence() before check()")
        gc.collect()
        now = live_array_stats()
        drift_count = now["count"] - self.baseline["count"]
        drift_bytes = now["bytes"] - self.baseline["bytes"]
        rec = {
            "loop": self.loop,
            "iterations": int(iterations),
            "drift_count": int(drift_count),
            "drift_bytes": int(drift_bytes),
            "baseline_count": self.baseline["count"],
            "baseline_bytes": self.baseline["bytes"],
            "ok": (drift_count <= self.tolerance_count
                   and drift_bytes <= self.tolerance_bytes),
        }
        self.result = rec
        self.recorder.metric("leak_check", dict(rec))
        return rec


@contextlib.contextmanager
def leak_fence(loop: str, iterations: int, tolerance_count: int = 0,
               tolerance_bytes: int = 0,
               recorder=None) -> Iterator[LeakSentinel]:
    """``with leak_fence("vm_swap", 50) as s: ...`` — fence on entry,
    check on clean exit; the verdict is ``s.result`` (never raises on
    drift: gating is the caller's call)."""
    s = LeakSentinel(loop, tolerance_count=tolerance_count,
                     tolerance_bytes=tolerance_bytes, recorder=recorder)
    s.fence()
    try:
        yield s
    finally:
        s.check(iterations)


# --------------------------------------------------------------- drills


def _drill_workload():
    """The test_vm_serve recipe: 8 nodes x 16 pods, deterministic."""
    from fks_tpu.data.synthetic import synthetic_workload

    return synthetic_workload(8, 16, seed=0)


def _drill_envelope():
    from fks_tpu.serve.artifact import ShapeEnvelope

    return ShapeEnvelope(max_pods=8, min_pod_bucket=8, max_batch=2,
                         max_gpu_milli=1000)


def _drill_queries(n: int, pods: int = 3) -> List[List[dict]]:
    return [[{"cpu_milli": 10 + 7 * i + j, "memory_mib": 50 + 11 * j,
              "creation_time": j, "duration_time": 40}
             for j in range(pods)] for i in range(n)]


def drill_vm_swap_leak(swaps: int = 50, batches: int = 200,
                       recorder=None) -> Dict[str, Any]:
    """The ISSUE-17 gated drill: ``swaps`` consecutive ``swap_program``
    promotions alternating two champions, interleaved with ``batches``
    served batches, must show ZERO net ``live_arrays()`` growth — every
    swap frees the displaced program tables, every batch's buffers are
    donated or cache-hits. Warmup (one full swap cycle + a served batch
    per champion) happens BEFORE the fence: first-use constants and the
    snapshot-table cache are residency, not leaks."""
    from fks_tpu.funsearch import template
    from fks_tpu.serve.artifact import ChampionSpec
    from fks_tpu.serve.vm_engine import VMServeEngine

    champs = [
        ChampionSpec(code=template.fill_template("score = 1000"),
                     score=0.4, source="<drill-a>"),
        ChampionSpec(code=template.fill_template(
            "score = 1000 + (node.cpu_milli_left - pod.cpu_milli) "
            "/ max(1, node.cpu_milli_total)"), score=0.9,
            source="<drill-b>"),
    ]
    eng = VMServeEngine(champs[0], _drill_workload(),
                        envelope=_drill_envelope(), engine="flat")
    queries = _drill_queries(2)
    # warmup: compile the bucket, populate the snapshot cache, touch both
    # champions' first-use paths
    for c in (champs[1], champs[0]):
        eng.swap_program(c)
        eng.answer_batch(queries)
    sent = LeakSentinel("vm_swap", recorder=recorder)
    sent.fence()
    b = 0
    for i in range(int(swaps)):
        eng.swap_program(champs[(i + 1) % 2])
        while b * swaps < (i + 1) * batches:  # interleave evenly
            eng.answer_batch(queries)
            b += 1
    while b < int(batches):
        eng.answer_batch(queries)
        b += 1
    rec = sent.check(int(swaps) + b)
    return {"ok": bool(rec["ok"]), "drill": "vm_swap_leak",
            "swaps": int(swaps), "batches": b, **rec}


def drill_snapshot_cache_bound(max_bytes: int = 0,
                               recorder=None) -> Dict[str, Any]:
    """The PR-14 snapshot-table LRU must respect a configured BYTE
    ceiling, not just an entry count: stream distinct-content queries
    (each a cache miss) through an engine whose cache is capped at ~2
    tables' bytes and verify the resident total never exceeds the cap,
    eviction actually happened, and a re-sent recent query still hits."""
    from fks_tpu.funsearch import template
    from fks_tpu.serve.artifact import ChampionSpec, ServeEngine

    champ = ChampionSpec(code=template.fill_template("score = 1000"),
                         score=0.4, source="<drill>")
    probe = ServeEngine(champ, _drill_workload(),
                        envelope=_drill_envelope(), engine="flat")
    # distinct real pod counts -> distinct snapshot-trigger tables (the
    # table content is a function of the query's pod count, so counts
    # 1..8 inside the one pod bucket give 8 distinct cache entries)
    distinct = [[{"cpu_milli": 10 + j, "memory_mib": 50 + j,
                  "creation_time": j, "duration_time": 40}
                 for j in range(n)] for n in range(1, 9)]
    probe.answer_batch(distinct[:1])
    one_table = max(probe.snapshot_cache_bytes, 1)
    cap = int(max_bytes) or 2 * one_table
    eng = ServeEngine(champ, _drill_workload(), envelope=_drill_envelope(),
                      engine="flat", snapshot_cache_max_bytes=cap)
    over = 0
    for q in distinct:
        eng.answer_batch([q])
        if eng.snapshot_cache_bytes > cap:
            over += 1
    stats = eng.snapshot_cache_stats()
    hits0 = stats["hits"]
    eng.answer_batch([distinct[-1]])  # most recent survivor must hit
    stats = eng.snapshot_cache_stats()
    evicted = stats["misses"] > stats["entries"]
    rehit = stats["hits"] > hits0
    ok = over == 0 and evicted and rehit
    rec = {"ok": ok, "drill": "snapshot_cache_bound",
           "cap_bytes": cap, "over_cap_observations": over,
           "evicted": evicted, "recent_rehit": rehit, **stats}
    r = recorder if recorder is not None else get_recorder()
    r.metric("leak_check", loop="drill",
             iterations=len(distinct), drift_count=over,
             drift_bytes=max(0, stats["bytes"] - cap), ok=ok)
    return rec


#: drill name -> callable returning {"ok": bool, ...} — the ``cli mem
#: --drill`` dispatch table
DRILLS: Dict[str, Callable[..., Dict[str, Any]]] = {
    "vm_swap_leak": drill_vm_swap_leak,
    "snapshot_cache_bound": drill_snapshot_cache_bound,
}


def run_drill(name: str, **kw) -> Dict[str, Any]:
    """Run one named memory drill; raises ``KeyError`` on unknown names
    (the cli surfaces the legal set)."""
    if name not in DRILLS:
        raise KeyError(f"unknown memory drill {name!r} "
                       f"(expect one of {sorted(DRILLS)})")
    t0 = time.perf_counter()
    out = DRILLS[name](**kw)
    out["seconds"] = round(time.perf_counter() - t0, 3)
    return out
