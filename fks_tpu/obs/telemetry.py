"""Compile/device telemetry: jax.monitoring listener + snapshot helpers.

Population-based JAX stacks attribute their throughput claims to
separating compile time from steady-state device time (PAPERS.md: evosax,
arxiv 2212.04180; Fast PBRL, arxiv 2206.08888). This module captures that
split from the host side, with zero instrumentation inside jitted code:

- ``CompileWatcher``: a ``jax.monitoring`` duration listener that records
  every jit compilation event (key, duration, running count) — the
  ``/jax/core/compile/*`` family: jaxpr trace, MLIR lowering, backend
  compile. Each event is appended to the active flight recorder as a
  ``kind="compile"`` event and accumulated in-process for summaries.
- ``device_snapshot``/``record_devices``: per-device identity plus
  ``memory_stats()`` (None on backends that don't report, e.g. CPU).
- ``mesh_snapshot``/``record_mesh``: mesh metadata — axis names/shape,
  shard count, and the pad-lane waste fraction from
  ``parallel.mesh.pad_stats`` (how many lanes of each launch are padding
  duplicates rather than real candidates).
"""
from __future__ import annotations

import contextlib
import threading
import time
from typing import Any, Dict, List, Optional

import jax

from fks_tpu.obs.recorder import get_recorder

#: the jax.monitoring event-key family emitted per jit compilation
COMPILE_PREFIX = "/jax/core/compile"
#: the key measuring the actual XLA backend compile (vs trace/lowering)
BACKEND_COMPILE = "backend_compile_duration"
#: the (duration-less) event JAX records when the persistent compilation
#: cache answers a compile request
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileWatcher:
    """Capture every jit compilation's (key, duration) while installed,
    and count how many of them the persistent compilation cache answered
    (``cache_hits``): JAX times a cache fetch under the same
    ``backend_compile_duration`` key as a real compile, so the number of
    programs XLA actually compiled is ``backend_compile_count -
    cache_hits`` (``compiled_count``).

    ``jax.monitoring`` listeners are global and additive; uninstall
    removes only this watcher's two callbacks (never clear ALL listeners,
    other subsystems may have their own).

    Usable as a context manager::

        with CompileWatcher(recorder) as w:
            ...  # any jit compiles in here are captured
        w.backend_compile_count, w.backend_compile_seconds
    """

    def __init__(self, recorder=None, prefix: str = COMPILE_PREFIX):
        self.recorder = recorder if recorder is not None else get_recorder()
        self.prefix = prefix
        self.events: List[tuple] = []  # (key, seconds)
        self.programs: List[str] = []  # fun_name per backend compile
        self.compiled_at: List[float] = []  # its end, time.perf_counter
        self.cache_hits = 0
        self._lock = threading.Lock()
        self._installed = False

    # the listener signature is (key, duration, **metadata) on this jax
    def _listen(self, key: str, seconds: float, **kwargs) -> None:
        if not self._installed or not key.startswith(self.prefix):
            return
        with self._lock:
            self.events.append((key, float(seconds)))
            if key.endswith(BACKEND_COMPILE):
                # jax names the program it compiled (log_elapsed_time)
                self.programs.append(str(kwargs.get("fun_name", "?")))
                self.compiled_at.append(time.perf_counter())
        self.recorder.event("compile", key=key, seconds=float(seconds))

    def _listen_event(self, key: str, **kwargs) -> None:
        if self._installed and key == CACHE_HIT:
            with self._lock:
                self.cache_hits += 1

    def install(self) -> "CompileWatcher":
        if not self._installed:
            self._installed = True
            _INSTALLED.append(self)
            jax.monitoring.register_event_duration_secs_listener(self._listen)
            jax.monitoring.register_event_listener(self._listen_event)
        return self

    def uninstall(self) -> None:
        if not self._installed:
            return
        self._installed = False  # gate first: inert even if unregister fails
        _INSTALLED.remove(self)
        from jax._src import monitoring as _monitoring
        _monitoring.unregister_event_duration_listener(self._listen)
        _monitoring.unregister_event_listener(self._listen_event)

    def __enter__(self) -> "CompileWatcher":
        return self.install()

    def __exit__(self, *exc) -> None:
        self.uninstall()

    # ----- summaries

    def summary(self) -> Dict[str, Dict[str, float]]:
        """Per event key: {"count", "total_seconds"}."""
        with self._lock:
            events = list(self.events)
        out: Dict[str, Dict[str, float]] = {}
        for key, secs in events:
            s = out.setdefault(key, {"count": 0, "total_seconds": 0.0})
            s["count"] += 1
            s["total_seconds"] += secs
        for s in out.values():
            s["total_seconds"] = round(s["total_seconds"], 6)
        return out

    @property
    def backend_compile_count(self) -> int:
        """XLA backend compiles observed (one per compiled program)."""
        with self._lock:
            return sum(1 for k, _ in self.events
                       if k.endswith(BACKEND_COMPILE))

    @property
    def compiled_count(self) -> int:
        """Programs XLA actually compiled: backend compile requests minus
        the ones the persistent cache answered."""
        return self.backend_compile_count - self.cache_hits

    @property
    def backend_compile_seconds(self) -> float:
        """Total XLA backend compile time observed."""
        with self._lock:
            return sum(s for k, s in self.events
                       if k.endswith(BACKEND_COMPILE))


#: the watchers installed now, for `compiles_between`
_INSTALLED: List[CompileWatcher] = []


def compiles_between(t0: float, t1: float) -> Optional[int]:
    """Backend compiles (cache fetches among them) that ended inside
    ``[t0, t1]`` on ``time.perf_counter``, as the installed watcher that
    saw most counts them; None where none is installed (``obs.spans``
    writes it into a slow call's record)."""
    seen = None
    for w in list(_INSTALLED):
        with w._lock:
            n = sum(t0 <= t <= t1 for t in w.compiled_at)
        seen = n if seen is None else max(seen, n)
    return seen


def watch_compiles(recorder=None):
    """A ``CompileWatcher`` context for ``recorder`` — or a null context
    when recording is disabled, so the no-run-dir path doesn't pay a
    per-compile listener callback."""
    rec = recorder if recorder is not None else get_recorder()
    if not rec.enabled:
        return contextlib.nullcontext(None)
    return CompileWatcher(rec)


# --------------------------------------------------------- snapshots

#: canonical memory_stats keys -> the per-backend spellings observed in
#: the wild (TPU/GPU PJRT report bytes_in_use/peak_bytes_in_use; some
#: stacks spell the pool limit bytes_limit vs bytes_reservable_limit)
_MEMORY_STAT_ALIASES = (
    ("bytes_in_use", ("bytes_in_use", "bytes_used", "used_bytes")),
    ("peak_bytes_in_use", ("peak_bytes_in_use", "peak_bytes",
                           "max_bytes_in_use", "largest_alloc_size")),
    ("bytes_limit", ("bytes_limit", "bytes_reservable_limit",
                     "pool_bytes", "limit_bytes")),
)


def normalize_memory_stats(raw: Any) -> Optional[Dict[str, int]]:
    """Canonicalize a backend's ``Device.memory_stats()`` dict to the
    closed ``bytes_in_use`` / ``peak_bytes_in_use`` / ``bytes_limit``
    subset the report's device table keys on. Backends that don't report
    — CPU returns None, some raise — normalize to None; partial dicts keep
    whichever canonical keys they can answer, so a reader never KeyErrors
    on a backend-specific spelling."""
    if not isinstance(raw, dict) or not raw:
        return None
    out: Dict[str, int] = {}
    for canon, spellings in _MEMORY_STAT_ALIASES:
        for k in spellings:
            v = raw.get(k)
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                out[canon] = int(v)
                break
    return out or None


def device_snapshot() -> List[Dict[str, Any]]:
    """Per-device identity + normalized ``memory_stats()`` (None where
    the backend doesn't report — CPU — rather than raising; key spellings
    canonicalized by ``normalize_memory_stats``)."""
    out = []
    for d in jax.devices():
        try:
            mem = d.memory_stats()
        except Exception:  # pragma: no cover - backend without the API
            mem = None
        out.append({
            "id": d.id,
            "platform": d.platform,
            "device_kind": getattr(d, "device_kind", ""),
            "process_index": getattr(d, "process_index", 0),
            "memory_stats": normalize_memory_stats(mem),
        })
    return out


def record_devices(recorder=None) -> List[Dict[str, Any]]:
    """Write one ``kind="device"`` event per visible device."""
    rec = recorder if recorder is not None else get_recorder()
    snap = device_snapshot() if rec.enabled else []
    for d in snap:
        rec.event("device", **d)
    return snap


def mesh_snapshot(mesh, real_count: Optional[int] = None) -> Dict[str, Any]:
    """Mesh metadata: axes/shape/device count/shard count, plus the
    pad-lane waste fraction when the caller's real candidate count is
    known (``pad_population`` pads to a shard multiple; the waste fraction
    is the share of launched lanes that are padding duplicates)."""
    from fks_tpu.parallel.mesh import num_shards, pad_stats

    info: Dict[str, Any] = {
        "axis_names": list(mesh.axis_names),
        "shape": {str(k): int(v) for k, v in mesh.shape.items()},
        "devices": int(mesh.devices.size),
        "shards": num_shards(mesh),
    }
    if real_count is not None:
        info.update(pad_stats(real_count, mesh))
    return info


def record_mesh(mesh, real_count: Optional[int] = None,
                recorder=None) -> Dict[str, Any]:
    """Write one ``kind="mesh"`` event describing the mesh."""
    rec = recorder if recorder is not None else get_recorder()
    if not rec.enabled:
        return {}
    snap = mesh_snapshot(mesh, real_count)
    rec.event("mesh", **snap)
    return snap
