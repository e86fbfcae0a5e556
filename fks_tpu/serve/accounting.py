"""What the service counts about the traffic it answers: SLO burn, query
fingerprints, per-tenant accounting.

- ``SLOConfig`` declares p99/qps targets and ``slo_burn`` prices observed
  latencies against them as burn rates (the multiple of the error budget
  being consumed — burn_rate > 1 means the SLO is being violated),
  recorded as ``slo_burn`` metrics (``record_slo_burn``) and surfaced by
  ``cli watch`` and the OpenMetrics exporter. The promotion controller's
  canary reads the same math.
- ``QueryFingerprinter``: a deterministic content/shape signature per
  query — pod-count bucket, per-pod resource-mix decade histogram (the
  pre-flight ``analysis.candidate._bucket`` idiom: sign + magnitude
  decade, so 120 and 160 cluster while 120 and 12000 split), and the
  snapshot-trigger-table content hash (the ``blake2b`` idiom the serve
  engine's device ktable cache uses). Classes are stable across
  processes and pod orderings, so live traffic clusters into workload
  classes and a windowed ``workload_mix`` metric records the
  distribution; the portfolio router's affinity key is this class.
- ``TenantAccountant``: per-tenant request/shed/expiry/degraded
  counters, EWMA service time, per-tenant SLO burn, and a Jain's
  fairness index over per-tenant goodput — recorded as one
  ``tenant_stats`` metric per tenant, exported as ``fks_tenant_*``
  gauges, rendered as a table by ``cli report`` and live lines by
  ``cli watch``.

Disabled path discipline: the service holds ``accountant=None`` /
``fingerprinter=None`` by default — no object, no lock, no per-request
cost (the NullRecorder rule applied to accounting). The load generator
that drives a service with tenants is ``fks_tpu.obs.workload``.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import math
import threading
import time
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from fks_tpu.obs.recorder import get_recorder
from fks_tpu.serve.batcher import query_gpu_spec
from fks_tpu.sim.evaluator import max_snapshot_count, snapshot_trigger_table

#: queries that name no tenant all account to one bucket — the
#: single-tenant deployments that existed before this module
DEFAULT_TENANT = "default"


# -------------------------------------------------------------------- SLOs


@dataclasses.dataclass(frozen=True)
class SLOConfig:
    """Serve-tier service-level objectives. ``p99_ms``: target warm tail
    latency (the SLI is the fraction of requests slower than it;
    ``error_budget`` of them are allowed). ``qps``: target sustained
    throughput (the SLI is the relative shortfall against it). 0 leaves
    an objective unset."""

    p99_ms: float = 0.0
    qps: float = 0.0
    error_budget: float = 0.01

    @property
    def enabled(self) -> bool:
        return bool(self.p99_ms or self.qps)


def slo_burn(slo: SLOConfig, latencies_ms: List[float],
             elapsed_s: float) -> List[Dict[str, Any]]:
    """Price an observation window against the SLOs: one record per set
    objective — ``{"slo", "target", "observed", "burn_rate", ...}`` —
    where burn_rate is the multiple of the error budget the window is
    consuming (>1 = violating; the alerting threshold everywhere)."""
    records: List[Dict[str, Any]] = []
    n = len(latencies_ms)
    if slo.p99_ms and n:
        over = sum(1 for v in latencies_ms if v > slo.p99_ms) / n
        srt = sorted(latencies_ms)
        p99 = srt[min(n - 1, int(0.99 * n))]
        records.append({
            "slo": "p99_ms", "target": float(slo.p99_ms),
            "observed": round(float(p99), 3),
            "over_fraction": round(over, 4),
            "burn_rate": round(over / slo.error_budget, 3),
            "requests": n,
        })
    if slo.qps and elapsed_s > 0 and n:
        observed = n / elapsed_s
        shortfall = max(0.0, 1.0 - observed / slo.qps)
        records.append({
            "slo": "qps", "target": float(slo.qps),
            "observed": round(observed, 3),
            "over_fraction": round(shortfall, 4),
            "burn_rate": round(shortfall / slo.error_budget, 3),
            "requests": n,
        })
    return records


def record_slo_burn(slo: SLOConfig, latencies_ms: List[float],
                    elapsed_s: float, recorder=None) -> List[Dict[str, Any]]:
    """``slo_burn`` metrics onto ``recorder`` for each set objective;
    returns the records."""
    rec = recorder if recorder is not None else get_recorder()
    records = slo_burn(slo, latencies_ms, elapsed_s)
    for r in records:
        rec.metric("slo_burn", dict(r))
    return records


def tenant_of(query: Dict[str, Any]) -> str:
    """The tenant a request accounts to: its ``tenant`` field, else
    ``DEFAULT_TENANT``. Always a str — accounting keys must never be
    unhashable or collide across JSON round trips."""
    t = query.get("tenant") if isinstance(query, dict) else None
    return str(t) if t else DEFAULT_TENANT


# ------------------------------------------------------------ fingerprints


def _decade(v: float) -> str:
    """Sign + magnitude-decade token (``analysis.candidate._bucket``):
    "0" for zero, else "+eK"/"-eK" — the resolution at which resource
    requests cluster into classes without hashing exact values."""
    v = float(v)
    if v == 0:
        return "0"
    mag = abs(v)
    dec = 0 if mag <= 1.0 else int(math.floor(math.log10(mag))) + 1
    return f"{'+' if v > 0 else '-'}e{dec}"


def _pow2_bucket(n: int) -> int:
    b = 1
    while b < n:
        b <<= 1
    return b


class QueryFingerprinter:
    """Deterministic workload-class signatures + a windowed class mix.

    ``classify(pods)`` is pure and ORDER-INDEPENDENT: the signature is
    (pod-count power-of-two bucket, sorted resource-mix histogram with
    each pod's ``gpu_spec`` set where it names one,
    snapshot-trigger-table hash), digested with ``blake2b`` — the same
    query permuted, re-serialized, or classified in another process
    lands in the same class. ``observe`` classifies AND counts;
    ``record_mix`` emits the windowed ``workload_mix`` metric."""

    def __init__(self, *, snapshot_interval: float = 0.05,
                 max_steps_per_pod: int = 8, window: int = 256):
        self.snapshot_interval = float(snapshot_interval)
        self.max_steps_per_pod = int(max_steps_per_pod)
        self.window = max(1, int(window))
        self._counts: Dict[str, int] = {}
        self._seen = 0
        self._lock = threading.Lock()

    def _ktable_digest(self, n_pods: int) -> str:
        """Content hash of the snapshot trigger table this query would
        ship (the serve upload's third tensor): sized from the REAL pod
        count exactly as ``batcher._query_ktable`` sizes it, hashed with
        the engine's device-cache ``blake2b`` idiom."""
        tbl = snapshot_trigger_table(
            n_pods,
            max_snapshot_count(self.max_steps_per_pod * n_pods, n_pods,
                               self.snapshot_interval),
            self.snapshot_interval)
        return hashlib.blake2b(np.asarray(tbl, np.int32).tobytes(),
                               digest_size=8).hexdigest()

    def classify(self, pods: Sequence[Dict[str, Any]]) -> str:
        """Pod list -> class label ``p{bucket}:{digest}`` (stable across
        processes, pod orderings, and dict key orders)."""
        n = len(pods)
        bucket = _pow2_bucket(max(1, n))
        mix: Dict[str, int] = {}
        for p in pods:
            tok = "/".join((
                _decade(p.get("cpu_milli", 0)),
                _decade(p.get("memory_mib", 0)),
                _decade(p.get("gpu_milli", 0)),
                _decade(p.get("duration_time", 0)),
            ))
            # the GPU models a pod accepts are part of its class (a set:
            # neither the order of the names nor a repeat matters); a
            # pod that names none keeps the token it always had
            spec = query_gpu_spec(p)
            if spec:
                tok += "/" + "|".join(sorted(set(filter(
                    None, spec.split("|")))))
            mix[tok] = mix.get(tok, 0) + 1
        canon = json.dumps(
            [bucket, sorted(mix.items()), self._ktable_digest(n)],
            separators=(",", ":"))
        digest = hashlib.blake2b(canon.encode(), digest_size=6).hexdigest()
        return f"p{bucket}:{digest}"

    def observe(self, pods: Sequence[Dict[str, Any]]) -> str:
        cls = self.classify(pods)
        with self._lock:
            self._counts[cls] = self._counts.get(cls, 0) + 1
            self._seen += 1
        return cls

    def mix(self) -> Dict[str, int]:
        """Class -> count for the current window (insertion order by
        first sighting; copy, safe to mutate)."""
        with self._lock:
            return dict(self._counts)

    def record_mix(self, recorder, *, reset: bool = True) -> dict:
        """Emit the windowed ``workload_mix`` metric and (by default)
        start a fresh window. Returns the record (empty window -> {})."""
        with self._lock:
            if not self._seen:
                return {}
            classes = dict(self._counts)
            seen = self._seen
            if reset:
                self._counts = {}
                self._seen = 0
        rec = {"window": seen, "distinct": len(classes),
               "classes": classes}
        if recorder is not None:
            recorder.metric("workload_mix", **rec)
        return rec


# ------------------------------------------------------------- accounting


def jain_fairness(values: Sequence[float]) -> float:
    """Jain's fairness index ``(sum x)^2 / (n * sum x^2)`` over
    per-tenant goodput: 1.0 = perfectly even, 1/n = one tenant has it
    all. Empty or all-zero inputs read as fair (1.0) — an idle service
    is not unfair."""
    vals = [float(v) for v in values]
    n = len(vals)
    total = sum(vals)
    if n == 0 or total == 0:
        return 1.0
    return (total * total) / (n * sum(v * v for v in vals))


class _TenantSlot:
    __slots__ = ("requests", "shed", "expired", "degraded", "ewma_ms",
                 "latencies_ms")

    def __init__(self):
        self.requests = 0
        self.shed = 0
        self.expired = 0
        self.degraded = 0
        self.ewma_ms = 0.0
        self.latencies_ms: List[float] = []


class TenantAccountant:
    """Per-tenant serve accounting with SLO burn and fairness.

    One slot per tenant: completed/shed/expired/degraded counts, an EWMA
    of service time (``alpha`` — recent traffic dominates), and the
    latency tail for percentile + burn math. ``record`` emits one
    ``tenant_stats`` metric per tenant; every row carries the GLOBAL
    ``fairness_index`` (Jain over per-tenant goodput) so any single row
    answers "is the service being fair right now". Thread-safe: sheds
    land from submitter threads (HTTP handlers), completions from the
    batcher thread."""

    def __init__(self, *, slo: Optional[SLOConfig] = None,
                 alpha: float = 0.2, max_latencies: int = 4096):
        self.slo = slo if slo is not None else SLOConfig()
        self.alpha = float(alpha)
        self.max_latencies = max(16, int(max_latencies))
        self._slots: Dict[str, _TenantSlot] = {}
        self._lock = threading.Lock()
        self._t_first: Optional[float] = None
        self._t_last: float = 0.0

    def _slot(self, tenant: str) -> _TenantSlot:
        s = self._slots.get(tenant)
        if s is None:
            s = self._slots[tenant] = _TenantSlot()
        return s

    def note_request(self, tenant: str, latency_ms: float, *,
                     degraded: bool = False) -> None:
        now = time.perf_counter()
        with self._lock:
            s = self._slot(tenant)
            s.requests += 1
            if degraded:
                s.degraded += 1
            s.ewma_ms = (latency_ms if s.requests == 1 else
                         self.alpha * latency_ms
                         + (1.0 - self.alpha) * s.ewma_ms)
            s.latencies_ms.append(float(latency_ms))
            if len(s.latencies_ms) > self.max_latencies:
                del s.latencies_ms[: len(s.latencies_ms) // 2]
            if self._t_first is None:
                self._t_first = now
            self._t_last = now

    def note_shed(self, tenant: str) -> None:
        with self._lock:
            self._slot(tenant).shed += 1

    def note_expired(self, tenant: str) -> None:
        with self._lock:
            self._slot(tenant).expired += 1

    def ewma_service_s(self, tenant: str) -> Optional[float]:
        """This tenant's EWMA service time in SECONDS, or None while the
        tenant is cold — the per-tenant Retry-After source the admission
        controller plugs in (``AdmissionController.service_time_for``)."""
        with self._lock:
            s = self._slots.get(tenant)
            if s is None or not s.requests:
                return None
            return s.ewma_ms / 1e3

    def _elapsed(self) -> float:
        return (self._t_last - self._t_first) \
            if self._t_first is not None else 0.0

    def fairness_index(self) -> float:
        with self._lock:
            return jain_fairness([s.requests
                                  for s in self._slots.values()])

    def tenants(self) -> List[str]:
        with self._lock:
            return sorted(self._slots)

    def stats(self) -> Dict[str, Dict[str, Any]]:
        """Per-tenant snapshot: counters, EWMA/percentile latencies,
        goodput qps over the accountant's own observation window, and
        the p99 SLO burn rate (0.0 when no SLO is set)."""
        elapsed = self._elapsed()
        fair = self.fairness_index()
        out: Dict[str, Dict[str, Any]] = {}
        with self._lock:
            items = [(t, s, list(s.latencies_ms))
                     for t, s in sorted(self._slots.items())]
        for tenant, s, lat in items:
            srt = sorted(lat)
            n = len(srt)
            burn = 0.0
            if self.slo.p99_ms and n:
                recs = slo_burn(SLOConfig(p99_ms=self.slo.p99_ms,
                                          error_budget=self.slo.error_budget),
                                lat, elapsed)
                burn = recs[0]["burn_rate"] if recs else 0.0
            out[tenant] = {
                "tenant": tenant,
                "requests": s.requests,
                "shed": s.shed,
                "expired": s.expired,
                "degraded": s.degraded,
                "ewma_ms": round(s.ewma_ms, 3),
                "p50_ms": round(srt[n // 2], 3) if n else 0.0,
                "p99_ms": round(srt[min(n - 1, int(0.99 * n))], 3)
                if n else 0.0,
                "goodput_qps": round(s.requests / elapsed, 2)
                if elapsed > 0 else 0.0,
                "burn_rate": burn,
                "fairness_index": round(fair, 4),
            }
        return out

    def record(self, recorder) -> Dict[str, Dict[str, Any]]:
        """One ``tenant_stats`` metric per tenant onto ``recorder``;
        returns the snapshot."""
        stats = self.stats()
        if recorder is not None:
            for row in stats.values():
                recorder.metric("tenant_stats", **row)
        return stats
