"""The multi-tenant load generator (``cli loadgen``).

Every published qps number used to come from a serial in-process loop.
``run_loadgen`` is a sustained multi-tenant arrival driver (open-loop
Poisson rates and closed-loop worker counts per tenant) over any
``send(query) -> outcome`` client — in-process ``service_client`` or
the concurrent-HTTP ``http_client`` — summarized into the four
compare-gated keys ``loadgen_qps`` / ``loadgen_p99_ms`` /
``loadgen_shed_rate`` / ``loadgen_fairness_index`` and recorded as a
``loadgen_summary`` metric.

It drives a service and ranks above one: what the service itself counts
about its traffic (``QueryFingerprinter``, ``TenantAccountant``, the SLO
burn math) lives in ``fks_tpu.serve.accounting``.
"""
from __future__ import annotations

import dataclasses
import json
import random
import threading
import time
import zlib
from typing import Any, Callable, Dict, List, Sequence

from fks_tpu.serve.accounting import jain_fairness

#: loadgen arrival modes (closed vocabulary — pinned by
#: tools/check_jsonl_schema.py against its own copy)
LOADGEN_MODES = ("open", "closed", "mixed")


# ---------------------------------------------------------------- loadgen


@dataclasses.dataclass(frozen=True)
class TenantLoad:
    """One tenant's arrival process. ``closed``: ``concurrency`` workers
    each submit-wait-repeat (throughput-seeking, self-clocking).
    ``open``: Poisson arrivals at ``rate_qps`` regardless of response
    times (latency-honest under overload — the arrival rate does not
    slow down because the server did)."""

    tenant: str
    mode: str = "closed"
    concurrency: int = 1
    rate_qps: float = 0.0
    pods_per_query: int = 2

    def __post_init__(self):
        if self.mode not in ("open", "closed"):
            raise ValueError(f"mode must be open|closed, got {self.mode!r}")
        if self.mode == "open" and self.rate_qps <= 0:
            raise ValueError("open-loop tenant needs rate_qps > 0")
        if self.mode == "closed" and self.concurrency < 1:
            raise ValueError("closed-loop tenant needs concurrency >= 1")


def parse_tenant_spec(spec: str) -> List[TenantLoad]:
    """``"a:closed:2,b:open:25"`` -> TenantLoads (third field: workers
    for closed, qps for open; optional fourth: pods per query)."""
    plan: List[TenantLoad] = []
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        bits = part.split(":")
        if len(bits) < 3:
            raise ValueError(
                f"tenant spec {part!r} needs name:mode:rate_or_workers")
        name, mode, amount = bits[0], bits[1], float(bits[2])
        pods = int(bits[3]) if len(bits) > 3 else 2
        if mode == "open":
            plan.append(TenantLoad(name, "open", rate_qps=amount,
                                   pods_per_query=pods))
        else:
            plan.append(TenantLoad(name, mode, concurrency=int(amount),
                                   pods_per_query=pods))
    if not plan:
        raise ValueError(f"empty tenant spec {spec!r}")
    return plan


def default_make_pods(load: TenantLoad, i: int) -> List[dict]:
    """Deterministic per-request pod lists: resources vary with the
    request ordinal so fingerprint classes differ across tenants but
    repeat runs are bit-identical."""
    return [{"cpu_milli": 10 + (i * 7 + j * 13) % 60,
             "memory_mib": 50 + 11 * j,
             "creation_time": j, "duration_time": 40}
            for j in range(load.pods_per_query)]


def service_client(service) -> Callable[[dict], dict]:
    """In-process client: ``submit().result()`` with shed/expiry mapped
    to outcomes (no socket — the accounting-overhead measurement path)."""
    from fks_tpu.resilience.deadline import ResilienceError

    def send(query: dict) -> dict:
        try:
            service.submit(query).result(timeout=60)
            return {"outcome": "ok"}
        except ResilienceError as e:
            return {"outcome": "shed", "reason": e.reason}
        except Exception as e:  # noqa: BLE001 — loadgen counts, not raises
            return {"outcome": "error", "reason": str(e)}
    return send


def http_client(port: int, *, host: str = "127.0.0.1",
                timeout_s: float = 30.0) -> Callable[[dict], dict]:
    """HTTP client against the serve front: POST /query, 503 -> shed
    (Retry-After honored as data, not by waiting), other non-200 ->
    error. One connection per request — loadgen measures the service,
    not a keep-alive pool."""
    import urllib.error
    import urllib.request

    url = f"http://{host}:{port}/query"

    def send(query: dict) -> dict:
        req = urllib.request.Request(
            url, data=json.dumps(query).encode(),
            headers={"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(req, timeout=timeout_s) as resp:
                resp.read()
                return {"outcome": "ok"}
        except urllib.error.HTTPError as e:
            e.read()
            if e.code == 503:
                return {"outcome": "shed",
                        "retry_after": e.headers.get("Retry-After")}
            return {"outcome": "error", "status": e.code}
        except Exception as e:  # noqa: BLE001 — loadgen counts, not raises
            return {"outcome": "error", "reason": str(e)}
    return send


def run_loadgen(send: Callable[[dict], dict], plan: Sequence[TenantLoad],
                duration_s: float, *, seed: int = 0,
                make_pods: Callable[[TenantLoad, int], List[dict]] = None,
                recorder=None) -> dict:
    """Drive the arrival plan against ``send`` for ``duration_s`` and
    summarize into the gated loadgen vocabulary.

    Closed-loop tenants run ``concurrency`` synchronous worker threads;
    open-loop tenants run one seeded-Poisson dispatcher firing each
    arrival on its own thread (arrivals never wait on responses — the
    open-loop contract; a shed answer is an outcome, not an error).
    Returns the summary dict and records it as ``loadgen_summary``."""
    make_pods = make_pods or default_make_pods
    results: List[tuple] = []  # (tenant, outcome, latency_ms)
    lock = threading.Lock()
    t_start = time.perf_counter()
    t_end = t_start + float(duration_s)

    def fire(load: TenantLoad, i: int) -> None:
        q = {"id": f"{load.tenant}-{i:05d}", "tenant": load.tenant,
             "pods": make_pods(load, i)}
        t0 = time.perf_counter()
        out = send(q)
        dt_ms = (time.perf_counter() - t0) * 1e3
        with lock:
            results.append((load.tenant, out.get("outcome", "error"),
                            dt_ms))

    threads: List[threading.Thread] = []
    arrival_threads: List[threading.Thread] = []

    def closed_worker(load: TenantLoad, w: int) -> None:
        i = w
        while time.perf_counter() < t_end:
            fire(load, i)
            i += load.concurrency

    def open_dispatcher(load: TenantLoad) -> None:
        rng = random.Random(seed ^ zlib.crc32(load.tenant.encode()))
        i = 0
        next_t = time.perf_counter()
        while True:
            next_t += rng.expovariate(load.rate_qps)
            if next_t >= t_end:
                return
            delay = next_t - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            t = threading.Thread(target=fire, args=(load, i), daemon=True)
            t.start()
            arrival_threads.append(t)
            i += 1

    for load in plan:
        if load.mode == "closed":
            for w in range(load.concurrency):
                threads.append(threading.Thread(
                    target=closed_worker, args=(load, w), daemon=True))
        else:
            threads.append(threading.Thread(
                target=open_dispatcher, args=(load,), daemon=True))
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for t in arrival_threads:  # open-loop stragglers finish their answer
        t.join(timeout=60)
    elapsed = time.perf_counter() - t_start

    modes = {load.mode for load in plan}
    mode = modes.pop() if len(modes) == 1 else "mixed"
    ok_lat = sorted(dt for _, outcome, dt in results if outcome == "ok")
    n_ok = len(ok_lat)
    n_shed = sum(1 for _, o, _ in results if o == "shed")
    n_err = sum(1 for _, o, _ in results if o == "error")
    per_tenant: Dict[str, Dict[str, Any]] = {}
    for load in plan:
        rows = [(o, dt) for t, o, dt in results if t == load.tenant]
        lat = sorted(dt for o, dt in rows if o == "ok")
        k = len(lat)
        per_tenant[load.tenant] = {
            "mode": load.mode,
            "sent": len(rows),
            "ok": k,
            "shed": sum(1 for o, _ in rows if o == "shed"),
            "errors": sum(1 for o, _ in rows if o == "error"),
            "p50_ms": round(lat[k // 2], 3) if k else 0.0,
            "p99_ms": round(lat[min(k - 1, int(0.99 * k))], 3) if k
            else 0.0,
            "goodput_qps": round(k / elapsed, 2) if elapsed > 0 else 0.0,
        }
    summary = {
        "mode": mode,
        "tenant_count": len(plan),
        "duration_s": round(elapsed, 3),
        "requests": len(results),
        "completed": n_ok,
        "shed": n_shed,
        "errors": n_err,
        "loadgen_qps": round(n_ok / elapsed, 2) if elapsed > 0 else 0.0,
        "loadgen_p50_ms": round(ok_lat[n_ok // 2], 3) if n_ok else 0.0,
        "loadgen_p99_ms": round(ok_lat[min(n_ok - 1, int(0.99 * n_ok))], 3)
        if n_ok else 0.0,
        "loadgen_shed_rate": round(n_shed / len(results), 4)
        if results else 0.0,
        "loadgen_fairness_index": round(jain_fairness(
            [v["ok"] for v in per_tenant.values()]), 4),
        "tenants": per_tenant,
    }
    if recorder is not None:
        recorder.metric("loadgen_summary", **summary)
    return summary
