"""Plain reference: ``plain_sim``'s loop with a fork in it, for a run whose
first ``E0`` events HAPPENED under one retry rule and whose later events
are made under another (``openb16-cpu250-midrun-snapshot``).

*A snapshot says what happened: the first E0 events of the run, timed by
the rule the snapshot names ("" or ``earliest_delete``). A forked run is
the run of ``base pods ++ query pods`` in which those E0 events happen as
logged and every later event is the engine's own: the policy decides each
CREATE attempt and the ENGINE's retry rule (``heap_array`` on the exact
engine) re-queues each refusal.*

``plain_sim.simulate`` takes one ``retry`` string for a whole run, so the
loop is written once more here, one event at a time on CPython's own
``heapq`` (so the heap at the fork is the list ``heapify`` and the logged
pops and pushes leave, slot for slot, and ``heap_array`` reads it as
upstream does). Before the fork a CREATE attempt is the log's next entry:
it has to be that pod's, a logged placement has to be feasible on what the
earlier events left and best-fit has to pick the logged GPUs, a logged
refusal is re-queued under the LOG's rule; no policy is asked. From event
``E0`` on the policy scores every candidate, the strict argmax (or
``decide``) places, and a refusal is re-queued under ``retry``. Everything
else (departures, the waiting set, fragmentation and utilization sums,
the counters, the fitness) is one loop's for the whole run, so all a
``Result`` reports is a quantity of the whole run.

It runs FREE after the fork: it never sees what the program placed.
Nothing of ``fks_tpu`` is imported; the state, the entities, feasibility
and the best-fit GPU pick are ``plain_sim``'s own.
"""
from __future__ import annotations

import heapq
from typing import Callable, Optional, Tuple

import numpy as np

from chipbench.reference.plain_sim import (
    CREATE, DELETE, Cluster, F, PodObj, Pods, Result, State,
    _best_fit_gpus, _feasible)
from chipbench.reference.plain_sim_midrun import Log

RULES = ("heap_array", "earliest_delete")


def _retry_time(heap: list, rule: str) -> Optional[int]:
    """When a refused pod comes back, less one: the first DELETE in raw
    heap-array order (upstream's rule), or the earliest pending one."""
    if rule == "heap_array":
        return next((e[0] for e in heap if e[2] == DELETE), None)
    return min((e[0] for e in heap if e[2] == DELETE), default=None)


def simulate(cluster: Cluster, pods: Pods, log: Log, policy, *,
             retry: str = "heap_array", max_steps: Optional[int] = None,
             prefilter_k: int = 0, interval: float = 0.05, acc_dtype=F,
             decide=None, at_fork: Optional[Callable] = None
             ) -> Tuple[Result, np.ndarray]:
    """The forked run of ``pods`` (``base ++ query``, the log keyed by
    position in it): ``(Result, waiting)``, ``waiting[i]`` true where pod
    ``i`` was refused and has not been placed since. ``max_steps`` is
    absolute (the prefix counts); ``decide(i, cand, scores)`` as
    ``plain_sim.simulate`` takes it, asked from the fork on;
    ``at_fork(heap)``, where given, sees the event heap as it stands
    before event ``E0`` (where the run gets that far). A log that the run
    does not meet (another pod at an attempt, an infeasible placement,
    other GPUs, an attempt missing or left over at ``E0``) is a
    ``ValueError``."""
    F = acc_dtype  # noqa: N806 — shadows the module's float32
    refused = any(node < 0 for _, node, _ in log.attempts)
    if retry not in RULES or (log.rule or refused) and log.rule not in RULES:
        raise ValueError(f"unknown retry rule {retry!r} / {log.rule!r}")
    P = pods.p
    if max_steps is None:
        max_steps = max(64, 8 * P)
    s = State(cluster)
    c = cluster
    heap = [(int(pods.creation_time[i]), int(pods.rank[i]), CREATE, i)
            for i in range(P)]
    heapq.heapify(heap)
    all_nodes = np.arange(c.n)

    total_cpu = int(c.cpu_total.sum())
    total_mem = int(c.mem_total.sum())
    total_gm = int(c.gpu_milli_total.sum())
    totals = (total_cpu, total_mem, int(c.num_gpus.sum()), total_gm)
    denom = [F(max(t, 1)) for t in totals]

    assigned = np.full(P, -1, np.int64)
    gpu_bits = np.zeros(P, np.int64)
    waiting = np.zeros(P, bool)
    wait_milli: dict = {}   # gpu_milli -> waiting GPU pods asking for it
    snap_sums = np.zeros(4, F)
    n_snap = frag_count = events = steps = max_nodes = used_log = 0
    threshold = interval
    frag_sum = F(0)
    failed = False

    pod = PodObj()
    while heap and not failed and steps < max_steps:
        if steps == log.e0 and at_fork is not None:
            at_fork(list(heap))
        t, rk, kind, i = heapq.heappop(heap)
        logged = steps < log.e0
        steps += 1
        cpu, mem = int(pods.cpu[i]), int(pods.mem[i])
        ngpu, milli = int(pods.num_gpu[i]), int(pods.gpu_milli[i])
        if kind == DELETE:
            s.apply(int(assigned[i]), +1, cpu, mem, ngpu, milli,
                    int(gpu_bits[i]))
        else:
            pod.cpu_milli, pod.memory_mib = cpu, mem
            pod.num_gpu, pod.gpu_milli = ngpu, milli
            pod.creation_time, pod.duration_time = t, int(pods.duration[i])
            if logged:
                node = _logged(log, used_log, i, pod, s)
                used_log += 1
            else:
                if prefilter_k:
                    feas = np.nonzero(_feasible(pod, s))[0][:prefilter_k]
                    cand = feas if len(feas) else all_nodes[:1]
                else:
                    cand = all_nodes
                scores = np.asarray(policy(pod, s, cand), np.int64)
                k = int(np.argmax(scores)) if decide is None \
                    else int(decide(i, cand, scores))
                node = int(cand[k]) if int(scores[k]) > 0 else -1
            if node >= 0:
                bits, ok = _best_fit_gpus(s.gpu_milli_left[node],
                                          c.gpu_mask[node], milli, ngpu)
                if ngpu > 0 and not ok:
                    failed = True      # upstream raises here
                    break
                if logged and bits != log.attempts[used_log - 1][2]:
                    raise ValueError(
                        f"the snapshot's attempt {used_log - 1} gives pod "
                        f"{i} GPUs {log.attempts[used_log - 1][2]:#b} of "
                        f"node {node}; best-fit picks {bits:#b} there")
                s.apply(node, -1, cpu, mem, ngpu, milli, bits)
                assigned[i] = node
                gpu_bits[i] = bits
                if waiting[i]:
                    waiting[i] = False
                    if ngpu > 0:
                        wait_milli[milli] -= 1
                        if not wait_milli[milli]:
                            del wait_milli[milli]
                heapq.heappush(heap, (t + int(pods.duration[i]), rk,
                                      DELETE, i))
            else:
                if not waiting[i]:
                    waiting[i] = True
                    if ngpu > 0:
                        wait_milli[milli] = wait_milli.get(milli, 0) + 1
                if wait_milli and total_gm > 0:
                    need = min(wait_milli)
                    g = s.gpu_milli_left
                    free = int(g[c.gpu_mask & (g > 0) & (g < need)].sum())
                    frag_sum = F(frag_sum + F(free) / denom[3])
                frag_count += 1
                dt = _retry_time(heap, log.rule if logged else retry)
                if dt is not None:
                    heapq.heappush(heap, (dt + 1, rk, CREATE, i))
        events += 1
        if P > 0 and events / P >= threshold:
            used = (total_cpu - s.sum_cpu, total_mem - s.sum_mem,
                    s.sum_gpu_used, total_gm - s.sum_milli)
            for a in range(4):
                if totals[a] > 0:
                    snap_sums[a] = F(snap_sums[a] + F(used[a]) / denom[a])
            n_snap += 1
            threshold += interval
        if s.n_active > max_nodes:
            max_nodes = s.n_active
    if steps >= log.e0 and used_log != len(log.attempts):
        raise ValueError(
            f"the snapshot's log does not end at event {log.e0}: the run "
            f"of its decisions used {used_log} of {len(log.attempts)} "
            "attempts by then")

    avg = (snap_sums / F(max(n_snap, 1))).astype(F)
    frag_mean = F(frag_sum / F(frag_count)) if frag_count else F(0)
    truncated = bool(heap) and not failed
    overall = F(F(F(F(avg[0] + avg[1]) + avg[2]) + avg[3]) / F(4))
    raw = F(min(max(overall - min(F(0.1), frag_mean), F(0)), F(1)))
    ok = n_snap > 0 and bool((assigned >= 0).all()) and not failed \
        and not truncated
    return Result(
        policy_score=float(raw) if ok else 0.0, avg_util=avg,
        frag_mean=float(frag_mean), num_snapshots=n_snap,
        num_frag_events=frag_count, events_processed=events,
        scheduled_pods=int((assigned >= 0).sum()), max_nodes=max_nodes,
        assigned_node=assigned, assigned_gpus=gpu_bits, failed=failed,
        truncated=truncated, steps=steps), waiting


def _logged(log: Log, n: int, i: int, pod: PodObj, s: State) -> int:
    """The node the log gives attempt ``n`` (-1: none), held to what the
    run meets there."""
    if n >= len(log.attempts):
        raise ValueError(f"a CREATE attempt among the first {log.e0} "
                         "events is not in the snapshot's log")
    who, node, _ = log.attempts[n]
    if who != i:
        raise ValueError(f"the snapshot's attempt {n} is pod {who}'s and "
                         f"the run meets pod {i} there")
    if node >= 0 and not _feasible(pod, s)[node]:
        raise ValueError(f"the snapshot's attempt {n} puts a pod on node "
                         f"{node}, which cannot hold it")
    return node


def validate(cluster: Cluster, pods: Pods, log: Log) -> Result:
    """The log is a snapshot of this workload: the run of its decisions
    alone reaches event ``E0`` with every attempt used and none missing.
    Returns that run (the state at the fork)."""
    res, _ = simulate(cluster, pods, log, None, max_steps=log.e0)
    if res.steps != log.e0 or res.failed:
        raise ValueError(
            f"the snapshot's log does not end at event {log.e0}: the run "
            f"of its decisions made {res.steps} events")
    return res
