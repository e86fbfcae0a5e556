"""Plain reference for a workload whose pods name the GPU models they
accept (``openb1523-gpuspec25-loaded``): OpenB's ``gpu_spec`` honoured.

*A node has a ``model`` (its CSV column; empty on a node without GPUs). A
pod's ``gpu_spec`` is a set of model names (split on ``|``; a repeated
name means nothing). A pod with a non-empty ``gpu_spec`` may be placed
only on a node whose ``model`` is in the set; for that pod every other
node is as a cordoned node is: it is no candidate of the large-cluster
rule (the first k nodes, in node order, that pass the static fit AND are
allowed), its score is 0 whatever the policy returns, and nothing else
knows of it (GPU picks, utilization and fragmentation sums, the retry
rule are untouched). A pod no allowed node takes is a failed placement
like any other. An empty ``gpu_spec`` allows every node; a name that is
no node's ``model`` allows nothing.*

It imports nothing of ``fks_tpu``: ``load_allowed`` is its own read of
the two columns from the two CSVs into ``allowed[pod] -> bool[N]``.
``plain_sim.simulate`` has no place to hand a per-pod mask to its first-k
cut, so ``simulate`` here is ONE copy of that event loop with the term
in its two places, each marked ``# TYPE``: the cut and the score gate.
Everything else is the loop's own, line for line; with every pod
unconstrained it equals ``plain_sim.simulate`` field for field
(``tests/test_gpu_spec.py``). The log-decided prefix is
``plain_sim_midrun``'s (``load_log``, ``_decided_by``, ``_check_gpus``
wrap any loop); ``validate`` and ``simulate_from`` are those of
``plain_sim_midrun`` written on this loop, and ``validate`` also holds
every logged placement to the rule. It runs FREE after the fork: it
never sees what the program placed.
"""
from __future__ import annotations

import heapq
from typing import Optional

import numpy as np

from chipbench.reference.data import _rows
from chipbench.reference.plain_sim import (
    CREATE, DELETE, Cluster, PodObj, Pods, Result, State, _best_fit_gpus,
    _feasible)
from chipbench.reference.plain_sim_midrun import (
    Log, _check_gpus, _decided_by, load_log)

__all__ = ["load_allowed", "load_log", "simulate", "simulate_from",
           "validate"]

F = np.float32


def load_allowed(cluster_csv: str, trace_csv: str) -> np.ndarray:
    """bool[P, N]: may pod ``i`` take node ``n``, from the pod list's
    ``gpu_spec`` and the node list's ``model`` (rows in file order)."""
    models = [r.get("model") or "" for r in _rows(cluster_csv)]
    by_spec: dict = {}
    out = []
    for r in _rows(trace_csv):
        spec = r.get("gpu_spec") or ""
        if spec not in by_spec:
            names = set(filter(None, spec.split("|")))
            by_spec[spec] = np.array(
                [not names or (bool(m) and m in names) for m in models],
                bool)
        out.append(by_spec[spec])
    return np.array(out, bool).reshape(len(out), len(models))


def simulate(cluster: Cluster, pods: Pods, allowed: np.ndarray, policy, *,
             retry: str = "heap_array", max_steps: Optional[int] = None,
             prefilter_k: int = 0, interval: float = 0.05,
             acc_dtype=F, decide=None) -> Result:
    """``plain_sim.simulate``'s loop with ``allowed`` (``load_allowed``)
    in the candidate cut and in the score gate."""
    F = acc_dtype  # noqa: N806 — shadows the module's float32
    if retry not in ("heap_array", "earliest_delete"):
        raise ValueError(f"unknown retry rule {retry!r}")
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
    total_gc = int(c.num_gpus.sum())
    total_gm = int(c.gpu_milli_total.sum())
    totals = (total_cpu, total_mem, total_gc, total_gm)
    denom = [F(max(t, 1)) for t in totals]

    assigned = np.full(P, -1, np.int64)
    gpu_bits = np.zeros(P, np.int64)
    waiting = np.zeros(P, bool)
    wait_milli: dict = {}   # gpu_milli -> waiting GPU pods asking for it
    snap_sums = np.zeros(4, F)
    n_snap = 0
    threshold = interval
    frag_sum = F(0)
    frag_count = 0
    events = 0
    steps = 0
    max_nodes = 0
    failed = False

    pod = PodObj()
    while heap and not failed and steps < max_steps:
        t, rk, kind, i = heapq.heappop(heap)
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
            if prefilter_k:
                # TYPE: the first k nodes that fit AND are allowed
                feas = np.nonzero(_feasible(pod, s)
                                  & allowed[i])[0][:prefilter_k]
                cand = feas if len(feas) else all_nodes[:1]
            else:
                cand = all_nodes
            scores = np.asarray(policy(pod, s, cand), np.int64)
            # TYPE: a node the pod may not take scores 0, whatever the
            # policy returns
            scores = np.where(allowed[i][cand], scores, 0)
            k = int(np.argmax(scores)) if decide is None \
                else int(decide(i, cand, scores))
            best = int(scores[k])
            node = int(cand[k]) if best > 0 else -1
            if node >= 0:
                bits, ok = _best_fit_gpus(s.gpu_milli_left[node],
                                          c.gpu_mask[node], milli, ngpu)
                if ngpu > 0 and not ok:
                    failed = True      # upstream raises here
                    break
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
                if retry == "heap_array":
                    dt = next((e[0] for e in heap if e[2] == DELETE), None)
                else:
                    dts = [e[0] for e in heap if e[2] == DELETE]
                    dt = min(dts) if dts else None
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
        truncated=truncated, steps=steps)


def validate(cluster: Cluster, pods: Pods, allowed: np.ndarray, log: Log,
             retry: str) -> Result:
    """``plain_sim_midrun.validate`` on this loop: the log is a snapshot
    of this workload under ``retry`` AND under the type rule: no logged
    placement is on a node its pod may not take, and the run of its
    decisions alone reaches event ``E0`` with every attempt used and none
    missing. Returns that run (the state at the fork)."""
    for n, (i, node, _) in enumerate(log.attempts):
        if node >= 0 and not allowed[i][node]:
            raise ValueError(
                f"the snapshot's attempt {n} puts pod {i} on node {node}, "
                "whose GPU model the pod's gpu_spec does not name")
    refused = any(node < 0 for _, node, _ in log.attempts)
    if (log.rule or refused) and log.rule != retry:
        # a log with no refusal names no rule: nothing was re-queued
        raise ValueError(f"the snapshot was made under the retry rule "
                         f"{log.rule!r}, the run is under {retry!r}")

    def none_left(pod, s, cand):
        raise ValueError(f"a CREATE attempt among the first {log.e0} "
                         "events is not in the snapshot's log")

    scores, winner, calls = _decided_by(log, none_left, None)
    res = simulate(cluster, pods, allowed, scores, retry=retry,
                   max_steps=log.e0, decide=winner)
    if res.steps != log.e0 or res.failed or calls[0] != len(log.attempts):
        raise ValueError(
            f"the snapshot's log does not end at event {log.e0}: the run "
            f"of its decisions made {res.steps} events and used "
            f"{calls[0]} of {len(log.attempts)} attempts")
    _check_gpus(log, res)
    return res


def simulate_from(cluster: Cluster, pods: Pods, allowed: np.ndarray,
                  log: Log, policy, *, retry: str = "earliest_delete",
                  max_steps: Optional[int] = None, prefilter_k: int = 0,
                  interval: float = 0.05, acc_dtype=np.float32,
                  decide=None) -> Result:
    """``simulate`` with the log (``validate``d by the caller) deciding
    its attempts and ``policy`` every later one. ``max_steps`` is
    absolute: the prefix counts."""
    scores, winner, _ = _decided_by(log, policy, decide)
    res = simulate(cluster, pods, allowed, scores, retry=retry,
                   max_steps=max_steps, prefilter_k=prefilter_k,
                   interval=interval, acc_dtype=acc_dtype, decide=winner)
    _check_gpus(log, res)
    return res
