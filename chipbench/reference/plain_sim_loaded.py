"""Plain reference for a run forked from a pinned snapshot of a loaded
cluster (``openb1523-loaded``).

*Evaluating a policy on a workload from a snapshot of E0 events is the run
of the workload in which events 0..E0-1 are decided by the snapshot and
every later event by the policy.* ``simulate_from`` is that sentence as a
loop: ``plain_sim.simulate``'s event loop, written out again, in which a
step below ``E0`` takes its node and GPUs from the snapshot's row for the
popped pod (and checks itself that the event is a CREATE, the row exists
and the placement is feasible on what the earlier rows left) and a later
step asks the policy. The heap, the evaluator's sums and every counter
are whatever the plain loop makes of the prefix: no special case, and all
a ``Result`` reports is a quantity of the whole run. It runs FREE after
the fork: it never sees what the program placed.

The snapshot file is read here with the reference's own CSV reader,
independently of ``fks_tpu.data``: rows
``name,node_sn,gpus`` keyed by the pod list's names and the node list's
``sn``, ``gpus`` the node's GPU slots joined by ``|``.
"""
from __future__ import annotations

import heapq
from typing import Dict, Optional, Tuple

import numpy as np

from chipbench.reference.data import _rows
from chipbench.reference.plain_sim import (
    CREATE, DELETE, Cluster, PodObj, Pods, Result, State, _best_fit_gpus,
    _feasible)

F = np.float32

#: pod index -> (node index, GPU bitmask)
Rows = Dict[int, Tuple[int, int]]


def load_rows(snapshot_csv: str, cluster_csv: str, trace_csv: str) -> Rows:
    """The snapshot's rows against the names of the two CSVs it is keyed
    by; an unknown or repeated name is an error."""
    pod_of = {r["name"]: i for i, r in enumerate(_rows(trace_csv))}
    node_of = {r["sn"]: i for i, r in enumerate(_rows(cluster_csv))}
    rows: Rows = {}
    for r in _rows(snapshot_csv):
        i = pod_of[r["name"]]
        if i in rows:
            raise ValueError(f"snapshot names pod {r['name']} twice")
        bits = 0
        for slot in filter(None, (r.get("gpus") or "").split("|")):
            bits |= 1 << int(slot)
        rows[i] = (node_of[r["node_sn"]], bits)
    return rows


def _snapshot_placement(rows: Rows, i: int, kind: int, pod: PodObj,
                        s: State) -> Tuple[int, int]:
    """The row's (node, bits) for event ``i`` of the prefix, checked."""
    if kind != CREATE or i not in rows:
        raise ValueError(f"the snapshot's prefix is not {len(rows)} "
                         f"CREATEs of its own pods (pod {i}, kind {kind})")
    node, bits = rows[i]
    c = s.c
    held = [j for j in range(c.gpu_mask.shape[1]) if bits >> j & 1]
    if bits >> c.gpu_mask.shape[1] or not _feasible(pod, s)[node] \
            or len(held) != pod.num_gpu or any(
                not c.gpu_mask[node, j]
                or s.gpu_milli_left[node, j] < pod.gpu_milli for j in held):
        raise ValueError(f"the snapshot's placement of pod {i} on node "
                         f"{node} (GPUs {held}) is infeasible")
    return node, bits


def simulate_from(cluster: Cluster, pods: Pods, rows: Rows, policy, *,
                  retry: str = "heap_array",
                  max_steps: Optional[int] = None, prefilter_k: int = 0,
                  interval: float = 0.05, acc_dtype=F,
                  decide=None) -> Result:
    """``plain_sim.simulate`` with steps ``< len(rows)`` decided by
    ``rows``. ``max_steps`` is absolute: the prefix counts."""
    F = acc_dtype  # noqa: N806 — shadows the module's float32
    if retry not in ("heap_array", "earliest_delete"):
        raise ValueError(f"unknown retry rule {retry!r}")
    P = pods.p
    e0 = len(rows)
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
    wait_milli: dict = {}
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
        pod.cpu_milli, pod.memory_mib = cpu, mem
        pod.num_gpu, pod.gpu_milli = ngpu, milli
        pod.creation_time, pod.duration_time = t, int(pods.duration[i])
        if steps <= e0:
            # the snapshot decides: node and GPUs from its row
            node, bits = _snapshot_placement(rows, i, kind, pod, s)
            s.apply(node, -1, cpu, mem, ngpu, milli, bits)
            assigned[i] = node
            gpu_bits[i] = bits
            heapq.heappush(heap, (t + int(pods.duration[i]), rk, DELETE, i))
        elif kind == DELETE:
            s.apply(int(assigned[i]), +1, cpu, mem, ngpu, milli,
                    int(gpu_bits[i]))
        else:
            if prefilter_k:
                feas = np.nonzero(_feasible(pod, s))[0][:prefilter_k]
                cand = feas if len(feas) else all_nodes[:1]
            else:
                cand = all_nodes
            scores = np.asarray(policy(pod, s, cand), np.int64)
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
