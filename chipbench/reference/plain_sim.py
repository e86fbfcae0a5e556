"""Plain reference: the discrete-event cluster simulator and its fitness.

Written from the description of upstream's simulator (SURVEY.md sections
2-4: ``simulator/main.py``, ``event_simulator.py``, ``evaluator.py``) in
plain Python and NumPy. It imports nothing of ``fks_tpu`` and no JAX, and
takes nothing the program has made: the cluster and the pods come from the
benchmark's own parse of the CSV files (``chipbench.reference.data``).

One event at a time, with CPython's own ``heapq`` as the event queue:

- events order by ``(time, pod-id rank)``; a CREATE scores every node with
  the policy and goes to the strict argmax (``> 0``, ties to the lowest
  node index); GPUs on the winner are picked best-fit (least free milli,
  ties by index); a DELETE is queued at ``t + duration``;
- a CREATE that no node accepts joins the waiting set, records a
  fragmentation event and is re-queued at ``1 + t(delete)``: the first
  DELETE in raw heap-array order (``retry="heap_array"``, upstream's rule,
  the program's ``exact`` engine) or the earliest pending DELETE
  (``retry="earliest_delete"``, the program's ``flat`` engine). With no
  DELETE pending the pod is dropped;
- a utilization snapshot fires whenever ``events / pods`` passes the next
  multiple of 5 % (float64 accumulation, past 100 % too);
- fitness = clamp01(mean of the four mean utilizations - min(0.1, mean
  fragmentation)), 0 when a pod stays unassigned, the run aborted on a GPU
  shortfall, or events remain (``max_steps`` reached).

``prefilter_k`` is the program's documented large-cluster rule
(``SimConfig.node_prefilter_k``): only the first k feasible nodes, in node
order, are scored.

The run is always FREE: it never sees what the program placed. The
comparison (``chipbench.reference.compare``) then asks for the same
placements, pod by pod.
"""
from __future__ import annotations

import dataclasses
import heapq
from typing import Callable, List, Optional, Sequence

import numpy as np

CREATE, DELETE = 0, 1
F = np.float32


@dataclasses.dataclass
class Cluster:
    """Real nodes only, in CSV row order."""

    cpu_total: np.ndarray      # i64[N]
    mem_total: np.ndarray      # i64[N]
    gpu_declared: np.ndarray   # i64[N] initial gpu_left (declared count)
    num_gpus: np.ndarray       # i64[N] GPUs that exist (model in the map)
    gpu_milli_total: np.ndarray  # i64[N, G], 0 where no GPU
    gpu_mask: np.ndarray       # bool[N, G]

    @property
    def n(self) -> int:
        return int(self.cpu_total.shape[0])


@dataclasses.dataclass
class Pods:
    """Real pods only, in input order. ``rank`` orders equal-time events
    (the rank of the pod id as a string, or the index for a query)."""

    cpu: np.ndarray
    mem: np.ndarray
    num_gpu: np.ndarray
    gpu_milli: np.ndarray
    creation_time: np.ndarray
    duration: np.ndarray
    rank: np.ndarray

    @property
    def p(self) -> int:
        return int(self.cpu.shape[0])

    def take(self, idx: Sequence[int], query: bool = False) -> "Pods":
        """A sub-list of pods; ``query`` re-ranks by position (a what-if
        query's pods are named by their ordinal)."""
        idx = np.asarray(idx, np.int64)
        rank = np.arange(len(idx)) if query else self.rank[idx]
        return Pods(self.cpu[idx], self.mem[idx], self.num_gpu[idx],
                    self.gpu_milli[idx], self.creation_time[idx],
                    self.duration[idx], np.asarray(rank, np.int64))


class GPUObj:
    __slots__ = ("gpu_milli_left", "gpu_milli_total")

    def __init__(self, total):
        self.gpu_milli_left = total
        self.gpu_milli_total = total


class NodeObj:
    """The entity a source policy reads (upstream ``Node``)."""

    __slots__ = ("cpu_milli_left", "cpu_milli_total", "memory_mib_left",
                 "memory_mib_total", "gpu_left", "gpus")


class PodObj:
    __slots__ = ("cpu_milli", "memory_mib", "num_gpu", "gpu_milli",
                 "creation_time", "duration_time")


@dataclasses.dataclass
class Result:
    policy_score: float
    avg_util: np.ndarray          # f32[4] cpu, mem, gpu count, gpu milli
    frag_mean: float
    num_snapshots: int
    num_frag_events: int
    events_processed: int
    scheduled_pods: int
    max_nodes: int
    assigned_node: np.ndarray     # i64[P], -1 = never placed
    assigned_gpus: np.ndarray     # i64[P] bitmask of GPU slots
    failed: bool
    truncated: bool
    steps: int


class State:
    """Mutable cluster state, as arrays and as the entity objects."""

    def __init__(self, c: Cluster):
        self.c = c
        self.cpu_left = c.cpu_total.astype(np.int64).copy()
        self.mem_left = c.mem_total.astype(np.int64).copy()
        self.gpu_left = c.gpu_declared.astype(np.int64).copy()
        self.gpu_milli_left = c.gpu_milli_total.astype(np.int64).copy()
        self.nodes: List[NodeObj] = []
        for i in range(c.n):
            nd = NodeObj()
            nd.cpu_milli_left = nd.cpu_milli_total = int(c.cpu_total[i])
            nd.memory_mib_left = nd.memory_mib_total = int(c.mem_total[i])
            nd.gpu_left = int(c.gpu_declared[i])
            nd.gpus = [GPUObj(int(c.gpu_milli_total[i, j]))
                       for j in range(int(c.num_gpus[i]))]
            self.nodes.append(nd)
        self.sum_cpu = int(self.cpu_left.sum())
        self.sum_mem = int(self.mem_left.sum())
        self.sum_gpu_used = int((c.num_gpus - self.gpu_left).sum())
        self.sum_milli = int(self.gpu_milli_left.sum())
        self.active = np.zeros(c.n, bool)
        self.n_active = 0
        for i in range(c.n):
            self._touch(i)

    def _touch(self, i: int) -> None:
        c = self.c
        a = bool(self.cpu_left[i] < c.cpu_total[i]
                 or self.mem_left[i] < c.mem_total[i]
                 or self.gpu_left[i] < c.num_gpus[i])
        if a != self.active[i]:
            self.n_active += 1 if a else -1
            self.active[i] = a

    def apply(self, i: int, sign: int, cpu: int, mem: int, ngpu: int,
              milli: int, bits: int) -> None:
        """``sign=-1`` places, ``+1`` refunds, on node ``i``."""
        self.cpu_left[i] += sign * cpu
        self.mem_left[i] += sign * mem
        self.gpu_left[i] += sign * ngpu
        self.sum_cpu += sign * cpu
        self.sum_mem += sign * mem
        self.sum_gpu_used -= sign * ngpu
        nd = self.nodes[i]
        nd.cpu_milli_left = int(self.cpu_left[i])
        nd.memory_mib_left = int(self.mem_left[i])
        nd.gpu_left = int(self.gpu_left[i])
        j = 0
        while bits:
            if bits & 1:
                self.gpu_milli_left[i, j] += sign * milli
                self.sum_milli += sign * milli
                nd.gpus[j].gpu_milli_left = int(self.gpu_milli_left[i, j])
            bits >>= 1
            j += 1
        self._touch(i)


#: a policy: (pod features, state, candidate node indices) -> int scores,
#: one per candidate
Policy = Callable[[PodObj, State, np.ndarray], np.ndarray]


def _feasible(pod: PodObj, s: State) -> np.ndarray:
    c = s.c
    eligible = (c.gpu_mask & (s.gpu_milli_left >= pod.gpu_milli)).sum(axis=1)
    ok = ((pod.cpu_milli <= s.cpu_left) & (pod.memory_mib <= s.mem_left)
          & (pod.num_gpu <= s.gpu_left))
    if pod.num_gpu > 0:
        ok &= eligible >= pod.num_gpu
    return ok


def _best_fit_gpus(milli_left: np.ndarray, mask: np.ndarray, req: int,
                   num: int):
    """(bitmask, ok): the ``num`` eligible GPUs with least free milli,
    ties by index (a stable sort, as upstream's list sort is)."""
    elig = [j for j in range(len(mask)) if mask[j] and milli_left[j] >= req]
    if len(elig) < num:
        return 0, False
    elig.sort(key=lambda j: int(milli_left[j]))
    bits = 0
    for j in elig[:num]:
        bits |= 1 << j
    return bits, True


def simulate(cluster: Cluster, pods: Pods, policy: Policy, *,
             retry: str = "heap_array", max_steps: Optional[int] = None,
             prefilter_k: int = 0, interval: float = 0.05,
             acc_dtype=F, decide=None) -> Result:
    """``acc_dtype``: the evaluator's accumulation type (utilization and
    fragmentation sums, the fitness): float32 as the configurations state.
    Only the control passes a lower one. ``decide(i, cand, scores)``,
    where given, returns for pod ``i`` the winner's position in ``cand``
    in place of the argmax (``chipbench/reference/nearties.py`` is its one user)."""
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
