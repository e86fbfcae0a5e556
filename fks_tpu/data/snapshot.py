"""A pinned snapshot of a loaded cluster: which pod sits on which node and
GPUs when evaluation starts.

A ``Snapshot`` ``S`` of a workload ``W`` is ``E0`` and, for each of the
first ``E0`` events of ``W``, the placement of its pod: node and GPU set.
It is valid when those events are CREATEs of ``E0`` distinct pods (no
DELETE or retry can fall among them: every resident's ``creation_time +
duration`` lies after the last of those arrivals) and each placement is
feasible on what the earlier ones left. *Evaluating a policy on W from S
is the run of W in which events 0..E0-1 are decided by S and every later
event by the policy*: everything a result reports (``events_processed``,
``scheduled_pods``, ``num_snapshots``, ``max_nodes``, fitness) stays a
quantity of the whole run and ``SimConfig.max_steps`` stays absolute, so
a window of ``k`` events after the fork is ``max_steps = E0 + k``.

The snapshot is data on the workload (``Workload.snapshot``, as
``faults`` is): ``TraceParser.parse_workload(..., snapshot_file=...)``
sets it and ``initial_state`` of the flat and of the exact engine
(``fks_tpu.sim.flat``, ``fks_tpu.sim.engine``) then returns the carry
after those events, so every runner built on either forks with no
further argument: candidate evaluation on the flat engine
(``CodeEvaluator``), what-if serving on the exact one (``ServeEngine`` /
``VMServeEngine``, whose queries arrive after the residents:
``fks_tpu.serve.batcher.QueryFork``). The exact engine's heap at the
fork is CPython's own, slot for slot (``fks_tpu.ops.heap.
heap_rows_after_prefix``: its retry rule reads the heap in array order).
The fused engine refuses a snapshot by name.

File format: a CSV (plain or ``.gz``) with the header ``name,node_sn,
gpus``: the pod's ``name`` in the pod list, the node's ``sn`` in the node
list, the node's GPU slots the pod holds joined by ``|`` (empty for a pod
without GPUs). Row order is free; rows are put in event order when read.
``write_snapshot_csv_gz`` writes them byte for byte reproducibly (gzip
mtime 0), so a snapshot is committed and hash-pinned like a trace.
``fks_tpu.sim.flat.make_snapshot`` makes one by running a policy for
``e0`` events; ``python -m fks_tpu.cli snapshot`` rewrites the committed
one.

Everything here is NumPy on the host: an invalid snapshot raises
``ValueError`` before any device program.
"""
from __future__ import annotations

import gzip
from pathlib import Path
from typing import Any, NamedTuple

import numpy as np

from fks_tpu.data.entities import Workload, _pytree_dataclass
from fks_tpu.data.traces import TraceParser

SNAPSHOT_COLUMNS = ("name", "node_sn", "gpus")


@_pytree_dataclass
class Snapshot:
    """The residents in event order: row ``i`` is event ``i`` of the
    workload. ``e0`` is the number of rows."""

    pod: Any   # i32[E0] pod index (input order of the pod list)
    node: Any  # i32[E0] node index (row order of the node list)
    gpus: Any  # u32[E0] bitmask of the node's GPU slots the pod holds

    @property
    def e0(self) -> int:
        return int(np.shape(self.pod)[0])


class Loaded(NamedTuple):
    """The cluster after the snapshot's placements (padded node axis)."""

    cpu_left: np.ndarray        # i64[N]
    mem_left: np.ndarray        # i64[N]
    gpu_left: np.ndarray        # i64[N]
    gpu_milli_left: np.ndarray  # i64[N, G]


def event_order(pods) -> np.ndarray:
    """Real pods' indices in CREATE order: ``(creation_time, tie_rank)``."""
    real = np.flatnonzero(np.asarray(pods.pod_mask))
    t = np.asarray(pods.creation_time, np.int64)[real]
    r = np.asarray(pods.tie_rank, np.int64)[real]
    return real[np.lexsort((r, t))]


def gpu_slots(snap: Snapshot, g: int) -> np.ndarray:
    """bool[E0, G]: which GPU slots each resident holds."""
    bits = np.asarray(snap.gpus, np.int64)
    return ((bits[:, None] >> np.arange(g)[None, :]) & 1).astype(bool)


def place_residents(workload: Workload, snap: Snapshot) -> Loaded:
    """The four ``*_left`` arrays after the snapshot's placements, and the
    whole of the validation: raises ``ValueError`` for a prefix that is
    not ``E0`` CREATEs, an unknown pod or node index, a GPU pick outside
    the node's GPUs or of the wrong count, and an over-committed node or
    GPU. Placements only take, so a snapshot whose END state holds no
    negative remainder was feasible at every step in order."""
    c, p = workload.cluster, workload.pods
    pod = np.asarray(snap.pod, np.int64)
    node = np.asarray(snap.node, np.int64)
    e0 = snap.e0
    order = event_order(p)
    if e0 > len(order):
        raise ValueError(f"snapshot: {e0} residents, the workload has "
                         f"{len(order)} pods")
    if ((pod < 0) | (pod >= p.p_padded)).any() or \
            not np.asarray(p.pod_mask)[pod].all():
        raise ValueError("snapshot: a resident is not a pod of the workload")
    if ((node < 0) | (node >= c.n_padded)).any() or \
            not np.asarray(c.node_mask)[node].all():
        raise ValueError("snapshot: a resident sits on a node the cluster "
                         "does not have")
    if not np.array_equal(pod, order[:e0]):
        raise ValueError(
            f"snapshot: its {e0} residents are not the workload's first "
            f"{e0} arrivals, one row each, in arrival order")
    ctime = np.asarray(p.creation_time, np.int64)
    rank = np.asarray(p.tie_rank, np.int64)
    if e0:
        end = ctime[pod] + np.asarray(p.duration, np.int64)[pod]
        last = pod[-1]
        early = (end < ctime[last]) | ((end == ctime[last])
                                       & (rank[pod] <= rank[last]))
        if early.any():
            i = int(np.argmax(early))
            raise ValueError(
                f"snapshot: pod {_name(p.pod_ids, int(pod[i]))} leaves at "
                f"{int(end[i])}, before arrival {e0 - 1} at "
                f"{int(ctime[last])}: the prefix is not {e0} CREATEs")

    g = c.g_padded
    sel = gpu_slots(snap, g)
    ngpu = np.asarray(p.num_gpu, np.int64)[pod]
    milli = np.asarray(p.gpu_milli, np.int64)[pod]
    absent = (sel & ~np.asarray(c.gpu_mask)[node]).any(axis=1) \
        | (np.asarray(snap.gpus, np.int64) >> g > 0)
    if absent.any():
        i = int(np.argmax(absent))
        raise ValueError(
            f"snapshot: pod {_name(p.pod_ids, int(pod[i]))} holds a GPU "
            f"that node {_name(c.node_ids, int(node[i]))} does not have")
    if (sel.sum(axis=1) != ngpu).any():
        i = int(np.argmax(sel.sum(axis=1) != ngpu))
        raise ValueError(
            f"snapshot: pod {_name(p.pod_ids, int(pod[i]))} asks for "
            f"{int(ngpu[i])} GPUs and holds {int(sel[i].sum())}")

    n = c.n_padded

    def taken(req):
        return np.bincount(node, weights=req, minlength=n).astype(np.int64)

    cpu_left = np.asarray(c.cpu_total, np.int64) \
        - taken(np.asarray(p.cpu, np.int64)[pod])
    mem_left = np.asarray(c.mem_total, np.int64) \
        - taken(np.asarray(p.mem, np.int64)[pod])
    gpu_left = np.asarray(c.gpu_declared, np.int64) - taken(ngpu)
    gpu_milli_left = np.asarray(c.gpu_milli_total, np.int64).copy()
    np.subtract.at(gpu_milli_left, node, sel * milli[:, None])
    short = (cpu_left < 0) | (mem_left < 0) | (gpu_left < 0) \
        | (gpu_milli_left < 0).any(axis=1)
    if short.any():
        i = int(np.argmax(short))
        raise ValueError(
            f"snapshot: node {_name(c.node_ids, i)} is over-committed "
            f"(cpu_left {int(cpu_left[i])}, mem_left {int(mem_left[i])}, "
            f"gpu_left {int(gpu_left[i])}, least gpu_milli_left "
            f"{int(gpu_milli_left[i].min())}): a placement is infeasible")
    return Loaded(cpu_left, mem_left, gpu_left, gpu_milli_left)


def _name(ids, i: int) -> str:
    return ids[i] if i < len(ids) else f"#{i}"


def from_placements(workload: Workload, e0: int, assigned_node,
                    assigned_gpus) -> Snapshot:
    """The snapshot of the first ``e0`` arrivals from per-pod placements
    (``SimResult.assigned_node`` / ``assigned_gpus``, input order),
    validated."""
    pod = event_order(workload.pods)[:int(e0)]
    snap = Snapshot(
        pod=pod.astype(np.int32),
        node=np.asarray(assigned_node, np.int64)[pod].astype(np.int32),
        gpus=np.asarray(assigned_gpus, np.int64)[pod].astype(np.uint32))
    place_residents(workload, snap)
    return snap


def head(snap: Snapshot, e0: int) -> Snapshot:
    """The snapshot of the first ``e0`` of its events: placements only
    take, so every prefix of a valid snapshot is one."""
    return Snapshot(pod=np.asarray(snap.pod)[:e0],
                    node=np.asarray(snap.node)[:e0],
                    gpus=np.asarray(snap.gpus)[:e0])


def snapshot_csv(workload: Workload, snap: Snapshot) -> str:
    c, p = workload.cluster, workload.pods
    lines = [",".join(SNAPSHOT_COLUMNS)]
    for i, nd, bits in zip(np.asarray(snap.pod), np.asarray(snap.node),
                           np.asarray(snap.gpus, np.int64)):
        slots = "|".join(str(j) for j in range(c.g_padded) if bits >> j & 1)
        lines.append(f"{p.pod_ids[int(i)]},{c.node_ids[int(nd)]},{slots}")
    return "\n".join(lines) + "\n"


def write_snapshot_csv_gz(workload: Workload, snap: Snapshot, path) -> None:
    """gzip with mtime 0 and no file name in the header, as the pod lists
    are written (``data.inflate.write_pods_csv_gz``)."""
    with open(path, "wb") as raw, gzip.GzipFile(
            filename="", mode="wb", fileobj=raw, mtime=0) as gz:
        gz.write(snapshot_csv(workload, snap).encode())


def load_snapshot(path, workload: Workload) -> Snapshot:
    """Read ``name,node_sn,gpus`` rows (``path`` plain or with a ``.gz``
    beside it) against the workload's own names, put them in event order
    and validate them."""
    rows = TraceParser._read_csv(Path(path))
    c, p = workload.cluster, workload.pods
    pod_of = {name: i for i, name in enumerate(p.pod_ids)}
    node_of = {name: i for i, name in enumerate(c.node_ids)}
    pod, node, gpus = [], [], []
    for row in rows:
        if row["name"] not in pod_of:
            raise ValueError(f"snapshot: unknown pod {row['name']!r}")
        if row["node_sn"] not in node_of:
            raise ValueError(f"snapshot: unknown node {row['node_sn']!r}")
        slots = [int(s) for s in (row.get("gpus") or "").split("|") if s]
        if any(s < 0 or s >= c.g_padded for s in slots):
            raise ValueError(
                f"snapshot: pod {row['name']} holds GPU slot "
                f"{max(slots)} of node {row['node_sn']}, which has at "
                f"most {c.g_padded}")
        pod.append(pod_of[row["name"]])
        node.append(node_of[row["node_sn"]])
        gpus.append(sum(1 << s for s in set(slots)))
    pod = np.asarray(pod, np.int64)
    # event order; a pod named twice stays twice and fails the validation
    rank = np.empty(p.p_padded, np.int64)
    order = event_order(p)
    rank[order] = np.arange(len(order))
    by_event = np.argsort(rank[pod], kind="stable") if len(pod) else pod
    snap = Snapshot(pod=pod[by_event].astype(np.int32),
                    node=np.asarray(node, np.int64)[by_event]
                    .astype(np.int32),
                    gpus=np.asarray(gpus, np.int64)[by_event]
                    .astype(np.uint32))
    place_residents(workload, snap)
    return snap
