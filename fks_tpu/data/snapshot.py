"""A pinned snapshot of a cluster at a moment of a run: what the first
``E0`` events of a workload's run decided.

A ``Snapshot`` ``S`` of a workload ``W`` under a retry rule is ``E0`` and
the decision of every CREATE attempt among the first ``E0`` events of a
run of ``W``, in event order: the pod, and either its node and GPU set
or "no node". Which event comes when is not the snapshot's to say: it
follows from ``W``, the rule and those decisions (a placed pod's DELETE
at ``t + duration``; a refused one re-queued at ``1 +`` the earliest
pending DELETE, the flat engine's rule, and dropped when none is
pending). ``S`` is valid when replaying ``W`` under the rule meets, at
each CREATE attempt among the first ``E0`` events, the logged pod, every
logged placement is feasible on what the earlier events left, and the
log ends exactly at event ``E0`` (``replay`` is that validation, and
what the events leave behind). *Evaluating a policy on W from S is the
run of W in which the first E0 events are decided by S and every later
one by the policy*, events being CREATEs, retries and DELETEs:
everything a result reports (``events_processed``, ``scheduled_pods``,
``num_snapshots``, ``num_fragmentation_events``, ``max_nodes``, fitness)
stays a quantity of the whole run and ``SimConfig.max_steps`` stays
absolute, so a window of ``k`` events after the fork is ``max_steps =
E0 + k``. A snapshot names the rule that re-queued its refused attempts
(``rule``: ``""`` when it has none, and the prefix is then the same run
under every rule, or ``earliest_delete``); ``_check_rows`` refuses a
rule it does not know.

One definition of a forked run, for every engine. *A snapshot says what
HAPPENED: the first ``E0`` events of the run, timed by the rule the
snapshot names. A forked run is the run of ``base pods ++ query pods``
(no query pods in candidate evaluation) in which those ``E0`` events
happen as logged and every later event is the engine's own: the policy
decides each CREATE attempt and the ENGINE's retry rule re-queues each
refusal* (``earliest_delete`` on the flat engine, upstream's
``heap_array`` on the exact one). The logged rule has to be one that
reads no heap layout: upstream's rule re-queues at ``1 +`` the first
DELETE in heap-ARRAY order, and the array's layout depends on every item
in the heap, a query's own CREATEs among them (``heappop`` moves the last
item to the root, a push sifts from slot ``size``), so a log timed by it
would change its own retry times with the size of the query asked and
could not be valid for two queries. ``earliest_delete`` (``1 +`` the
earliest pending DELETE) is the same run whatever else waits in the
heap. The exact engine's heap at the fork is what CPython's ``heapq``
holds after ``heapify`` of all CREATEs (base and query, pod-list order)
and the ``E0`` logged pops and pushes, slot for slot: ``replay`` runs the
real ``heapq`` over exactly those operations and hands them on
(``Prefix.pushes``), ``heap_after`` re-runs them alone.

The snapshot is data on the workload (``Workload.snapshot``, as
``faults`` is): ``TraceParser.parse_workload(..., snapshot_file=...)``
sets it and ``initial_state`` of the flat and of the exact engine
(``fks_tpu.sim.flat``, ``fks_tpu.sim.engine``) then returns the carry
after those events, so every runner built on either forks with no
further argument, from any valid snapshot: departed, resident and
waiting pods, the waiting histogram, the fragmentation and utilization
sums as the engine's own step accumulates them. Candidate evaluation
(``CodeEvaluator``, the populations, the mesh) forks on the flat engine;
what-if serving (``ServeEngine`` / ``VMServeEngine``, whose queries
arrive after the prefix: ``fks_tpu.serve.batcher.QueryFork``) on the
exact one. The fused engine and the portfolio refuse every snapshot by
name.

File format: a CSV (plain or ``.gz``). A prefix of placed CREATEs, one
per arrival, is the header ``name,node_sn,gpus`` and a row an arrival:
the pod's ``name`` in the pod list, the node's ``sn`` in the node list,
the node's GPU slots the pod holds joined by ``|`` (empty for a pod
without GPUs). Any other prefix adds the columns ``event,rule``: a row
is one CREATE attempt with the index of its event in the run, an empty
``node_sn`` says "no node", and one last row with an empty ``name``
holds ``E0`` under ``event`` and the retry rule under ``rule``. Row
order is free; rows are put in event order when read.
``write_snapshot_csv_gz`` writes either byte for byte reproducibly (gzip
mtime 0), so a snapshot is committed and hash-pinned like a trace.
``fks_tpu.sim.flat.make_snapshot`` makes one by running a policy for
``e0`` events; ``python -m fks_tpu.cli snapshot`` rewrites the committed
ones.

Everything here is NumPy and Python on the host: an invalid snapshot
raises ``ValueError`` before any device program.
"""
from __future__ import annotations

import gzip
import heapq
from pathlib import Path
from typing import Any, NamedTuple, Optional

import numpy as np

from fks_tpu.data.entities import (
    Workload, _pytree_dataclass, gpu_spec_allows, static_field)
from fks_tpu.data.traces import TraceParser

SNAPSHOT_COLUMNS = ("name", "node_sn", "gpus")
#: the columns a prefix with a departure or a refusal adds
EVENT_COLUMNS = ("event", "rule")
#: the rule a log's refusals are timed by (the flat engine's own, and the
#: one ``replay`` re-queues under): a refused pod comes back at 1 + the
#: earliest pending DELETE, whatever the heap's layout
RETRY_RULE = "earliest_delete"
NO_EVENT = np.iinfo(np.int32).max   # no event queued (the engines' INF)


@_pytree_dataclass
class Snapshot:
    """The CREATE attempts among the first ``e0`` events of a run, in
    event order: row ``k`` is attempt ``k``."""

    pod: Any    # i32[A] pod index (input order of the pod list)
    node: Any   # i32[A] node index (row order of the node list), -1: none
    gpus: Any   # u32[A] bitmask of the node's GPU slots the pod holds
    event: Any  # i32[A] the attempt's index among the run's events
    e0: int = static_field()
    rule: str = static_field(default="")


def placed_creates(pod, node, gpus) -> Snapshot:
    """The snapshot of a prefix that is one placed CREATE an event."""
    pod = np.asarray(pod, np.int32)
    return Snapshot(pod=pod, node=np.asarray(node, np.int32),
                    gpus=np.asarray(gpus, np.uint32),
                    event=np.arange(len(pod), dtype=np.int32),
                    e0=len(pod))


class Prefix(NamedTuple):
    """What the first ``e0`` events leave behind, in whole numbers (the
    float sums are ``fks_tpu.sim.engine.fork_leaves``', in the engine's
    own dtype and order). Node axis padded; pod axis in input order."""

    e0: int
    cpu_left: np.ndarray        # i64[N]
    mem_left: np.ndarray        # i64[N]
    gpu_left: np.ndarray        # i64[N]
    gpu_milli_left: np.ndarray  # i64[N, G]
    used: np.ndarray            # i64[E0, 4] cpu, mem, GPU count, GPU milli
                                # in use after each event
    totals: np.ndarray          # i64[4] the cluster's capacity of each
    max_nodes: int              # most nodes active after any of the events
    node: np.ndarray            # i64[P] where each pod was placed, -1: not
    gpus: np.ndarray            # i64[P] bitmask of the GPU slots it took
    waiting: np.ndarray         # bool[P] refused and not placed since
    wait_milli: np.ndarray      # i64[W] gpu_milli of each waiting GPU pod
    next_event: np.ndarray      # i64[P] time of the pod's queued event
                                # (CREATE, retry or DELETE), NO_EVENT: none
    ctime: np.ndarray           # i64[P] creation time, moved by each retry
    pending: int                # pods with an event queued
    frag_free: np.ndarray       # i64[F] per refused attempt: the milli
                                # free in GPUs too small for any waiting pod
    departed: int               # DELETEs among the events
    last_time: Optional[int]    # time of event e0 - 1 (None: no event)
    pushes: list                # per event, the item (a ``heap_key``) that
                                # its pop was followed by on the event
                                # heap, or None: pushed nothing
    heap: list                  # the event heap after the events, as
                                # CPython's heapq lays it out (keys):
                                # ``heap_after`` of the CREATEs and pushes

    @property
    def refused(self) -> int:
        """Failed placements among the events."""
        return int(len(self.frag_free))


#: An item of the event heap as ONE int, ``time << 22 | rank << 2 | kind``
#: (``heap_key``). The engines' heap rows are ``(time, rank, kind, pod)``
#: and order by ``(time, rank)``, which is unique among queued items (a
#: pod has one event queued at a time and ``rank`` names the pod), so the
#: keys compare as the rows do and every ``heapq`` operation lays them out
#: alike; the pod is read back off the rank. A replay on ints allocates
#: no tuple (serving makes one a query: 6,700 tuples a query were 20 ms of
#: the collector's pauses a call of eight, my chip runs, PR 52).
KEY_RANK_BITS, KEY_KIND_BITS = 20, 2
_RANK_MASK = (1 << KEY_RANK_BITS) - 1
_KIND_MASK = (1 << KEY_KIND_BITS) - 1
_CREATE, _DELETE = 0, 1         # ``fks_tpu.ops.heap.KIND_CREATE`` / ``_DELETE``


def heap_key(time, rank, kind):
    """The key of a heap item (Python ints, or int64 arrays of them)."""
    return (time << (KEY_RANK_BITS + KEY_KIND_BITS)) \
        | (rank << KEY_KIND_BITS) | kind


def key_fields(keys):
    """``(time, rank, kind)`` of ``heap_key``s."""
    return (keys >> (KEY_RANK_BITS + KEY_KIND_BITS),
            (keys >> KEY_KIND_BITS) & _RANK_MASK, keys & _KIND_MASK)


def rekey(keys, new_rank) -> list:
    """``keys`` (a list of ``heap_key``s and Nones) with every rank ``r``
    replaced by ``new_rank[r]``; an item whose new rank is negative is
    dropped, a None stays one."""
    new_rank, out = np.asarray(new_rank).tolist(), []
    for key in keys:
        if key is None:
            out.append(None)
            continue
        t, r, kind = key_fields(key)
        if new_rank[r] >= 0:
            out.append(heap_key(t, new_rank[r], kind))
    return out


def heap_after(creates, pushes, pod_of_rank, capacity=None):
    """The exact engine's heap rows after a logged prefix of a run, slot
    for slot what CPython holds there: ``heapify`` of ``creates`` (the
    ``heap_key`` of every CREATE, in pod-list order), then one ``heappop``
    per entry of ``pushes`` followed by the ``heappush`` of that entry
    where it is a key and not None. The engine's retry rule reads the
    array in ARRAY order, so a valid heap of the same events is not
    enough; the real ``heapq`` re-runs the operations (no validation and
    no cluster arithmetic: WHICH operations a prefix makes is ``replay``'s
    to say, in ``Prefix.pushes``: a placed CREATE's DELETE, a refused
    one's retry, nothing after a DELETE or a dropped pod). The ONE replay
    of a prefix's heap beside ``replay`` itself: the exact engine's fork
    and, once per query, serving's (``creates`` then holds the query's
    CREATEs too, which is why the layout cannot be computed once: 4 ms a
    query for the 12,288 events of cpu250's moment on this sandbox's
    CPU). ``pod_of_rank[r]`` is the pod whose ``tie_rank`` is ``r``.
    Returns ``(i32[capacity, 4] rows (time, rank, kind, pod), size)``."""
    pod_of_rank = np.asarray(pod_of_rank, np.int64)
    if len(pod_of_rank) > 1 << KEY_RANK_BITS:
        raise ValueError(f"snapshot: a heap key holds ranks below "
                         f"{1 << KEY_RANK_BITS}, not {len(pod_of_rank)}")
    items = np.asarray(creates, np.int64).tolist()
    heapq.heapify(items)
    pop, push = heapq.heappop, heapq.heappush
    for key in pushes:
        pop(items)
        if key is not None:
            push(items, key)
    n = len(items)
    cap = capacity or n
    if cap < n:
        raise ValueError(f"heap capacity {cap} < {n}")
    rows = np.zeros((cap, 4), np.int32)
    t, r, kind = key_fields(np.asarray(items, np.int64))
    rows[:n] = np.stack([t, r, kind, pod_of_rank[r]], axis=1)
    return rows, n


def event_order(pods) -> np.ndarray:
    """Real pods' indices in CREATE order: ``(creation_time, tie_rank)``."""
    real = np.flatnonzero(np.asarray(pods.pod_mask))
    t = np.asarray(pods.creation_time, np.int64)[real]
    r = np.asarray(pods.tie_rank, np.int64)[real]
    return real[np.lexsort((r, t))]


def gpu_slots(snap: Snapshot, g: int) -> np.ndarray:
    """bool[A, G]: which GPU slots each attempt took."""
    bits = np.asarray(snap.gpus, np.int64)
    return ((bits[:, None] >> np.arange(g)[None, :]) & 1).astype(bool)


def _check_rows(workload: Workload, snap: Snapshot) -> None:
    """What a row says by itself: a pod, a node and GPUs that exist, as
    many GPUs as the pod asks for, a node of a GPU model the pod accepts
    (where the workload honours ``gpu_spec``), events in order below
    ``e0``."""
    c, p = workload.cluster, workload.pods
    pod = np.asarray(snap.pod, np.int64)
    node = np.asarray(snap.node, np.int64)
    event = np.asarray(snap.event, np.int64)
    placed = node >= 0
    if snap.rule not in ("", RETRY_RULE):
        raise ValueError(
            f"snapshot: made under the retry rule {snap.rule!r}; a log is "
            f"timed by {RETRY_RULE!r} (or by no rule, where it holds no "
            "refusal), the one rule that reads no heap layout")
    if ((pod < 0) | (pod >= p.p_padded)).any() or \
            not np.asarray(p.pod_mask)[pod].all():
        raise ValueError("snapshot: an attempt is not a pod of the workload")
    if ((node < -1) | (node >= c.n_padded)).any() or \
            not np.asarray(c.node_mask)[node[placed]].all():
        raise ValueError("snapshot: a resident sits on a node the cluster "
                         "does not have")
    if (np.diff(event) <= 0).any() or (len(event) and (
            event[0] < 0 or event[-1] >= snap.e0)):
        raise ValueError(
            f"snapshot: its attempts are not at rising events below "
            f"{snap.e0}, where the log ends")
    g = c.g_padded
    sel = gpu_slots(snap, g)
    bits = np.asarray(snap.gpus, np.int64)
    if (bits[~placed] != 0).any():
        i = int(np.argmax(~placed & (bits != 0)))
        raise ValueError(
            f"snapshot: pod {_name(p.pod_ids, int(pod[i]))} is refused at "
            f"attempt {i} and holds GPUs")
    ngpu = np.where(placed, np.asarray(p.num_gpu, np.int64)[pod], 0)
    absent = (sel & ~np.asarray(c.gpu_mask)[node]).any(axis=1) \
        | (bits >> g > 0)
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
    if workload.typed:
        # a workload that honours gpu_spec: the engines would never
        # have made such a placement, so the log is no run of it
        forbidden = placed & ~gpu_spec_allows(
            np.asarray(p.gpu_spec, np.int32)[pod],
            np.asarray(c.gpu_model, np.int32)[node])
        if forbidden.any():
            i = int(np.argmax(forbidden))
            raise ValueError(
                f"snapshot: pod {_name(p.pod_ids, int(pod[i]))} sits on "
                f"node {_name(c.node_ids, int(node[i]))}, whose GPU model "
                f"its gpu_spec does not name: a placement the workload's "
                "type constraints forbid")


def replay(workload: Workload, snap: Snapshot) -> Prefix:
    """Run the first ``e0`` events of ``workload`` with ``snap`` deciding
    every CREATE attempt: the whole of the validation (``ValueError`` for
    a row that names what does not exist, a pod on a node its
    ``gpu_spec`` forbids, another pod at an attempt than
    the logged one, an infeasible placement, a log that ends before or
    after event ``e0``, a run that ends before it) and the state those
    events leave. One pass in event order, the cluster's sums kept as
    they change, so an event costs the same on 16 nodes and on 1,523;
    only a refused attempt sweeps the GPUs (its fragmentation). The event
    queue is the real ``heapq`` over the ``heap_key``s of the exact
    engine's heap rows: what it holds at the end (``Prefix.heap``) and
    what each pop was followed by (``Prefix.pushes``) go with the result,
    so that the heap of the same events among OTHER pending CREATEs (a
    query's) is ``heap_after``: the pops and pushes alone, with no
    validation and no cluster arithmetic."""
    _check_rows(workload, snap)
    c, p = workload.cluster, workload.pods
    e0 = int(snap.e0)
    log_pod = np.asarray(snap.pod).tolist()
    log_node = np.asarray(snap.node).tolist()
    log_event = np.asarray(snap.event).tolist()
    log_bits = np.asarray(snap.gpus, np.int64).tolist()
    slots = [[j for j in range(c.g_padded) if b >> j & 1] for b in log_bits]

    cpu_total = np.asarray(c.cpu_total, np.int64).tolist()
    mem_total = np.asarray(c.mem_total, np.int64).tolist()
    num_gpus = np.asarray(c.num_gpus, np.int64).tolist()
    cpu_left, mem_left = list(cpu_total), list(mem_total)
    gpu_left = np.asarray(c.gpu_declared, np.int64).tolist()
    milli_left = np.asarray(c.gpu_milli_total, np.int64).copy()
    gpu_mask = np.asarray(c.gpu_mask)
    totals = np.asarray([sum(cpu_total), sum(mem_total), sum(num_gpus),
                         int(milli_left.sum())], np.int64)
    cpu, mem, ngpu, milli, dur = (np.asarray(x, np.int64).tolist() for x in (
        p.cpu, p.mem, p.num_gpu, p.gpu_milli, p.duration))
    rank = np.asarray(p.tie_rank, np.int64).tolist()
    ctime = np.asarray(p.creation_time, np.int64).tolist()

    create, delete = _CREATE, _DELETE
    real = np.flatnonzero(np.asarray(p.pod_mask)).tolist()
    pod_of_rank = {rank[i]: i for i in real}
    if len(pod_of_rank) != len(real) or any(
            not 0 <= r < 1 << KEY_RANK_BITS for r in pod_of_rank):
        raise ValueError("snapshot: the pods' tie ranks do not name them "
                         f"(distinct, below {1 << KEY_RANK_BITS})")
    queue = [heap_key(ctime[i], rank[i], create) for i in real]
    heapq.heapify(queue)
    deletes: list = []               # (time, rank) of the pending DELETEs
    pp = p.p_padded
    node, bits, held = [-1] * pp, [0] * pp, [()] * pp   # held: GPU slots
    waiting = [False] * pp
    next_event = [NO_EVENT] * pp
    for i in real:
        next_event[i] = ctime[i]
    wait_milli: dict = {}            # gpu_milli -> waiting GPU pods
    # what is in use, as the engines' step sums it: GPUs by num_gpus -
    # gpu_left, so a node that declares more than it has starts below 0
    in_use = [0, 0, sum(num_gpus) - sum(gpu_left), 0]
    active = [False] * c.n_padded
    n_active = max_nodes = departed = k = 0
    used, frag_free, pushes, t = [], [], [], None

    def touch(nd):
        nonlocal n_active
        a = (cpu_left[nd] < cpu_total[nd] or mem_left[nd] < mem_total[nd]
             or gpu_left[nd] < num_gpus[nd])
        n_active += a - active[nd]
        active[nd] = a

    for e in range(e0):
        if not queue:
            raise ValueError(
                f"snapshot: the run ends after {e} events, before event "
                f"{e0}, where the log ends")
        t, r, kind = key_fields(heapq.heappop(queue))
        i, pushed = pod_of_rank[r], None
        if kind == delete:
            if k < len(log_pod) and log_event[k] == e:
                raise ValueError(
                    f"snapshot: attempt {k} is logged at event {e}, which "
                    f"is pod {_name(p.pod_ids, i)}'s DELETE in the run and "
                    "no CREATE attempt")
            heapq.heappop(deletes)
            nd, sign, took = node[i], 1, held[i]
            next_event[i] = NO_EVENT
            departed += 1
        else:
            if k == len(log_pod) or log_event[k] != e:
                raise ValueError(
                    f"snapshot: event {e} of the run is a CREATE attempt "
                    f"of pod {_name(p.pod_ids, i)}, and the log has "
                    + ("no attempt left" if k == len(log_pod) else
                       f"its next attempt at event {log_event[k]}"))
            if log_pod[k] != i:
                raise ValueError(
                    f"snapshot: attempt {k} (event {e}) is logged for pod "
                    f"{_name(p.pod_ids, log_pod[k])}, and the run meets "
                    f"pod {_name(p.pod_ids, i)} there: the log is not "
                    "this workload's run")
            nd, sign, took = log_node[k], -1, slots[k]
            if nd < 0:
                if not waiting[i]:
                    waiting[i] = True
                    if ngpu[i] > 0:
                        wait_milli[milli[i]] = wait_milli.get(milli[i], 0) + 1
                need = min(wait_milli, default=0)
                frag_free.append(int(milli_left[
                    gpu_mask & (milli_left > 0) & (milli_left < need)].sum()))
                if deletes:
                    again = deletes[0][0] + 1
                    pushed = heap_key(again, r, create)
                    next_event[i] = ctime[i] = again
                else:           # nobody leaves: the pod is dropped
                    next_event[i] = NO_EVENT
            elif (cpu_left[nd] < cpu[i] or mem_left[nd] < mem[i]
                  or gpu_left[nd] < ngpu[i]
                  or any(milli_left[nd, j] < milli[i] for j in took)):
                raise ValueError(
                    f"snapshot: node {_name(c.node_ids, nd)} is "
                    f"over-committed at event {e} by pod "
                    f"{_name(p.pod_ids, i)} (cpu_left {cpu_left[nd]}, "
                    f"mem_left {mem_left[nd]}, gpu_left {gpu_left[nd]}, "
                    f"gpu_milli_left {milli_left[nd].tolist()}): a "
                    "placement is infeasible")
            else:
                node[i], bits[i], held[i] = nd, log_bits[k], took
                if waiting[i]:
                    waiting[i] = False
                    if ngpu[i] > 0:
                        wait_milli[milli[i]] -= 1
                        if not wait_milli[milli[i]]:
                            del wait_milli[milli[i]]
                leave = t + dur[i]
                pushed = heap_key(leave, r, delete)
                heapq.heappush(deletes, (leave, r))
                next_event[i] = leave
            k += 1
        if pushed is not None:
            heapq.heappush(queue, pushed)
        pushes.append(pushed)
        if nd >= 0:
            cpu_left[nd] += sign * cpu[i]
            mem_left[nd] += sign * mem[i]
            gpu_left[nd] += sign * ngpu[i]
            for j in took:
                milli_left[nd, j] += sign * milli[i]
            in_use[0] -= sign * cpu[i]
            in_use[1] -= sign * mem[i]
            in_use[2] -= sign * ngpu[i]
            in_use[3] -= sign * milli[i] * len(took)
            touch(nd)
        used.append(tuple(in_use))
        max_nodes = max(max_nodes, n_active)
    return Prefix(
        e0=e0, cpu_left=np.asarray(cpu_left, np.int64),
        mem_left=np.asarray(mem_left, np.int64),
        gpu_left=np.asarray(gpu_left, np.int64), gpu_milli_left=milli_left,
        used=np.asarray(used, np.int64).reshape(e0, 4), totals=totals,
        max_nodes=max_nodes, node=np.asarray(node, np.int64),
        gpus=np.asarray(bits, np.int64), waiting=np.asarray(waiting, bool),
        wait_milli=np.asarray([m for m, n in sorted(wait_milli.items())
                               for _ in range(n)], np.int64),
        next_event=np.asarray(next_event, np.int64),
        ctime=np.asarray(ctime, np.int64), pending=len(queue),
        frag_free=np.asarray(frag_free, np.int64), departed=departed,
        last_time=t, pushes=pushes, heap=queue)


def _name(ids, i: int) -> str:
    return ids[i] if i < len(ids) else f"#{i}"


def from_placements(workload: Workload, e0: int, assigned_node,
                    assigned_gpus) -> Snapshot:
    """The snapshot of the first ``e0`` arrivals, each placed at its
    CREATE, from per-pod placements (``SimResult.assigned_node`` /
    ``assigned_gpus``, input order), validated."""
    pod = event_order(workload.pods)[:int(e0)]
    snap = placed_creates(pod, np.asarray(assigned_node, np.int64)[pod],
                          np.asarray(assigned_gpus, np.int64)[pod])
    replay(workload, snap)
    return snap


def head(snap: Snapshot, e0: int) -> Snapshot:
    """The snapshot of the first ``e0`` of its events: the attempts among
    them, so every prefix of a valid snapshot is one."""
    keep = np.asarray(snap.event) < e0
    node = np.asarray(snap.node)[keep]
    return Snapshot(pod=np.asarray(snap.pod)[keep], node=node,
                    gpus=np.asarray(snap.gpus)[keep],
                    event=np.asarray(snap.event)[keep], e0=int(e0),
                    rule=snap.rule if (node < 0).any() else "")


def _is_placed_creates(snap: Snapshot) -> bool:
    """One placed CREATE an event: what the three-column file says."""
    return len(np.asarray(snap.pod)) == snap.e0 and bool(
        (np.asarray(snap.node) >= 0).all())


def snapshot_csv(workload: Workload, snap: Snapshot) -> str:
    c, p = workload.cluster, workload.pods
    plain = _is_placed_creates(snap)
    lines = [",".join(SNAPSHOT_COLUMNS if plain
                      else SNAPSHOT_COLUMNS + EVENT_COLUMNS)]
    for i, nd, bits, ev in zip(
            np.asarray(snap.pod).tolist(), np.asarray(snap.node).tolist(),
            np.asarray(snap.gpus, np.int64).tolist(),
            np.asarray(snap.event).tolist()):
        slots = "|".join(str(j) for j in range(c.g_padded) if bits >> j & 1)
        row = f"{p.pod_ids[i]},{c.node_ids[nd] if nd >= 0 else ''},{slots}"
        lines.append(row if plain else f"{row},{ev},")
    if not plain:
        lines.append(f",,,{snap.e0},{snap.rule}")
    return "\n".join(lines) + "\n"


def write_snapshot_csv_gz(workload: Workload, snap: Snapshot, path) -> None:
    """gzip with mtime 0 and no file name in the header, as the pod lists
    are written (``data.inflate.write_pods_csv_gz``)."""
    with open(path, "wb") as raw, gzip.GzipFile(
            filename="", mode="wb", fileobj=raw, mtime=0) as gz:
        gz.write(snapshot_csv(workload, snap).encode())


def load_snapshot(path, workload: Workload) -> Snapshot:
    """Read the rows (``path`` plain or with a ``.gz`` beside it) against
    the workload's own names, put them in event order and validate them
    (``replay``). Without the ``event`` column a row is an arrival, placed
    at its CREATE, and the rows are the prefix."""
    rows = TraceParser._read_csv(Path(path))
    c, p = workload.cluster, workload.pods
    pod_of = {name: i for i, name in enumerate(p.pod_ids)}
    node_of = {name: i for i, name in enumerate(c.node_ids)}
    pod, node, gpus, event, end = [], [], [], [], None
    for row in rows:
        if not row["name"] and "event" in row:
            end = (int(row["event"]), row.get("rule") or "")
            continue
        if row["name"] not in pod_of:
            raise ValueError(f"snapshot: unknown pod {row['name']!r}")
        # an empty node says "no node", where the file has attempts
        if row["node_sn"] not in node_of and (
                row["node_sn"] or "event" not in row):
            raise ValueError(f"snapshot: unknown node {row['node_sn']!r}")
        slots = [int(s) for s in (row.get("gpus") or "").split("|") if s]
        if any(s < 0 or s >= c.g_padded for s in slots):
            raise ValueError(
                f"snapshot: pod {row['name']} holds GPU slot "
                f"{max(slots)} of node {row['node_sn']}, which has at "
                f"most {c.g_padded}")
        pod.append(pod_of[row["name"]])
        node.append(node_of.get(row["node_sn"], -1))
        gpus.append(sum(1 << s for s in set(slots)))
        if "event" in row:
            event.append(int(row["event"]))
    pod = np.asarray(pod, np.int64)
    if event:
        if end is None:
            raise ValueError(
                "snapshot: the file has the event column and no last row "
                "(empty name) that says where the log ends")
        key, (e0, rule) = np.asarray(event, np.int64), end
    else:
        # arrivals: event order is arrival order; a pod named twice
        # stays twice and fails the validation
        key = np.empty(p.p_padded, np.int64)
        order = event_order(p)
        key[order] = np.arange(len(order))
        key, e0, rule = key[pod], len(pod), ""
    by_event = np.argsort(key, kind="stable")
    snap = Snapshot(
        pod=pod[by_event].astype(np.int32),
        node=np.asarray(node, np.int64)[by_event].astype(np.int32),
        gpus=np.asarray(gpus, np.int64)[by_event].astype(np.uint32),
        event=(key[by_event] if event else np.arange(len(pod))
               ).astype(np.int32), e0=int(e0), rule=rule)
    replay(workload, snap)
    return snap
