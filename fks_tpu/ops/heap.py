"""An exact, on-device replica of CPython's binary heap (``heapq``).

Why this exists: the reference's event queue is a ``heapq`` of
``(time, Event)`` tuples (reference: simulator/event_simulator.py:19-58), and
one of its behaviors is *layout dependent*: when a pod cannot be placed, the
retry time is taken from the first DELETION found in raw heap-array order
(event_simulator.py:51-58), not in time order. To reproduce the reference's
observable numbers exactly (snapshot counts, fragmentation series, fitness)
we replicate the heap's array layout, which requires implementing CPython's
exact sift algorithms (``heapq._siftdown`` / ``_siftup``; the C module
mirrors the pure-Python ones).

Keys are ``(time, tie_rank)`` int32 pairs compared lexicographically -- the
reference compares tuples ``(time, Event)`` where ``Event.__lt__`` is pod-id
string order (event_simulator.py:16-17); ``tie_rank`` is the precomputed rank
of the pod id in lexicographic order, so integer comparison is equivalent.
Payload is ``(kind, pod_index)`` with kind 0=CREATION, 1=DELETION.

TPU-native formulation: a sift is "insert one item into the sorted
root-to-hole chain of slots" -- the chain is at most ``ceil(log2(cap))+1``
slots, its indices are pure arithmetic (push) or a fixed-depth unrolled
smaller-child descent with a *scalar* carry (pop), and the whole mutation is
ONE gather + ONE duplicate-free scatter of <= ~14 elements. No
data-dependent ``while_loop`` ever touches the backing arrays, so the ops
cost O(log n) elements of HBM traffic per event and batch cleanly under
``vmap`` (a lane-masked op is a dropped scatter, not a full-array select).
This is what makes the engine's event loop a lean ``lax.while_loop`` body
(SURVEY.md §7 "hard parts": 2.5M scan-steps/s/chip budget).

Storage layout (round 3): the four per-item fields live as COLUMNS of one
``i32[cap, 4]`` matrix, so every heap mutation is a single row-gather plus
a single row-scatter instruction instead of four of each. On TPU,
per-lane-indexed gathers/scatters in a vmapped loop body cost serialized
latency PER INSTRUCTION (~35 us each, PROFILE.md), so
instruction count -- not bytes -- is the price; rows cut it 4x.
"""
from __future__ import annotations

import heapq
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

KIND_CREATE = 0
KIND_DELETE = 1
# Scenario fault events (fks_tpu.scenarios): cordon / uncordon a node.
# They ride the same heap with pod column = node index; the retry-rule
# scan below matches KIND_DELETE only, so fault events never become
# retry anchors (the reference has no fault vocabulary to mirror).
KIND_NODE_DOWN = 2
KIND_NODE_UP = 3

# column indices of EventHeap.data
COL_TIME, COL_RANK, COL_KIND, COL_POD = 0, 1, 2, 3


class EventHeap(NamedTuple):
    """Array-backed binary min-heap of scheduling events.

    ``data[i] == (time, rank, kind, pod)`` of heap slot ``i``; ``size`` is
    the live element count. The ``time``/``rank``/``kind``/``pod``
    properties are column views for read paths (tests, the engine's
    pending-deletion scans); mutation always goes through row ops.
    """

    data: jax.Array  # i32[cap, 4]
    size: jax.Array  # i32[] live element count

    @property
    def capacity(self) -> int:
        return self.data.shape[0]

    @property
    def levels(self) -> int:
        """Max root-to-leaf path length: ceil(log2(cap)) + 1."""
        return max(1, int(np.ceil(np.log2(max(self.capacity, 2)))) + 1)

    @property
    def time(self):
        return self.data[..., COL_TIME]

    @property
    def rank(self):
        return self.data[..., COL_RANK]

    @property
    def kind(self):
        return self.data[..., COL_KIND]

    @property
    def pod(self):
        return self.data[..., COL_POD]


def _less(ta, ra, tb, rb):
    """Lexicographic (time, rank) compare == reference tuple compare."""
    return (ta < tb) | ((ta == tb) & (ra < rb))


def heap_from_events(times, ranks, kinds, pods, capacity: int | None = None) -> EventHeap:
    """Build the initial heap on host with CPython ``heapq.heapify`` itself.

    The reference heapifies the CREATE events in pod-list order
    (event_simulator.py:23-34); running the real ``heapq`` here guarantees an
    identical starting layout. Host-side only (trace prep), so using the
    stdlib is both simplest and exact.
    """
    items = [(int(t), int(r), int(k), int(p))
             for t, r, k, p in zip(times, ranks, kinds, pods)]
    heapq.heapify(items)  # (time, rank) unique per live pod => tuple order == key order
    n = len(items)
    cap = capacity or n
    if cap < n:
        raise ValueError(f"heap capacity {cap} < {n}")
    arr = np.zeros((cap, 4), dtype=np.int64)
    if n:
        arr[:n, :] = np.array(items, dtype=np.int64)
    return EventHeap(data=jnp.asarray(arr, jnp.int32),
                     size=jnp.asarray(n, jnp.int32))


def _rows(h: EventHeap, idx):
    """Clamped row-gather of items at ``idx`` (any shape): one instruction.
    Returns ``[..., 4]`` rows."""
    i = jnp.clip(idx, 0, h.capacity - 1)
    return h.data[i]


def _scatter_rows(h: EventHeap, idx, rows, new_size) -> EventHeap:
    """Duplicate-free drop-mode row scatter; indices == cap are dropped.
    One instruction for all four fields."""
    return EventHeap(data=h.data.at[idx].set(rows, mode="drop"),
                     size=new_size)


def heap_push(h: EventHeap, time, rank, kind, pod, pred=True) -> EventHeap:
    """``heapq.heappush``; no-op when ``pred`` is False.

    CPython's ``_siftdown(heap, 0, size)`` bubbles the new item up the
    ancestor chain of the insertion slot. In a valid heap that chain is
    sorted ascending root->leaf, so the sift is equivalent to: find the
    insertion depth ``s`` = number of ancestors <= newitem, shift the deeper
    ancestors down one level, write newitem at depth ``s``. All chain
    indices are arithmetic in ``pos = size``; one gather + one scatter.
    """
    L = h.levels
    cap = jnp.int32(h.capacity)
    pos = h.size
    xt = jnp.asarray(time, jnp.int32)
    xr = jnp.asarray(rank, jnp.int32)
    xk = jnp.asarray(kind, jnp.int32)
    xp = jnp.asarray(pod, jnp.int32)
    pred = jnp.asarray(pred, bool)

    # depth of the insertion slot: e = floor(log2(pos + 1))
    pos1 = pos + 1
    e = jnp.int32(0)
    for b in range(1, L + 1):
        e = e + ((pos1 >> b) > 0).astype(jnp.int32)

    # ancestor chain root->parent(pos): q_k = ((pos+1) >> (e-k)) - 1, k < e
    ks = jnp.arange(L, dtype=jnp.int32)
    shift = jnp.clip(e - ks, 0, 31)
    q = (pos1 >> shift) - 1  # [L]; q_e == pos for k == e
    valid = ks < e
    v = _rows(h, q)  # [L, 4]
    vt, vr = v[:, COL_TIME], v[:, COL_RANK]

    # insertion depth: ancestors with key <= newitem stay above it
    s = jnp.sum((valid & ~_less(xt, xr, vt, vr)).astype(jnp.int32))

    # ancestors at depth k in [s, e) move down to q_{k+1}; newitem -> q_s.
    # q_{k+1} = 2*q_k + 1 + (child parity of the path), but simpler: the
    # chain is q itself shifted, and q_{k+1} for k<e is exactly q[k+1]
    # (q has L entries; k+1 <= e <= L-1).
    q_next = jnp.concatenate([q[1:], jnp.full((1,), cap, jnp.int32)])
    move = valid & (ks >= s) & pred
    tgt = jnp.where(move, q_next, cap)  # drop when not moving
    x_tgt = jnp.where(pred, q[jnp.minimum(s, L - 1)], cap)

    idx = jnp.concatenate([tgt, x_tgt[None]])
    x_row = jnp.stack([xt, xr, xk, xp])
    rows = jnp.concatenate([v, x_row[None, :]], axis=0)  # [L+1, 4]
    new_size = h.size + pred.astype(jnp.int32)
    return _scatter_rows(h, idx, rows, new_size)


def heap_pop(h: EventHeap, pred=True):
    """``heapq.heappop``; no-op (garbage item) when ``pred`` is False.

    CPython's pop moves the last element into the root hole and runs
    ``_siftup``: descend the smaller-child path all the way to a leaf,
    shifting each child up one level, then ``_siftdown`` the moved item
    back up that path. Net effect: insert the last element into the sorted
    root-to-leaf smaller-child chain -- items above its insertion depth
    shift up one level, items below stay put. The descent carries only a
    scalar position (unrolled, fixed depth); the mutation is one scatter.

    Caller must ensure size > 0 when ``pred`` holds. Returns (heap, item)
    with item = (time, rank, kind, pod) scalars.
    """
    L = h.levels
    cap = jnp.int32(h.capacity)
    newsize = jnp.maximum(h.size - 1, 0)
    head_last = _rows(h, jnp.stack([jnp.int32(0), newsize]))  # [2, 4]
    item = (head_last[0, COL_TIME], head_last[0, COL_RANK],
            head_last[0, COL_KIND], head_last[0, COL_POD])
    x = head_last[1]  # relocated last element
    xt, xr = x[COL_TIME], x[COL_RANK]

    # smaller-child descent from the root among live slots [0, newsize):
    # one [2, 4] row-gather per level (child + right sibling)
    qs, vrows, alive_ks = [], [], []
    pos = jnp.int32(0)
    alive = jnp.bool_(True)
    for _ in range(1, L):
        child = 2 * pos + 1
        right = child + 1
        pair = _rows(h, jnp.stack([child, right]))  # [2, 4]
        ct, cr = pair[0, COL_TIME], pair[0, COL_RANK]
        rt, rr = pair[1, COL_TIME], pair[1, COL_RANK]
        use_right = (right < newsize) & ~_less(ct, cr, rt, rr)
        cpos = jnp.where(use_right, right, child)
        alive = alive & (child < newsize)
        vrow = jnp.where(use_right, pair[1], pair[0])  # [4]
        qs.append(cpos)
        vrows.append(vrow)
        alive_ks.append(alive)
        pos = jnp.where(alive, cpos, pos)

    q = jnp.stack(qs)  # [L-1] path slots q_1..q_{L-1}
    v = jnp.stack(vrows)  # [L-1, 4]
    vt, vr = v[:, COL_TIME], v[:, COL_RANK]
    valid = jnp.stack(alive_ks)  # k <= d (live path levels)

    # insertion depth s = #{live v_k <= x}; chain ascending => suffix moves
    s = jnp.sum((valid & ~_less(xt, xr, vt, vr)).astype(jnp.int32))

    # v_k for k in [1, s] shift up to q_{k-1}; x -> q_s (q_0 = root slot 0)
    ks = 1 + jnp.arange(L - 1, dtype=jnp.int32)
    q_prev = jnp.concatenate([jnp.zeros((1,), jnp.int32), q[:-1]])
    pred = jnp.asarray(pred, bool)
    move = valid & (ks <= s) & pred
    tgt = jnp.where(move, q_prev, cap)
    x_tgt = jnp.where(
        pred, jnp.where(s > 0, q[jnp.clip(s - 1, 0, L - 2)], 0), cap)

    idx = jnp.concatenate([tgt, x_tgt[None]])
    rows = jnp.concatenate([v, x[None, :]], axis=0)  # [L, 4]
    new_size = jnp.where(pred, newsize, h.size)
    h2 = _scatter_rows(h, idx, rows, new_size)
    return h2, item


def first_deletion_in_array_order(h: EventHeap):
    """Reference ``repush_creation_event`` scan (event_simulator.py:51-58):
    the first DELETION in raw backing-array order. Returns (found, time)."""
    cap = h.capacity
    idx = jnp.arange(cap, dtype=jnp.int32)
    kind = h.data[:, COL_KIND]
    is_del = (kind == KIND_DELETE) & (idx < h.size)
    pos = jnp.argmax(is_del)  # first True in array order
    found = is_del[pos]
    return found, h.data[pos, COL_TIME]
