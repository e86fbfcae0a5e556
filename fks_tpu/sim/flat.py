"""The flat (slot-per-pod) event-queue engine — the TPU throughput path.

Why a second engine: the exact engine (fks_tpu.sim.engine) replicates the
reference's CPython heap bit-for-bit (required for the layout-dependent
retry rule, reference: simulator/event_simulator.py:51-58), but heap sifts
are chains of ~14 dependent tiny gather/scatters per event — the worst
possible shape for a TPU. Measurement on a v5e chip (PROFILE.md) showed something stronger: EVERY per-lane-indexed scatter or
gather in a vmapped loop body costs ~35 us/step of serialized latency,
while full-array vector passes (reduces, dense blends) run at HBM
bandwidth. So this engine is built from exactly two kinds of op:

- **Full-sweep pops.** One slot per pod (a pod has at most ONE pending
  event: CREATE / retried CREATE / DELETE), ``ev_time[Q]`` with INF for
  empty. Slots are ordered by ``tie_rank`` (pod-id string rank, the
  reference's equal-time tie-break, event_simulator.py:16-17), so the next
  event is simply ``argmin(ev_time)`` — argmin's first-index tie rule IS
  the reference's tie rule, with no rank array and no lexicographic
  two-pass reduce.
- **Dense one-hot blends.** Every state write (the popped slot's rewrite,
  node refunds/placements, the waiting histogram) is a predicated
  full-array ``where``, never a scatter. XLA fuses the blends that share a
  mask into single bandwidth-bound passes.

A companion ``aux[Q]`` array carries each pod's scheduling state in one
int32: -1 = CREATE pending / never placed, -2 = in the waiting set
(failed at least once), >= 0 = placed, packed ``(node << G) | gpu_bits``
(falls back to a separate gpu-bits array when node_bits + G > 31). The
pop's kind test, the pending-DELETE minimum for the retry rule, the
was-waiting flag, and the final assigned/unassigned verdict all read this
one array, so the whole step touches O(Q) bytes across ~3 fused passes.

Pop order is EXACTLY the reference's wherever the reference's own order is
well-defined: keys ``(time, tie_rank)`` are unique per pod, and a pod's
CREATE always precedes its own DELETE because the DELETE only enters the
queue when the CREATE is placed (event_simulator.py:45-49).

Divergence from the reference, by design (SURVEY.md §7 explicitly blesses
this): the retry time for an unplaceable pod is ``1 + (earliest pending
DELETE time)`` instead of ``1 + (first DELETE in raw heap-ARRAY order)``,
which is an artifact of CPython heapq's layout. Instrumenting the
reference shows its scan lands on the time-earliest pending delete in the
median case (mean rank 0.8), so the time-order rule is both principled
AND the closest match; residual fitness deltas on the default trace's
published policies are chaotic (any single different retry snowballs) and
measured at |d| <= 0.029 (PROFILE.md).
Everything else (placement, refunds, fragmentation, snapshot overshoot,
fitness) is shared with or identical to the exact engine, so:

- runs with ZERO failed placements are bit-identical to the exact engine
  (and therefore to the reference) — enforced by differential tests;
- runs with retries differ only in retry timing; the exact engine remains
  the parity/golden path.

Like the reference, a pod that fails placement when NO deletion is pending
is silently dropped (event_simulator.py:51-58 falls through) -> unassigned
-> fitness 0.

Degenerate candidates that refuse many placements retry once per fired
deletion (quadratic event count — the reference grinds through the same
blowup without a cap); under the default ``max_steps_factor`` such runs
hit the step budget and score 0 with ``truncated=True``. The earliest-
delete rule reaches the cap somewhat more often than the exact engine's
array-order rule. Raise ``SimConfig.max_steps_factor`` when strict
handling of pathological candidates matters more than bounding their
cost.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from fks_tpu.data.entities import Workload
from fks_tpu.ops.allocator import best_fit_gpus, first_fit_gpus
from fks_tpu.ops.heap import KIND_NODE_UP
from fks_tpu.sim.engine import (
    FEAT_GPU_SPEC, PREFILTER_MIN_NODES, SimConfig, _audit,
    _gather_node_view, _node_view, _prefilter_candidates, _trace_append,
    _widest_int, finalize_fields, fork_leaves, fork_prefix, loop_tables,
    place_mask_at, place_mask_of, run_batched_lanes,
)
from fks_tpu.sim.guards import guard_scores
from fks_tpu.sim.types import FlatState, PodView, PolicyFn, SimResult, empty_trace
from fks_tpu.utils.segments import segment_budget, validate_seg_steps

INF = jnp.iinfo(jnp.int32).max  # empty-slot sentinel

# aux[q] scheduling-state encoding (one int32 per pod)
AUX_FRESH = -1    # CREATE pending, never failed
AUX_WAITING = -2  # retried CREATE pending or dropped (in the waiting set)
# aux >= 0: placed -- (node << G) | gpu_bits when packable, else node index


def _node_bits(n_padded: int) -> int:
    return max(1, (max(n_padded, 1) - 1).bit_length())


def _packable(n_padded: int, g_padded: int) -> bool:
    """Can (node, gpu_bits) share one non-negative int32?"""
    return _node_bits(n_padded) + g_padded <= 31


def _pack_dtypes(cfg: SimConfig, c, p) -> dict:
    """Per-column carry dtypes under ``SimConfig.state_pack`` (flat engine
    only). Packing is strictly EXACT: a column narrows to 16 bits only
    when its full value range provably fits at this workload's shape —
    per-GPU milli capacity <= 32767 for ``gpu_milli_left``, declared GPU
    count for ``gpu_left``, pod count for ``wait_hist`` (bucket counts
    cannot exceed waiting pods), node/GPU encoding width for ``aux`` /
    ``aux_gpus`` (the -1/-2 sentinels need the sign bit, so the packed
    encoding must fit 14 value bits). Columns that cannot prove their
    range stay int32 — the knob degrades shape-by-shape to a no-op, never
    to wraparound. Step arithmetic still promotes to int32 (so policies
    always see int32 views); only the while_loop CARRY narrows, halving
    its bandwidth for these columns. With ``state_pack=False`` every
    entry is the historical int32/uint32 and the compiled program is
    bit-identical."""
    i32, u32 = jnp.int32, jnp.uint32
    if not cfg.state_pack:
        return dict(aux=i32, aux_gpus=u32, wait_hist=i32,
                    gpu_left=i32, gpu_milli_left=i32)
    n, g = c.n_padded, c.g_padded
    if _packable(n, g):
        aux_fits = _node_bits(n) + g <= 14
    else:
        aux_fits = n <= 32767  # unpacked aux holds a bare node index
    max_pg_milli = int(np.asarray(c.gpu_milli_total).max(initial=0))
    max_gd = int(np.asarray(c.gpu_declared).max(initial=0))
    num_real = int(np.asarray(p.pod_mask).sum())
    return dict(
        aux=jnp.int16 if aux_fits else i32,
        aux_gpus=jnp.uint16 if g <= 16 else u32,
        wait_hist=jnp.int16 if num_real <= 32767 else i32,
        gpu_left=jnp.int16 if max_gd <= 32767 else i32,
        gpu_milli_left=jnp.int16 if max_pg_milli <= 32767 else i32,
    )


def _rank_perm(pod_mask, tie_rank):
    """Slot order: real pods by ascending tie_rank, padding last. Stable
    argsort, so host (numpy) and device (jnp) agree for the same input."""
    if isinstance(pod_mask, np.ndarray):
        key = np.where(pod_mask, tie_rank, INF)
        return np.argsort(key, kind="stable").astype(np.int32)
    key = jnp.where(pod_mask, tie_rank, INF)
    return jnp.argsort(key, stable=True).astype(jnp.int32)


def initial_state(workload: Workload, cfg: SimConfig) -> FlatState:
    """t=0 carry: every real pod's slot (in tie-rank order) holds its
    CREATE time; ``aux`` starts at AUX_FRESH. A workload with a
    ``snapshot`` (``fks_tpu.data.snapshot``) gives the carry AFTER the
    snapshot's ``E0`` events instead, whatever they are (CREATEs placed
    or refused, retries, DELETEs), leaf for leaf and bit for bit what
    ``build_step`` reaches after ``E0`` steps under a policy that makes
    the logged decisions: every runner that starts from here forks from
    that moment of the run. The events are replayed on the host, so an
    invalid snapshot is a ``ValueError`` before any device program."""
    c, p = workload.cluster, workload.pods
    pp = p.p_padded
    pm = np.asarray(p.pod_mask)
    perm = _rank_perm(pm, np.asarray(p.tie_rank))
    r_mask = pm[perm]
    ev_time = np.where(r_mask, np.asarray(p.creation_time)[perm], INF)
    packed = _packable(c.n_padded, c.g_padded)

    max_milli = int(np.asarray(p.gpu_milli).max(initial=0))
    hist_size = (cfg.wait_hist_size if cfg.wait_hist_size is not None
                 else max(1001, max_milli + 2))
    if hist_size <= max_milli:
        raise ValueError(
            f"wait_hist_size {hist_size} <= trace max gpu_milli; "
            "fragmentation min_needed would be miscounted")
    f = cfg.score_dtype
    dt = _pack_dtypes(cfg, c, p)
    state = FlatState(
        ev_time=jnp.asarray(ev_time, jnp.int32),
        aux=jnp.full(pp, AUX_FRESH, dt["aux"]),
        aux_gpus=None if packed else jnp.zeros(pp, dt["aux_gpus"]),
        pending=jnp.int32(int(pm.sum())),
        cpu_left=jnp.asarray(c.cpu_total, jnp.int32),
        mem_left=jnp.asarray(c.mem_total, jnp.int32),
        gpu_left=jnp.asarray(c.gpu_declared, dt["gpu_left"]),
        gpu_milli_left=jnp.asarray(c.gpu_milli_total, dt["gpu_milli_left"]),
        pod_ctime=jnp.asarray(np.asarray(p.creation_time)[perm], jnp.int32),
        wait_hist=jnp.zeros(hist_size, dt["wait_hist"]),
        events_processed=jnp.int32(0),
        snap_idx=jnp.int32(0),
        snap_sums=jnp.zeros(4, f),
        frag_sum=jnp.asarray(0, f),
        frag_count=jnp.int32(0),
        max_nodes=jnp.int32(0),
        failed=jnp.bool_(False),
        steps=jnp.int32(0),
        violations=jnp.int32(0),
        numeric_flags=jnp.int32(0),
        trace=(empty_trace(cfg.resolve_trace_len(workload.num_pods), f)
               if cfg.decision_trace else None),
        fault_time=None if workload.faults is None else jnp.where(
            jnp.asarray(workload.faults.mask),
            jnp.asarray(workload.faults.time, jnp.int32), INF),
        node_avail=(None if workload.faults is None
                    else jnp.ones(c.n_padded, bool)),
    )
    if workload.snapshot is None:
        return state
    return state._replace(**_loaded_leaves(workload, cfg, perm, dt))


def _loaded_leaves(workload: Workload, cfg: SimConfig, perm, dt: dict) -> dict:
    """The leaves of the carry that the snapshot's ``E0`` events change,
    from ONE host replay of them (``sim.engine.fork_prefix``). The
    cluster, the counters, the waiting histogram and the evaluator's sums
    are ``sim.engine.fork_leaves``' (one arithmetic for both engines);
    this engine's own are the pods' slots: a pod that has not arrived
    holds its CREATE, a resident its DELETE, a waiting pod its retry
    (``AUX_WAITING``, its ``pod_ctime`` moved), a departed or dropped one
    nothing, and a placed pod keeps its node and GPUs after it has gone."""
    c, p = workload.cluster, workload.pods
    prefix = fork_prefix(workload)
    g = c.g_padded
    packed = _packable(c.n_padded, g)
    aux = np.where(prefix.node >= 0,
                   (prefix.node << g) | prefix.gpus if packed else prefix.node,
                   np.where(prefix.waiting, AUX_WAITING, AUX_FRESH))
    out = dict(ev_time=jnp.asarray(prefix.next_event[perm], jnp.int32),
               aux=jnp.asarray(aux[perm], dt["aux"]),
               pending=jnp.int32(prefix.pending))
    if not packed:
        out["aux_gpus"] = jnp.asarray(prefix.gpus[perm], dt["aux_gpus"])
    if cfg.track_ctime:
        out["pod_ctime"] = jnp.asarray(prefix.ctime[perm], jnp.int32)
    for name, leaf in fork_leaves(workload, cfg, prefix).items():
        out[name] = jnp.asarray(leaf, dt.get(name, leaf.dtype))
    return out


def fork_counts(workload: Workload, state: FlatState) -> dict:
    """What a forked carry holds, read off its slots (the fields of the
    ``tier/fork_state`` span): pods placed and not gone, the nodes they
    sit on, pods gone, waiting pods whose retry is queued (and those of
    them that carry a GPU-type constraint), and the failed placements
    among the events."""
    c, p = workload.cluster, workload.pods
    aux = np.asarray(state.aux, np.int64)
    queued = np.asarray(state.ev_time) < INF
    placed = aux >= 0
    on = aux[placed & queued]
    if _packable(c.n_padded, c.g_padded):
        on = on >> c.g_padded
    waiting = (aux == AUX_WAITING) & queued
    spec = np.asarray(p.gpu_spec)[_rank_perm(
        np.asarray(p.pod_mask), np.asarray(p.tie_rank))] \
        if workload.typed else np.zeros(len(aux), np.int32)
    return dict(residents=int((placed & queued).sum()),
                nodes_loaded=int(len(np.unique(on))),
                departed=int((placed & ~queued).sum()),
                waiting=int(waiting.sum()),
                typed_waiting=int((waiting & (spec != 0)).sum()),
                prefix_failed=int(state.frag_count))


def _node_row(gpu_milli_left, gpu_mask, w):
    """Node ``w``'s rows of the two ``[N, G]`` grids. On a node axis short
    enough to sweep (under ``PREFILTER_MIN_NODES``, a static size) the row
    is a masked sum over the node planes, not a gather: one dense pass over
    a grid the step passes over anyway, where a gather pins the grid's
    layout to G-minor under the population ``vmap``, 8 GPUs on the chip's
    128 lanes (PERF.md section 6, PR 41). Exact either way."""
    n = gpu_milli_left.shape[0]
    if n >= PREFILTER_MIN_NODES:
        return gpu_milli_left[w], gpu_mask[w]
    at_w = (jnp.arange(n, dtype=jnp.int32) == w)[:, None]
    return (jnp.sum(jnp.where(at_w, gpu_milli_left, 0), axis=0,
                    dtype=gpu_milli_left.dtype),
            jnp.any(at_w & gpu_mask, axis=0))


def lane_active(s: FlatState, max_steps: int):
    """Termination predicate (single source of truth for the loop cond and
    the step's self-masking). ``pending`` counts live slots, maintained
    incrementally so neither the cond nor the predicate needs a full
    ev_time sweep. Unconsumed fault events keep the lane live too (the
    exact engine's heap counts them the same way), so trailing NODE_UP
    events drain in both engines."""
    live = s.pending > 0
    if s.fault_time is not None:
        live = live | (jnp.min(s.fault_time, axis=-1) < INF)
    return live & ~s.failed & (s.steps < max_steps)


def build_step(workload: Workload, policy: PolicyFn, cfg: SimConfig,
               ktable, max_steps: int) -> Callable[[FlatState], FlatState]:
    """One event. Self-masking like the exact engine's step, so the
    population layer can run ONE while_loop over vmapped lanes.

    ``workload`` arrays may be tracers (multi-trace batching); everything
    derived from them (the rank permutation, permuted pod features, totals)
    is loop-invariant, so XLA hoists it out of the while_loop either way.
    """
    c, p = workload.cluster, workload.pods
    c = jax.tree_util.tree_map(jnp.asarray, c)
    p = jax.tree_util.tree_map(jnp.asarray, p)
    pp = p.p_padded
    n = workload.cluster.n_padded
    g = workload.cluster.g_padded
    f = cfg.score_dtype
    alloc = best_fit_gpus if cfg.gpu_allocator == "best_fit" else first_fit_gpus
    packed = _packable(n, g)
    total_cpu = jnp.sum(c.cpu_total)
    total_mem = jnp.sum(c.mem_total)
    total_gc = jnp.sum(c.num_gpus)
    total_gm = jnp.sum(c.gpu_milli_total)
    g_iota = jnp.arange(g, dtype=jnp.uint32)
    n_iota = jnp.arange(n, dtype=jnp.int32)
    q_iota = jnp.arange(pp, dtype=jnp.int32)
    ktable = jnp.asarray(ktable, jnp.int32)
    klen = ktable.shape[0]

    # pod features permuted into slot (tie-rank) order, packed into one
    # gather table so the pop costs a single [8]-row read
    perm = _rank_perm(p.pod_mask, p.tie_rank)
    # Python-static gating, as has_faults below: the gpu_spec word rides
    # the row only where the workload is typed (engine.FEAT_GPU_SPEC)
    has_types = workload.typed
    feat = jnp.stack([
        p.cpu[perm], p.mem[perm], p.num_gpu[perm], p.gpu_milli[perm],
        p.duration[perm],
        p.gpu_spec[perm] if has_types else jnp.zeros(pp, jnp.int32),
        jnp.zeros(pp, jnp.int32),
        jnp.zeros(pp, jnp.int32)], axis=-1).astype(jnp.int32)  # [Q, 8]
    if cfg.validate_invariants:
        import dataclasses as _dc
        p_rank = _dc.replace(
            p, cpu=p.cpu[perm], mem=p.mem[perm], num_gpu=p.num_gpu[perm],
            gpu_milli=p.gpu_milli[perm], creation_time=p.creation_time[perm],
            duration=p.duration[perm], tie_rank=p.tie_rank[perm],
            pod_mask=p.pod_mask[perm],
            gpu_spec=p.gpu_spec[perm] if has_types else None)

    # Python-static fault gating (like watchdog/decision_trace): fault-free
    # workloads compile to the exact pre-scenario program.
    has_faults = workload.faults is not None
    if has_faults:
        flt = jax.tree_util.tree_map(jnp.asarray, workload.faults)
        f_iota = jnp.arange(flt.time.shape[0], dtype=jnp.int32)
    # large-cluster scale tier: 0 = dense sweep (bit-identical program)
    prefilter_k = cfg.resolve_prefilter_k(n)

    def step(s: FlatState) -> FlatState:
        active = lane_active(s, max_steps)

        # ---- pop + retry-rule minimum: ONE fused sweep over ev_time/aux.
        # Slot order == tie-rank order, so argmin's first-index tie-break
        # IS the reference's pod-id tie rule (event_simulator.py:16-17).
        t = jnp.min(s.ev_time)
        sidx = jnp.argmin(s.ev_time).astype(jnp.int32)
        next_del = jnp.min(jnp.where(s.aux >= 0, s.ev_time, INF))

        if has_faults:
            # fault-vs-pod arbitration: the earliest unconsumed fault wins
            # ties against equal-time pod events (the exact engine gives
            # faults negative tie ranks), and argmin's first-index rule
            # among equal-time faults matches their heap rank order
            fidx = jnp.argmin(s.fault_time).astype(jnp.int32)
            take_fault = active & (s.fault_time[fidx] <= t)
            fault_node = flt.node[fidx]
            fault_is_up = flt.kind[fidx] == KIND_NODE_UP
            pod_act = active & ~take_fault
        else:
            pod_act = active

        pf = feat[sidx]  # [8]
        pcpu, pmem, pngpu, pmilli, pdur = pf[0], pf[1], pf[2], pf[3], pf[4]
        aux_s = s.aux[sidx]
        is_del = pod_act & (aux_s >= 0)
        create = pod_act & (aux_s < 0)
        was_waiting = aux_s == AUX_WAITING

        if packed:
            held_node = aux_s >> g
            held_bits = (aux_s & ((1 << g) - 1)).astype(jnp.uint32)
        else:
            held_node = aux_s
            held_bits = s.aux_gpus[sidx]

        # ---- DELETION: refund resources (reference main.py:74-99).
        # Node-array updates are DENSE one-hot adds over the tiny node
        # axis, never scatters.
        a = jnp.where(is_del, held_node, 0)
        di = is_del.astype(jnp.int32)
        oh_a = (n_iota == a).astype(jnp.int32) * di  # [N]
        cpu_left = s.cpu_left + oh_a * pcpu
        mem_left = s.mem_left + oh_a * pmem
        gpu_left = s.gpu_left + oh_a * pngpu
        sel_bits = ((held_bits >> g_iota) & 1).astype(jnp.int32)  # [G]
        gpu_milli_left = s.gpu_milli_left + oh_a[:, None] * pmilli * sel_bits[None, :]

        # ---- FAULT: consume the event + flip the cordon bit (dense blends)
        fault_time = s.fault_time
        node_avail = s.node_avail
        if has_faults:
            fault_time = jnp.where((f_iota == fidx) & take_fault, INF,
                                   s.fault_time)
            oh_f = n_iota == jnp.where(take_fault, fault_node, jnp.int32(n))
            node_avail = jnp.where(oh_f, fault_is_up, node_avail)

        # ---- CREATION: strict argmax placement (main.py:101-111).
        # creation_time == pop time for both fresh and retried pods (the
        # reference mutates pod.creation_time to the retry time, so at pop
        # it always equals the event time).
        pod_view = PodView(pcpu, pmem, pngpu, pmilli, t, pdur)
        node_view = _node_view(c, cpu_left, mem_left, gpu_left, gpu_milli_left)
        spec = pf[FEAT_GPU_SPEC] if has_types else None
        if prefilter_k:
            # a cordoned (downed) node scores 0 until NODE_UP, and so
            # does, for this pod, a node whose GPU model its gpu_spec does
            # not name — under the prefilter neither may outrank a
            # feasible candidate, so the mask feeds the ranking itself
            place_mask = place_mask_of(c, node_avail, spec)
            cand = _prefilter_candidates(
                pod_view, node_view, place_mask, prefilter_k)
            node_view = _gather_node_view(node_view, cand)
        if cfg.cond_policy:
            out = jax.eval_shape(policy, pod_view, node_view)
            raw_scores = jax.lax.cond(
                create, lambda: jnp.asarray(policy(pod_view, node_view)),
                lambda: jnp.zeros(out.shape, out.dtype))
        else:
            raw_scores = policy(pod_view, node_view)
        raw_scores, numeric_flags = guard_scores(
            raw_scores, create, s.numeric_flags, enabled=cfg.watchdog)
        if prefilter_k:
            # re-mask through the gather: when fewer than k nodes are
            # feasible the candidate tail is padding (cordoned and
            # forbidden nodes included) — zero those slots whatever the
            # policy scored
            scores = jnp.where(
                place_mask_at(c, place_mask, node_avail, spec, cand),
                raw_scores, 0)
        else:
            # a cordoned or forbidden node scores 0 — "cannot/refuse"
            place_mask = place_mask_of(c, node_avail, spec)
            scores = jnp.where(place_mask, raw_scores, 0)
        # wk indexes the scored view ([k] candidates or [N] nodes);
        # w is always the GLOBAL node index (gather-back through cand)
        wk = jnp.argmax(scores).astype(jnp.int32)
        w = cand[wk] if prefilter_k else wk
        placed = create & (scores[wk] > 0)

        sel, ok = alloc(*_node_row(gpu_milli_left, c.gpu_mask, w),
                        pmilli, pngpu)
        alloc_fail = placed & (pngpu > 0) & ~ok  # reference raises here
        pl = placed & ~alloc_fail
        pli = pl.astype(jnp.int32)
        oh_w = (n_iota == w).astype(jnp.int32) * pli  # [N]
        cpu_left = cpu_left - oh_w * pcpu
        mem_left = mem_left - oh_w * pmem
        gpu_left = gpu_left - oh_w * pngpu
        gpu_milli_left = gpu_milli_left - (
            oh_w[:, None] * pmilli * sel.astype(jnp.int32)[None, :])
        new_bits = jnp.sum(jnp.where(sel, jnp.uint32(1) << g_iota,
                                     jnp.uint32(0)), dtype=jnp.uint32)
        # packed-carry handoff (SimConfig.state_pack): the refund/placement
        # arithmetic above promotes to int32 (policies always see int32
        # views); narrow back to the carry dtype. The Python guards keep
        # the unpacked path contributing zero jaxpr equations.
        if gpu_left.dtype != s.gpu_left.dtype:
            gpu_left = gpu_left.astype(s.gpu_left.dtype)
        if gpu_milli_left.dtype != s.gpu_milli_left.dtype:
            gpu_milli_left = gpu_milli_left.astype(s.gpu_milli_left.dtype)

        # ---- failed creation: waiting set + fragmentation + retry
        failp = create & ~placed
        bucket = jnp.clip(pmilli, 0, s.wait_hist.shape[0] - 1)
        hdelta = ((failp & ~was_waiting & (pngpu > 0)).astype(jnp.int32)
                  - (pl & was_waiting & (pngpu > 0)).astype(jnp.int32))
        h_iota = jnp.arange(s.wait_hist.shape[0], dtype=jnp.int32)
        hist = s.wait_hist + (h_iota == bucket).astype(jnp.int32) * hdelta
        if hist.dtype != s.wait_hist.dtype:  # state_pack carry handoff
            hist = hist.astype(s.wait_hist.dtype)

        hvals = hist > 0
        has_gpu_waiting = jnp.any(hvals)
        min_needed = jnp.argmax(hvals).astype(jnp.int32)
        frag_free = jnp.where(
            c.gpu_mask & (gpu_milli_left > 0) & (gpu_milli_left < min_needed),
            gpu_milli_left, 0)
        frag_score = jnp.where(
            has_gpu_waiting & (total_gm > 0),
            jnp.sum(frag_free, dtype=_widest_int()).astype(f)
            / jnp.maximum(total_gm, 1).astype(f),
            jnp.asarray(0, f))
        frag_sum = s.frag_sum + jnp.where(failp, frag_score, 0)
        frag_count = s.frag_count + failp.astype(jnp.int32)

        # retry rule (defined semantics; see module docstring): 1 + the
        # EARLIEST pending DELETE time. ``next_del`` is from the pre-step
        # sweep, which is exactly the post-pop pending-delete set (the
        # popped event is a CREATE here, and this step adds no deletes
        # before the reference's scan point).
        found = next_del < INF
        retry = failp & found
        dropped = failp & ~found
        rt = next_del + 1

        # ---- slot rewrite + pod bookkeeping: one fused blend pass
        new_t = jnp.where(pl, t + pdur, jnp.where(retry, rt, INF))
        if packed:
            enc = (w << g) | new_bits.astype(jnp.int32)
        else:
            enc = w
        new_aux = jnp.where(pl, enc, jnp.where(failp, AUX_WAITING, aux_s))
        if new_aux.dtype != s.aux.dtype:  # state_pack carry handoff
            new_aux = new_aux.astype(s.aux.dtype)
        m = (q_iota == sidx) & pod_act
        ev_time = jnp.where(m, new_t, s.ev_time)
        aux = jnp.where(m, new_aux, s.aux)
        aux_gpus = s.aux_gpus
        if not packed:
            upd_bits = jnp.where(pl, new_bits, held_bits)
            if upd_bits.dtype != s.aux_gpus.dtype:  # state_pack handoff
                upd_bits = upd_bits.astype(s.aux_gpus.dtype)
            aux_gpus = jnp.where(m, upd_bits, s.aux_gpus)
        pod_ctime = (jnp.where(m & retry, rt, s.pod_ctime)
                     if cfg.track_ctime else s.pod_ctime)
        pending = s.pending - (is_del | dropped).astype(jnp.int32)

        # ---- evaluator bookkeeping (identical to the exact engine).
        # Fault events are control events: excluded from events_processed,
        # snapshots, and max_nodes (pod_act is active outside fault steps).
        valid = pod_act & ~alloc_fail
        events = s.events_processed + valid.astype(jnp.int32)
        fire = valid & (s.snap_idx < klen) & (
            events >= ktable[jnp.minimum(s.snap_idx, klen - 1)])
        used = jnp.stack([
            (total_cpu - jnp.sum(cpu_left)).astype(f),
            (total_mem - jnp.sum(mem_left)).astype(f),
            jnp.sum(c.num_gpus - gpu_left).astype(f),
            (total_gm - jnp.sum(gpu_milli_left)).astype(f),
        ])
        totals_vec = jnp.stack([total_cpu, total_mem, total_gc, total_gm])
        denom = jnp.maximum(totals_vec, 1).astype(f)
        utils = jnp.where(totals_vec <= 0, 0, used / denom)
        snap_sums = s.snap_sums + jnp.where(fire, utils, 0)
        snap_idx = s.snap_idx + fire.astype(jnp.int32)

        active_nodes = jnp.sum((c.node_mask & (
            (cpu_left < c.cpu_total) | (mem_left < c.mem_total)
            | (gpu_left < c.num_gpus))), dtype=jnp.int32)
        max_nodes = jnp.maximum(s.max_nodes, jnp.where(valid, active_nodes, 0))

        violations = s.violations
        if cfg.validate_invariants:
            active_pods = (aux >= 0) & (ev_time < INF)
            an, ag = _decode_assignment(aux, aux_gpus, g, packed)
            violations = violations + active.astype(jnp.int32) * _audit(
                c, p_rank, active_pods, cpu_left, mem_left, gpu_left,
                gpu_milli_left, an, ag)

        trace = s.trace
        if cfg.decision_trace:
            # pod column holds perm[sidx] — the ORIGINAL input-order pod id
            # — so rows align with the exact engine's without un-permuting.
            # The pending column counts remaining fault events too, like
            # the exact engine's heap size (align_traces compares exactly).
            tpod = perm[sidx]
            tnode = jnp.where(is_del, held_node, jnp.where(pl, w, -1))
            trace_pending = pending
            fault_kw = {}
            if has_faults:
                tpod = jnp.where(take_fault, -1, tpod)
                tnode = jnp.where(take_fault, fault_node, tnode)
                trace_pending = pending + jnp.sum(
                    (fault_time < INF).astype(jnp.int32))
                fault_kw = dict(fault_down=take_fault & ~fault_is_up,
                                fault_up=take_fault & fault_is_up)
            trace = _trace_append(
                trace, active=active, create=create, is_del=is_del,
                was_waiting=was_waiting, pod=tpod, node=tnode,
                scores=scores, winner=w, pending=trace_pending,
                cpu_left=cpu_left, mem_left=mem_left, gpu_left=gpu_left,
                gpu_milli_left=gpu_milli_left, **fault_kw)

        return FlatState(
            ev_time=ev_time, aux=aux, aux_gpus=aux_gpus, pending=pending,
            cpu_left=cpu_left, mem_left=mem_left, gpu_left=gpu_left,
            gpu_milli_left=gpu_milli_left, pod_ctime=pod_ctime,
            wait_hist=hist, events_processed=events, snap_idx=snap_idx,
            snap_sums=snap_sums, frag_sum=frag_sum, frag_count=frag_count,
            max_nodes=max_nodes, failed=s.failed | alloc_fail,
            steps=s.steps + active.astype(jnp.int32), violations=violations,
            numeric_flags=numeric_flags, trace=trace,
            fault_time=fault_time, node_avail=node_avail,
        )

    return step


def _decode_assignment(aux, aux_gpus, g: int, packed: bool):
    """(assigned_node[Q], assigned_gpus[Q]) from the aux encoding (slot
    order). Placed pods keep aux >= 0 after their DELETE fires, so this is
    valid mid-run and at finalize."""
    if packed:
        an = jnp.where(aux >= 0, aux >> g, -1)
        ag = jnp.where(aux >= 0, (aux & ((1 << g) - 1)).astype(jnp.uint32),
                       jnp.uint32(0))
    else:
        an = jnp.where(aux >= 0, aux, -1)
        ag = jnp.where(aux >= 0, aux_gpus, jnp.uint32(0))
    # SimResult dtypes stay int32/uint32 whatever the carry dtypes were
    # (state_pack): a no-op convert when the carry is already wide
    return an.astype(jnp.int32), ag.astype(jnp.uint32)


class _FinalView(NamedTuple):
    """finalize_fields-compatible view of a FlatState with per-pod arrays
    decoded from aux and un-permuted back to input (CSV) order."""

    assigned_node: Any
    assigned_gpus: Any
    pod_ctime: Any
    cpu_left: Any
    mem_left: Any
    gpu_left: Any
    gpu_milli_left: Any
    events_processed: Any
    snap_idx: Any
    snap_sums: Any
    frag_sum: Any
    frag_count: Any
    max_nodes: Any
    failed: Any
    violations: Any
    numeric_flags: Any
    trace: Any = None


def finalize(workload: Workload, cfg: SimConfig, s: FlatState) -> SimResult:
    c, p = workload.cluster, workload.pods
    perm = _rank_perm(jnp.asarray(p.pod_mask), jnp.asarray(p.tie_rank))
    inv = jnp.argsort(perm)  # slot index of each input-order pod
    an, ag = _decode_assignment(
        s.aux, s.aux_gpus, c.g_padded, _packable(c.n_padded, c.g_padded))
    view = _FinalView(
        assigned_node=an[inv], assigned_gpus=ag[inv],
        pod_ctime=s.pod_ctime[inv],
        cpu_left=s.cpu_left, mem_left=s.mem_left,
        # widen packed carries so SimResult dtypes are config-independent
        gpu_left=s.gpu_left.astype(jnp.int32),
        gpu_milli_left=s.gpu_milli_left.astype(jnp.int32),
        events_processed=s.events_processed, snap_idx=s.snap_idx,
        snap_sums=s.snap_sums, frag_sum=s.frag_sum, frag_count=s.frag_count,
        max_nodes=s.max_nodes, failed=s.failed, violations=s.violations,
        numeric_flags=s.numeric_flags, trace=s.trace,
    )
    pend = s.pending > 0
    if s.fault_time is not None:
        # unconsumed fault events mean a truncated run, exactly as they
        # would still sit in the exact engine's heap
        pend = pend | (jnp.min(s.fault_time) < INF)
    return finalize_fields(workload, cfg, pending=pend, s=view)


def make_param_run_fn(workload: Workload, param_policy,
                      cfg: SimConfig = SimConfig()):
    """``run(params, state) -> SimResult`` — flat-engine counterpart of
    engine.make_param_run_fn (same ktable/max_steps/finalize assembly)."""
    ktable, max_steps = loop_tables(workload, cfg)

    def cond(s: FlatState):
        return lane_active(s, max_steps)

    def run(params, state: FlatState) -> SimResult:
        step = build_step(
            workload, lambda pod, nodes: param_policy(params, pod, nodes),
            cfg, ktable, max_steps)
        final = jax.lax.while_loop(cond, step, state)
        return finalize(workload, cfg, final)

    return run


def make_run_fn(workload: Workload, policy: PolicyFn,
                cfg: SimConfig = SimConfig()):
    run = make_param_run_fn(
        workload, lambda _p, pod, nodes: policy(pod, nodes), cfg)
    return functools.partial(run, None)


def simulate(workload: Workload, policy: PolicyFn,
             cfg: SimConfig = SimConfig(), jit: bool = True) -> SimResult:
    """Host convenience API, mirroring engine.simulate."""
    run = make_run_fn(workload, policy, cfg)
    if jit:
        run = jax.jit(run)
    return run(initial_state(workload, cfg))


def make_snapshot(workload: Workload, policy: PolicyFn, e0: int,
                  cfg: SimConfig = SimConfig()):
    """The ``fks_tpu.data.snapshot.Snapshot`` of the first ``e0`` events
    of ``policy``'s run of ``workload``, whatever they are: this engine
    run for ``e0`` steps from the empty cluster with its decision trace
    on, every CREATE attempt read back with its node (or none) and GPUs.
    Raises ``ValueError`` only for what cannot be forked: a run that ended
    or aborted before event ``e0``."""
    from fks_tpu.data.snapshot import RETRY_RULE, Snapshot, replay
    from fks_tpu.sim.types import TRACE_DELETE, TraceBuffer

    e0 = int(e0)
    workload = dataclasses.replace(workload, snapshot=None)
    res = simulate(workload, policy, dataclasses.replace(
        cfg, max_steps=e0, decision_trace=True, trace_len=max(e0, 1)))
    events = int(res.events_processed)
    if events != e0 or bool(res.failed):
        raise ValueError(
            f"snapshot: the run cannot be forked at event {e0}: it "
            + ("aborted on a GPU shortfall" if bool(res.failed)
               else "ended") + f" after {events} events")
    rows = np.asarray(res.trace.data)[:e0]
    event = np.flatnonzero(rows[:, TraceBuffer.COL_KIND] != TRACE_DELETE)
    pod = rows[event, TraceBuffer.COL_POD]
    node = rows[event, TraceBuffer.COL_NODE]
    snap = Snapshot(
        pod=pod.astype(np.int32), node=node.astype(np.int32),
        gpus=np.where(node >= 0, np.asarray(res.assigned_gpus)[pod],
                      0).astype(np.uint32),
        event=event.astype(np.int32), e0=e0,
        rule=RETRY_RULE if (node < 0).any() else "")
    replay(workload, snap)
    return snap


def broadcast_state(state0: FlatState, lanes: int) -> FlatState:
    return jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(jnp.asarray(x), (lanes,) + jnp.shape(x)),
        state0)


#: jitted broadcast for host-loop callers (the segmented runner): one
#: dispatch, and XLA materializes the per-lane state in a single program
_broadcast_jit = jax.jit(broadcast_state, static_argnums=1)


def make_population_run_fn(workload: Workload, param_policy,
                           cfg: SimConfig = SimConfig()):
    """``run(params[C, ...], state0) -> SimResult`` batched over candidates:
    ONE while_loop whose body is the vmapped self-masking step (finished
    lanes idle cheaply), exactly like engine.make_population_run_fn."""
    ktable, max_steps = loop_tables(workload, cfg)

    def run(params, state0: FlatState) -> SimResult:
        pop = jax.tree_util.tree_leaves(params)[0].shape[0]

        def step_one(prm, s):
            return build_step(
                workload, lambda pod, nodes: param_policy(prm, pod, nodes),
                cfg, ktable, max_steps)(s)

        vstep = jax.vmap(step_one, in_axes=(0, 0))
        final = run_batched_lanes(
            lambda s: vstep(params, s), broadcast_state(state0, pop),
            max_steps, active_fn=lane_active)
        return jax.vmap(lambda s: finalize(workload, cfg, s))(final)

    return run


def make_segmented_population_run(workload: Workload, param_policy,
                                  cfg: SimConfig = SimConfig(),
                                  seg_steps: int = 4096,
                                  on_segment=None,
                                  double_buffer: bool = True):
    """``make_population_run_fn`` with a bounded device-call length: the
    while_loop stops every ``seg_steps`` events and the carry returns to
    the host, which re-dispatches until every lane drains.

    Exists because one device call can be neither observed nor
    interrupted, and its wall time scales with steps, not lanes: a
    full-trace batched-VM launch runs minutes on the chip and a 100k-pod
    scale run longer, whatever the population. Segments return control to
    the host every ``seg_steps`` events — progress ticks for the flight
    recorder, Ctrl-C between dispatches, and the seam the scale tier
    streams through. Active lanes advance in lockstep (the self-masking
    step freezes only finished lanes), so ``steps - start`` is uniform
    across active lanes and the segment bound is exact.

    ``double_buffer`` (default on) pipelines the segment handoff: segment
    i+1 is dispatched BEFORE segment i's any-lane-active flag is read, so
    the device never waits for the host's flag sync — JAX's async
    dispatch keeps the next segment's program (and its event-block carry)
    enqueued while the current one runs. The flag therefore lags one
    segment behind the dispatch front and the loop runs exactly one
    overrun segment past the draining one; drained lanes stay drained
    (``lane_active`` is monotonic), the overrun segment self-masks to a
    no-op, and results stay identical to the unsegmented runner — pinned
    by tests/test_flat_engine.py::test_segmented_population_matches.
    ``double_buffer=False`` restores the classic sync-per-segment loop
    (one scalar device->host sync per segment).

    ``on_segment`` (zero-arg callable) fires on the host after every
    segment dispatch — the flight recorder's segment counter
    (fks_tpu.obs); it runs between device calls, never inside them.

    The returned ``run`` exposes ``run.advance`` (the jitted one-segment
    program) and ``run.seg_steps`` so bench harnesses can AOT-lower the
    hot program for cost/memory introspection without a second compile.
    """
    seg_steps = validate_seg_steps(seg_steps, zero_disables=False)
    ktable, max_steps = loop_tables(workload, cfg)

    def step_one(prm, s):
        return build_step(
            workload, lambda pod, nodes: param_policy(prm, pod, nodes),
            cfg, ktable, max_steps)(s)

    vstep = jax.vmap(step_one, in_axes=(0, 0))

    @jax.jit
    def advance(params, bstate):
        start = bstate.steps  # frozen at segment entry

        def cond(s):
            return jnp.any(lane_active(s, max_steps)
                           & (s.steps - start < seg_steps))

        out = jax.lax.while_loop(
            cond, lambda s: vstep(params, s), bstate)
        return out, jnp.any(lane_active(out, max_steps))

    @jax.jit
    def finalize_pop(bstate):
        return jax.vmap(lambda s: finalize(workload, cfg, s))(bstate)

    def run(params, state0: FlatState) -> SimResult:
        pop = jax.tree_util.tree_leaves(params)[0].shape[0]
        # jitted broadcast: one dispatch for the whole per-lane state
        # instead of ~20 per-leaf broadcast ops (round-4 advisor note;
        # the compile is trivial — no loop in the program)
        bstate = _broadcast_jit(state0, pop)
        # segment count is bounded by the step budget, so a cond/step
        # divergence cannot spin the host loop forever. The double-
        # buffered loop reads a flag that lags one segment, so it needs
        # one extra observation slot in the budget (slack 2 vs 1).
        active = True
        prev = None
        for _ in range(segment_budget(max_steps, seg_steps,
                                      slack=2 if double_buffer else 1)):
            bstate, active = advance(params, bstate)
            if on_segment is not None:
                on_segment()
            if double_buffer:
                # sync on the PREVIOUS segment's flag only after this
                # segment is already in flight: the device pipeline never
                # stalls on the host round-trip
                if prev is not None and not bool(prev):
                    active = prev
                    break
                prev = active
            elif not bool(active):  # the only per-segment host sync
                break
        if bool(active):
            # the budget above is exact for lockstep lanes; reaching it
            # with live lanes means cond/step divergence — surface it
            # loudly instead of finalizing a partially-drained state
            # (round-4 advisor finding: silently-wrong SimResults)
            raise RuntimeError(
                "segmented runner exhausted its segment budget with lanes "
                "still active — cond/step divergence in the flat engine")
        return finalize_pop(bstate)

    run.advance = advance
    run.finalize_pop = finalize_pop
    run.seg_steps = seg_steps
    return run
