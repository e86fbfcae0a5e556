"""The jit-compiled discrete-event simulation engine.

TPU-native re-design of the reference's Python event loop
(reference: simulator/main.py:28-199 ``KubernetesSimulator`` +
simulator/event_simulator.py ``DiscreteEventSimulator``): one
``lax.while_loop`` whose body pops the next event from the exact on-device
heap replica, applies the deletion-refund or creation-placement rule
branchlessly, and folds the evaluator into the carry. Everything is fixed
shape; the only data-dependent quantity is the trip count (== number of
events processed, capped by ``max_steps``).

Semantics replicated exactly (SURVEY.md §2 fine print):
- strict-argmax placement with ``> 0`` gate, ties to the lowest node index
  (main.py:104-111; node axis order == CSV order)
- best-fit GPU sub-allocation, stable (milli, index) order (main.py:150-177)
- retry re-push at (first DELETION in raw heap-array order).time + 1,
  silently dropping the pod when no deletion exists (event_simulator.py:51-58)
- pod.creation_time mutated on retry, so a delayed pod keeps its full
  duration (event_simulator.py:45-58)
- snapshot overshoot past 100% progress (see fks_tpu.sim.evaluator)
- fragmentation event on every failed creation, scored over waiting GPU
  pods' minimum gpu_milli (evaluator.py:69-75,144-163)
- GPU-allocation shortfall aborts the run (reference raises ValueError,
  main.py:164-165 -> caller maps to score 0, funsearch_integration.py:63-64)

The policy is a vectorized ``PolicyFn`` scoring all nodes at once; the
population axis is added OUTSIDE via ``vmap`` (see fks_tpu.parallel).

A workload that carries a ``snapshot`` (``fks_tpu.data.snapshot``) starts
AFTER its ``E0`` events: ``initial_state`` returns ``forked_state``, the
carry those events leave, whatever they are (placed and refused CREATEs,
retries, DELETEs). *A snapshot says what happened: the first E0 events of
the run, timed by the rule the snapshot names; every later event is this
engine's own: the policy decides each CREATE attempt and the heap-array
rule above re-queues each refusal* (the one definition of a forked run,
``fks_tpu.data.snapshot``). Because that rule reads the heap in array
order, the heap at the fork is CPython's own after the prefix, slot for
slot (``data.snapshot.heap_after``: the logged pops and pushes re-run on
the real ``heapq`` among all the CREATEs of the workload at hand); the pod
axis holds the residents, the waiting and the departed pods as the step
leaves them. ``fork_prefix`` / ``fork_leaves`` hold the arithmetic that
this engine and the flat one (``sim.flat._loaded_leaves``) share: the
host replay of the prefix, whatever its events. The fused engine and the
portfolio refuse every snapshot by name.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from fks_tpu.data.entities import (
    ClusterArrays, PodArrays, Workload, gpu_spec_allows)
from fks_tpu.ops.allocator import best_fit_gpus, first_fit_gpus
from fks_tpu.ops.heap import (
    KIND_CREATE, KIND_DELETE, KIND_NODE_DOWN, KIND_NODE_UP, EventHeap,
    first_deletion_in_array_order, heap_from_events, heap_pop, heap_push,
)
from fks_tpu.sim.evaluator import max_snapshot_count, snapshot_trigger_table
from fks_tpu.sim.guards import fitness_flags, guard_scores
from fks_tpu.sim.types import (
    TRACE_CREATE, TRACE_DELETE, TRACE_NODE_DOWN, TRACE_NODE_UP, TRACE_RETRY,
    NodeView, PodView, PolicyFn, SimResult, SimState, TraceBuffer, empty_trace,
)


@dataclasses.dataclass(frozen=True)
class SimConfig:
    """Static simulation knobs (constructor args in the reference:
    main.py:29-48, evaluator.py:30)."""

    max_steps_factor: int = 8  # runaway guard: max events = factor * num_pods
    max_steps: Optional[int] = None  # overrides the factor when set
    snapshot_interval: float = 0.05
    gpu_allocator: str = "best_fit"  # or "first_fit" (main.py:133-134)
    score_dtype: Any = jnp.float32  # evaluator accumulation dtype
    validate_invariants: bool = False  # reference main.py:201-272 (opt-in)
    # wait-histogram width override (buckets = gpu_milli values of waiting
    # GPU pods; must exceed the trace's max gpu_milli). Set it when batching
    # traces whose derived sizes differ so the stacked states share a shape.
    wait_hist_size: Optional[int] = None
    # skip the policy on non-creation events via lax.cond. A win when the
    # policy is expensive (the funsearch VM interpreter) AND the loop runs
    # unbatched — under vmap, cond degenerates to executing both branches,
    # so batched paths should keep this off.
    cond_policy: bool = False
    # maintain SimResult.pod_ctime (retry-mutated creation times, reference
    # event_simulator.py:56). Pure bookkeeping — nothing downstream of the
    # simulation reads it — but in the flat engine the write is a full
    # [P]-wide blend per event, so throughput-only paths (bench, population
    # fitness) turn it off. When off, SimResult.pod_ctime holds the
    # original creation times. The exact engine always tracks (its scatter
    # write is not on the critical path).
    track_ctime: bool = True
    # numerics watchdog (sim.guards): flag NaN/Inf policy scores into the
    # carry (masking them to "refuse") and audit the final fitness for
    # NaN/Inf/out-of-[0,1]. Python-static, so the disabled path compiles
    # to the exact same program as a build without guards.
    watchdog: bool = False
    # decision-trace instrument (fks_tpu.funsearch.tracing): log one row per
    # processed event — kind (CREATE/DELETE/RETRY), pod id, chosen node,
    # winning score + second-best margin, pending count, post-step free
    # aggregates — into a bounded TraceBuffer carried in the engine state.
    # Python-static like ``watchdog``: disabled, the state's trace field is
    # None (zero pytree leaves) and the compiled program is identical.
    decision_trace: bool = False
    trace_len: Optional[int] = None  # trace rows; default resolve_max_steps
    # probe scoring (fks_tpu.funsearch.budget): score a truncated prefix.
    # The normal gate zeroes any run that still has pending events or
    # unassigned pods — correct for full evaluations, useless for a budget
    # probe that deliberately stops at ``probe_steps``. With probe_score
    # the fitness is the utilization integral over the consumed prefix
    # (still zeroed on failure / zero snapshots), and SimResult.truncated
    # keeps reporting the truth. Python-static like ``watchdog``: the
    # default-off path selects the same jnp.where gate expression as
    # before, compiling the identical program.
    probe_score: bool = False
    # large-cluster scale tier (README "Large-cluster scale tier"): top-k
    # candidate-node prefiltering. 0 (the default) sweeps every node per
    # event exactly as before — Python-static like ``watchdog``, so the
    # disabled path compiles the bit-identical program. k > 0 ranks nodes
    # by a cheap static feasibility score (free CPU/mem/GPU fit under the
    # cordon mask, ties to the LOWEST node index — dense argmax's tie
    # rule), gathers the top k into a [k, ...] NodeView, runs the policy
    # on that view only, and maps the winner back to the global node
    # index. Exact (placement-sequence-preserving) for policies that
    # refuse infeasible nodes and prefer lower indices among equal scores
    # (first_fit and every zoo/parametric feasibility-gated policy on its
    # preferred node); for other policies the winner is the argmax over
    # the candidate set, so fitness parity vs the dense sweep must be
    # validated per policy (tests/test_scale_tier.py). k >= n_padded
    # falls back to the dense sweep (a full gather is strictly slower).
    # Code candidates do not leave this at 0 on a large cluster:
    # CodeEvaluator and VMServeEngine read 0 as "not set" and fill it
    # from the cluster's shape (``shape_prefilter_k`` below: 64 from 256
    # padded nodes on). To have them sweep every node of a large cluster,
    # pass ``node_prefilter_k=n_padded``.
    node_prefilter_k: int = 0
    # packed state dtypes (flat engine only; the exact engine ignores the
    # flag). True narrows FlatState columns whose full value range is
    # exactly representable at this workload's shape — gpu_milli_left /
    # gpu_left / wait_hist / aux to int16, aux_gpus to uint16 — halving
    # the while_loop carry bandwidth for those columns with ZERO fitness
    # drift (integer packing is exact; columns whose range cannot be
    # proven at this shape stay int32, so the knob degrades to a no-op
    # rather than wrapping). bfloat16 accumulators were REJECTED by the
    # parity sweep (PROFILE.md round 11: ~1e-3 fitness drift vs the 1e-5
    # bar), so snap_sums/frag_sum stay at ``score_dtype``. Python-static:
    # the default-off path compiles the bit-identical program.
    state_pack: bool = False

    def resolve_prefilter_k(self, n_padded: int) -> int:
        """Static candidate count for top-k node prefiltering: 0 means
        dense sweep. Values >= n_padded fall back to 0 (gathering every
        node in rank order is strictly slower than the dense sweep and
        would perturb argmax tie-breaks for nothing)."""
        k = self.node_prefilter_k
        if k < 0:
            raise ValueError(
                f"node_prefilter_k must be >= 0 (0 disables prefiltering), "
                f"got {k}")
        return k if 0 < k < n_padded else 0

    def resolve_max_steps(self, num_pods: int) -> int:
        if self.max_steps is not None:
            return self.max_steps
        return max(64, self.max_steps_factor * num_pods)

    def resolve_trace_len(self, num_pods: int) -> int:
        if self.trace_len is not None:
            return self.trace_len
        return self.resolve_max_steps(num_pods)


def _hist_size(p: PodArrays, cfg: SimConfig) -> int:
    max_milli = int(np.asarray(p.gpu_milli).max(initial=0))
    hist_size = (cfg.wait_hist_size if cfg.wait_hist_size is not None
                 else max(1001, max_milli + 2))
    if hist_size <= max_milli:
        raise ValueError(
            f"wait_hist_size {hist_size} <= trace max gpu_milli; "
            "fragmentation min_needed would be miscounted")
    return hist_size


def initial_state(workload: Workload, cfg: SimConfig) -> SimState:
    """Build the t=0 carry. Host-side; the initial heap layout is produced
    by real CPython heapq so it matches the reference bit-for-bit. A
    workload with a ``snapshot`` (``fks_tpu.data.snapshot``) gives the
    carry AFTER the snapshot's ``E0`` events instead (``forked_state``):
    every runner that starts from here forks from the loaded cluster."""
    if workload.snapshot is not None:
        return jax.tree_util.tree_map(jnp.asarray,
                                      forked_state(workload, cfg))
    c, p = workload.cluster, workload.pods
    n_real = p.num_pods
    pm = np.asarray(p.pod_mask)
    times = np.asarray(p.creation_time)[pm]
    ranks = np.asarray(p.tie_rank)[pm]
    kinds = np.zeros(n_real, np.int32)
    payload = np.nonzero(pm)[0].astype(np.int32)
    capacity = p.p_padded
    fe = workload.faults
    if fe is not None:
        # Fault events ride the same heap: payload column = node index,
        # rank = (row index - F_pad) < 0, so at equal time every fault
        # sorts BEFORE every pod event (tie_rank >= 0) and faults among
        # themselves keep array order — the flat engine's argmin-first-
        # index arbitration reproduces both orderings exactly.
        fm = np.asarray(fe.mask)
        fpad = int(fm.shape[0])
        times = np.concatenate([times, np.asarray(fe.time)[fm]])
        ranks = np.concatenate(
            [ranks, np.nonzero(fm)[0].astype(np.int32) - fpad])
        kinds = np.concatenate([kinds, np.asarray(fe.kind)[fm]])
        payload = np.concatenate([payload, np.asarray(fe.node)[fm]])
        capacity = p.p_padded + fpad
    heap = heap_from_events(times, ranks, kinds, payload, capacity=capacity)
    n, g, pp = c.n_padded, c.g_padded, p.p_padded
    hist_size = _hist_size(p, cfg)
    f = cfg.score_dtype
    pod_state = jnp.stack([
        jnp.full(pp, -1, jnp.int32),                     # assigned node
        jnp.zeros(pp, jnp.int32),                        # gpu bitmask
        jnp.asarray(p.creation_time, jnp.int32),         # pod_ctime
        jnp.zeros(pp, jnp.int32),                        # waiting flag
    ], axis=-1)
    return SimState(
        heap=heap,
        cpu_left=jnp.asarray(c.cpu_total, jnp.int32),
        mem_left=jnp.asarray(c.mem_total, jnp.int32),
        gpu_left=jnp.asarray(c.gpu_declared, jnp.int32),
        gpu_milli_left=jnp.asarray(c.gpu_milli_total, jnp.int32),
        pod_state=pod_state,
        wait_hist=jnp.zeros(hist_size, jnp.int32),
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
        node_avail=None if fe is None else jnp.ones(n, bool),
    )


def fork_prefix(workload: Workload):
    """Replay the workload's snapshot on the host
    (``fks_tpu.data.snapshot.replay``: the whole of its validation, a
    ``ValueError`` before any device program) and return what its ``E0``
    events leave, a ``data.snapshot.Prefix``: the part of a forked carry
    that is computed once, whatever pods come after the events (serving
    builds it once per engine and forks every query from it)."""
    from fks_tpu.data.snapshot import replay

    if workload.faults is not None:
        raise ValueError(
            "snapshot: a workload with fault events or a decision trace "
            "cannot start from a snapshot (the prefix holds neither)")
    return replay(workload, workload.snapshot)


def fork_leaves(workload: Workload, cfg: SimConfig, prefix=None,
                ktable=None) -> dict:
    """The leaves of a carry that a snapshot's ``E0`` events change and
    that both engines hold alike, in NumPy: the cluster after them, the
    counters, the waiting histogram and the evaluator's sums, each float
    sum in ``cfg.score_dtype`` and in the step's own order. Every one of
    the events counts as an event and a step; each refused attempt adds
    its fragmentation score; the utilization snapshots among the events
    are the sums in use at the trigger points of ``ktable`` (the
    workload's own, from ``loop_tables``, unless given: it is sized from
    the WHOLE run's pod count, so it differs by what follows the prefix
    while ``prefix`` does not). A prefix of placed CREATEs, a snapshot of
    the loaded cluster, is the case in which nothing waits or fragments."""
    if cfg.decision_trace:
        raise ValueError(
            "snapshot: a workload with fault events or a decision trace "
            "cannot start from a snapshot (the prefix holds neither)")
    if prefix is None:
        prefix = fork_prefix(workload)
    if ktable is None:
        ktable, _ = loop_tables(workload, cfg)
    e0, totals = prefix.e0, prefix.totals
    f = np.dtype(cfg.score_dtype)
    # the step divides by totals that XLA folds to constants, and XLA
    # turns a division by a constant into a product with its reciprocal
    # (AlgebraicSimplifier, every backend): the same two roundings here,
    # or the sums are an ulp off the engine's own
    inv = f.type(1) / np.maximum(totals, 1).astype(f)
    # the evaluator's sums, as the step accumulates them: one snapshot at
    # most per event, when the event count reaches the next trigger
    snap_sums = np.zeros(4, f)
    snap_idx = events = 0
    for trigger in np.asarray(ktable).tolist():
        events = max(events + 1, int(trigger))
        if events > e0:
            break
        utils = np.where(totals <= 0, f.type(0),
                         prefix.used[events - 1].astype(f) * inv)
        snap_sums = (snap_sums + utils).astype(f)
        snap_idx += 1
    # one fragmentation score per refused attempt, added in event order
    # (cumsum adds left to right); 0 on a cluster without GPU milli
    scores = prefix.frag_free.astype(f) * (inv[3] if totals[3] > 0
                                           else f.type(0))
    frag_sum = np.cumsum(scores, dtype=f)[-1] if len(scores) else f.type(0)
    size = _hist_size(workload.pods, cfg)
    return dict(
        cpu_left=prefix.cpu_left.astype(np.int32),
        mem_left=prefix.mem_left.astype(np.int32),
        gpu_left=prefix.gpu_left.astype(np.int32),
        gpu_milli_left=prefix.gpu_milli_left.astype(np.int32),
        wait_hist=np.bincount(np.clip(prefix.wait_milli, 0, size - 1),
                              minlength=size).astype(np.int32),
        events_processed=np.int32(e0), steps=np.int32(e0),
        snap_idx=np.int32(snap_idx), snap_sums=snap_sums,
        frag_sum=np.asarray(frag_sum, f),
        frag_count=np.int32(prefix.refused),
        max_nodes=np.int32(prefix.max_nodes))


def forked_state(workload: Workload, cfg: SimConfig, prefix=None,
                 ktable=None) -> SimState:
    """The exact engine's carry after the workload's snapshot, leaf for
    leaf what ``build_step`` reaches when those ``E0`` events happen as
    logged, as NumPy (``initial_state`` uploads it; serving stacks a
    batch of them first). Any valid prefix: the heap is CPython's own
    after its pops and pushes among ALL the workload's CREATEs
    (``data.snapshot.heap_after``: the retry rule reads it in array order),
    the pending retry of a waiting pod in it; ``pod_state`` holds what
    the step leaves in a pod's row: node and GPU bits of a placed pod
    (a departed one keeps them), ``COL_WAIT`` of a pod refused and not
    placed since, ``COL_CTIME`` as the retries moved it. ``prefix`` and
    ``ktable`` as in ``fork_leaves``; a ``prefix`` may end before the
    workload's pod axis does (serving's: the pods behind it are a
    query's, untouched by the events)."""
    from fks_tpu.data.snapshot import heap_after, heap_key

    p = workload.pods
    if prefix is None:
        prefix = fork_prefix(workload)
    shared = fork_leaves(workload, cfg, prefix, ktable)
    real = np.flatnonzero(np.asarray(p.pod_mask))
    rank = np.asarray(p.tie_rank, np.int64)[real]
    pod_of_rank = np.zeros(int(rank.max(initial=-1)) + 1, np.int64)
    pod_of_rank[rank] = real
    rows, size = heap_after(
        heap_key(np.asarray(p.creation_time, np.int64)[real], rank,
                 KIND_CREATE),
        prefix.pushes, pod_of_rank, capacity=p.p_padded)
    pod_state = np.zeros((p.p_padded, 4), np.int32)
    pod_state[:, SimState.COL_NODE] = -1
    pod_state[:, SimState.COL_CTIME] = np.asarray(p.creation_time)
    known = pod_state[:len(prefix.node)]
    known[:, SimState.COL_NODE] = prefix.node
    known[:, SimState.COL_BITS] = prefix.gpus.astype(np.uint32).view(np.int32)
    known[:, SimState.COL_CTIME] = prefix.ctime
    known[:, SimState.COL_WAIT] = prefix.waiting
    return SimState(
        heap=EventHeap(data=rows, size=np.int32(size)),
        pod_state=pod_state, failed=np.bool_(False),
        violations=np.int32(0), numeric_flags=np.int32(0), trace=None,
        node_avail=None, **shared)


def _widest_int():
    """Accumulation dtype for cluster-wide integer sums: int64 when x64 is
    enabled, else int32 (on by default on TPU, where 64-bit is emulated)."""
    return jnp.int64 if jax.config.jax_enable_x64 else jnp.int32


def _trace_append(trace: TraceBuffer, *, active, create, is_del, was_waiting,
                  pod, node, scores, winner, pending,
                  cpu_left, mem_left, gpu_left, gpu_milli_left,
                  fault_down=None, fault_up=None) -> TraceBuffer:
    """Append one decision row (see TraceBuffer column docs). Shared by the
    exact and flat engines so the recorded vocabulary cannot drift between
    them. Self-masking: an inactive step, or a full buffer, appends via an
    out-of-range index whose scatter drops. Deletes record score/margin 0
    (the step's score vector is undefined on non-creation events under
    ``cond_policy``), keeping row content engine-deterministic. Fault rows
    (``fault_down``/``fault_up`` predicates, fault-carrying workloads only)
    override the kind; their node column is the cordoned node and their
    score/margin are 0 like deletes."""
    tlen = trace.data.shape[0]
    kind = jnp.where(is_del, TRACE_DELETE,
                     jnp.where(was_waiting, TRACE_RETRY, TRACE_CREATE))
    if fault_down is not None:
        kind = jnp.where(fault_down, TRACE_NODE_DOWN,
                         jnp.where(fault_up, TRACE_NODE_UP, kind))
    wi = _widest_int()
    row = jnp.stack([
        kind.astype(jnp.int32), pod.astype(jnp.int32),
        node.astype(jnp.int32), pending.astype(jnp.int32),
        jnp.sum(cpu_left, dtype=wi).astype(jnp.int32),
        jnp.sum(mem_left, dtype=wi).astype(jnp.int32),
        jnp.sum(gpu_left, dtype=wi).astype(jnp.int32),
        jnp.sum(gpu_milli_left, dtype=wi).astype(jnp.int32),
    ])
    sdt = trace.scores.dtype
    win = scores[winner].astype(sdt)
    if scores.shape[0] > 1:
        others = jnp.where(jnp.arange(scores.shape[0]) == winner,
                           -jnp.inf, scores.astype(sdt))
        margin = win - jnp.max(others)
    else:
        margin = jnp.zeros_like(win)
    win = jnp.where(create, win, 0)
    margin = jnp.where(create, margin, 0)
    write = active & (trace.count < tlen)
    idx = jnp.where(write, trace.count, tlen)
    return TraceBuffer(
        data=trace.data.at[idx].set(row, mode="drop"),
        scores=trace.scores.at[idx].set(jnp.stack([win, margin]), mode="drop"),
        count=trace.count + write.astype(jnp.int32),
    )


def _node_view(c: ClusterArrays, cpu_left, mem_left, gpu_left, gpu_milli_left):
    return NodeView(
        cpu_milli_left=cpu_left, cpu_milli_total=c.cpu_total,
        memory_mib_left=mem_left, memory_mib_total=c.mem_total,
        gpu_left=gpu_left, num_gpus=c.num_gpus,
        gpu_milli_left=gpu_milli_left, gpu_milli_total=c.gpu_milli_total,
        gpu_mem_total=c.gpu_mem_total, gpu_mask=c.gpu_mask,
        node_mask=c.node_mask,
    )


def place_mask_of(c: ClusterArrays, node_avail, spec):
    """The nodes a CREATE may be placed on, whatever the policy scores:
    real nodes, not cordoned (``node_avail``, None on a fault-free
    workload), of a GPU model the pod accepts (``spec``, the pod's
    ``gpu_spec`` word, None on a workload without type constraints; the
    rule is ``fks_tpu.data.entities.gpu_spec_allows``, which the snapshot
    replay holds a log to on the host, and its node side is loop
    invariant). Every term beyond ``node_mask``
    is emitted only where the workload has the leaves for it. The ONE
    mask of both engines: it feeds ``_prefilter_candidates`` and the
    score gate, and nothing else reads it (GPU picks, utilization,
    fragmentation and the retry rule know nothing of cordons or types)."""
    mask = c.node_mask
    if node_avail is not None:
        mask = mask & node_avail
    if spec is not None:
        mask = mask & gpu_spec_allows(spec, c.gpu_model, jnp)
    return mask


def place_mask_at(c: ClusterArrays, place_mask, node_avail, spec, cand):
    """``place_mask[cand]``: the mask re-read through the candidate
    gather (``place_mask`` is ``place_mask_of(c, node_avail, spec)``).
    On a typed workload the type term is evaluated AT the candidates,
    from the cluster's own model vector (``c.gpu_model[cand]``: a gather
    from a lane-invariant operand, as every constant ``NodeView`` leaf's
    is), not gathered from the per-lane ``[N]`` mask: under the
    population ``vmap`` that gather has a batched operand, and on the
    v5e its flattened ``[lanes * k]`` form re-laid ``cand`` for every
    gather that shares it (seven reshapes a step, 25.6 us an event of
    0.71 ms; PERF.md section 6, PR 45). Equal by construction: both are
    ``gpu_spec_allows`` of the same word and the same node's model."""
    if spec is None:
        return place_mask[cand]
    return (place_mask_of(c, node_avail, None)[cand]
            & gpu_spec_allows(spec, c.gpu_model[cand], jnp))


#: the column of the engines' pod feature table (one ``[8]`` row a pod,
#: read by the one gather of the popped pod's request) that holds the
#: ``gpu_spec`` word on a typed workload; zeros, as always, on any other
FEAT_GPU_SPEC = 5


def _prefilter_candidates(pod: PodView, nodes: NodeView, place_mask, k: int):
    """Top-k candidate nodes for one creation event (SimConfig
    ``node_prefilter_k``): rank every node by a cheap static feasibility
    test — the same free CPU/mem/GPU-count/GPU-milli fit the zoo policies
    gate on (fks_tpu.models.zoo.feasible_mask), under ``place_mask``
    (``place_mask_of``: real, not cordoned, of a GPU model the pod
    accepts) so a cordoned, forbidden or padding node can NEVER enter a
    candidate slot — and keep
    the k best, i.e. the first k FEASIBLE nodes in ascending global
    index: argmax over the gathered view then preserves the dense sweep's
    lowest-index tie rule exactly. Selection is a cumsum + one-hot argmax
    (candidate slot j = first node whose running feasible-count is j),
    NOT ``jax.lax.top_k``: the rank order is already "feasible by
    ascending index", so a full selection sort buys nothing — and a
    vmapped top_k(1000, 64) measures ~1.2 ms/call on CPU, 4x an entire
    dense step — while the one-hot form is O(N*k) dense vectorized work
    and stays scatter-free (the TPU design rule every state write in this
    engine follows). When fewer than k nodes are feasible, the unmatched
    tail repeats the FIRST candidate, so whenever any feasible node
    exists every slot holds a feasible one (cordoned/padding nodes never
    enter the list) and duplicates tie in the winner argmax at the same
    global node. Only when NO node is feasible does the list degrade to
    node 0 — callers re-mask through the gather (``place_mask[cand]``
    with the ``> 0`` placement gate), so that event fails exactly like
    the dense sweep. Returns i32[k] global node indices."""
    eligible = jnp.sum(
        (nodes.gpu_mask & (nodes.gpu_milli_left >= pod.gpu_milli)
         ).astype(jnp.int32), axis=1)
    gpu_ok = jnp.where(pod.num_gpu > 0, eligible >= pod.num_gpu, True)
    feasible = (place_mask
                & (pod.cpu_milli <= nodes.cpu_milli_left)
                & (pod.memory_mib <= nodes.memory_mib_left)
                & (pod.num_gpu <= nodes.gpu_left) & gpu_ok)
    # slot of node i among feasibles = #feasible before it; infeasible
    # nodes get an out-of-range slot so they match no candidate column
    slot = jnp.where(feasible,
                     jnp.cumsum(feasible.astype(jnp.int32)) - 1,
                     jnp.int32(-1))
    k_iota = jnp.arange(k, dtype=jnp.int32)
    onehot = slot[:, None] == k_iota[None, :]
    cand = jnp.argmax(onehot, axis=0).astype(jnp.int32)
    return jnp.where(k_iota < jnp.sum(feasible.astype(jnp.int32)),
                     cand, cand[0])


def _gather_node_view(nodes: NodeView, cand) -> NodeView:
    """The [k, ...] candidate view: every NodeView leaf gathered along the
    node axis (leaves are [N] or [N, G]; a row gather covers both)."""
    return NodeView(*(leaf[cand] for leaf in nodes))


def lane_active(s: SimState, max_steps: int):
    """THE termination predicate: a lane keeps stepping while events remain,
    no GPU-allocation abort happened, and the runaway guard holds. Single
    source of truth for both the step's self-masking and every loop cond —
    if they ever diverged, a loop whose cond is any(lane_active) over
    no-op'ing lanes would spin forever."""
    return (s.heap.size > 0) & ~s.failed & (s.steps < max_steps)


def build_step(workload: Workload, policy: PolicyFn, cfg: SimConfig,
               ktable, max_steps: int) -> Callable[[SimState], SimState]:
    """One event: the body of the while_loop. See module docstring.

    ``workload`` arrays and ``ktable`` may be tracers (the multi-trace path
    passes them as jit/vmap arguments so one compiled program serves every
    same-shape trace); all totals are therefore computed with jnp ops, which
    XLA constant-folds when the workload is a compile-time constant.

    The step is *self-masking*: it computes its own ``active`` predicate
    (same condition as the loop guard) and becomes a no-op when inactive --
    every mutation is either a dropped scatter or a predicate-gated add.
    That lets the population layer run ONE ``while_loop`` whose body is the
    vmapped step and whose cond is ``any(active)``: finished lanes idle for
    O(log n) dropped scatters instead of the full-carry per-lane select that
    ``vmap(while_loop)`` would insert every iteration."""
    c, p = workload.cluster, workload.pods
    # device-resident copies (parser emits numpy; tracers can't index numpy)
    c = jax.tree_util.tree_map(jnp.asarray, c)
    p = jax.tree_util.tree_map(jnp.asarray, p)
    n, g = workload.cluster.n_padded, workload.cluster.g_padded
    f = cfg.score_dtype
    alloc = best_fit_gpus if cfg.gpu_allocator == "best_fit" else first_fit_gpus
    # cluster-wide capacity totals (reference: evaluator.py:35-38); padding
    # rows are zero so plain sums are exact
    total_cpu = jnp.sum(c.cpu_total)
    total_mem = jnp.sum(c.mem_total)
    total_gc = jnp.sum(c.num_gpus)
    total_gm = jnp.sum(c.gpu_milli_total)
    g_iota = jnp.arange(g, dtype=jnp.uint32)
    ktable = jnp.asarray(ktable, jnp.int32)
    klen = ktable.shape[0]
    # pod features packed into one gather table so reading the popped
    # pod's request costs a single row-gather (per-lane-indexed gathers
    # cost serialized latency per INSTRUCTION under vmap; PROFILE.md).
    # Padded 5 -> 8 columns: power-of-two rows keep the gather's slice
    # aligned to the TPU lane tiling (same layout as flat.py's table).
    # Python-static gating (like watchdog/decision_trace): a workload
    # without GPU-type constraints, or without faults, compiles to the
    # exact program it had before either existed.
    has_types = workload.typed
    feat = jnp.stack([p.cpu, p.mem, p.num_gpu, p.gpu_milli, p.duration,
                      p.gpu_spec if has_types else jnp.zeros_like(p.cpu),
                      jnp.zeros_like(p.cpu),
                      jnp.zeros_like(p.cpu)], axis=-1).astype(jnp.int32)
    has_faults = workload.faults is not None
    # large-cluster scale tier: 0 = dense sweep (bit-identical program)
    prefilter_k = cfg.resolve_prefilter_k(n)

    def step(s: SimState) -> SimState:
        active = lane_active(s, max_steps)
        h, (t, rk, kind, pod) = heap_pop(s.heap, pred=active)
        is_del = active & (kind == KIND_DELETE)
        if has_faults:
            # fault events (pod column = node index): flip the cordon bit,
            # touch nothing else. Every pod-event mutation below is gated
            # on is_del/create, so a fault step is a pure availability flip.
            fault_down = active & (kind == KIND_NODE_DOWN)
            fault_up = active & (kind == KIND_NODE_UP)
            is_fault = fault_down | fault_up
            create = active & (kind == KIND_CREATE)
        else:
            create = active & ~(kind == KIND_DELETE)

        pf = feat[pod]  # [8], one gather
        pcpu, pmem, pngpu, pmilli, pdur = pf[0], pf[1], pf[2], pf[3], pf[4]
        ps_row = s.pod_state[pod]  # [4], one gather
        held_node = ps_row[SimState.COL_NODE]
        bits = jax.lax.bitcast_convert_type(
            ps_row[SimState.COL_BITS], jnp.uint32)
        pod_ct = ps_row[SimState.COL_CTIME]
        was_waiting = ps_row[SimState.COL_WAIT] != 0

        # ---- DELETION: refund resources (reference main.py:74-99).
        # Dense one-hot adds over the tiny node axis, not scatters — TPU
        # scatters serialize per element (PROFILE.md).
        a = jnp.where(is_del, held_node, 0)
        di = is_del.astype(jnp.int32)
        n_iota = jnp.arange(n, dtype=jnp.int32)
        oh_a = (n_iota == a).astype(jnp.int32) * di  # [N]
        cpu_left = s.cpu_left + oh_a * pcpu
        mem_left = s.mem_left + oh_a * pmem
        gpu_left = s.gpu_left + oh_a * pngpu
        sel_bits = ((bits >> g_iota) & 1).astype(jnp.int32)  # [G]
        gpu_milli_left = s.gpu_milli_left + oh_a[:, None] * pmilli * sel_bits[None, :]

        # ---- FAULT: cordon/uncordon via one dense one-hot blend
        node_avail = s.node_avail
        if has_faults:
            oh_f = n_iota == jnp.where(is_fault, pod, jnp.int32(n))
            node_avail = jnp.where(oh_f, fault_up, node_avail)

        # ---- CREATION: score every node, strict argmax (main.py:101-111)
        pod_view = PodView(pcpu, pmem, pngpu, pmilli, pod_ct, pdur)
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
        # b is always the GLOBAL node index (gather-back through cand)
        wk = jnp.argmax(scores).astype(jnp.int32)
        b = cand[wk] if prefilter_k else wk
        placed = create & (scores[wk] > 0)

        # GPU sub-allocation on the winner (main.py:125-145)
        sel, ok = alloc(gpu_milli_left[b], c.gpu_mask[b], pmilli, pngpu)
        alloc_fail = placed & (pngpu > 0) & ~ok  # reference raises here
        pl = placed & ~alloc_fail
        pli = pl.astype(jnp.int32)
        oh_b = (n_iota == b).astype(jnp.int32) * pli  # [N]
        cpu_left = cpu_left - oh_b * pcpu
        mem_left = mem_left - oh_b * pmem
        gpu_left = gpu_left - oh_b * pngpu
        gpu_milli_left = gpu_milli_left - (
            oh_b[:, None] * pmilli * sel.astype(jnp.int32)[None, :])

        new_bits = jnp.sum(jnp.where(sel, jnp.uint32(1) << g_iota, jnp.uint32(0)),
                           dtype=jnp.uint32)

        # ---- failed creation: waiting set + fragmentation + retry
        # (main.py:113-123, evaluator.py:69-75,144-163, event_simulator.py:51-58)
        failp = create & ~placed
        bucket = jnp.clip(pmilli, 0, s.wait_hist.shape[0] - 1)
        hdelta = ((failp & ~was_waiting & (pngpu > 0)).astype(jnp.int32)
                  - (pl & was_waiting & (pngpu > 0)).astype(jnp.int32))
        # dense one-hot blend over the small histogram axis, not a scatter
        h_iota = jnp.arange(s.wait_hist.shape[0], dtype=jnp.int32)
        hist = s.wait_hist + (h_iota == bucket).astype(jnp.int32) * hdelta

        hvals = hist > 0
        has_gpu_waiting = jnp.any(hvals)
        min_needed = jnp.argmax(hvals).astype(jnp.int32)  # first nonzero bucket
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

        found, dt = first_deletion_in_array_order(h)
        retry = failp & found
        rt = dt + 1
        # ONE merged push serves both outcomes — they are mutually
        # exclusive (pl => placed; retry => not placed): DELETE at t+dur
        # when placed, retried CREATE at rt on a failed placement with a
        # pending deletion. Scanning ``h`` (the post-pop heap) is exactly
        # the reference's scan point: when its repush scans, no DELETE
        # was pushed for this event (the pod was not placed), so the
        # pre-delete-push and post-delete-push heaps are identical.
        heap3 = heap_push(
            h, jnp.where(pl, t + pdur, rt), rk,
            jnp.where(pl, KIND_DELETE, KIND_CREATE), pod, pred=pl | retry)

        # ---- pod bookkeeping: ONE row scatter updates assignment, GPU
        # bits, retry-mutated creation time, and waiting-set membership
        new_row = jnp.stack([
            jnp.where(pl, b, held_node),
            jax.lax.bitcast_convert_type(
                jnp.where(pl, new_bits, bits), jnp.int32),
            jnp.where(retry, rt, pod_ct),
            ((was_waiting | failp) & ~pl).astype(jnp.int32)])
        pod_state = s.pod_state.at[pod].set(new_row)

        # ---- evaluator bookkeeping (main.py:63-72, evaluator.py:55-67).
        # On alloc_fail the reference raises BEFORE record_event_processed.
        # Fault events are control events, not scheduling events: they are
        # excluded from events_processed (snapshot cadence), max_nodes, and
        # the trace-step 'valid' accounting in BOTH engines.
        valid = active & ~alloc_fail
        if has_faults:
            valid = valid & ~is_fault
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
            hi = jnp.arange(heap3.pod.shape[0])
            pend_del = (hi < heap3.size) & (heap3.kind == KIND_DELETE)
            active_pods = jnp.zeros(
                pod_state.shape[0], bool).at[heap3.pod].max(pend_del)
            violations = violations + active.astype(jnp.int32) * _audit(
                c, p, active_pods, cpu_left, mem_left, gpu_left,
                gpu_milli_left, pod_state[:, SimState.COL_NODE],
                jax.lax.bitcast_convert_type(
                    pod_state[:, SimState.COL_BITS], jnp.uint32))

        trace = s.trace
        if cfg.decision_trace:
            tpod = pod
            tnode = jnp.where(is_del, held_node, jnp.where(pl, b, -1))
            fault_kw = {}
            if has_faults:
                tpod = jnp.where(is_fault, -1, tpod)
                tnode = jnp.where(is_fault, pod, tnode)
                fault_kw = dict(fault_down=fault_down, fault_up=fault_up)
            # winner indexes the scored view (local top-k slot when
            # prefiltered); tnode above already carries the GLOBAL index b
            trace = _trace_append(
                trace, active=active, create=create, is_del=is_del,
                was_waiting=was_waiting, pod=tpod, node=tnode,
                scores=scores, winner=wk, pending=heap3.size,
                cpu_left=cpu_left, mem_left=mem_left, gpu_left=gpu_left,
                gpu_milli_left=gpu_milli_left, **fault_kw)

        return SimState(
            heap=heap3, cpu_left=cpu_left, mem_left=mem_left,
            gpu_left=gpu_left, gpu_milli_left=gpu_milli_left,
            pod_state=pod_state, wait_hist=hist,
            events_processed=events, snap_idx=snap_idx, snap_sums=snap_sums,
            frag_sum=frag_sum, frag_count=frag_count, max_nodes=max_nodes,
            failed=s.failed | alloc_fail, steps=s.steps + active.astype(jnp.int32),
            violations=violations, numeric_flags=numeric_flags,
            trace=trace, node_avail=node_avail,
        )

    return step


def _audit(c: ClusterArrays, p: PodArrays, active_pods, cpu_left, mem_left,
           gpu_left, gpu_milli_left, assigned_node, assigned_gpus):
    """Opt-in full-state audit after every event — the reference's
    invariant checker semantics (reference: simulator/main.py:201-272):
    non-negative remnants, remnant <= total, and conservation
    (used == total - remaining) at node and per-GPU granularity,
    cross-checked against ``active_pods`` — the engine's "DELETE still
    pending" mask (heap-derived here, slot-derived in the flat engine).
    Returns i32 1 if any invariant fails at this step.

    The reference raises on first violation; a jitted loop cannot, so
    violations are counted into the carry instead (checkify-style)."""
    n, g = c.gpu_mask.shape
    pp = assigned_node.shape[0]

    nm = c.node_mask
    neg = (jnp.any(nm & (cpu_left < 0)) | jnp.any(nm & (mem_left < 0))
           | jnp.any(nm & (gpu_left < 0))
           | jnp.any(c.gpu_mask & (gpu_milli_left < 0)))
    over = (jnp.any(nm & (cpu_left > c.cpu_total))
            | jnp.any(nm & (mem_left > c.mem_total))
            | jnp.any(nm & (gpu_left > c.gpu_declared))
            | jnp.any(c.gpu_mask & (gpu_milli_left > c.gpu_milli_total)))

    active = active_pods & (assigned_node >= 0)
    seg = jnp.clip(assigned_node, 0, n - 1)

    def used_by_node(req):
        return jax.ops.segment_sum(
            jnp.where(active, req, 0), seg, num_segments=n)

    cons = (jnp.any(nm & (c.cpu_total - cpu_left != used_by_node(p.cpu)))
            | jnp.any(nm & (c.mem_total - mem_left != used_by_node(p.mem)))
            | jnp.any(nm & (c.gpu_declared - gpu_left != used_by_node(p.num_gpu))))

    # per-GPU milli conservation: expand each active pod's GPU bitmask
    g_iota = jnp.arange(g, dtype=jnp.uint32)
    bits = ((assigned_gpus[:, None] >> g_iota[None, :]) & 1).astype(jnp.int32)
    contrib = jnp.where(active[:, None], bits * p.gpu_milli[:, None], 0)  # [P,G]
    used_milli = jax.ops.segment_sum(contrib, seg, num_segments=n)  # [N,G]
    cons_g = jnp.any(c.gpu_mask & (c.gpu_milli_total - gpu_milli_left != used_milli))

    return (neg | over | cons | cons_g).astype(jnp.int32)


def _gpu_count_used(c: ClusterArrays, gpu_left):
    return jnp.sum(c.num_gpus - gpu_left)


def finalize_fields(workload: Workload, cfg: SimConfig, *, pending, s) -> SimResult:
    """Fitness + results (reference evaluator.py:77-127) from any engine
    state carrying the shared evaluator fields. ``pending`` is that
    engine's "events remain unprocessed" predicate (the exact engine's
    heap size, the flat engine's live-slot test) — sharing everything else
    keeps the two engines' fitness semantics identical by construction."""
    p = workload.pods
    f = cfg.score_dtype
    pod_mask = jnp.asarray(p.pod_mask)
    n_snap = s.snap_idx
    denom = jnp.maximum(n_snap, 1).astype(f)
    avg = s.snap_sums / denom
    frag_mean = jnp.where(
        s.frag_count > 0, s.frag_sum / jnp.maximum(s.frag_count, 1).astype(f),
        jnp.asarray(0, f))
    all_assigned = jnp.all((s.assigned_node >= 0) | ~pod_mask)
    truncated = pending & ~s.failed
    overall = jnp.sum(avg) / 4
    raw = jnp.clip(overall - jnp.minimum(jnp.asarray(0.1, f), frag_mean), 0.0, 1.0)
    if cfg.probe_score:
        gate = (n_snap > 0) & ~s.failed
    else:
        gate = (n_snap > 0) & all_assigned & ~s.failed & ~truncated
    score = jnp.where(gate, raw, jnp.asarray(0, f))
    scheduled = jnp.sum((s.assigned_node >= 0) & pod_mask, dtype=jnp.int32)
    numeric_flags = s.numeric_flags
    if cfg.watchdog:
        numeric_flags = numeric_flags | fitness_flags(score)
    return SimResult(
        policy_score=score,
        avg_cpu_utilization=avg[0], avg_memory_utilization=avg[1],
        avg_gpu_count_utilization=avg[2], avg_gpu_memory_utilization=avg[3],
        gpu_fragmentation_score=frag_mean,
        num_snapshots=n_snap, num_fragmentation_events=s.frag_count,
        events_processed=s.events_processed, scheduled_pods=scheduled,
        max_nodes=s.max_nodes, assigned_node=s.assigned_node,
        assigned_gpus=s.assigned_gpus, pod_ctime=s.pod_ctime,
        cpu_left=s.cpu_left, mem_left=s.mem_left, gpu_left=s.gpu_left,
        gpu_milli_left=s.gpu_milli_left, failed=s.failed, truncated=truncated,
        invariant_violations=s.violations, numeric_flags=numeric_flags,
        trace=getattr(s, "trace", None),
    )


def finalize(workload: Workload, cfg: SimConfig, s: SimState) -> SimResult:
    """Fitness + results (reference evaluator.py:77-127)."""
    return finalize_fields(workload, cfg, pending=s.heap.size > 0, s=s)


def make_param_run_fn(workload: Workload, param_policy, cfg: SimConfig = SimConfig()):
    """Build ``run(params, state) -> SimResult`` for a parameterized policy
    ``(params, PodView, NodeView) -> i32[N]``.

    Single-lane loop assembly: ``loop_tables`` sizing + ``lane_active``
    cond + while_loop + finalize. Batched paths (population/trace-batch/
    mesh) share the same pieces via ``make_population_run_fn`` /
    ``run_batched_lanes``, so fitness semantics cannot diverge between
    them. ``params`` may be a tracer: the step closure is rebuilt under
    the caller's trace.
    """
    ktable, max_steps = loop_tables(workload, cfg)

    def cond(s: SimState):
        return lane_active(s, max_steps)

    def run(params, state: SimState) -> SimResult:
        step = build_step(
            workload, lambda pod, nodes: param_policy(params, pod, nodes),
            cfg, ktable, max_steps)
        final = jax.lax.while_loop(cond, step, state)
        return finalize(workload, cfg, final)

    return run


def loop_tables(workload: Workload, cfg: SimConfig):
    """(ktable, max_steps) for a workload — the static loop-sizing half of
    loop assembly, shared by every runner so snapshot semantics can't
    diverge between the plain, population, trace-batch, and mesh paths."""
    num_pods = workload.num_pods
    max_steps = cfg.resolve_max_steps(num_pods)
    ktable = snapshot_trigger_table(
        num_pods, max_snapshot_count(max_steps, num_pods, cfg.snapshot_interval),
        cfg.snapshot_interval)
    return ktable, max_steps


def broadcast_state(state0: SimState, lanes: int) -> SimState:
    """Broadcast one initial state to ``lanes`` identical device-resident
    copies (vs. the reference's per-subprocess re-parse + deepcopy,
    funsearch_integration.py:38-48)."""
    return jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(jnp.asarray(x), (lanes,) + jnp.shape(x)),
        state0)


def run_batched_lanes(vstep, bstate, max_steps: int, active_fn=None):
    """Drive any stack of self-masking lanes to completion.

    NOT ``vmap(while_loop)``: that would select the entire per-lane carry
    (queue arrays included) every iteration to freeze finished lanes.
    Instead the vmapped self-masking step runs INSIDE one ``while_loop``
    whose cond is "any lane active", so a finished lane costs only dropped
    writes. ``vstep`` must wrap an engine's ``build_step`` lanes (any
    nesting of vmaps); ``active_fn`` is that engine's ``lane_active`` —
    the EXACT predicate the step masks with (a cond/step divergence would
    spin forever). Defaults to this module's. The single shared scaffold
    for the population, flat-population, and multi-trace paths."""
    if active_fn is None:
        active_fn = lane_active
    return jax.lax.while_loop(
        lambda s: jnp.any(active_fn(s, max_steps)), vstep, bstate)


def make_population_run_fn(workload: Workload, param_policy,
                           cfg: SimConfig = SimConfig()):
    """Build ``run(params[C, ...], state0) -> SimResult`` batched over the
    candidate axis — the TPU-native replacement for the reference's
    per-candidate subprocess fan-out (funsearch_integration.py:535-562).
    Loop scaffold: ``run_batched_lanes`` over the vmapped self-masking step.
    """
    ktable, max_steps = loop_tables(workload, cfg)

    def run(params, state0: SimState) -> SimResult:
        pop = jax.tree_util.tree_leaves(params)[0].shape[0]

        def step_one(p, s):
            return build_step(
                workload, lambda pod, nodes: param_policy(p, pod, nodes),
                cfg, ktable, max_steps)(s)

        vstep = jax.vmap(step_one, in_axes=(0, 0))
        final = run_batched_lanes(
            lambda s: vstep(params, s), broadcast_state(state0, pop), max_steps)
        return jax.vmap(lambda s: finalize(workload, cfg, s))(final)

    return run


def make_run_fn(workload: Workload, policy: PolicyFn,
                cfg: SimConfig = SimConfig()):
    """Build the jittable end-to-end run: initial state -> SimResult.

    The returned fn takes the initial SimState (so callers can vmap over
    batched states or donate buffers) and returns a SimResult.
    """
    run = make_param_run_fn(workload, lambda _p, pod, nodes: policy(pod, nodes), cfg)
    return functools.partial(run, None)


def simulate(workload: Workload, policy: PolicyFn,
             cfg: SimConfig = SimConfig(), jit: bool = True) -> SimResult:
    """Host convenience API: the reference's 'build simulator, run_schedule,
    get results' flow (main.py:29-72 + evaluator read-out) in one call."""
    run = make_run_fn(workload, policy, cfg)
    if jit:
        run = jax.jit(run)
    return run(initial_state(workload, cfg))


# ------------------------------------------------- prefilter auto-enable
#
# PR 7's measurement (PROFILE.md round 11): top-k node prefiltering pays
# 13-16x when the per-node policy is expensive (the VM code-candidate
# tier) and LOSES (~0.6x) when it is cheap (parametric dot products)
# because the step is then queue-dominated and the candidate gather is
# pure overhead. The break-even is a property of the policy's
# per-invocation cost, not of any static code attribute — so the
# auto-enable heuristic keys on a measured probe.

#: k chosen when the heuristic enables prefiltering (the PROFILE round-11
#: sweep's winning setting at 1k nodes)
PREFILTER_AUTO_K = 64
#: policy cost above which prefiltering wins. The round-11 data points on
#: flat CPU: parametric ~2e-5 s/invocation (prefilter loses), VM code
#: candidates ~1e-3 s (prefilter wins 13-16x); the threshold sits an
#: order of magnitude clear of both.
PREFILTER_COST_THRESHOLD_S = 2e-4
#: below this node count the dense sweep is cheap regardless of policy
#: cost and the gather bookkeeping cannot win it back
PREFILTER_MIN_NODES = 256
#: static per-node work bound (fks_tpu.analysis CostEstimate.work) below
#: which a policy is trivially cheap — a handful of fused elementwise ops
#: lands orders of magnitude under PREFILTER_COST_THRESHOLD_S, so the
#: timing probe (which costs a full XLA compile) can be skipped outright.
#: Template-derived code candidates (gpu loop + prologue) sit well above.
PREFILTER_WORK_HINT_MIN = 16


def probe_policy_cost(param_policy, params, n_padded: int, g_padded: int,
                      reps: int = 5) -> float:
    """Steady-state wall seconds of ONE policy invocation at the padded
    cluster shape: jit the bare policy on all-ones dummy views, discard
    the compile call, return the min over ``reps`` timed calls. Host-side
    and backend-agnostic; the one-time compile is the probe's only real
    cost (the timed calls are microseconds)."""
    import time as _time

    i = jnp.zeros((), jnp.int32)
    vn = jnp.ones(n_padded, jnp.int32)
    vg = jnp.ones((n_padded, g_padded), jnp.int32)
    pod = PodView(i, i, i, i, i, i)
    nodes = NodeView(vn, vn, vn, vn, vn, vn, vg, vg, vg,
                     jnp.ones((n_padded, g_padded), bool),
                     jnp.ones(n_padded, bool))
    fn = jax.jit(lambda p: param_policy(p, pod, nodes))
    jax.block_until_ready(fn(params))  # compile, excluded from timing
    best = float("inf")
    for _ in range(max(1, reps)):
        t0 = _time.perf_counter()
        jax.block_until_ready(fn(params))
        best = min(best, _time.perf_counter() - t0)
    return best


def auto_prefilter_k(n_padded: int, policy_cost_s: Optional[float], *,
                     override: Optional[int] = None,
                     k: int = PREFILTER_AUTO_K,
                     threshold_s: float = PREFILTER_COST_THRESHOLD_S,
                     min_nodes: int = PREFILTER_MIN_NODES) -> int:
    """Pick ``SimConfig.node_prefilter_k`` from a measured policy cost.

    Pure decision function (timing-free, unit-testable): an explicit
    ``override`` always wins; otherwise prefiltering turns on iff the
    node axis is large enough (``min_nodes``) AND one policy invocation
    costs more than ``threshold_s``. ``policy_cost_s`` of None reads as
    "unknown" and keeps the conservative dense sweep."""
    if override is not None:
        return int(override)
    if n_padded < min_nodes:
        return 0
    if policy_cost_s is None or policy_cost_s <= threshold_s:
        return 0
    return k


def shape_prefilter_k(n_padded: int, override: Optional[int] = None) -> int:
    """The large-cluster rule for candidates that are interpreted or traced
    per node (the VM, the per-AST jit tier, the thread-pool fallback, a VM
    champion being served): a pure function of the padded node count, no
    timing. Once the node axis reaches ``PREFILTER_MIN_NODES`` the policy
    scores the first ``PREFILTER_AUTO_K`` feasible nodes in node order;
    below that it sweeps every node, and the compiled program is the one
    an explicit 0 compiles. An explicit ``override`` wins, 0 included
    (``VMServeEngine(prefilter_k=0)``); ``CodeEvaluator`` hands its
    ``SimConfig`` field's 0 on as None, because 0 is that field's
    default, so there the dense sweep on a large cluster is asked for as
    ``n_padded`` or more (``SimConfig.resolve_prefilter_k``). One
    function for evaluation (``funsearch.backend.CodeEvaluator``) and VM
    serving (``serve.vm_engine.VMServeEngine``), so that a fitness does
    not depend
    on the tier that answered and a champion is served under the
    semantics it was scored under."""
    if override is not None:
        return int(override)
    return PREFILTER_AUTO_K if n_padded >= PREFILTER_MIN_NODES else 0


def resolve_auto_prefilter(param_policy, params, n_padded: int,
                           g_padded: int, *, override: Optional[int] = None,
                           recorder=None, work_hint: Optional[int] = None,
                           **heuristic_kw) -> int:
    """``auto_prefilter_k`` with the timing probe run only when its answer
    can matter: an explicit override or a small node axis skips the
    (compile-costing) probe entirely, and so does a static ``work_hint``
    (fks_tpu.analysis ``CostEstimate.work``) proving the policy trivially
    cheap — prefiltering never pays for cheap policies (PROFILE.md round
    11), so there is nothing to measure. Records a ``prefilter_auto``
    event on the given recorder so run dirs show why k was chosen."""
    if override is not None:
        return int(override)
    min_nodes = heuristic_kw.get("min_nodes", PREFILTER_MIN_NODES)
    if n_padded < min_nodes:
        return 0
    if work_hint is not None and work_hint < PREFILTER_WORK_HINT_MIN:
        if recorder is not None:
            recorder.event("prefilter_auto", policy_cost_s=None,
                           work_hint=int(work_hint), chosen_k=0,
                           n_padded=n_padded)
        return 0
    cost = probe_policy_cost(param_policy, params, n_padded, g_padded)
    chosen = auto_prefilter_k(n_padded, cost, **heuristic_kw)
    if recorder is not None:
        recorder.event("prefilter_auto", policy_cost_s=round(cost, 7),
                       chosen_k=chosen, n_padded=n_padded)
    return chosen
