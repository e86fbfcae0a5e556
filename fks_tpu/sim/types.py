"""Simulation-facing views and state pytrees.

``PodView``/``NodeView`` are the policy interface -- the TPU-native
re-design of the reference's ``PodNodeScorer = Callable[[Pod, Node], int]``
(reference: simulator/main.py:8). Instead of one (pod, node) pair per call,
a policy scores ONE pod against ALL nodes at once: vectorized over the node
axis, jit-traceable, and therefore fusible into the simulation step.
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax.lax
import jax.numpy as jnp

from fks_tpu.ops.heap import EventHeap


class PodView(NamedTuple):
    """Scalar features of the pod being scheduled (reference Pod fields,
    simulator/entities.py:29-43)."""

    cpu_milli: Any
    memory_mib: Any
    num_gpu: Any
    gpu_milli: Any
    creation_time: Any  # as mutated by retries (event_simulator.py:56)
    duration_time: Any


class NodeView(NamedTuple):
    """Per-node state arrays, axis N (+ per-GPU axis G).

    Mirrors reference Node/GPU observable fields (simulator/entities.py:4-21).
    ``gpu_mem_total`` never changes during simulation (the reference never
    allocates GPU memory, only milli), so there is no ``gpu_mem_left``.
    """

    cpu_milli_left: Any  # i32[N]
    cpu_milli_total: Any  # i32[N]
    memory_mib_left: Any  # i32[N]
    memory_mib_total: Any  # i32[N]
    gpu_left: Any  # i32[N] (starts at declared count, parser.py:56)
    num_gpus: Any  # i32[N] == len(node.gpus)
    gpu_milli_left: Any  # i32[N, G]
    gpu_milli_total: Any  # i32[N, G]
    gpu_mem_total: Any  # i32[N, G]
    gpu_mask: Any  # bool[N, G]
    node_mask: Any  # bool[N]


# A policy scores one pod against every node; 0 means "cannot/refuse"
# (strict-argmax > 0 gate, reference main.py:104-111).
PolicyFn = Callable[[PodView, NodeView], Any]  # -> i32[N]


# decision-trace event kinds (TraceBuffer COL_KIND values). RETRY marks a
# creation attempt of a pod that already failed at least once; NODE_DOWN /
# NODE_UP are scenario fault events (fks_tpu.scenarios — pod column -1,
# node column the cordoned node, score/margin 0).
TRACE_CREATE = 0
TRACE_DELETE = 1
TRACE_RETRY = 2
TRACE_NODE_DOWN = 3
TRACE_NODE_UP = 4
TRACE_KIND_NAMES = ("CREATE", "DELETE", "RETRY", "NODE_DOWN", "NODE_UP")


class TraceBuffer(NamedTuple):
    """Bounded per-step decision log carried in the engine state (see
    ``SimConfig.decision_trace``): one row per processed event, filled
    inside the jitted step and appended with a dropped out-of-range
    scatter once full. Integer observables live as COLUMNS of one
    ``i32[T, 8]`` matrix (single row-scatter per event, the ``pod_state``
    layout rationale); the two float observables (winning score,
    second-best margin) ride in a separate ``f[T, 2]`` so the score dtype
    survives. Rows are comparable ACROSS engines: pod ids are original
    input order (the flat engine un-permutes its slot index on write) and
    deletes record score/margin as 0."""

    data: Any  # i32[T, 8], columns below
    scores: Any  # f[T, 2]: (winning score, second-best margin)
    count: Any  # i32 rows written (saturates at T; appends then drop)

    # data column indices
    COL_KIND = 0  # TRACE_CREATE / TRACE_DELETE / TRACE_RETRY
    COL_POD = 1  # original input-order pod id
    # chosen node (-1 = failed/none); held node on DELETE. ALWAYS the
    # GLOBAL node index: under SimConfig.node_prefilter_k the winner is
    # gathered back through the candidate list before the row is written
    # (the local top-k slot never leaks), so cli trace-diff rows stay
    # comparable across prefilter configs.
    COL_NODE = 2
    COL_PENDING = 3  # post-step pending event count
    COL_FREE_CPU = 4  # post-step cluster-wide free aggregates
    COL_FREE_MEM = 5
    COL_FREE_GPU = 6
    COL_FREE_GPU_MILLI = 7


def empty_trace(length: int, score_dtype: Any = jnp.float32) -> TraceBuffer:
    """An all-zero ``TraceBuffer`` with ``length`` rows."""
    return TraceBuffer(
        data=jnp.zeros((length, 8), jnp.int32),
        scores=jnp.zeros((length, 2), score_dtype),
        count=jnp.int32(0),
    )


class SimState(NamedTuple):
    """The lax.while_loop carry: complete simulation + evaluator state.

    Per-pod scheduling state (reference Pod.assigned_* + waiting-set
    membership + retry-mutated creation time, entities.py:42-43,
    main.py:43, event_simulator.py:56) lives as COLUMNS of one
    ``i32[P, 4]`` matrix so each event's read and write are single
    row-gather/row-scatter instructions — per-lane-indexed scatters under
    vmap cost serialized latency per INSTRUCTION on TPU (PROFILE.md), so
    four separate arrays cost 4x. Columns: (assigned_node, gpu bitmask
    bit-cast to i32, pod_ctime, waiting flag). Use the ``assigned_node``/
    ``assigned_gpus``/``pod_ctime``/``waiting`` properties to read."""

    heap: EventHeap
    # cluster (reference Node/GPU mutable fields)
    cpu_left: Any  # i32[N]
    mem_left: Any  # i32[N]
    gpu_left: Any  # i32[N]
    gpu_milli_left: Any  # i32[N, G]
    pod_state: Any  # i32[P, 4] (see class docstring)
    wait_hist: Any  # i32[M] histogram of gpu_milli of waiting GPU pods
    # evaluator accumulators (reference SchedulingEvaluator)
    events_processed: Any  # i32
    snap_idx: Any  # i32 number of snapshots taken
    snap_sums: Any  # f[4] summed cpu/mem/gpu-count/gpu-milli utilization
    frag_sum: Any  # f[] sum of fragmentation event scores
    frag_count: Any  # i32
    max_nodes: Any  # i32 peak active-node count (main.py:67-72)
    # control
    failed: Any  # bool: GPU allocation raised in the reference -> abort
    steps: Any  # i32
    violations: Any  # i32: invariant-audit failures (0 unless enabled)
    numeric_flags: Any  # i32 watchdog bitmask (0 unless SimConfig.watchdog)
    # TraceBuffer, or None unless SimConfig.decision_trace. None adds zero
    # pytree leaves, so the disabled path's carry structure — and therefore
    # the compiled program — is bit-identical to a build without tracing.
    trace: Any = None
    # bool[N] node availability (cordon bit), or None unless the workload
    # carries FaultEvents — same zero-leaf gating as ``trace``.
    node_avail: Any = None

    # pod_state column indices
    COL_NODE = 0
    COL_BITS = 1
    COL_CTIME = 2
    COL_WAIT = 3

    @property
    def assigned_node(self):  # i32[P], -1 = unassigned
        return self.pod_state[..., SimState.COL_NODE]

    @property
    def assigned_gpus(self):  # u32[P] bitmask over G
        return jax.lax.bitcast_convert_type(
            self.pod_state[..., SimState.COL_BITS], jnp.uint32)

    @property
    def pod_ctime(self):  # i32[P] creation_time (mutated on retry)
        return self.pod_state[..., SimState.COL_CTIME]

    @property
    def waiting(self):  # bool[P] waiting_pods membership (main.py:43)
        return self.pod_state[..., SimState.COL_WAIT] != 0


class FlatState(NamedTuple):
    """The flat engine's while_loop carry (fks_tpu.sim.flat): slot-per-pod
    event queue in tie-rank order + the SAME cluster/evaluator fields as
    SimState. Per-pod arrays are in SLOT (tie-rank) order; finalize
    un-permutes them back to input order.

    Dtype annotations below are the defaults. Under ``SimConfig.
    state_pack`` the ``aux`` / ``aux_gpus`` / ``wait_hist`` / ``gpu_left``
    / ``gpu_milli_left`` columns narrow to 16 bits when their full value
    range provably fits at the workload's shape (see
    ``flat._pack_dtypes``) — exact integer packing, never accumulators,
    so results are bit-identical; finalize widens everything back so
    SimResult dtypes are config-independent."""

    # event queue: one slot per pod, slots sorted by tie_rank
    ev_time: Any  # i32[Q]; INF = no pending event
    # per-pod scheduling state in ONE int32: -1 fresh CREATE pending,
    # -2 waiting (failed at least once), >= 0 placed: (node << G)|gpu_bits
    # when packable, else the node index with bits in aux_gpus
    aux: Any  # i32[Q]
    aux_gpus: Any  # u32[Q] gpu bitmask, or None when packed into aux
    pending: Any  # i32 live-slot count (loop-cond scalar)
    # cluster state (as SimState)
    cpu_left: Any
    mem_left: Any
    gpu_left: Any
    gpu_milli_left: Any
    pod_ctime: Any  # i32[Q] creation time, retry-mutated (slot order)
    wait_hist: Any
    # evaluator accumulators (as SimState)
    events_processed: Any
    snap_idx: Any
    snap_sums: Any
    frag_sum: Any
    frag_count: Any
    max_nodes: Any
    failed: Any
    steps: Any
    violations: Any
    numeric_flags: Any  # i32 watchdog bitmask (0 unless SimConfig.watchdog)
    trace: Any = None  # TraceBuffer or None (see SimState.trace)
    # fault-event queue (None unless the workload carries FaultEvents):
    # per-event times, INF once consumed; and the cordon bit per node.
    fault_time: Any = None  # i32[F]
    node_avail: Any = None  # bool[N]


class SimResult(NamedTuple):
    """Final observables; superset of reference EvaluationResults
    (evaluator.py:16-25) + policy score + run metadata."""

    policy_score: Any
    avg_cpu_utilization: Any
    avg_memory_utilization: Any
    avg_gpu_count_utilization: Any
    avg_gpu_memory_utilization: Any
    gpu_fragmentation_score: Any
    num_snapshots: Any
    num_fragmentation_events: Any
    events_processed: Any
    scheduled_pods: Any
    max_nodes: Any
    assigned_node: Any  # i32[P]
    assigned_gpus: Any  # u32[P] bitmask
    pod_ctime: Any  # i32[P] final (retry-mutated) creation times
    cpu_left: Any  # i32[N] final node state
    mem_left: Any
    gpu_left: Any
    gpu_milli_left: Any  # i32[N, G]
    failed: Any  # bool
    truncated: Any  # bool: hit max_steps with events remaining
    invariant_violations: Any  # i32 (0 unless validate_invariants)
    # i32 watchdog bitmask (sim.guards.FLAG_*; 0 unless SimConfig.watchdog):
    # sticky OR of per-step policy-score violations + final fitness check
    numeric_flags: Any
    # decision TraceBuffer, or None unless SimConfig.decision_trace
    # (fks_tpu.funsearch.tracing extracts/aligns it)
    trace: Any = None
