"""Struct-of-arrays data model for cluster + workload state.

TPU-first redesign of the reference's mutable Python dataclasses
(reference: simulator/entities.py:4-43 -- GPU/Node/Cluster/Pod). Instead of
object graphs we keep padded, fixed-shape integer arrays so the whole
simulation state is a pytree that lives on device and flows through
``lax.while_loop`` / ``vmap`` / ``shard_map``.

Conventions:
- Node axis ``N`` (padded), per-node GPU axis ``G`` (padded), pod axis ``P``
  (padded). Padding is masked via ``node_mask`` / ``gpu_mask`` / ``pod_mask``
  and never contributes to placement decisions or utilization denominators.
- All resource quantities are int32 (the reference uses exact Python ints;
  int32 covers every shipped trace: cpu_milli <= 128000, memory_mib <= 786432,
  gpu_milli <= 1000, times < 2**31).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import numpy as np


def _pytree_dataclass(cls):
    """Register a dataclass as a JAX pytree (all array fields are leaves)."""
    cls = dataclasses.dataclass(cls)
    fields = [f.name for f in dataclasses.fields(cls) if not f.metadata.get("static")]
    static = [f.name for f in dataclasses.fields(cls) if f.metadata.get("static")]
    jax.tree_util.register_dataclass(cls, data_fields=fields, meta_fields=static)
    return cls


def static_field(**kwargs):
    return dataclasses.field(metadata={"static": True}, **kwargs)


@_pytree_dataclass
class ClusterArrays:
    """Initial cluster state as arrays.

    Mirrors the information content of reference ``Node``/``GPU``/``Cluster``
    (simulator/entities.py:4-26): per-node CPU/memory/GPU-count capacity and
    per-GPU compute (milli) + memory capacity.

    ``gpu_left`` can legitimately exceed ``num_gpus``: the reference parser
    (benchmarks/parser.py:39,56) sets ``gpu_left`` from the declared CSV count
    but only materializes GPU objects when the GPU model is in the memory
    mapping; we preserve that asymmetry.
    """

    cpu_total: Any  # i32[N]
    mem_total: Any  # i32[N]
    gpu_declared: Any  # i32[N] declared GPU count (initial gpu_left)
    num_gpus: Any  # i32[N] number of materialized GPUs (len(node.gpus))
    gpu_milli_total: Any  # i32[N, G] per-GPU compute capacity (0 where padded)
    gpu_mem_total: Any  # i32[N, G] per-GPU memory MiB (0 where padded)
    gpu_mask: Any  # bool[N, G] which GPU slots exist
    node_mask: Any  # bool[N] which node slots are real
    node_ids: tuple = static_field(default=())  # host-side node names, real nodes only

    @property
    def n_padded(self) -> int:
        return int(self.cpu_total.shape[0])

    @property
    def g_padded(self) -> int:
        return int(self.gpu_milli_total.shape[1])

    @property
    def num_nodes(self) -> int:
        return len(self.node_ids)

    def totals(self) -> dict:
        """Cluster-wide capacity totals (reference: evaluator.py:35-38)."""
        return {
            "cpu": int(np.sum(np.asarray(self.cpu_total))),
            "memory": int(np.sum(np.asarray(self.mem_total))),
            "gpu_count": int(np.sum(np.asarray(self.num_gpus))),
            "gpu_milli": int(np.sum(np.asarray(self.gpu_milli_total))),
        }


@_pytree_dataclass
class PodArrays:
    """Workload (pod requests) as time-ordered-by-input arrays.

    Mirrors reference ``Pod`` (simulator/entities.py:29-43). ``tie_rank`` is
    the rank of the pod id in lexicographic string order -- the reference
    breaks equal-time event ordering by ``pod_id`` string comparison
    (event_simulator.py:16-17); ranks reproduce that exactly without strings
    on device.
    """

    cpu: Any  # i32[P]
    mem: Any  # i32[P]
    num_gpu: Any  # i32[P]
    gpu_milli: Any  # i32[P]
    creation_time: Any  # i32[P]
    duration: Any  # i32[P]
    tie_rank: Any  # i32[P]
    pod_mask: Any  # bool[P]
    pod_ids: tuple = static_field(default=())  # host-side pod names, real pods only

    @property
    def p_padded(self) -> int:
        return int(self.cpu.shape[0])

    @property
    def num_pods(self) -> int:
        return len(self.pod_ids)


@_pytree_dataclass
class FaultEvents:
    """Precomputed node fault timeline (fks_tpu.scenarios generator).

    One row per NODE_DOWN / NODE_UP event, padded to a fixed length ``F``
    and masked like every other axis. Faults are *trace events*: both
    engines merge them into the event stream ahead of equal-time pod
    events and flip a per-node availability bit (cordon — a downed node
    scores 0 for new placements; running pods are not evicted), so the
    jitted step stays a pure scan.
    """

    time: Any  # i32[F] event times (padding: INT32_MAX)
    node: Any  # i32[F] node index the event applies to (padding: 0)
    kind: Any  # i32[F] KIND_NODE_DOWN | KIND_NODE_UP (ops.heap vocabulary)
    mask: Any  # bool[F] which rows are real

    @property
    def f_padded(self) -> int:
        return int(self.time.shape[0])

    @property
    def num_events(self) -> int:
        return int(np.sum(np.asarray(self.mask)))


@_pytree_dataclass
class Workload:
    """A parsed (cluster, pods) pair -- unit of simulation input.

    ``faults`` is None for plain workloads (zero pytree leaves — fault-free
    programs compile unchanged) or a ``FaultEvents`` timeline for
    scenario-generated variants. ``snapshot`` is None for a cluster that
    starts empty or a ``fks_tpu.data.snapshot.Snapshot``: what the first
    ``E0`` events of a run decided, from which an engine's
    ``initial_state`` forks (no program reads it: it only shapes the
    initial carry).
    """

    cluster: ClusterArrays
    pods: PodArrays
    faults: Any = None
    snapshot: Any = None

    @property
    def num_nodes(self) -> int:
        return self.cluster.num_nodes

    @property
    def num_pods(self) -> int:
        return self.pods.num_pods
