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


#: a pod's accepted GPU models are bits 0..30 of an int32; the sign bit
#: stands for "a name that is no node's model" (it matches no node)
MAX_GPU_MODELS = 31
GPU_SPEC_NO_NODE = np.int32(-2 ** 31)


def gpu_model_vocabulary(models) -> tuple:
    """The sorted distinct non-empty model names of a node list."""
    vocab = tuple(sorted({m for m in models if m}))
    if len(vocab) > MAX_GPU_MODELS:
        raise ValueError(
            f"gpu_spec: the node list has {len(vocab)} GPU models; a pod's "
            f"accepted set is an int32 bit word of at most {MAX_GPU_MODELS}")
    return vocab


def gpu_model_leaves(models, n_padded: int) -> dict:
    """The cluster's two type fields from its nodes' model names (empty:
    none), in node order: ``gpu_model`` (index into the vocabulary, -1
    for none and for padding) and ``gpu_models`` (the vocabulary)."""
    models = list(models)
    vocab = gpu_model_vocabulary(models)
    index = np.full(n_padded, -1, np.int32)
    index[:len(models)] = [vocab.index(m) if m else -1 for m in models]
    return dict(gpu_model=index, gpu_models=vocab)


def gpu_spec_bits(spec: str, vocab) -> int:
    """A ``gpu_spec`` (model names joined by ``|``; a repeated name means
    nothing) as a bit word over ``vocab``. Empty: 0, any node. A name
    outside ``vocab`` sets the sign bit only, so a spec of such names
    alone allows no node."""
    bits = 0
    for name in filter(None, (spec or "").split("|")):
        bits |= (1 << vocab.index(name)) if name in vocab \
            else int(GPU_SPEC_NO_NODE)
    return bits


#: what ``gpu_spec_names`` writes for the sign bit: the names a word lost
#: were no node's model, and neither is this one
GPU_SPEC_NO_NODE_NAME = "<no-node>"


def gpu_spec_names(bits: int, vocab) -> str:
    """``gpu_spec_bits`` back: the names of a word's models joined by
    ``|`` (empty for 0), ``GPU_SPEC_NO_NODE_NAME`` for the sign bit."""
    bits = int(bits)
    names = [m for i, m in enumerate(vocab) if bits >> i & 1]
    if bits < 0:
        names.append(GPU_SPEC_NO_NODE_NAME)
    return "|".join(names)


def gpu_spec_allows(spec, gpu_model, xp=np):
    """THE rule of GPU-type constraints, elementwise over broadcastable
    int32 arrays (``xp``: numpy on the host, ``jax.numpy`` in the
    engines' step): may a pod whose accepted-model word is ``spec`` take
    a node whose model index is ``gpu_model``? A word of 0 allows every
    node; otherwise the node needs a model (``>= 0``) whose bit is set,
    so a node without GPUs is in no set and a word of unknown names
    alone (the sign bit) allows nothing."""
    node_bit = xp.where(gpu_model >= 0,
                        xp.int32(1) << xp.maximum(gpu_model, 0), 0)
    return (spec == 0) | ((spec & node_bit) != 0)


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
    # GPU-type constraints (a workload parsed to honour ``gpu_spec``): the
    # node's model as an index into ``gpu_models``, -1 for a node without
    # one (and for padding). None on every other workload: no leaf, and
    # the engines then emit no type term (``sim.engine.place_mask_of``).
    gpu_model: Any = None  # i32[N] | None
    gpu_models: tuple = static_field(default=())  # the sorted model names

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
    # the GPU models a pod accepts as a bit word over the cluster's
    # ``gpu_models`` (bit m = model m), 0 = any, ``GPU_SPEC_NO_NODE`` alone
    # = names only models no node has; None where ``gpu_spec`` is ignored
    gpu_spec: Any = None  # i32[P] | None

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
    initial carry). GPU-type constraints are data too: ``typed`` says
    whether the workload was parsed to honour ``gpu_spec`` (the nodes'
    models AND the pods' accepted sets are there), and nothing else
    switches the engines' type term on.
    """

    cluster: ClusterArrays
    pods: PodArrays
    faults: Any = None
    snapshot: Any = None

    @property
    def typed(self) -> bool:
        return (self.cluster.gpu_model is not None
                and self.pods.gpu_spec is not None)

    @property
    def num_nodes(self) -> int:
        return self.cluster.num_nodes

    @property
    def num_pods(self) -> int:
        return self.pods.num_pods
