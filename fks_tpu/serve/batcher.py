"""Request coalescing onto the population axis.

The serving insight (ISSUE 8; "Speeding up Policy Simulation in Supply
Chain RL"): the engine already knows how to run many independent lanes in
ONE compiled program — the population/trace-batch machinery
(fks_tpu.parallel). A what-if query is just a one-trace lane, so N
concurrent queries cost one vmapped call: build each query's padded
workload, stack them exactly like ``parallel.traces.stack_traces`` does,
pad the lane axis to the compiled lane bucket with
``parallel.mesh.pad_population`` (the population padder IS the request
batcher), run the AOT executable, and scatter each lane's answer back to
its request.

Three layers here, none of which import the artifact layer (so the
dependency points artifact -> batcher):

- query -> padded ``Workload`` construction (``build_query_workload``)
  + leaf-wise stacking with sentinel-padded snapshot tables
  (``stack_queries``), mirroring ``stack_traces`` at a FIXED bucket
  shape so every same-bucket batch shares one treedef and one aval set;
- ``RequestBatcher``: the flush-policy coalescer (max batch / max wait)
  mapping concurrent ``submit()`` futures onto synchronous batch calls;
- pod-array <-> dict conversion helpers shared by the CLI/service layer.
"""
from __future__ import annotations

import dataclasses
import queue
import threading
import time
from concurrent.futures import Future, InvalidStateError
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from fks_tpu.data.entities import (
    PodArrays, Workload, gpu_spec_bits, gpu_spec_names)
from fks_tpu.obs import spans, trace_ctx
from fks_tpu.parallel.traces import strip_ids
from fks_tpu.resilience.admission import AdmissionConfig, AdmissionController
from fks_tpu.resilience.deadline import (
    Deadline, DeadlineExceeded, ResilienceError, ShedError,
)
from fks_tpu.sim.evaluator import max_snapshot_count, snapshot_trigger_table

#: query pod schema — the reference entity field names (simulator/
#: entities.py:29-43), matching the LLM-facing template docstring: the
#: six whole numbers of a pod. A pod may carry ``gpu_spec`` beside them
#: (``GPU_SPEC_FIELD``: the GPU models it accepts), which no policy reads
POD_FIELDS = ("cpu_milli", "memory_mib", "num_gpu", "gpu_milli",
              "creation_time", "duration_time")
GPU_SPEC_FIELD = "gpu_spec"

#: default lifetime for query pods that omit duration_time: effectively
#: "never deleted inside the what-if horizon"
DEFAULT_DURATION = 1_000_000


def query_gpu_spec(pod: Dict[str, Any]) -> str:
    """A query pod's ``gpu_spec`` as the trace's CSV column writes it:
    GPU model names joined by ``|``. A list of strings is the same set;
    absent, None or empty allows every node. Anything else is a
    ``ValueError`` (the service's 4xx)."""
    spec = pod.get(GPU_SPEC_FIELD)
    if spec is None or isinstance(spec, str):
        return spec or ""
    if isinstance(spec, (list, tuple)) and all(
            isinstance(m, str) for m in spec):
        return "|".join(spec)
    raise ValueError(
        f"gpu_spec {spec!r} is neither a string of GPU model names joined "
        "by '|' nor a list of such names")


def gpu_spec_words(pods: Sequence[Dict[str, Any]], vocab,
                   size: int) -> np.ndarray:
    """i32[size]: each pod's accepted-model word (``gpu_spec_bits``
    against ``vocab``, the cluster's ``gpu_models``; 0 where a pod names
    nothing, and in the padding)."""
    words = np.zeros(size, np.int32)
    for i, p in enumerate(pods):
        if p.get(GPU_SPEC_FIELD):
            words[i] = gpu_spec_bits(query_gpu_spec(p), vocab)
    return words


def validate_query_pods(pods: Sequence[Dict[str, Any]], *, max_pods: int,
                        max_gpu_milli: int,
                        not_before: Optional[int] = None,
                        typed: bool = False) -> None:
    """Reject malformed queries before any device work (the error message
    is the service's 4xx body). ``not_before``: an engine that forks from
    a snapshot (``QueryFork``) takes no pod created before the time of the
    snapshot's last event, because the events before the fork happened.
    ``typed``: does the engine's cluster carry its nodes' GPU models
    (``Workload.typed``)? One that does not refuses a pod with a
    non-empty ``gpu_spec`` by name: it would answer as if the pod named
    nothing, and a wrong answer is worse than none."""
    if not pods:
        raise ValueError("query has no pods")
    if len(pods) > max_pods:
        raise ValueError(
            f"query has {len(pods)} pods > envelope max_pods {max_pods}")
    for i, p in enumerate(pods):
        if not isinstance(p, dict):
            raise ValueError(f"pod {i} is not an object")
        gm = int(p.get("gpu_milli", 0))
        if gm > max_gpu_milli:
            raise ValueError(
                f"pod {i} gpu_milli {gm} > envelope max_gpu_milli "
                f"{max_gpu_milli}")
        for field in ("cpu_milli", "memory_mib", "num_gpu", "gpu_milli"):
            if int(p.get(field, 0)) < 0:
                raise ValueError(f"pod {i} {field} is negative")
        if not_before is not None \
                and int(p.get("creation_time", 0)) < not_before:
            raise ValueError(
                f"pod {i} creation_time {int(p.get('creation_time', 0))} "
                f"lies before the fork: this engine answers from a "
                f"snapshot whose last event is at {not_before}")
        try:
            spec = query_gpu_spec(p)
        except ValueError as e:
            raise ValueError(f"pod {i} {e}") from None
        if spec and not typed:
            raise ValueError(
                f"pod {i} names the GPU models it accepts (gpu_spec "
                f"{spec!r}), and this engine's cluster was parsed without "
                "GPU models: it would answer as if the pod named none. "
                "Build the engine on a workload parsed with "
                "gpu_spec='honor'")


def build_query_workload(cluster, pods: Sequence[Dict[str, Any]],
                         bucket: int, fork: "Optional[QueryFork]" = None
                         ) -> Workload:
    """One query -> a ``Workload`` padded to the pod bucket.

    Pod ids are zero-padded ordinals, so the reference's lexicographic
    tie order equals index order and ``tie_rank = arange`` reproduces it
    exactly. Padding rows are zeros under a False pod_mask (the
    ``pad_workload`` idiom — never read by the engine). With a ``fork``
    the workload is ``base pods ++ query pods`` on a pod axis of
    ``fork.base + bucket`` and carries the fork's snapshot, the query's
    ``tie_rank`` after the base's.

    GPU-type constraints are data: on a cluster that carries its nodes'
    models (``cluster.gpu_model``; a serve engine keeps the leaf only
    where its workload is ``typed``) EVERY query has the ``gpu_spec``
    leaf, each pod's word made by ``gpu_spec_bits`` against the cluster's
    own vocabulary (0 where a pod names nothing), so a bucket has one
    program whatever a query holds. On any other cluster no query has
    the leaf, and the workload is what it was before the field existed
    (``validate_query_pods`` has refused a pod that names models)."""
    p_real = len(pods)
    if p_real > bucket:
        raise ValueError(f"{p_real} pods exceed pod bucket {bucket}")
    base = 0 if fork is None else fork.base     # its rows come first

    def col(field: str, default: int = 0) -> np.ndarray:
        a = np.zeros(base + bucket, np.int32)
        if base:
            a[:base] = fork.cols[field]
        for i, p in enumerate(pods):
            a[base + i] = int(p.get(field, default))
        return a

    spec = None
    if cluster.gpu_model is not None:
        spec = gpu_spec_words(pods, cluster.gpu_models, bucket)
        if base:
            spec = np.concatenate([fork.spec, spec])
    pa = PodArrays(
        cpu=col("cpu_milli"),
        mem=col("memory_mib"),
        num_gpu=col("num_gpu"),
        gpu_milli=col("gpu_milli"),
        creation_time=col("creation_time"),
        duration=col("duration_time", DEFAULT_DURATION),
        tie_rank=(np.arange(bucket, dtype=np.int32) if fork is None
                  else np.concatenate([
                      fork.rank,
                      np.arange(base, base + bucket, dtype=np.int32)])),
        pod_mask=np.arange(base + bucket) < base + p_real,
        pod_ids=(() if fork is None else fork.pod_ids)
        + tuple(f"q-{i:05d}" for i in range(p_real)),
        gpu_spec=spec,
    )
    return Workload(cluster=cluster, pods=pa, faults=None,
                    snapshot=None if fork is None else fork.snapshot)


class QueryFork:
    """The moment of a run that every query of one serve engine forks
    from: the part of a workload's ``snapshot`` (``fks_tpu.data.snapshot``)
    that no query changes, built and validated ONCE per engine. *A
    snapshot says what happened: the first ``E0`` events of the run. A
    forked query's run is the run of ``base pods ++ query pods`` in which
    those events happen as logged and every later event is the exact
    engine's own: the champion decides each CREATE attempt, upstream's
    heap-array rule re-queues each refusal.* Its answer is where the queue
    lands on the cluster as it stands and what the cluster's next events
    look like with the queue in it.

    The base is every pod with an attempt in the log, once, in
    first-attempt order (``base`` of them: after a prefix of placed
    CREATEs the residents, ``base == e0``; after a general prefix the
    departed pods too, whose rows stay on the pod axis, inert). Held
    here, all NumPy: the base's pod columns in that order (on a typed
    workload their ``gpu_spec`` words beside the six, as the trace gave
    them: the replay has refused a snapshot that puts one of them on a
    node it may not take), the snapshot re-indexed to it, and
    ``data.snapshot.Prefix`` re-indexed alike (the cluster after the
    events, their running sums for the evaluator, each base pod's node,
    GPUs, waiting flag and moved creation time, the heap operations).
    Per query, ``stack`` adds what does depend on the query: the trigger
    table, which is sized from the WHOLE run's pod count, with the
    evaluator's sums at the fork read off the prefix, and the heap, which
    CPython lays out by heapifying the query's CREATEs together with the
    base's before the prefix's pops and pushes run
    (``data.snapshot.heap_after`` through ``sim.engine.forked_state``: the
    retry rule reads the heap in array order, so the operations are
    re-run for every query, on ints, and nothing else is: 3 ms for 5,888
    placed CREATEs, 4 ms for the 12,288 events of cpu250's moment on this
    sandbox's CPU)."""

    def __init__(self, workload: Workload):
        from fks_tpu.data.snapshot import NO_EVENT, Snapshot, rekey
        from fks_tpu.sim.engine import fork_prefix

        snap, p = workload.snapshot, workload.pods
        prefix = fork_prefix(workload)          # validates, once
        attempts = np.asarray(snap.pod, np.int64)
        _, first = np.unique(attempts, return_index=True)
        order = attempts[np.sort(first)]
        #: events of the prefix; pods of the base (their rows on the axis)
        self.e0, self.base = snap.e0, len(order)
        fields = {"cpu_milli": p.cpu, "memory_mib": p.mem,
                  "num_gpu": p.num_gpu, "gpu_milli": p.gpu_milli,
                  "creation_time": p.creation_time,
                  "duration_time": p.duration}
        self.cols = {k: np.asarray(v)[order].astype(np.int32)
                     for k, v in fields.items()}
        #: the base's accepted-model words, or None (not typed)
        self.spec = np.asarray(p.gpu_spec, np.int32)[order] \
            if workload.typed else None
        rank = np.empty(self.base, np.int32)
        rank[np.argsort(np.asarray(p.tie_rank)[order], kind="stable")] = \
            np.arange(self.base, dtype=np.int32)
        self.rank = rank        # the base's pod-id order, made dense
        self.pod_ids = tuple(p.pod_ids[int(i)] for i in order)
        at = np.full(p.p_padded, -1, np.int64)  # a pod's row in the base
        at[order] = np.arange(self.base)
        self.snapshot = Snapshot(
            pod=at[attempts].astype(np.int32), node=np.asarray(snap.node),
            gpus=np.asarray(snap.gpus), event=np.asarray(snap.event),
            e0=snap.e0, rule=snap.rule)

        # the heap's items name a pod by its rank: the base's, made dense
        old = np.asarray(p.tie_rank, np.int64)
        new_rank = np.full(int(old.max(initial=-1)) + 1, -1, np.int64)
        new_rank[old[order]] = rank
        self.prefix = prefix._replace(
            node=prefix.node[order], gpus=prefix.gpus[order],
            waiting=prefix.waiting[order], ctime=prefix.ctime[order],
            next_event=prefix.next_event[order],
            pushes=rekey(prefix.pushes, new_rank),
            heap=rekey(prefix.heap, new_rank))
        #: no query pod may be created before this (the 4xx of
        #: ``validate_query_pods``): the time of the last prefix event
        self.not_before = prefix.last_time
        resident = (self.prefix.node >= 0) \
            & (self.prefix.next_event != NO_EVENT)
        self.residents = int(resident.sum())
        self.waiting = int((self.prefix.waiting
                            & (self.prefix.next_event != NO_EVENT)).sum())
        self.typed_residents = 0 if self.spec is None \
            else int(np.count_nonzero(self.spec[resident]))
        self.nodes_loaded = int(len(np.unique(self.prefix.node[resident])))
        c = workload.cluster
        #: bytes of one lane's upload that are the base's and not the
        #: query's: its rows on the pod axis (pod columns, the
        #: ``gpu_spec`` words among them where there are any, mask,
        #: ``pod_state`` and as many rows of the heap, which is as wide
        #: as the axis) and the cluster's four ``*_left`` arrays
        self.lane_bytes = int(
            self.base * ((7 + (self.spec is not None)) * 4 + 1 + 16 + 16)
            + 4 * c.n_padded * (3 + c.g_padded))

    def stack(self, cluster, pod_lists: Sequence[Sequence[dict]],
              bucket: int, cfg, klen: int):
        """``stack_query_tables`` for forked queries, in NumPy throughout
        (the one upload is the engine's h2d stage): ``(pods[Q, base +
        bucket], ktable[Q, K], state0[Q, ...])``, ``state0`` the exact
        engine's ``forked_state`` of each query. ``cfg.max_steps`` is
        absolute: ``E0`` plus the bucket's budget. The per-query heap
        replays lie in the ``serve/chunk/stack/heap_replay`` span (the
        whole of ``forked_state``: the heap operations and a few array
        fills)."""
        from fks_tpu.sim.engine import forked_state

        wls = [build_query_workload(cluster, p, bucket, self)
               for p in pod_lists]
        kt = _query_ktable(wls, cfg, klen)
        with spans.span("serve/chunk/stack/heap_replay",
                        queries=len(wls), events=self.e0):
            states = [forked_state(w, cfg, self.prefix, kt[i])
                      for i, w in enumerate(wls)]
        stack = lambda *xs: np.stack([np.asarray(x) for x in xs])  # noqa: E731
        # the ids are static pytree meta: dropped, as ``strip_ids`` does
        bare = [dataclasses.replace(w.pods, pod_ids=()) for w in wls]
        return (jax.tree_util.tree_map(stack, *bare), kt,
                jax.tree_util.tree_map(stack, *states))


def _query_ktable(wls: Sequence[Workload], cfg, klen: int) -> np.ndarray:
    """Per-query snapshot trigger tables at the bucket's fixed width:
    each table is sized from the query's REAL pod count (the reference's
    ``initialize(total_events)`` semantics) and padded with the INT32_MAX
    sentinel, which never fires. A forked query's real pod count is the
    WHOLE run's, residents included (``Workload.num_pods``), and
    ``cfg.max_steps`` its absolute cap: the snapshots that lie before the
    fork are in the table."""
    kt = np.full((len(wls), klen), KT_SENTINEL, np.int32)
    for i, w in enumerate(wls):
        tbl = snapshot_trigger_table(
            w.num_pods,
            max_snapshot_count(cfg.max_steps, w.num_pods,
                               cfg.snapshot_interval),
            cfg.snapshot_interval)
        if len(tbl) > klen:
            raise ValueError(
                f"query with {w.num_pods} pods needs {len(tbl)} snapshot "
                f"slots > bucket table width {klen}; route it to a smaller "
                "bucket")
        kt[i, : len(tbl)] = tbl
    return kt


def stack_queries(mod, cluster, pod_lists: Sequence[Sequence[dict]],
                  bucket: int, cfg, klen: int):
    """Stack Q query workloads into (workload[Q,...], ktable[Q,K],
    state0[Q,...]) at the bucket's fixed shapes.

    The ``stack_traces`` recipe with serving's extra constraint: K
    (``klen``) is fixed per bucket so every batch matches the AOT
    executable's avals. ``cfg.max_steps`` must be the bucket's resolved
    step budget. This is the historical full-workload stacking entry;
    the mesh-sharded hot path uses ``stack_query_tables``, which splits
    the constant cluster out of the per-batch upload."""
    max_steps = cfg.max_steps
    assert max_steps is not None, "bucket SimConfig must pin max_steps"
    wls = [build_query_workload(cluster, p, bucket) for p in pod_lists]
    kt = _query_ktable(wls, cfg, klen)
    states = [mod.initial_state(w, cfg) for w in wls]
    stacked_wl = jax.tree_util.tree_map(
        lambda *xs: jnp.stack([jnp.asarray(x) for x in xs]),
        *[strip_ids(w) for w in wls])
    stacked_state = jax.tree_util.tree_map(
        lambda *xs: jnp.stack([jnp.asarray(x) for x in xs]), *states)
    return stacked_wl, jnp.asarray(kt), stacked_state


def stack_query_tables(mod, cluster, pod_lists: Sequence[Sequence[dict]],
                       bucket: int, cfg, klen: int,
                       fork: Optional[QueryFork] = None):
    """``stack_queries`` split for the device-resident serve hot path:
    returns ``(pods[Q,...] numpy, ktable[Q,K] numpy, state0[Q,...])``.

    The constant cluster arrays are NOT stacked or returned — the serve
    engine bakes them into the compiled program as closure constants, so
    a batch ships only the query delta (pod tables), the snapshot trigger
    table (content-hash cached on device by the engine), and the initial
    state. Pods and ktable stay host-side numpy so the engine can hash
    the ktable bytes BEFORE any transfer and account every uploaded
    byte; the upload itself is one explicit ``device_put`` at the
    engine's h2d stage."""
    max_steps = cfg.max_steps
    assert max_steps is not None, "bucket SimConfig must pin max_steps"
    if fork is not None:
        return fork.stack(cluster, pod_lists, bucket, cfg, klen)
    wls = [build_query_workload(cluster, p, bucket) for p in pod_lists]
    kt = _query_ktable(wls, cfg, klen)
    stacked_pods = jax.tree_util.tree_map(
        lambda *xs: np.stack([np.asarray(x) for x in xs]),
        *[strip_ids(w).pods for w in wls])
    states = [mod.initial_state(w, cfg) for w in wls]
    stacked_state = jax.tree_util.tree_map(
        lambda *xs: jnp.stack([jnp.asarray(x) for x in xs]), *states)
    return stacked_pods, kt, stacked_state


# ------------------------------------------------- packed query uploads
#
# SimConfig.state_pack narrows the FLAT engine's carry columns to 16-bit
# where ranges provably fit (sim/flat.py). The serve upload path reuses
# the same idea on the REQUEST tables: the wire/H2D format is 16-bit, the
# engine widens back to int32 on device (a free VPU cast), and every
# packing decision is static per bucket — never per batch — so packed
# avals are stable and the warm path stays recompile-free.

#: int32 sentinel in snapshot trigger tables ("never fires")
KT_SENTINEL = np.iinfo(np.int32).max
#: its image on the packed (uint16) upload path
KT_SENTINEL_PACKED = np.iinfo(np.uint16).max


def query_pack_plan(cfg, bucket: int, max_gpu_milli: int) -> dict:
    """The static per-bucket packing plan for query upload tables (empty
    unless ``cfg.state_pack``). Packable columns and their proofs:

    - ``ktable`` -> uint16: trigger steps are bounded by the bucket's
      ``max_steps`` plus the last fractional-progress rung (< max_steps
      + bucket for the reference 0.05 interval), so they fit below the
      remapped sentinel whenever ``max_steps + bucket + 4 < 65535``;
    - ``gpu_milli`` -> int16: admission validates every pod against the
      envelope's ``max_gpu_milli``;
    - ``tie_rank`` -> int16: always ``arange(bucket)``.

    Every other leaf ships as it is: the ``gpu_spec`` words of a typed
    engine's queries are bit sets whose sign bit means something
    (``GPU_SPEC_NO_NODE``), so nothing narrows them.

    All casts are integer->integer with proven ranges, so the round trip
    through ``pack_query_tables``/``unpack_query_tables`` is
    bit-identical (asserted by tests/test_serve_sharded.py)."""
    if not getattr(cfg, "state_pack", False):
        return {}
    plan: Dict[str, Any] = {}
    if (cfg.max_steps is not None
            and cfg.max_steps + bucket + 4 < KT_SENTINEL_PACKED):
        plan["ktable"] = np.uint16
    if 0 <= int(max_gpu_milli) <= np.iinfo(np.int16).max:
        plan["gpu_milli"] = np.int16
    if bucket <= np.iinfo(np.int16).max:
        plan["tie_rank"] = np.int16
    return plan


def pack_query_tables(pods: PodArrays, kt: np.ndarray, plan: dict):
    """Apply a ``query_pack_plan`` to host-staged tables (numpy, before
    upload). Identity when the plan is empty."""
    if not plan:
        return pods, kt
    if "ktable" in plan:
        kt = np.where(kt == KT_SENTINEL,
                      KT_SENTINEL_PACKED, kt).astype(plan["ktable"])
    repl = {f: np.asarray(getattr(pods, f)).astype(plan[f])
            for f in ("gpu_milli", "tie_rank") if f in plan}
    if repl:
        pods = dataclasses.replace(pods, **repl)
    return pods, kt


def unpack_query_tables(pods, kt, plan: dict):
    """Invert ``pack_query_tables`` ON DEVICE (traced inside the compiled
    serve program): widen back to the engine's int32, remapping the
    ktable sentinel. The H2D transfer stays packed."""
    if not plan:
        return pods, kt
    if "ktable" in plan:
        kt = jnp.where(kt == np.asarray(KT_SENTINEL_PACKED, plan["ktable"]),
                       jnp.int32(KT_SENTINEL), kt.astype(jnp.int32))
    repl = {f: getattr(pods, f).astype(jnp.int32)
            for f in ("gpu_milli", "tie_rank") if f in plan}
    if repl:
        pods = dataclasses.replace(pods, **repl)
    return pods, kt


def pack_program_tables(prog) -> tuple:
    """A ``VMProgram`` -> the packed host-side wire pytree the VM serve
    engine uploads on a hot-swap: the four i32[O] op-index tables ride
    ONE contiguous ``i32[4, O]`` buffer and the two i32 scalars one
    ``i32[2]`` buffer, so a champion swap ships 4 H2D transfers
    (tables/imm/consts/meta) instead of 8 — the ``query_pack_plan`` idea
    applied to the program side of the upload. Host numpy throughout, so
    the engine can size and account the transfer before it happens."""
    tables = np.stack([np.asarray(prog.opcode), np.asarray(prog.a),
                       np.asarray(prog.b), np.asarray(prog.c)]
                      ).astype(np.int32)
    meta = np.asarray([int(prog.n_ops), int(prog.out_reg)], np.int32)
    return (tables, np.asarray(prog.imm), np.asarray(prog.consts), meta)


def unpack_program_tables(packed):
    """Invert ``pack_program_tables`` ON DEVICE (traced inside the
    compiled VM serve program): split the contiguous table block back
    into the ``VMProgram`` pytree the VM executor consumes."""
    from fks_tpu.funsearch.vm import VMProgram

    tables, imm, consts, meta = packed
    return VMProgram(opcode=tables[0], a=tables[1], b=tables[2],
                     c=tables[3], imm=imm, consts=consts,
                     n_ops=meta[0], out_reg=meta[1])


def pack_portfolio_tables(progs) -> tuple:
    """N ``VMProgram``s -> ONE stacked packed wire pytree: the per-slot
    ``pack_program_tables`` tuples gain a leading slot axis, so the whole
    portfolio ships as the same 4 H2D transfers a single champion does
    (i32[S,4,O] tables / f32[S,O] imm / f32[S,32] consts / i32[S,2]
    meta). A slot swap re-uploads this block — still a pure table upload,
    never a recompile."""
    packed = [pack_program_tables(p) for p in progs]
    return tuple(np.stack([pk[i] for pk in packed]) for i in range(4))


def unpack_portfolio_tables(packed):
    """Invert ``pack_portfolio_tables`` ON DEVICE: the stacked wire block
    back into ONE slot-stacked ``VMProgram`` pytree (leading slot axis on
    every leaf) that ``vm.select_slot`` gathers per lane."""
    from fks_tpu.funsearch.vm import VMProgram

    tables, imm, consts, meta = packed
    return VMProgram(opcode=tables[:, 0], a=tables[:, 1], b=tables[:, 2],
                     c=tables[:, 3], imm=imm, consts=consts,
                     n_ops=meta[:, 0], out_reg=meta[:, 1])


def tree_h2d_bytes(*trees) -> int:
    """Total bytes a host->device upload of these pytrees ships — the
    engine's ``serve_h2d_bytes_per_query`` accounting."""
    return int(sum(x.nbytes for t in trees
                   for x in jax.tree_util.tree_leaves(t)
                   if hasattr(x, "nbytes")))


def pods_to_dicts(pods: PodArrays, limit: Optional[int] = None,
                  gpu_models: Sequence[str] = ()) -> List[dict]:
    """Real pod rows back to query-schema dicts — sources selftest and
    trace-replay queries from a parsed workload. A pod of a typed
    workload that names GPU models gets its ``gpu_spec`` back as the
    trace wrote it, its word read against ``gpu_models`` (the cluster's
    ``gpu_models``); one that names none has no such key."""
    mask = np.asarray(pods.pod_mask)
    idx = np.nonzero(mask)[0]
    if limit is not None:
        idx = idx[:limit]
    cols = {
        "cpu_milli": np.asarray(pods.cpu),
        "memory_mib": np.asarray(pods.mem),
        "num_gpu": np.asarray(pods.num_gpu),
        "gpu_milli": np.asarray(pods.gpu_milli),
        "creation_time": np.asarray(pods.creation_time),
        "duration_time": np.asarray(pods.duration),
    }
    out = [{k: int(v[i]) for k, v in cols.items()} for i in idx]
    if pods.gpu_spec is not None:
        spec = np.asarray(pods.gpu_spec)
        for row, i in zip(out, idx):
            if spec[i]:
                row[GPU_SPEC_FIELD] = gpu_spec_names(spec[i], gpu_models)
    return out


class QueuedRequest:
    """One queued submit: the query, its Future, its timestamps, and the
    request's ``TraceContext`` (the caller's, or one made at submit),
    carried OBJECT-in-hand across the submit-thread -> worker-thread
    boundary (the hop where thread-local span nesting loses causality)."""

    __slots__ = ("query", "fut", "t_enq", "deadline", "ctx", "t_deq")

    def __init__(self, query, fut, t_enq, deadline, ctx):
        self.query = query
        self.fut = fut
        self.t_enq = t_enq
        self.deadline = deadline
        self.ctx = ctx
        self.t_deq = t_enq  # stamped by the worker at dequeue

    @property
    def trace_id(self):
        return self.ctx.trace_id if self.ctx is not None else None


class RequestBatcher:
    """Flush-policy request coalescer over a synchronous batch handler.

    ``submit(query)`` returns a Future; a daemon thread accumulates
    pending requests and flushes a batch when it reaches ``max_batch``
    OR the oldest pending request has waited ``max_wait_s`` — the
    classic latency/occupancy trade. The handler receives
    ``(queries, enqueue_times)`` and returns one answer per query in
    order (scatter-back is positional); a handler exception fails every
    future in the batch. ``close()`` flushes the remainder and joins.

    Resilience hooks (fks_tpu.resilience):

    - every submit passes ADMISSION CONTROL: a bounded queue
      (``max_queue``) plus a projected-wait check against the request's
      ``Deadline`` — refused work raises ``ShedError`` (an HTTP 503 with
      Retry-After upstream) instead of queueing to miss its deadline;
    - a request whose deadline expires while queued is completed with
      ``DeadlineExceeded``, never silently handled late;
    - every dequeued Future is completed EXACTLY ONCE — the batch-failure
      path, a short handler answer list, and drain-time shedding all
      resolve through one ``_complete`` funnel;
    - ``drain()`` is the SIGTERM path: stop admitting, give the worker a
      grace budget to finish real work, then shed whatever remains with
      a typed error so no client ever hangs on a dying server.

    Spans (fks_tpu.obs.spans; always on): ``submit(..., ctx=)`` (or the
    submitting thread's active context, or a fresh one) rides the
    ``QueuedRequest`` to the worker, which stamps each request's
    ``serve/request/queue_wait`` (submit to dequeue) and
    ``serve/request/batch_wait`` (dequeue to batch start) when the batch
    starts and its ``serve/request`` root (submit to answer) when its
    Future completes. The handler runs under a per-flush context, so the
    engine's ``serve/batch`` root joins that flush's trace, lists the
    request traces it carries, and every request root names it (``batch``).
    Every typed error raised or completed for a request carries its
    ``trace_id`` (so 503 bodies correlate to the flight-recorder trail),
    and the handler can read the in-flight requests via ``inflight()``."""

    def __init__(self, handle_batch: Callable[[list, list], list],
                 max_batch: int = 8, max_wait_s: float = 0.005,
                 max_queue: int = 0,
                 admission_cfg: Optional[AdmissionConfig] = None,
                 recorder: Any = None,
                 expired_cb: Optional[Callable[[Any], None]] = None):
        from fks_tpu import obs

        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        self._handle = handle_batch
        # accounting hook: called with the QUERY of every request whose
        # deadline expired while queued (the service charges the tenant;
        # the batcher knows futures, not tenants). Must not raise.
        self._expired_cb = expired_cb
        self.max_batch = int(max_batch)
        self.max_wait_s = float(max_wait_s)
        cfg = admission_cfg or AdmissionConfig()
        if max_queue:
            cfg = dataclasses.replace(cfg, max_queue=int(max_queue))
        self.admission = AdmissionController(cfg)
        self.recorder = recorder if recorder is not None else obs.get_recorder()
        self._q: "queue.SimpleQueue" = queue.SimpleQueue()
        self.batches = 0
        self.submitted = 0
        self.completed = 0
        self.expired = 0
        self.shed_inflight = 0  # dequeued futures shed at drain time
        self.shed_draining = 0  # submits refused because drain started
        self._occupancy_sum = 0.0
        self._closed = False
        self._draining = False
        self._shed_mode = False  # grace exhausted: flush = shed, not run
        self._inflight: Sequence[QueuedRequest] = ()
        self._thread = threading.Thread(
            target=self._loop, name="serve-batcher", daemon=True)
        self._thread.start()

    def submit(self, query, deadline: Optional[Deadline] = None,
               ctx: Optional[trace_ctx.TraceContext] = None) -> Future:
        if ctx is None:  # inherit the submitting thread's trace, if any
            ctx = trace_ctx.current() or trace_ctx.new_trace()
        tid = ctx.trace_id
        if self._draining:  # before the closed check: drain() sets both,
            # and a drained server sheds with a TYPED error
            self.shed_draining += 1
            self.recorder.event("shed", reason="draining",
                                queue_depth=self.admission.depth,
                                **({"trace_id": tid} if tid else {}))
            raise ShedError("server is draining", reason="draining",
                            trace_id=tid)
        if self._closed:
            raise RuntimeError("batcher is closed")
        try:
            # the service's query tuple carries the tenant at index 2
            # (the _note_expired convention): admission uses it to price
            # the Retry-After hint at the SHEDDING tenant's service time
            tenant = (query[2] if isinstance(query, tuple)
                      and len(query) > 2 else None)
            self.admission.admit(deadline, tenant=tenant)
        except ShedError as e:
            e.trace_id = tid
            self.recorder.event("shed", reason=e.reason,
                                queue_depth=self.admission.depth,
                                retry_after_s=e.retry_after_s,
                                **({"trace_id": tid} if tid else {}))
            raise
        self.submitted += 1
        fut: Future = Future()
        self._q.put(QueuedRequest(query, fut, time.perf_counter(),
                                  deadline, ctx))
        return fut

    def inflight(self) -> Sequence[QueuedRequest]:
        """The requests of the batch currently inside the handler (their
        contexts + enqueue/dequeue stamps) — read by the handler itself
        to emit per-request waterfall spans. Empty outside a handler
        call; only meaningful ON the worker thread."""
        return self._inflight

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        self._q.put(None)
        self._thread.join()

    def drain(self, grace_s: float = 5.0) -> Dict[str, Any]:
        """SIGTERM path: shed new submits, let the worker finish queued
        work within ``grace_s``, then shed the remainder with a typed
        error. Returns completion accounting; never leaves a Future
        pending."""
        if self._closed:
            return {"pending": 0, "completed": 0, "expired": 0,
                    "shed": 0, "stuck": False}
        pending_at = self.admission.depth
        c0, e0, s0 = self.completed, self.expired, self.shed_inflight
        self._draining = True
        self._q.put(None)
        self._thread.join(max(0.0, float(grace_s)))
        if self._thread.is_alive():
            # grace exhausted — remaining flushes shed instead of running
            self._shed_mode = True
            self._thread.join(max(0.1, float(grace_s)))
        self._closed = True
        return {"pending": pending_at,
                "completed": self.completed - c0,
                "expired": self.expired - e0,
                "shed": self.shed_inflight - s0,
                "stuck": self._thread.is_alive()}

    @property
    def mean_occupancy(self) -> float:
        """Mean fraction of max_batch filled per flushed batch."""
        return self._occupancy_sum / self.batches if self.batches else 0.0

    # ----- internals

    @staticmethod
    def _complete(fut: Future, *, result=None, exc=None) -> bool:
        """The single completion funnel: every dequeued Future resolves
        through here exactly once (a cancelled or already-completed
        Future is left alone, never raised over)."""
        if not fut.set_running_or_notify_cancel():
            return False  # client cancelled while queued
        try:
            if exc is not None:
                fut.set_exception(exc)
            else:
                fut.set_result(result)
        except InvalidStateError:  # pragma: no cover — funnel invariant
            return False
        return True

    def _loop(self) -> None:
        pending: List[QueuedRequest] = []
        while True:
            timeout = None
            if pending:
                waited = time.perf_counter() - pending[0].t_enq
                timeout = max(0.0, self.max_wait_s - waited)
            try:
                item = self._q.get(timeout=timeout)
            except queue.Empty:  # oldest request hit max_wait
                self._flush(pending)
                pending = []
                continue
            if item is None:  # close/drain sentinel
                self._flush(pending)
                return
            item.t_deq = time.perf_counter()
            pending.append(item)
            if len(pending) >= self.max_batch:
                self._flush(pending)
                pending = []

    def _flush(self, pending: List[QueuedRequest]) -> None:
        if not pending:
            return
        self.admission.release(len(pending))
        if self._shed_mode:  # drain grace exhausted: typed shed, no work
            for r in pending:
                if self._complete(r.fut, exc=ShedError(
                        "server shut down before this request ran",
                        trace_id=r.trace_id)):
                    self.shed_inflight += 1
            return
        live: List[QueuedRequest] = []
        for r in pending:
            if r.deadline is not None and r.deadline.expired():
                if self._complete(r.fut, exc=DeadlineExceeded(
                        "deadline expired while queued",
                        trace_id=r.trace_id)):
                    self.expired += 1
                    self.admission.note_expired()
                    if self._expired_cb is not None:
                        try:
                            self._expired_cb(r.query)
                        except Exception:  # noqa: BLE001 — accounting
                            pass  # must never fail the drain/flush path
            else:
                live.append(r)
        if not live:
            return
        self.batches += 1
        self._occupancy_sum += len(live) / self.max_batch
        queries = [r.query for r in live]
        enq = [r.t_enq for r in live]
        t0 = time.perf_counter()
        batch = trace_ctx.TraceContext(
            "batch-" + trace_ctx.new_span_id(), None,
            carries=tuple(r.trace_id for r in live))
        for r in live:
            r.t_deq = min(max(r.t_deq, r.t_enq), t0)
            self._emit("serve/request/queue_wait", r, t0=r.t_enq,
                       t1=r.t_deq)
            self._emit("serve/request/batch_wait", r, t0=r.t_deq, t1=t0)
        self._inflight = live
        try:
            with trace_ctx.activate(batch):
                answers = self._handle(queries, enq)
        except Exception as e:  # noqa: BLE001 — fail the batch, not the loop
            for r in live:
                self._complete(r.fut, exc=e)
                self._request_span(r, batch, error=type(e).__name__)
            return
        finally:
            self._inflight = ()
        self.admission.note_batch(len(live), time.perf_counter() - t0)
        answers = list(answers)
        for i, r in enumerate(live):
            if i < len(answers):
                if self._complete(r.fut, result=answers[i]):
                    self.completed += 1
            else:
                # a short answer list must FAIL the unmatched futures,
                # never leave them hanging (the old zip() bug)
                self._complete(r.fut, exc=ResilienceError(
                    f"batch handler returned {len(answers)} answers for "
                    f"{len(live)} queries", reason="short_answer",
                    trace_id=r.trace_id))
            self._request_span(r, batch)

    def _request_span(self, r: QueuedRequest, batch, **fields) -> None:
        """The ``serve/request`` root of one request that went through the
        handler: submit to the completion of its Future. The service's
        query tuple carries the request id at index 0 and the tenant at
        index 2 (the admission convention)."""
        q = r.query
        if isinstance(q, tuple):
            if q and isinstance(q[0], str):
                fields["request"] = q[0]
            if len(q) > 2 and q[2]:
                fields["tenant"] = q[2]
        self._emit(trace_ctx.SERVE_ROOT, r, t0=r.t_enq, root=True,
                   batch=batch.trace_id, **fields)

    def _emit(self, path: str, r: QueuedRequest, **kw) -> None:
        try:
            trace_ctx.emit(self.recorder, path, ctx=r.ctx, **kw)
        except Exception:  # noqa: BLE001 — a run directory that cannot be
            pass  # written must never cost a request its answer
