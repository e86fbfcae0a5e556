"""Champion-serving artifacts: pinned champion -> warm AOT query engine.

The evolution loop persists champions as JSON in the ledger format
(``policies/discovered/funsearch_*.json``); this module turns one of them
plus a declared shape envelope into a **no-recompile** query engine:

- ``load_champion`` / ``latest_champion``: read a champion (single-dict
  or top-policies-list ledger files) back off disk.
- ``ShapeEnvelope``: the declared serving envelope — max pods per query,
  max batch, the pod-bucket ladder queries pad to, the gpu_milli range
  the shared wait histogram must cover. Shape-bucketing is what makes
  "warm" possible: a finite set of (lane_bucket, pod_bucket) shapes,
  each compiled exactly once.
- ``ServeEngine``: per (lane_bucket, pod_bucket) combination, the engine
  step/finalize pipeline is AOT-compiled via
  ``jax.jit(fn).lower(example).compile()`` with the champion's policy
  baked in as closure constants and the stacked workload/ktable/state as
  ARGUMENTS — the inverse of ``make_trace_batch_eval``'s closure capture,
  which would re-trace per batch. Calling the resulting ``Compiled``
  executable can never trigger compilation, so the zero-recompile warm
  path is structural, not best-effort. An artifact on disk is its
  ``artifact.json`` alone: a reloaded one re-lowers, and fetches the XLA
  binaries from the process's one persistent compilation cache
  (``fks_tpu.utils.place_compile_cache``, placed by the entry point —
  never repointed here).

The engine answers are plain dicts (score, scheduled count, per-pod
placements) so the service layer can JSON them straight out.
"""
from __future__ import annotations

import contextlib
import dataclasses
import glob
import hashlib
import json
import os
import warnings
from collections import OrderedDict
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from fks_tpu import obs
from fks_tpu.data.entities import ClusterArrays, Workload
from fks_tpu.obs import trace_ctx
from fks_tpu.parallel.mesh import (
    lanes_per_device, make_sharded_serve_fn, num_shards, occupancy_stats,
    pad_population, serve_lane_count, serve_sharding,
)
from fks_tpu.serve.batcher import (
    QueryFork, build_query_workload, pack_query_tables, pods_to_dicts,
    query_pack_plan, stack_query_tables, tree_h2d_bytes, unpack_query_tables,
    validate_query_pods,
)
from fks_tpu.sim import get_engine
from fks_tpu.sim.engine import (
    SimConfig, resolve_auto_prefilter, run_batched_lanes,
)
from fks_tpu.sim.evaluator import max_snapshot_count

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
#: where the evolution loop lands its champion JSONs
CHAMPION_DIR = os.path.join(REPO, "policies", "discovered")

ARTIFACT_VERSION = 1


# ---------------------------------------------------------------- champions


@dataclasses.dataclass(frozen=True)
class ChampionSpec:
    """A pinned champion: the evolved source plus its ledger provenance."""

    code: str
    score: float = 0.0
    generation: int = -1
    timestamp: str = ""
    source: str = ""  # file path it was loaded from, "" for in-memory

    def to_json(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_json(cls, doc: dict, source: str = "") -> "ChampionSpec":
        return cls(code=doc["code"], score=float(doc.get("score", 0.0)),
                   generation=int(doc.get("generation", -1)),
                   timestamp=str(doc.get("timestamp", "")), source=source)


def load_champion(path: str) -> ChampionSpec:
    """Load a champion from an evolution-ledger JSON: either a single
    champion dict (``save_best_policy``) or a top-policies list
    (``save_top_policies`` — the best-scoring entry wins). Validates the
    fields an engine build would otherwise trip over later: ``code`` must
    be a non-empty string and ``score`` a finite number — a torn or
    hand-mangled ledger file fails HERE, with the path in the message,
    not deep inside the transpiler."""
    import math

    try:
        with open(path) as f:
            doc = json.load(f)
    except json.JSONDecodeError as e:
        raise ValueError(f"{path}: not valid JSON "
                         f"(truncated mid-write?): {e}") from e
    if isinstance(doc, list):
        if not doc:
            raise ValueError(f"{path}: empty top-policies list")
        doc = max(doc, key=lambda d: float(d.get("score", 0.0)))
    if not isinstance(doc, dict):
        raise ValueError(f"{path}: champion JSON must be a dict or list, "
                         f"got {type(doc).__name__}")
    code = doc.get("code")
    if not isinstance(code, str) or not code.strip():
        raise ValueError(f"{path}: no usable 'code' field — "
                         "not a champion JSON")
    try:
        score = float(doc.get("score", 0.0))
    except (TypeError, ValueError) as e:
        raise ValueError(f"{path}: non-numeric 'score' "
                         f"{doc.get('score')!r}") from e
    if not math.isfinite(score):
        raise ValueError(f"{path}: non-finite 'score' {score!r}")
    return ChampionSpec.from_json(doc, source=path)


def latest_champion(directory: str = "", recorder=None) -> Optional[str]:
    """Path of the best champion JSON under ``directory`` (default: the
    repo's discovered-policies ledger), by score then filename; None when
    the ledger is empty. A malformed file — typically the newest one,
    torn by a crash mid-write — is skipped with a recorded ``alert``
    event instead of hiding the whole ledger or raising."""
    directory = directory or CHAMPION_DIR
    rec = recorder if recorder is not None else obs.get_recorder()
    best: Optional[Tuple[float, str]] = None
    for path in sorted(glob.glob(os.path.join(directory, "*.json"))):
        try:
            spec = load_champion(path)
        except (ValueError, KeyError, OSError) as e:
            rec.event("alert", source="champion_ledger", path=path,
                      detail=f"skipping unreadable champion: {e}")
            continue  # one malformed file must not hide the ledger
        if best is None or spec.score > best[0]:
            best = (spec.score, path)
    return best[1] if best else None


# ----------------------------------------------------------------- envelope


@dataclasses.dataclass(frozen=True)
class ShapeEnvelope:
    """The declared serving envelope: every shape the warm engine must
    answer without compiling. Queries pad UP to the nearest bucket, so
    the compiled-program set is finite and enumerable (``warmup``)."""

    max_pods: int = 1024       # largest query (pods per what-if)
    max_batch: int = 8         # largest coalesced batch (lane bucket cap)
    min_pod_bucket: int = 16   # smallest pod bucket
    pod_bucket_growth: int = 4  # bucket ladder ratio
    max_gpu_milli: int = 1000  # sizes the shared wait histogram

    def __post_init__(self):
        if self.max_pods < 1 or self.max_batch < 1:
            raise ValueError("max_pods and max_batch must be >= 1")
        if self.min_pod_bucket < 1 or self.pod_bucket_growth < 2:
            raise ValueError("min_pod_bucket >= 1, pod_bucket_growth >= 2")

    def pod_buckets(self) -> Tuple[int, ...]:
        """The pod-bucket ladder: min_bucket * growth^i, clipped at
        max_pods (the top bucket is max_pods itself when the ladder does
        not land on it)."""
        out: List[int] = []
        b = self.min_pod_bucket
        while b < self.max_pods:
            out.append(b)
            b *= self.pod_bucket_growth
        out.append(self.max_pods)
        # dedupe while preserving order (max_pods may equal the last rung)
        return tuple(dict.fromkeys(out))

    def pod_bucket_for(self, n_pods: int) -> int:
        for b in self.pod_buckets():
            if n_pods <= b:
                return b
        raise ValueError(
            f"query with {n_pods} pods exceeds envelope max_pods "
            f"{self.max_pods}")

    def min_real_pods(self, bucket: int) -> int:
        """Smallest real pod count routed to ``bucket`` (the previous
        rung + 1; 1 for the smallest bucket). Sizes the bucket's fixed
        snapshot-table width: tables grow as real pods shrink, and
        routing guarantees no query below this count lands here."""
        buckets = self.pod_buckets()
        i = buckets.index(bucket)
        return 1 if i == 0 else buckets[i - 1] + 1

    def lane_buckets(self) -> Tuple[int, ...]:
        """Lane (batch) buckets: powers of two up to max_batch, plus
        max_batch itself."""
        out = []
        b = 1
        while b < self.max_batch:
            out.append(b)
            b *= 2
        out.append(self.max_batch)
        return tuple(dict.fromkeys(out))

    def lanes_for(self, n_queries: int) -> int:
        for b in self.lane_buckets():
            if n_queries <= b:
                return b
        raise ValueError(
            f"batch of {n_queries} queries exceeds envelope max_batch "
            f"{self.max_batch}; chunk it first")

    @property
    def wait_hist_size(self) -> int:
        """Shared wait-histogram width covering the declared gpu_milli
        range (the engine's own sizing rule, pinned so every bucket's
        states share one shape)."""
        return max(1001, self.max_gpu_milli + 2)

    def to_json(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_json(cls, doc: dict) -> "ShapeEnvelope":
        return cls(**doc)


# ---------------------------------------------------------- persistence


def _cluster_to_json(c: ClusterArrays) -> dict:
    """Cluster arrays as JSON-serializable lists (clusters are small —
    O(nodes) ints — so JSON keeps the artifact single-file-inspectable)."""
    return {
        "cpu_total": np.asarray(c.cpu_total).tolist(),
        "mem_total": np.asarray(c.mem_total).tolist(),
        "gpu_declared": np.asarray(c.gpu_declared).tolist(),
        "num_gpus": np.asarray(c.num_gpus).tolist(),
        "gpu_milli_total": np.asarray(c.gpu_milli_total).tolist(),
        "gpu_mem_total": np.asarray(c.gpu_mem_total).tolist(),
        "gpu_mask": np.asarray(c.gpu_mask).astype(int).tolist(),
        "node_mask": np.asarray(c.node_mask).astype(int).tolist(),
        "node_ids": list(c.node_ids),
        # a typed cluster's two fields (``gpu_model`` None: not typed)
        **({} if c.gpu_model is None else {
            "gpu_model": np.asarray(c.gpu_model).tolist(),
            "gpu_models": list(c.gpu_models)}),
    }


def _cluster_from_json(doc: dict) -> ClusterArrays:
    i32 = lambda k: np.asarray(doc[k], np.int32)  # noqa: E731
    return ClusterArrays(
        cpu_total=i32("cpu_total"), mem_total=i32("mem_total"),
        gpu_declared=i32("gpu_declared"), num_gpus=i32("num_gpus"),
        gpu_milli_total=i32("gpu_milli_total"),
        gpu_mem_total=i32("gpu_mem_total"),
        gpu_mask=np.asarray(doc["gpu_mask"], bool),
        node_mask=np.asarray(doc["node_mask"], bool),
        node_ids=tuple(doc["node_ids"]),
        gpu_model=i32("gpu_model") if "gpu_model" in doc else None,
        gpu_models=tuple(doc.get("gpu_models", ())),
    )


# ------------------------------------------------------------------ engine


class _Inflight(NamedTuple):
    """One dispatched-but-unharvested chunk of the double-buffered
    answer pipeline."""

    res: Any            # the executable's (async) SimResult
    idxs: List[int]     # answer slots, in lane order
    bucket: int
    lanes: int
    real: int
    chunk: int          # its index in the batch, as the spans carry it


class ServeEngine:
    """A pinned (champion, cluster, envelope) triple compiled for serving.

    One AOT ``Compiled`` executable per (lane_bucket, pod_bucket)
    combination, built on demand (or eagerly via ``warmup``) and cached
    for the engine's lifetime. The executable's signature is
    ``(pods[L,...], ktable[L,K], state0[L,...]) -> SimResult[L,...]``
    — the query deltas are arguments; the policy AND the pinned cluster
    tables are closure constants (device-resident, never re-uploaded) —
    so the warm path runs zero Python tracing and zero XLA compilation.

    With a ``mesh`` the lane axis is sharded over the mesh's pop axes
    (``parallel.mesh.make_sharded_serve_fn``): one executable per
    (global_lanes, pod_bucket) spans every device, where global lanes =
    per-device lane bucket x shard count; remainder lanes are
    ``pad_population`` duplicates accounted by ``occupancy_stats``.

    The hot path is built not to touch the host or the PCIe bus more
    than it must: snapshot trigger tables are cached on device keyed on
    a content hash of their bytes (``snapshot_cache_stats``), uploads
    are 16-bit packed under ``state_pack`` (``query_pack_plan``), the
    per-batch pods/state buffers are DONATED to the executable so steady
    state allocates nothing net per batch, and ``answer_batch`` double-
    buffers: chunk N+1's stacking + upload overlaps chunk N's execution,
    synchronizing one chunk behind dispatch like the segmented replay
    runner.

    A ``workload`` that carries a ``snapshot`` (``fks_tpu.data.snapshot``)
    makes every query a FORK from that moment of the cluster's run
    (``serve.batcher.QueryFork``, exact engine only), whatever its
    ``E0`` events hold (arrivals alone, or departures, refusals and
    waiting pods too): a query's run is ``base pods ++ query pods`` in
    which those events happen as logged; the pod axis of a bucket is
    ``fork.base + bucket`` (every pod with an attempt in the log, then
    the query's), its step budget counts from the fork
    (``SimConfig.max_steps`` stays absolute: ``E0 + max(64,
    max_steps_factor x bucket)``), and an answer lists the query's pods
    only, says which of them still wait at the end (``waiting``), whether
    the run ended with an empty heap inside its budget (``finished``) and
    reports whole-run counts (``events``, ``scheduled``, ``frag_events``:
    the prefix's included; ``start_event`` says where the champion took
    over). The snapshot is the engine's, not a query's: a query pod
    created before the time of the snapshot's last event is refused.

    GPU-type constraints are data too, and nothing else switches them on:
    on a ``workload`` that is ``typed`` (parsed with ``gpu_spec="honor"``:
    the cluster carries ``gpu_model``, the pods ``gpu_spec``) a query pod
    may carry ``gpu_spec``, the GPU model names it accepts joined by
    ``|`` (or a list of them), and is placed only on a node whose model
    is among them (``sim.engine.place_mask_of``); every query of such an
    engine is built with the leaf, zeros where a pod names nothing, so a
    bucket still has one program. An engine on any other workload builds
    the queries and compiles the programs it always did, and refuses a
    pod with a non-empty ``gpu_spec`` by name (``ValueError``).

    ``engine`` picks the simulation module ("exact" serves reference
    semantics and is the parity default; "flat" trades the documented
    retry-rule divergence for throughput). ``prefilter_k=None`` engages
    the auto-enable heuristic (``sim.engine.resolve_auto_prefilter``; the
    VM engine takes the shape rule, ``sim.engine.shape_prefilter_k``).
    """

    #: how this engine binds the champion: "aot" bakes the policy into
    #: the executable as a closure constant (a new champion = a rebuild);
    #: "vm" (serve.vm_engine.VMServeEngine) passes it as a device-resident
    #: argument (a new champion = a table upload)
    engine_kind = "aot"

    def __init__(self, champion: ChampionSpec, workload: Workload, *,
                 envelope: Optional[ShapeEnvelope] = None,
                 engine: str = "exact",
                 prefilter_k: Optional[int] = None,
                 state_pack: bool = False,
                 max_steps_factor: int = 8,
                 mesh=None,
                 snapshot_cache_max_bytes: int = 0,
                 recorder=None, profiler=None):
        if engine == "fused":
            raise ValueError(
                "the fused kernel evaluates parametric populations only; "
                "serve champions on 'exact' (parity default) or 'flat'")
        self.champion = champion
        # the nodes' GPU models stay with the cluster only where the pods
        # name theirs: the leaf is what makes a query typed
        # (``build_query_workload``)
        self.cluster = workload.cluster if workload.typed else \
            dataclasses.replace(workload.cluster, gpu_model=None,
                                gpu_models=())
        self.base_pods = pods_to_dicts(workload.pods,
                                       gpu_models=self.cluster.gpu_models)
        self.envelope = envelope or ShapeEnvelope()
        #: the moment of a run every query forks from, or None
        self.fork: Optional[QueryFork] = None
        if workload.snapshot is not None:
            if engine != "exact":
                raise ValueError(
                    "snapshot: serving forks on the exact engine "
                    "(ServeEngine / VMServeEngine with engine='exact'); "
                    "candidates are evaluated from a snapshot on "
                    "engine='flat' (CodeEvaluator)")
            # as given, with the pods' tie order: what ``save`` keeps
            self._snapshot = (workload.snapshot, np.asarray(
                workload.pods.tie_rank)[np.asarray(workload.pods.pod_mask)])
            with obs.span("serve/fork_state",
                          start_event=workload.snapshot.e0) as sp:
                self.fork = fork = QueryFork(workload)
                sp.set(events=fork.e0, residents=fork.residents,
                       departed=fork.prefix.departed,
                       refused=fork.prefix.refused, waiting=fork.waiting,
                       nodes_loaded=fork.nodes_loaded,
                       # the base's own: residents' DELETEs, queued retries
                       heap_size=len(fork.prefix.heap),
                       bytes=fork.lane_bytes)
                if self.typed:
                    sp.set(typed_residents=self.fork.typed_residents,
                           node_models=len(self.cluster.gpu_models))
        self.engine_name = engine
        self.state_pack = bool(state_pack)
        self.max_steps_factor = int(max_steps_factor)
        self.recorder = recorder if recorder is not None else obs.get_recorder()
        # device-time attribution (fks_tpu.obs.profiler): with an enabled
        # StageProfiler every bucket compile, warmup sweep, and steady
        # batch is a fenced device_profile stage; the default NULL
        # profiler adds no fences and no conditionals to the serve path
        self.profiler = (profiler if profiler is not None
                         else obs.NULL_PROFILER)
        self._mod = get_engine(engine)
        self._compiled: Dict[Tuple[int, int], Any] = {}
        # id(executable) -> vm.trace_counts() over its trace: how the
        # op-slot loop's row write, fetch and turns were lowered in it
        self._vm_writes: Dict[int, Tuple[int, ...]] = {}
        self.cold_compiles = 0
        # mesh-wide serving: lane axis sharded over the pop axes
        self.mesh = mesh
        self._shards = num_shards(mesh) if mesh is not None else 1
        self._sharding = serve_sharding(mesh) if mesh is not None else None
        # device-resident snapshot tables: content-hash -> (buffer, bytes)
        self._ktable_cache: "OrderedDict[Tuple, Tuple[Any, int]]" = \
            OrderedDict()
        self._ktable_cache_cap = 32
        # byte ceiling on the resident tables (0 = count-capped only):
        # the LRU evicts until BOTH the entry cap and the byte cap hold,
        # so a configured HBM budget is a hard bound, not a suggestion
        self._ktable_cache_max_bytes = int(snapshot_cache_max_bytes)
        self._ktable_cache_bytes = 0
        self.snapshot_cache_hits = 0
        self.snapshot_cache_misses = 0
        # H2D accounting (bytes actually shipped per answered query)
        self.h2d_bytes_total = 0
        self.h2d_queries = 0
        # host-wall split of the last answer_batch call, summed over its
        # chunks from the stamps of the chunk spans (``_reset_batch_log``
        # says what each key covers)
        self.last_batch_timing: Dict[str, float] = {
            "pack_h2d_s": 0.0, "dispatch_s": 0.0}
        # the last answer_batch call's chunk spans and, per chunk index,
        # the answer slots it carried: the per-request waterfall's source
        # (fks_tpu.serve.service._trace_batch)
        self.last_batch_spans: List[obs.SpanRecord] = []
        self.last_batch_chunks: List[List[int]] = []
        # the most recent harvested chunk's [lanes] score array, kept so
        # that last_lanes_per_device can read its placement when asked
        self._last_scores = None

        n, g = self.cluster.n_padded, self.cluster.g_padded
        self.param_policy, self.params, self.policy_tier = \
            self._resolve_policy(champion.code, n, g)
        self.prefilter_k = self._resolve_prefilter(prefilter_k, n, g)

    def _resolve_prefilter(self, override: Optional[int], n: int,
                           g: int) -> int:
        """``SimConfig.node_prefilter_k`` for every bucket: the timing
        probe's answer (``resolve_auto_prefilter``) unless overridden.
        A baked-in champion may be a handful of fused ops or a long
        program, so its cost is measured; retiring the probe is ROADMAP
        D4's."""
        return resolve_auto_prefilter(
            self.param_policy, self.params, n, g,
            override=override, recorder=self.recorder,
            work_hint=self._static_work_hint(self.champion.code, g))

    @staticmethod
    def _resolve_policy(code: str, n: int, g: int):
        """Champion source -> (param_policy, params, tier). VM lowering
        first (register program as the param pytree — the population
        tier's representation); candidates outside the VM vocabulary fall
        back to direct transpile + jit closure. TranspileError (invalid
        source) propagates: a broken champion is a caller error."""
        from fks_tpu.funsearch import transpiler, vm

        try:
            prog = vm.compile_policy(code, n, g)
            return vm.score, prog, "vm"
        except vm.VMUnsupported:
            policy = transpiler.transpile(code)
            return (lambda _p, pod, nodes: policy(pod, nodes)), None, "jit"

    @staticmethod
    def _static_work_hint(code: str, g: int) -> Optional[int]:
        """Static per-node work bound from the pre-flight cost model, fed
        to the prefilter auto-heuristic so trivially cheap champions skip
        the runtime probe entirely. None (no hint) when the analyzer
        cannot price the source — the heuristic then probes as before."""
        from fks_tpu import analysis

        rep = analysis.preflight_check(code)
        if rep.ok and rep.cost is not None:
            return rep.cost.work(g)
        return None

    # ----- bucket plumbing

    def bucket_config(self, pod_bucket: int) -> SimConfig:
        """The bucket's SimConfig — SHARED by the batched path and the
        unbatched exact reference (``reference_answer``), so bucket
        padding is part of the serving semantics, not a parity leak."""
        return SimConfig(
            max_steps=self.start_event
            + max(64, self.max_steps_factor * pod_bucket),
            wait_hist_size=self.envelope.wait_hist_size,
            node_prefilter_k=self.prefilter_k,
            state_pack=self.state_pack,
        )

    @property
    def start_event(self) -> int:
        """Where the champion takes over: 0, or the fork's ``E0``."""
        return 0 if self.fork is None else self.fork.e0

    @property
    def base_pods_on_axis(self) -> int:
        """Rows of a bucket's pod axis that lie before a query's: 0, or
        the fork's base (every pod with an attempt in the snapshot)."""
        return 0 if self.fork is None else self.fork.base

    @property
    def typed(self) -> bool:
        """Do this engine's queries carry ``gpu_spec`` (was its workload
        ``typed``)?"""
        return self.cluster.gpu_model is not None

    def _klen(self, pod_bucket: int) -> int:
        """Fixed snapshot-table width for the bucket, sized at the
        SMALLEST real pod count routing can send here (tables grow as
        real pods shrink; see ``ShapeEnvelope.min_real_pods``)."""
        cfg = self.bucket_config(pod_bucket)
        return max_snapshot_count(
            cfg.max_steps,
            self.base_pods_on_axis
            + self.envelope.min_real_pods(pod_bucket),
            cfg.snapshot_interval)

    def _pack_plan(self, pod_bucket: int) -> dict:
        """The bucket's static upload-packing plan (empty unless
        ``state_pack``) — shared by compile, example and dispatch so the
        packed avals can never diverge from the executable's."""
        return query_pack_plan(self.bucket_config(pod_bucket),
                               self.base_pods_on_axis + pod_bucket,
                               self.envelope.max_gpu_milli)

    def _make_serve_fn(self, pod_bucket: int):
        """The jittable batched pipeline for one pod bucket: vmapped
        self-masking step driven by the shared ``run_batched_lanes``
        scaffold, finalized per lane. The champion policy AND the pinned
        cluster tables are closure constants (device-resident — a batch
        never re-uploads them); pods/ktable/state are traced ARGUMENTS,
        widened on device from the packed wire format."""
        cfg = self.bucket_config(pod_bucket)
        max_steps = cfg.max_steps
        mod, pp, params = self._mod, self.param_policy, self.params
        plan = self._pack_plan(pod_bucket)
        cluster = dataclasses.replace(self.cluster, node_ids=())

        def step_one(p, k, s):
            w = Workload(cluster=cluster, pods=p, faults=None)
            return mod.build_step(
                w, lambda pod, nodes: pp(params, pod, nodes),
                cfg, k, max_steps)(s)

        vstep = jax.vmap(step_one, in_axes=(0, 0, 0))
        vfin = jax.vmap(
            lambda p, s: mod.finalize(
                Workload(cluster=cluster, pods=p, faults=None), cfg, s),
            in_axes=(0, 0))

        def serve_fn(pods, kt, state0):
            pods, kt = unpack_query_tables(pods, kt, plan)
            final = run_batched_lanes(lambda s: vstep(pods, kt, s), state0,
                                      max_steps, active_fn=mod.lane_active)
            return self._result(vfin(pods, final), final)

        return serve_fn

    def _result(self, res, final):
        """What a bucket's executable returns: the lanes' ``SimResult``
        and, from a fork, their pods' waiting flags at the end beside it
        (a cut run leaves pods in the waiting set; ``SimResult`` does not
        say which)."""
        return res if self.fork is None else (res, final.waiting)

    @staticmethod
    def _pad_kt(kt: np.ndarray, lanes: int) -> np.ndarray:
        """Replicate the last query's snapshot table into pad lanes (the
        ``pad_population`` rule, host-side so the table can be hashed and
        uploaded as one contiguous buffer)."""
        q = kt.shape[0]
        if q < lanes:
            kt = np.concatenate([kt, np.repeat(kt[-1:], lanes - q, axis=0)])
        return kt

    def _example_batch(self, lanes: int, pod_bucket: int):
        """A minimal valid batch at the bucket's exact avals (and, on a
        mesh, exact shardings), for ``lower()``: the smallest query
        routing can send here, replicated across lanes by the same
        pack/pad path real batches use."""
        t0 = 0 if self.fork is None else self.fork.not_before or 0
        pods = [{"cpu_milli": 1, "memory_mib": 1, "creation_time": t0 + t,
                 "duration_time": 10}
                for t in range(self.envelope.min_real_pods(pod_bucket))]
        cfg = self.bucket_config(pod_bucket)
        pq, kt, s0 = stack_query_tables(self._mod, self.cluster, [pods],
                                        pod_bucket, cfg,
                                        self._klen(pod_bucket), self.fork)
        pq, kt = pack_query_tables(pq, kt, self._pack_plan(pod_bucket))
        (pq, s0), _ = pad_population((pq, s0), lanes)
        example = (pq, jnp.asarray(self._pad_kt(kt, lanes)), s0)
        if self._sharding is not None:
            example = jax.device_put(example, self._sharding)
        return example

    def compiled_for(self, lanes: int, pod_bucket: int):
        """The (lanes, pod_bucket) AOT executable, compiling on first use
        (``lanes`` is the GLOBAL lane count — per-device bucket x shard
        count on a mesh). ``jax.jit(...).lower(...).compile()`` returns a
        ``Compiled`` object whose __call__ never compiles — argument
        avals either match or raise. pods (arg 0) and state0 (arg 2) are
        donated: each batch's upload buffers are released to XLA, so
        steady-state serving recycles instead of growing the arena; the
        content-hash-cached ktable (arg 1) is NOT donated — its device
        buffer must survive across batches."""
        key = (lanes, pod_bucket)
        hit = self._compiled.get(key)
        if hit is not None:
            return hit
        with self.profiler.stage("compile", lanes=lanes, pods=pod_bucket):
            with obs.span("serve_compile", lanes=lanes, pods=pod_bucket,
                          engine=self.engine_name):
                fn = self._make_serve_fn(pod_bucket)
                if self.mesh is not None:
                    fn = make_sharded_serve_fn(fn, self.mesh)
                from fks_tpu.funsearch import vm
                example = self._example_batch(lanes, pod_bucket)
                writes0 = vm.trace_counts()
                with warnings.catch_warnings():
                    # buckets whose SimResult cannot alias a donated
                    # input warn once per compile; donation still lets
                    # XLA recycle the buffers as scratch
                    warnings.filterwarnings("ignore",
                                            message="Some donated")
                    compiled = jax.jit(fn, donate_argnums=(0, 2)) \
                        .lower(*example).compile()
                self._keep_writes(compiled, writes0)
        self._compiled[key] = compiled
        self.cold_compiles += 1
        return compiled

    def warmup(self, lane_buckets: Optional[Sequence[int]] = None,
               pod_buckets: Optional[Sequence[int]] = None) -> int:
        """Eagerly compile every (lane, pod) bucket combination (or the
        given subsets; lane buckets are PER-DEVICE and scale by the mesh
        shard count). Returns the number of executables now resident."""
        with self.profiler.stage("warmup"):
            for lb in lane_buckets or self.envelope.lane_buckets():
                for pb in pod_buckets or self.envelope.pod_buckets():
                    self.compiled_for(serve_lane_count(lb, self.mesh), pb)
        return len(self._compiled)

    # ----- answering

    def _global_lanes(self, n_queries: int) -> int:
        """Global lane count for an n-query chunk: the smallest envelope
        lane bucket covering the PER-DEVICE share, scaled by the mesh."""
        per_dev = -(-int(n_queries) // self._shards)
        return serve_lane_count(self.envelope.lanes_for(max(1, per_dev)),
                                self.mesh)

    def snapshot_cache_stats(self) -> dict:
        """Device-resident snapshot-table cache counters plus the H2D
        accounting — the ``fks_serve_snapshot_cache_*`` gauge source."""
        total = self.snapshot_cache_hits + self.snapshot_cache_misses
        return {
            "hits": self.snapshot_cache_hits,
            "misses": self.snapshot_cache_misses,
            "entries": len(self._ktable_cache),
            "hit_rate": self.snapshot_cache_hits / total if total else 0.0,
            "h2d_bytes_total": int(self.h2d_bytes_total),
            "h2d_bytes_per_query": (self.h2d_bytes_total / self.h2d_queries
                                    if self.h2d_queries else 0.0),
            "bytes": int(self._ktable_cache_bytes),
            "max_bytes": int(self._ktable_cache_max_bytes),
        }

    @property
    def snapshot_cache_bytes(self) -> int:
        """Bytes of snapshot tables currently resident in the cache."""
        return int(self._ktable_cache_bytes)

    def _ktable_for(self, lanes: int, bucket: int, kt: np.ndarray):
        """The device-resident snapshot-table buffer for this batch:
        content-hash cache keyed on the (packed) table bytes at the
        dispatch shape. Consecutive batches whose queries share pod
        counts — the steady-serving common case — hash identically and
        re-use the resident buffer, shipping zero snapshot bytes."""
        digest = hashlib.blake2b(kt.tobytes(), digest_size=16).digest()
        key = (lanes, bucket, kt.dtype.str, digest)
        hit = self._ktable_cache.get(key)
        if hit is not None:
            self._ktable_cache.move_to_end(key)
            self.snapshot_cache_hits += 1
            return hit[0]
        self.snapshot_cache_misses += 1
        padded = self._pad_kt(kt, lanes)
        dev = (jax.device_put(padded, self._sharding)
               if self._sharding is not None else jnp.asarray(padded))
        nbytes = int(padded.nbytes)
        self.h2d_bytes_total += nbytes
        self._ktable_cache[key] = (dev, nbytes)
        self._ktable_cache_bytes += nbytes
        while self._ktable_cache and (
                len(self._ktable_cache) > self._ktable_cache_cap
                or (self._ktable_cache_max_bytes
                    and self._ktable_cache_bytes
                    > self._ktable_cache_max_bytes)):
            _, (_, freed) = self._ktable_cache.popitem(last=False)
            self._ktable_cache_bytes -= freed
        return dev

    def _reset_batch_log(self) -> None:
        """Start the per-batch views of the chunk spans.
        ``last_batch_timing`` keeps its two keys (and their names) for
        the readers that have them; what they cover, per chunk:
        ``pack_h2d_s`` is the start of ``serve/chunk/stack`` to the end of
        ``serve/chunk/enqueue`` (stacking, packing, lane padding, the
        upload, the executable lookup AND the enqueue: all host staging,
        not the upload alone); ``dispatch_s`` is the start of
        ``serve/chunk/wait_device`` to the end of ``serve/chunk/d2h``
        (the wait for the device plus the device-to-host copy: no
        dispatch is in it)."""
        self.last_batch_timing = {"pack_h2d_s": 0.0, "dispatch_s": 0.0}
        self.last_batch_spans = []
        self.last_batch_chunks = []

    def _batch_guard(self):
        """What a whole batch is answered under (the VM engine: its swap
        lock)."""
        return contextlib.nullcontext()

    def answer_batch(self, pod_lists: Sequence[Sequence[dict]]) -> List[dict]:
        """Answer N "place this pod list" queries. Queries are grouped by
        pod bucket, chunked at the mesh-wide max batch, lane-padded to
        the compiled lane bucket (``pad_population`` — the request
        batcher), run through the warm executable, and scattered back in
        input order. Chunks are DOUBLE-BUFFERED: chunk i+1 is stacked,
        uploaded and dispatched before chunk i's results are pulled, so
        host staging and H2D overlap device compute (the segmented
        replay runner's one-behind handoff, at the batch level).

        One ``serve/batch`` span is the root of the call; when the
        batcher's flush context is active it joins that flush's trace
        and lists the request traces it carries. From a fork it counts
        the lanes that ended with an empty heap inside their budget
        (``finished_lanes``)."""
        ctx = trace_ctx.current()
        with obs.span("serve/batch", queries=len(pod_lists)) as root:
            if ctx is not None and ctx.carries:
                root.set(requests=list(ctx.carries))
            with self._batch_guard():
                answers = self._answer_chunks(pod_lists)
            if self.fork is not None:
                root.set(finished_lanes=sum(a["finished"] for a in answers))
            return answers

    def validate_query(self, pods: Sequence[dict]) -> None:
        """``validate_query_pods`` under this engine's envelope, fork and
        cluster (``ValueError``: the service's 4xx)."""
        validate_query_pods(
            pods, max_pods=self.envelope.max_pods,
            max_gpu_milli=self.envelope.max_gpu_milli,
            not_before=None if self.fork is None
            else self.fork.not_before, typed=self.typed)

    def _answer_chunks(self, pod_lists) -> List[dict]:
        for pods in pod_lists:
            self.validate_query(pods)
        self._reset_batch_log()
        answers: List[Optional[dict]] = [None] * len(pod_lists)
        groups: Dict[int, List[int]] = {}
        for i, pods in enumerate(pod_lists):
            groups.setdefault(
                self.envelope.pod_bucket_for(len(pods)), []).append(i)
        mb = self.envelope.max_batch * self._shards
        inflight: Optional[_Inflight] = None
        for bucket, idxs in groups.items():
            for c0 in range(0, len(idxs), mb):
                nxt = self._dispatch_chunk(bucket, idxs[c0:c0 + mb],
                                           pod_lists)
                if inflight is not None:
                    self._harvest(inflight, pod_lists, answers)
                inflight = nxt
        if inflight is not None:
            self._harvest(inflight, pod_lists, answers)
        return answers  # type: ignore[return-value]

    def _dispatch_chunk(self, bucket: int, idxs: List[int],
                        pod_lists) -> "_Inflight":
        """Stack, pack, upload and enqueue one chunk (async), one span
        each. The ``h2d`` span reads what the upload costs the HOST (the
        enqueue of the copy; an enabled profiler fences it); the
        execution lands in ``_harvest``'s ``wait_device``."""
        chunk = len(self.last_batch_chunks)
        self.last_batch_chunks.append(list(idxs))
        lanes = self._global_lanes(len(idxs))
        # of a forked chunk's upload, what is the base's and not the
        # queries' (the base is shipped with every batch, lane for lane)
        forked = {} if self.fork is None else {
            "start_event": self.fork.e0,
            "resident_bytes": lanes * self.fork.lane_bytes}
        with obs.span("serve/chunk/stack", chunk=chunk, bucket=bucket,
                      lanes=lanes, real=len(idxs), **forked) as t_stack:
            pods, kt, s0 = stack_query_tables(
                self._mod, self.cluster, [pod_lists[i] for i in idxs],
                bucket, self.bucket_config(bucket), self._klen(bucket),
                self.fork)
            if self.typed:
                # read off the table that ships, not off the request: a
                # word lost on the way in reads 0 here
                t_stack.set(
                    pods=sum(len(pod_lists[i]) for i in idxs),
                    typed_pods=int(np.count_nonzero(np.asarray(
                        pods.gpu_spec)[:, self.base_pods_on_axis:])))
        with obs.span("serve/chunk/pack", chunk=chunk) as t_pack:
            pods, kt = pack_query_tables(pods, kt, self._pack_plan(bucket))
        with self.profiler.stage("h2d", span="serve/chunk/h2d", chunk=chunk,
                                 lanes=lanes, pods=bucket) as hh:
            sent0 = self.h2d_bytes_total
            (pods, s0), real = pad_population((pods, s0), lanes)
            kt_dev = self._ktable_for(lanes, bucket, kt)
            if self._sharding is not None:
                pods, s0 = jax.device_put((pods, s0), self._sharding)
            else:
                pods, s0 = jax.device_put((pods, s0))
            self.h2d_bytes_total += tree_h2d_bytes(pods, s0)
            hh.span.set(bytes=self.h2d_bytes_total - sent0, **forked)
            hh.sync(jax.tree_util.tree_leaves(s0)[0])
        self.h2d_queries += len(idxs)
        # async dispatch; per-batch buffers donated. _invoke is the
        # engine-kind seam: the AOT engine calls the executable directly,
        # the VM engine prepends its device-resident champion tables.
        with obs.span("serve/chunk/enqueue", chunk=chunk,
                      **self._loop_fields()) as t_enq:
            compiled = self.compiled_for(lanes, bucket)
            t_enq.set(**self._write_fields(compiled))
            res = self._invoke(compiled, pods, kt_dev, s0)
        self.last_batch_timing["pack_h2d_s"] += t_enq.t1 - t_stack.t0
        self.last_batch_spans += [t_stack.record, t_pack.record,
                                  hh.span.record, t_enq.record]
        return _Inflight(res, list(idxs), bucket, lanes, real, chunk)

    def _keep_writes(self, compiled, before: Tuple[int, ...]) -> None:
        """Keep with a new executable how its trace lowered the VM's
        op-slot loop: ``vm.trace_counts`` since ``before`` (read just
        before the ``lower()`` that traced it, on this thread; the
        executable is never traced again)."""
        from fks_tpu.funsearch import vm
        self._vm_writes[id(compiled)] = tuple(
            x - y for x, y in zip(vm.trace_counts(), before))

    def _write_fields(self, compiled) -> Dict[str, int]:
        """``vm.TRACE_FIELDS`` of the executable being enqueued
        (`_keep_writes`): ``slice_writes`` / ``scatter_writes``, and how
        its fetch and its loop's turns went (all 0 where the program is
        never per lane). Empty where its trace held no batched VM write (a
        champion on the jit tier)."""
        from fks_tpu.funsearch import vm
        traced = self._vm_writes.get(id(compiled), ())
        if not sum(traced[:2]):
            return {}
        return dict(zip(vm.TRACE_FIELDS, traced))

    def _invoke(self, compiled, pods, kt_dev, s0):
        return compiled(pods, kt_dev, s0)

    def _loop_fields(self) -> Dict[str, int]:
        """``slots`` / ``capacity`` of the chunk being enqueued: how far
        the VM's op-slot loop runs (``vm._loop_bound``) and the padded
        bucket it runs in. Empty for a champion on the jit tier."""
        if self.policy_tier != "vm":
            return {}
        return {"slots": int(self.params.n_ops),
                "capacity": int(self.params.capacity)}

    @property
    def last_lanes_per_device(self) -> Dict[int, int]:
        """device id -> lanes it held in the most recent harvested chunk.
        Worked out when asked (chip_smoke.py does), never per batch."""
        if self._last_scores is None:
            return {}
        return lanes_per_device(self._last_scores)

    def _harvest(self, inflight: "_Inflight", pod_lists, answers) -> None:
        """Block on a dispatched chunk and scatter its answers back. The
        ``wait_device`` span holds the blocking call and nothing else."""
        res, idxs, bucket, lanes, real, chunk = inflight
        with self.profiler.stage("steady", span="serve/chunk/wait_device",
                                 chunk=chunk, lanes=lanes, real=real) as hs:
            score = (res if self.fork is None else res[0]).policy_score
            jax.block_until_ready(score)
            if self.profiler.enabled:
                hs.annotate(**occupancy_stats(real, lanes))
        with obs.span("serve/chunk/d2h", chunk=chunk) as t_d2h:
            self._last_scores = score
            res = jax.device_get(res)
            t_d2h.set(bytes=tree_h2d_bytes(res))
            res, waiting = res if self.fork is not None else (res, None)
        self.last_batch_timing["dispatch_s"] += t_d2h.t1 - hs.span.t0
        with obs.span("serve/chunk/extract", chunk=chunk,
                      real=len(idxs)) as t_ext:
            for lane, i in enumerate(idxs):
                answers[i] = self._extract(res, lane, len(pod_lists[i]),
                                           bucket, lanes, waiting)
            if self.fork is not None:
                # the regime a forked call ran in: failed placements of
                # its real lanes FROM THE FORK (an answer's count is the
                # whole run's, the prefix's refusals included) over their
                # events after the fork
                real_ans = [answers[i] for i in idxs]
                before = self.fork.prefix.refused
                t_ext.set(frag_events=sum(a["frag_events"] - before
                                          for a in real_ans),
                          lane_events=sum(a["events"] - self.fork.e0
                                          for a in real_ans))
        self.last_batch_spans += [hs.span.record, t_d2h.record,
                                  t_ext.record]

    def _extract(self, res, lane: Optional[int], p_real: int,
                 bucket: int, lanes: int, waiting=None) -> dict:
        """One lane's SimResult slice -> an answer dict (``lane=None``
        reads an unbatched scalar result). Placements cover REAL pods
        only; node -1 means unplaced; GPU bitmask unpacked to indices.
        From a fork the query's pods follow the base's on the pod
        axis and are the only ones listed; the counts stay the whole
        run's, ``finished`` says that the run ended with an empty heap
        inside its budget, and ``waiting`` (the lanes' flags, where the
        executable gave them) names the query's pods that a placement
        failed for and that hold no node at the end."""
        pick = (lambda x: np.asarray(x)) if lane is None else \
            (lambda x: np.asarray(x)[lane])
        mine = slice(self.base_pods_on_axis,
                     self.base_pods_on_axis + p_real)
        assigned = pick(res.assigned_node)[mine]
        gpus = pick(res.assigned_gpus)[mine].astype(np.int64)
        node_ids = self.cluster.node_ids
        placements = []
        for i, (nd, gm) in enumerate(zip(assigned, gpus)):
            row = {"pod": i, "node": int(nd),
                   "gpus": [b for b in range(int(gm).bit_length())
                            if int(gm) >> b & 1]}
            if 0 <= int(nd) < len(node_ids):
                row["node_id"] = node_ids[int(nd)]
            placements.append(row)
        out = {
            "score": float(pick(res.policy_score)),
            "scheduled": int(pick(res.scheduled_pods)),
            "failed": bool(pick(res.failed)),
            "truncated": bool(pick(res.truncated)),
            "events": int(pick(res.events_processed)),
            "placements": placements,
            "bucket_pods": bucket,
            "bucket_lanes": lanes,
        }
        if self.fork is not None:
            # the evaluator of the whole run, as the run ended or was
            # cut: what the cluster's next events look like with the
            # queue in it
            out.update(
                start_event=self.fork.e0,
                finished=not (out["truncated"] or out["failed"]),
                frag_events=int(pick(res.num_fragmentation_events)),
                snapshots=int(pick(res.num_snapshots)),
                max_nodes=int(pick(res.max_nodes)),
                utilization=[float(pick(x)) for x in (
                    res.avg_cpu_utilization, res.avg_memory_utilization,
                    res.avg_gpu_count_utilization,
                    res.avg_gpu_memory_utilization)],
                fragmentation=float(pick(res.gpu_fragmentation_score)))
            if waiting is not None:
                out["waiting"] = np.flatnonzero(
                    pick(waiting)[mine]).tolist()
        return out

    def reference_answer(self, pods: Sequence[dict]) -> dict:
        """The UNBATCHED exact-engine answer for one query, at the same
        bucket semantics (same padded workload, same SimConfig) — what
        the ParitySentinel audits served answers against. Independent
        code path on purpose: single-lane ``make_param_run_fn`` with its
        own ``loop_tables`` sizing, no vmap, no lane padding."""
        from fks_tpu.sim import engine as exact

        self.validate_query(pods)
        bucket = self.envelope.pod_bucket_for(len(pods))
        cfg = self.bucket_config(bucket)
        wl = build_query_workload(self.cluster, pods, bucket, self.fork)
        run = jax.jit(exact.make_param_run_fn(wl, self.param_policy, cfg))
        res = jax.device_get(run(self.params, exact.initial_state(wl, cfg)))
        return self._extract(res, None, len(pods), bucket, 1)

    # ----- persistence

    def save(self, directory: str) -> str:
        """Persist the engine spec (champion + cluster + envelope + knobs)
        as ``artifact.json``. Compiled programs are not part of the
        artifact: they live in the process's persistent compilation
        cache, wherever the entry point placed it."""
        os.makedirs(directory, exist_ok=True)
        doc = {
            "version": ARTIFACT_VERSION,
            "champion": self.champion.to_json(),
            "envelope": self.envelope.to_json(),
            "engine": self.engine_name,
            "engine_kind": self.engine_kind,
            "prefilter_k": self.prefilter_k,
            "state_pack": self.state_pack,
            "max_steps_factor": self.max_steps_factor,
            "policy_tier": self.policy_tier,
            "cluster": _cluster_to_json(self.cluster),
            "base_pods": self.base_pods,   # with gpu_spec, where typed
        }
        if self.fork is not None:
            # base_pods are in input order; the rows name them by index
            snap, ranks = self._snapshot
            doc["snapshot"] = {
                "pod": np.asarray(snap.pod).tolist(),
                "node": np.asarray(snap.node).tolist(),
                "gpus": np.asarray(snap.gpus).tolist(),
                "event": np.asarray(snap.event).tolist(),
                "e0": int(snap.e0), "rule": snap.rule,
                "tie_rank": ranks.tolist()}
        cap = getattr(self, "program_capacity", None)
        if cap is not None:
            doc["program_capacity"] = int(cap)
        path = os.path.join(directory, "artifact.json")
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(doc, f)
        os.replace(tmp, path)  # atomic: a loader never sees a half-write
        return path

    @classmethod
    def load(cls, directory: str, recorder=None, mesh=None) -> "ServeEngine":
        """Rebuild a saved engine. Self-contained: the artifact pins the
        cluster arrays and the resolved prefilter-k (no re-probe).
        ``compiled_for`` re-lowers each bucket and hits the process's
        persistent compilation cache where an earlier run compiled it.
        ``mesh`` is a RUNTIME property (device topology differs per
        process), so it is passed here, never persisted."""
        with open(os.path.join(directory, "artifact.json")) as f:
            doc = json.load(f)
        if doc.get("version") != ARTIFACT_VERSION:
            raise ValueError(
                f"artifact version {doc.get('version')} != "
                f"{ARTIFACT_VERSION}")
        cluster = _cluster_from_json(doc["cluster"])
        wl = Workload(cluster=cluster,
                      pods=_pods_from_dicts(doc.get("base_pods", []),
                                            cluster))
        if doc.get("snapshot"):
            from fks_tpu.data.snapshot import Snapshot, placed_creates
            rows = doc["snapshot"]
            snap = placed_creates(rows["pod"], rows["node"], rows["gpus"])
            if "event" in rows:     # a moment of a run, not arrivals alone
                snap = Snapshot(snap.pod, snap.node, snap.gpus,
                                np.asarray(rows["event"], np.int32),
                                e0=int(rows["e0"]), rule=rows["rule"])
            wl = dataclasses.replace(
                wl, pods=dataclasses.replace(wl.pods, tie_rank=np.asarray(
                    rows["tie_rank"], np.int32)),
                snapshot=snap)
        extra = {}
        portfolio = doc.get("portfolio")
        if doc.get("engine_kind", "aot") == "vm" and cls.engine_kind != "vm":
            # artifact saved by a VMServeEngine: reload it as one (the
            # champion-as-data executable set, not the AOT ladder) — or,
            # when the doc carries a portfolio manifest, as the whole
            # slot table
            if portfolio:
                from fks_tpu.portfolio.engine import PortfolioEngine
                cls = PortfolioEngine
            else:
                from fks_tpu.serve.vm_engine import VMServeEngine
                cls = VMServeEngine
        if cls.engine_kind == "vm" and doc.get("program_capacity"):
            extra["program_capacity"] = int(doc["program_capacity"])
        champ_arg: Any = ChampionSpec.from_json(doc["champion"])
        if portfolio and getattr(cls, "is_portfolio", False):
            champ_arg = [ChampionSpec.from_json(c,
                                                source=c.get("source", ""))
                         for c in portfolio["slots"]]
            extra["n_slots"] = int(portfolio["n_slots"])
        eng = cls(champ_arg, wl,
                  envelope=ShapeEnvelope.from_json(doc["envelope"]),
                  engine=doc["engine"],
                  prefilter_k=int(doc["prefilter_k"]),
                  state_pack=bool(doc["state_pack"]),
                  max_steps_factor=int(doc["max_steps_factor"]),
                  mesh=mesh, recorder=recorder, **extra)
        return eng


def _pods_from_dicts(pods: List[dict],
                     cluster: Optional[ClusterArrays] = None):
    """Query-schema dicts -> a real-sized PodArrays (artifact base trace).
    With a typed ``cluster`` the pods get their ``gpu_spec`` words back
    (``base_pods`` carries the strings), so the workload is typed again."""
    from fks_tpu.data.entities import PodArrays
    from fks_tpu.serve.batcher import DEFAULT_DURATION, gpu_spec_words

    p = max(1, len(pods))
    col = lambda f, d=0: np.asarray(  # noqa: E731
        [int(x.get(f, d)) for x in pods] + [0] * (p - len(pods)), np.int32)
    spec = None
    if cluster is not None and cluster.gpu_model is not None:
        spec = gpu_spec_words(pods, cluster.gpu_models, p)
    return PodArrays(
        cpu=col("cpu_milli"), mem=col("memory_mib"),
        num_gpu=col("num_gpu"), gpu_milli=col("gpu_milli"),
        creation_time=col("creation_time"),
        duration=col("duration_time", DEFAULT_DURATION),
        tie_rank=np.arange(p, dtype=np.int32),
        pod_mask=np.arange(p) < len(pods),
        pod_ids=tuple(f"q-{i:05d}" for i in range(len(pods))),
        gpu_spec=spec,
    )
