"""VM-native serving: the champion is an ARGUMENT, not a closure constant.

``ServeEngine`` (serve.artifact) bakes the champion's policy into every
AOT executable as closure constants, so a promotion rebuilds the whole
bucket ladder — seconds of XLA compile for a swap that itself is one
attribute flip. ``VMServeEngine`` inverts that binding the same way the
evolve tier does (fks_tpu.funsearch.vm runs a heterogeneous population
through ONE compiled engine): the champion is lowered to a ``VMProgram``
register program, NOP-padded to a capacity bucket, and passed to the
executable as a device-resident pytree input alongside the batched
queries. One executable per (lane_bucket, pod_bucket, program_capacity)
then serves EVERY champion of that capacity bucket, and a hot-swap
degenerates to ``swap_program``: transpile + lower + pack + H2D upload
of the new opcode/constant tables — zero XLA compiles, microseconds of
device traffic (the evosax / population-based-RL move: replace
per-member compilation with parameter upload).

The program tables are deliberately NOT donated to the executable: they
are the resident champion, reused by every batch until the next swap
(the snapshot-ktable precedent — donation would invalidate the buffer
after one call). The per-batch pods/state buffers stay donated exactly
as in the AOT engine.

Champions outside the VM vocabulary raise ``VMUnsupported`` from the
constructor / ``swap_program`` — the caller (cli serve, the promotion
controller's fast path) falls back to the AOT closure engine, which
remains the exact reference and the escape hatch.
"""
from __future__ import annotations

import contextlib
import copy
import dataclasses
import hashlib
import threading
import time
import warnings
from collections import OrderedDict
from typing import Optional

import jax

from fks_tpu import obs
from fks_tpu.data.entities import Workload
from fks_tpu.funsearch import vm
from fks_tpu.parallel.mesh import make_sharded_vm_serve_fn
from fks_tpu.serve.artifact import ChampionSpec, ServeEngine
from fks_tpu.serve.batcher import (
    pack_program_tables, tree_h2d_bytes, unpack_program_tables,
    unpack_query_tables,
)
from fks_tpu.sim.engine import run_batched_lanes, shape_prefilter_k


class VMServeEngine(ServeEngine):
    """A serve engine whose executables are champion-agnostic.

    Construction lowers the champion via ``vm.compile_policy`` and pads
    it to ``program_capacity`` (default: ``vm.capacity_bucket`` of the
    lowered op count) — ``VMUnsupported`` propagates to the caller, the
    AOT-fallback trigger. Everything else (shape envelope, bucket
    routing, snapshot-table cache, double-buffered dispatch, mesh
    sharding) is inherited; the executables differ only in taking the
    packed program tables as argument 0, replicated across the mesh
    (``make_sharded_vm_serve_fn``) while the lane axes shard as before.

    ``swap_program(champion)`` is the whole promotion hot path: it
    re-binds the served champion IN PLACE under a lock that excludes
    in-flight batches, and returns the previous ``ChampionSpec`` as the
    rollback handle (``ServeService.swap_engine`` accepts a
    ``ChampionSpec`` and routes it here)."""

    engine_kind = "vm"

    def __init__(self, champion: ChampionSpec, workload: Workload, *,
                 program_capacity: Optional[int] = None, **kw):
        # set BEFORE super().__init__: the parent constructor resolves
        # the policy (which fixes the capacity bucket) during init
        self._capacity_override = (int(program_capacity)
                                   if program_capacity else None)
        self.program_capacity = 0
        self.vm_swaps = 0
        self.vm_swap_h2d_bytes = 0
        self.last_swap_breakdown: dict = {}
        # host-side transpile cache: canonical code key -> padded
        # VMProgram. The transpile is ~60ms of the 64ms swap
        # (ROADMAP-named); a probation rollback or A/B flip re-swaps a
        # champion this engine already lowered, so the warm swap is the
        # H2D upload alone. Bounded FIFO — programs are a few KB.
        self._transpile_cache: "OrderedDict[tuple, vm.VMProgram]" = \
            OrderedDict()
        self._transpile_cache_max = 32
        self.transpile_cache_hits = 0
        self.transpile_cache_misses = 0
        # the cache is shared with shadow views AND (promotion overlap)
        # a background transpile worker — all access goes under this lock
        self._transpile_lock = threading.Lock()
        # code keys whose transpile was overlapped with shadow eval: the
        # next swap of that champion reports transpile_overlapped=True
        self._overlap_warmed: set = set()
        # swaps exclude in-flight batches: answer_batch holds this for
        # the whole batch, swap_program for the pointer flip only
        self._swap_lock = threading.RLock()
        super().__init__(champion, workload, **kw)
        self._prog_dev = self._upload_program(self.params)

    # ----- champion lowering / residency

    def _resolve_policy(self, code: str, n: int, g: int):
        """Champion source -> (``vm.score``, padded VMProgram, "vm").
        No jit fallback here — a champion outside the VM vocabulary
        raises ``VMUnsupported`` to the caller, who serves it on the AOT
        closure engine instead."""
        prog = vm.compile_policy(code, n, g)
        cap = self._capacity_override or vm.capacity_bucket(int(prog.n_ops))
        prog = vm.pad_capacity(prog, cap)  # VMUnsupported if too long
        self.program_capacity = cap
        # seed the transpile cache: re-swapping the construction
        # champion (rollback after a failed promotion) is a warm swap
        with self._transpile_lock:
            self._transpile_cache[self._code_key(code, n, g, cap)] = prog
        return vm.score, prog, "vm"

    def _resolve_prefilter(self, override: Optional[int], n: int,
                           g: int) -> int:
        """A VM champion is interpreted per node, so it is served under
        the rule it was evaluated under (``CodeEvaluator``): chosen from
        the cluster's shape, never from a timing probe."""
        return shape_prefilter_k(n, override)

    @staticmethod
    def _code_key(code: str, n: int, g: int, cap: int) -> tuple:
        """Canonical transpile-cache key: exact content hash of the
        champion source plus the lowering shape. NOT the analysis-layer
        ``fingerprint`` — that one buckets constants by decade (dedup
        semantics), which would alias two DIFFERENT champions onto one
        cached program. A swap must serve exactly what was promoted."""
        return (hashlib.sha256(code.encode()).hexdigest(), n, g, cap)

    def _lower_champion(self, code: str, n: int, g: int) -> tuple:
        """``compile_policy`` + ``pad_capacity`` through the host-side
        cache; returns ``(prog, "hit"|"miss")``. ``VMUnsupported``
        propagates uncached — a rejected champion must re-raise on
        retry, not silently hit."""
        key = self._code_key(code, n, g, self.program_capacity)
        with self._transpile_lock:
            hit = self._transpile_cache.get(key)
            if hit is not None:
                self.transpile_cache_hits += 1
                self._transpile_cache.move_to_end(key)
                return hit, "hit"
        prog = vm.pad_capacity(vm.compile_policy(code, n, g),
                               self.program_capacity)
        with self._transpile_lock:
            self.transpile_cache_misses += 1
            self._transpile_cache[key] = prog
            while len(self._transpile_cache) > self._transpile_cache_max:
                self._transpile_cache.popitem(last=False)
        return prog, "miss"

    def begin_overlapped_transpile(self, champion: ChampionSpec):
        """Kick the host-side transpile of ``champion`` on a worker
        thread — the promotion controller calls this when an attempt
        enters SHADOW, so the ~60ms ``compile_policy`` on a cache miss
        overlaps the shadow replay instead of sitting on the commit
        swap's critical path. The worker lowers THROUGH the shared
        transpile cache (lock-guarded — a racing swap that gets there
        first simply wins and the worker hits); the next swap of this
        champion reports ``transpile_overlapped=True`` in its vm_swap /
        slot_swap event. ``VMUnsupported`` candidates are swallowed —
        the swap itself re-raises with full context. Returns the thread
        (joinable in tests)."""
        n, g = self.cluster.n_padded, self.cluster.g_padded
        key = self._code_key(champion.code, n, g, self.program_capacity)

        def _work() -> None:
            try:
                self._lower_champion(champion.code, n, g)
            except vm.VMUnsupported:
                return
            with self._transpile_lock:
                self._overlap_warmed.add(key)

        thread = threading.Thread(target=_work, daemon=True,
                                  name="vm-transpile-overlap")
        thread.start()
        return thread

    def _consume_overlap(self, key: tuple) -> bool:
        """Whether this swap's transpile was prewarmed by an overlapped
        worker (one-shot: the flag is consumed)."""
        with self._transpile_lock:
            if key in self._overlap_warmed:
                self._overlap_warmed.discard(key)
                return True
            return False

    def _upload_program(self, prog: vm.VMProgram):
        """Packed program tables -> device-resident pytree (replicated
        across the mesh), synchronously — the swap's H2D cost must be on
        the swap, not smeared into the next batch."""
        packed = pack_program_tables(prog)
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            dev = jax.device_put(packed,
                                 NamedSharding(self.mesh, PartitionSpec()))
        else:
            dev = jax.device_put(packed)
        jax.block_until_ready(dev)
        return dev

    def swap_program(self, champion: ChampionSpec) -> ChampionSpec:
        """The zero-rebuild promotion hot path: lower the new champion,
        pad to THIS engine's capacity bucket, upload the packed tables,
        flip the resident pointers. Raises ``VMUnsupported`` (champion
        outside the vocabulary, or longer than the bucket) with the
        engine untouched. Returns the previous champion — the rollback
        handle; rolling back is another ``swap_program``."""
        t0 = time.perf_counter()
        n, g = self.cluster.n_padded, self.cluster.g_padded
        prog, cache = self._lower_champion(champion.code, n, g)
        overlapped = self._consume_overlap(
            self._code_key(champion.code, n, g, self.program_capacity))
        t1 = time.perf_counter()
        dev = self._upload_program(prog)
        t2 = time.perf_counter()
        h2d = tree_h2d_bytes(pack_program_tables(prog))
        with self._swap_lock:  # exclude in-flight batches for the flip
            old = self.champion
            self.champion = champion
            self.params = prog
            self._prog_dev = dev
        self.vm_swaps += 1
        self.vm_swap_h2d_bytes += h2d
        self.last_swap_breakdown = {
            "transpile_ms": round((t1 - t0) * 1e3, 3),
            "h2d_ms": round((t2 - t1) * 1e3, 3),
            "swap_ms": round((time.perf_counter() - t0) * 1e3, 3),
            "h2d_bytes": h2d,
            "capacity": self.program_capacity,
            "transpile_cache": cache,
            "transpile_overlapped": overlapped,
            "transpile_cache_hits": self.transpile_cache_hits,
            "transpile_cache_misses": self.transpile_cache_misses,
        }
        self.recorder.event("vm_swap", outcome="swapped",
                            champion=champion.source or "<inline>",
                            **self.last_swap_breakdown)
        return old

    def shadow_for(self, champion: ChampionSpec) -> "VMServeEngine":
        """A shadow VIEW of this engine serving ``champion``: shares the
        compiled executable set and the device snapshot cache (warm by
        construction — shadow evaluation compiles nothing) with its own
        champion tables, so the promotion controller can replay traffic
        through the candidate while the incumbent keeps serving.
        ``VMUnsupported`` propagates — the controller's AOT-fallback
        trigger."""
        n, g = self.cluster.n_padded, self.cluster.g_padded
        # shares the incumbent's transpile cache too: promoting the
        # champion just shadow-evaluated is then a warm swap
        prog, _ = self._lower_champion(champion.code, n, g)
        shadow = copy.copy(self)
        shadow.champion = champion
        shadow.params = prog
        shadow._prog_dev = self._upload_program(prog)
        shadow._swap_lock = threading.RLock()
        shadow._reset_batch_log()
        shadow.last_swap_breakdown = {}
        return shadow

    # ----- compilation (champion-agnostic executables)

    def _make_serve_fn(self, pod_bucket: int):
        """The parent's batched pipeline with the program as a traced
        argument: ONE program drives every lane (in_axes=None — the
        single-tenant case of the portfolio layout), so the register
        program is loop-invariant and XLA hoists the table reads."""
        cfg = self.bucket_config(pod_bucket)
        max_steps = cfg.max_steps
        mod = self._mod
        plan = self._pack_plan(pod_bucket)
        cluster = dataclasses.replace(self.cluster, node_ids=())

        def step_one(prog, p, k, s):
            w = Workload(cluster=cluster, pods=p, faults=None)
            return mod.build_step(
                w, lambda pod, nodes: vm.score(prog, pod, nodes),
                cfg, k, max_steps)(s)

        vstep = jax.vmap(step_one, in_axes=(None, 0, 0, 0))
        vfin = jax.vmap(
            lambda p, s: mod.finalize(
                Workload(cluster=cluster, pods=p, faults=None), cfg, s),
            in_axes=(0, 0))

        def serve_fn(packed, pods, kt, state0):
            prog = unpack_program_tables(packed)
            pods, kt = unpack_query_tables(pods, kt, plan)
            final = run_batched_lanes(
                lambda s: vstep(prog, pods, kt, s), state0,
                max_steps, active_fn=mod.lane_active)
            return self._result(vfin(pods, final), final)

        return serve_fn

    def compiled_for(self, lanes: int, pod_bucket: int):
        """The (lanes, pod_bucket, program_capacity) AOT executable —
        keyed on the CAPACITY BUCKET, never the champion, so it survives
        every ``swap_program``. pods (arg 1) and state0 (arg 3) are
        donated per batch; the resident program tables (arg 0) and the
        cached ktable (arg 2) are NOT — their buffers outlive the call."""
        key = (lanes, pod_bucket, self.program_capacity)
        hit = self._compiled.get(key)
        if hit is not None:
            return hit
        with self.profiler.stage("compile", lanes=lanes, pods=pod_bucket):
            with obs.span("serve_compile", lanes=lanes, pods=pod_bucket,
                          engine=self.engine_name,
                          capacity=self.program_capacity):
                fn = self._make_serve_fn(pod_bucket)
                if self.mesh is not None:
                    fn = make_sharded_vm_serve_fn(fn, self.mesh)
                example = ((self._prog_dev,)
                           + super()._example_batch(lanes, pod_bucket))
                writes0 = vm.trace_counts()
                with warnings.catch_warnings():
                    warnings.filterwarnings("ignore",
                                            message="Some donated")
                    compiled = jax.jit(fn, donate_argnums=(1, 3)) \
                        .lower(*example).compile()
                self._keep_writes(compiled, writes0)
        self._compiled[key] = compiled
        self.cold_compiles += 1
        return compiled

    # ----- answering

    def _invoke(self, compiled, pods, kt_dev, s0):
        return compiled(self._prog_dev, pods, kt_dev, s0)

    @contextlib.contextmanager
    def _batch_guard(self):
        # a whole batch answers under ONE champion: swap_program's flip
        # waits for the in-flight batch instead of tearing it
        with obs.span("serve/batch/swap_wait"):
            self._swap_lock.acquire()
        try:
            yield
        finally:
            self._swap_lock.release()
