"""PortfolioEngine: N resident champions in ONE vmapped VM executable.

``VMServeEngine`` made the champion an argument; this engine makes the
ARGUMENT a table of champions. All N resident ``VMProgram``s are padded
to one shared capacity bucket (``vm.stack_programs`` semantics), packed
into a single stacked wire block (``pack_portfolio_tables``), and kept
device-resident replicated across the mesh — exactly as the single
champion's tables were — while each batch lane carries a SLOT INDEX that
``vm.select_slot`` gathers per lane inside the vmap. One executable per
(lanes, pod_bucket, program_capacity, n_slots) therefore answers batches
that MIX tenants and policies, and the whole fleet shares one compile
(the "Fast Population-Based RL on a Single Machine" move, serve-side).

Slot lifecycle is the ``swap_program`` story per slot: ``swap_slot(i,
champion)`` lowers through the shared transpile cache, re-stacks the
slot table host-side, uploads the block, and flips the resident pointer
under the batch lock — zero XLA compiles, the old slot champion returned
as the rollback handle, one ``slot_swap`` event emitted. Spare slots
(``n_slots`` > len(champions)) start as clones of slot 0 and serve as
SHADOW staging slots for the FleetController: a candidate is uploaded
into a spare slot and evaluated on mirrored traffic inside the same
executable before its target slot is flipped.
"""
from __future__ import annotations

import dataclasses
import time
import warnings
from typing import Dict, List, Optional, Sequence

import jax
import numpy as np

from fks_tpu import obs
from fks_tpu.data.entities import Workload
from fks_tpu.funsearch import vm
from fks_tpu.parallel.mesh import make_sharded_portfolio_serve_fn
from fks_tpu.serve.artifact import ChampionSpec
from fks_tpu.serve.batcher import (
    pack_portfolio_tables, tree_h2d_bytes, unpack_portfolio_tables,
    unpack_query_tables,
)
from fks_tpu.serve.vm_engine import VMServeEngine
from fks_tpu.sim.engine import run_batched_lanes


class PortfolioEngine(VMServeEngine):
    """A VM serve engine whose resident program is a SLOT TABLE.

    ``champions`` fills slots 0..len-1 (slot 0 is the default/reference
    champion — ``self.champion``/``self.params`` track it so every
    inherited single-champion path, ``reference_answer`` included, stays
    honest); ``n_slots`` (default ``len(champions)``) fixes the compiled
    slot-table shape, so spare slots are free shadow-staging capacity,
    not a recompile. All champions must lower to the VM vocabulary —
    ``VMUnsupported`` propagates from construction, and the Router's
    coverage fallback keeps such champions on the AOT escape hatch."""

    is_portfolio = True

    def __init__(self, champions: Sequence[ChampionSpec],
                 workload: Workload, *, n_slots: Optional[int] = None,
                 program_capacity: Optional[int] = None, **kw):
        champions = list(champions)
        if not champions:
            raise ValueError("PortfolioEngine needs at least one champion")
        if workload.snapshot is not None:
            raise ValueError(
                "snapshot: serving forks one champion an engine "
                "(VMServeEngine); the portfolio's slot-table executables "
                "are not built from a fork")
        if workload.typed:
            raise ValueError(
                "gpu_spec: the portfolio's slot-table executables are "
                "built for queries whose pods carry no GPU-type "
                "constraints; a query's gpu_spec is honoured by "
                "ServeEngine / VMServeEngine, one champion an engine. "
                "Serve a workload parsed with gpu_spec='honor' there, or "
                "parse it without the choice")
        self.n_slots = int(n_slots) if n_slots else len(champions)
        if self.n_slots < len(champions):
            raise ValueError(
                f"n_slots={self.n_slots} < {len(champions)} champions")
        # consumed by _resolve_policy during the parent constructor
        self._pending_portfolio = champions
        self._slot_champions: List[ChampionSpec] = []
        self._slot_progs: List[vm.VMProgram] = []
        self.slot_requests = [0] * self.n_slots
        self.slot_swaps = [0] * self.n_slots
        self.last_slot_swapped: Optional[int] = None
        self._batch_slots: Optional[List[int]] = None
        self._pending_slots_dev = None
        self._pending_slots: List[int] = [0]
        super().__init__(champions[0], workload,
                         program_capacity=program_capacity, **kw)
        # the parent uploaded slot 0 alone; replace with the full table
        self._prog_dev = self._upload_stacked(self._slot_progs)

    # ----- portfolio lowering / residency

    def _resolve_policy(self, code: str, n: int, g: int):
        """Lower EVERY pending champion, size the shared capacity bucket
        to the longest member, pad all to it, seed the transpile cache
        (re-swapping any construction champion is a warm swap). The
        parent contract (``vm.score``, slot-0 program, "vm") holds."""
        champs = self._pending_portfolio
        raw = [vm.compile_policy(c.code, n, g) for c in champs]
        cap = self._capacity_override or max(
            vm.capacity_bucket(int(p.n_ops)) for p in raw)
        progs = [vm.pad_capacity(p, cap) for p in raw]
        self.program_capacity = cap
        with self._transpile_lock:
            for c, p in zip(champs, progs):
                self._transpile_cache[self._code_key(c.code, n, g, cap)] = p
        spare = self.n_slots - len(champs)
        self._slot_champions = list(champs) + [champs[0]] * spare
        self._slot_progs = list(progs) + [progs[0]] * spare
        return vm.score, progs[0], "vm"

    @property
    def slot_champions(self) -> List[ChampionSpec]:
        """The resident champion of every slot (copy)."""
        return list(self._slot_champions)

    def _upload_stacked(self, progs: Sequence[vm.VMProgram]):
        """Stacked slot tables -> device-resident pytree (replicated
        across the mesh), synchronously — same contract as the parent's
        ``_upload_program``, one slot axis wider."""
        packed = pack_portfolio_tables(progs)
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            dev = jax.device_put(packed,
                                 NamedSharding(self.mesh, PartitionSpec()))
        else:
            dev = jax.device_put(packed)
        jax.block_until_ready(dev)
        return dev

    def swap_slot(self, slot: int, champion: ChampionSpec) -> ChampionSpec:
        """Per-slot zero-rebuild promotion: lower the champion (warm via
        the shared transpile cache), re-stack the slot table host-side,
        upload the block, flip the pointer under the batch lock. Raises
        ``VMUnsupported`` with the engine untouched. Returns the slot's
        previous champion — the rollback handle; rolling back is another
        ``swap_slot``. Emits one ``slot_swap`` event."""
        slot = int(slot)
        if not 0 <= slot < self.n_slots:
            raise ValueError(
                f"slot {slot} outside portfolio [0, {self.n_slots})")
        t0 = time.perf_counter()
        n, g = self.cluster.n_padded, self.cluster.g_padded
        prog, cache = self._lower_champion(champion.code, n, g)
        overlapped = self._consume_overlap(
            self._code_key(champion.code, n, g, self.program_capacity))
        t1 = time.perf_counter()
        new_progs = list(self._slot_progs)
        new_progs[slot] = prog
        dev = self._upload_stacked(new_progs)
        t2 = time.perf_counter()
        h2d = tree_h2d_bytes(pack_portfolio_tables(new_progs))
        with self._swap_lock:  # exclude in-flight batches for the flip
            old = self._slot_champions[slot]
            self._slot_progs = new_progs
            self._slot_champions[slot] = champion
            self._prog_dev = dev
            if slot == 0:  # slot 0 is the default/reference champion
                self.champion = champion
                self.params = prog
        self.slot_swaps[slot] += 1
        self.vm_swaps += 1
        self.vm_swap_h2d_bytes += h2d
        self.last_slot_swapped = slot
        self.last_swap_breakdown = {
            "slot": slot,
            "transpile_ms": round((t1 - t0) * 1e3, 3),
            "h2d_ms": round((t2 - t1) * 1e3, 3),
            "swap_ms": round((time.perf_counter() - t0) * 1e3, 3),
            "h2d_bytes": h2d,
            "capacity": self.program_capacity,
            "transpile_cache": cache,
            "transpile_overlapped": overlapped,
        }
        self.recorder.event(
            "slot_swap", outcome="swapped",
            champion=champion.source or "<inline>",
            **self.last_swap_breakdown)
        return old

    def swap_program(self, champion: ChampionSpec) -> ChampionSpec:
        """The single-champion hot path maps to the DEFAULT slot, so
        ``ServeService.swap_engine(ChampionSpec)`` keeps working over a
        portfolio unchanged."""
        return self.swap_slot(0, champion)

    def shadow_for(self, champion: ChampionSpec):
        """Portfolio shadows are SLOTS, not engine copies — a copied view
        cannot satisfy the slot-table executable signature. The
        FleetController stages candidates in a spare slot instead."""
        raise TypeError(
            "PortfolioEngine stages shadows in slots: use "
            "FleetController (shadow_slot=...) or swap_slot directly")

    # ----- compilation (slot-agnostic executables)

    def _make_serve_fn(self, pod_bucket: int):
        """The VM pipeline with per-lane slot dispatch: the stacked
        program is broadcast into the vmap (``in_axes=None``) and each
        lane gathers its own champion via ``vm.select_slot`` — the
        general case of the parent's one-program layout."""
        cfg = self.bucket_config(pod_bucket)
        max_steps = cfg.max_steps
        mod = self._mod
        plan = self._pack_plan(pod_bucket)
        cluster = dataclasses.replace(self.cluster, node_ids=())

        def step_one(stacked, slot, p, k, s):
            prog = vm.select_slot(stacked, slot)
            w = Workload(cluster=cluster, pods=p, faults=None)
            return mod.build_step(
                w, lambda pod, nodes: vm.score(prog, pod, nodes),
                cfg, k, max_steps)(s)

        vstep = jax.vmap(step_one, in_axes=(None, 0, 0, 0, 0))
        vfin = jax.vmap(
            lambda p, s: mod.finalize(
                Workload(cluster=cluster, pods=p, faults=None), cfg, s),
            in_axes=(0, 0))

        def serve_fn(packed, slots, pods, kt, state0):
            stacked = unpack_portfolio_tables(packed)
            pods, kt = unpack_query_tables(pods, kt, plan)
            final = run_batched_lanes(
                lambda s: vstep(stacked, slots, pods, kt, s), state0,
                max_steps, active_fn=mod.lane_active)
            return vfin(pods, final)

        return serve_fn

    def _lane_put(self, arr: np.ndarray):
        """Host lane-axis array -> device, sharded like the batch."""
        if self._sharding is not None:
            return jax.device_put(arr, self._sharding)
        return jax.device_put(arr)

    def compiled_for(self, lanes: int, pod_bucket: int):
        """The (lanes, pod_bucket, program_capacity, n_slots) executable
        — keyed on the slot-table SHAPE, never its contents, so it
        survives every ``swap_slot``. pods (arg 2) and state0 (arg 4)
        are donated per batch; the resident slot tables (0), the lane
        slot indices (1) and the cached ktable (3) are NOT."""
        key = (lanes, pod_bucket, self.program_capacity, self.n_slots)
        hit = self._compiled.get(key)
        if hit is not None:
            return hit
        with self.profiler.stage("compile", lanes=lanes, pods=pod_bucket):
            with obs.span("serve_compile", lanes=lanes, pods=pod_bucket,
                          engine=self.engine_name,
                          capacity=self.program_capacity,
                          slots=self.n_slots):
                fn = self._make_serve_fn(pod_bucket)
                if self.mesh is not None:
                    fn = make_sharded_portfolio_serve_fn(fn, self.mesh)
                slots0 = self._lane_put(np.zeros(lanes, np.int32))
                example = ((self._prog_dev, slots0)
                           + self._example_batch(lanes, pod_bucket))
                writes0 = vm.trace_counts()
                with warnings.catch_warnings():
                    warnings.filterwarnings("ignore",
                                            message="Some donated")
                    compiled = jax.jit(fn, donate_argnums=(2, 4)) \
                        .lower(*example).compile()
                self._keep_writes(compiled, writes0)
        self._compiled[key] = compiled
        self.cold_compiles += 1
        return compiled

    # ----- answering (slot threading)

    def answer_batch(self, pod_lists, slots: Optional[Sequence[int]] = None):
        """Answer a batch that may MIX champions: ``slots[i]`` picks the
        resident policy for query i (default: slot 0 for every lane).
        The slot list rides the instance across the parent's bucket
        grouping — ``_dispatch_chunk`` below re-derives each chunk's
        per-lane slice — and the whole batch stays under the swap lock,
        so a concurrent ``swap_slot`` flips between batches, never
        inside one."""
        if slots is not None:
            slots = [int(s) for s in slots]
            if len(slots) != len(pod_lists):
                raise ValueError(
                    f"{len(slots)} slots for {len(pod_lists)} queries")
            for s in slots:
                if not 0 <= s < self.n_slots:
                    raise ValueError(
                        f"slot {s} outside portfolio [0, {self.n_slots})")
        with self._swap_lock:
            self._batch_slots = slots
            try:
                return super().answer_batch(pod_lists)
            finally:
                self._batch_slots = None

    def _dispatch_chunk(self, bucket: int, idxs, pod_lists):
        lanes = self._global_lanes(len(idxs))
        chunk = ([self._batch_slots[i] for i in idxs]
                 if self._batch_slots is not None else [0] * len(idxs))
        for s in chunk:
            self.slot_requests[s] += 1
        # pad lanes replicate the last real lane's slot (the _pad_kt /
        # pad_population rule); their answers are never scattered back
        padded = np.asarray(chunk + [chunk[-1]] * (lanes - len(chunk)),
                            np.int32)
        self._pending_slots_dev = self._lane_put(padded)
        self._pending_slots = chunk
        return super()._dispatch_chunk(bucket, idxs, pod_lists)

    def _loop_fields(self) -> Dict[str, int]:
        """The longest live program any lane of the chunk selected: what
        ``vm._loop_bound`` reduces to under the per-lane slot gather."""
        return {"slots": max(int(self._slot_progs[s].n_ops)
                             for s in self._pending_slots),
                "capacity": int(self.program_capacity)}

    def _invoke(self, compiled, pods, kt_dev, s0):
        return compiled(self._prog_dev, self._pending_slots_dev,
                        pods, kt_dev, s0)

    # ----- persistence (portfolio manifest)

    def save(self, directory: str) -> str:
        """The parent artifact plus a ``portfolio`` manifest: the full
        slot table, so ``ServeEngine.load`` rebuilds the whole fleet."""
        import json
        import os

        path = super().save(directory)
        with open(path) as f:
            doc = json.load(f)
        doc["portfolio"] = {
            "n_slots": self.n_slots,
            "slots": [c.to_json() for c in self._slot_champions],
        }
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(doc, f)
        os.replace(tmp, path)
        return path


def portfolio_selftest(engine: PortfolioEngine, count: int = 8,
                       pods_per_query: int = 3, tol: float = 1e-5) -> dict:
    """The portfolio parity sweep the ``portfolio_gate`` runs: every
    slot's answers through the SHARED executable must match a
    single-champion ``VMServeEngine`` serving that champion alone
    (integer placements bit-identical, scores within ``tol``), and a
    batch MIXING slots must reproduce the per-slot answers lane for
    lane. The reference engine is ONE VM engine re-pointed per slot via
    ``swap_program`` at the portfolio's capacity bucket — so the sweep
    itself compiles exactly one reference ladder, not one per champion.
    """
    from fks_tpu.serve.artifact import _pods_from_dicts

    base = engine.base_pods
    if not base:
        base = [{"cpu_milli": 1 + i, "memory_mib": 1, "creation_time": i,
                 "duration_time": 100} for i in range(pods_per_query * 2)]
    queries = []
    for i in range(count):
        start = i % max(1, len(base) - pods_per_query + 1)
        q = base[start:start + pods_per_query]
        queries.append(q if q else base[:1])
    wl = Workload(cluster=engine.cluster,
                  pods=_pods_from_dicts(engine.base_pods))
    ref = VMServeEngine(engine.slot_champions[0], wl,
                        envelope=engine.envelope,
                        engine=engine.engine_name,
                        prefilter_k=engine.prefilter_k,
                        state_pack=engine.state_pack,
                        max_steps_factor=engine.max_steps_factor,
                        program_capacity=engine.program_capacity,
                        mesh=engine.mesh, recorder=engine.recorder)
    max_drift = 0.0
    placements_ok = True
    failures: List[dict] = []
    per_slot: List[List[dict]] = []
    for k in range(engine.n_slots):
        mine = engine.answer_batch(queries, slots=[k] * len(queries))
        ref.swap_program(engine.slot_champions[k])
        solo = ref.answer_batch(queries)
        per_slot.append(mine)
        for i, (a, b) in enumerate(zip(mine, solo)):
            drift = abs(a["score"] - b["score"])
            max_drift = max(max_drift, drift)
            same = a["placements"] == b["placements"]
            placements_ok = placements_ok and same
            if drift > tol or not same:
                failures.append({"slot": k, "query": i,
                                 "drift": round(drift, 8),
                                 "placements_match": same})
    # the mixing check: one batch, every lane on its own slot, must
    # reproduce the per-slot sweeps bit for bit
    mix = [i % engine.n_slots for i in range(len(queries))]
    mixed = engine.answer_batch(queries, slots=mix)
    mixed_drift = 0.0
    for i, a in enumerate(mixed):
        b = per_slot[mix[i]][i]
        drift = abs(a["score"] - b["score"])
        mixed_drift = max(mixed_drift, drift)
        same = a["placements"] == b["placements"]
        placements_ok = placements_ok and same
        if drift > tol or not same:
            failures.append({"slot": mix[i], "query": i, "mixed": True,
                             "drift": round(drift, 8),
                             "placements_match": same})
    return {
        "ok": not failures,
        "checked": len(queries),
        "n_slots": engine.n_slots,
        "program_capacity": engine.program_capacity,
        "max_drift": round(max_drift, 10),
        "mixed_max_drift": round(mixed_drift, 10),
        "placements_match": placements_ok,
        "tol": tol,
        "failures": failures[:5],
    }
