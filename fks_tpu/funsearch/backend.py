"""Fitness backend for code candidates: transpile -> jit -> evaluate.

TPU-native replacement for the reference's subprocess fitness fan-out
(reference: funsearch/funsearch_integration.py:30-64 ``evaluate_policy_
standalone`` + 535-562 ProcessPoolExecutor): instead of forking a process
per candidate that re-parses the trace CSVs and runs the Python event loop,
each unique candidate is transpiled once into a vectorized policy, jitted
against the device-resident workload, and executed on-chip. The trace is
parsed once for the life of the backend; repeated/near-identical candidates
hit an AST-keyed compile cache (SURVEY.md §7: dedup doubles as compile-cache
key).

Failure semantics follow the reference's subprocess path: any failure —
validation, transpile, or execution — maps to fitness 0.0 and the candidate
stays in the pool's view (reference: funsearch_integration.py:63-64;
SURVEY.md §2 fine print 8).

Three throughput tiers:
- VM candidates (default): the candidate's jaxpr is lowered to a register
  program (fks_tpu.funsearch.vm) and interpreted by ONE engine program
  compiled once per evaluator — a fresh candidate costs a device run, not
  an XLA compile; with ``mesh=`` (a >1-device population mesh) the stacked
  generation is SHARDED over the mesh via
  fks_tpu.parallel.mesh.make_sharded_code_eval, each device interpreting
  its shard of the batch;
- jit candidates (fallback): one compiled program per unique AST, for the
  rare candidate outside the VM vocabulary;
- parametric candidates: one program TOTAL for the whole population
  (fks_tpu.parallel.population / .mesh) — the fast path the evolution
  controller uses for weight-vector mutation between LLM rounds.
"""
from __future__ import annotations

import concurrent.futures
import dataclasses
import os
import threading
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

import dataclasses as _dc

from fks_tpu import obs
from fks_tpu.data.entities import Workload
from fks_tpu.funsearch import lower_pool, transpiler, vm
from fks_tpu.sim.engine import SimConfig, shape_prefilter_k
from fks_tpu.sim.types import SimResult
from fks_tpu.utils.segments import validate_seg_steps


@dataclasses.dataclass
class EvalRecord:
    """One candidate's evaluation outcome."""

    code: str
    score: float
    error: Optional[str] = None  # why fitness is 0, when it is
    result: Optional[SimResult] = None
    # scenario-suite evaluations only: the per-scenario fitness vector the
    # composite ``score`` was folded from, and the fold that produced it
    scenario_scores: Optional[List[float]] = None
    aggregation: Optional[str] = None
    # budget-allocated evaluations (fks_tpu.funsearch.budget): the rung
    # this record's fidelity comes from — 0 = pruned at the probe rung
    # (score is the capped probe aggregate), 1 = survived to the full
    # suite; None on unbudgeted evaluations
    budget_rung: Optional[int] = None

    @property
    def ok(self) -> bool:
        return self.error is None


class CodeEvaluator:
    """Evaluate candidate source strings against one workload.

    The compile cache maps canonical AST keys to jitted run functions, so a
    re-submitted (or whitespace-variant) candidate costs one device launch,
    not a retrace. XLA's own jit cache adds a second layer keyed on the
    traced computation.
    """

    VM_CAPACITY = 512  # op budget; longer programs use the jit tier

    def __init__(self, workload: Workload, cfg: SimConfig = SimConfig(),
                 max_workers: Optional[int] = None, use_vm: bool = True,
                 engine: str = "exact", vm_batch: Optional[bool] = None,
                 mesh=None, suite=None, robust=None, budget=None,
                 preflight: bool = True, fp_dedup: bool = True,
                 profiler=None):
        from fks_tpu.parallel.mesh import num_shards
        from fks_tpu.sim import get_engine

        self.workload = workload
        # Mesh-sharded batched tier: with a >1-device mesh each device
        # interprets its shard of the stacked generation
        # (parallel.mesh.make_sharded_code_eval) — the jit/parametric
        # tiers and single-device behavior are unchanged.
        self.mesh = mesh
        self._n_shards = num_shards(mesh) if mesh is not None else 1
        # Batched VM evaluation: under vmap the interpreter's lax.switch
        # over a per-lane opcode executes ALL ~40 branches and selects.
        # On TPU each branch is one elementwise vreg op — noise next to
        # the engine step — so a generation as ONE launch wins; on a CPU
        # host the same 40x op fan-out runs serially and loses badly to
        # the sequential unbatched VM tier. Auto: batch iff the default
        # backend is an accelerator — or a multi-device mesh was passed,
        # which only the batched tier can use.
        if vm_batch is None:
            vm_batch = (jax.default_backend() != "cpu"
                        or self._n_shards > 1
                        # the budget rung ladder IS a batched-tier
                        # construct (one stacked launch per rung); with
                        # an enabled budget the pruning win dominates the
                        # CPU switch-fan-out loss, so batch there too
                        or (budget is not None and budget.enabled))
        self.vm_batch = vm_batch
        self.use_vm = use_vm
        # The batched tier lowers a generation's sources in the process's
        # pool of workers (fks_tpu.funsearch.lower_pool). Its first
        # evaluator starts it, before its own work, so that the workers'
        # start overlaps that and the first generation, which is lowered
        # here as far as they are not yet up.
        if use_vm and vm_batch:
            c = workload.cluster
            lower_pool.start(c.n_padded, c.g_padded)
        # Device-time attribution (fks_tpu.obs.profiler): when an enabled
        # StageProfiler is passed, evaluate() fences and attributes its
        # sandbox+preflight / transpile / device-eval stages; the default
        # NULL_PROFILER keeps every stage a no-op with no fences.
        self.profiler = (profiler if profiler is not None
                         else obs.NULL_PROFILER)
        # The large-cluster rule, chosen from the cluster's SHAPE and for
        # every tier at once (batched VM, unbatched VM, per-AST jit, the
        # thread-pool fallback, suite and budget rungs: all read
        # ``self.cfg``; the exact re-rank and the watchdog's reference
        # copy it), so a fitness does not depend on which tier answered.
        # A caller's non-zero value wins. 0 is the field's default and
        # reads as "not set": on a large cluster the dense sweep is asked
        # for as ``node_prefilter_k=n_padded``. ``prefilter_derived`` says
        # that the rule, not the caller, chose. The fused kernel has no
        # prefilter path and keeps what it was given.
        self.prefilter_derived = False
        if engine != "fused":
            k = shape_prefilter_k(workload.cluster.n_padded,
                                  cfg.node_prefilter_k or None)
            self.prefilter_derived = k != cfg.node_prefilter_k
            cfg = _dc.replace(cfg, node_prefilter_k=k)
        self.cfg = cfg
        self.engine = engine
        self._mod = get_engine(engine)
        # Scenario-suite mode (fks_tpu.scenarios): with ``suite`` (a
        # materialized ScenarioSuite over this workload) every candidate is
        # evaluated on ALL scenarios in one vmapped program and scored by
        # the composite robust aggregate; EvalRecords carry the
        # per-scenario breakdown. The jitted fused kernel has no fault
        # vocabulary (sim/fused.py rejects fault workloads), so suite mode
        # requires the exact or flat engine.
        self.suite = suite
        self.robust = robust
        # Eval-budget allocation (fks_tpu.funsearch.budget): with an
        # enabled BudgetConfig the batched VM tier spends its device
        # budget in rungs — the whole generation on a cheap probe, only
        # the surviving 1/eta fraction on the full suite.
        self.budget = budget if (budget is not None
                                 and budget.enabled) else None
        self.last_budget_stats: List[dict] = []  # per-rung, last evaluate()
        if self.budget is not None and engine == "fused":
            raise ValueError(
                "budget-pruned rungs (fks_tpu.funsearch.budget) are not "
                "supported in the fused kernel (probe scoring and fault "
                "suites have no Pallas lowering); run budget-allocated "
                "suite evaluation with engine='exact' or 'flat'")
        if self.budget is not None and suite is None:
            raise ValueError(
                "budget allocation prunes between a probe suite and the "
                "full suite, so it requires suite mode; set "
                "EvolutionConfig.scenario_suite (cli evolve --suite)")
        if suite is not None:
            if engine == "fused":
                raise ValueError(
                    "scenario suites are not supported on the fused "
                    "engine (fault events have no Pallas lowering); use "
                    "engine='exact' or 'flat'")
            if robust is None:
                from fks_tpu.scenarios.robust import RobustConfig
                self.robust = RobustConfig()
        # A workload with a snapshot (fks_tpu.data.snapshot) forks: the
        # flat engine's initial_state is the carry after the snapshot's
        # events, so every tier below starts from the loaded cluster and
        # a result still counts the whole run. ``start_event`` is where
        # the policy takes over (0 without a snapshot).
        snap = workload.snapshot
        if snap is not None and engine != "flat":
            raise ValueError(
                "snapshot: flat engine only for candidate evaluation (the "
                "tiers are not wired to the exact engine's fork, which "
                "serving uses; ROADMAP, Reach); use engine='flat'")
        self.start_event = 0 if snap is None else snap.e0
        #: GPU-type constraints of the workload, as the spans report them:
        #: pods that name the GPU models they accept (0 where the
        #: workload was parsed without ``gpu_spec='honor'``) and the size
        #: of the cluster's model vocabulary
        self.typed_fields = dict(
            typed_pods=int(np.count_nonzero(
                np.asarray(workload.pods.gpu_spec)[
                    np.asarray(workload.pods.pod_mask)]))
            if workload.typed else 0,
            node_models=len(workload.cluster.gpu_models))
        #: failed placements among the snapshot's events: in every
        #: result's whole-run count, and no work of the policy's
        self.fork_failed = 0
        if snap is None:
            self.state0 = self._mod.initial_state(workload, cfg)
        else:
            with obs.span("tier/fork_state", start_event=snap.e0,
                          rule=snap.rule, **self.typed_fields) as sp:
                self.state0 = jax.block_until_ready(
                    self._mod.initial_state(workload, cfg))
                counts = self._mod.fork_counts(workload, self.state0)
                sp.set(bytes=int(sum(
                    x.nbytes
                    for x in jax.tree_util.tree_leaves(self.state0))),
                    **counts)
            self.fork_failed = counts["prefix_failed"]
        self._cache: Dict[str, object] = {}
        self._lock = threading.Lock()
        self.compile_count = 0  # observability: unique programs built
        self.vm_count = 0  # candidates served by the VM tier (no compile)
        # Static pre-flight (fks_tpu.analysis.candidate): reject candidates
        # the transpiler provably cannot lower BEFORE sandbox/transpile/
        # compile spend anything on them, and collapse normalized-AST
        # fingerprint duplicates within a batch onto one representative.
        # Both paths emit ``candidate_rejected`` ledger events with a
        # machine-readable taxonomy.
        self.preflight = preflight
        self.fp_dedup = fp_dedup
        self.preflight_rejected = 0  # counters: ledger reads deltas
        self.preflight_duplicates = 0
        # observability: host-loop segment dispatches from the segmented
        # batched runners (fks_tpu.obs ledger reads per-generation deltas)
        self.segments_dispatched = 0
        self.last_eval_stats: Dict[str, int] = {}  # most recent evaluate()
        self.max_workers = max_workers or min(8, os.cpu_count() or 1)
        self._vm_run = None  # lazily built shared engine program
        self._vm_pop_run = None  # lazily built POPULATION engine program
        self._vm_mesh_run = None  # lazily built SHARDED population program
        self._budget_eval = None  # lazily built rung ladder (budget mode)
        self.vm_batch_count = 0  # observability: batched VM launches
        # (lanes, capacity) -> vm.trace_counts() over the first batched
        # launch of that bucket, which traced it (vm.TRACE_FIELDS)
        self._vm_traced: Dict[Tuple[int, int], Tuple[int, ...]] = {}
        # the most recent batched launch's [lanes] score array, on the
        # device: last_lanes_per_device reads its placement when asked
        self._last_scores = None
        # Bounded device-call length for the batched tier (flat engine
        # only). A full-trace batched-VM launch is minutes of device time
        # whatever the population size (v5e, 8 lanes x 370 live op
        # slots: 2.9 ms/event, up to 65k events), and one device call can be
        # neither observed nor interrupted: segments hand control back to
        # the host every ``vm_seg_steps`` events, which is what ticks the
        # profiler/flight-recorder segment counters and lets Ctrl-C land
        # between dispatches. 0 disables. Whether the segmented or the
        # single-dispatch runner is faster on the chip is ROADMAP D7's to
        # time; chip_smoke.py prints what was chosen.
        seg = os.environ.get("FKS_VM_SEG_STEPS")
        if seg is not None:
            self.vm_seg_steps = validate_seg_steps(
                seg, source="FKS_VM_SEG_STEPS")
        else:
            self.vm_seg_steps = (
                4096 if jax.default_backend() == "tpu" else 0)
        # double-buffered segment handoff (flat.make_segmented_population
        # _run): dispatch segment i+1 before syncing segment i's all-done
        # flag, so the device never stalls on the host round-trip. On by
        # default (results are pinned identical); FKS_VM_DOUBLE_BUFFER=0
        # restores the classic sync-per-segment loop for debugging.
        self.vm_double_buffer = (
            os.environ.get("FKS_VM_DOUBLE_BUFFER", "1") not in ("0", ""))

    @property
    def last_lanes_per_device(self) -> Dict[int, int]:
        """device id -> lanes it held in the most recent batched launch.
        Worked out when asked (chip_smoke.py does), never per launch."""
        if self._last_scores is None:
            return {}
        from fks_tpu.parallel.mesh import lanes_per_device
        return lanes_per_device(self._last_scores)

    # ----- VM tier: one engine program, candidates as data

    def _vm_runner(self):
        if self._vm_run is None:
            if self.suite is not None:
                # one candidate x all scenarios in one vmapped program;
                # cond_policy stays off — under the trace vmap a lax.cond
                # runs both branches anyway
                from fks_tpu.scenarios.robust import make_suite_eval
                ev = make_suite_eval(self.suite, vm.score, self.cfg,
                                     engine=self.engine)
                self._vm_run = lambda prog, _s: ev(prog)
            else:
                # the VM interpreter is expensive per event; skip it on
                # deletions (cond_policy) — this tier runs unbatched, where
                # lax.cond executes one branch
                cfg = _dc.replace(self.cfg, cond_policy=True)
                self._vm_run = jax.jit(
                    self._mod.make_param_run_fn(self.workload, vm.score, cfg))
        return self._vm_run

    def _try_vm(self, code: str) -> Optional[SimResult]:
        """SimResult via the shared interpreter program, or None when the
        candidate is outside the VM vocabulary (caller jits it instead)."""
        c = self.workload.cluster
        try:
            prog = vm.compile_policy(code, c.n_padded, c.g_padded,
                                     capacity=self.VM_CAPACITY)
        except vm.VMUnsupported:
            return None
        with self._lock:
            self.vm_count += 1
        return self._vm_runner()(prog, self.state0)

    # ----- batched VM tier: a GENERATION as one device program

    def _count_segment(self):
        """Host-loop segment-dispatch callback from the segmented batched
        runners (runs between device calls, never inside them)."""
        with self._lock:
            self.segments_dispatched += 1
        self.profiler.segment_tick()

    def _vm_pop_runner(self):
        if self._vm_pop_run is None:
            if self.suite is not None:
                # candidates x scenarios [C, T] from one program; the
                # segmented runners have no trace-batched variant, so
                # suite mode always takes the single-dispatch path
                from fks_tpu.scenarios.robust import make_suite_eval
                ev = make_suite_eval(self.suite, vm.score, self.cfg,
                                     population=True, engine=self.engine)
                self._vm_pop_run = lambda progs, _s: ev(progs)
                return self._vm_pop_run
            # population semantics per SimConfig.cond_policy docs: under
            # vmap a cond runs both branches, so keep cond_policy off and
            # let the self-masking step skip nothing — the batch amortizes
            if (self.vm_seg_steps > 0
                    and hasattr(self._mod, "make_segmented_population_run")):
                # manages its own inner jits; results identical to the
                # unsegmented runner (tests/test_flat_engine.py)
                self._vm_pop_run = self._mod.make_segmented_population_run(
                    self.workload, vm.score, self.cfg,
                    seg_steps=self.vm_seg_steps,
                    on_segment=self._count_segment,
                    double_buffer=self.vm_double_buffer)
            else:
                self._vm_pop_run = jax.jit(
                    self._mod.make_population_run_fn(
                        self.workload, vm.score, self.cfg))
        return self._vm_pop_run

    def _vm_mesh_runner(self):
        if self._vm_mesh_run is None:
            from fks_tpu.parallel.mesh import make_sharded_code_eval
            self._vm_mesh_run = make_sharded_code_eval(
                self.workload, self.mesh, cfg=self.cfg, elite_k=1,
                engine=self.engine, seg_steps=self.vm_seg_steps,
                on_segment=self._count_segment)
        return self._vm_mesh_run

    def _vm_traced_fields(self, bucket: Tuple[int, int], slots: int,
                          before: Tuple[int, ...], opcode,
                          shards: int) -> Dict[str, int]:
        """``slice_writes`` / ``scatter_writes``, ``merged_reads`` /
        ``split_reads`` and ``blocked_loops`` / ``plain_loops`` of a
        batched launch: how the bucket's runner lowered the op-slot loop's
        row write, its operand fetch and its trip structure
        (``vm.write_count``, ``vm.read_count``, ``vm.loop_count``, counted
        while a program is traced), and ``turns``: the turns that loop
        makes an event over the launch's ``slots`` (``vm.loop_turns``),
        and ``wide_turns``: those of them in which some lane of a device
        holds an opcode of ``vm.WIDE``, so that the turn runs the whole
        opcode table and not the narrow one (``vm.loop_wide_turns`` over
        the launch's stacked ``opcode`` words, ``shards`` devices each
        with its own lanes, on the host).
        The launch that moved a count since ``before``
        (``vm.trace_counts``) traced the bucket's program and the
        difference stays with the bucket; every later launch of it traces
        nothing and reports the same six numbers."""
        traced = tuple(x - y for x, y in zip(vm.trace_counts(), before))
        if any(traced):
            self._vm_traced[bucket] = traced
        traced = self._vm_traced.get(bucket, (0,) * 6)
        return dict(zip(vm.TRACE_FIELDS, traced),
                    turns=vm.loop_turns(slots, *traced[4:]),
                    wide_turns=vm.loop_wide_turns(
                        opcode, slots, *traced[4:], shards=shards))

    def _run_vm_batch(self, progs: List[vm.VMProgram]) -> List[SimResult]:
        """Evaluate stacked VM candidates in ONE device launch — sharded
        over the mesh when one with >1 device was passed.

        Shapes are bucketed (capacity to the stack's power-of-two, the
        population axis to the next power of two rounded to the shard
        count, padded by repeating the last program) so the jitted
        population runner retraces only per bucket, never per generation.
        Replaces the reference's one-subprocess-per-candidate fan-out
        (funsearch_integration.py:535-562) with one XLA program.
        """
        pop = vm.bucket_lanes(len(progs), self._n_shards)
        sharded = self._n_shards > 1 and self.suite is None
        with obs.span("tier/vm_batch/stack_programs",
                      candidates=len(progs), lanes=pop) as ts:
            # the generation's programs are NumPy words (``_evaluate``):
            # padded and stacked on the host, read on the host (slots,
            # the opcode words of `_vm_traced_fields`), and uploaded ONCE,
            # the eight leaves of the batch in one ``device_put``. A mesh
            # runner's sharded ``device_put`` (``mesh.shard_population``)
            # takes the host batch as it is: no stop on the first device
            padded = list(progs) + [progs[-1]] * (pop - len(progs))
            host = vm.stack_programs(padded)
            slots = max(int(p.n_ops) for p in progs)
            opcode = np.asarray(host.opcode)
            stacked = host if sharded else jax.device_put(host)
            ts.set(uploads=len(host))
        # launch + wait_device is the device's part of the generation (a
        # segmented runner already waits for its segments inside launch);
        # d2h is the one transfer and nothing else
        # slots / capacity: how far the op-slot loop runs (vm._loop_bound:
        # the longest live program; the slowest shard's when sharded)
        # nodes / view / register_bytes: the node axis, the width of it the
        # policy sees (the prefilter's k, or every node) and the register
        # file one device carries through the op-slot loop, which is what
        # each slot's update copies
        c = self.workload.cluster
        capacity = int(stacked.opcode.shape[-1])
        view = self.cfg.resolve_prefilter_k(c.n_padded) or c.n_padded
        with obs.span("tier/vm_batch/launch", lanes=pop,
                      shards=self._n_shards, start_event=self.start_event,
                      slots=slots,
                      capacity=capacity, nodes=c.n_padded, view=view,
                      register_bytes=(pop // self._n_shards)
                      * vm.register_rows(capacity) * view * c.g_padded
                      * stacked.imm.dtype.itemsize) as t_launch:
            traced0 = vm.trace_counts()
            if sharded:
                # each device interprets pop/shards lanes; the elite
                # outputs are discarded here (the evolution loop ranks on
                # the host, where admission/dedup live). Suite mode skips
                # this tier: make_sharded_code_eval has no scenario axis —
                # the [C, T] population runner serves the batch instead
                # (mesh-sharded SUITE evaluation lives at the parametric
                # tier, fks_tpu.scenarios.robust.make_sharded_suite_eval).
                result, _, _ = self._vm_mesh_runner()(stacked, len(progs))
            else:
                result = self._vm_pop_runner()(stacked, self.state0)
            t_launch.set(**self._vm_traced_fields((pop, capacity), slots,
                                                  traced0, opcode,
                                                  self._n_shards if sharded
                                                  else 1))
        self._last_scores = result.policy_score
        with obs.span("tier/vm_batch/wait_device"):
            jax.block_until_ready(result)
        # ONE device->host transfer for the whole generation: slicing
        # lazy device arrays would cost ~3 tiny syncs/lane in _record
        with obs.span("tier/vm_batch/d2h", bytes=int(sum(
                x.nbytes for x in jax.tree_util.tree_leaves(result)))):
            result = jax.device_get(result)
        with self._lock:
            self.vm_batch_count += 1
            self.vm_count += len(progs)
        with obs.span("tier/record", lanes=len(progs)):
            return [jax.tree_util.tree_map(lambda x, i=i: x[i], result)
                    for i in range(len(progs))]

    # ----- budgeted batched tier: probe rung -> survivors -> full rung

    def _budget_ladder(self):
        """The lazily built rung ladder (fks_tpu.funsearch.budget). The
        full rung reuses THIS evaluator's population suite program, so
        budget mode adds one compiled program (the probe), not two."""
        if self._budget_eval is None:
            from fks_tpu.funsearch.budget import BudgetedSuiteEval
            self._budget_eval = BudgetedSuiteEval(
                self.workload, self.cfg, self.budget, self.robust,
                full_runner=lambda stacked: self._vm_pop_runner()(
                    stacked, self.state0),
                engine=self.engine, n_shards=self._n_shards,
                segment_counter=lambda: self.segments_dispatched)
        return self._budget_eval

    def _budget_active(self, n: int) -> bool:
        """Budget pruning engages only when it would actually prune: an
        enabled schedule, suite mode, and a batch big enough that the
        survivor count is a strict subset."""
        return (self.budget is not None and self.suite is not None
                and n >= 2 and self.budget.survivors(n) < n)

    def _run_vm_batch_budget(self, progs: List[vm.VMProgram],
                             codes: List[str]) -> List[EvalRecord]:
        """Budgeted generation evaluation: every rung is one device
        launch on a bucketed static shape (fks_tpu.funsearch.budget).
        Survivors get full-fidelity suite records (budget_rung=1); the
        pruned keep their probe aggregate capped below the worst
        survivor's full score (budget_rung=0), so pruning can demote but
        never promote — the generation champion is always a survivor,
        and ParitySentinel.check_champion audits the rest."""
        outcome = self._budget_ladder().run(progs)
        with self._lock:
            self.vm_batch_count += len(outcome.rungs)
            self.vm_count += len(progs)
        records: List[Optional[EvalRecord]] = [None] * len(progs)
        floor = None
        for i in outcome.survivor_indices:
            rec = self._record_suite(codes[i], outcome.results[i])
            rec.budget_rung = 1
            records[i] = rec
            floor = rec.score if floor is None else min(floor, rec.score)
        for i, pruned in enumerate(outcome.pruned):
            if pruned:
                records[i] = self._record_pruned(
                    codes[i], outcome.results[i],
                    outcome.probe_scores[i], floor or 0.0)
        self.last_budget_stats = [r.asdict() for r in outcome.rungs]
        return records

    def _record_pruned(self, code: str, result: SimResult,
                       probe_score: float, floor: float) -> EvalRecord:
        """Probe-rung record for a pruned candidate. Truncation is the
        probe's DESIGN (probe_steps stops the run early), so unlike
        ``_record_suite`` an all-truncated probe is not an error — only
        an all-scenarios failure is. The score is the probe robust
        aggregate capped at the worst survivor's full-suite score: probe
        fitness is biased high (partial-prefix scoring ignores the
        unassigned-pods gate), and an uncapped probe score could crown a
        pruned dud over a fully-evaluated survivor."""
        per = np.asarray(result.policy_score, np.float64)
        breakdown = [float(x) for x in per]
        agg = self.robust.aggregation
        if bool(np.asarray(result.failed).all()):
            return EvalRecord(code, 0.0, "gpu allocation aborted "
                              "(all scenarios)", result, breakdown, agg,
                              budget_rung=0)
        return EvalRecord(code, float(min(probe_score, floor)), None,
                          result, breakdown, agg, budget_rung=0)

    def _record(self, code: str, result: SimResult) -> EvalRecord:
        if self.suite is not None:
            return self._record_suite(code, result)
        if bool(result.failed):
            return EvalRecord(code, 0.0, "gpu allocation aborted", result)
        if bool(result.truncated):
            return EvalRecord(code, 0.0, "event budget exceeded", result)
        return EvalRecord(code, float(result.policy_score), None, result)

    def _record_suite(self, code: str, result: SimResult) -> EvalRecord:
        """Suite-mode record: result leaves carry the scenario axis [T].
        A scenario that fails scores 0 THERE (finalize already gates the
        fitness) and drags the aggregate — reference failure semantics
        applied per scenario; the candidate only errors out when every
        scenario failed."""
        from fks_tpu.scenarios.robust import aggregate

        per = np.asarray(result.policy_score, np.float64)
        breakdown = [float(x) for x in per]
        agg = self.robust.aggregation
        failed = np.asarray(result.failed)
        truncated = np.asarray(result.truncated)
        if bool(failed.all()):
            return EvalRecord(code, 0.0, "gpu allocation aborted "
                              "(all scenarios)", result, breakdown, agg)
        if bool((failed | truncated).all()):
            return EvalRecord(code, 0.0, "event budget exceeded "
                              "(all scenarios)", result, breakdown, agg)
        score = float(aggregate(per, self.robust))
        return EvalRecord(code, score, None, result, breakdown, agg)

    def _compiled(self, code: str):
        key = transpiler.canonical_key(code)
        with self._lock:
            fn = self._cache.get(key)
        if fn is None:
            # transpile + trace OUTSIDE the lock: XLA compilation is native
            # code (GIL released), so distinct candidates compile in
            # parallel across evaluate()'s thread pool
            policy = transpiler.transpile(code)
            if self.suite is not None:
                from fks_tpu.scenarios.robust import make_suite_eval
                ev = make_suite_eval(
                    self.suite,
                    lambda _p, pod, nodes: policy(pod, nodes),
                    self.cfg, engine=self.engine)
                fn = lambda _s: ev(None)  # noqa: E731 — state0-call shape
            else:
                fn = jax.jit(
                    self._mod.make_run_fn(self.workload, policy, self.cfg))
            with self._lock:
                if key in self._cache:  # lost the race: reuse the winner
                    fn = self._cache[key]
                else:
                    self._cache[key] = fn
                    self.compile_count += 1
        return fn

    def evaluate_one(self, code: str, *,
                     try_vm: Optional[bool] = None) -> EvalRecord:
        """Reference semantics: exceptions -> score 0 with the reason kept
        (the reference loses the reason; we keep it for observability).
        ``try_vm=False`` skips the VM attempt (used by ``evaluate`` for
        candidates already known to be outside the VM vocabulary)."""
        try:
            result: Optional[SimResult] = None
            if self.use_vm if try_vm is None else try_vm:
                result = self._try_vm(code)
            if result is None:
                run = self._compiled(code)
                result = run(self.state0)
            return self._record(code, result)
        except transpiler.TranspileError as e:
            return EvalRecord(code, 0.0, f"transpile: {e}")
        except Exception as e:  # noqa: BLE001 — candidate code is untrusted
            return EvalRecord(code, 0.0, f"runtime: {e}")

    def evaluate(self, codes: Sequence[str]) -> List[EvalRecord]:
        """Evaluate a batch; duplicate sources are computed once.

        VM-vocabulary candidates (the common case) are lowered to register
        programs on the host, STACKED, and evaluated as ONE device launch
        (`_run_vm_batch`) — a generation of LLM candidates costs one
        population-engine execution, zero per-candidate XLA compiles. The
        rare candidate outside the VM vocabulary fans out over a thread
        pool to the per-code jit tier, whose XLA compiles (native code, GIL
        released) overlap each other. Result order — and therefore
        population admission order — matches the input order regardless of
        completion order.

        One ``tier/evaluate`` span is the root of the generation; its
        stages are spans whether the profiler is enabled or not.
        """
        with obs.span("tier/evaluate", candidates=len(codes),
                      start_event=self.start_event, **self.typed_fields):
            return self._evaluate(codes)

    def _evaluate(self, codes: Sequence[str]) -> List[EvalRecord]:
        """A generation's host stages, then its launch. What runs where:
        everything that is per SOURCE (the static pre-flight, the
        canonical key, the lowering, the packing into words) is
        ``lower_pool.lower_source``, run once a distinct text by the
        process's lowering workers side by side, or here, one after
        another, where there is no pool for it; this thread keeps what is
        per GENERATION: the exact-text dedup before the pool
        (``tier/preflight``), then, on what came back and in input order,
        the canonical-key and fingerprint dedup, the rejections' records,
        events and counters, the lane order (``tier/transpile/pack``), the
        stack and the one upload (`_run_vm_batch`). Two sources that
        differ in text and agree in key or fingerprint are lowered side
        by side and the first is kept."""
        seg0 = self.segments_dispatched
        vm0 = self.vm_count
        pf_rejected = 0
        fp_dupes = 0
        works: List[int] = []  # static per-node work bounds (accepted)
        fps: Dict[str, Optional[str]] = {}  # canonical key -> fingerprint
        keyed: List[Optional[str]] = []
        errors: Dict[int, EvalRecord] = {}
        unique: Dict[str, str] = {}
        alias: Dict[str, str] = {}
        with self.profiler.stage("sandbox+preflight", span="tier/preflight",
                                 candidates=len(codes)) as hp:
            # an exact echo is checked and lowered once, with its first
            texts = list(dict.fromkeys(codes))
            hp.annotate(unique=len(texts))

        memo: Dict[str, EvalRecord] = {}
        vm_progs: Dict[str, vm.VMProgram] = {}
        jit_only: Dict[str, str] = {}  # known outside the VM vocabulary
        general: Dict[str, str] = {}  # default tier choice (VM then jit)
        c = self.workload.cluster
        sources = [lower_pool.Source(code, self.preflight, self.fp_dedup)
                   for code in texts]
        # the batched tier takes a generation; the other tiers lower for
        # themselves, a source at a time, and need the check alone
        lower = self.use_vm and self.vm_batch and len(texts) > 1
        with self.profiler.stage("transpile", span="tier/transpile") as ht:
            if lower:
                # every source is checked, keyed, lowered and packed once,
                # side by side in the process's workers where it has
                # them; what is raised or recorded for a source is what
                # its task returned, wherever it ran
                lowered, pool = lower_pool.lower_all(
                    sources, c.n_padded, c.g_padded)
            else:
                lowered, pool = [lower_pool.check_source(src, c.g_padded)
                                 for src in sources], lower_pool.NOT_POOLED
            # each check and each lowering as a child span, on the stamps
            # of the process that did it; none of them where a worker's
            # clock is not this one's (a wrong interval is worse)
            misfit = lower_pool.clock_misfit(lowered)
            for i, low in enumerate(() if misfit else lowered):
                where = dict(source=i, pid=low.pid,
                             pooled=int(low.sent is not None))
                ht.span.child("tier/transpile/check", low.t0, low.t_checked,
                              ok=int(low.rejection is None), **where)
                if lower and low.rejection is None:
                    ht.span.child(
                        "tier/transpile/lower", low.t_checked, low.t1,
                        trace_ms=(low.t_traced - low.t_checked) * 1e3,
                        eqns=low.eqns, ops_lowered=low.ops_lowered,
                        ops_kept=len(low.kept[0]) if low.kept else 0,
                        **where)
            by_text = dict(zip(texts, lowered))
            with obs.span("tier/transpile/pack") as tp:
                # the parent's dedup, in input order, on what came back:
                # the records, events and counters of the rejected, then
                # the first source of a canonical key
                for i, code in enumerate(codes):
                    low = by_text[code]
                    keyed.append(low.key)
                    if low.rejection is None:
                        if low.work is not None:
                            works.append(low.work)
                        fps.setdefault(low.key, low.fingerprint)
                        unique.setdefault(low.key, code)
                        continue
                    taxonomy, reason = low.rejection
                    if taxonomy is None:
                        errors[i] = EvalRecord(code, 0.0, f"syntax: {reason}")
                        continue
                    # statically doomed: never reaches sandbox.validate,
                    # transpile, or any compile tier (pinned by tests)
                    errors[i] = EvalRecord(
                        code, 0.0, f"preflight: {taxonomy}: {reason}")
                    obs.get_recorder().event(
                        "candidate_rejected", taxonomy=taxonomy,
                        stage="preflight", reason=reason[:200])
                    pf_rejected += 1

                # normalized-AST near-duplicate suppression (within this
                # batch): fingerprint-colliding sources collapse onto one
                # representative — one compile/eval instead of k — and
                # every echo still receives the representative's full
                # EvalRecord
                if self.fp_dedup:
                    by_fp: Dict[str, str] = {}
                    for key in list(unique):
                        fp = fps.get(key)
                        if fp is None:
                            continue
                        owner = by_fp.setdefault(fp, key)
                        if owner != key:
                            alias[key] = owner
                            del unique[key]
                            fp_dupes += 1
                            obs.get_recorder().event(
                                "candidate_rejected",
                                taxonomy="duplicate_fingerprint",
                                stage="fp_dedup", reason=f"fingerprint {fp}")

                # the representatives' programs, in unique's order: it is
                # the lane order. They are NumPy words as they came back:
                # nothing is uploaded before the generation is stacked
                # (`_run_vm_batch`)
                if lower and len(unique) > 1:
                    for key, code in unique.items():
                        low = by_text[code]
                        if low.error is None:
                            if low.words.capacity <= self.VM_CAPACITY:
                                vm_progs[key] = low.words
                            else:  # over the op budget: the jit tier's
                                jit_only[key] = code
                        elif isinstance(low.error, vm.VMUnsupported):
                            jit_only[key] = code
                        else:
                            tag = ("transpile" if isinstance(
                                low.error, transpiler.TranspileError)
                                else "runtime")
                            memo[key] = EvalRecord(code, 0.0,
                                                   f"{tag}: {low.error}")
                else:
                    general = dict(unique)
                tp.set(programs=len(vm_progs), uploads=0)
            if len(vm_progs) == 1:  # a population program for one lane
                (key,) = vm_progs  # isn't worth it: unbatched VM tier
                general[key] = unique[key]
                vm_progs = {}
            ht.annotate(vm_lanes=len(vm_progs),
                        jit_fallback=len(jit_only) + len(general),
                        rejected=pf_rejected, duplicates=fp_dupes,
                        unique=len(unique))
            # sources: the representatives, which enter the tiers, and
            # pooled: those of them a worker ran (chipbench:
            # tier.pooled_source_share); workers: that ran a task. Over
            # every distinct text, as the child spans are: traces,
            # counted where a policy body runs, so a second trace per
            # source shows, as a key or fingerprint echo's does
            # (chipbench: tier.traces_per_source); ops_lowered /
            # ops_kept: what vm.simplify_ops was given and what it left
            # to pack (chipbench: vm.ops_kept_share), chains_folded: the
            # whole column chains it folded on the way. clock_misfit: 1
            # where the workers' stamps were refused and no child written
            ht.span.set(sources=len(unique), clock_misfit=int(misfit),
                        traces=sum(low.traces for low in lowered),
                        ops_lowered=sum(low.ops_lowered for low in lowered),
                        ops_kept=sum(len(low.kept[0]) for low in lowered
                                     if low.kept is not None),
                        chains_folded=sum(low.chains_folded
                                          for low in lowered),
                        pooled=sum(by_text[code].sent is not None
                                   for code in unique.values()),
                        workers=pool["workers"])

        batch_served = 0
        self.last_budget_stats = []
        with self.profiler.stage("device-eval") as hd:
            if vm_progs:
                vm_keys = list(vm_progs)
                try:
                    if self._budget_active(len(vm_keys)):
                        recs = self._run_vm_batch_budget(
                            [vm_progs[k] for k in vm_keys],
                            [unique[k] for k in vm_keys])
                        for key, rec in zip(vm_keys, recs):
                            memo[key] = rec
                    else:
                        results = self._run_vm_batch(
                            [vm_progs[k] for k in vm_keys])
                        with obs.span("tier/record", lanes=len(vm_keys)):
                            for key, res in zip(vm_keys, results):
                                memo[key] = self._record(unique[key], res)
                    batch_served = len(vm_keys)
                except Exception as e:  # noqa: BLE001 — batch failed:
                    # per-candidate fallback still produces scores, but say
                    # WHY the one-launch-per-generation path is not engaging
                    from fks_tpu.utils import get_logger
                    get_logger("fks_tpu.funsearch.backend").warning(
                        "batched VM launch failed (%s: %s); falling back "
                        "to per-candidate evaluation", type(e).__name__, e)
                    for key in vm_keys:
                        general.setdefault(key, unique[key])

            if jit_only or general:
                with obs.span("tier/fallback",
                              lanes=len(jit_only) + len(general)), \
                        concurrent.futures.ThreadPoolExecutor(
                            max_workers=self.max_workers) as ex:
                    futs = {key: ex.submit(self.evaluate_one, code,
                                           try_vm=False)
                            for key, code in jit_only.items()}
                    futs.update({key: ex.submit(self.evaluate_one, code)
                                 for key, code in general.items()})
                    for key, f in futs.items():
                        memo[key] = f.result()

            # occupancy over the three batch axes (padded lanes x
            # scenarios x trace segments): only the batched tier pads
            # lanes; the threadpool fallback launches real work only
            if batch_served:
                from fks_tpu.parallel.mesh import occupancy_stats
                hd.annotate(lanes=batch_served, **occupancy_stats(
                    batch_served, self._n_shards,
                    scenarios=len(self.suite) if self.suite else 1,
                    segments=max(1, self.segments_dispatched - seg0)))
            else:
                hd.annotate(lanes=len(jit_only) + len(general),
                            pad_waste_fraction=0.0)

        # observability: how this batch was served, for the evolution
        # ledger / flight recorder (host bookkeeping only — no device work)
        self.preflight_rejected += pf_rejected
        self.preflight_duplicates += fp_dupes
        self.last_eval_stats = {
            "candidates": len(codes),
            "unique": len(unique),
            "syntax_failed": len(errors) - pf_rejected,
            "preflight_rejected": pf_rejected,
            "fingerprint_duplicates": fp_dupes,
            "mean_static_work": (round(sum(works) / len(works), 1)
                                 if works else 0),
            "vm_batch_lanes": batch_served,
            "fallback_lanes": len(jit_only) + len(general),
            # where the policy took over (0: from the empty cluster) and
            # the placements that failed after it, over the generation's
            # lanes: a result's whole-run count less the snapshot's own
            "start_event": self.start_event,
            "frag_events": sum(
                int(np.sum(r.result.num_fragmentation_events
                           - self.fork_failed))
                for r in memo.values() if r.result is not None),
            "segments": self.segments_dispatched - seg0,
            # the large-cluster rule in effect (0 = every node is scored)
            "prefilter_k": self.cfg.resolve_prefilter_k(c.n_padded),
            "prefilter_derived": self.prefilter_derived,
            "budget_pruned": sum(r["entered"] - r["survived"]
                                 for r in self.last_budget_stats),
            # fraction of the batch's unique candidates served by the
            # VM tier — the live estimate of how much of the population
            # the zero-rebuild serve fast path can carry
            "vm_coverage": round((self.vm_count - vm0)
                                 / max(1, len(unique)), 4),
        }

        out = []
        for i, (key, code) in enumerate(zip(keyed, codes)):
            if key is None:
                out.append(errors[i])
            else:
                r = memo[alias.get(key, key)]
                out.append(EvalRecord(code, r.score, r.error, r.result,
                                      r.scenario_scores, r.aggregation,
                                      r.budget_rung))
        return out

    def scores(self, codes: Sequence[str]) -> np.ndarray:
        return np.asarray([r.score for r in self.evaluate(codes)], np.float64)
