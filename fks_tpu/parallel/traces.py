"""Multi-trace batching: vmap the simulator over a stacked trace axis.

BASELINE.json config 4 ("multi-trace batch, padded lax.scan, shape-bucketed
jit") done the TPU-native way: traces inside one shape bucket
(fks_tpu.data.synthetic.bucket_workloads) are stacked leaf-by-leaf into one
pytree with a leading trace axis ``T`` and the whole engine runs under
``vmap`` — ONE compiled program per (bucket shape, policy), regardless of
how many traces it serves. The reference has no analogue: its benchmark
harness re-runs the Python simulator per trace file
(reference: tests/test_scheduler.py:245-284 one deep-copied run per policy,
benchmarks/parser.py:103-115 per-file discovery).

Composes with the population axis: ``make_trace_batch_eval`` optionally
vmaps params too -> fitness[C, T] from one program.
"""
from __future__ import annotations

from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from fks_tpu.data.entities import ClusterArrays, PodArrays, Workload
from fks_tpu.models import parametric
from fks_tpu.parallel.population import ParamPolicyFn
from fks_tpu.sim import get_engine
from fks_tpu.sim.engine import SimConfig
from fks_tpu.sim.evaluator import max_snapshot_count, snapshot_trigger_table


def strip_ids(wl: Workload) -> Workload:
    """Drop host-side id tuples (static pytree meta) so same-shape workloads
    share one treedef and can stack under vmap. Public: the serving tier
    (fks_tpu.serve.batcher) stacks per-query workloads with exactly this
    normalization so queries match the AOT-compiled example's treedef."""
    if wl.snapshot is not None:
        raise ValueError(
            "snapshot: trace batching starts every workload from the "
            "empty cluster; evaluate a loaded cluster through "
            "CodeEvaluator / make_population_eval with engine='flat', "
            "serve one through ServeEngine / VMServeEngine "
            "(engine='exact')")
    return Workload(
        cluster=ClusterArrays(**{
            **{f: getattr(wl.cluster, f) for f in (
                "cpu_total", "mem_total", "gpu_declared", "num_gpus",
                "gpu_milli_total", "gpu_mem_total", "gpu_mask", "node_mask",
                "gpu_model", "gpu_models")},
            "node_ids": ()}),
        pods=PodArrays(**{
            **{f: getattr(wl.pods, f) for f in (
                "cpu", "mem", "num_gpu", "gpu_milli", "creation_time",
                "duration", "tie_rank", "pod_mask", "gpu_spec")},
            "pod_ids": ()}),
        faults=wl.faults)


_strip_ids = strip_ids  # internal alias, kept for existing call sites


def stack_traces(workloads: Sequence[Workload], cfg: SimConfig,
                 engine: str = "exact"):
    """Stack same-shape workloads into (workload[T,...], ktable[T,K],
    state0[T,...], max_steps).

    Host-side prep: per-trace snapshot tables are sized from each trace's
    REAL pod count (the reference's ``initialize(total_events)``,
    evaluator.py:47-53) then padded with an unreachable sentinel to a shared
    width; initial states are built per trace by the chosen engine (the
    exact engine runs real CPython heapq for its starting layout).
    """
    mod = get_engine(engine)
    if not workloads:
        raise ValueError("no workloads")
    shapes = {(w.cluster.n_padded, w.cluster.g_padded, w.pods.p_padded)
              for w in workloads}
    if len(shapes) != 1:
        raise ValueError(f"workloads span multiple padded shapes {shapes}; "
                         "bucket them first (fks_tpu.data.synthetic)")
    fshapes = {None if w.faults is None else w.faults.f_padded
               for w in workloads}
    if len(fshapes) != 1:
        raise ValueError(
            f"workloads mix fault-event padding {fshapes}; a stacked batch "
            "needs one shared FaultEvents shape on every trace (or none) — "
            "materialize suites via fks_tpu.scenarios, which pads faults "
            "uniformly (fault-free scenarios get an all-masked timeline)")
    max_steps = max(cfg.resolve_max_steps(w.num_pods) for w in workloads)
    ktables = [snapshot_trigger_table(
        w.num_pods,
        max_snapshot_count(max_steps, w.num_pods, cfg.snapshot_interval),
        cfg.snapshot_interval) for w in workloads]
    klen = max(len(k) for k in ktables)
    sentinel = np.iinfo(np.int32).max
    kt = np.full((len(workloads), klen), sentinel, np.int32)
    for i, k in enumerate(ktables):
        kt[i, : len(k)] = k

    states = [mod.initial_state(w, cfg) for w in workloads]
    hist_sizes = {s.wait_hist.shape[0] for s in states}
    if len(hist_sizes) != 1:
        raise ValueError(f"wait histogram sizes differ across traces "
                         f"{hist_sizes}; traces exceed the shared gpu_milli "
                         "range — split the bucket")

    stacked_wl = jax.tree_util.tree_map(
        lambda *xs: jnp.stack([jnp.asarray(x) for x in xs]),
        *[_strip_ids(w) for w in workloads])
    stacked_state = jax.tree_util.tree_map(
        lambda *xs: jnp.stack([jnp.asarray(x) for x in xs]), *states)
    return stacked_wl, jnp.asarray(kt), stacked_state, max_steps


def make_trace_batch_eval(workloads: Sequence[Workload],
                          param_policy: ParamPolicyFn = parametric.score,
                          cfg: SimConfig = SimConfig(),
                          population: bool = False,
                          jit: bool = True,
                          engine: str = "exact"):
    """Build ``eval(params) -> SimResult`` batched over the trace axis T.

    ``population=False``: params is one candidate, results have leading
    axis [T]. ``population=True``: params[C, ...] adds an outer candidate
    axis -> results [C, T] (fitness of every candidate on every trace from
    one program — the full config-4 matrix).

    Loop scaffold: the shared ``run_batched_lanes`` (one while_loop, cond
    = any of the chosen engine's ``lane_active``) over the (nested-)vmapped
    self-masking step, with the workload itself a traced vmap argument so
    one compiled program serves every same-shape trace.
    """
    from fks_tpu.sim.engine import run_batched_lanes

    mod = get_engine(engine)
    wl, kt, state0, max_steps = stack_traces(workloads, cfg, engine)

    def step_one(workload, ktable, params, s):
        return mod.build_step(
            workload, lambda pod, nodes: param_policy(params, pod, nodes),
            cfg, ktable, max_steps)(s)

    fin = lambda w, s: mod.finalize(w, cfg, s)  # noqa: E731

    def drive(vstep_bound, s0):
        return run_batched_lanes(vstep_bound, s0, max_steps,
                                 active_fn=mod.lane_active)

    if population:
        # lanes [C, T]: traces inner, candidates outer
        vstep = jax.vmap(jax.vmap(step_one, in_axes=(0, 0, None, 0)),
                         in_axes=(None, None, 0, 0))
        vfin = jax.vmap(jax.vmap(fin, in_axes=(0, 0)), in_axes=(None, 0))

        def eval_fn(params):
            pop = jax.tree_util.tree_leaves(params)[0].shape[0]
            final = drive(lambda s: vstep(wl, kt, params, s),
                          mod.broadcast_state(state0, pop))
            return vfin(wl, final)
    else:
        vstep = jax.vmap(step_one, in_axes=(0, 0, None, 0))
        vfin = jax.vmap(fin, in_axes=(0, 0))

        def eval_fn(params):
            final = drive(lambda s: vstep(wl, kt, params, s), state0)
            return vfin(wl, final)

    return jax.jit(eval_fn) if jit else eval_fn
