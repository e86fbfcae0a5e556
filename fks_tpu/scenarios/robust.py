"""Robust fitness: evaluate candidates over a scenario suite in one call.

A suite's workloads share one padded shape (suite.py pins the fault pad),
so the whole suite rides the existing multi-trace machinery
(``parallel.traces.make_trace_batch_eval``): ONE vmapped device program
evaluates a candidate on every scenario — fault-injected variants
included — instead of T sequential single-trace runs. On a mesh the
candidate axis additionally shards over the pop axes exactly like
``parallel.mesh.make_sharded_eval``, and elite selection ranks the
COMPOSITE robust score, not any single trace's fitness.

Aggregations (host-static choice, folded over the trailing scenario axis):

- ``mean`` — (optionally weighted) average; the E[fitness] estimate.
- ``min``  — worst case; a candidate is only as good as its worst scenario.
- ``cvar`` — CVaR-α: mean of the worst ``ceil(α·T)`` scenarios; tail risk
  without min's single-outlier brittleness.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from fks_tpu.models import parametric
from fks_tpu.parallel.mesh import (
    SCN_AXIS, _pop_axes, _resolve_layout, _top_k_real, shard_population,
)
from fks_tpu.parallel.population import ParamPolicyFn
from fks_tpu.parallel.traces import make_trace_batch_eval, stack_traces
from fks_tpu.scenarios.suite import ScenarioSuite
from fks_tpu.sim.engine import SimConfig

AGGREGATIONS = ("mean", "min", "cvar")


@dataclasses.dataclass(frozen=True)
class RobustConfig:
    """How per-scenario fitness folds into one robust score."""

    aggregation: str = "mean"
    cvar_alpha: float = 0.25  # tail fraction for aggregation="cvar"
    weights: Optional[Tuple[float, ...]] = None  # aggregation="mean" only

    def __post_init__(self):
        if self.aggregation not in AGGREGATIONS:
            raise ValueError(f"unknown aggregation {self.aggregation!r}; "
                             f"choose from {AGGREGATIONS}")
        if not (0.0 < self.cvar_alpha <= 1.0):
            raise ValueError(f"cvar_alpha {self.cvar_alpha} not in (0, 1]")
        if self.weights is not None and self.aggregation != "mean":
            raise ValueError("weights only apply to aggregation='mean'")

    def describe(self) -> dict:
        return dataclasses.asdict(self)


def aggregate(scores, rc: RobustConfig = RobustConfig()):
    """Fold per-scenario scores (TRAILING axis) into the robust score.
    jit/vmap-safe: the aggregation choice and CVaR tail size are host
    constants, only the scores are traced."""
    scores = jnp.asarray(scores)
    if rc.aggregation == "mean":
        if rc.weights is not None:
            w = jnp.asarray(rc.weights, scores.dtype)
            if w.shape[0] != scores.shape[-1]:
                raise ValueError(
                    f"{w.shape[0]} weights for {scores.shape[-1]} scenarios")
            return jnp.sum(scores * w, axis=-1) / jnp.sum(w)
        return jnp.mean(scores, axis=-1)
    if rc.aggregation == "min":
        return jnp.min(scores, axis=-1)
    # cvar: mean of the worst ceil(alpha * T) scenarios
    k = max(1, int(np.ceil(rc.cvar_alpha * scores.shape[-1])))
    return jnp.mean(jnp.sort(scores, axis=-1)[..., :k], axis=-1)


def make_suite_eval(suite: ScenarioSuite,
                    param_policy: ParamPolicyFn = parametric.score,
                    cfg: SimConfig = SimConfig(),
                    population: bool = False,
                    jit: bool = True,
                    engine: str = "exact"):
    """``eval(params) -> SimResult`` over the suite's scenario axis: result
    leaves are [T] (one candidate) or [C, T] (``population=True``) with
    T = len(suite). Thin delegation to the multi-trace batcher — a suite
    IS a same-shape trace batch, faults included."""
    return make_trace_batch_eval(
        list(suite.workloads), param_policy=param_policy, cfg=cfg,
        population=population, jit=jit, engine=engine)


def make_sharded_suite_eval(suite: ScenarioSuite, mesh: Mesh,
                            param_policy: ParamPolicyFn = parametric.score,
                            cfg: SimConfig = SimConfig(),
                            rc: RobustConfig = RobustConfig(),
                            elite_k: int = 8, engine: str = "exact",
                            layout=None):
    """Build ``eval(params[C, ...], real_count) -> (robust[C],
    per_scenario[C, T], elite_idx[K], elite_scores[K])``: candidates
    sharded over the mesh's pop axes, each shard vmapping its chunk over
    candidates x scenarios, then ONE all-gather of the composite robust
    vector so every device ranks the identical robust elite set. Per-
    scenario scores stay shard-local (out_spec P(axes)) — only the
    aggregate crosses the interconnect, mirroring
    ``parallel.mesh.make_sharded_eval``'s traffic shape.

    ``layout`` (fks_tpu.obs.layout.LayoutSpec) may additionally shard the
    SCENARIO axis: on a 2-D ``layout_mesh`` each device then evaluates a
    (candidate chunk x scenario chunk) tile, the per-scenario scores
    all-gather over the inner "scn" axis before aggregation, and the
    robust gather crosses candidate shards exactly as before. The suite
    length must divide the scenario shard count's mesh extent (scenario
    suites are authored, never remainder-padded). ``layout=None`` is the
    default candidates-only spec — the behavior above, lowered
    bit-identically (jaxpr-pinned). Wiring and every launch land
    ``layout_ledger`` rows (component "suite_eval")."""
    from fks_tpu.obs.layout import record_layout, tag_layout

    spec = _resolve_layout(layout, scenarios=True, scenario_shardable=True)
    axes = _pop_axes(mesh)
    scn_shards = int(mesh.shape.get(SCN_AXIS, 1))
    if "scenarios" in spec.shard:
        if scn_shards <= 1:
            raise ValueError(
                f"layout {spec.key!r} shards scenarios but the mesh has "
                f"no '{SCN_AXIS}' axis — build one with "
                "parallel.mesh.layout_mesh(devices, scenario_shards)")
        if len(suite) % scn_shards:
            raise ValueError(
                f"suite of {len(suite)} scenarios does not divide into "
                f"{scn_shards} scenario shards")
        shard_eval = _scenario_sharded_suite_eval(
            suite, mesh, param_policy, cfg, rc, elite_k, engine, axes)
    elif scn_shards > 1:
        raise ValueError(
            f"mesh has a {scn_shards}-way '{SCN_AXIS}' axis but layout "
            f"{spec.key!r} does not shard scenarios")
    else:
        inner = make_trace_batch_eval(
            list(suite.workloads), param_policy=param_policy, cfg=cfg,
            population=True, jit=False, engine=engine)

        @functools.partial(
            jax.shard_map, mesh=mesh,
            in_specs=(P(axes), P()),
            out_specs=(P(axes), P(axes), P(), P()),
            check_vma=False,
        )
        def shard_eval(params_shard, real_count):
            res = inner(params_shard)          # leaves [C/shards, T]
            per = res.policy_score
            robust = aggregate(per, rc)
            global_robust = jax.lax.all_gather(robust, axes, tiled=True)
            elite_scores, elite_idx = _top_k_real(global_robust, real_count,
                                                  elite_k)
            return robust, per, elite_idx, elite_scores

    def sharded_eval(params, real_count=None):
        params = shard_population(params, mesh)
        if real_count is None:
            real_count = jax.tree_util.tree_leaves(params)[0].shape[0]
        return shard_eval(params, jnp.asarray(real_count, jnp.int32))

    jitted = jax.jit(sharded_eval)
    record_layout("suite_eval", spec, mesh=mesh)

    def run(params, real_count=None):
        from fks_tpu.parallel.population import lead_axis_size
        real = (lead_axis_size(params) if real_count is None
                else int(real_count))
        record_layout("suite_eval", spec, mesh=mesh, real_count=real,
                      scenarios=len(suite))
        return jitted(params, real_count)

    run.lower = jitted.lower
    run._fks_jitted = jitted
    return tag_layout(run, spec.key)


def _scenario_sharded_suite_eval(suite, mesh, param_policy, cfg, rc,
                                 elite_k, engine, axes):
    """The scenario-sharded body of ``make_sharded_suite_eval``: the
    stacked suite pytrees (workload[T,...], ktable[T,K], state0[T,...])
    become shard_map ARGUMENTS split over the inner "scn" axis — the
    same arrays ``make_trace_batch_eval`` closes over on the default
    path — so each device drives its own scenario chunk through the
    shared ``run_batched_lanes`` while_loop. Per-scenario scores gather
    over "scn" (one [C_local, T] tile per device) before the host-static
    aggregation, so the robust fold sees the full scenario axis and the
    elite ranking is layout-invariant (parity-gated at 1e-5 by
    tools/run_full_suite's layout_gate). layout-exempt: the enclosing
    ``make_sharded_suite_eval`` resolves the spec and tags/records the
    runner it wraps around this body."""
    from fks_tpu.sim import get_engine
    from fks_tpu.sim.engine import run_batched_lanes

    mod = get_engine(engine)
    wl, kt, state0, max_steps = stack_traces(list(suite.workloads), cfg,
                                             engine)

    def step_one(workload, ktable, params, s):
        return mod.build_step(
            workload, lambda pod, nodes: param_policy(params, pod, nodes),
            cfg, ktable, max_steps)(s)

    vstep = jax.vmap(jax.vmap(step_one, in_axes=(0, 0, None, 0)),
                     in_axes=(None, None, 0, 0))
    vfin = jax.vmap(jax.vmap(lambda w, s: mod.finalize(w, cfg, s),
                             in_axes=(0, 0)), in_axes=(None, 0))

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P(axes), P(), P(SCN_AXIS), P(SCN_AXIS), P(SCN_AXIS)),
        out_specs=(P(axes), P(axes, SCN_AXIS), P(), P()),
        check_vma=False,
    )
    def shard_eval_args(params_shard, real_count, wl_s, kt_s, s0_s):
        pop = jax.tree_util.tree_leaves(params_shard)[0].shape[0]
        final = run_batched_lanes(
            lambda s: vstep(wl_s, kt_s, params_shard, s),
            mod.broadcast_state(s0_s, pop), max_steps,
            active_fn=mod.lane_active)
        res = vfin(wl_s, final)
        per = res.policy_score                    # [C_local, T_local]
        per_full = jax.lax.all_gather(per, SCN_AXIS, axis=1, tiled=True)
        robust = aggregate(per_full, rc)          # [C_local]
        global_robust = jax.lax.all_gather(robust, axes, tiled=True)
        elite_scores, elite_idx = _top_k_real(global_robust, real_count,
                                              elite_k)
        return robust, per, elite_idx, elite_scores

    def shard_eval(params, real_count):
        return shard_eval_args(params, real_count, wl, kt, state0)

    return shard_eval
