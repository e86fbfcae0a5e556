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
from fks_tpu.parallel.mesh import _pop_axes, _top_k_real, shard_population
from fks_tpu.parallel.population import ParamPolicyFn
from fks_tpu.parallel.traces import make_trace_batch_eval
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
                            elite_k: int = 8, engine: str = "exact"):
    """Build ``eval(params[C, ...], real_count) -> (robust[C],
    per_scenario[C, T], elite_idx[K], elite_scores[K])``: candidates
    sharded over the mesh's pop axes, each shard vmapping its chunk over
    candidates x scenarios, then ONE all-gather of the composite robust
    vector so every device ranks the identical robust elite set. Per-
    scenario scores stay shard-local (out_spec P(axes)) — only the
    aggregate crosses the interconnect, mirroring
    ``parallel.mesh.make_sharded_eval``'s traffic shape."""
    axes = _pop_axes(mesh)
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

    return jax.jit(sharded_eval)
