"""Population-parallel fitness evaluation: ``vmap`` over candidates.

TPU-native replacement for the reference's "distributed backend" — a
``ProcessPoolExecutor`` that forks one subprocess per candidate policy,
re-parses the trace CSVs, deep-copies cluster state, and runs the pure-Python
simulator (reference: funsearch/funsearch_integration.py:30-64, 535-562).
Here the whole population is ONE compiled XLA program: the trace lives on
device once, the initial state is broadcast (never copied per candidate),
and the event loop runs for all candidates in lockstep under ``vmap``.

Two candidate representations are supported:
- **parametric** (this module's fast path): candidate = weight vector,
  population = ``params[C, F]``, evaluated by the vmapped self-masking
  step inside ONE while_loop (engine.make_population_run_fn — not
  ``vmap(while_loop)``, which would full-carry-select every lane each
  event to freeze finished candidates).
- **compiled code** (general path): candidates from the LLM transpiler are
  distinct computations; they batch by Python loop over per-code jitted runs
  with an AST-keyed compile cache (fks_tpu.funsearch.backend).
"""
from __future__ import annotations

from typing import Callable

import jax

from fks_tpu.data.entities import Workload
from fks_tpu.models import parametric
from fks_tpu.sim.engine import (
    SimConfig, initial_state, make_param_run_fn, make_population_run_fn,
)
from fks_tpu.sim.types import NodeView, PodView, SimResult

# A parameterized policy: (params, PodView, NodeView) -> i32[N] scores.
ParamPolicyFn = Callable[[jax.Array, PodView, NodeView], jax.Array]

# Loop assembly (ktable/cond/while/finalize) is shared with the single-policy
# path via the engine, so batched and plain fitness cannot diverge.
make_single_run = make_param_run_fn


def lead_axis_size(tree) -> int:
    """Leading-axis length of a batched pytree — the candidate count of a
    population batch or the lane count of a coalesced serve batch. The
    one definition shared by the mesh padder/sharder and the serve tier,
    so "what is the batch axis" cannot drift between them."""
    return jax.tree_util.tree_leaves(tree)[0].shape[0]


#: engine names served by the fused Pallas kernel. "fused" compiles under
#: Mosaic (TPU only); "fused_interpret" is the same kernel in the Pallas
#: interpreter — slow, for correctness tests on hosts without a TPU.
FUSED_ENGINES = ("fused", "fused_interpret")


def fused_runner(workload: Workload, param_policy, cfg: SimConfig,
                 engine: str = "fused", lanes: int = 64):
    """The ONE dispatch point for the fused Pallas engine (shared by the
    vmap path here and the shard_map path in fks_tpu.parallel.mesh, so the
    fused contract cannot drift between them). The kernel hard-wires the
    parametric feature basis, so any other policy is rejected. ``lanes``
    caps the per-grid-step chunk (the kernel auto-shrinks it to the VMEM
    budget). Interpret mode is never inferred from the backend: it runs
    only under the engine name that asks for it."""
    if param_policy is not parametric.score:
        raise ValueError("engine='fused' hard-wires the parametric feature "
                         "basis; pass param_policy=parametric.score or use "
                         "engine='flat'")
    from fks_tpu.sim import fused
    return fused.make_fused_population_run(
        workload, cfg, lanes=lanes, interpret=engine == "fused_interpret")


def make_population_eval(workload: Workload,
                         param_policy: ParamPolicyFn = parametric.score,
                         cfg: SimConfig = SimConfig(),
                         jit: bool = True,
                         engine: str = "exact"):
    """Build ``eval(params[C, ...]) -> SimResult`` batched over candidates.

    The reference's per-candidate subprocess fan-out collapsed into one
    compiled program: all candidates advance in lockstep through the
    while_loop; a candidate that finishes early (fewer retries) idles as
    dropped scatters until the slowest lane drains its queue.

    ``engine``: "exact" replicates the reference bit-for-bit (heap replica,
    layout-dependent retry rule); "flat" is the TPU throughput engine
    (fks_tpu.sim.flat — identical semantics except the documented
    retry-time rule; ~an order of magnitude faster per step on TPU);
    "fused" is the Pallas whole-loop-in-VMEM kernel (fks_tpu.sim.fused —
    flat semantics, parametric policies ONLY: ``param_policy`` must be
    the default ``parametric.score``; "fused_interpret" runs the same
    kernel in the Pallas interpreter for hosts without a TPU).
    """
    if engine in FUSED_ENGINES:
        run = fused_runner(workload, param_policy, cfg, engine)
        # jit covers run()'s XLA-side pre/post work (padding, aux decode,
        # finalize) around the pallas_call
        return jax.jit(run) if jit else run

    from fks_tpu.sim import get_engine
    mod = get_engine(engine)
    run = mod.make_population_run_fn(workload, param_policy, cfg)
    state0 = mod.initial_state(workload, cfg)

    def population_eval(params):
        return run(params, state0)

    return jax.jit(population_eval) if jit else population_eval


def fitness(result: SimResult) -> jax.Array:
    """The scalar the evolution loop ranks on (reference evaluator.py:101-127
    semantics are already folded into ``policy_score`` by the engine)."""
    return result.policy_score
