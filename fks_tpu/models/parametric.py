"""Parametric scheduling policies: a fixed feature basis + weight vector.

This is the TPU fast path for population evaluation (SURVEY.md §7 key design
moves): where the reference evaluates each candidate policy as arbitrary
Python code in its own subprocess (reference: funsearch/funsearch_integration.py
:30-64, 535-562), a *parametric* candidate is just a weight vector over a
fixed library of placement features. The whole population then evaluates as
ONE ``vmap`` over the weight axis — a single XLA program, no per-candidate
compilation — and shards across a TPU mesh along the population axis
(fks_tpu.parallel).

Arbitrary LLM-generated code still works through the general path
(fks_tpu.funsearch.transpiler); this module is the throughput backbone and
the search space for gradient-free evolution (mutation = Gaussian jitter on
weights).

Score contract matches the reference policy shape (reference:
funsearch/safe_execution.py:174-224 template): infeasible nodes score 0;
feasible nodes score ``max(1, int(raw))`` so they are never refused.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from fks_tpu.models.zoo import feasible_mask
from fks_tpu.sim.types import NodeView, PodView, PolicyFn

#: Names of the feature basis, in order. Keep appended-only: persisted
#: checkpoints store weights positionally.
FEATURE_NAMES = (
    "bias",
    "rem_cpu_frac",      # (cpu_left - pod.cpu) / cpu_total
    "rem_mem_frac",      # (mem_left - pod.mem) / mem_total
    "rem_gpu_frac",      # (gpu_left - pod.ngpu) / num_gpus
    "cpu_util",          # used fraction before placement
    "mem_util",
    "gpu_count_util",
    "gpu_milli_util",    # node-level milli used fraction
    "balance",           # 1 - |cpu_util - mem_util|
    "frag_mod",          # (free_milli % pod.gpu_milli) / 1000, gpu pods only
    "eligible_frac",     # eligible GPUs / num_gpus for this pod
    "pod_is_gpu",
    "node_has_gpu",
    "best_fit",          # 1 - weighted normalized remaining (zoo best_fit core)
    "gpu_imbalance",     # (max - min free milli) / 1000
    "headroom",          # 1 if node has > 2x the pod's cpu AND mem free
)

NUM_FEATURES = len(FEATURE_NAMES)

#: Raw dot product is scaled by this before int truncation, so weights of
#: order 1 produce score magnitudes comparable to the reference zoo (~1e4).
SCORE_SCALE = 10_000.0


def _fold_gpus(op, x):
    """``op`` folded over the GPU planes ``x[:, g]`` of an ``[N, G]`` array.

    G is static and small (8), and the fold is what the reduction IS in
    integer arithmetic, so the result is ``jnp.sum / max / min(x, axis=1)``
    bit for bit. Written this way no XLA ``reduce`` runs along the GPU
    axis, and under a population ``vmap`` the compiler is free to lay
    ``[lanes, N, G]`` out with the lanes, not the 8 GPUs, on the chip's 128
    vector lanes (PERF.md section 6, PR 41)."""
    return functools.reduce(op, [x[:, g] for g in range(x.shape[1])])


def features(pod: PodView, nodes: NodeView, dtype=jnp.float32):
    """Feature matrix f[N, F] for one pod against all nodes."""
    d = dtype
    cpu_tot = jnp.maximum(nodes.cpu_milli_total, 1).astype(d)
    mem_tot = jnp.maximum(nodes.memory_mib_total, 1).astype(d)
    ngpus = jnp.maximum(nodes.num_gpus, 1).astype(d)
    milli_tot = jnp.maximum(_fold_gpus(
        jnp.add, jnp.where(nodes.gpu_mask, nodes.gpu_milli_total, 0)), 1
    ).astype(d)

    rem_cpu = (nodes.cpu_milli_left - pod.cpu_milli).astype(d) / cpu_tot
    rem_mem = (nodes.memory_mib_left - pod.memory_mib).astype(d) / mem_tot
    rem_gpu = (nodes.gpu_left - pod.num_gpu).astype(d) / ngpus
    cpu_util = 1 - nodes.cpu_milli_left.astype(d) / cpu_tot
    mem_util = 1 - nodes.memory_mib_left.astype(d) / mem_tot
    gpu_count_util = 1 - nodes.gpu_left.astype(d) / ngpus

    milli_left = jnp.where(nodes.gpu_mask, nodes.gpu_milli_left, 0)
    free_milli = _fold_gpus(jnp.add, milli_left)
    gpu_milli_util = 1 - free_milli.astype(d) / milli_tot

    balance = 1 - jnp.abs(cpu_util - mem_util)
    pod_gpu = pod.num_gpu > 0
    frag_mod = jnp.where(
        pod_gpu, (free_milli % jnp.maximum(pod.gpu_milli, 1)).astype(d) / 1000.0, 0.0)
    eligible = _fold_gpus(jnp.add, (
        nodes.gpu_mask & (nodes.gpu_milli_left >= pod.gpu_milli)
    ).astype(jnp.int32))
    eligible_frac = eligible.astype(d) / ngpus
    node_has_gpu = (nodes.num_gpus > 0).astype(d)
    best_fit = 1 - (rem_cpu * 0.33 + rem_mem * 0.33 + rem_gpu * 0.34)
    gmax = _fold_gpus(jnp.maximum, milli_left)
    gmin = _fold_gpus(
        jnp.minimum, jnp.where(nodes.gpu_mask, nodes.gpu_milli_left, 2**30))
    gpu_imbalance = jnp.where(
        nodes.num_gpus > 0, (gmax - jnp.minimum(gmin, gmax)).astype(d) / 1000.0, 0.0)
    headroom = ((nodes.cpu_milli_left > pod.cpu_milli * 2)
                & (nodes.memory_mib_left > pod.memory_mib * 2)).astype(d)

    ones = jnp.ones_like(rem_cpu)
    return jnp.stack([
        ones, rem_cpu, rem_mem, rem_gpu, cpu_util, mem_util, gpu_count_util,
        gpu_milli_util, balance, frag_mod, eligible_frac,
        jnp.where(pod_gpu, ones, 0.0), node_has_gpu, best_fit, gpu_imbalance,
        headroom,
    ], axis=1)


def score(params, pod: PodView, nodes: NodeView, dtype=jnp.float32):
    """Parametric policy: ``max(1, int(f @ w * SCALE))`` under feasibility.

    ``params`` is f[F] (or any leading batch dims handled by an outer vmap).
    """
    f = features(pod, nodes, dtype)
    raw = f @ params.astype(dtype) * SCORE_SCALE
    as_int = jnp.trunc(raw).astype(jnp.int32)
    return jnp.where(feasible_mask(pod, nodes), jnp.maximum(1, as_int), 0)


def as_policy(params, dtype=jnp.float32) -> PolicyFn:
    """Close over a concrete weight vector -> a zoo-compatible PolicyFn."""
    return lambda pod, nodes: score(params, pod, nodes, dtype)


# ----------------------------------------------------------- seed weights

def seed_weights(name: str):
    """Hand-picked weight vectors reproducing the spirit (not the bit-exact
    arithmetic) of the reference baseline factories
    (reference: funsearch_integration.py:217-269)."""
    w = {n: 0.0 for n in FEATURE_NAMES}
    if name == "first_fit":
        w["bias"] = 0.1  # constant 1000 for every feasible node
    elif name == "best_fit":
        w["best_fit"] = 1.0
    elif name == "worst_fit":
        w["best_fit"] = -1.0
        w["bias"] = 1.0
    elif name == "packing":
        w["best_fit"] = 0.6
        w["gpu_milli_util"] = 0.3
        w["frag_mod"] = -0.2
        w["balance"] = 0.1
    else:
        raise KeyError(name)
    return jnp.asarray([w[n] for n in FEATURE_NAMES], jnp.float32)


def init_population(key, pop_size: int, noise: float = 0.1):
    """Seeds + Gaussian jitter: the t=0 population for parametric evolution."""
    seeds = jnp.stack([seed_weights(n)
                       for n in ("first_fit", "best_fit", "worst_fit", "packing")])
    reps = (pop_size + seeds.shape[0] - 1) // seeds.shape[0]
    base = jnp.tile(seeds, (reps, 1))[:pop_size]
    jitter = noise * jax.random.normal(key, base.shape, base.dtype)
    keep = jnp.arange(pop_size) < seeds.shape[0]  # keep the seeds themselves pure
    return jnp.where(keep[:, None], base, base + jitter)


def mutate(key, parents, pop_size: int, noise: float = 0.05):
    """Offspring = random parent + Gaussian noise (gradient-free step)."""
    k1, k2 = jax.random.split(key)
    idx = jax.random.randint(k1, (pop_size,), 0, parents.shape[0])
    base = parents[idx]
    return base + noise * jax.random.normal(k2, base.shape, base.dtype)


# ------------------------------------------------- weights -> candidate code

#: Restricted-Python rendering of each feature, in FEATURE_NAMES order.
#: The expressions use only the transpilable subset (and the reference's
#: whitelisted builtins, safe_execution.py:19-27), so a rendered candidate
#: flows through the normal code path: sandbox -> transpiler -> engine.
_FEATURE_EXPRS = (
    "1.0",
    "(node.cpu_milli_left - pod.cpu_milli) / max(1, node.cpu_milli_total)",
    "(node.memory_mib_left - pod.memory_mib) / max(1, node.memory_mib_total)",
    "(node.gpu_left - pod.num_gpu) / max(1, len(node.gpus))",
    "1.0 - node.cpu_milli_left / max(1, node.cpu_milli_total)",
    "1.0 - node.memory_mib_left / max(1, node.memory_mib_total)",
    "1.0 - node.gpu_left / max(1, len(node.gpus))",
    "1.0 - free_milli / max(1, total_milli)",
    "1.0 - abs(node.cpu_milli_left / max(1, node.cpu_milli_total)"
    " - node.memory_mib_left / max(1, node.memory_mib_total))",
    "((free_milli % max(1, pod.gpu_milli)) / 1000.0) if pod.num_gpu > 0 else 0.0",
    "sum(1 for gpu in node.gpus if gpu.gpu_milli_left >= pod.gpu_milli)"
    " / max(1, len(node.gpus))",
    "1.0 if pod.num_gpu > 0 else 0.0",
    "1.0 if len(node.gpus) > 0 else 0.0",
    "1.0 - (0.33 * (node.cpu_milli_left - pod.cpu_milli) / max(1, node.cpu_milli_total)"
    " + 0.33 * (node.memory_mib_left - pod.memory_mib) / max(1, node.memory_mib_total)"
    " + 0.34 * (node.gpu_left - pod.num_gpu) / max(1, len(node.gpus)))",
    "((max(gpu.gpu_milli_left for gpu in node.gpus)"
    " - min(gpu.gpu_milli_left for gpu in node.gpus)) / 1000.0)"
    " if len(node.gpus) > 0 else 0.0",
    "1.0 if (node.cpu_milli_left > 2 * pod.cpu_milli"
    " and node.memory_mib_left > 2 * pod.memory_mib) else 0.0",
)

#: features whose expression reads the free/total gpu_milli prologue vars
_NEEDS_MILLI = {"gpu_milli_util", "frag_mod"}


def render_code(params, threshold: float = 1e-4) -> str:
    """Render a weight vector as a reference-style candidate SOURCE — the
    bridge from the device-resident parametric search back into the code
    population: the rendered candidate re-enters through the normal
    sandbox/transpiler/dedup pipeline and is re-scored there, so rendering
    need not be bit-exact to the f32 on-device arithmetic (and is not).

    Near-zero weights are dropped to keep candidates short and readable.
    """
    import numpy as np

    from fks_tpu.funsearch import template

    w = np.asarray(params, np.float64)
    terms = []
    needs_milli = False
    for name, expr, wi in zip(FEATURE_NAMES, _FEATURE_EXPRS, w):
        if abs(wi) < threshold:
            continue
        terms.append(f"({wi:.6g}) * ({expr})")
        if name in _NEEDS_MILLI:
            needs_milli = True
    if not terms:
        terms = ["0.0"]
    lines = []
    if needs_milli:
        lines.append("free_milli = sum(gpu.gpu_milli_left for gpu in node.gpus)")
        lines.append(
            "total_milli = sum(gpu.gpu_milli_total for gpu in node.gpus)")
    body = "\n    + ".join(terms)
    lines.append(f"score = {SCORE_SCALE:.1f} * ({body})")
    return template.fill_template("\n".join("    " + l if i else l
                                            for i, l in enumerate(lines)))
