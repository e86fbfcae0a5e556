"""Policies for the plain reference.

- ``source_policy``: a candidate's source run as the Python function it
  is (``priority_function(pod, node)`` per node), in an environment
  holding only upstream's whitelisted builtins and ``math``. In
  ``dtype="float64"`` the fields are Python ints and the arithmetic is
  CPython's binary64, as upstream runs. In ``dtype="float32"``, what a
  configuration's ``guarantees.score_dtype`` states and the drivers pass,
  every field the source reads, and every number it gets from ``len``,
  ``sum``, ``int``, ``round`` or ``float``, is a ``numpy.float32``
  scalar: an operation with such an operand then rounds to float32, one
  rounding an operation in the source's own order, and arithmetic among
  the source's own literals stays Python's (NumPy's scalar rules; they
  are what the configuration's words mean: one float32 register model,
  constants folded before). The fields are integers under 2**24, exact in
  either type, so the two differ only where a score is rounded.
- ``parametric_policy``: the weight-vector policy (16 features, float32)
  written out in NumPy from its definition in the README/ROADMAP E1
  config: ``max(1, int(f . w * 10000))`` on feasible nodes, 0 elsewhere.
- ``first_fit`` / ``best_fit``: upstream's two baseline scorers, for the
  golden fixtures.

``low_precision=True`` is the CONTROL, never the reference: every score is
rounded to bfloat16 before ``int()``, the step a later PR would be tempted
by. The comparison has to fail it.
"""
from __future__ import annotations

import builtins
import math

import ml_dtypes
import numpy as np

from chipbench.reference.plain_sim import _feasible

F = np.float32
SCALE = F(10_000.0)


def _bf16(x):
    return float(np.asarray(x, np.float32).astype(ml_dtypes.bfloat16)
                 .astype(np.float32))


class _Data:
    """An entity as a float32 register model holds it: every field a
    ``numpy.float32``, a GPU list a list of such entities."""

    def __init__(self, obj):
        for name in obj.__slots__:
            v = getattr(obj, name)
            setattr(self, name,
                    [_Data(g) for g in v] if isinstance(v, list) else F(v))


def source_policy(code: str, low_precision: bool = False,
                  dtype: str = "float64"):
    if dtype not in ("float64", "float32"):
        raise ValueError(f"source_policy: no dtype {dtype!r}")
    env = {k: getattr(builtins, k) for k in (
        "abs", "min", "max", "sum", "len", "range", "enumerate", "int",
        "float", "bool", "str", "round", "sorted")}
    if low_precision:
        env["int"] = lambda x: int(_bf16(x))
    view = lambda obj: obj  # noqa: E731
    if dtype == "float32":
        # what these make of data is data: a count or a truncated score
        # that is divided next divides in float32, not as two Python ints
        for name in ("len", "sum", "int", "round", "float"):
            env[name] = (lambda fn: lambda *a: F(fn(*a)))(env[name])
        view = _Data
    glob = {"__builtins__": env, "math": math}
    exec(compile(code, "<candidate>", "exec"), glob)  # noqa: S102 — repo's own champions
    fn = glob["priority_function"]

    def policy(pod, state, cand):
        nodes, pod = state.nodes, view(pod)
        return [int(max(0, fn(pod, view(nodes[int(i)])))) for i in cand]

    return policy


def parametric_policy(weights, low_precision: bool = False):
    w = np.asarray(weights, F)
    d = ml_dtypes.bfloat16 if low_precision else F
    w = w.astype(d)

    def policy(pod, s, cand):
        c = s.c
        cand = np.asarray(cand)
        cl, ml, gl = s.cpu_left[cand], s.mem_left[cand], s.gpu_left[cand]
        gm = s.gpu_milli_left[cand]
        mask = c.gpu_mask[cand]
        cpu_tot = np.maximum(c.cpu_total[cand], 1).astype(d)
        mem_tot = np.maximum(c.mem_total[cand], 1).astype(d)
        ngpus = np.maximum(c.num_gpus[cand], 1).astype(d)
        milli_tot = np.maximum(
            np.where(mask, c.gpu_milli_total[cand], 0).sum(axis=1), 1
        ).astype(d)
        one = d(1)
        rem_cpu = (cl - pod.cpu_milli).astype(d) / cpu_tot
        rem_mem = (ml - pod.memory_mib).astype(d) / mem_tot
        rem_gpu = (gl - pod.num_gpu).astype(d) / ngpus
        cpu_util = one - cl.astype(d) / cpu_tot
        mem_util = one - ml.astype(d) / mem_tot
        gpu_count_util = one - gl.astype(d) / ngpus
        free = np.where(mask, gm, 0).sum(axis=1)
        gpu_milli_util = one - free.astype(d) / milli_tot
        balance = one - np.abs(cpu_util - mem_util)
        is_gpu = pod.num_gpu > 0
        zeros = np.zeros(len(cand), d)
        frag_mod = ((free % max(pod.gpu_milli, 1)).astype(d) / d(1000.0)
                    if is_gpu else zeros)
        eligible = (mask & (gm >= pod.gpu_milli)).sum(axis=1)
        eligible_frac = eligible.astype(d) / ngpus
        has_gpu = (c.num_gpus[cand] > 0).astype(d)
        best_fit = one - (rem_cpu * d(0.33) + rem_mem * d(0.33)
                          + rem_gpu * d(0.34))
        gmax = np.where(mask, gm, 0).max(axis=1)
        gmin = np.where(mask, gm, 2 ** 30).min(axis=1)
        imbalance = np.where(c.num_gpus[cand] > 0,
                             (gmax - np.minimum(gmin, gmax)).astype(d)
                             / d(1000.0), zeros)
        headroom = ((cl > pod.cpu_milli * 2)
                    & (ml > pod.memory_mib * 2)).astype(d)
        ones = np.ones(len(cand), d)
        f = np.stack([
            ones, rem_cpu, rem_mem, rem_gpu, cpu_util, mem_util,
            gpu_count_util, gpu_milli_util, balance, frag_mod,
            eligible_frac, ones if is_gpu else zeros, has_gpu, best_fit,
            imbalance, headroom], axis=1).astype(d)
        raw = (f @ w).astype(d) * d(10_000.0)
        as_int = np.trunc(raw.astype(np.float64)).astype(np.int64)
        feas = _feasible(pod, s)[cand]
        return np.where(feas, np.maximum(1, as_int), 0)

    return policy


def first_fit(pod, state, cand):
    feas = _feasible(pod, state)
    return [1000 if feas[int(i)] else 0 for i in cand]


def best_fit(pod, state, cand):
    """Upstream's best-fit: ``int((1 - weighted normalised remaining) *
    10000)``, at least 1, on feasible nodes (float64, as upstream runs)."""
    feas = _feasible(pod, state)
    out = []
    for i in cand:
        nd = state.nodes[int(i)]
        if not feas[int(i)]:
            out.append(0)
            continue
        norm = ((nd.cpu_milli_left - pod.cpu_milli) / nd.cpu_milli_total * 0.33
                + (nd.memory_mib_left - pod.memory_mib) / nd.memory_mib_total * 0.33
                + (nd.gpu_left - pod.num_gpu) / max(len(nd.gpus), 1) * 0.34)
        out.append(max(1, int((1 - norm) * 10000)))
    return out
