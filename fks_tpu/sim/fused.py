"""Fused Pallas TPU kernel: the whole parametric-population simulation in
ONE kernel, state resident in VMEM.

Why: the XLA flat engine (fks_tpu.sim.flat) is a while_loop whose body is
~3 fused HBM passes over the [lanes, Q] queue arrays per event — measured
bandwidth-bound at ~110 us/step for 256 lanes on a v5e chip (PROFILE.md).
Every one of those bytes moves HBM<->VMEM each step because XLA keeps
while_loop carries in HBM. The queue for 64 lanes is ~4 MB — it FITS in
VMEM (128 MiB per v5e core; the kernel raises Mosaic's 16 MiB default
scoped limit). This kernel keeps it there: the full event loop runs
inside one ``pl.pallas_call``, so per-step traffic is zero HBM bytes and
the step cost is pure VPU/MXU work on resident arrays.

Semantics are the flat engine's, exactly (same pop order via tie-rank slot
ordering, same retry rule, same evaluator arithmetic — see
fks_tpu/sim/flat.py); the policy is the parametric feature-basis model
(fks_tpu/models/parametric.py), hard-wired so the feature pipeline fuses
into the step. Arbitrary-code candidates (VM / per-candidate jit tiers)
stay on the XLA engines.

Kernel shape notes (Mosaic/TPU constraints):
- per-lane scalars are [L, 1] columns; iotas via ``broadcasted_iota``
  (1-D iota does not lower on TPU);
- the popped pod's feature row is fetched with an MXU one-hot matmul
  ``mask_f32 [L,Q] @ feat_f32 [Q,8]`` at HIGHEST precision — exact
  because every pod feature value is < 2**24 (asserted at build time) and
  no operand is rounded to bf16; aux (which can exceed 2**24
  once node/gpu bits are packed) is fetched with an integer masked reduce;
- the GPU best-fit sub-allocation is G static rounds of lexicographic
  min-picking over the winner node's [L, N, G] milli row — same
  (milli, slot) order as ops/allocator.best_fit_gpus;
- grid = population chunks of ``lanes`` candidates; each grid step runs
  its chunk's whole simulation start-to-finish in VMEM.

Limits (asserted, with the XLA flat engine as the general fallback):
packed aux encoding must fit (node_bits + G <= 31), best_fit allocator,
no invariant audit, float32 scoring, and VMEM has to hold ~5 [L, Q] i32
arrays plus the [L, N, G] grids (small-N workloads; the default trace's
16x8 node grid is ideal). ``SimResult.pod_ctime`` reports the original
creation times (the throughput paths run ``track_ctime=False`` anyway).
"""
from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fks_tpu.data.entities import Workload
from fks_tpu.models.parametric import NUM_FEATURES, SCORE_SCALE
from fks_tpu.sim.engine import SimConfig, finalize_fields, loop_tables
from fks_tpu.sim.flat import (
    AUX_FRESH, AUX_WAITING, INF, _decode_assignment, _FinalView, _packable,
    _rank_perm,
)
from fks_tpu.sim.types import SimResult

_BIG = 2**30
_EXACT_F32 = 1 << 24  # one-hot matmul gathers are exact below this

#: What the lane plan may fill with the arrays the kernel names, and the
#: scoped-VMEM limit the kernel asks Mosaic for. The two differ because the
#: plan cannot see Mosaic's own allocations — the pipeline's double-buffered
#: output blocks, the step's [L, Q] temporaries, the HIGHEST-precision
#: matmul's passes: at the plan's 12.8 MB (64 lanes, default trace) the
#: compiler asked for 16.8 MB, over its 16 MiB default limit. A v5e core
#: has 128 MiB of VMEM.
_VMEM_PLAN_BYTES = 14 * 2**20
_VMEM_LIMIT_BYTES = 48 * 2**20


def _iota(shape, dim):
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim)


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


# [L, N, G] -> per-lane reductions that never pass through a rank-1
# vector (Mosaic's layout inference aborts on a reshape of one): reduce
# the minor axis first, then the node axis with keepdims.
def _sum_ng(x):
    return jnp.sum(jnp.sum(x, axis=2, dtype=jnp.int32), axis=1,
                   keepdims=True, dtype=jnp.int32)            # [L,1]


def _min_ng(x):
    return jnp.min(jnp.min(x, axis=2, keepdims=True), axis=1,
                   keepdims=True)                              # [L,1,1]


class _Plan(NamedTuple):
    """Static geometry + host-prepared constants for the kernel."""

    q: int            # slot count (p_padded rounded up to 128)
    n: int
    g: int
    hist: int
    klen: int
    max_steps: int
    pending0: int
    node_bits: int
    ev0: Any          # i32[1, q] initial slot times (tie-rank order)
    feat_f: Any       # f32[8, q] pod features (cpu, mem, ngpu, milli, dur)
                      # transposed: [q, 8] would tile-pad to 128 lanes (4 MB)
    ktable: Any       # i32[1, K]
    nrow: Any         # i32[6, n]: cpu_tot, mem_tot, gpu_declared, num_gpus,
                      #            node_mask, milli_tot(per node)
    gmt: Any          # i32[n, g] per-GPU milli totals
    gmask: Any        # i32[n, g]
    totals: tuple     # (total_cpu, total_mem, total_gc, total_gm) python ints


def _build_plan(workload: Workload, cfg: SimConfig) -> _Plan:
    c, p = workload.cluster, workload.pods
    n, g, pp = c.n_padded, c.g_padded, p.p_padded
    if workload.snapshot is not None:
        raise ValueError(
            "snapshot: flat engine only (the fused kernel builds its own "
            "initial state); use engine='flat'")
    if workload.typed:
        raise ValueError(
            "gpu_spec: GPU-type constraints are not supported in the fused "
            "kernel (its fixed-function sweep has no per-pod node mask); "
            "use engine='flat' or 'exact', or parse the workload without "
            "gpu_spec='honor'")
    if not _packable(n, g):
        raise ValueError("fused kernel needs packed aux (node_bits+G<=31); "
                         "use the XLA flat engine")
    if cfg.gpu_allocator != "best_fit":
        raise ValueError("fused kernel implements best_fit only")
    if cfg.validate_invariants:
        raise ValueError("invariant audit is not supported in the fused "
                         "kernel; use engine='flat'")
    if cfg.decision_trace:
        raise ValueError("decision trace is not supported in the fused "
                         "kernel; replay with engine='exact' or 'flat' "
                         "(fks_tpu.funsearch.tracing / cli trace-diff)")
    if cfg.probe_score:
        raise ValueError("budget probe rungs (SimConfig.probe_score, "
                         "fks_tpu.funsearch.budget) are not supported in "
                         "the fused kernel; run budget-pruned suite "
                         "evaluation with engine='exact' or 'flat'")
    if workload.faults is not None:
        raise ValueError("fault-injected workloads (fks_tpu.scenarios "
                         "NODE_DOWN/NODE_UP events) are not supported in "
                         "the fused kernel; evaluate scenario suites with "
                         "engine='exact' or 'flat'")
    if cfg.node_prefilter_k:
        raise ValueError("top-k node prefiltering (SimConfig."
                         "node_prefilter_k) is not supported in the fused "
                         "kernel — its fixed-function policy already "
                         "sweeps nodes in one fused pass; use "
                         "engine='flat' for the large-cluster scale tier")
    if cfg.state_pack:
        raise ValueError("packed state dtypes (SimConfig.state_pack) are "
                         "not supported in the fused kernel; use "
                         "engine='flat' for the large-cluster scale tier")
    q = _round_up(pp, 128)

    pm = np.asarray(p.pod_mask)
    perm = _rank_perm(pm, np.asarray(p.tie_rank))
    r_mask = pm[perm]
    ev0 = np.where(r_mask, np.asarray(p.creation_time)[perm], INF)
    ev0 = np.pad(ev0, (0, q - pp), constant_values=INF).astype(np.int32)

    feat = np.zeros((8, q), np.float32)
    for k, arr in enumerate((p.cpu, p.mem, p.num_gpu, p.gpu_milli,
                             p.duration)):
        col = np.asarray(arr)[perm].astype(np.float64)
        if np.abs(col).max(initial=0) >= _EXACT_F32:
            raise ValueError("pod feature values must be < 2**24 for the "
                             "exact one-hot matmul gather")
        feat[k, :pp] = col

    ktable, max_steps = loop_tables(workload, cfg)
    ktable = np.asarray(ktable, np.int32)[None, :]

    gmt = np.asarray(c.gpu_milli_total, np.int32)
    gmask = np.asarray(c.gpu_mask).astype(np.int32)
    milli_tot = (gmt * gmask).sum(axis=1).astype(np.int32)
    nrow = np.stack([
        np.asarray(c.cpu_total, np.int32),
        np.asarray(c.mem_total, np.int32),
        np.asarray(c.gpu_declared, np.int32),
        np.asarray(c.num_gpus, np.int32),
        np.asarray(c.node_mask).astype(np.int32),
        milli_tot,
    ])

    max_milli = int(np.asarray(p.gpu_milli).max(initial=0))
    hist = (cfg.wait_hist_size if cfg.wait_hist_size is not None
            else max(1001, max_milli + 2))
    if hist <= max_milli:
        raise ValueError("wait_hist_size <= trace max gpu_milli")

    totals = (int(nrow[0].sum()), int(nrow[1].sum()),
              int(nrow[3].sum()), int(milli_tot.sum()))
    return _Plan(
        q=q, n=n, g=g, hist=hist, klen=ktable.shape[1],
        max_steps=int(max_steps), pending0=int(pm.sum()),
        node_bits=max(1, (max(n, 1) - 1).bit_length()),
        ev0=jnp.asarray(ev0)[None, :], feat_f=jnp.asarray(feat),
        ktable=jnp.asarray(ktable), nrow=jnp.asarray(nrow),
        gmt=jnp.asarray(gmt), gmask=jnp.asarray(gmask), totals=totals,
    )


def _kernel(plan: _Plan, lanes: int,
            # inputs
            params_ref, ev0_ref, feat_ref, ktable_ref, nrow_ref, gmt_ref,
            gmask_ref,
            # outputs
            aux_out, cpu_out, mem_out, gpu_out, gmil_out, acci_out, accf_out,
            # scratch
            ev, aux, cpu, mem, gpu, gmil, hist, acci, accf):
    L, Q, N, G = lanes, plan.q, plan.n, plan.g
    H, K = plan.hist, plan.klen
    t_cpu, t_mem, t_gc, t_gm = plan.totals
    f32 = jnp.float32

    # ---- init VMEM state
    ev[:] = jnp.broadcast_to(ev0_ref[0:1, :], (L, Q))
    aux[:] = jnp.full((L, Q), AUX_FRESH, jnp.int32)
    cpu[:] = jnp.broadcast_to(nrow_ref[0:1, :], (L, N))
    mem[:] = jnp.broadcast_to(nrow_ref[1:2, :], (L, N))
    gpu[:] = jnp.broadcast_to(nrow_ref[2:3, :], (L, N))
    gmil[:] = jnp.broadcast_to(gmt_ref[:][None, :, :], (L, N, G))
    hist[:] = jnp.zeros((L, H), jnp.int32)
    # iota/where blend, not ``.at[:, 0].set`` — basic-index .at updates
    # lower to lax.scatter, which Mosaic has no TPU lowering for (first
    # real-hardware compile, round-4 session stage fused64)
    acci[:] = jnp.where(_iota((L, 8), 1) == 0,
                        jnp.int32(plan.pending0), jnp.int32(0))
    accf[:] = jnp.zeros((L, 8), f32)

    w_all = params_ref[:]                     # [L, F]
    nmask_b = nrow_ref[4:5, :] > 0            # [1, N]
    cpu_tot = nrow_ref[0:1, :]                # [1, N] i32
    mem_tot = nrow_ref[1:2, :]
    gpu_dec = nrow_ref[2:3, :]
    num_gpus = nrow_ref[3:4, :]
    milli_tot = nrow_ref[5:6, :]
    gmask_b = gmask_ref[:][None, :, :] > 0    # [1, N, G]

    q_iota = _iota((L, Q), 1)
    n_iota = _iota((L, N), 1)
    g_iota3 = _iota((L, N, G), 2)
    h_iota = _iota((L, H), 1)
    k_iota = _iota((L, K), 1)

    def step(_):
        pending = acci[:, 0:1]
        steps = acci[:, 1:2]
        failed = acci[:, 6:7] > 0
        active = (pending > 0) & ~failed & (steps < plan.max_steps)  # [L,1]

        # ---- pop: min time, first-index (== lowest tie rank) slot
        evv = ev[:]
        auxv = aux[:]
        t = jnp.min(evv, axis=1, keepdims=True)                   # [L,1]
        sidx = jnp.min(jnp.where(evv == t, q_iota, Q), axis=1,
                       keepdims=True)                             # [L,1]
        next_del = jnp.min(jnp.where(auxv >= 0, evv, INF), axis=1,
                           keepdims=True)
        mask_b = q_iota == sidx                                   # [L,Q]

        # integer reductions pin dtype=i32: under x64 this jax's jnp.sum
        # widens i32 operands to i64, which the i32 VMEM refs (and real
        # Mosaic) reject
        aux_s = jnp.sum(jnp.where(mask_b, auxv, 0), axis=1,
                        keepdims=True, dtype=jnp.int32)           # [L,1]
        # HIGHEST, or the gather is not exact: at the TPU's default matmul
        # precision the MXU rounds f32 operands to bf16, which keeps 8 bits
        # of a pod's cpu/memory value (first chip run: every lane's
        # trajectory left flat's within 50 pods)
        pf = jax.lax.dot_general(
            mask_b.astype(f32), feat_ref[:],
            dimension_numbers=(((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=f32)                           # [L,8]
        pcpu = pf[:, 0:1].astype(jnp.int32)
        pmem = pf[:, 1:2].astype(jnp.int32)
        pngpu = pf[:, 2:3].astype(jnp.int32)
        pmilli = pf[:, 3:4].astype(jnp.int32)
        pdur = pf[:, 4:5].astype(jnp.int32)

        is_del = active & (aux_s >= 0)
        create = active & (aux_s < 0)
        was_waiting = aux_s == AUX_WAITING

        # ---- DELETION refunds (dense one-hot over node axes)
        a = jnp.where(is_del, aux_s >> G, 0)                      # [L,1]
        di = is_del.astype(jnp.int32)
        oh_a = (n_iota == a).astype(jnp.int32) * di               # [L,N]
        cpu_v = cpu[:] + oh_a * pcpu
        mem_v = mem[:] + oh_a * pmem
        gpu_v = gpu[:] + oh_a * pngpu
        held_bits = jnp.where(is_del, aux_s & ((1 << G) - 1), 0)  # [L,1]
        selb = ((held_bits[:, :, None] >> g_iota3) & 1)           # [L,N,G]*
        gmil_v = gmil[:] + (oh_a[:, :, None] * pmilli[:, :, None]) * selb

        # ---- parametric policy (fks_tpu/models/parametric.py features,
        # same op order so scores match the XLA path)
        d = f32
        cpu_totf = jnp.maximum(cpu_tot, 1).astype(d)
        mem_totf = jnp.maximum(mem_tot, 1).astype(d)
        ngpusf = jnp.maximum(num_gpus, 1).astype(d)
        milli_totf = jnp.maximum(milli_tot, 1).astype(d)
        rem_cpu = (cpu_v - pcpu).astype(d) / cpu_totf             # [L,N]
        rem_mem = (mem_v - pmem).astype(d) / mem_totf
        rem_gpu = (gpu_v - pngpu).astype(d) / ngpusf
        cpu_util = 1 - cpu_v.astype(d) / cpu_totf
        mem_util = 1 - mem_v.astype(d) / mem_totf
        gpu_count_util = 1 - gpu_v.astype(d) / ngpusf
        free_milli = jnp.sum(jnp.where(gmask_b, gmil_v, 0), axis=2,
                             dtype=jnp.int32)
        gpu_milli_util = 1 - free_milli.astype(d) / milli_totf
        balance = 1 - jnp.abs(cpu_util - mem_util)
        pod_gpu = pngpu > 0                                       # [L,1]
        frag_mod = jnp.where(
            pod_gpu, (free_milli % jnp.maximum(pmilli, 1)).astype(d) / 1000.0,
            0.0)
        eligible = jnp.sum(
            (gmask_b & (gmil_v >= pmilli[:, :, None])).astype(jnp.int32),
            axis=2, dtype=jnp.int32)                              # [L,N]
        eligible_frac = eligible.astype(d) / ngpusf
        node_has_gpu = (num_gpus > 0).astype(d) + jnp.zeros((L, N), d)
        best_fitf = 1 - (rem_cpu * 0.33 + rem_mem * 0.33 + rem_gpu * 0.34)
        gmax = jnp.max(jnp.where(gmask_b, gmil_v, 0), axis=2)
        gmin = jnp.min(jnp.where(gmask_b, gmil_v, 2**30), axis=2)
        gpu_imbalance = jnp.where(
            num_gpus > 0, (gmax - jnp.minimum(gmin, gmax)).astype(d) / 1000.0,
            0.0)
        headroom = ((cpu_v > pcpu * 2) & (mem_v > pmem * 2)).astype(d)
        ones = jnp.ones((L, N), d)
        feats = jnp.stack([
            ones, rem_cpu, rem_mem, rem_gpu, cpu_util, mem_util,
            gpu_count_util, gpu_milli_util, balance, frag_mod, eligible_frac,
            jnp.where(pod_gpu, ones, 0.0), node_has_gpu, best_fitf,
            gpu_imbalance, headroom,
        ], axis=-1)                                               # [L,N,F]
        # explicit mul+reduce, NOT einsum: a batched dot_general (batch
        # dim l) is a known Mosaic rejection class, while a VPU
        # elementwise-multiply + small-axis reduce (F=16) always lowers
        raw = jnp.sum(feats * w_all[:, None, :], axis=-1) * SCORE_SCALE
        feasible = (nmask_b
                    & (pcpu <= cpu_v) & (pmem <= mem_v) & (pngpu <= gpu_v)
                    # or-of-masks, not where(): Mosaic has no select
                    # that produces an i1 vector
                    & (~pod_gpu | (eligible >= pngpu)))
        scores = jnp.where(feasible,
                           jnp.maximum(1, jnp.trunc(raw).astype(jnp.int32)),
                           0)                                     # [L,N]

        mx = jnp.max(scores, axis=1, keepdims=True)               # [L,1]
        wn = jnp.min(jnp.where(scores == mx, n_iota, N), axis=1,
                     keepdims=True)                               # [L,1]
        placed = create & (mx > 0)

        # ---- best-fit GPU pick on the winner node: G rounds of
        # lexicographic (milli, slot) minima (ops/allocator.py order)
        oh_w = (n_iota == wn).astype(jnp.int32)                   # [L,N]
        elig_w = (gmask_b & (gmil_v >= pmilli[:, :, None])
                  & (oh_w[:, :, None] > 0))                       # [L,N,G]
        n_elig = _sum_ng(elig_w.astype(jnp.int32))                # [L,1]
        key = jnp.where(elig_w, gmil_v * G + g_iota3, _BIG)
        sel = jnp.zeros((L, N, G), bool)
        for k in range(G):
            cur = _min_ng(key)                                    # [L,1,1]
            take = (k < pngpu)[:, :, None] & (cur < _BIG)
            pick = (key == cur) & take
            sel = sel | pick
            key = jnp.where(pick, _BIG, key)
        ok = n_elig >= pngpu
        alloc_fail = placed & (pngpu > 0) & ~ok
        plc = placed & ~alloc_fail                                # [L,1]
        pli = plc.astype(jnp.int32)
        oh_p = oh_w * pli                                         # [L,N]
        cpu_v = cpu_v - oh_p * pcpu
        mem_v = mem_v - oh_p * pmem
        gpu_v = gpu_v - oh_p * pngpu
        gmil_v = gmil_v - (oh_p[:, :, None] * pmilli[:, :, None]
                           * sel.astype(jnp.int32))
        new_bits = _sum_ng(jnp.where(sel, jnp.int32(1) << g_iota3, 0))

        # ---- failed creation: waiting histogram + fragmentation + retry
        failp = create & ~placed
        bucket = jnp.clip(pmilli, 0, H - 1)                       # [L,1]
        hdelta = ((failp & ~was_waiting & (pngpu > 0)).astype(jnp.int32)
                  - (plc & was_waiting & (pngpu > 0)).astype(jnp.int32))
        hist_v = hist[:] + (h_iota == bucket).astype(jnp.int32) * hdelta
        has_w = jnp.any(hist_v > 0, axis=1, keepdims=True)        # [L,1]
        mn = jnp.min(jnp.where(hist_v > 0, h_iota, _BIG), axis=1,
                     keepdims=True)
        mn = jnp.where(has_w, mn, 0)
        frag_free = jnp.where(
            gmask_b & (gmil_v > 0) & (gmil_v < mn[:, :, None]), gmil_v, 0)
        fsum = _sum_ng(frag_free)                                 # [L,1] i32
        frag_score = jnp.where(
            has_w & (t_gm > 0), fsum.astype(f32) / f32(max(t_gm, 1)),
            f32(0))
        found = next_del < INF
        retry = failp & found
        dropped = failp & ~found
        rt = next_del + 1

        # ---- slot rewrite (one blended pass over the VMEM queue)
        new_t = jnp.where(plc, t + pdur, jnp.where(retry, rt, INF))
        enc = (wn << G) | new_bits
        new_aux = jnp.where(plc, enc, jnp.where(failp, AUX_WAITING, aux_s))
        wmask = mask_b & active
        ev[:] = jnp.where(wmask, new_t, evv)
        aux[:] = jnp.where(wmask, new_aux, auxv)
        cpu[:] = cpu_v
        mem[:] = mem_v
        gpu[:] = gpu_v
        gmil[:] = gmil_v
        hist[:] = hist_v

        # ---- evaluator bookkeeping (identical arithmetic to the engines)
        valid = active & ~alloc_fail
        events = acci[:, 2:3] + valid.astype(jnp.int32)
        snap_idx = acci[:, 3:4]
        kt_at = jnp.sum(
            jnp.where(k_iota == jnp.minimum(snap_idx, K - 1), ktable_ref[:],
                      0), axis=1, keepdims=True, dtype=jnp.int32)
        fire = valid & (snap_idx < K) & (events >= kt_at)
        firef = fire.astype(f32)
        u_cpu = f32(t_cpu) - jnp.sum(
            cpu_v, axis=1, keepdims=True, dtype=jnp.int32).astype(f32)
        u_mem = f32(t_mem) - jnp.sum(
            mem_v, axis=1, keepdims=True, dtype=jnp.int32).astype(f32)
        u_gc = jnp.sum(
            num_gpus - gpu_v, axis=1, keepdims=True,
            dtype=jnp.int32).astype(f32)
        u_gm = f32(t_gm) - _sum_ng(
            jnp.where(gmask_b, gmil_v, 0)).astype(f32)
        utils = jnp.concatenate([
            0.0 * u_cpu if t_cpu <= 0 else u_cpu / f32(max(t_cpu, 1)),
            0.0 * u_mem if t_mem <= 0 else u_mem / f32(max(t_mem, 1)),
            0.0 * u_gc if t_gc <= 0 else u_gc / f32(max(t_gc, 1)),
            0.0 * u_gm if t_gm <= 0 else u_gm / f32(max(t_gm, 1)),
        ], axis=1)                                                # [L,4]
        accf[:, 0:4] = accf[:, 0:4] + utils * firef
        accf[:, 4:5] = accf[:, 4:5] + jnp.where(failp, frag_score, 0)

        active_nodes = jnp.sum(
            (nmask_b & ((cpu_v < cpu_tot) | (mem_v < mem_tot)
                        | (gpu_v < gpu_dec))).astype(jnp.int32),
            axis=1, keepdims=True, dtype=jnp.int32)
        acci[:, 0:1] = acci[:, 0:1] - (is_del | dropped).astype(jnp.int32)
        acci[:, 1:2] = steps + active.astype(jnp.int32)
        acci[:, 2:3] = events
        acci[:, 3:4] = snap_idx + fire.astype(jnp.int32)
        acci[:, 4:5] = acci[:, 4:5] + failp.astype(jnp.int32)
        acci[:, 5:6] = jnp.maximum(acci[:, 5:6],
                                   jnp.where(valid, active_nodes, 0))
        acci[:, 6:7] = acci[:, 6:7] | alloc_fail.astype(jnp.int32)

        pending2 = acci[:, 0:1]
        failed2 = acci[:, 6:7] > 0
        return jnp.any((pending2 > 0) & ~failed2
                       & (acci[:, 1:2] < plan.max_steps))

    jax.lax.while_loop(lambda cont: cont, step, jnp.bool_(plan.pending0 > 0))

    # ---- write results
    aux_out[:] = aux[:]
    cpu_out[:] = cpu[:]
    mem_out[:] = mem[:]
    gpu_out[:] = gpu[:]
    gmil_out[:] = gmil[:]
    acci_out[:] = acci[:]
    accf_out[:] = accf[:]


def make_fused_population_run(workload: Workload,
                              cfg: SimConfig = SimConfig(),
                              lanes: int = 64,
                              interpret: bool = False):
    """``run(params[P, F]) -> SimResult`` (leading axis P) through the fused
    kernel. P is padded up to a multiple of ``lanes``; each chunk of
    ``lanes`` candidates is one grid step.

    Compiled by Mosaic (TPU only) unless ``interpret=True`` asks for the
    Pallas interpreter by name — slow, for correctness tests off the chip.
    The mode is never chosen from the backend: a non-TPU caller that
    forgets to ask fails at compile instead of silently interpreting."""
    plan = _build_plan(workload, cfg)
    Q, N, G = plan.q, plan.n, plan.g
    p = workload.pods
    pp = p.p_padded

    shared = lambda *shape: pl.BlockSpec(  # noqa: E731
        shape, lambda i: tuple(0 for _ in shape), memory_space=pltpu.VMEM)
    blocked = lambda *shape: pl.BlockSpec(  # noqa: E731
        shape, lambda i: (i,) + tuple(0 for _ in shape[1:]),
        memory_space=pltpu.VMEM)

    def call(params_padded, L):
        chunks = params_padded.shape[0] // L
        return pl.pallas_call(
            functools.partial(_kernel, plan, L),
            grid=(chunks,),
            in_specs=[
                blocked(L, NUM_FEATURES),
                shared(1, Q), shared(8, Q), shared(1, plan.klen),
                shared(6, N), shared(N, G), shared(N, G),
            ],
            out_specs=[
                blocked(L, Q), blocked(L, N), blocked(L, N), blocked(L, N),
                blocked(L, N, G), blocked(L, 8), blocked(L, 8),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((chunks * L, Q), jnp.int32),
                jax.ShapeDtypeStruct((chunks * L, N), jnp.int32),
                jax.ShapeDtypeStruct((chunks * L, N), jnp.int32),
                jax.ShapeDtypeStruct((chunks * L, N), jnp.int32),
                jax.ShapeDtypeStruct((chunks * L, N, G), jnp.int32),
                jax.ShapeDtypeStruct((chunks * L, 8), jnp.int32),
                jax.ShapeDtypeStruct((chunks * L, 8), jnp.float32),
            ],
            scratch_shapes=[
                pltpu.VMEM((L, Q), jnp.int32),   # ev
                pltpu.VMEM((L, Q), jnp.int32),   # aux
                pltpu.VMEM((L, N), jnp.int32),   # cpu
                pltpu.VMEM((L, N), jnp.int32),   # mem
                pltpu.VMEM((L, N), jnp.int32),   # gpu
                pltpu.VMEM((L, N, G), jnp.int32),  # gmil
                pltpu.VMEM((L, plan.hist), jnp.int32),
                pltpu.VMEM((L, 8), jnp.int32),
                pltpu.VMEM((L, 8), jnp.float32),
            ],
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=_VMEM_LIMIT_BYTES),
            interpret=interpret,
        )(params_padded, plan.ev0, plan.feat_f, plan.ktable, plan.nrow,
          plan.gmt, plan.gmask)

    perm = _rank_perm(np.asarray(p.pod_mask), np.asarray(p.tie_rank))
    inv = jnp.asarray(np.argsort(perm))
    ctime0 = jnp.asarray(p.creation_time, jnp.int32)

    # VMEM feasibility: ~5 [L,q] i32 live arrays (ev, aux, blend mask +
    # fusion temps), the tile-padded [L,n,128] grids, the [L,hist]
    # waiting histogram, and slack for the small accumulators. Lanes
    # auto-shrink to fit _VMEM_PLAN_BYTES; shapes that cannot fit even 8
    # lanes are rejected up front instead of letting Mosaic fail
    # opaquely — the XLA flat engine handles them.
    per_lane_bytes = (5 * Q + 3 * N * 128 + plan.hist + 2048) * 4
    lanes_fit = (_VMEM_PLAN_BYTES // per_lane_bytes) // 8 * 8
    if lanes_fit < 8:
        raise ValueError(
            f"workload too large for the fused kernel's VMEM plan "
            f"({per_lane_bytes >> 10} KB/lane for q={Q}, n={N}, "
            f"hist={plan.hist}; under 8 lanes fit); use the XLA flat "
            "engine for large-node/pod shapes")

    def run(params) -> SimResult:
        pop = params.shape[0]
        # lane width: the cap, the whole (8-aligned) population when
        # smaller — small shard sizes under shard_map stay cheap — or
        # whatever VMEM can hold
        L = min(lanes, _round_up(pop, 8), lanes_fit)
        padded = _round_up(pop, L)
        if padded != pop:
            params = jnp.concatenate(
                [params, jnp.broadcast_to(params[:1],
                                          (padded - pop,) + params.shape[1:])])
        aux, cpu, mem, gpu, gmil, acci, accf = call(
            jnp.asarray(params, jnp.float32), L)
        aux = aux[:pop, :pp]
        an, ag = jax.vmap(
            lambda a: _decode_assignment(a, None, G, True))(aux)
        view = _FinalView(
            assigned_node=an[:, inv], assigned_gpus=ag[:, inv],
            pod_ctime=jnp.broadcast_to(ctime0, (pop, pp)),
            cpu_left=cpu[:pop], mem_left=mem[:pop], gpu_left=gpu[:pop],
            gpu_milli_left=gmil[:pop],
            events_processed=acci[:pop, 2], snap_idx=acci[:pop, 3],
            snap_sums=accf[:pop, 0:4], frag_sum=accf[:pop, 4],
            frag_count=acci[:pop, 4], max_nodes=acci[:pop, 5],
            failed=acci[:pop, 6] > 0, violations=jnp.zeros(pop, jnp.int32),
            numeric_flags=jnp.zeros(pop, jnp.int32),
        )
        return jax.vmap(
            lambda v, pend: finalize_fields(workload, cfg, pending=pend, s=v)
        )(view, acci[:pop, 0] > 0)

    return run
