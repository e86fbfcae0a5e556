"""Candidate policies as DATA: a jaxpr->bytecode compiler + on-device VM.

Why: every LLM candidate is new code, and jitting the simulation engine per
candidate costs seconds of XLA compile (the engine dominates: ~7 s on this
container's CPU, far more on TPU) for milliseconds of run. The reference
sidesteps this because CPython "compiles" instantly (reference:
funsearch/funsearch_integration.py:67-101 compiles candidates with exec());
a TPU-native framework needs a different shape: compile the engine ONCE
with the policy as an interpreted register program, so a fresh candidate is
a few arrays uploaded to the device, not a recompilation.

Pipeline:
  candidate source
    -> transpiler.build_policy (sandbox validation + the vectorized
       closure, not yet run)
    -> jax.make_jaxpr on the padded (N, G) view shapes: the ONE trace of
       the candidate's body, so also its subset validation (a violation
       raises TranspileError from inside it; transpiler.transpile's 2 x 2
       dry trace is for callers without a shape and is not run here)
    -> this module lowers the (inlined) jaxpr to a register program:
       every value lives as an f32[N, G] register (scalars and [N] values
       broadcast across G), each op writes one fresh register, reductions
       over the GPU axis re-broadcast their result
    -> ``VMProgram`` pytree of int32/float32 arrays, padded to a bucket size
       so ONE compiled engine serves every candidate of that bucket.

Execution (`score`, the one entry point, batched or not): ``fori_loop``
over the LIVE op slots — one program's ``n_ops``, or under ``vmap`` the
longest live program of the batch as one unbatched scalar
(`_loop_bound`); NOP padding past it never runs. Each slot is a
``lax.switch`` over a deliberately minimal 33-opcode table on [N, G]
values (scalar literals load from a pooled register block, not op slots;
boolean and sign ops are canonicalized into arithmetic at lowering — see the
CONST_POOL / opcode-table comments below for the vmap rationale). Numeric model: everything runs at the
AMBIENT float precision — f64 when x64 is on (CPU tests / golden parity,
where the transpiler also computes floats in f64, matching the reference's
CPython binary64), f32 otherwise (TPU, where the jit tier is f32 too).
Keeping the two tiers at the same precision is what makes VM scores
integer-exact against the transpiled policy: a trunc after an f32 division
can land one short of the f64 result right at integer boundaries. Bools
are 0/1; integer ops are exact below the mantissa (trace resources are
≤ ~1e6). Integer division/remainder use C-style truncation exactly like
lax.

Candidates using constructs outside the lowerable vocabulary raise
``VMUnsupported`` — the caller falls back to the per-candidate jit tier
(fks_tpu.funsearch.backend), so coverage is a throughput optimization, not
a correctness gate.
"""
from __future__ import annotations

from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from fks_tpu.funsearch import transpiler
from fks_tpu.sim.types import NodeView, PodView

def _ambient_float():
    """f64 under x64 (what the transpiled jit tier computes floats in
    there), else f32. Evaluated at trace time, not import time."""
    return jax.dtypes.canonicalize_dtype(np.float64)

# --------------------------------------------------------------- input plan

# register ids 0..N_INPUTS-1 hold the broadcast policy inputs, in this order
_POD_FIELDS = ("cpu_milli", "memory_mib", "num_gpu", "gpu_milli",
               "creation_time", "duration_time")
_NODE_SCALARS = ("cpu_milli_left", "cpu_milli_total", "memory_mib_left",
                 "memory_mib_total", "gpu_left", "num_gpus")
_NODE_GRIDS = ("gpu_milli_left", "gpu_milli_total", "gpu_mem_total")
N_INPUTS = len(_POD_FIELDS) + len(_NODE_SCALARS) + len(_NODE_GRIDS) + 2

# Constant pool: scalar literals live in a fixed block of registers right
# after the inputs, filled host-side from ``VMProgram.consts`` — NOT in op
# slots. Two wins, both sized for the vmapped population path where every
# branch in the switch table runs for every slot: constants stop consuming
# slot iterations, and the CONST branch leaves the table entirely. The
# pool size is FIXED so register numbering is identical across programs
# (stacked programs must agree on the layout); overflow -> VMUnsupported
# -> the jit tier.
CONST_POOL = 32

# opcodes (order is the lax.switch branch table in `_branches`). The table
# is deliberately MINIMAL: under vmap (population-batched evaluation) the
# switch index is per-lane data, so XLA executes EVERY branch per op slot
# and selects — each table entry costs [N, G] work per slot whether or not
# any program uses it. Ops with an exactness-safe expansion are therefore
# canonicalized at lowering instead of tabled: AND->MUL, OR->MAX (0/1
# domain), NOT->1-x, NEG->x*(-1) (sign-exact for -0.0, unlike 0-x),
# SQUARE->x*x, integer_pow->POW against a pooled constant, and constants
# load from the pool.
(OP_NOP, OP_ADD, OP_SUB, OP_MUL, OP_DIV, OP_MAX, OP_MIN,
 OP_GE, OP_GT, OP_LT, OP_LE, OP_EQ, OP_NE,
 OP_SEL, OP_TRUNC, OP_FLOOR, OP_CEIL, OP_ABS, OP_SIGN,
 OP_ISFIN, OP_REM, OP_POW, OP_EXP, OP_LOG, OP_SQRT,
 OP_SIN, OP_COS, OP_TAN, OP_COL, OP_RSUM_G, OP_RMAX_G, OP_RMIN_G,
 OP_SETCOL) = range(33)


class VMUnsupported(Exception):
    """Candidate uses a construct outside the VM vocabulary."""


class VMProgram(NamedTuple):
    """One lowered candidate. Pure data — a pytree of arrays the compiled
    engine takes as an argument (and can be stacked/batched)."""

    opcode: jax.Array  # i32[O]
    a: jax.Array  # i32[O] operand register
    b: jax.Array  # i32[O]
    c: jax.Array  # i32[O]
    imm: jax.Array  # f32[O] immediate (COL/SETCOL column index)
    consts: jax.Array  # f32[CONST_POOL] pooled scalar literals
    n_ops: jax.Array  # i32[] live op count (the op-slot loop's bound is the
    # largest among the lanes of a batch: `_loop_bound`)
    out_reg: jax.Array  # i32[]

    @property
    def capacity(self) -> int:
        return self.opcode.shape[0]


# ---------------------------------------------------------------- compiler


class _Lowerer:
    def __init__(self, n: int, g: int):
        self.n, self.g = n, g
        self.ops: List[Tuple[int, int, int, int, float]] = []
        self.consts: List[float] = []  # pool values, register N_INPUTS + i
        self.reg_of: Dict[Any, int] = {}  # jaxpr Var id -> register
        self.const_reg: Dict[float, int] = {}
        self.cse: Dict[Tuple, int] = {}  # value numbering (all ops pure)
        # concatenate provenance: reg -> list of piece regs (for fold-away
        # of the stack+reduce pattern the transpiler's gpu loops emit)
        self.pieces: Dict[int, List[int]] = {}

    # -- emission

    def emit(self, op: int, a: int = 0, b: int = 0, c: int = 0,
             imm: float = 0.0) -> int:
        key = (op, a, b, c, float(imm))
        if op != OP_NOP:  # NOPs are concat placeholders with identity
            r = self.cse.get(key)
            if r is not None:
                return r
        self.ops.append((op, a, b, c, float(imm)))
        r = N_INPUTS + CONST_POOL + len(self.ops) - 1
        if op != OP_NOP:
            self.cse[key] = r
        return r

    def const(self, v: float) -> int:
        import math

        v = float(v)
        # key includes the sign bit: -0.0 == 0.0 in Python, but the pool
        # value is THE source of the literal and 1/-0 != 1/+0 — collapsing
        # them would break sign-exactness vs the jit tier
        key = (v, math.copysign(1.0, v))
        r = self.const_reg.get(key)
        if r is None:
            if len(self.consts) >= CONST_POOL:
                raise VMUnsupported(
                    f"more than {CONST_POOL} distinct constants")
            self.consts.append(v)
            r = N_INPUTS + len(self.consts) - 1
            self.const_reg[key] = r
        return r

    # -- operand resolution

    def reg(self, atom) -> int:
        from jax.extend.core import Literal

        if isinstance(atom, Literal):
            val = np.asarray(atom.val)
            if val.ndim == 0:
                return self.const(float(val))
            raise VMUnsupported(f"array literal of shape {val.shape}")
        r = self.reg_of.get(id(atom))
        if r is None:
            raise VMUnsupported(f"unbound variable {atom}")
        if r in self.pieces:
            # a stacked-pieces placeholder holds piece 0's value, not the
            # concatenation; only the reduce fold may consume it
            raise VMUnsupported("concatenate consumed by non-reduce op")
        return r

    def reg_any(self, atom) -> int:
        """Operand lookup that lets stacked-pieces placeholders through —
        used at call boundaries (nested jit) so a concatenate can reach the
        reduce inside the callee; any real consumer still goes via reg()."""
        r = self.reg_of.get(id(atom))
        if r is not None:
            return r
        return self.reg(atom)

    def bind(self, var, reg: int) -> None:
        self.reg_of[id(var)] = reg

    # -- lowering

    def lower_closed(self, closed, in_regs: Sequence[int]) -> List[int]:
        jaxpr = closed.jaxpr
        if len(jaxpr.invars) != len(in_regs):
            raise VMUnsupported("arity mismatch in nested jaxpr")
        for var, reg in zip(jaxpr.invars, in_regs):
            self.bind(var, reg)
        for var, val in zip(jaxpr.constvars, closed.consts):
            arr = np.asarray(val)
            if arr.ndim == 0:
                self.bind(var, self.const(float(arr)))
            else:
                raise VMUnsupported(f"array constant of shape {arr.shape}")
        for eqn in jaxpr.eqns:
            self.eqn(eqn)
        return [self.reg_any(v) for v in jaxpr.outvars]

    def eqn(self, eqn) -> None:
        name = eqn.primitive.name
        handler = getattr(self, f"_p_{name}", None)
        if handler is None:
            raise VMUnsupported(f"primitive {name}")
        handler(eqn)

    # -- helpers

    def _unary(self, eqn, op):
        self.bind(eqn.outvars[0], self.emit(op, self.reg(eqn.invars[0])))

    def _binary(self, eqn, op):
        a, b = (self.reg(v) for v in eqn.invars)
        self.bind(eqn.outvars[0], self.emit(op, a, b))

    @staticmethod
    def _is_int(var) -> bool:
        return jnp.issubdtype(var.aval.dtype, jnp.integer)

    # -- structural primitives

    def _p_pjit(self, eqn):
        outs = self.lower_closed(eqn.params["jaxpr"],
                                 [self.reg_any(v) for v in eqn.invars])
        for var, reg in zip(eqn.outvars, outs):
            self.bind(var, reg)

    _p_closed_call = _p_pjit
    _p_jit = _p_pjit  # jax>=0.7 names the inlineable call primitive "jit"

    def _p_custom_jvp_call(self, eqn):
        outs = self.lower_closed(eqn.params["call_jaxpr"],
                                 [self.reg_any(v) for v in eqn.invars])
        for var, reg in zip(eqn.outvars, outs):
            self.bind(var, reg)

    def _p_broadcast_in_dim(self, eqn):
        # storage is already fully broadcast [N, G]; pure aliasing
        self.bind(eqn.outvars[0], self.reg(eqn.invars[0]))

    def _p_squeeze(self, eqn):
        self.bind(eqn.outvars[0], self.reg(eqn.invars[0]))

    def _p_reshape(self, eqn):
        # reshapes between (), [1], [N], [N,1], [1,N] views of the same
        # broadcast value are aliases; anything that reorders data is not
        src = tuple(d for d in eqn.invars[0].aval.shape if d != 1)
        dst = tuple(d for d in eqn.outvars[0].aval.shape if d != 1)
        if src != dst:
            raise VMUnsupported(
                f"reshape {eqn.invars[0].aval.shape} -> "
                f"{eqn.outvars[0].aval.shape}")
        self.bind(eqn.outvars[0], self.reg(eqn.invars[0]))

    def _p_convert_element_type(self, eqn):
        src_f = not self._is_int(eqn.invars[0]) and \
            eqn.invars[0].aval.dtype != jnp.bool_
        dst_i = self._is_int(eqn.outvars[0])
        r = self.reg(eqn.invars[0])
        if src_f and dst_i:
            r = self.emit(OP_TRUNC, r)  # f->i casts truncate toward zero
        self.bind(eqn.outvars[0], r)

    def _p_stop_gradient(self, eqn):
        self.bind(eqn.outvars[0], self.reg(eqn.invars[0]))

    def _p_slice(self, eqn):
        aval = eqn.invars[0].aval
        start = eqn.params["start_indices"]
        limit = eqn.params["limit_indices"]
        strides = eqn.params["strides"] or (1,) * len(start)
        if any(s != 1 for s in strides):
            raise VMUnsupported("strided slice")
        shape = aval.shape
        if len(shape) == 2 and shape == (self.n, self.g) and \
                start[0] == 0 and limit[0] == self.n and \
                limit[1] - start[1] == 1:
            # gpu column pick: [N, G][:, g:g+1] (transpiler's per-GPU loop)
            r = self.emit(OP_COL, self.reg(eqn.invars[0]), imm=start[1])
            self.bind(eqn.outvars[0], r)
            return
        if all(s == 0 for s in start) and tuple(limit) == tuple(shape):
            self.bind(eqn.outvars[0], self.reg(eqn.invars[0]))  # full slice
            return
        raise VMUnsupported(f"slice {shape} [{start}:{limit}]")

    def _p_concatenate(self, eqn):
        out_shape = eqn.outvars[0].aval.shape
        dim = eqn.params["dimension"]
        if (len(out_shape) == 2 and out_shape == (self.n, self.g)
                and dim == 1
                and all(v.aval.shape[1] == 1 for v in eqn.invars)):
            # the transpiler's per-GPU generators stack G column values
            # [N,1] into an [N,G] grid — build a REAL grid register so any
            # consumer (select_n masking, reductions, arithmetic) works
            acc = self.const(0.0)
            for col, v in enumerate(eqn.invars):
                acc = self.emit(OP_SETCOL, acc, self.reg(v), imm=col)
            self.bind(eqn.outvars[0], acc)
            return
        if len(out_shape) == 1:
            # 1-D stack (e.g. min/max over a scalar generator): keep piece
            # provenance; only a reduce may consume it, as a pairwise fold
            piece_regs = [self.reg(v) for v in eqn.invars]
            r = self.emit(OP_NOP, piece_regs[0])  # placeholder: piece 0
            self.pieces[r] = piece_regs
            self.bind(eqn.outvars[0], r)
            return
        raise VMUnsupported(
            f"concatenate -> {out_shape} along axis {dim}")

    # -- arithmetic

    def _p_add(self, eqn):
        self._binary(eqn, OP_ADD)

    def _p_sub(self, eqn):
        self._binary(eqn, OP_SUB)

    def _p_mul(self, eqn):
        self._binary(eqn, OP_MUL)

    def _p_div(self, eqn):
        a, b = (self.reg(v) for v in eqn.invars)
        r = self.emit(OP_DIV, a, b)
        if self._is_int(eqn.outvars[0]):
            r = self.emit(OP_TRUNC, r)  # lax int div truncates toward zero
        self.bind(eqn.outvars[0], r)

    def _p_rem(self, eqn):
        self._binary(eqn, OP_REM)

    def _p_max(self, eqn):
        self._binary(eqn, OP_MAX)

    def _p_min(self, eqn):
        self._binary(eqn, OP_MIN)

    def _p_pow(self, eqn):
        self._binary(eqn, OP_POW)

    def _p_integer_pow(self, eqn):
        y = eqn.params["y"]
        r = self.reg(eqn.invars[0])
        if y == 2:
            self.bind(eqn.outvars[0], self.emit(OP_MUL, r, r))  # x*x exact
        else:
            # jnp.power(x, float(y)) — what the removed IPOW branch ran
            self.bind(eqn.outvars[0],
                      self.emit(OP_POW, r, self.const(float(y))))

    def _p_neg(self, eqn):
        # x * -1, NOT 0 - x: sub flips the sign of +0.0 (0 - 0 = +0 where
        # -(+0) = -0), and 1/-0 != 1/+0 — the mul form is sign-exact
        self.bind(eqn.outvars[0],
                  self.emit(OP_MUL, self.reg(eqn.invars[0]),
                            self.const(-1.0)))

    def _p_abs(self, eqn):
        self._unary(eqn, OP_ABS)

    def _p_sign(self, eqn):
        self._unary(eqn, OP_SIGN)

    def _p_floor(self, eqn):
        self._unary(eqn, OP_FLOOR)

    def _p_ceil(self, eqn):
        self._unary(eqn, OP_CEIL)

    def _p_round(self, eqn):
        raise VMUnsupported("round")  # rounding-mode sensitive; keep exact

    def _p_exp(self, eqn):
        self._unary(eqn, OP_EXP)

    def _p_log(self, eqn):
        self._unary(eqn, OP_LOG)

    def _p_sqrt(self, eqn):
        self._unary(eqn, OP_SQRT)

    def _p_sin(self, eqn):
        self._unary(eqn, OP_SIN)

    def _p_cos(self, eqn):
        self._unary(eqn, OP_COS)

    def _p_tan(self, eqn):
        self._unary(eqn, OP_TAN)

    def _p_is_finite(self, eqn):
        self._unary(eqn, OP_ISFIN)

    # -- logic / comparison (bools are 0/1 f32, so the boolean ops are
    # plain arithmetic — no dedicated table branches)

    def _p_and(self, eqn):
        self._binary(eqn, OP_MUL)

    def _p_or(self, eqn):
        self._binary(eqn, OP_MAX)

    def _p_xor(self, eqn):
        self._binary(eqn, OP_NE)  # 0/1 xor == ne

    def _p_not(self, eqn):
        self.bind(eqn.outvars[0],
                  self.emit(OP_SUB, self.const(1.0),
                            self.reg(eqn.invars[0])))

    def _p_ge(self, eqn):
        self._binary(eqn, OP_GE)

    def _p_gt(self, eqn):
        self._binary(eqn, OP_GT)

    def _p_lt(self, eqn):
        self._binary(eqn, OP_LT)

    def _p_le(self, eqn):
        self._binary(eqn, OP_LE)

    def _p_eq(self, eqn):
        self._binary(eqn, OP_EQ)

    def _p_ne(self, eqn):
        self._binary(eqn, OP_NE)

    def _p_select_n(self, eqn):
        pred, x0, x1 = (self.reg(v) for v in eqn.invars)
        # select_n picks cases[pred]: pred==0 -> x0, pred==1 -> x1
        self.bind(eqn.outvars[0], self.emit(OP_SEL, pred, x0, x1))

    # -- reductions (GPU axis or stacked-pieces folds)

    def _reduce(self, eqn, op_grid, fold_op):
        (src,) = eqn.invars
        r = self.reg_of.get(id(src))  # direct lookup: pieces allowed here
        if r is None:
            r = self.reg(src)
        axes = tuple(eqn.params["axes"])
        shape = src.aval.shape
        if r in self.pieces:
            # transpiler's per-GPU generator: stack pieces then reduce over
            # the stacked axis -> fold the pieces pairwise instead
            if len(axes) != 1:
                raise VMUnsupported("multi-axis reduce of stacked pieces")
            regs = self.pieces[r]
            acc = regs[0]
            for p in regs[1:]:
                acc = self.emit(fold_op, acc, p)
            self.bind(eqn.outvars[0], acc)
            return
        if shape == (self.n, self.g) and axes == (1,):
            self.bind(eqn.outvars[0], self.emit(op_grid, r))
            return
        raise VMUnsupported(f"reduce over axes {axes} of {shape}")

    def _p_reduce_sum(self, eqn):
        self._reduce(eqn, OP_RSUM_G, OP_ADD)

    def _p_reduce_max(self, eqn):
        self._reduce(eqn, OP_RMAX_G, OP_MAX)

    def _p_reduce_min(self, eqn):
        self._reduce(eqn, OP_RMIN_G, OP_MIN)

    def _p_reduce_and(self, eqn):
        self._reduce(eqn, OP_RMIN_G, OP_MUL)  # 0/1 and == mul

    def _p_reduce_or(self, eqn):
        self._reduce(eqn, OP_RMAX_G, OP_MAX)


def _dummy_views(n: int, g: int) -> Tuple[PodView, NodeView]:
    i = jnp.zeros((), jnp.int32)
    vn = jnp.zeros(n, jnp.int32)
    vg = jnp.zeros((n, g), jnp.int32)
    return (PodView(i, i, i, i, i, i),
            NodeView(vn, vn, vn, vn, vn, vn, vg, vg, vg,
                     jnp.ones((n, g), bool), jnp.ones(n, bool)))


def compile_policy(code: str, n: int, g: int,
                   capacity: Optional[int] = None) -> VMProgram:
    """Lower candidate source to a VMProgram for padded shapes (n, g).

    Raises TranspileError (invalid candidate) or VMUnsupported (valid but
    outside the VM vocabulary -> caller uses the jit tier). The candidate's
    body runs under a JAX trace once, here, at (n, g): a candidate is valid
    on this path iff that trace succeeds.
    """
    policy = transpiler.build_policy(code)
    pod, nodes = _dummy_views(n, g)
    closed = jax.make_jaxpr(policy)(pod, nodes)

    lo = _Lowerer(n, g)
    flat_in = [*range(N_INPUTS)]
    # jaxpr invars = flattened (PodView, NodeView) leaves, in pytree order,
    # which matches the register input plan (both are field order)
    outs = lo.lower_closed(closed, flat_in)
    out_reg = outs[0]

    n_ops = len(lo.ops)
    cap = capacity or max(64, 1 << (n_ops - 1).bit_length())
    if n_ops > cap:
        raise VMUnsupported(f"program too long: {n_ops} ops > {cap}")
    arr = np.zeros((5, cap), np.float64)
    for k, (op, a, b, c, imm) in enumerate(lo.ops):
        arr[:, k] = (op, a, b, c, imm)
    pool = np.zeros(CONST_POOL, np.float64)
    pool[: len(lo.consts)] = lo.consts
    return VMProgram(
        opcode=jnp.asarray(arr[0], jnp.int32),
        a=jnp.asarray(arr[1], jnp.int32),
        b=jnp.asarray(arr[2], jnp.int32),
        c=jnp.asarray(arr[3], jnp.int32),
        imm=jnp.asarray(arr[4], _ambient_float()),
        consts=jnp.asarray(pool, _ambient_float()),
        n_ops=jnp.asarray(n_ops, jnp.int32),
        out_reg=jnp.asarray(out_reg, jnp.int32),
    )


def compile_for_workload(code: str, workload, capacity: int = 512) -> VMProgram:
    """``compile_policy`` with (n, g) taken from a parsed workload's padded
    cluster shape — the replay / trace-diff entry point
    (fks_tpu.obs.tracing), where the caller holds a Workload, not shapes."""
    c = workload.cluster
    return compile_policy(code, c.n_padded, c.g_padded, capacity=capacity)


# ---------------------------------------------------------------- executor


def _inputs(pod: PodView, nodes: NodeView) -> jax.Array:
    """[N_INPUTS, N, G] ambient-float broadcast input registers."""
    n, g = nodes.gpu_mask.shape
    F = _ambient_float()

    def full(x):
        return jnp.full((n, g), jnp.asarray(x, F))

    def cols(x):
        return jnp.broadcast_to(jnp.asarray(x, F)[:, None], (n, g))

    rows = [full(getattr(pod, f)) for f in _POD_FIELDS]
    rows += [cols(getattr(nodes, f)) for f in _NODE_SCALARS]
    rows += [jnp.asarray(getattr(nodes, f), F) for f in _NODE_GRIDS]
    rows += [jnp.asarray(nodes.gpu_mask, F), cols(nodes.node_mask)]
    return jnp.stack(rows)


def _branches(n: int, g: int):
    F = _ambient_float()

    def red(fn):
        def go(va, vb, vc, im):
            return jnp.broadcast_to(fn(va, axis=1, keepdims=True), (n, g))
        return go

    def col(va, vb, vc, im):
        c = jnp.clip(im.astype(jnp.int32), 0, g - 1)
        return jnp.broadcast_to(
            lax.dynamic_slice_in_dim(va, c, 1, axis=1), (n, g))

    return [
        lambda va, vb, vc, im: va,  # NOP (value = operand a)
        lambda va, vb, vc, im: va + vb,
        lambda va, vb, vc, im: va - vb,
        lambda va, vb, vc, im: va * vb,
        lambda va, vb, vc, im: va / vb,
        lambda va, vb, vc, im: jnp.maximum(va, vb),
        lambda va, vb, vc, im: jnp.minimum(va, vb),
        lambda va, vb, vc, im: (va >= vb).astype(F),
        lambda va, vb, vc, im: (va > vb).astype(F),
        lambda va, vb, vc, im: (va < vb).astype(F),
        lambda va, vb, vc, im: (va <= vb).astype(F),
        lambda va, vb, vc, im: (va == vb).astype(F),
        lambda va, vb, vc, im: (va != vb).astype(F),
        lambda va, vb, vc, im: jnp.where(va > 0.5, vc, vb),  # SEL
        lambda va, vb, vc, im: jnp.trunc(va),
        lambda va, vb, vc, im: jnp.floor(va),
        lambda va, vb, vc, im: jnp.ceil(va),
        lambda va, vb, vc, im: jnp.abs(va),
        lambda va, vb, vc, im: jnp.sign(va),
        lambda va, vb, vc, im: jnp.isfinite(va).astype(F),
        lambda va, vb, vc, im: jnp.fmod(va, vb),  # REM (trunc-signed)
        lambda va, vb, vc, im: jnp.power(va, vb),
        lambda va, vb, vc, im: jnp.exp(va),
        lambda va, vb, vc, im: jnp.log(va),
        lambda va, vb, vc, im: jnp.sqrt(va),
        lambda va, vb, vc, im: jnp.sin(va),
        lambda va, vb, vc, im: jnp.cos(va),
        lambda va, vb, vc, im: jnp.tan(va),
        col,  # COL
        red(jnp.sum),  # RSUM_G
        red(jnp.max),  # RMAX_G
        red(jnp.min),  # RMIN_G
        lambda va, vb, vc, im: jnp.where(  # SETCOL: va with column im := vb
            jnp.arange(g)[None, :] == im.astype(jnp.int32), vb, va),
    ]


def _execute(prog: VMProgram, pod: PodView, nodes: NodeView,
             bound) -> jax.Array:
    n, g = nodes.gpu_mask.shape
    branches = _branches(n, g)
    inp = _inputs(pod, nodes)
    cap = prog.capacity
    pool = jnp.broadcast_to(
        prog.consts.astype(_ambient_float())[:, None, None],
        (prog.consts.shape[0], n, g))
    regs = jnp.concatenate(
        [inp, pool, jnp.zeros((cap, n, g), _ambient_float())])
    op_base = N_INPUTS + prog.consts.shape[0]

    def body(k, regs):
        res = lax.switch(
            prog.opcode[k], branches,
            regs[prog.a[k]], regs[prog.b[k]], regs[prog.c[k]], prog.imm[k])
        return lax.dynamic_update_index_in_dim(regs, res, op_base + k, 0)

    regs = lax.fori_loop(0, bound, body, regs)
    out = regs[prog.out_reg][:, 0]
    # Non-finite values (a candidate dividing by zero, log of a negative)
    # would hit the int cast below with implementation-defined results;
    # mask them to 0 — the engines' "refuse placement" sentinel — so a
    # pathological candidate degrades deterministically. Identity for
    # finite values, which the cast assumes are integral.
    out = jnp.where(jnp.isfinite(out), out, jnp.zeros_like(out))
    # the policy's jaxpr already ends in an int cast; values are integral
    return out.astype(jnp.int32)


@jax.custom_batching.custom_vmap
def _loop_bound(n_ops: jax.Array) -> jax.Array:
    """Trip count of the op-slot loop: the program's live op count — and,
    under ``vmap`` over stacked programs, ONE scalar for the whole batch,
    the longest live program among the lanes that share the loop.

    A *per-lane* ``n_ops`` under ``vmap`` would be a batched loop bound:
    ``fori_loop`` then lowers to a while_loop whose every iteration
    selects the full [N_INPUTS+CONST_POOL+cap, N, G] register file per
    lane to freeze finished lanes — far more HBM traffic than the ops
    themselves. The bound only has to cover every lane, not be each
    lane's own: a lane shorter than the longest runs its OP_NOP padding
    up to the shared bound (each copies register 0 into a fresh register
    the output never reads), which is semantically free. So the batching
    rule below reduces the lanes' counts to their maximum and declares
    the result UNBATCHED: the loop predicate stays a scalar, nothing is
    selected, and the padding past the longest live program never runs.
    The maximum is taken on the device from the tables already there, so
    a new generation or a hot swap changes a value, never a shape. A
    program mapped with ``in_axes=None`` (serving) never reaches the
    rule; inside ``shard_map`` the maximum is over the device's own
    lanes (no collective)."""
    return n_ops


@_loop_bound.def_vmap
def _loop_bound_lanes(axis_size, in_batched, n_ops):
    del axis_size, in_batched  # one operand: the rule runs only if batched
    # through the primitive again: an enclosing vmap that ALSO batches the
    # programs reduces over its axis the same way
    return _loop_bound(jnp.max(n_ops)), False


def score(prog: VMProgram, pod: PodView, nodes: NodeView) -> jax.Array:
    """Execute a lowered candidate -> i32 scores over the node axis.

    The signature matches ``ParamPolicyFn`` with the program as the
    parameter pytree, so every engine runner (plain, population, trace
    batch, mesh, serving) accepts VM candidates unchanged: stack
    candidates with ``stack_programs`` and pass this as the
    ``param_policy`` of ``make_population_run_fn``. The op-slot loop runs
    the LIVE slots only — to ``n_ops`` for one program, to the longest
    live program of the batch under ``vmap`` (``_loop_bound``) — while
    shapes and the register file stay at the padded capacity, so one
    executable serves every program of a capacity bucket.
    """
    return _execute(prog, pod, nodes, _loop_bound(prog.n_ops))


def register_rows(capacity: int) -> int:
    """Rows of the [rows, N, G] register file ``_execute`` carries through
    the op-slot loop: inputs, the constant pool, one row per op slot."""
    return N_INPUTS + CONST_POOL + int(capacity)


def capacity_bucket(n_ops: int) -> int:
    """Program-capacity bucket for ``n_ops`` live ops: the smallest power
    of two covering it, floored at 64 (``compile_policy``'s own default
    ladder). The serve tier keys its compiled programs on this bucket —
    every champion padding to the same rung shares ONE executable, so a
    hot-swap is a table upload, never a recompile."""
    return max(64, 1 << max(0, int(n_ops) - 1).bit_length())


def pad_capacity(prog: VMProgram, capacity: int) -> VMProgram:
    """Re-pad a program's op arrays to ``capacity`` (NOP fill)."""
    n_live = int(prog.n_ops)
    if n_live > capacity:
        raise VMUnsupported(f"program too long: {n_live} ops > {capacity}")
    cur = prog.capacity
    if cur == capacity:
        return prog
    if cur < capacity:
        pad = capacity - cur

        def ext(x, fill):
            return jnp.concatenate(
                [x, jnp.full((pad,), fill, x.dtype)])

        return prog._replace(
            opcode=ext(prog.opcode, OP_NOP), a=ext(prog.a, 0),
            b=ext(prog.b, 0), c=ext(prog.c, 0), imm=ext(prog.imm, 0.0))
    return prog._replace(
        opcode=prog.opcode[:capacity], a=prog.a[:capacity],
        b=prog.b[:capacity], c=prog.c[:capacity], imm=prog.imm[:capacity])


def stack_programs(progs: Sequence[VMProgram],
                   capacity: Optional[int] = None) -> VMProgram:
    """Stack lowered candidates into ONE batched ``VMProgram`` pytree.

    The shared capacity defaults to the smallest power of two covering the
    longest member (min 32) so one compiled population-engine program
    serves every batch of that bucket. This is the data half of the
    population-batched code-candidate path: the reference evaluates a
    generation by forking a subprocess per candidate (reference:
    funsearch/funsearch_integration.py:535-562); here a generation is one
    stacked pytree handed to one XLA program.
    """
    if not progs:
        raise ValueError("stack_programs needs at least one program")
    longest = max(int(p.n_ops) for p in progs)
    cap = capacity or max(32, 1 << max(0, (longest - 1)).bit_length())
    padded = [pad_capacity(p, cap) for p in progs]
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *padded)


def select_slot(stacked: VMProgram, slot) -> VMProgram:
    """One member of a ``stack_programs`` pytree by (possibly traced) slot
    index — the portfolio serve tier's per-lane dispatch primitive.

    Under ``vmap`` with the stacked program broadcast (``in_axes=None``)
    and ``slot`` batched per lane, this lowers to one gather per table, so
    a single executable answers a batch that MIXES champions: each lane
    reads its own opcode/operand rows out of the resident slot tables.
    The selected program's ``capacity`` stays shape-derived (static under
    tracing); ``n_ops``/``out_reg`` become per-lane traced scalars, and
    ``score`` bounds the shared op-slot loop by the longest program any
    lane of the batch selected (``_loop_bound``)."""
    return jax.tree_util.tree_map(lambda x: x[slot], stacked)


def bucket_lanes(n: int, multiple: int = 1) -> int:
    """Lane count for a batch of ``n`` programs: the next power of two
    (so the jitted population runner retraces per BUCKET, never per
    generation), rounded up to a multiple of ``multiple`` — the mesh
    shard count, so a stacked batch divides evenly over the population
    shards. For power-of-two shard counts (every real topology) the
    round-up is absorbed by the bucket and the bucket set is unchanged.

    Never fewer than two lanes per shard: the batch-of-one program is the
    slow one. XLA folds the size-1 batch axis away, the op-slot loop then
    carries per-slot scalars, and three of the five program-word arrays
    stay in HBM instead of VMEM (optimized HLO, AOT-compiled for v5e). On
    a v5e chip one lane costs 11.5 ms per lockstep event against 1.7 ms
    for two lanes and 2.5 ms for four, with or without ``shard_map``
    (PERF.md, PR 21). A pad lane repeats the last program, so it adds no
    lockstep events and never raises the op-slot loop's bound
    (``_loop_bound``: the longest live program among the lanes).
    """
    pop = max(2, 1 << (max(1, n) - 1).bit_length())
    return max(2, -(-pop // multiple)) * multiple


def lower_fake_candidates(n: int, g: int, need: int, *, capacity: int = 256,
                          seed: int = 7, max_tries_factor: int = 12):
    """Generate + lower ``need`` FakeLLM candidates to VM programs.

    The candidate source for code-candidate throughput (``cli scale
    --code-pop``, ``__graft_entry__.py``): deterministic
    FakeLLM completions, template-filled, lowered via ``compile_policy``;
    junk/too-long candidates are skipped. Returns ``(progs, lower_seconds)``
    — per-candidate host lowering times ride along for the lowering-cost
    metric. The attempt loop is bounded by ``max_tries_factor * need``, so
    a degenerate generator cannot spin forever; callers must check
    ``len(progs)`` against ``need``.
    """
    import time as _time

    from fks_tpu.funsearch import llm, template

    fake = llm.FakeLLM(seed=seed, junk_rate=0.0)
    progs: List[VMProgram] = []
    lower_s: List[float] = []
    for _ in range(max_tries_factor * need):
        if len(progs) >= need:
            break
        code = template.fill_template(fake.complete("x"))
        t0 = _time.perf_counter()
        try:
            prog = compile_policy(code, n, g, capacity=capacity)
        except Exception:  # noqa: BLE001 — outside the VM vocabulary
            continue
        lower_s.append(_time.perf_counter() - t0)
        progs.append(prog)
    return progs, lower_s
