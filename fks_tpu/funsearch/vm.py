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
    -> this module lowers the jaxpr (flat: the transpiler stages `lax`
       primitives, there is no nested call) to a register program:
       every value lives as an f32[N, G] register (scalars and [N] values
       broadcast across G), each op writes one fresh register, reductions
       over the GPU axis re-broadcast their result (`lower_ops`)
    -> `simplify_ops` drops the op slots that compute nothing (the
       if-conversion's 0/1 mask times a factor it already holds, SEL with
       equal arms, a grid rebuilt column by column by a per-GPU generator,
       dead ops: 370 -> 238 for a ledger champion), by rules that keep
       every surviving register bit-identical; a slot costs the same on
       the chip whatever it computes, so fewer slots is what pays
    -> ``VMProgram`` pytree of int32/float32 arrays, padded to a bucket size
       so ONE compiled engine serves every candidate of that bucket
       (`pack_program`).

Execution (`score`, the one entry point, batched or not): ``fori_loop``
over the LIVE op slots — one program's ``n_ops``, a slot a turn; under
``vmap`` over stacked programs the longest live program of the batch as
one unbatched scalar (`_loop_bound`), a block of `SLOT_BLOCK` slots a
turn (`_slot_loop`); NOP padding past the last turn never runs. Each slot
is a ``lax.switch`` over a deliberately minimal 33-opcode table on [N, G]
values (scalar literals load from a pooled register block, not op slots;
boolean and sign ops are canonicalized into arithmetic at lowering — see the
CONST_POOL / opcode-table comments below for the vmap rationale). Under
``vmap`` every branch of that table runs for every lane and a select keeps
one, and eight branches (`WIDE`: REM and the seven transcendentals) are
half of what a slot's write then costs on the chip; so the table has a
NARROW form without them, and the batched loop walks RUNS of turns: a
block in which no lane holds a WIDE opcode runs on the narrow form, every
other block on the whole table, chosen on the device from the opcode words
once an event, so no live slot ever meets a stand-in. Numeric model: everything runs at the
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

import functools
import math
import threading
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

#: the opcodes whose branch is DEAR on the chip: under ``vmap`` every
#: branch of the table runs for every lane in every slot and a select keeps
#: one, so these eight are most of what a slot's write kernel computes
#: whether or not a lane holds one (PERF.md section 6, PR 47: the chip's
#: readings with each group stubbed). The table therefore has a NARROW
#: form, `_branches(n, g, narrow=True)`: every opcode keeps its number and
#: a WIDE one's place holds NOP's stand-in, which costs nothing. The
#: batched op-slot loop runs a block of slots on the narrow form wherever
#: no lane holds a WIDE opcode in it, and on the whole table elsewhere
#: (`_slot_loop`), so no live slot ever selects a stand-in. Membership is
#: by what a branch costs on the chip and by nothing else.
WIDE = (OP_REM, OP_POW, OP_EXP, OP_LOG, OP_SQRT, OP_SIN, OP_COS, OP_TAN)


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

    def bind(self, var, reg: int) -> None:
        self.reg_of[id(var)] = reg

    # -- lowering

    def lower_closed(self, closed, in_regs: Sequence[int]) -> List[int]:
        jaxpr = closed.jaxpr
        if len(jaxpr.invars) != len(in_regs):
            raise VMUnsupported("arity mismatch in the policy's jaxpr")
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
        return [self.reg(v) for v in jaxpr.outvars]

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

    # (a policy's jaxpr is flat, the transpiler stages `lax` primitives:
    # there is no call primitive here, and a `jit` equation is VMUnsupported)

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
        # select_n picks cases[pred]: pred==0 -> x0, pred==1 -> x1. The
        # arm taken is read FIRST, as ``where(pred, x1, x0)`` names it: a
        # literal enters the pool when it is first read, and the pool's
        # order is part of the program
        pred, x1, x0 = (self.reg(eqn.invars[k]) for k in (0, 2, 1))
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
    """The shapes and dtypes a policy is traced at: nothing is allocated."""
    def of(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype)

    i, vn, vg = of(jnp.int32), of(jnp.int32, n), of(jnp.int32, n, g)
    return (PodView(i, i, i, i, i, i),
            NodeView(vn, vn, vn, vn, vn, vn, vg, vg, vg,
                     of(jnp.bool_, n, g), of(jnp.bool_, n)))


_EQNS = threading.local()


def eqns_traced() -> int:
    """Equations of the jaxprs `lower_ops` has traced on THIS thread (770
    for a ledger champion): `lower_pool.lower_source` reads the difference
    over a lowering, the ``eqns`` field of ``tier/transpile/lower``."""
    return getattr(_EQNS, "n", 0)


def lower_ops(code: str, n: int, g: int):
    """The raw lowering of candidate source at padded shapes (n, g), before
    `simplify_ops`: ``(ops, consts, out_reg)`` with ``ops`` a list of
    ``(opcode, a, b, c, imm)`` writing registers ``N_INPUTS + CONST_POOL +
    k`` and ``consts`` the pool's values. Raises as `compile_policy` does;
    the candidate's body runs under a JAX trace once, here."""
    policy = transpiler.build_policy(code)
    pod, nodes = _dummy_views(n, g)
    closed = jax.make_jaxpr(policy)(pod, nodes)
    _EQNS.n = eqns_traced() + len(closed.jaxpr.eqns)

    lo = _Lowerer(n, g)
    # jaxpr invars = flattened (PodView, NodeView) leaves, in pytree order,
    # which matches the register input plan (both are field order)
    outs = lo.lower_closed(closed, [*range(N_INPUTS)])
    return lo.ops, lo.consts, outs[0]


# ----------------------------------------------------------- simplification

_OP_BASE = N_INPUTS + CONST_POOL
_UNARY = frozenset((
    OP_NOP, OP_TRUNC, OP_FLOOR, OP_CEIL, OP_ABS, OP_SIGN, OP_ISFIN, OP_EXP,
    OP_LOG, OP_SQRT, OP_SIN, OP_COS, OP_TAN, OP_COL, OP_RSUM_G, OP_RMAX_G,
    OP_RMIN_G))
_COMPARE = frozenset((OP_GE, OP_GT, OP_LT, OP_LE, OP_EQ, OP_NE, OP_ISFIN))
# 0/1 operands in, 0/1 out (of SEL the arms: whatever the predicate, the
# value is one of them)
_MASK_CLOSED = frozenset((OP_MUL, OP_MAX, OP_MIN, OP_SEL, OP_COL, OP_RMAX_G,
                          OP_RMIN_G))
# column j of the result is NOT the op over column j of its operands (or
# the op is a stack placeholder, which has identity): never lifted out of
# a column chain (`_Simplifier._lift`)
_NOT_ELEMENTWISE = frozenset((OP_NOP, OP_COL, OP_SETCOL, OP_RSUM_G,
                              OP_RMAX_G, OP_RMIN_G))
# a column's expression deeper than this stays column by column: `_lift`
# recurses, and a candidate's source is untrusted
_LIFT_DEPTH = 64


def _operands(op: Tuple) -> Tuple[int, ...]:
    """The registers an op reads (the unused operand fields hold 0)."""
    return op[1:2] if op[0] in _UNARY else op[1:4] if op[0] == OP_SEL \
        else op[1:3]


class _Simplifier:
    """One forward pass over a lowered op list (`simplify_ops`): `add`
    maps an op over already-mapped operands to a register of the new
    program — an input, a pool constant or a new op — that holds the same
    bits for all inputs."""

    def __init__(self, consts: Sequence[float], g: Optional[int]):
        self.ops: List[Tuple[int, int, int, int, float]] = []
        self.consts = list(consts)
        self.cse: Dict[Tuple, int] = {}
        self.g = g  # the grid's width; None: no chain is known to be whole
        # SETCOL register -> the newest links of its chain, oldest first,
        # at most g of them: ((column, value register), ...)
        self.links: Dict[int, Tuple[Tuple[float, int], ...]] = {}
        # the whole chains folded: the last link as asked for -> the grid
        self.folded: Dict[Tuple, int] = {}
        self.lifted: Dict[Tuple, Optional[int]] = {}  # `_lift`'s memo
        # 0/1-typed register -> the set of 0/1 registers whose product it
        # is: itself, unless a MUL of typed registers; the pool's 1 is the
        # empty product. In the 0/1 domain a product IS its factor set.
        self.factors: Dict[int, frozenset] = {
            r: frozenset((r,)) for r in (N_INPUTS - 2, N_INPUTS - 1)}
        self.product: Dict[frozenset, int] = {}
        self.zero = self.one = None  # the pool's +0.0 and 1.0, if there
        for i, v in enumerate(self.consts):
            self._note_const(N_INPUTS + i, v)

    def _note_const(self, r: int, v: float) -> None:
        # +0.0 only: -0.0 is another constant (1 / -0.0) and stays untyped
        if v == 0.0 and math.copysign(1.0, v) > 0 and self.zero is None:
            self.zero = r
            self.factors[r] = frozenset((r,))
        elif v == 1.0 and self.one is None:
            self.one = r
            self.factors[r] = frozenset()

    def _pool_zero(self) -> Optional[int]:
        """The pool's +0.0, appended if absent and the pool has room."""
        if self.zero is None and len(self.consts) < CONST_POOL:
            self.consts.append(0.0)
            self._note_const(N_INPUTS + len(self.consts) - 1, 0.0)
        return self.zero

    def _lift(self, vs: Tuple[int, ...], cols: Tuple[float, ...],
              depth: int = 0) -> Optional[int]:
        """The ONE register whose column ``cols[k]`` holds the bits of
        column ``cols[k]`` of ``vs[k]``, for every k, or None: one register
        for all k is itself; ``COL(x, cols[k])`` of one ``x`` is ``x``;
        the same elementwise opcode for all k is that opcode, once, over
        its operands lifted position by position (``op(p, q)[:, j] ==
        op(p[:, j], q[:, j])``), entered through `add`. A reduction over
        G, a COL of another column and a SETCOL are none of these."""
        if len(set(vs)) == 1:
            return vs[0]
        key = (vs, cols)
        if key not in self.lifted:
            # an input or a pool register is no op; a deeper walk is cut
            liftable = depth < _LIFT_DEPTH and min(vs) >= _OP_BASE
            self.lifted[key] = self._lift_ops(
                [self.ops[v - _OP_BASE] for v in vs], cols,
                depth + 1) if liftable else None
        return self.lifted[key]

    def _lift_ops(self, ops, cols, depth: int) -> Optional[int]:
        op, imm = ops[0][0], ops[0][4]
        if any(o[0] != op for o in ops):
            return None
        if op == OP_COL:
            same = all(o[1] == ops[0][1] and o[4] == col
                       for o, col in zip(ops, cols))
            return ops[0][1] if same else None
        if op in _NOT_ELEMENTWISE or any(o[4] != imm for o in ops):
            return None
        args = [0, 0, 0]
        for p in range(len(_operands(ops[0]))):
            args[p] = self._lift(tuple(o[1 + p] for o in ops), cols, depth)
            if args[p] is None:
                return None
        return self.add(op, *args, imm)

    def add(self, op: int, a: int, b: int, c: int, imm: float) -> int:
        zero, one = self.zero, self.one
        fa, fb, fc = (self.factors.get(x) for x in (a, b, c))
        # -- a whole chain of SETCOLs is the grid its columns came from
        links = None
        if op == OP_SETCOL and self.g:
            links = (self.links.get(a, ()) + ((imm, b),))[-self.g:]
            cols = tuple(col for col, _ in links)
            if sorted(cols) == [*range(self.g)]:
                r = self._lift(tuple(v for _, v in links), cols)
                if r is not None:
                    self.folded[op, a, b, c, imm] = r
                    return r
        # -- moves that hold for any value
        if op in (OP_MAX, OP_MIN) and a == b:
            return a
        if op == OP_SEL:  # where(a > 0.5, c, b)
            if b == c or a == zero:
                return b
            if a == one:
                return c
        # -- identities of the 0/1 domain, on 0/1-typed operands only
        product = None
        if op == OP_MUL and fa is not None and fb is not None:
            if zero in (a, b):
                return zero
            product = fa | fb
            if product == fa:
                return a
            if product == fb:
                return b
            if product in self.product:
                return self.product[product]
        elif op == OP_MAX and fa is not None and fb is not None:
            if zero in (a, b):
                return b if a == zero else a
            if one in (a, b):
                return one
        elif op == OP_SUB and a == one and b == zero:
            return one
        elif op == OP_SUB and a == one and b == one:
            if self._pool_zero() is not None:
                return self.zero
        # -- a new op, unless the same one is there (value numbering)
        key = (op, a, b, c, imm)
        r = self.cse.get(key) if op != OP_NOP else None
        if r is not None:
            return r
        self.ops.append(key)
        r = _OP_BASE + len(self.ops) - 1
        if op != OP_NOP:  # a stack placeholder has identity
            self.cse[key] = r
        if links is not None:
            self.links[r] = links
        # -- is the new register 0/1-typed?
        if product is not None:
            self.factors[r], self.product[product] = product, r
        elif op in _COMPARE or (op == OP_SUB and a == one
                                and fb is not None):
            self.factors[r] = frozenset((r,))
        elif op in _MASK_CLOSED:
            read = (fb, fc) if op == OP_SEL else \
                (fa,) if op in _UNARY else (fa, fb)
            if None not in read:
                self.factors[r] = frozenset((r,))
        return r


_FOLDS = threading.local()


def chains_folded() -> int:
    """Whole column chains `simplify_ops` has folded on THIS thread (5 for
    a ledger champion, 0 for a source that holds none):
    `lower_pool.lower_source` reads the difference over a source, as it
    reads `eqns_traced`."""
    return getattr(_FOLDS, "n", 0)


def simplify_ops(ops, consts, out_reg: int, g: Optional[int]):
    """Drop the op slots of a lowered program that compute nothing:
    ``(ops, consts, out_reg)`` -> the same triple, shorter, registers
    renumbered (``out_reg`` may now name an input or a pool register; the
    pool may have gained a 0.0). ``g`` is the GPU width the program was
    lowered at (`lower_ops`' ``g``), which rule 3 needs (None: not known,
    and rule 3 is not tried). Every rule leaves
    each surviving register bit-identical for ALL inputs at any precision,
    NaN / inf / -0.0 included; nothing here rounds, reorders or folds a
    float:

    1. identities of the 0/1 domain, fired ONLY on operands typed 0/1
       (``gpu_mask``, ``node_mask``, the pool's +0.0 / 1.0, comparisons,
       ISFIN, ``1 - m``, and MUL / MAX / MIN / SEL arms / COL / RMAX_G /
       RMIN_G of typed registers): ``1*m``, ``0*m``, ``m*m``, a product
       that already holds a factor (products are factor sets), ``MAX(0,
       m)``, ``MAX(1, m)``, ``1 - 0``, ``1 - 1``. ``0 * x`` with ``x``
       untyped stays: ``x`` may be NaN or inf;
    2. moves that hold for any value: ``SEL p a a``, SEL on the pool's 0 /
       1 as predicate, ``MAX a a``, ``MIN a a``;
    3. the whole-grid column chain. `_Lowerer._p_concatenate` lowers every
       per-GPU generator of a candidate to ``g`` SETCOLs that build an
       ``[N, G]`` grid a column a slot, and SETCOL takes COLUMN ``imm`` of
       its value operand; so where the newest ``g`` links of a chain write
       each of the columns ``0..g-1`` once, the base never shows, and the
       last link IS (`_Simplifier._lift`) the one register ``v`` every
       link wrote; or ``x``, where link j wrote ``COL(x, j)``; or one
       elementwise op over grids, where every link wrote that op over
       operands of these forms (``MUL(COL(m, j), GE(COL(x, j), p))`` into
       column j is ``MUL(m, GE(x, p))``). The new op goes through rules
       1-2 and value numbering like any other; the earlier links stay
       what they are for another reader. A chain that leaves a column
       out or writes one twice, that mixes two grids or two opcodes, or
       whose value is a reduction over G stays; and with no ``g`` (or a
       wider one than the lowering's) every chain stays, and the program
       is what PR 30's pass made of it, word for word;
    4. value numbering on the rewritten operands, then every op
       ``out_reg`` does not reach goes (the stack placeholders ``OP_NOP``
       among them, once their fold has consumed them).

    With rule 3 a ledger champion keeps 238 of its 370 ops (292 without),
    in the 256 bucket. Not here: a sum over G of a lifted product (it
    would reorder the sum), ``m * (1 - m)``, and numbering that commutes
    operands (ROADMAP S2(e))."""
    s = _Simplifier(consts, g)
    new_of = [*range(_OP_BASE)]  # old register -> new register
    for op, a, b, c, imm in ops:
        new_of.append(s.add(op, new_of[a], new_of[b], new_of[c], float(imm)))
    out = new_of[out_reg]
    live, stack = set(), [out]
    while stack:
        r = stack.pop()
        if r >= _OP_BASE and r not in live:
            live.add(r)
            stack.extend(_operands(s.ops[r - _OP_BASE]))
    order = sorted(live)
    slot = {r: _OP_BASE + k for k, r in enumerate(order)}
    kept = [(op, *(slot.get(x, x) for x in (a, b, c)), imm)
            for op, a, b, c, imm in (s.ops[r - _OP_BASE] for r in order)]
    _FOLDS.n = chains_folded() + len(s.folded)
    return kept, s.consts, slot.get(out, out)


def pack_words(ops, consts, out_reg: int,
               capacity: Optional[int] = None) -> VMProgram:
    """An op list as a ``VMProgram`` of NumPy leaves, padded (OP_NOP) to
    ``capacity``, or to its own `capacity_bucket`, in the dtypes the
    device takes (int32 words; immediates and constants in the ambient
    float). Pure host work, no upload: what a lowering worker sends home
    (``lower_pool.lower_source``) and what `stack_programs` stacks on the
    host for a generation's ONE upload."""
    n_ops = len(ops)
    cap = capacity or capacity_bucket(n_ops)
    if n_ops > cap:
        raise VMUnsupported(f"program too long: {n_ops} ops > {cap}")
    arr = np.zeros((5, cap), np.float64)
    for k, (op, a, b, c, imm) in enumerate(ops):
        arr[:, k] = (op, a, b, c, imm)
    pool = np.zeros(CONST_POOL, np.float64)
    pool[: len(consts)] = consts
    word, real = np.dtype(np.int32), np.dtype(_ambient_float())
    return VMProgram(
        opcode=arr[0].astype(word), a=arr[1].astype(word),
        b=arr[2].astype(word), c=arr[3].astype(word),
        imm=arr[4].astype(real), consts=pool.astype(real),
        n_ops=np.asarray(n_ops, word), out_reg=np.asarray(out_reg, word))


def pack_program(ops, consts, out_reg: int,
                 capacity: Optional[int] = None) -> VMProgram:
    """`pack_words`, uploaded: a ``VMProgram`` of device arrays, eight
    ``jnp.asarray`` transfers a program. For the callers that hold ONE
    program (``compile_policy``: the unbatched tier, serving, the
    portfolio); a generation stays on the host until it is stacked."""
    return jax.tree_util.tree_map(
        jnp.asarray, pack_words(ops, consts, out_reg, capacity))


def compile_policy(code: str, n: int, g: int,
                   capacity: Optional[int] = None) -> VMProgram:
    """Lower candidate source to a VMProgram for padded shapes (n, g):
    `lower_ops`, then `simplify_ops`, then `pack_program` — ``n_ops`` and
    the capacity bucket are the SIMPLIFIED program's.

    Raises TranspileError (invalid candidate) or VMUnsupported (valid but
    outside the VM vocabulary -> caller uses the jit tier). The candidate's
    body runs under a JAX trace once, here, at (n, g): a candidate is valid
    on this path iff that trace succeeds.
    """
    return pack_program(*simplify_ops(*lower_ops(code, n, g), g), capacity)


def compile_for_workload(code: str, workload, capacity: int = 512) -> VMProgram:
    """``compile_policy`` with (n, g) taken from a parsed workload's padded
    cluster shape — the replay / trace-diff entry point
    (fks_tpu.funsearch.tracing), where the caller holds a Workload, not shapes."""
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


def _branches(n: int, g: int, narrow: bool = False):
    """The opcode table, in opcode order. ``narrow``: the same table with
    NOP's branch in the places of the `WIDE` opcodes, for a block of slots
    in which no lane holds one (`_slot_loop`)."""
    F = _ambient_float()

    def red(fn):
        def go(va, vb, vc, im):
            return jnp.broadcast_to(fn(va, axis=1, keepdims=True), (n, g))
        return go

    def col(va, vb, vc, im):
        c = jnp.clip(im.astype(jnp.int32), 0, g - 1)
        return jnp.broadcast_to(_col_picker(0)(va, c), (n, g))

    table = [
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
    if narrow:
        for op in WIDE:
            table[op] = table[OP_NOP]
    return table


def _execute(prog: VMProgram, pod: PodView, nodes: NodeView,
             bound) -> jax.Array:
    n, g = nodes.gpu_mask.shape
    inp = _inputs(pod, nodes)
    cap = prog.capacity
    pool = jnp.broadcast_to(
        prog.consts.astype(_ambient_float())[:, None, None],
        (prog.consts.shape[0], n, g))
    regs = jnp.concatenate(
        [inp, pool, jnp.zeros((cap, n, g), _ambient_float())])
    regs = _slot_loop(0)(regs, prog.opcode, prog.a, prog.b, prog.c, prog.imm,
                         bound)
    out = regs[prog.out_reg][:, 0]
    # Non-finite values (a candidate dividing by zero, log of a negative)
    # would hit the int cast below with implementation-defined results;
    # mask them to 0 — the engines' "refuse placement" sentinel — so a
    # pathological candidate degrades deterministically. Identity for
    # finite values, which the cast assumes are integral.
    out = jnp.where(jnp.isfinite(out), out, jnp.zeros_like(out))
    # the policy's jaxpr already ends in an int cast; values are integral
    return out.astype(jnp.int32)


#: op slots a turn of the op-slot loop where the program words are per
#: lane (`_slot_loop`)
SLOT_BLOCK = 8

_LOOP_COUNT = threading.local()


def _bump(counter: threading.local, which: int) -> None:
    """One more of the ``which``-th of a rule's two outcomes, on this
    thread (`loop_count`, `write_count`, `read_count`)."""
    n = list(getattr(counter, "n", (0, 0)))
    n[which] += 1
    counter.n = tuple(n)


def loop_count() -> Tuple[int, int]:
    """(blocked, plain): how often, on THIS thread, the batching rule of
    the op-slot loop (`_slot_loop`) ran with per-lane program words and
    traced a turn of `SLOT_BLOCK` slots, and how often it fell back to the
    one-slot turn under them (a batched bound, a capacity that is no
    multiple of the block). Counted while a runner is traced, as
    `write_count` is and with the same meaning; a program that no ``vmap``
    batches (serving, one program alone) keeps the one-slot turn and moves
    neither. The evaluator keeps what a runner's first call added
    (``blocked_loops`` / ``plain_loops`` of ``tier/vm_batch/launch``)."""
    return getattr(_LOOP_COUNT, "n", (0, 0))


#: the span fields the evaluator and the serve engines write from
#: `trace_counts`, in its order
TRACE_FIELDS = ("slice_writes", "scatter_writes", "merged_reads",
                "split_reads", "blocked_loops", "plain_loops")


def trace_counts() -> Tuple[int, ...]:
    """`write_count`, `read_count` and `loop_count`, joined: what this
    thread's traces have made of the op-slot loop so far. A runner's
    first call moves it; the difference is kept with the runner."""
    return write_count() + read_count() + loop_count()


def loop_turns(slots: int, blocked: int, plain: int) -> int:
    """Turns the op-slot loop makes over ``slots`` live slots in a runner
    whose trace moved `loop_count` by ``(blocked, plain)``."""
    return -(-slots // SLOT_BLOCK) if blocked and not plain else slots


def loop_wide_turns(opcode, slots: int, blocked: int, plain: int,
                    shards: int = 1) -> int:
    """Of `loop_turns`' turns, those that run the WHOLE opcode table: the
    device's rule (`_slot_loop`: a block is wide if ANY of the lanes that
    share the loop holds a `WIDE` opcode in it) in NumPy, over the live
    turns of a launch whose stacked opcode words are ``opcode`` ``[lanes,
    capacity]``; sharded ``shards`` ways along the lanes, the count of the
    shard that has most. Every turn where the loop is not blocked: the
    one-slot turn knows the whole table only."""
    if not blocked or plain:
        return slots
    opcode = np.asarray(opcode)
    lanes, cap = opcode.shape
    wide = np.isin(opcode, WIDE).reshape(
        shards, lanes // shards, cap // SLOT_BLOCK, SLOT_BLOCK)
    live = wide.any(axis=(1, 3))[:, :loop_turns(slots, blocked, plain)]
    return int(live.sum(axis=1).max())


@functools.lru_cache(maxsize=None)
def _slot_loop(axis: int):
    """``run(regs, opcode, a, b, c, imm, bound)``: the op-slot loop over a
    register file whose row axis is ``axis`` (the number of ``vmap``
    levels that batched the file and not the program), with a batching
    rule of its own for program words that are PER LANE.

    One program turns the loop once a slot: ``fori_loop(0, bound, slot)``.
    With a device-scalar ``bound`` that is a ``while`` whose counter,
    bound and predicate the chip awaits on its scalar core every turn,
    0.40-0.50 us whatever the slot holds (PERF.md section 6, PRs 44 and
    46). Under ``vmap`` over stacked programs the rule turns the loop once
    a BLOCK of `SLOT_BLOCK` slots, ``ceil(bound / SLOT_BLOCK)`` turns under
    the same unbatched bound (`_loop_bound`), slot ``i * SLOT_BLOCK + j``
    with ``j`` static. The slots of the last block past ``bound`` are the
    OP_NOP padding every lane holds there (each copies register ``a`` into
    a fresh row the output never reads), so every register the output
    reads is the one-slot loop's, bit for bit: the rule moves control
    flow, not arithmetic. It needs the table to end on a block (every
    `capacity_bucket` does); under a capacity that does not, or a batched
    bound, which no runner makes, it keeps the one-slot turn and is
    counted (`loop_count`).

    The blocked loop walks RUNS of turns. Under ``vmap`` the opcode is
    per-lane data, so the whole table's 33 branches run for every lane in
    every slot, and the eight of `WIDE` are half of what the write kernel
    then costs on the chip (0.92 us a slot with them, 0.48 without; PERF.md
    section 6, PR 47). From the lanes' opcode words the rule takes, once
    an event and unbatched like the bound (`_block_runs`, `_any_lane`),
    for every block the next block at or after it in which some lane holds
    a WIDE opcode and the next in which none does; an outer ``while`` then
    runs the narrow turns (`_branches(n, g, narrow=True)`) up to the next
    wide block and the wide turns (the whole table) up to the next narrow
    one, each run a ``fori_loop`` that may not turn at all. Every live
    slot selects the branch it selected before and a narrow turn holds no
    lane that could select a stand-in, so again control flow moves and
    arithmetic does not. Every loop is a ``while`` that carries the file,
    which therefore stays in the chip's memory space 1; a ``lax.cond`` a
    turn would lose that, and a conditional a slot is the scalar-core wait
    a slot that the blocks removed (ISSUE 47). A generation that holds no
    WIDE opcode never enters the wide turn; one that holds them in every
    block runs what it ran before, plus a few scalar fetches an event.

    A ``vmap`` that batches the file alone (queries, scenarios; serving
    never batches the program) goes to the loop of the next axis, which
    traces what JAX's own rules trace there, and leaves the rule within
    reach of an enclosing ``vmap`` that batches the programs."""

    def run(block, regs, opcode, a, b, c, imm, bound):
        n, g = regs.shape[axis + 1:]
        op_base = regs.shape[axis] - opcode.shape[-1]

        def slot_on(branches):
            def slot(k, regs):
                op, *operands = _slot_operands(axis)(
                    regs, opcode, a, b, c, imm, k)
                res = _per_file(lambda *xs: lax.switch(op, branches, *xs),
                                axis, in_axes=(0, 0, 0, None))(*operands)
                return _write_row(regs, res, op_base + k, axis)
            return slot

        if block == 1:
            return lax.fori_loop(0, bound, slot_on(_branches(n, g)), regs)

        def turn_on(branches):
            # one trace of the slot a block, not ``block`` of them: a
            # turn's calls share a jaxpr, which XLA inlines into the same
            # kernels
            slot_once = jax.jit(slot_on(branches))

            def turn(i, regs):
                for j in range(block):
                    regs = slot_once(i * block + j, regs)
                return regs
            return turn

        narrow_turn = turn_on(_branches(n, g, narrow=True))
        wide_turn = turn_on(_branches(n, g))
        turns = (bound + block - 1) // block
        next_wide, next_narrow = _block_runs(opcode, block)

        def run_pair(carry):
            # the narrow turns up to the next wide block, then the wide
            # ones up to the next narrow block; either run may be empty
            i, regs = carry
            s = jnp.minimum(next_wide[i], turns)
            regs = lax.fori_loop(i, s, narrow_turn, regs)
            e = jnp.minimum(next_narrow[s], turns)
            return e, lax.fori_loop(s, e, wide_turn, regs)

        return lax.while_loop(lambda carry: carry[0] < turns, run_pair,
                              (jnp.zeros_like(turns), regs))[1]

    loop = jax.custom_batching.custom_vmap(functools.partial(run, 1))

    @loop.def_vmap
    def loop_lanes(axis_size, in_batched, regs, opcode, a, b, c, imm, bound):
        words = (opcode, a, b, c, imm)
        if not any(in_batched[1:]):
            # the next axis' loop writes its row as a slice of the file one
            # axis further right: what the write's own rule says, and
            # counts, where it is reached (`_row_writer`)
            _bump(_WRITE_COUNT, 0)
            return _slot_loop(axis + 1)(regs, *words, bound), True
        block = SLOT_BLOCK
        if in_batched[6] or opcode.shape[-1] % SLOT_BLOCK:
            block = 1
        _bump(_LOOP_COUNT, int(block == 1))
        return jax.vmap(
            functools.partial(run, block),
            in_axes=[0 if b else None for b in in_batched],
            axis_size=axis_size)(regs, *words, bound), True

    return loop


@jax.custom_batching.custom_vmap
def _any_lane(wide: jax.Array) -> jax.Array:
    """``bool[capacity]``, "this slot holds a `WIDE` opcode": one
    program's own and, under ``vmap`` over stacked programs, ONE vector
    for the whole batch, "some lane holds one here". Like `_loop_bound`
    the rule reduces over the lanes that share the loop, declares the
    result UNBATCHED, so that every ``while`` of the loop over runs keeps
    a scalar predicate, and goes through the primitive again for an
    enclosing ``vmap`` that also batches the programs; inside
    ``shard_map`` the lanes are the device's own (no collective). Reached
    from `_slot_loop`'s blocked form only, which serving never traces."""
    return wide


@_any_lane.def_vmap
def _any_lane_lanes(axis_size, in_batched, wide):
    del axis_size, in_batched  # one operand: the rule runs only if batched
    return _any_lane(jnp.any(wide, axis=0)), False


def _block_runs(opcode: jax.Array, block: int):
    """``(next_wide, next_narrow)``, each ``i32[blocks + 1]``, from a
    lane's opcode words under the blocked loop's ``vmap``: the first block
    of ``block`` slots at or after ``i`` in which some lane holds a `WIDE`
    opcode / in which none does, ``blocks`` where there is none (so the
    last entry, which ends a run at the table's end). Taken on the device
    from the tables already there, once an event: a new generation changes
    values, never a shape."""
    wide = _any_lane(functools.reduce(
        jnp.logical_or, (opcode == op for op in WIDE)))
    blocks = opcode.shape[-1] // block
    wide = wide.reshape((blocks, block)).any(axis=1)
    at = jnp.arange(blocks, dtype=jnp.int32)
    end = jnp.full((1,), blocks, jnp.int32)
    return tuple(
        jnp.concatenate([lax.cummin(jnp.where(here, at, blocks),
                                    reverse=True), end])
        for here in (wide, ~wide))


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
    selected, and the padding past the longest live program never runs,
    but for the rest of its last block: the loop such a bound drives makes
    ``ceil(bound / SLOT_BLOCK)`` turns of `SLOT_BLOCK` slots (`_slot_loop`),
    so up to ``SLOT_BLOCK - 1`` more of the same free slots.
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


_WRITE_COUNT = threading.local()


def write_count() -> Tuple[int, int]:
    """(slice, scatter): how often, on THIS thread, the batching rule of
    the op-slot loop's row write (`_row_writer`) kept the write one
    ``dynamic_update_slice`` and how often it fell back to JAX's scatter.
    Counted where the rule runs, that is while a runner is TRACED: once
    per ``vmap`` around a write and per pass the tracer makes over the
    loop's body, so the two counts say which way the writes went, not how
    many there are; an unbatched program never reaches the rule. The
    evaluator and the serve engines keep what a runner's first call added
    (``slice_writes`` / ``scatter_writes`` of ``tier/vm_batch/launch`` and
    ``serve/chunk/enqueue``)."""
    return getattr(_WRITE_COUNT, "n", (0, 0))


@functools.lru_cache(maxsize=None)
def _row_writer(axis: int):
    """``write(regs, res, row)``: ``res`` into row ``row`` of ``regs``,
    whose row axis is ``axis`` (the number of ``vmap`` levels around the
    write), as ONE ``dynamic_update_slice``, with a batching rule of its
    own.

    JAX's rule for ``dynamic_update_slice`` has no case for an unbatched
    index: it always rewrites the update as a scatter over a concatenated
    index vector. On the chip that scatter's bounds test is an AND-reduce
    to ``pred[]`` every slot, and where the file is also gathered from
    with per-lane indices in the same iteration XLA no longer updates the
    loop-carried file in place and copies all of it, every slot (ledger,
    PR 33: half of a code cell's wall). But the row is ``op_base`` plus
    the loop counter, the same for every lane in every runner, so the
    batched write IS a slice update of the batched file one axis further
    right. The rule says so, and says it through the writer of the next
    axis so that an enclosing ``vmap`` (suite x population, ``vmap`` in
    ``shard_map``) keeps the slice at every depth. A batched row, which
    no runner makes, takes JAX's own rule and is counted
    (`write_count`)."""

    def write_plain(regs, res, row):
        return lax.dynamic_update_slice_in_dim(
            regs, jnp.expand_dims(res, axis), row, axis)

    write = jax.custom_batching.custom_vmap(write_plain)

    @write.def_vmap
    def write_lanes(axis_size, in_batched, regs, res, row):
        _bump(_WRITE_COUNT, int(in_batched[2]))
        if in_batched[2]:
            return jax.vmap(
                write_plain, in_axes=[0 if b else None for b in in_batched],
                axis_size=axis_size)(regs, res, row), True
        regs, res = (x if b else jnp.broadcast_to(x, (axis_size,) + x.shape)
                     for x, b in zip((regs, res), in_batched))
        return _row_writer(axis + 1)(regs, res, row), True

    return write


def _write_row(regs: jax.Array, res: jax.Array, row,
               axis: int = 0) -> jax.Array:
    """The op-slot loop's register write (`_row_writer`)."""
    return _row_writer(axis)(regs, res, row)


_READ_COUNT = threading.local()


def read_count() -> Tuple[int, int]:
    """(merged, split): how often, on THIS thread, the batching rule of a
    slot's operand fetch (`_slot_operands`) fetched the three rows with
    one gather and how often it fell back to JAX's own rules (a gather a
    row). Counted while a runner is traced, as `write_count` is and with
    the same meaning; a program that no ``vmap`` batches never moves
    either. The evaluator keeps what a runner's first call added
    (``merged_reads`` / ``split_reads`` of ``tier/vm_batch/launch``)."""
    return getattr(_READ_COUNT, "n", (0, 0))


def _per_file(f, axis: int, in_axes=0):
    """``f`` over the ``axis`` leading batch axes of its arguments (of
    those ``in_axes`` maps)."""
    for _ in range(axis):
        f = jax.vmap(f, in_axes=in_axes)
    return f


@functools.lru_cache(maxsize=None)
def _slot_operands(axis: int):
    """``fetch(regs, opcode, a, b, c, imm, k)``: op slot ``k``'s
    ``(opcode, va, vb, vc, imm)``, the arguments of its ``lax.switch``
    after the table, read from a register file whose row axis is ``axis``
    (the number of ``vmap`` levels that batched the file and not the
    program), with a batching rule of its own for program words that are
    PER LANE.

    One program reads ``opcode[k]``, ``a[k]``, ``b[k]``, ``c[k]`` and
    ``imm[k]`` as scalars and slices three rows out of the file. Under
    ``vmap`` over stacked programs JAX's rules make of that three index
    fetches and three gathers of a row a lane, and on the chip a gather
    costs its start whatever it moves (0.27 us for 8 rows, 0.295 for 24:
    PERF.md section 6, PR 44). The rule stacks the program's three operand arrays to
    ``[lanes, 3, capacity]`` (loop invariants: XLA builds the table
    outside the slot loop), fetches ONE ``[lanes, 3]`` index a slot and
    gathers the three rows with it in ONE gather. The index arithmetic is
    the scalar read's (negative wrap, clamp to the file), so every row is
    the row the three reads return, bit for bit.

    A ``vmap`` that batches the file alone (queries, scenarios; serving
    never batches the program) goes to the fetch of the next axis, which
    traces what JAX's own rules trace there, and leaves the rule within
    reach of an enclosing ``vmap`` that batches the programs. A batched
    slot counter, which no runner makes, takes JAX's rules and is counted
    (`read_count`)."""

    def fetch_plain(regs, opcode, a, b, c, imm, k):
        op = opcode[k]
        rows = _per_file(
            lambda regs: (regs[a[k]], regs[b[k]], regs[c[k]]), axis)(regs)
        return (op, *rows, imm[k])

    def rows_merged(regs, idx):
        idx = jnp.where(idx < 0, idx + regs.shape[axis], idx)
        got = jnp.take(regs, idx, axis=axis, mode="clip")
        return tuple(lax.index_in_dim(got, i, axis, keepdims=False)
                     for i in range(3))

    fetch = jax.custom_batching.custom_vmap(fetch_plain)

    @fetch.def_vmap
    def fetch_lanes(axis_size, in_batched, regs, opcode, a, b, c, imm, k):
        words, per_lane = (opcode, a, b, c, imm), in_batched[1:6]
        if in_batched[6]:
            _bump(_READ_COUNT, 1)
            return jax.vmap(
                fetch_plain, in_axes=[0 if b else None for b in in_batched],
                axis_size=axis_size)(regs, *words, k), (True,) * 5
        if not any(per_lane):
            return (_slot_operands(axis + 1)(regs, *words, k),
                    (False, True, True, True, False))
        _bump(_READ_COUNT, 0)
        opcode, a, b, c, imm = (
            x if b else jnp.broadcast_to(x, (axis_size,) + x.shape)
            for x, b in zip(words, per_lane))
        idx = lax.dynamic_index_in_dim(jnp.stack([a, b, c], axis=1), k,
                                       axis=2, keepdims=False)
        rows = jax.vmap(rows_merged,
                        in_axes=(0 if in_batched[0] else None, 0))(regs, idx)
        return (opcode[:, k], *rows, imm[:, k]), (True,) * 5

    return fetch


@functools.lru_cache(maxsize=None)
def _col_picker(axis: int):
    """``pick(va, c)``: column ``c`` of ``va`` ([..., N, G] with ``axis``
    leading batch axes) as [..., N, 1], COL's read, with a batching rule
    of its own for a column that is PER LANE. One program slices at the
    traced column. Under ``vmap`` over stacked programs that slice is a
    gather from the row the slot's gather just returned, a second kernel
    start in a row, every slot, whatever the opcode; the rule picks the
    column by a select over the G static planes, which fuses into the
    kernels around it. The same value either way: a select moves bits.
    As in `_slot_operands`, a ``vmap`` that batches ``va`` alone goes to
    the picker of the next axis."""

    def pick_plain(va, c):
        return _per_file(
            lambda va: lax.dynamic_slice_in_dim(va, c, 1, axis=1), axis)(va)

    def pick_planes(va, c):
        out = va[..., :1]
        for j in range(1, va.shape[-1]):
            out = jnp.where(c == j, va[..., j:j + 1], out)
        return out

    pick = jax.custom_batching.custom_vmap(pick_plain)

    @pick.def_vmap
    def pick_lanes(axis_size, in_batched, va, c):
        if not in_batched[1]:
            return _col_picker(axis + 1)(va, c), True
        return jax.vmap(pick_planes,
                        in_axes=(0 if in_batched[0] else None, 0))(va, c), True

    return pick


def score(prog: VMProgram, pod: PodView, nodes: NodeView) -> jax.Array:
    """Execute a lowered candidate -> i32 scores over the node axis.

    The signature matches ``ParamPolicyFn`` with the program as the
    parameter pytree, so every engine runner (plain, population, trace
    batch, mesh, serving) accepts VM candidates unchanged: stack
    candidates with ``stack_programs`` and pass this as the
    ``param_policy`` of ``make_population_run_fn``. The op-slot loop runs
    the LIVE slots only — to ``n_ops`` for one program, to the longest
    live program of the batch under ``vmap`` (``_loop_bound``), there
    rounded up to a whole block of slots (``_slot_loop``) — while
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


def _on_host(prog: VMProgram) -> bool:
    """Whether a program's leaves are NumPy (`pack_words`) and not device
    arrays (`pack_program`)."""
    return all(isinstance(x, np.ndarray) for x in prog)


def pad_capacity(prog: VMProgram, capacity: int) -> VMProgram:
    """Re-pad a program's op arrays to ``capacity`` (NOP fill): with NumPy
    where its leaves are NumPy, on the device where they live there."""
    n_live = int(prog.n_ops)
    if n_live > capacity:
        raise VMUnsupported(f"program too long: {n_live} ops > {capacity}")
    cur = prog.capacity
    if cur == capacity:
        return prog
    if cur < capacity:
        pad = capacity - cur
        xp = np if _on_host(prog) else jnp

        def ext(x, fill):
            return xp.concatenate(
                [x, xp.full((pad,), fill, x.dtype)])

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

    Programs of NumPy leaves (`pack_words`) are padded and stacked with
    NumPy and stay on the host: no transfer, no device program, ``n_ops``
    read where it lies; the caller uploads the batch once. Device
    programs (`pack_program`) are stacked on the device as ever, leaf for
    leaf the same values, shapes and dtypes.
    """
    if not progs:
        raise ValueError("stack_programs needs at least one program")
    longest = max(int(p.n_ops) for p in progs)
    cap = capacity or max(32, 1 << max(0, (longest - 1)).bit_length())
    padded = [pad_capacity(p, cap) for p in progs]
    xp = np if all(_on_host(p) for p in padded) else jnp
    return jax.tree_util.tree_map(lambda *xs: xp.stack(xs), *padded)


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
