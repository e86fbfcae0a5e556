"""Restricted-Python -> vectorized JAX policy compiler.

This is the TPU-native answer to the reference's sandboxed interpretation of
evolved code: where the reference ``exec``s candidate source and calls the
resulting scalar ``priority_function(pod, node)`` once per node per event
(reference: funsearch/funsearch_integration.py:67-101,
funsearch/safe_execution.py:126-168), here the SAME source is compiled once
into a jit-traceable ``PolicyFn`` that scores ALL nodes in one fused vector
program — so evolved candidates run inside the device event loop at zoo-policy
speed, with no Python in the hot path.

Lowering rules (SURVEY.md §7 "dynamic policy code on device"):
- every value is (broadcastable to) an array over the node axis N;
- ``if``/``elif``/``else`` -> both branches execute, assignments blend under
  the branch predicate (a select) — classic predication;
- ``return`` -> a per-lane ``returned`` mask + first-return-wins value blend;
- ``for gpu in node.gpus`` -> a static unrolled loop over the padded GPU
  axis G, body masked by ``gpu_mask[:, g]`` (real-GPU lanes only);
- ``a and b`` / ``a or b`` keep Python value semantics
  (``where(truthy(a), b, a)`` / ``where(truthy(a), a, b)``);
- ``int(x)`` truncates toward zero like Python; ``//``/``%`` follow Python
  sign semantics (numpy matches for these);
- the final result is truncated to int32 — the engine's score contract.

Divergence from the reference, by design: arithmetic faults (division by
zero, log of a negative) do not raise — lanes whose score comes out
non-finite score 0 (refuse) instead of aborting the whole candidate. The
reference maps such candidates to fitness 0 via the exception path
(funsearch_integration.py:63-64); here they merely refuse the affected
nodes. The prompt instructs guarded division, and differential tests only
use guarded candidates.
"""
from __future__ import annotations

import ast
import math
import threading
from typing import Any, Dict, Optional

import jax
import numpy as np
from jax import lax

from fks_tpu.funsearch import sandbox
from fks_tpu.sim.types import NodeView, PodView, PolicyFn


class TranspileError(ValueError):
    """Candidate uses syntax outside the JAX-lowerable subset."""


# ---------------------------------------------------------- staged arithmetic
#
# Every value `_Interp` computes is a Python scalar or an array (under a
# trace, a tracer) of rank 0, [N] or [N, G]. The helpers below stage the `lax`
# primitives that `jax.numpy` binds for such operands, with `jax.numpy`'s own
# promotion (weak Python scalars against bool / int / float arrays:
# `jax.dtypes.result_type`, memoised per combination of kinds) and nothing
# between them and the primitive. Under ``jax.make_jaxpr`` a `jax.numpy` call
# is a `pjit` cache miss that traces a jaxpr of its own in Python (414 of them,
# 150 of 244 ms of a ledger champion's profiled lowering: PERF.md section 6,
# PR 50); a `lax` call is one bind. A policy's jaxpr therefore holds
# `jax.numpy`'s primitives in `jax.numpy`'s order and no nested call; the one
# equation that went is the conversion of a scalar literal that sat inside a
# nested ``_where``: a Python scalar is converted here (`_convert`), as the
# trace folded it everywhere else.

_BOOL, _I32 = np.dtype(np.bool_), np.dtype(np.int32)
_PY = (bool, int, float)


def _float():
    """The ambient float: f64 under x64 (tests, golden parity), else f32."""
    return jax.dtypes.canonicalize_dtype(np.float64)


def _default_int():
    return jax.dtypes.canonicalize_dtype(np.int64)


def _aval(v):
    try:
        return v.aval
    except AttributeError:  # a numpy scalar: `_convert`'s, `_const`'s
        return jax.typeof(v)


def _kind(v):
    """What promotion sees of ``v``: the type of a Python scalar, else
    ``(dtype, weak_type)``."""
    if type(v) in _PY:
        return type(v)
    a = _aval(v)
    return a.dtype, a.weak_type


def _dtype(v):
    return _join(v)[0] if type(v) in _PY else _aval(v).dtype


def _shape(v):
    return () if type(v) in _PY else _aval(v).shape


def _is_int(v) -> bool:
    return np.issubdtype(_dtype(v), np.integer)


def _is_float(v) -> bool:
    return np.issubdtype(_dtype(v), np.floating)


_JOINS: Dict[tuple, tuple] = {}


def _join(*vals):
    """``(dtype, weak_type)`` that ``vals`` are promoted to."""
    key = (_float(), *map(_kind, vals))
    out = _JOINS.get(key)
    if out is None:
        dtype, weak = jax.dtypes.result_type(*vals, return_weak_type_flag=True)
        out = _JOINS[key] = (np.dtype(dtype), bool(weak))
    return out


def _convert(v, dtype, weak=False):
    """``v`` as ``dtype``. No equation for an array that is one already,
    and none for a Python scalar: it is converted here, as the trace
    would fold the equation (a weak type is a Python scalar's own, and a
    Python float that stays one stays unrounded)."""
    if type(v) in _PY:
        if not weak:
            return np.asarray(v).astype(dtype)
        return v if _join(v)[0] == dtype else dtype.type(v).item()
    a = _aval(v)
    if a.dtype == dtype and a.weak_type == weak and isinstance(v, jax.Array):
        return v
    return lax.convert_element_type_p.bind(
        v, new_dtype=dtype, weak_type=weak, sharding=None)


def _astype(v, dtype):
    return _convert(v, np.dtype(dtype), False)


def _promote(*vals):
    dtype, weak = _join(*vals)
    return [_convert(v, dtype, weak) for v in vals]


def _promote_numeric(*vals):
    """`_promote`, bools as ints."""
    dtype, weak = _join(*vals)
    if dtype == _BOOL:
        dtype = _default_int()
    return [_convert(v, dtype, weak) for v in vals]


def _binary(prim, on_bool=None):
    """``prim`` on promoted operands; ``+`` and ``*`` are OR and AND
    (``on_bool``) where both are bools, as `jax.numpy` has them."""
    def go(a, b):
        a, b = _promote(a, b)
        if on_bool is not None and _dtype(a) == _BOOL:
            return on_bool(a, b)
        return prim(a, b)
    return go


_plus = _binary(lax.add, lax.bitwise_or)
_times = _binary(lax.mul, lax.bitwise_and)
_sub, _min, _max = _binary(lax.sub), _binary(lax.min), _binary(lax.max)
_eq, _ne = _binary(lax.eq), _binary(lax.ne)
_lt, _le = _binary(lax.lt), _binary(lax.le)
_gt, _ge = _binary(lax.gt), _binary(lax.ge)
_and, _or = _binary(lax.bitwise_and), _binary(lax.bitwise_or)


def _not(v):
    return (not v) if type(v) is bool else lax.bitwise_not(v)


def _abs(v):
    return v if _dtype(v) == _BOOL else lax.abs(v)


def _div(a, b):
    """True division: ints divide as the float of their width."""
    dtype, weak = _join(a, b)
    if not np.issubdtype(dtype, np.inexact):
        dtype = jax.dtypes.canonicalize_dtype(
            np.float64 if dtype.itemsize == 8 else np.float32)
    return lax.div(_convert(a, dtype, weak), _convert(b, dtype, weak))


def _round(v):
    """Half to even, as ``jax.numpy.round``; an int is whole already."""
    return v if _is_int(v) else lax.round(
        v, lax.RoundingMethod.TO_NEAREST_EVEN)


def _const(like, value):
    """``value`` as a scalar of ``like``'s dtype."""
    return np.array(value, _dtype(like))


def _full(shape, v, dtype):
    return lax.broadcast_in_dim(_astype(v, dtype), shape, ())


def _broadcast(v, shape):
    """A scalar over ``shape``; an array of that shape as it is."""
    return v if _shape(v) == shape else lax.broadcast_in_dim(v, shape, ())


def _where(mask, new, old):
    """``where(mask, new, old)`` on a bool ``mask``; a rank-0 mask selects
    without being broadcast."""
    new, old = _promote(new, old)
    shape = max(_shape(new), _shape(old), key=len)
    if _shape(mask):
        shape = max(shape, _shape(mask), key=len)
        mask = _broadcast(mask, shape)
    return lax.select(mask, _broadcast(new, shape), _broadcast(old, shape))


def _trunc(a):
    """A float rounded toward zero."""
    return _where(lax.lt(a, _const(a, 0)), lax.ceil(a), lax.floor(a))


def _floor_divide(a, b):
    """``a // b`` with Python's sign, as ``jax.numpy.floor_divide``."""
    a, b = _promote_numeric(a, b)
    if _is_int(a):
        quotient = lax.div(a, b)
        select = _and(_ne(lax.sign(a), lax.sign(b)), _ne(lax.rem(a, b), 0))
        return _where(select, _sub(quotient, 1), quotient)
    mod = lax.rem(a, b)
    div = lax.div(lax.sub(a, mod), b)
    ind = lax.bitwise_and(_ne(mod, 0), _ne(lax.sign(b), lax.sign(mod)))
    return lax.round(lax.select(ind, _sub(div, _const(div, 1)), div))


def _mod(a, b):
    """``a % b`` with the sign of ``b``, as ``jax.numpy.remainder``."""
    a, b = _promote_numeric(a, b)
    zero = _const(a, 0)
    if _is_int(b):
        b = _where(_eq(b, 0), _full(_shape(b), 1, _dtype(b)), b)
    trunc_mod = lax.rem(a, b)
    not_zero = lax.ne(trunc_mod, zero)
    do_plus = lax.bitwise_and(
        lax.ne(lax.lt(trunc_mod, zero), lax.lt(b, zero)), not_zero)
    return lax.select(do_plus, lax.add(trunc_mod, b), trunc_mod)


def _power(a, b):
    """``a ** b`` as ``jax.numpy.power``: a static int exponent is
    `lax.integer_pow`, ints to an int power square and multiply, a float to
    an int power is `lax.pow` on the operands as they are."""
    if type(b) is int:
        return lax.integer_pow(*_promote_numeric(a), b)
    pa, pb = _promote_numeric(a, b)
    if _is_int(pa):
        zero, one = _const(pb, 0), _const(pb, 1)
        acc = _where(lax.bitwise_and(lax.eq(pa, zero), lax.ne(pb, zero)),
                     zero, one)
        for _ in range(6):  # more bits would overflow for any base > 1
            acc = _where(lax.bitwise_and(pb, one), lax.mul(acc, pa), acc)
            pa = lax.mul(pa, pa)
            pb = lax.shift_right_logical(pb, one)
        return acc
    if _is_float(a) and _is_int(b):
        return lax.pow(a, b)
    return lax.pow(pa, pb)


def _col(grid, g: int):
    """Column ``g`` of an [N, G] array (the primitives bound as they are:
    `lax.slice` and `lax.squeeze` canonicalise what is canonical here,
    at two thirds of the pair's cost, 96 times a champion)."""
    n = _shape(grid)[0]
    return lax.squeeze_p.bind(
        lax.slice_p.bind(grid, start_indices=(0, g),
                         limit_indices=(n, g + 1), strides=None),
        dimensions=(1,))


def _stack(cols):
    """[N] arrays side by side: [N, len(cols)]."""
    cols = _promote(*(lax.expand_dims(c, (1,)) for c in cols))
    while len(cols) > 1:  # jax.numpy concatenates sixteen at a time
        cols = [lax.concatenate(cols[i:i + 16], 1)
                for i in range(0, len(cols), 16)]
    return cols[0]


def _sum_rows(grid):
    """``sum(axis=1)``: bools count as int32, and an int narrower than the
    default int sums in the default int (int64 under x64)."""
    if _dtype(grid) == _BOOL:
        grid = _astype(grid, _I32)
    dtype = _dtype(grid)
    if np.issubdtype(dtype, np.signedinteger) \
            and dtype.itemsize < _default_int().itemsize:
        dtype = _default_int()
    return lax.reduce_sum(_astype(grid, dtype), (1,))


def _min_rows(grid):
    return lax.reduce_min(_astype(grid, _dtype(grid)), (1,))


def _max_rows(grid):
    return lax.reduce_max(_astype(grid, _dtype(grid)), (1,))


def _any_rows(grid):
    return lax.reduce_or(grid, (1,))


def _extreme(dtype, low: bool):
    """The least (``low``) or greatest value of ``dtype``."""
    if np.issubdtype(dtype, np.integer):
        info = np.iinfo(dtype)
        return int(info.min if low else info.max)
    return -math.inf if low else math.inf


def _take_rows(grid, idx):
    """``grid[i, idx[i]]`` as an [N, 1] array, an index below zero counted
    from the row's end (``jax.numpy.take_along_axis`` on ``idx[:, None]``)."""
    n, g = _shape(grid)
    idx = _astype(lax.expand_dims(idx, (1,)), _default_int())
    idx = lax.select(_lt(idx, 0), lax.add(idx, _const(idx, g)), idx)
    dnums = lax.GatherDimensionNumbers(
        offset_dims=(), collapsed_slice_dims=(1,), start_index_map=(1,),
        operand_batching_dims=(0,), start_indices_batching_dims=(0,))
    return lax.gather(grid, lax.reshape(idx, (n, 1, 1)), dnums, (1, 1),
                      mode="fill")


# ------------------------------------------------------------ object model

class _Pod:
    """Scalar pod fields (rank 0: `lax` broadcasts them over N)."""

    FIELDS = ("cpu_milli", "memory_mib", "num_gpu", "gpu_milli",
              "creation_time", "duration_time")

    def __init__(self, pod: PodView):
        self._pod = pod

    def attr(self, name: str):
        if name not in self.FIELDS:
            raise TranspileError(f"unknown pod attribute {name!r}")
        return getattr(self._pod, name)


class _GpuList:
    """``node.gpus`` — iteration yields one padded-GPU column at a time."""

    def __init__(self, nodes: NodeView):
        self.nodes = nodes

    @property
    def count(self):
        return self.nodes.num_gpus  # i32[N] == len(node.gpus) per node

    @property
    def padded(self) -> int:
        return self.nodes.gpu_mask.shape[1]


class _Gpu:
    """One column g of the per-GPU arrays. ``memory_mib_left`` maps to the
    static total: the reference never allocates GPU memory
    (SURVEY.md §2 fine print 11)."""

    def __init__(self, nodes: NodeView, g: int):
        self.nodes, self.g = nodes, g

    def attr(self, name: str):
        n, g = self.nodes, self.g
        if name == "gpu_milli_left":
            return _col(n.gpu_milli_left, g)
        if name == "gpu_milli_total":
            return _col(n.gpu_milli_total, g)
        if name in ("memory_mib_left", "memory_mib_total"):
            return _col(n.gpu_mem_total, g)
        raise TranspileError(f"unknown gpu attribute {name!r}")


class _SortedVals:
    """``sorted(expr for gpu in node.gpus [if cond])`` — per-node ascending
    values over the padded GPU axis. Masked-out slots sort to the tail via
    a dtype-max sentinel; ``count[N]`` is the per-node live length, so
    indexing can reproduce Python's IndexError as lane poison (the
    reference maps the raised IndexError to candidate fitness 0,
    funsearch_integration.py:63-64; here only the offending lanes refuse).
    """

    def __init__(self, vals, sel):
        big = _extreme(_dtype(vals), low=False)
        if not _is_int(vals):
            big = _const(vals, big)
        self.vals = lax.sort(_where(sel, vals, big), dimension=1)
        self.count = _astype(_sum_rows(sel), _I32)

    def index(self, k: int, mask, interp):
        gp = _shape(self.vals)[1]
        if k >= 0:
            interp.doom(_and(mask, _le(self.count, k)))
            return _col(self.vals, min(k, gp - 1))
        interp.doom(_and(mask, _lt(self.count, -k)))
        idx = _min(gp - 1, _max(0, _plus(self.count, k)))
        return _col(_take_rows(self.vals, idx), 0)


class _Node:
    FIELDS = ("cpu_milli_left", "cpu_milli_total", "memory_mib_left",
              "memory_mib_total", "gpu_left")

    def __init__(self, nodes: NodeView):
        self._nodes = nodes
        self.gpus = _GpuList(nodes)

    def attr(self, name: str):
        if name == "gpus":
            return self.gpus
        if name not in self.FIELDS:
            raise TranspileError(f"unknown node attribute {name!r}")
        return getattr(self._nodes, name)


def _to_inexact(v):
    """Float coercion matching the reference's numeric model: CPython
    computes ``/`` and ``math.*`` in binary64 regardless of operand types
    (reference: funsearch/safe_execution.py math whitelist), so integral
    operands are promoted to the ambient float — f64 under x64 (tests,
    golden parity), f32 otherwise (TPU). Without this, JAX's
    ``to_inexact_dtype`` picks f32 for int32 operands and f64 for int64
    ones even under x64, so the SAME candidate mixes precisions depending
    on which entity field fed the expression — and the VM tier
    (fks_tpu.funsearch.vm), which runs a single-dtype register model,
    cannot reproduce the mix."""
    return v if _is_float(v) else _astype(v, _float())


def _mathfn(fn):
    def go(*args):
        return fn(*(_to_inexact(a) for a in args))
    return go


_MATH_FNS = {
    "sqrt": _mathfn(lax.sqrt), "log": _mathfn(lax.log),
    "exp": _mathfn(lax.exp), "pow": _mathfn(_power),
    "sin": _mathfn(lax.sin), "cos": _mathfn(lax.cos),
    "tan": _mathfn(lax.tan),
}


def _truthy(v):
    if isinstance(v, bool):
        return v
    return v if _dtype(v) == _BOOL else _ne(v, 0)


def _int_trunc(v):
    """Python int(): truncate toward zero. Non-finite inputs (where Python
    raises OverflowError/ValueError and the reference maps the candidate to
    fitness 0) become 0 — the lane refuses (module docstring divergence)."""
    if _is_int(v):
        return v
    if _dtype(v) == _BOOL:
        return _astype(v, _I32)
    return _astype(_where(lax.is_finite(v), _trunc(v), 0), _I32)


class _Interp:
    """Vectorized symbolic executor over the function AST.

    ``mask`` threading: each block executes under an "active lanes" bool[N];
    assignments and returns only take effect on active lanes. ``returned``
    is global (a return deactivates the lane for the rest of the function,
    including subsequent loop iterations).
    """

    MAX_UNROLL = 64  # static range() loops larger than this are rejected

    def __init__(self, pod: PodView, nodes: NodeView):
        self.n = nodes.node_mask.shape[0]
        self.env: Dict[str, Any] = {
            "pod": _Pod(pod), "node": _Node(nodes), "math": "math",
        }
        self.nodes = nodes
        self.returned = _full((self.n,), False, _BOOL)
        self.retval = _full((self.n,), 0, _I32)
        # lanes where Python would have raised (int() of a non-finite,
        # min()/max() of an empty generator, read of a variable the taken
        # path never assigned); they refuse at the end instead of aborting
        # the whole candidate
        self.poison = _full((self.n,), False, _BOOL)
        # per-variable "assigned on this lane" masks; absent = all lanes
        self.defined: Dict[str, Any] = {}
        # syntactic conditional-nesting depth: 0 = function top level, where
        # a statement executes on every lane that hasn't returned (masks
        # become tracers after the first data-dependent return, so
        # "unconditional" must be tracked syntactically, not by value)
        self.cond_depth = 0

    def live(self, mask):
        """The lanes of ``mask`` that have not returned."""
        return _and(mask, _not(self.returned))

    def doom(self, lanes):
        """Poison ``lanes``: Python would have raised there."""
        self.poison = _or(self.poison, lanes)

    # ----- statements

    def run_block(self, stmts, mask):
        for st in stmts:
            self.run_stmt(st, self.live(mask))

    def run_stmt(self, st, mask):
        if isinstance(st, ast.Assign):
            if len(st.targets) != 1 or not isinstance(st.targets[0], ast.Name):
                raise TranspileError("only simple `name = expr` assignment")
            self.assign(st.targets[0].id, self.eval(st.value, mask), mask)
        elif isinstance(st, ast.AugAssign):
            if not isinstance(st.target, ast.Name):
                raise TranspileError("only simple augmented assignment")
            cur = self.load(st.target.id, mask)
            val = self.binop(st.op, cur, self.eval(st.value, mask))
            self.assign(st.target.id, val, mask)
        elif isinstance(st, ast.If):
            cond = _truthy(self.eval(st.test, mask))
            self.cond_depth += 1
            try:
                self.run_block(st.body, _and(mask, cond))
                if st.orelse:
                    self.run_block(st.orelse, _and(mask, _not(cond)))
            finally:
                self.cond_depth -= 1
        elif isinstance(st, ast.Return):
            if st.value is None:
                raise TranspileError("bare return not allowed")
            val = self.eval(st.value, mask)
            active = self.live(mask)
            self.retval = _where(active, val, self.retval)
            self.returned = _or(self.returned, active)
        elif isinstance(st, ast.For):
            self.run_for(st, mask)
        elif isinstance(st, ast.Expr):
            if isinstance(st.value, ast.Constant):  # docstring
                return
            raise TranspileError("expression statements have no effect")
        elif isinstance(st, ast.Pass):
            return
        else:
            raise TranspileError(f"unsupported statement {type(st).__name__}")

    def run_for(self, st, mask):
        if st.orelse:
            raise TranspileError("for/else not supported")
        it = self.eval_iter(st.iter, mask)
        if isinstance(it, _GpuList):
            if not isinstance(st.target, ast.Name):
                raise TranspileError("gpu loop target must be a name")
            self.cond_depth += 1  # bodies run under a per-lane gpu mask
            try:
                for g in range(it.padded):
                    gmask = self.live(_and(mask,
                                           _col(self.nodes.gpu_mask, g)))
                    self.env[st.target.id] = _Gpu(self.nodes, g)
                    self.run_block(st.body, gmask)
            finally:
                self.cond_depth -= 1
            self.env.pop(st.target.id, None)
        elif isinstance(it, _EnumGpus):
            if not (isinstance(st.target, ast.Tuple)
                    and len(st.target.elts) == 2
                    and all(isinstance(e, ast.Name) for e in st.target.elts)):
                raise TranspileError("enumerate target must be `i, gpu`")
            iname, gname = (e.id for e in st.target.elts)
            self.cond_depth += 1
            try:
                for g in range(it.gpus.padded):
                    gmask = self.live(_and(mask,
                                           _col(self.nodes.gpu_mask, g)))
                    self.env[iname] = g
                    self.env[gname] = _Gpu(self.nodes, g)
                    self.run_block(st.body, gmask)
            finally:
                self.cond_depth -= 1
            self.env.pop(iname, None)
            self.env.pop(gname, None)
        elif isinstance(it, range):
            if not isinstance(st.target, ast.Name):
                raise TranspileError("range loop target must be a name")
            if len(it) > self.MAX_UNROLL:
                raise TranspileError(f"range loop longer than {self.MAX_UNROLL}")
            for i in it:
                self.env[st.target.id] = i
                self.run_block(st.body, self.live(mask))
            self.env.pop(st.target.id, None)
        else:
            raise TranspileError(
                "only `for gpu in node.gpus`, enumerate(node.gpus), or "
                "constant range() loops are supported")

    def eval_iter(self, node, mask):
        if (isinstance(node, ast.Call) and isinstance(node.func, ast.Name)):
            if node.func.id == "range":
                args = [self.eval(a, mask) for a in node.args]
                _check_arity("range", len(args))
                if not all(isinstance(a, int) for a in args):
                    raise TranspileError("range() bounds must be static ints")
                return range(*args)
            if node.func.id == "enumerate":
                _check_arity("enumerate", len(node.args))
                inner = self.eval(node.args[0], mask)
                if isinstance(inner, _GpuList):
                    return _EnumGpus(inner)
                raise TranspileError("enumerate() only over node.gpus")
        return self.eval(node, mask)

    # ----- environment

    def assign(self, name: str, val, mask):
        if name in ("pod", "node", "math"):
            raise TranspileError(f"cannot rebind {name!r}")
        if isinstance(val, (_Pod, _Node, _Gpu, _GpuList, _EnumGpus)):
            raise TranspileError("cannot store entity objects in variables")
        active = self.live(mask)
        all_active = _statically_true(active)
        if isinstance(self.env.get(name), _SortedVals) \
                and not isinstance(val, _SortedVals):
            # overwriting a list with a scalar/array: plain rebinding is
            # fine when the statement executes on every lane that hasn't
            # returned (returned lanes can never read the name again);
            # a branch-local overwrite would need lane-wise blending of a
            # list with a scalar, which has no meaning
            if self.cond_depth != 0:
                raise TranspileError(
                    "cannot conditionally overwrite a sorted() list")
            self.env[name] = val
            self.defined.pop(name, None)
            return
        if isinstance(val, _SortedVals):
            # the object holds data for EVERY lane, so a masked first
            # assignment just records which lanes may legally read it
            # (others poison on read, like any conditionally-bound name);
            # lane-wise BLENDING of two different lists is meaningless
            if name in self.env and not all_active:
                raise TranspileError(
                    "cannot conditionally reassign a sorted() list")
            self.env[name] = val
            if name in self.defined:
                self.defined[name] = _or(self.defined[name], active)
            elif not all_active:
                self.defined[name] = active
            return
        if name in self.env:
            old = self.env[name]
            if isinstance(old, (int, float)) and isinstance(val, (int, float)) \
                    and all_active:
                self.env[name] = val  # stay scalar on unconditional paths
            else:
                self.env[name] = _where(active, val, old)
            if name in self.defined:
                self.defined[name] = _or(self.defined[name], active)
        else:
            if isinstance(val, (int, float)) and all_active:
                self.env[name] = val
            else:
                # first assignment under a condition: untaken lanes hold a
                # placeholder 0 and are poisoned if they ever READ it
                # (Python raises UnboundLocalError there -> candidate
                # fitness 0 in the reference; here the lane refuses)
                self.env[name] = _where(active, val, 0)
                if not all_active:
                    self.defined[name] = active

    def load(self, name: str, mask=None):
        if name not in self.env:
            raise TranspileError(f"undefined variable {name!r}")
        if mask is not None and name in self.defined:
            self.doom(_and(mask, _not(self.defined[name])))
        return self.env[name]

    # ----- expressions

    def eval(self, node, mask):
        if isinstance(node, ast.Constant):
            if isinstance(node.value, bool) or isinstance(node.value, (int, float)):
                return node.value
            raise TranspileError(f"unsupported constant {node.value!r}")
        if isinstance(node, ast.Name):
            return self.load(node.id, mask)
        if isinstance(node, ast.Attribute):
            base = self.eval(node.value, mask)
            if isinstance(base, _Pod) or isinstance(base, _Node) \
                    or isinstance(base, _Gpu):
                return base.attr(node.attr)
            raise TranspileError(
                f"attribute access on non-entity value: .{node.attr}")
        if isinstance(node, ast.BinOp):
            return self.binop(node.op, self.eval(node.left, mask),
                              self.eval(node.right, mask))
        if isinstance(node, ast.UnaryOp):
            v = self.eval(node.operand, mask)
            if isinstance(node.op, ast.USub):
                return -v if isinstance(v, (int, float)) else lax.neg(v)
            if isinstance(node.op, ast.UAdd):
                return v
            if isinstance(node.op, ast.Not):
                t = _truthy(v)
                return (not t) if isinstance(t, bool) else _not(t)
            raise TranspileError("unsupported unary operator")
        if isinstance(node, ast.BoolOp):
            # later operands evaluate under the lanes where Python would
            # actually reach them (short-circuit narrowing), so side effects
            # (poison) in an unreached operand can't leak
            out = self.eval(node.values[0], mask)
            reach = mask
            for v in node.values[1:]:
                t = _truthy(out)
                if isinstance(t, bool):
                    if isinstance(node.op, ast.And):
                        out = self.eval(v, reach) if t else out
                    else:
                        out = out if t else self.eval(v, reach)
                elif isinstance(node.op, ast.And):
                    reach = _and(reach, t)
                    out = _where(t, self.eval(v, reach), out)
                else:
                    reach = _and(reach, _not(t))
                    out = _where(t, out, self.eval(v, reach))
            return out
        if isinstance(node, ast.Compare):
            left = self.eval(node.left, mask)
            result = None
            reach = mask
            for op, rhs_node in zip(node.ops, node.comparators):
                rhs = self.eval(rhs_node, reach)
                c = self.compare(op, left, rhs)
                result = c if result is None else _and(result, c)
                if not isinstance(result, bool):
                    reach = _and(reach, result)  # chains short-circuit
                left = rhs
            return result
        if isinstance(node, ast.IfExp):
            cond = _truthy(self.eval(node.test, mask))
            if isinstance(cond, bool):
                return self.eval(node.body if cond else node.orelse, mask)
            a = self.eval(node.body, _and(mask, cond))
            b = self.eval(node.orelse, _and(mask, _not(cond)))
            return _where(cond, a, b)
        if isinstance(node, ast.Call):
            return self.call(node, mask)
        if isinstance(node, ast.Subscript):
            return self.subscript(node, mask)
        raise TranspileError(f"unsupported expression {type(node).__name__}")

    def subscript(self, node, mask):
        base = self.eval(node.value, mask)
        idx = node.slice
        k: Optional[int] = None
        if isinstance(idx, ast.Constant) and isinstance(idx.value, int) \
                and not isinstance(idx.value, bool):
            k = idx.value
        elif isinstance(idx, ast.UnaryOp) and isinstance(idx.op, ast.USub) \
                and isinstance(idx.operand, ast.Constant) \
                and isinstance(idx.operand.value, int):
            k = -idx.operand.value
        if k is None:
            raise TranspileError("subscripts must use a static integer index")
        if isinstance(base, _SortedVals):
            return base.index(k, mask, self)
        if isinstance(base, _GpuList):
            # node.gpus[k]: out-of-range lanes poison (Python IndexError)
            if k < 0:
                raise TranspileError("negative gpu index not supported")
            if k >= base.padded:
                self.doom(mask)
                return _Gpu(self.nodes, 0)
            self.doom(_and(mask, _not(_col(self.nodes.gpu_mask, k))))
            return _Gpu(self.nodes, k)
        raise TranspileError("subscript of unsupported value")

    def binop(self, op, a, b):
        both_py = isinstance(a, (int, float)) and isinstance(b, (int, float))
        if isinstance(op, ast.Add):
            return a + b if both_py else _plus(a, b)
        if isinstance(op, ast.Sub):
            return a - b if both_py else _sub(a, b)
        if isinstance(op, ast.Mult):
            return a * b if both_py else _times(a, b)
        if isinstance(op, ast.Div):
            if both_py:
                return a / b if b != 0 else math.inf  # lowered to refuse later
            return _div(_to_inexact(a), _to_inexact(b))
        if isinstance(op, ast.FloorDiv):
            if both_py:
                return a // b if b != 0 else math.inf
            return _floor_divide(a, b)
        if isinstance(op, ast.Mod):
            if both_py:
                return a % b if b != 0 else math.inf
            return _mod(a, b)
        if isinstance(op, ast.Pow):
            if both_py:
                try:
                    return a ** b
                except (OverflowError, ZeroDivisionError):
                    return math.inf
            return _power(a, b)
        raise TranspileError("unsupported binary operator")

    def compare(self, op, a, b):
        if isinstance(op, ast.Eq):
            return _eq(a, b) if not _is_py(a, b) else a == b
        if isinstance(op, ast.NotEq):
            return _ne(a, b) if not _is_py(a, b) else a != b
        if isinstance(op, ast.Lt):
            return _lt(a, b) if not _is_py(a, b) else a < b
        if isinstance(op, ast.LtE):
            return _le(a, b) if not _is_py(a, b) else a <= b
        if isinstance(op, ast.Gt):
            return _gt(a, b) if not _is_py(a, b) else a > b
        if isinstance(op, ast.GtE):
            return _ge(a, b) if not _is_py(a, b) else a >= b
        raise TranspileError("unsupported comparison")

    def call(self, node, mask):
        if node.keywords:
            raise TranspileError("keyword arguments not supported")
        f = node.func
        if isinstance(f, ast.Attribute):
            if isinstance(f.value, ast.Name) and f.value.id == "math" \
                    and f.attr in _MATH_FNS:
                args = [self.eval(a, mask) for a in node.args]
                _check_arity(f"math.{f.attr}", len(args))
                return _MATH_FNS[f.attr](*args)
            raise TranspileError("only math.<fn> attribute calls allowed")
        if not isinstance(f, ast.Name):
            raise TranspileError("computed call targets not allowed")
        name = f.id

        # reductions over a generator comprehension
        if name in ("sum", "min", "max") and len(node.args) == 1 \
                and isinstance(node.args[0], ast.GeneratorExp):
            return self.reduce_genexp(name, node.args[0], mask)
        if name == "sorted":
            if len(node.args) == 1 \
                    and isinstance(node.args[0], ast.GeneratorExp):
                return _SortedVals(*self.genexp_grid(node.args[0], mask))
            raise TranspileError("sorted() only over a generator")

        args = [self.eval(a, mask) for a in node.args]
        _check_arity(name, len(args))
        if name == "abs":
            (a,) = args
            return abs(a) if isinstance(a, (int, float)) else _abs(a)
        if name in ("min", "max"):
            if len(args) < 2:
                raise TranspileError(f"{name}() needs 2+ args or a generator")
            fn = _min if name == "min" else _max
            py = min if name == "min" else max
            out = args[0]
            for a in args[1:]:
                out = py(out, a) if _is_py(out, a) else fn(out, a)
            return out
        if name == "len":
            (a,) = args
            if isinstance(a, (_GpuList, _SortedVals)):
                return a.count
            raise TranspileError("len() only of node.gpus or sorted(...)")
        if name == "int":
            (a,) = args
            if isinstance(a, (int, float)):
                if not math.isfinite(a):
                    self.doom(mask)
                    return 0
                return int(a)
            if _is_float(a):
                self.doom(_and(mask, _not(lax.is_finite(a))))
            return _int_trunc(a)
        if name == "float":
            (a,) = args
            return float(a) if isinstance(a, (int, float)) \
                else _astype(a, _float())
        if name == "bool":
            (a,) = args
            return _truthy(a)
        if name == "round":
            args2 = args if len(args) == 2 else (args[0],)
            if all(isinstance(a, (int, float)) for a in args2):
                return round(*args2)
            if len(args2) == 2:
                if not isinstance(args2[1], int):
                    raise TranspileError("round() digits must be static")
                s = 10 ** args2[1]
                return _div(_round(_times(args2[0], s)), s)
            return _round(args2[0])
        if name == "sum":
            raise TranspileError("sum() only over a generator")
        raise TranspileError(f"call to unsupported function {name!r}")

    def genexp_grid(self, gen, mask):
        """Evaluate ``(expr for gpu in node.gpus [if cond])`` into
        ``(vals[N, Gp], sel[N, Gp])`` over the padded GPU axis."""
        if len(gen.generators) != 1:
            raise TranspileError("single-clause generators only")
        comp = gen.generators[0]
        if comp.is_async:
            raise TranspileError("async generators not allowed")
        it = self.eval_iter(comp.iter, mask)
        if not isinstance(it, _GpuList):
            raise TranspileError("generators only over node.gpus")
        if not isinstance(comp.target, ast.Name):
            raise TranspileError("generator target must be a name")
        tname = comp.target.id
        saved = self.env.get(tname)
        cols, conds = [], []
        for g in range(it.padded):
            self.env[tname] = _Gpu(self.nodes, g)
            sel = _col(self.nodes.gpu_mask, g)
            for if_ in comp.ifs:
                sel = _and(sel, _truthy(self.eval(if_, mask)))
            cols.append(self.eval(gen.elt, mask))
            conds.append(sel)
        if saved is None:
            self.env.pop(tname, None)
        else:
            self.env[tname] = saved
        vals = _stack([_broadcast(c, (self.n,)) for c in cols])
        return vals, _stack(conds)

    def reduce_genexp(self, name, gen, mask):
        """``sum/min/max(expr for gpu in node.gpus [if cond])`` -> masked
        reduction over the padded GPU axis."""
        vals, sel = self.genexp_grid(gen, mask)
        if name == "sum":
            return _sum_rows(_where(sel, vals, 0))
        # Python min()/max() of an empty iterable raises (-> reference maps
        # the candidate to fitness 0); lanes whose generator selects nothing
        # are poisoned so the identity sentinel can never leak as a score
        self.doom(_and(mask, _not(_any_rows(sel))))
        out = _where(sel, vals, _const(
            vals, _extreme(_dtype(vals), low=name == "max")))
        return _min_rows(out) if name == "min" else _max_rows(out)


class _EnumGpus:
    def __init__(self, gpus: _GpuList):
        self.gpus = gpus


#: name -> (min_args, max_args) for whitelisted calls; malformed arity must
#: reject the candidate (TranspileError), not crash the evolution loop
_ARITY = {
    "abs": (1, 1), "len": (1, 1), "int": (1, 1), "float": (1, 1),
    "bool": (1, 1), "round": (1, 2), "min": (2, None), "max": (2, None),
    "range": (1, 3), "enumerate": (1, 1),
    "math.sqrt": (1, 1), "math.log": (1, 1), "math.exp": (1, 1),
    "math.pow": (2, 2), "math.sin": (1, 1), "math.cos": (1, 1),
    "math.tan": (1, 1),
}


def _check_arity(name: str, n: int) -> None:
    lo, hi = _ARITY.get(name, (0, None))
    if n < lo or (hi is not None and n > hi):
        raise TranspileError(f"{name}() called with {n} argument(s)")


def _is_py(*vals):
    return all(isinstance(v, (int, float, bool)) for v in vals)


def _statically_true(mask) -> bool:
    """True iff ``mask`` is a compile-time constant that is all-True (safe
    under jit: tracers — data-dependent masks — report False)."""
    if isinstance(mask, jax.core.Tracer):
        return False
    try:
        return bool(np.all(np.asarray(mask)))
    except Exception:
        return False


# --------------------------------------------------------------- public API

def canonical_key(code: str) -> str:
    """Compile-cache key: the AST dump, insensitive to comments/whitespace
    (SURVEY.md §7: dedup doubles as compile-cache key)."""
    return ast.dump(ast.parse(code))


_BODY_RUNS = threading.local()


def body_runs() -> int:
    """How many times a policy body built here has run on THIS thread, the
    dry trace included; under ``jit`` / ``make_jaxpr`` / ``eval_shape`` a
    run is a trace. ``backend._evaluate`` reads the difference over its
    transpile stage (the ``traces`` field of ``tier/transpile``)."""
    return getattr(_BODY_RUNS, "n", 0)


def build_policy(code: str,
                 entry_point: str = "priority_function") -> PolicyFn:
    """Validate ``code`` (``sandbox.validate``) and build its vectorized
    PolicyFn WITHOUT running it: the subset checks of ``_Interp`` fire when
    the body first runs. For callers that hold the workload's shape and
    trace the closure at once (``vm.compile_policy``'s ``make_jaxpr``), so
    that one trace is also the validation; everyone else wants
    ``transpile``."""
    r = sandbox.validate(code, entry_point)
    if not r:
        raise TranspileError(f"validation failed: {r.reason}")
    tree = ast.parse(code)
    fn = next(n for n in tree.body if isinstance(n, ast.FunctionDef))
    body = fn.body

    def policy(pod: PodView, nodes: NodeView):
        _BODY_RUNS.n = body_runs() + 1
        interp = _Interp(pod, nodes)
        interp.run_block(body, _full((interp.n,), True, _BOOL))
        vf = interp.retval
        # lanes that never returned, or whose arithmetic went non-finite,
        # refuse (see module docstring divergence note)
        if not _is_int(vf):
            vf = _where(lax.is_finite(vf), vf, 0)
        out = _astype(_int_trunc(vf), _I32)
        return _where(_and(interp.returned, _not(interp.poison)), out, 0)

    return policy


def transpile(code: str, entry_point: str = "priority_function") -> PolicyFn:
    """Validate + compile candidate source into a vectorized PolicyFn, and
    dry-trace it once on 2 x 2 dummy views (``_dry_trace``): the entry
    point of every caller that has no shape to trace at.

    Raises ``TranspileError`` for code outside the lowerable subset (this is
    the TPU-tightened third validation stage, SURVEY.md §2 fine print 10).
    """
    policy = build_policy(code, entry_point)
    _dry_trace(policy)
    return policy


def _dry_trace(policy: PolicyFn) -> None:
    """Abstractly evaluate the lowered policy on tiny dummy views so subset
    violations (unsupported calls, oversized unrolls, unknown attributes)
    surface at transpile time, not at first simulation. Not run on the VM
    path: ``vm.compile_policy`` traces ``build_policy``'s closure at the
    workload's padded shape straight away, and that trace raises the same
    errors."""
    n, g = 2, 2
    i = jax.ShapeDtypeStruct((), _I32)
    pod = PodView(i, i, i, i, i, i)
    vn = jax.ShapeDtypeStruct((n,), _I32)
    vg = jax.ShapeDtypeStruct((n, g), _I32)
    nodes = NodeView(vn, vn, vn, vn, vn, vn, vg, vg, vg,
                     jax.ShapeDtypeStruct((n, g), _BOOL),
                     jax.ShapeDtypeStruct((n,), _BOOL))
    jax.eval_shape(policy, pod, nodes)
