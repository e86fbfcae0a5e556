"""The flat step's GPU-axis work over static planes (PR 41).

The step's reductions, its sort and its row pick along the 8-wide GPU axis
are written over the G static planes, so that under a population ``vmap``
XLA may lay ``[lanes, N, G]`` out with the POPULATION on the chip's 128
lanes. Each site is integer arithmetic and must equal the ``reduce`` /
``argsort`` / gather formulation it replaced bit for bit; the layout pin
compiles param256's program for a described v5e (no chip) and is the test
that would have caught 512 vregs an array at 6 % occupancy.
"""
import itertools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fks_tpu.models import parametric
from fks_tpu.ops import allocator
from fks_tpu.sim import flat
from fks_tpu.sim.types import NodeView, PodView

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools"))
import describe_compile  # noqa: E402

MILLI = (0, 250, 500, 1000)


# ------------------------------------------------- site 1: features

#: what each fold of ``parametric._fold_gpus`` was before PR 41: an XLA
#: ``reduce`` along the GPU axis
_REDUCE = {jnp.add: jnp.sum, jnp.maximum: jnp.max, jnp.minimum: jnp.min}


def _random_nodes(n: int, g: int, seed: int) -> NodeView:
    """Integer node views as a run holds them: GPUs per node 0..g (zero-GPU
    nodes among them, the rest of the axis masked), free milli anywhere in
    0..total on every slot, masked ones included."""
    rng = np.random.default_rng(seed)
    num = rng.integers(0, g + 1, n)
    num[:2] = (0, g)
    mask = np.arange(g)[None, :] < num[:, None]
    total = np.where(mask, 1000, 0)
    # masked slots hold junk: the mask, not the value, must exclude them
    left = np.where(mask, rng.choice(MILLI + (125, 999), (n, g)),
                    rng.integers(0, 1001, (n, g)))
    cpu_tot = rng.choice((0, 32000, 64000, 96000), n)
    mem_tot = rng.choice((0, 131072, 262144), n)
    i32 = lambda a: jnp.asarray(a, jnp.int32)  # noqa: E731
    return NodeView(
        cpu_milli_left=i32(rng.integers(0, cpu_tot + 1)),
        cpu_milli_total=i32(cpu_tot),
        memory_mib_left=i32(rng.integers(0, mem_tot + 1)),
        memory_mib_total=i32(mem_tot),
        gpu_left=i32(rng.integers(0, num + 1)), num_gpus=i32(num),
        gpu_milli_left=i32(left), gpu_milli_total=i32(total),
        gpu_mem_total=i32(np.where(mask, 16384, 0)),
        gpu_mask=jnp.asarray(mask), node_mask=jnp.ones(n, bool))


@pytest.mark.parametrize("n", [16, 1528])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_features_over_planes_equal_the_reduce(n, dtype, monkeypatch):
    planes = jax.jit(lambda p, v: parametric.features(p, v, dtype))
    reduce = jax.jit(lambda p, v: parametric.features(p, v, dtype))
    folds = []
    with monkeypatch.context() as m:    # ``reduce`` is traced in here
        m.setattr(parametric, "_fold_gpus",
                  lambda op, x: folds.append(op) or _REDUCE[op](x, axis=1))
        reduce(PodView(*(jnp.int32(0),) * 6), _random_nodes(n, 8, 0))
    assert folds.count(jnp.add) == 3 and len(folds) == 5
    # the formulation under test runs no reduction at all
    assert "reduce" not in str(jax.make_jaxpr(parametric.features)(
        PodView(*(jnp.int32(0),) * 6), _random_nodes(n, 8, 0)))
    for seed in range(3):
        nodes = _random_nodes(n, 8, seed)
        present = sorted(set(np.asarray(nodes.gpu_milli_left).ravel()))
        # 0, every value a GPU holds, and one over each
        millis = sorted({0, *present, *(m + 1 for m in present)})
        for ngpu, milli in itertools.chain(
                ((0, 0),), itertools.product((1, 2, 8), millis)):
            pod = PodView(jnp.int32(4000), jnp.int32(8192), jnp.int32(ngpu),
                          jnp.int32(milli), jnp.int32(0), jnp.int32(10))
            got, want = planes(pod, nodes), reduce(pod, nodes)
            assert got.dtype == want.dtype and got.shape == (n, 16)
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ------------------------------------------------ site 2: the rank

def _best_fit_argsort(milli_left, gpu_mask, gpu_milli_req, num_gpu):
    """``best_fit_gpus`` as it was before PR 41: ``argsort`` and a scatter
    back."""
    g = milli_left.shape[0]
    iota = jnp.arange(g, dtype=jnp.int32)
    eligible = gpu_mask & (milli_left >= gpu_milli_req)
    key = jnp.where(eligible, milli_left * g + iota, 2**30)
    order = jnp.argsort(key)
    rank = jnp.zeros(g, jnp.int32).at[order].set(iota)
    select = eligible & (rank < num_gpu)
    ok = jnp.sum(eligible.astype(jnp.int32)) >= num_gpu
    return select, ok


def _key_rows() -> np.ndarray:
    """Every multiset of 8 keys from ``MILLI``, each in three orders
    (ascending, descending, one fixed shuffle), so that ties among
    eligible GPUs fall on every pattern of slot indices."""
    rng = np.random.default_rng(41)
    rows = []
    for combo in itertools.combinations_with_replacement(MILLI, 8):
        rows += [combo, combo[::-1], tuple(rng.permutation(combo))]
    return np.asarray(sorted(set(rows)), np.int32)


@pytest.mark.parametrize("num_gpu", range(9))
def test_best_fit_rank_equals_argsort_selection(num_gpu):
    keys = _key_rows()                                      # [K, 8]
    masks = np.asarray(list(itertools.product((False, True), repeat=8)))
    assert len(keys) >= 165 and masks.shape == (256, 8)
    both = lambda f: jax.jit(jax.vmap(jax.vmap(           # noqa: E731
        f, in_axes=(None, 0, None, None)), in_axes=(0, None, None, None)))
    new, old = both(allocator.best_fit_gpus), both(_best_fit_argsort)
    for req in (0, 250, 251, 1000, 1001):
        sel, ok = new(keys, masks, jnp.int32(req), jnp.int32(num_gpu))
        sel0, ok0 = old(keys, masks, jnp.int32(req), jnp.int32(num_gpu))
        assert sel.shape == (len(keys), 256, 8) and sel.dtype == bool
        np.testing.assert_array_equal(np.asarray(sel), np.asarray(sel0))
        np.testing.assert_array_equal(np.asarray(ok), np.asarray(ok0))
        sel, ok = np.asarray(sel), np.asarray(ok)
        eligible = masks[None] & (keys[:, None, :] >= req)
        assert not (sel & ~eligible).any()   # ineligible: never selected
        want = np.minimum(eligible.sum(-1), num_gpu)
        np.testing.assert_array_equal(sel.sum(-1), want)
        np.testing.assert_array_equal(ok, eligible.sum(-1) >= num_gpu)


# --------------------------------------------- site 3: the winner's row

@pytest.mark.parametrize("n", [16, 128, 255, 256, 1528])
def test_node_row_equals_the_gather_for_every_winner(n):
    nodes = _random_nodes(n, 8, seed=n)
    grid, mask = nodes.gpu_milli_left, nodes.gpu_mask
    jaxpr = str(jax.make_jaxpr(flat._node_row)(grid, mask, jnp.int32(0)))
    # the fold engages by the node axis' static size alone
    assert ("gather" in jaxpr or "dynamic_slice" in jaxpr) == (n >= 256)
    rows, masks = jax.jit(jax.vmap(flat._node_row, in_axes=(None, None, 0)))(
        grid, mask, jnp.arange(n, dtype=jnp.int32))
    assert rows.dtype == grid.dtype and masks.dtype == bool
    np.testing.assert_array_equal(np.asarray(rows), np.asarray(grid))
    np.testing.assert_array_equal(np.asarray(masks), np.asarray(mask))
    narrow = flat._node_row(grid.astype(jnp.int16), mask, jnp.int32(n - 1))
    assert narrow[0].dtype == jnp.int16   # state_pack's carry keeps its type


# ------------------------------------------------------ the layout pin

def test_param256_compiles_with_the_population_on_the_lanes():
    device = describe_compile.topology_device("v5e:2x2")
    if device is None:
        pytest.skip("no TPU compiler in this installation can describe "
                    "v5e:2x2 (jax.experimental.topologies)")
    with jax.enable_x64(False):   # the chip's program: int32 / float32
        hlo = describe_compile.population(device, "16", 256)
    body = describe_compile.loop_body(hlo)
    ops = [r["op"] for r in body]
    assert "sort" not in ops
    # G (dimension 2) minor is 8 GPUs padded to 128 lanes: 512 vregs an
    # array at 6 % where the population on the lanes takes 32
    g_minor = [(r["name"], a) for r in body for a in r["arrays"]
               if a[1] == (256, 16, 8) and a[2][0] == 2]
    assert not g_minor, g_minor
    assert not describe_compile.operand_layouts(hlo, (256, 16, 8)).get(
        "2,1,0", 0)
    kernels = sum(op in ("fusion", "copy") for op in ops)
    assert 30 <= kernels <= 70, kernels


# --------------------- the interpreter's op-slot loop (PRs 44, 46, 47)

def _described_device():
    device = describe_compile.topology_device("v5e:2x2")
    if device is None:
        pytest.skip("no TPU compiler in this installation can describe "
                    "v5e:2x2 (jax.experimental.topologies)")
    return device


def _vector_kernels(rows):
    """Of a loop's instructions, the kernels: what is not arithmetic on
    the scalar core (the loop counter and the row it writes)."""
    return [r for r in rows if any(dims for _, dims, _ in r["arrays"])]


#: what the branches of ``vm.WIDE`` are inside a compiled write
WIDE_HLO = {"remainder", "power", "exponential", "log", "sqrt", "sine",
            "cosine", "tan"}


@pytest.mark.parametrize("cluster,view", [("16", 16), ("1523", 64)])
def test_codegen_slot_fetches_its_operands_with_one_gather(cluster, view):
    """The batched VM tier's op-slot loop, compiled for a described v5e.
    Since PR 47 it is a loop over RUNS of turns that holds two inner
    ``while``s over the register file, the narrow turn's and the wide
    turn's, each read per BLOCK of ``vm.SLOT_BLOCK`` slots (PR 46). In
    each of the two: a slot is ONE gather from the register file (three
    rows a lane), no gather of COL's own from a row, and one write of the
    file in place in the ONE layout the ``while`` carries (no copy and no
    second layout of it); the index word is fetched once a block; and no
    more than 9 kernels a slot (PR 44: 11, its parent 14, four of them
    gathers). The narrow turn's writes hold no branch of ``vm.WIDE`` and
    the wide turn's hold all eight. The file lies in memory space 1
    wherever the module names it (a ``lax.cond`` a turn loses that, and
    the event's one copy of the file is then priced 66 times dearer:
    ISSUE 47), and no loop copies it."""
    from fks_tpu.funsearch import vm

    lanes, g, block = 8, 8, vm.SLOT_BLOCK
    with jax.enable_x64(False):   # the chip's program: int32 / float32
        hlo = describe_compile.codegen(_described_device(), cluster, lanes)
    # a ledger champion's file: 238 live ops in the 256 bucket, 305 rows
    file_shape = (lanes, vm.register_rows(256), view, g)
    run_loop, *turns = describe_compile.slot_loops(hlo, outer=True)
    assert len(turns) == 2
    carried = [a[2] for r in describe_compile.loop_body(hlo)
               if r["op"] == "while" for a in r["arrays"]
               if a[1] == file_shape]
    assert len(carried) == 1      # the layout the event loop hands over
    wide_ops = []
    for rows in turns:
        kernels = _vector_kernels(rows)
        inside = {r["name"]: describe_compile.fused_ops(hlo, r)
                  for r in kernels}
        gathers = [r for r in kernels if "gather" in inside[r["name"]]]
        assert [r["arrays"][0][1] for r in gathers] \
            == [(3 * lanes, view, g)] * block
        writes = [r for r in kernels for a in r["arrays"]
                  if a[1] == file_shape]
        files = [(r["op"], r["arrays"][0][2],
                  inside[r["name"]].count("dynamic-update-slice"))
                 for r in writes]
        assert files == [("fusion", carried[0], 1)] * block, files
        wide_ops.append([WIDE_HLO & set(inside[r["name"]]) for r in writes])
        # the [lanes, 3, 1] index word of every slot of the block: one fetch
        fetches = [r for r in kernels
                   if (lanes, 3, 1) in [a[1] for a in r["arrays"]]]
        assert len(fetches) == 1 and len(fetches[0]["arrays"]) == block
        assert len(kernels) <= 9 * block, [r["name"] for r in kernels]
    assert wide_ops == [[set()] * block, [WIDE_HLO] * block], wide_ops
    # what the run loop adds is bookkeeping: the two turns' loops and no
    # kernel over the file
    assert [a[1] for r in _vector_kernels(run_loop) if r["op"] != "while"
            for a in r["arrays"] if a[1] == file_shape] == []
    assert not [r for rows in (run_loop, *turns) for r in rows
                if r["op"] == "copy"
                and file_shape in [a[1] for a in r["arrays"]]]
    # memory space 1 wherever the module names the file
    named = describe_compile.array_mentions(hlo, file_shape)
    assert len(named) >= 2 * block and all("S(1)" in m for m in named), \
        [m for m in named if "S(1)" not in m]
    if cluster == "16":     # the event loop is still found where a
        # blocked turn is the larger body of the two
        assert len(turns[0]) > len(describe_compile.loop_body(hlo))


def test_whatif_slot_loop_is_the_scalar_one():
    """Serving never batches the program, so no rule of the fetch is
    reached: its slot is what it was before PR 44, four scalar fetches,
    the rows by dynamic-slice, a real conditional and the in-place
    write, with no gather in it."""
    import collections

    with jax.enable_x64(False):
        hlo = describe_compile.whatif(_described_device(), "1523", 2)
    rows = describe_compile.slot_loop(hlo)
    assert collections.Counter(r["op"] for r in rows) == {
        "add": 6, "compare": 4, "select": 4, "dynamic-slice": 4,
        "fusion": 4, "clamp": 1, "conditional": 1}
    inside = [op for r in rows for op in describe_compile.fused_ops(hlo, r)]
    assert "gather" not in inside
    assert inside.count("dynamic-update-slice") == 1
