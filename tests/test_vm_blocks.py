"""The op-slot loop's trip structure (``vm._slot_loop``, PR 46): where the
program words are per lane the loop turns once a block of ``vm.SLOT_BLOCK``
slots under the one unbatched bound, and the slots of the last block past
the bound are the NOP padding every lane holds there. Contract: every lane
of a stack scores, and through an engine places, bit for bit what its
program gives alone through the one-slot loop, whatever the longest live
count is modulo the block, under ``vmap``, under ``vmap`` in ``shard_map``
and under suite x population; a table that does not end on a block keeps
the one-slot turn and is counted."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fks_tpu.funsearch import vm
from tests.test_vm import _rand_views, G, N

B = vm.SLOT_BLOCK
#: the longest program's live count is ``BASE[cap] + r`` for the residue
#: ``r`` under test; the other lanes are shorter by amounts that land
#: them on other residues, one lane holds ONE op and one none at all
BASE = {64: 40, 512: 288}
SHORTER = (0, 3, 13)
FIELDS = ("assigned_node", "assigned_gpus", "policy_score", "scheduled_pods",
          "events_processed", "failed", "truncated")

_STEP = (vm.OP_ADD, vm.OP_SUB, vm.OP_MAX, vm.OP_MIN)
_KEEP = (vm.OP_ABS, vm.OP_RMAX_G, vm.OP_RMIN_G, vm.OP_COL, vm.OP_NOP)


def _chain(n_ops: int, seed: int, g: int):
    """``(ops, consts, out_reg)`` of ``n_ops`` live ops, each reading the
    one before it (so the output needs every slot, the last one most of
    all) and an input or pool register: sums, extrema, column picks and
    writes whose magnitudes stay far below 2^31 over 512 slots."""
    rng = np.random.default_rng(seed)
    consts = [0.0, 1.0, 3.0, -7.0]
    acc = int(rng.integers(0, vm.N_INPUTS))
    ops = []
    for k in range(n_ops):
        other = int(rng.integers(0, vm.N_INPUTS + len(consts)))
        kind = rng.integers(0, 10)
        if kind < 6:
            op = (int(rng.choice(_STEP)), acc, other, 0, 0.0)
        elif kind < 8:
            op = (int(rng.choice(_KEEP)), acc, 0, 0, float(rng.integers(g)))
        elif kind < 9:
            op = (vm.OP_SETCOL, acc, other, 0, float(rng.integers(g)))
        else:
            op = (vm.OP_SEL, other, acc, int(rng.integers(vm.N_INPUTS)), 0.0)
        ops.append(op)
        acc = vm.N_INPUTS + vm.CONST_POOL + k
    return ops, consts, acc


def _counts(cap: int, r: int):
    longest = BASE[cap] + r
    return [longest - d for d in SHORTER] + [1, 0]


@functools.lru_cache(maxsize=None)
def _programs(cap: int, r: int, g: int):
    progs = [vm.pack_program(*_chain(n, 1000 * cap + 10 * r + i, g), cap)
             for i, n in enumerate(_counts(cap, r))]
    assert [int(p.n_ops) for p in progs] == _counts(cap, r)
    assert max(_counts(cap, r)) % B == r and cap % B == 0
    assert len({int(p.n_ops) % B for p in progs}) >= 3
    return progs


CASES = [(cap, r) for cap in sorted(BASE) for r in range(B)]


# ------------------------------------------------------------- vm.score

@functools.lru_cache(maxsize=None)
def _scorers():
    return (jax.jit(jax.vmap(vm.score, in_axes=(0, None, None))),
            jax.jit(vm.score))


@pytest.mark.parametrize("cap,r", CASES)
def test_blocked_scores_equal_each_program_alone(cap, r):
    progs = _programs(cap, r, G)
    stacked = vm.stack_programs(progs, capacity=cap)
    batched, alone = _scorers()
    rng = np.random.default_rng(7 * cap + r)
    before = vm.loop_count()
    jax.make_jaxpr(jax.vmap(vm.score, in_axes=(0, None, None)))(
        stacked, *_rand_views(rng))
    assert tuple(x - y for x, y in zip(vm.loop_count(), before)) == (1, 0)
    for _ in range(2):
        pod, nodes = _rand_views(rng)
        got = np.asarray(batched(stacked, pod, nodes))
        assert got.shape == (len(progs), N) and got.dtype == np.int32
        for i, prog in enumerate(progs):
            np.testing.assert_array_equal(
                got[i], np.asarray(alone(prog, pod, nodes)), err_msg=str(i))
    assert len({got[i].tobytes() for i in range(len(progs))}) > 1


def test_a_full_table_runs_its_last_block_and_no_further():
    """A program that fills its capacity: the last turn ends on the
    table's last slot, which the output reads."""
    cap = 64
    full = vm.pack_program(*_chain(cap, 5, G), cap)
    short = vm.pack_program(*_chain(cap - B - 1, 6, G), cap)
    stacked = vm.stack_programs([short, full], capacity=cap)
    batched, alone = _scorers()
    pod, nodes = _rand_views(np.random.default_rng(3))
    got = np.asarray(batched(stacked, pod, nodes))
    for i, prog in enumerate((short, full)):
        np.testing.assert_array_equal(
            got[i], np.asarray(alone(prog, pod, nodes)))


# --------------------------------------------------- through the engines

def _runners(kind: str, wl):
    """``(run(stacked) -> SimResult with a leading lane axis, lanes,
    alone(prog) -> one program's SimResult through the one-slot loop)``."""
    from fks_tpu.parallel import make_sharded_code_eval, population_mesh
    from fks_tpu.scenarios import get_suite
    from fks_tpu.scenarios.robust import make_suite_eval
    from fks_tpu.sim import flat
    from fks_tpu.sim.engine import SimConfig

    cfg = SimConfig()
    if kind == "suite":
        suite = get_suite("smoke3", wl)
        return (make_suite_eval(suite, vm.score, cfg, population=True,
                                engine="exact"), 5,
                make_suite_eval(suite, vm.score, cfg, engine="exact"))
    s0 = flat.initial_state(wl, cfg)
    one = jax.jit(flat.make_param_run_fn(wl, vm.score, cfg))
    alone = lambda prog: one(prog, s0)  # noqa: E731
    if kind == "population":
        run = jax.jit(flat.make_population_run_fn(wl, vm.score, cfg))
        return lambda st: run(st, s0), 5, alone
    # 2 lanes a device, as the four-chip cell
    ev = make_sharded_code_eval(wl, population_mesh(jax.devices()[:4]),
                                cfg=cfg, elite_k=1, engine="flat")
    return lambda st: ev(st, 5)[0], 8, alone


@pytest.fixture(scope="module")
def runners(micro_workload):
    return functools.lru_cache(maxsize=None)(
        lambda kind: _runners(kind, micro_workload))


@pytest.mark.parametrize("cap,r", CASES)
@pytest.mark.parametrize("kind", ["population", "shard_map", "suite"])
def test_blocked_placements_equal_each_program_alone(micro_workload, runners,
                                                     kind, cap, r):
    """The stack through the flat step's population runner, through the
    sharded code eval (``vmap`` in ``shard_map``, four virtual devices, pad
    lanes) and through suite x population (the programs ride the OUTER
    ``vmap``): every lane's placements, GPU picks, fitness and counts are
    what its program gives alone through the one-slot loop."""
    progs = _programs(cap, r, micro_workload.cluster.g_padded)
    run, lanes, alone = runners(kind)
    stacked = vm.stack_programs(progs + [progs[-1]] * (lanes - len(progs)),
                                capacity=cap)
    res = jax.device_get(run(stacked))
    for i, prog in enumerate(progs):
        want = jax.device_get(alone(prog))
        for field in FIELDS:
            np.testing.assert_array_equal(
                np.asarray(getattr(res, field))[i],
                np.asarray(getattr(want, field)), err_msg=f"{i} {field}")


# ------------------------------------------ where the blocks do not engage

@pytest.mark.parametrize("cap", [B + 1, 100, 513 if B > 1 else 3])
def test_a_capacity_that_is_no_multiple_keeps_the_one_slot_turn(cap):
    """``pad_capacity`` / ``pack_program`` take any capacity. A table that
    does not end on a block is never read past its end: the rule keeps the
    one-slot loop (one write in the ``while`` body), counts it, and the
    scores are each program's own."""
    from tests.test_vm_batch import _assert_one_slice_write_a_slot

    assert cap % B
    progs = [vm.pack_program(*_chain(n, cap + n, G), cap)
             for n in (cap, cap - 1, 2)]
    stacked = vm.stack_programs(progs, capacity=cap)
    pod, nodes = _rand_views(np.random.default_rng(cap))
    batched = jax.vmap(vm.score, in_axes=(0, None, None))
    before = vm.loop_count()
    jaxpr = jax.make_jaxpr(batched)(stacked, pod, nodes)
    assert tuple(x - y for x, y in zip(vm.loop_count(), before)) == (0, 1)
    assert _assert_one_slice_write_a_slot(jaxpr, cap, block=1) == 1
    got = np.asarray(batched(stacked, pod, nodes))
    for i, prog in enumerate(progs):
        np.testing.assert_array_equal(
            got[i], np.asarray(vm.score(prog, pod, nodes)))


def test_a_batched_bound_takes_the_counted_fall_back():
    """No runner batches the bound (``vm._loop_bound`` reduces it over the
    lanes). If one did, the loop keeps the one-slot turn, whose batched
    predicate freezes each lane at its own count, and ``loop_count`` says
    so."""
    progs = _programs(64, 3, G)
    stacked = vm.stack_programs(progs, capacity=64)
    pod, nodes = _rand_views(np.random.default_rng(8))
    per_lane = jax.vmap(
        lambda p, pod, nodes: vm._execute(p, pod, nodes, p.n_ops),
        in_axes=(0, None, None))
    before = vm.loop_count()
    got = np.asarray(per_lane(stacked, pod, nodes))
    assert tuple(x - y for x, y in zip(vm.loop_count(), before)) == (0, 1)
    for i, prog in enumerate(progs):
        np.testing.assert_array_equal(
            got[i], np.asarray(vm.score(prog, pod, nodes)))


@pytest.mark.parametrize("in_axes", [(None, 0, 0), None])
def test_an_unbatched_program_never_reaches_the_choice(in_axes):
    """Serving's shape (one program, lanes of views) and one program
    alone: the one-slot loop, and neither count moves."""
    from tests.test_vm_batch import _assert_one_slice_write_a_slot

    prog = _programs(64, 5, G)[0]
    views = [_rand_views(np.random.default_rng(s)) for s in (1, 2, 3)]
    before = vm.loop_count()
    if in_axes is None:
        f, args = vm.score, (prog, *views[0])
    else:
        f = jax.vmap(vm.score, in_axes=in_axes)
        args = (prog, *jax.tree_util.tree_map(lambda *x: jnp.stack(x),
                                              *views))
    jaxpr = jax.make_jaxpr(f)(*args)
    assert vm.loop_count() == before
    assert _assert_one_slice_write_a_slot(jaxpr, 64, block=1) == 1
    got = np.asarray(f(*args))
    if in_axes is not None:
        for i, view in enumerate(views):
            np.testing.assert_array_equal(
                got[i], np.asarray(vm.score(prog, *view)))


@pytest.mark.parametrize("slots,blocked,plain,turns", [
    (292, 1, 0, -(-292 // B)), (292, 3, 0, -(-292 // B)),
    (B, 1, 0, 1), (B + 1, 1, 0, 2), (0, 1, 0, 0),
    (292, 0, 0, 292),       # the rule never ran: serving, one program
    (292, 0, 1, 292),       # the counted fall back
    (292, 1, 1, 292),       # both ways in one runner: the longer says
])
def test_loop_turns(slots, blocked, plain, turns):
    assert vm.loop_turns(slots, blocked, plain) == turns


# ------------------------------------- what the launch span says of it

def test_population_launch_span_carries_the_trip_structure(micro_workload):
    """A single-device generation through ``CodeEvaluator``: the launch
    that traced the (lanes, capacity) bucket counted the blocked loop, the
    count stays with the bucket for every later launch, and ``turns`` is
    the span's ``slots`` in whole blocks."""
    from fks_tpu.funsearch import backend
    from fks_tpu.obs import spans
    from tests.test_vm import _corpus

    ev = backend.CodeEvaluator(micro_workload, vm_batch=True, engine="flat")
    seen = []
    for _ in range(2):
        t0 = spans.LOG.snapshot()[-1].seq if spans.LOG.snapshot() else -1
        assert all(r.ok for r in ev.evaluate(_corpus()[:4]))
        seen.append([r.fields for r in spans.LOG.snapshot()
                     if r.seq > t0 and r.name == "tier/vm_batch/launch"][-1])
    first, again = seen
    assert first["blocked_loops"] >= 1 and first["plain_loops"] == 0
    assert first["turns"] == -(-first["slots"] // B) < first["slots"]
    assert {k: again[k] for k in ("blocked_loops", "plain_loops", "turns")} \
        == {k: first[k] for k in ("blocked_loops", "plain_loops", "turns")}
