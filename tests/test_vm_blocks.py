"""The op-slot loop's trip structure (``vm._slot_loop``, PR 46): where the
program words are per lane the loop turns once a block of ``vm.SLOT_BLOCK``
slots under the one unbatched bound, and the slots of the last block past
the bound are the NOP padding every lane holds there. Contract: every lane
of a stack scores, and through an engine places, bit for bit what its
program gives alone through the one-slot loop, whatever the longest live
count is modulo the block, under ``vmap``, under ``vmap`` in ``shard_map``
and under suite x population; a table that does not end on a block keeps
the one-slot turn and is counted.

Since PR 47 the blocked loop walks RUNS of turns: a block in which no lane
holds an opcode of ``vm.WIDE`` runs on the narrow opcode table (NOP's
stand-in in the WIDE places), every other block on the whole one. The same
contract, for stacks whose lanes hold WIDE opcodes in no block, in one
block, in each place of a block, in different and in adjacent blocks, in
the last live block and in every slot; and ``vm.loop_wide_turns``, the
host's count of the wide turns, is the device's rule in NumPy."""
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


#: a run of consecutive WIDE slots applies these in turn to the value
#: before it, whatever that is, and every value stays finite and small:
#: SIN -> [-1, 1], EXP -> [0.37, 2.72], SQRT -> [0.6, 1.65], POW(., 3) ->
#: [0.22, 4.5], LOG -> [-1.5, 1.5], TAN -> [-14.2, 14.2], COS -> [-1, 1],
#: REM(., 3) -> (-1, 1), and round again
_WIDE_RUN = (vm.OP_SIN, vm.OP_EXP, vm.OP_SQRT, vm.OP_POW, vm.OP_LOG,
             vm.OP_TAN, vm.OP_COS, vm.OP_REM)
#: a WIDE slot alone takes one of those that are safe on any finite value
_WIDE_ALONE = (vm.OP_SIN, vm.OP_COS, vm.OP_REM)
_THREE = vm.N_INPUTS + 2    # the pool register that holds 3.0


def _chain(n_ops: int, seed: int, g: int, wide_at=frozenset()):
    """``(ops, consts, out_reg)`` of ``n_ops`` live ops, each reading the
    one before it (so the output needs every slot, the last one most of
    all) and an input or pool register: sums, extrema, column picks and
    writes whose magnitudes stay far below 2^31 over 512 slots. The slots
    of ``wide_at`` hold an opcode of ``vm.WIDE`` applied to the value
    before them (`_WIDE_RUN`, `_WIDE_ALONE`) and the slots after the
    first of them only add and subtract, so that no extremum forgets what
    a WIDE slot computed; the slots before it are what they are without
    it."""
    rng = np.random.default_rng(seed)
    consts = [0.0, 1.0, 3.0, -7.0]
    acc = int(rng.integers(0, vm.N_INPUTS))
    ops, run = [], 0
    for k in range(n_ops):
        other = int(rng.integers(0, vm.N_INPUTS + len(consts)))
        kind = rng.integers(0, 10)
        run = run + 1 if k in wide_at else 0
        if run:
            alone = run == 1 and k + 1 not in wide_at
            op = (int(rng.choice(_WIDE_ALONE)) if alone
                  else _WIDE_RUN[(run - 1) % len(_WIDE_RUN)],
                  acc, _THREE, 0, 0.0)
        elif wide_at and k > min(wide_at):
            op = (int(rng.choice(_STEP[:2])), acc, other, 0, 0.0)
        elif kind < 6:
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


# ------------------------------ narrow turns and wide turns (PR 47)

#: live counts of a WIDE stack's five programs: the longest ends two slots
#: short of its block's end (a NOP tail beside live slots), and 46, 38 and
#: 30 slots of `_WIDE_RUN` end on TAN, whose values spread over -14..14
WIDE_COUNTS = (46, 38, 30, 12, 0)
WIDE_CAP = 64
#: case -> lane -> the slots that hold a WIDE opcode
WIDE_CASES = {
    "none": {},
    "first_block_of_one_lane": {0: {3}},
    **{f"place_{j}_of_a_block": {1: {2 * B + j}} for j in range(B)},
    "different_blocks_of_different_lanes": {0: {5}, 1: {2 * B + 3},
                                            2: {3 * B + 2}},
    "adjacent_blocks": {0: {2 * B - 2, 2 * B - 1},
                        1: {2 * B, 2 * B + 1, 3 * B}, 2: {3 * B + 1}},
    "last_block_beside_the_nop_tail": {0: {45}, 1: {5 * B - 3}},
    "every_slot": {i: set(range(WIDE_COUNTS[i])) for i in range(3)},
}


@functools.lru_cache(maxsize=None)
def _wide_programs(case: str, g: int, cap: int = WIDE_CAP):
    at = WIDE_CASES[case]
    progs = [vm.pack_program(
        *_chain(n, 4700 + i, g, frozenset(at.get(i, ()))), cap)
        for i, n in enumerate(WIDE_COUNTS)]
    for i, p in enumerate(progs):
        got = np.flatnonzero(np.isin(np.asarray(p.opcode), vm.WIDE))
        assert set(got) == set(at.get(i, ())), (case, i)
    return progs


def _count_wide_turns(opcode, slots: int, shards: int) -> int:
    """The device's rule, spelt out: a block is wide on a device if any of
    the device's lanes holds a WIDE opcode in any of its slots; the launch
    reports the device that has most among its live blocks."""
    lanes = opcode.shape[0] // shards
    most = 0
    for d in range(shards):
        wide = 0
        for i in range(-(-slots // B)):
            block = opcode[d * lanes:(d + 1) * lanes, i * B:(i + 1) * B]
            wide += any(int(op) in vm.WIDE for op in block.ravel())
        most = max(most, wide)
    return most


def test_the_wide_cases_hit_every_wide_opcode():
    hit = {int(op) for case in WIDE_CASES
           for p in _wide_programs(case, G)
           for op in np.asarray(p.opcode)} & set(vm.WIDE)
    assert hit == set(vm.WIDE)
    assert vm.OP_NOP not in vm.WIDE and len(set(vm.WIDE)) == len(vm.WIDE)


@functools.lru_cache(maxsize=None)
def _wide_scorer(how: str):
    """``score(stacked[8], pod, nodes) -> [8, ...]``: the lanes under
    ``vmap``; under ``vmap`` in ``shard_map`` over four virtual devices, 2
    lanes each, as the four-chip cell; and under suite x population (the
    programs ride the OUTER ``vmap``, three copies of the views the inner
    one)."""
    from jax.sharding import PartitionSpec as P

    from fks_tpu.parallel import population_mesh
    from fks_tpu.parallel.mesh import POP_AXIS

    lanes = jax.vmap(vm.score, in_axes=(0, None, None))
    if how == "shard_map":
        lanes = jax.shard_map(
            lanes, mesh=population_mesh(jax.devices()[:4]),
            in_specs=(P(POP_AXIS), P(), P()), out_specs=P(POP_AXIS))
    if how == "suite":
        lanes = jax.vmap(jax.vmap(vm.score, in_axes=(None, 0, 0)),
                         in_axes=(0, None, None))
    return jax.jit(lanes)


@pytest.mark.parametrize("case", sorted(WIDE_CASES))
@pytest.mark.parametrize("how", ["vmap", "shard_map", "suite"])
def test_narrow_and_wide_turns_score_each_program_alone(how, case):
    """Every lane's scores are its program's alone through the one-slot
    loop, which knows the whole table only. Under ``shard_map`` each
    device takes its runs from its own two lanes, so the devices walk
    different runs in one program."""
    progs = _wide_programs(case, G)
    stacked = vm.stack_programs(progs + [progs[-1]] * (8 - len(progs)),
                                capacity=WIDE_CAP)
    batched, (_, alone) = _wide_scorer(how), _scorers()
    rng = np.random.default_rng(len(case))
    if how == "vmap":
        before = vm.loop_count()
        jax.make_jaxpr(jax.vmap(vm.score, in_axes=(0, None, None)))(
            stacked, *_rand_views(rng))
        assert tuple(x - y for x, y
                     in zip(vm.loop_count(), before)) == (1, 0)
    for _ in range(2):
        pod, nodes = _rand_views(rng)
        views = (pod, nodes) if how != "suite" else jax.tree_util.tree_map(
            lambda x: jnp.stack([x] * 3), (pod, nodes))
        got = np.asarray(batched(stacked, *views))
        for i, prog in enumerate(progs):
            want = np.asarray(alone(prog, pod, nodes))
            for mine in (got[i] if how == "suite" else got[i:i + 1]):
                np.testing.assert_array_equal(mine, want, err_msg=str(i))
    assert len({got[i].tobytes() for i in range(len(progs))}) > 1


@pytest.mark.parametrize("case", sorted(WIDE_CASES))
@pytest.mark.parametrize("kind", ["population", "shard_map", "suite"])
def test_narrow_and_wide_turns_place_each_program_alone(
        micro_workload, runners, kind, case):
    """`test_blocked_placements_equal_each_program_alone` for the WIDE
    stacks; under ``shard_map`` each device takes its runs from its own
    two lanes, so the devices walk different runs in one program."""
    progs = _wide_programs(case, micro_workload.cluster.g_padded)
    run, lanes, alone = runners(kind)
    stacked = vm.stack_programs(progs + [progs[-1]] * (lanes - len(progs)),
                                capacity=WIDE_CAP)
    res = jax.device_get(run(stacked))
    for i, prog in enumerate(progs):
        want = jax.device_get(alone(prog))
        for field in FIELDS:
            np.testing.assert_array_equal(
                np.asarray(getattr(res, field))[i],
                np.asarray(getattr(want, field)), err_msg=f"{i} {field}")


@pytest.mark.parametrize("case", sorted(WIDE_CASES))
def test_wide_turns_is_the_devices_rule_in_numpy(case):
    progs = list(_wide_programs(case, G))
    slots = max(WIDE_COUNTS)
    turns = vm.loop_turns(slots, 1, 0)
    for lanes, shards in ((5, 1), (8, 1), (8, 4)):
        opcode = np.asarray(vm.stack_programs(
            progs + [progs[-1]] * (lanes - len(progs)),
            capacity=WIDE_CAP).opcode)
        got = vm.loop_wide_turns(opcode, slots, 1, 0, shards)
        assert got == _count_wide_turns(opcode, slots, shards) <= turns
        # the one-slot loop, counted or not: the whole table every turn
        for blocked, plain in ((0, 0), (0, 1), (1, 1)):
            assert vm.loop_wide_turns(opcode, slots, blocked, plain,
                                      shards) == slots
    want = {"none": 0, "every_slot": turns,
            "different_blocks_of_different_lanes": 3, "adjacent_blocks": 3,
            "last_block_beside_the_nop_tail": 2}.get(case, 1)
    assert vm.loop_wide_turns(opcode, slots, 1, 0, 1) == want
    # a block past the live turns is not counted, whatever it holds
    assert vm.loop_wide_turns(opcode, B, 1, 0, 1) == (
        1 if case in ("first_block_of_one_lane", "every_slot",
                      "different_blocks_of_different_lanes") else 0)


# ------------------------------------------ where the blocks do not engage

@pytest.mark.parametrize("cap", [B + 1, 100, 513 if B > 1 else 3])
def test_a_capacity_that_is_no_multiple_keeps_the_one_slot_turn(cap):
    """``pad_capacity`` / ``pack_program`` take any capacity. A table that
    does not end on a block is never read past its end: the rule keeps the
    one-slot loop (one write in the ``while`` body), counts it, and the
    scores are each program's own, WIDE opcodes and all: that loop knows
    the whole table only, so every one of its turns counts as wide."""
    from tests.test_vm_batch import _assert_one_slice_write_a_slot

    assert cap % B
    progs = [vm.pack_program(*_chain(n, cap + n, G, frozenset({1, n - 2})),
                             cap)
             for n in (cap, cap - 1, 3)]
    stacked = vm.stack_programs(progs, capacity=cap)
    pod, nodes = _rand_views(np.random.default_rng(cap))
    batched = jax.vmap(vm.score, in_axes=(0, None, None))
    before = vm.loop_count()
    jaxpr = jax.make_jaxpr(batched)(stacked, pod, nodes)
    assert tuple(x - y for x, y in zip(vm.loop_count(), before)) == (0, 1)
    assert _assert_one_slice_write_a_slot(jaxpr, cap, block=1) == 1
    assert vm.loop_wide_turns(np.asarray(stacked.opcode), cap, 0, 1) == cap
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
    kept = ("blocked_loops", "plain_loops", "turns", "wide_turns")
    assert {k: again[k] for k in kept} == {k: first[k] for k in kept}
    # the same generation's words, counted by the device's rule spelt out
    c = micro_workload.cluster
    progs = [vm.compile_policy(code, c.n_padded, c.g_padded)
             for code in _corpus()[:4]]
    assert first["wide_turns"] == _count_wide_turns(
        np.asarray(vm.stack_programs(progs).opcode), first["slots"], 1)
