"""Population-batched VM evaluation (fks_tpu.funsearch.vm.stack_programs +
backend._run_vm_batch). Contract: a stacked generation through ONE
population-engine launch produces fitness identical to per-candidate
evaluation, with zero per-candidate XLA compiles — the on-device
counterpart of the reference's subprocess fan-out
(funsearch/funsearch_integration.py:535-562)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fks_tpu.funsearch import backend, template, vm
from tests.test_vm import _corpus, _rand_views, G, N


def test_pad_capacity_is_semantically_neutral():
    """NOP padding never changes scores: a program re-padded to twice
    its capacity scores what it scored before."""
    rng = np.random.default_rng(11)
    code = list(template.seed_policies().values())[0]
    prog = vm.compile_policy(code, N, G)
    padded = vm.pad_capacity(prog, 2 * prog.capacity)
    assert padded.capacity == 2 * prog.capacity
    for _ in range(3):
        pod, nodes = _rand_views(rng)
        np.testing.assert_array_equal(
            np.asarray(vm.score(prog, pod, nodes)),
            np.asarray(vm.score(padded, pod, nodes)))


def test_stack_programs_shapes_and_bucket():
    codes = list(template.seed_policies().values())
    progs = [vm.compile_policy(c, N, G) for c in codes]
    stacked = vm.stack_programs(progs)
    longest = max(int(p.n_ops) for p in progs)
    assert stacked.opcode.shape[0] == len(progs)
    cap = stacked.opcode.shape[1]
    assert cap >= longest and cap & (cap - 1) == 0  # pow2 bucket
    assert stacked.n_ops.shape == (len(progs),)


def test_bucket_lanes_never_builds_the_batch_of_one_program():
    """Power-of-two buckets that divide over the shards, and at least two
    lanes per shard: on a v5e the one-lane program costs 6.8x the two-lane
    one per event (PERF.md, PR 21)."""
    assert [vm.bucket_lanes(n) for n in (1, 2, 3, 5, 8, 9)] == \
        [2, 2, 4, 8, 8, 16]
    assert [vm.bucket_lanes(n, 4) for n in (1, 3, 4, 5, 8, 9)] == \
        [8, 8, 8, 8, 8, 16]
    assert vm.bucket_lanes(4, 8) == 16 and vm.bucket_lanes(17, 8) == 32


def test_stacked_scores_match_per_candidate():
    """vmapped score over a stacked generation == per-candidate score,
    integer-exact."""
    rng = np.random.default_rng(5)
    codes = _corpus()[:6]
    progs = [vm.compile_policy(c, N, G) for c in codes]
    stacked = vm.stack_programs(progs)
    pod, nodes = _rand_views(rng)
    batched = jax.jit(jax.vmap(vm.score, in_axes=(0, None, None)))
    got = np.asarray(batched(stacked, pod, nodes))
    for i, prog in enumerate(progs):
        np.testing.assert_array_equal(
            got[i], np.asarray(vm.score(prog, pod, nodes)))


def test_evaluator_batches_a_generation(micro_workload):
    """evaluate() on a mixed generation: VM-able candidates land in ONE
    batched launch, the VM-unsupported one falls to the jit tier, a syntax
    error maps to 0.0 — and every fitness equals evaluate_one's."""
    wl = micro_workload
    vmable = _corpus()[:5]
    hard = template.fill_template(
        "gpus = sorted(g.gpu_milli_left for g in node.gpus)\n"
        "return max(1, gpus[0]) if pod.num_gpu == 0 else 1")
    codes = vmable[:3] + [hard, "def broken(:"] + vmable[3:]

    ev = backend.CodeEvaluator(wl, vm_batch=True)
    recs = ev.evaluate(codes)
    assert len(recs) == len(codes)
    assert ev.vm_batch_count == 1  # one device launch for the generation
    assert ev.vm_count == len(vmable)
    assert ev.compile_count == 1  # only the VM-unsupported candidate
    assert recs[4].score == 0.0 and "syntax" in recs[4].error

    solo = backend.CodeEvaluator(wl, vm_batch=False)
    for rec, code in zip(recs, codes):
        if code == "def broken(:":
            continue
        one = solo.evaluate_one(code)
        assert rec.score == one.score, code
        assert rec.ok == one.ok


def test_single_candidate_keeps_unbatched_vm_tier(micro_workload):
    wl = micro_workload
    ev = backend.CodeEvaluator(wl, vm_batch=True)
    code = list(template.seed_policies().values())[0]
    rec = ev.evaluate([code])[0]
    assert rec.ok
    assert ev.vm_batch_count == 0  # no population program for one lane
    assert ev.vm_count == 1 and ev.compile_count == 0


def test_duplicate_candidates_evaluate_once(micro_workload):
    wl = micro_workload
    ev = backend.CodeEvaluator(wl, vm_batch=True)
    codes = list(template.seed_policies().values())
    recs = ev.evaluate(codes + codes)
    assert ev.vm_count == len(codes)
    for a, b in zip(recs[:len(codes)], recs[len(codes):]):
        assert a.score == b.score


def test_const_pool_overflow_falls_back():
    """>CONST_POOL distinct literals -> VMUnsupported (the jit tier's
    job), never silent pool corruption."""
    body = "score = 1.0\n"
    terms = "\n".join(
        f"    score = score + {i}.{i:03d}1 * pod.cpu_milli"
        for i in range(vm.CONST_POOL + 2))
    code = template.fill_template(body + "    " + terms.strip())
    with pytest.raises(vm.VMUnsupported, match="constants"):
        vm.compile_policy(code, N, G, capacity=512)


def test_const_pool_preserves_signed_zero():
    """-0.0 and 0.0 are distinct pool entries: 1/min(x, -0.0) style math
    must match the jit tier's sign semantics."""
    lo = vm._Lowerer(N, G)
    r_pos = lo.const(0.0)
    r_neg = lo.const(-0.0)
    assert r_pos != r_neg
    import math
    assert math.copysign(1.0, lo.consts[r_neg - vm.N_INPUTS]) == -1.0


def _stack_corpus(wl, n):
    c = wl.cluster
    progs = [vm.compile_policy(code, c.n_padded, c.g_padded)
             for code in _corpus()[:n]]
    return vm.stack_programs(progs)


@pytest.mark.parametrize("seg_steps", [0, 3])
def test_sharded_code_eval_matches_single_device(micro_workload, seg_steps):
    """Mesh-sharded VM-batch evaluation (make_sharded_code_eval, pad
    lanes = duplicates of the last program) == the single-device vmapped
    population run to 1e-9, for both the one-dispatch and the segmented
    host-loop paths; elites never come from pad lanes."""
    from fks_tpu.parallel import (
        make_sharded_code_eval, pad_population, population_mesh,
    )
    from fks_tpu.sim import flat
    from fks_tpu.sim.engine import SimConfig

    wl = micro_workload
    stacked = _stack_corpus(wl, 6)
    mesh = population_mesh()
    padded, real = pad_population(stacked, mesh)
    assert real == 6 and padded.opcode.shape[0] == 8  # conftest mesh
    cfg = SimConfig()
    ev = make_sharded_code_eval(wl, mesh, cfg=cfg, elite_k=3,
                                engine="flat", seg_steps=seg_steps)
    res, elite_idx, elite_scores = ev(padded, real)
    ref = flat.make_population_run_fn(wl, vm.score, cfg)(
        stacked, flat.initial_state(wl, cfg))
    got = np.asarray(res.policy_score)[:real]
    want = np.asarray(ref.policy_score)
    np.testing.assert_allclose(got, want, atol=1e-9)
    ei = np.asarray(elite_idx)
    assert np.all(ei < real)  # pad duplicates never win elite slots
    np.testing.assert_allclose(np.asarray(elite_scores),
                               np.sort(want)[::-1][:3], atol=1e-9)
    np.testing.assert_allclose(want[ei], np.asarray(elite_scores),
                               atol=1e-9)


def test_evaluator_mesh_shards_the_generation(micro_workload):
    """CodeEvaluator(mesh=...) turns the batched tier on automatically and
    routes the generation through ONE sharded launch, with per-candidate
    fitness identical to the unbatched single-device tier."""
    from fks_tpu.parallel import population_mesh

    wl = micro_workload
    ev = backend.CodeEvaluator(wl, mesh=population_mesh())
    assert ev.vm_batch  # >1 mesh shard flips the auto default on CPU
    codes = _corpus()[:5]
    recs = ev.evaluate(codes)
    assert ev.vm_batch_count == 1
    # five candidates over eight devices: two lanes on every device
    assert ev.last_lanes_per_device == {d.id: 2 for d in jax.devices()}
    solo = backend.CodeEvaluator(wl, vm_batch=False)
    for rec, code in zip(recs, codes):
        one = solo.evaluate_one(code)
        assert rec.ok and one.ok
        np.testing.assert_allclose(rec.score, one.score, atol=1e-9)


def test_segmented_batch_tier_matches_unsegmented(micro_workload, monkeypatch):
    """FKS_VM_SEG_STEPS forces the batched tier through the segmented
    runner (the TPU default: bounded device calls keep the host in the
    loop); every generation fitness must match the monolithic launch."""
    monkeypatch.setenv("FKS_VM_SEG_STEPS", "3")
    seg = backend.CodeEvaluator(micro_workload, vm_batch=True, engine="flat")
    assert seg.vm_seg_steps == 3
    monkeypatch.setenv("FKS_VM_SEG_STEPS", "0")
    mono = backend.CodeEvaluator(micro_workload, vm_batch=True, engine="flat")
    assert mono.vm_seg_steps == 0
    codes = _corpus()[:4]
    a = seg.evaluate(codes)
    b = mono.evaluate(codes)
    assert seg.vm_batch_count == 1 and mono.vm_batch_count == 1
    for ra, rb in zip(a, b):
        assert ra.score == rb.score and ra.ok == rb.ok


# ------------------------------------------- the op-slot loop's trip count
#
# vm.score bounds the op-slot loop by the longest LIVE program among the
# lanes that share it (vm._loop_bound): one unbatched scalar, so nothing
# is selected per lane and NOP padding past it never runs.

CAP = 512


def _champion_code():
    """The pinned ledger champion (score 0.5365): 238 live ops (370 as
    lowered, before ``vm.simplify_ops``; 292 until PR 53 folded its five
    column chains), like all 13 ledger champions."""
    import glob
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = sorted(glob.glob(os.path.join(
        root, "policies", "discovered", "funsearch_*score0.5365.json")))[0]
    with open(path) as f:
        return json.load(f)["code"]


def _mix_codes(mix):
    seeds = template.seed_policies()
    by = {"ff": seeds["first_fit"], "bf": seeds["best_fit"],
          "champ": _champion_code()}
    return [by[k] for k in mix]


def test_ledger_champions_fill_the_256_bucket():
    """What the benchmark's VM cells run: a champion of 238 live ops (370
    as lowered, before ``vm.simplify_ops``) in the 256 bucket, 18 slots
    under its rung, which ``stack_programs`` and the serve tier still
    pick by their own rule (until PR 53: 292 in the 512 bucket, two
    fifths of it empty); the seed policies are shorter still."""
    codes = _mix_codes(["ff", "bf", "champ"])
    progs = [vm.compile_policy(c, N, G) for c in codes]
    assert int(progs[2].n_ops) == 238
    assert len(vm.lower_ops(codes[2], N, G)[0]) == 370
    assert vm.capacity_bucket(238) == 256 == progs[2].capacity
    assert vm.stack_programs(progs).opcode.shape == (3, 256)
    assert int(progs[0].n_ops) < int(progs[1].n_ops) < 238


def _count_slot_iterations(monkeypatch, fn, *args):
    """Run ``fn`` counting the op-slot loop's iterations: a debug
    callback with no operands beside ``lax.switch`` (the one call of the
    loop body) is unbatched, so it fires once per slot, not per lane."""
    from jax import lax

    fired = []
    real = lax.switch

    def counting(index, branches, *operands):
        jax.debug.callback(lambda: fired.append(1))
        return real(index, branches, *operands)

    monkeypatch.setattr(vm.lax, "switch", counting)
    out = jax.block_until_ready(fn(*args))
    jax.effects_barrier()
    return out, len(fired)


@pytest.mark.parametrize("mix,lanes", [
    (["ff", "champ"], 4),           # short + champion + two pad lanes
    (["ff", "bf"], 2),              # no champion: the bound is best_fit's
    (["champ", "ff", "bf"], 8),
])
def test_trip_count_is_the_longest_live_program(monkeypatch, mix, lanes):
    """A stack whose longest program has k live ops at capacity 512 runs
    k slot iterations rounded up to whole blocks of ``vm.SLOT_BLOCK`` (pad
    lanes repeat the last program and never raise the bound), and every
    lane scores what its program scores alone."""
    rng = np.random.default_rng(21)
    progs = [vm.compile_policy(c, N, G, capacity=CAP)
             for c in _mix_codes(mix)]
    stacked = vm.stack_programs(progs + [progs[-1]] * (lanes - len(progs)),
                                capacity=CAP)
    assert stacked.opcode.shape == (lanes, CAP)
    pod, nodes = _rand_views(rng)
    batched = jax.vmap(vm.score, in_axes=(0, None, None))
    got, slots = _count_slot_iterations(monkeypatch, batched, stacked, pod,
                                        nodes)
    longest = max(int(p.n_ops) for p in progs)
    assert slots == -(-longest // vm.SLOT_BLOCK) * vm.SLOT_BLOCK < CAP
    assert slots - longest < vm.SLOT_BLOCK
    monkeypatch.undo()
    for i, prog in enumerate(progs):
        np.testing.assert_array_equal(
            np.asarray(got[i]), np.asarray(vm.score(prog, pod, nodes)))
    # one program alone runs its own live ops
    _, alone = _count_slot_iterations(monkeypatch, vm.score, progs[0], pod,
                                      nodes)
    assert alone == int(progs[0].n_ops)


def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        for x in (v if isinstance(v, (list, tuple)) else (v,)):
            if hasattr(x, "eqns"):
                yield x
            elif hasattr(x, "jaxpr") and hasattr(x.jaxpr, "eqns"):
                yield x.jaxpr


def _op_slot_loops(jaxpr, regs, which="all"):
    """The ``while``s of the jaxpr tree that carry a register file
    ([..., regs, N, G]): ``"all"`` of them; the ``"outermost"``, one an
    op-slot loop of the program (where the programs are batched the loop
    over RUNS of turns, PR 47; else the turn's own); or the ``"turns"``,
    the innermost, whose bodies run slots (the narrow turn's and the wide
    turn's inside a loop over runs)."""
    found = []
    for eqn in jaxpr.eqns:
        inner = [loop for sub in _sub_jaxprs(eqn)
                 for loop in _op_slot_loops(sub, regs, which)]
        if eqn.primitive.name == "while" and any(
                len(v.aval.shape) >= 3 and v.aval.shape[-3] == regs
                for v in eqn.outvars):
            inner = {"all": [eqn] + inner, "outermost": [eqn],
                     "turns": inner or [eqn]}[which]
        found += inner
    return found


def _assert_unbatched_op_slot_loop(closed_jaxpr, capacity):
    """The thing a PER-LANE bound under vmap would cost: a batched
    predicate (reduced over the lanes to drive the loop) and a
    ``select_n`` of the whole register file per slot to freeze finished
    lanes. Neither may be there."""
    regs = vm.N_INPUTS + vm.CONST_POOL + capacity
    loops = _op_slot_loops(closed_jaxpr.jaxpr, regs)
    assert loops, "no op-slot loop in the program"
    for eqn in loops:
        cond = eqn.params["cond_jaxpr"].jaxpr
        for c in cond.eqns:
            assert all(v.aval.shape == () for v in c.invars + c.outvars), c
        for b in eqn.params["body_jaxpr"].jaxpr.eqns:
            if b.primitive.name == "select_n":
                shape = b.outvars[0].aval.shape
                assert not (len(shape) >= 3 and shape[-3] == regs), b
    return len(_op_slot_loops(closed_jaxpr.jaxpr, regs, "outermost"))


def _reads_since(before):
    """`vm.read_count` now less an earlier reading of it."""
    return tuple(x - y for x, y in zip(vm.read_count(), before))


def _lowered_batched_paths(wl):
    """name -> (closed jaxpr, capacity) of every batched runner's device
    program, traced on a short + long stack."""
    from fks_tpu.parallel import (
        make_sharded_code_eval, pad_population, population_mesh,
    )
    from fks_tpu.scenarios import get_suite
    from fks_tpu.scenarios.robust import make_suite_eval
    from fks_tpu.sim import engine as exact, flat
    from fks_tpu.sim.engine import SimConfig

    c = wl.cluster
    progs = [vm.compile_policy(code, c.n_padded, c.g_padded, capacity=CAP)
             for code in _mix_codes(["ff", "champ"])]
    stacked = vm.stack_programs(progs, capacity=CAP)
    cfg = SimConfig()
    reads = {}

    class Traced(dict):     # name -> jaxpr, and what tracing it counted
        def __setitem__(self, name, jaxpr):
            reads[name] = _reads_since(self.mark)
            self.mark = vm.read_count()
            super().__setitem__(name, jaxpr)

    out = Traced()
    out.mark = vm.read_count()
    for name, mod in (("flat", flat), ("exact", exact)):
        run = mod.make_population_run_fn(wl, vm.score, cfg)
        out[f"population/{name}"] = jax.make_jaxpr(run)(
            stacked, mod.initial_state(wl, cfg))
    seg = flat.make_segmented_population_run(wl, vm.score, cfg, seg_steps=3)
    out["segmented/flat"] = jax.make_jaxpr(seg.advance)(
        stacked, flat.broadcast_state(flat.initial_state(wl, cfg), 2))
    mesh = population_mesh(jax.devices()[:4])
    padded, real = pad_population(
        vm.stack_programs(progs * 4, capacity=CAP), mesh)
    ev = make_sharded_code_eval(wl, mesh, cfg=cfg, elite_k=1, engine="flat")
    out["mesh/flat"] = jax.make_jaxpr(lambda st: ev(st, real))(padded)
    # what the chip's four-chip generation runs: bounded segments
    seg_ev = make_sharded_code_eval(wl, mesh, cfg=cfg, elite_k=1,
                                    engine="flat", seg_steps=3)
    out["mesh-segmented/flat"] = jax.make_jaxpr(seg_ev.advance)(
        padded, flat.broadcast_state(flat.initial_state(wl, cfg), 8))
    # candidates x scenarios: the programs ride the OUTER vmap only
    suite = make_suite_eval(get_suite("smoke3", wl), vm.score, cfg,
                            population=True, jit=False, engine="exact")
    out["suite/exact"] = jax.make_jaxpr(suite)(stacked)
    return {k: (v, CAP, reads[k]) for k, v in out.items()}


@pytest.fixture(scope="module")
def batched_paths(micro_workload):
    return _lowered_batched_paths(micro_workload)


BATCHED_PATHS = ["population/flat", "population/exact", "segmented/flat",
                 "mesh/flat", "mesh-segmented/flat", "suite/exact"]


def test_no_batched_path_selects_the_register_file(batched_paths):
    assert sorted(batched_paths) == sorted(BATCHED_PATHS)
    for name, (jaxpr, cap, _) in batched_paths.items():
        assert _assert_unbatched_op_slot_loop(jaxpr, cap) >= 1, name


def _walk(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn):
            yield from _walk(sub)


def _assert_one_slice_write_a_slot(closed_jaxpr, capacity,
                                   block=vm.SLOT_BLOCK):
    """What JAX's batching rule for ``dynamic_update_slice`` makes of the
    op-slot loop's row write: a ``scatter`` of the register file, every
    slot (on the chip a bounds test over its index vector and a copy of
    the whole file). The body of every op-slot loop must hold no scatter
    on a value of the file's shape and exactly ONE ``dynamic_update_slice``
    on it (``vm._row_writer``) a slot: ``block`` of them a turn where the
    programs are batched (``vm._slot_loop``: two turns' loops, the narrow
    table's and the whole table's, inside the loop over runs), one for
    one program alone. Returns the number of op-slot loops, a loop over
    runs counting as one."""
    regs = vm.N_INPUTS + vm.CONST_POOL + capacity
    loops = _op_slot_loops(closed_jaxpr.jaxpr, regs, "turns")
    nests = _op_slot_loops(closed_jaxpr.jaxpr, regs, "outermost")
    assert loops, "no op-slot loop in the program"
    assert len(loops) == len(nests) * (2 if block > 1 else 1)
    for eqn in loops:
        writes = [b.primitive.name
                  for b in _walk(eqn.params["body_jaxpr"].jaxpr)
                  if b.primitive.name.startswith(("scatter",
                                                  "dynamic_update_slice"))
                  and any(len(v.aval.shape) >= 3
                          and v.aval.shape[-3] == regs for v in b.outvars)]
        assert writes == ["dynamic_update_slice"] * block, writes
    return len(nests)


@pytest.mark.parametrize("name", BATCHED_PATHS)
def test_every_batched_path_writes_a_register_as_one_slice(batched_paths,
                                                           name):
    jaxpr, cap, _ = batched_paths[name]
    assert _assert_one_slice_write_a_slot(jaxpr, cap) >= 1


def test_the_default_rule_scatter_would_be_caught(monkeypatch):
    """The check above is not vacuous: the write as it was, a bare
    ``dynamic_update_index_in_dim`` that ``vmap`` turns into a scatter of
    the register file, trips it; and unbatched, where no rule runs, both
    are the same one slice."""
    from jax import lax

    rng = np.random.default_rng(2)
    progs = [vm.compile_policy(c, N, G, capacity=256)
             for c in _mix_codes(["ff", "bf"])]
    stacked = vm.stack_programs(progs, capacity=256)
    pod, nodes = _rand_views(rng)
    batched = jax.vmap(vm.score, in_axes=(0, None, None))
    assert _assert_one_slice_write_a_slot(
        jax.make_jaxpr(batched)(stacked, pod, nodes), 256) == 1
    assert _assert_one_slice_write_a_slot(
        jax.make_jaxpr(vm.score)(progs[0], pod, nodes), 256, block=1) == 1
    monkeypatch.setattr(
        vm, "_write_row",
        lambda regs, res, row, axis: lax.dynamic_update_index_in_dim(
            regs, res, row, axis))
    # fresh functions: a trace of the same function object is cached
    as_it_was = jax.vmap(lambda *a: vm.score(*a), in_axes=(0, None, None))
    with pytest.raises(AssertionError, match="scatter"):
        _assert_one_slice_write_a_slot(
            jax.make_jaxpr(as_it_was)(stacked, pod, nodes), 256)
    assert _assert_one_slice_write_a_slot(
        jax.make_jaxpr(lambda *a: vm.score(*a))(progs[0], pod, nodes),
        256, block=1) == 1


def test_unbatched_score_never_reaches_the_write_rule():
    """One program alone: the jaxpr holds one ``dynamic_update_slice`` on
    the file and no scatter, and the rule's counter does not move."""
    rng = np.random.default_rng(4)
    prog = vm.compile_policy(_mix_codes(["bf"])[0], N, G, capacity=256)
    pod, nodes = _rand_views(rng)
    before = vm.write_count()
    jaxpr = jax.make_jaxpr(vm.score)(prog, pod, nodes)
    assert vm.write_count() == before
    names = [e.primitive.name for e in _walk(jaxpr.jaxpr)]
    assert names.count("dynamic_update_slice") == 1
    assert not [n for n in names if n.startswith("scatter")]


@pytest.mark.parametrize("inner,outer,prog_of,view_of", [
    # programs x programs: lane (i, j) runs program j on the one view
    ((0, None, None), (0, None, None), lambda i, j: j, None),
    # queries inside, programs outside (a serve batch per tenant)
    ((None, 0, 0), (0, None, None), lambda i, j: i, lambda i, j: j),
    # programs inside, scenarios outside (suite x population)
    ((0, None, None), (None, 0, 0), lambda i, j: j, lambda i, j: i),
])
def test_nested_vmap_keeps_the_slice(inner, outer, prog_of, view_of):
    """Two ``vmap`` levels around ``score``: the rule hands the write to
    the writer of the next axis, so it is still ONE slice of the
    [A, B, rows, N, G] file, no scatter one level up, and every lane
    scores what its program scores alone on its view."""
    rng = np.random.default_rng(9)
    progs = [vm.compile_policy(c, N, G, capacity=256)
             for c in _mix_codes(["ff", "bf"])]
    views = [_rand_views(rng) for _ in range(3)]
    stacked = vm.stack_programs(progs, capacity=256)
    if view_of is None:     # both levels batch the programs: [2, 2, ...]
        prog = jax.tree_util.tree_map(lambda x: jnp.stack([x, x]), stacked)
        view = views[0]
    else:
        prog = stacked
        view = jax.tree_util.tree_map(lambda *x: jnp.stack(x), *views)
    f = jax.vmap(jax.vmap(vm.score, in_axes=inner), in_axes=outer)
    before = vm.write_count()
    jaxpr = jax.make_jaxpr(f)(prog, *view)
    slices, scatters = (x - y for x, y in zip(vm.write_count(), before))
    assert slices >= 2 and scatters == 0     # the rule ran at both levels
    assert _assert_one_slice_write_a_slot(jaxpr, 256) == 1
    got = np.asarray(f(prog, *view))
    for i in range(got.shape[0]):
        for j in range(got.shape[1]):
            pod, nodes = views[view_of(i, j) if view_of else 0]
            np.testing.assert_array_equal(
                got[i, j],
                np.asarray(vm.score(progs[prog_of(i, j)], pod, nodes)))


def test_a_batched_row_takes_the_counted_fall_back():
    """No runner batches the row index (it is ``op_base`` plus the loop
    counter). If one did, the rule falls back to JAX's own (a scatter),
    the result is the reference's, and ``write_count`` says so."""
    from jax import lax

    rng = np.random.default_rng(5)
    regs = jnp.asarray(rng.normal(size=(4, 12, 3, 2)))
    res = jnp.asarray(rng.normal(size=(4, 3, 2)))
    rows = jnp.asarray([0, 11, 5, 5], jnp.int32)
    before = vm.write_count()
    got = jax.vmap(vm._write_row)(regs, res, rows)
    slices, scatters = (a - b for a, b in zip(vm.write_count(), before))
    assert (slices, scatters) == (0, 1)
    want = jnp.stack([lax.dynamic_update_index_in_dim(r, v, i, 0)
                      for r, v, i in zip(regs, res, rows)])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    names = [e.primitive.name for e in _walk(
        jax.make_jaxpr(jax.vmap(vm._write_row))(regs, res, rows).jaxpr)]
    assert "scatter" in names and "dynamic_update_slice" not in names
    # an unbatched row with only ONE of file and value batched: a slice
    for axes in ((0, None, None), (None, 0, None), (0, 0, None)):
        before = vm.write_count()
        a = [regs if axes[0] == 0 else regs[0],
             res if axes[1] == 0 else res[0], 7]
        got = jax.vmap(vm._write_row, in_axes=axes)(*a)
        assert tuple(x - y for x, y in zip(vm.write_count(), before)) \
            == (1, 0)
        for lane in range(4):
            np.testing.assert_array_equal(
                np.asarray(got[lane]),
                np.asarray(lax.dynamic_update_index_in_dim(
                    a[0][lane] if axes[0] == 0 else a[0],
                    a[1][lane] if axes[1] == 0 else a[1], 7, 0)))


# ------------------------------------------- the slot's operand fetch

def _file_gathers(closed_jaxpr, capacity):
    """Per turn's loop of the jaxpr (`_op_slot_loops`): the shapes of
    what each ``gather`` in its body reads from. The register file ([..., rows, N, G]) is the
    operand fetch; a gather from a row ([..., N, G]) is COL's column pick
    as JAX's own rules make it."""
    regs = vm.N_INPUTS + vm.CONST_POOL + capacity
    loops = _op_slot_loops(closed_jaxpr.jaxpr, regs, "turns")
    assert loops, "no op-slot loop in the program"
    out = []
    for eqn in loops:
        shapes = [b.invars[0].aval.shape
                  for b in _walk(eqn.params["body_jaxpr"].jaxpr)
                  if b.primitive.name == "gather"]
        from_file = [s for s in shapes if len(s) >= 3 and s[-3] == regs]
        assert from_file, "the loop reads no register"
        out.append((from_file,
                    [s for s in shapes if s not in from_file
                     and s[-2:] == from_file[0][-2:]]))
    return out


def _assert_one_gather_a_slot(closed_jaxpr, capacity):
    """The merged fetch: every op-slot loop gathers from the register file
    ONCE a slot (three rows a lane), ``vm.SLOT_BLOCK`` times a turn, and
    never from a row (COL)."""
    found = _file_gathers(closed_jaxpr, capacity)
    for from_file, from_row in found:
        assert len(from_file) == vm.SLOT_BLOCK and not from_row, (
            from_file, from_row)
    # the loops, a loop over runs (its two turns' loops) counting as one
    return len(_op_slot_loops(
        closed_jaxpr.jaxpr, vm.N_INPUTS + vm.CONST_POOL + capacity,
        "outermost"))


@pytest.mark.parametrize("name", BATCHED_PATHS)
def test_every_batched_path_fetches_a_slot_with_one_gather(batched_paths,
                                                           name):
    jaxpr, cap, (merged, split) = batched_paths[name]
    assert merged > 0 and split == 0
    assert _assert_one_gather_a_slot(jaxpr, cap) >= 1


def test_the_default_rule_gathers_would_be_caught(monkeypatch):
    """The check above is not vacuous: the fetch as it was, three bare
    reads and COL's slice at a traced column, is under ``vmap`` three
    gathers from the file and one from a row, and trips it."""
    from jax import lax

    rng = np.random.default_rng(2)
    progs = [vm.compile_policy(c, N, G, capacity=256)
             for c in _mix_codes(["ff", "bf"])]
    stacked = vm.stack_programs(progs, capacity=256)
    pod, nodes = _rand_views(rng)

    def as_it_was(axis):
        assert axis == 0
        return lambda regs, opcode, a, b, c, imm, k: (
            opcode[k], regs[a[k]], regs[b[k]], regs[c[k]], imm[k])

    monkeypatch.setattr(vm, "_slot_operands", as_it_was)
    monkeypatch.setattr(
        vm, "_col_picker",
        lambda axis: lambda va, c: lax.dynamic_slice_in_dim(va, c, 1, axis=1))
    batched = jax.vmap(lambda *a: vm.score(*a), in_axes=(0, None, None))
    jaxpr = jax.make_jaxpr(batched)(stacked, pod, nodes)
    narrow, wide = _file_gathers(jaxpr, 256)    # the two turns' loops
    assert narrow == wide
    from_file, from_row = wide
    assert len(from_file) == 3 * vm.SLOT_BLOCK
    assert len(from_row) == vm.SLOT_BLOCK
    with pytest.raises(AssertionError):
        _assert_one_gather_a_slot(jaxpr, 256)


def test_unbatched_score_never_reaches_the_read_rule():
    """One program alone: scalar fetches and slices of the file as before
    (five fetches, three rows, COL's column: no gather anywhere), one
    33-way conditional, and the rule's counter does not move."""
    rng = np.random.default_rng(4)
    prog = vm.compile_policy(_mix_codes(["bf"])[0], N, G, capacity=256)
    pod, nodes = _rand_views(rng)
    before = vm.read_count()
    jaxpr = jax.make_jaxpr(vm.score)(prog, pod, nodes)
    assert vm.read_count() == before
    loop, = _op_slot_loops(jaxpr.jaxpr,
                           vm.N_INPUTS + vm.CONST_POOL + 256)
    body = list(_walk(loop.params["body_jaxpr"].jaxpr))
    names = [e.primitive.name for e in body]
    assert "gather" not in names and "select_n" in names
    conds = [e for e in body if e.primitive.name == "cond"]
    assert [len(e.params["branches"]) for e in conds] == [33]
    # opcode, a, b, c, imm; the three rows; COL's column inside its branch
    assert names.count("dynamic_slice") == 9


#: ``imm`` below 0, in range, at G-1, at G and above; operand registers
#: that wrap (negative), wrap and still miss (clamped to row 0) and
#: overshoot (clamped to the last row)
_IMMS = (-3.0, -1.0, 0.0, 1.0, G - 1.0, float(G), G + 5.0)
_SPECIALS = (np.nan, 0.0, -0.0, np.inf, -np.inf, 1.5, -2.0, 7.0)


def _slot_case(opcode, rng):
    """A [lanes] stack of one-slot programs of ``opcode`` over a register
    file of special values, one lane an ``imm``."""
    rows = 12
    regs = rng.choice(_SPECIALS, size=(rows, N, G))
    regs[1] = rng.integers(0, G, size=(N, G))    # a finite row: POW, REM
    lanes = len(_IMMS)
    words = dict(
        opcode=np.full((lanes, 4), opcode, np.int32),
        a=rng.choice([0, 1, 5, rows - 1, -1, -rows - 5, rows + 7],
                     size=(lanes, 4)).astype(np.int32),
        b=rng.integers(-rows, rows + 3, size=(lanes, 4)).astype(np.int32),
        c=rng.integers(0, rows, size=(lanes, 4)).astype(np.int32),
        imm=np.tile(np.asarray(_IMMS)[:, None], (1, 4)))
    return jnp.asarray(regs), {k: jnp.asarray(v) for k, v in words.items()}


def _reads_as_they_were(regs, w, lane, k):
    """What the slot read before the rule: three separate reads with the
    scalar read's wrap and clamp, done here in NumPy."""
    regs = np.asarray(regs)

    def row(i):
        i = int(i)
        i = i + regs.shape[0] if i < 0 else i
        return regs[min(max(i, 0), regs.shape[0] - 1)]

    return tuple(row(np.asarray(w[f])[lane, k]) for f in "abc")


@pytest.mark.parametrize("opcode", range(33))
def test_merged_fetch_equals_the_three_reads_and_col(opcode):
    """One slot of every opcode, a stack of lanes under ``vmap``: the
    merged fetch returns bit for bit the rows three separate reads return,
    the slot's value is what the table gives on those rows, and COL is the
    column the slice at the clipped ``imm`` picked, on NaN, signed zeros
    and infinities, for ``imm`` from below 0 to above G."""
    from jax import lax

    rng = np.random.default_rng(100 + opcode)
    regs, w = _slot_case(opcode, rng)
    k = 2
    branches = vm._branches(N, G)

    def slot(opcode, a, b, c, imm):
        op, *operands = vm._slot_operands(0)(regs, opcode, a, b, c, imm, k)
        return (op, *operands, lax.switch(op, branches, *operands))

    before = vm.read_count()
    op, va, vb, vc, im, res = jax.vmap(slot)(*(w[f] for f in (
        "opcode", "a", "b", "c", "imm")))
    assert _reads_since(before) == (1, 0)
    bits = lambda x: np.asarray(x).view(np.uint64)   # x64 in the tests
    for lane, imm in enumerate(_IMMS):
        rows = _reads_as_they_were(regs, w, lane, k)
        for got, want in zip((va, vb, vc), rows):
            np.testing.assert_array_equal(bits(got[lane]), bits(want))
        assert int(op[lane]) == opcode and float(im[lane]) == imm
        if opcode == vm.OP_COL:
            col = min(max(int(imm), 0), G - 1)
            want = np.broadcast_to(rows[0][:, col:col + 1], (N, G))
            np.testing.assert_array_equal(bits(res[lane]), bits(want))
        elif opcode == vm.OP_SETCOL:       # an imm outside 0..G-1 writes
            want = rows[0].copy()          # no column
            if 0 <= imm < G:
                want[:, int(imm)] = rows[1][:, int(imm)]
            np.testing.assert_array_equal(bits(res[lane]), bits(want))
        else:
            want = branches[opcode](*(jnp.asarray(r) for r in rows),
                                    jnp.asarray(imm))
            np.testing.assert_array_equal(np.asarray(res[lane]),
                                          np.asarray(want))
        # and the unbatched slot, which no rule touches, says the same
        alone = slot(*(w[f][lane] for f in ("opcode", "a", "b", "c", "imm")))
        np.testing.assert_array_equal(np.asarray(res[lane]),
                                      np.asarray(alone[-1]))


@pytest.mark.parametrize("inner,outer,prog_of,view_of", [
    ((0, None, None), (0, None, None), lambda i, j: j, None),
    ((None, 0, 0), (0, None, None), lambda i, j: i, lambda i, j: j),
    ((0, None, None), (None, 0, 0), lambda i, j: j, lambda i, j: i),
])
def test_nested_vmap_keeps_the_one_gather(inner, outer, prog_of, view_of):
    """Two ``vmap`` levels around ``score``, the programs batched by the
    inner one, by the outer one or by both: whichever level batches them
    merges the fetch (a level that batches the views alone hands the rule
    on), so the [A, B, rows, N, G] file is gathered from once a slot and
    no row is, and every lane scores what its program scores alone."""
    rng = np.random.default_rng(19)
    progs = [vm.compile_policy(c, N, G, capacity=CAP)
             for c in _mix_codes(["champ", "bf"])]    # the champion has COL
    views = [_rand_views(rng) for _ in range(3)]
    stacked = vm.stack_programs(progs, capacity=CAP)
    if view_of is None:
        prog = jax.tree_util.tree_map(lambda x: jnp.stack([x, x]), stacked)
        view = views[0]
    else:
        prog = stacked
        view = jax.tree_util.tree_map(lambda *x: jnp.stack(x), *views)
    f = jax.vmap(jax.vmap(vm.score, in_axes=inner), in_axes=outer)
    before = vm.read_count()
    jaxpr = jax.make_jaxpr(f)(prog, *view)
    merged, split = _reads_since(before)
    assert merged >= 1 and split == 0
    assert _assert_one_gather_a_slot(jaxpr, CAP) == 1
    got = np.asarray(f(prog, *view))
    for i in range(got.shape[0]):
        for j in range(got.shape[1]):
            pod, nodes = views[view_of(i, j) if view_of else 0]
            np.testing.assert_array_equal(
                got[i, j],
                np.asarray(vm.score(progs[prog_of(i, j)], pod, nodes)))


def test_a_batched_slot_counter_takes_the_counted_fall_back():
    """No runner batches the slot counter (it is the loop's own). If one
    did, the rule cannot merge: it falls back to JAX's rules, the rows are
    the reference's, and ``read_count`` says so. Program words of which
    only SOME are per lane are merged all the same."""
    rng = np.random.default_rng(6)
    regs, w = _slot_case(vm.OP_ADD, rng)
    words = [w[f][0] for f in ("opcode", "a", "b", "c", "imm")]
    ks = jnp.asarray([0, 3, 1], jnp.int32)
    before = vm.read_count()
    got = jax.vmap(vm._slot_operands(0), in_axes=(None,) * 6 + (0,))(
        regs, *words, ks)
    assert _reads_since(before) == (0, 1)
    for lane, k in enumerate(np.asarray(ks)):
        want = vm._slot_operands(0)(regs, *words, int(k))
        for g_, w_ in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g_[lane]),
                                          np.asarray(w_))
    before = vm.read_count()
    some = jax.vmap(vm._slot_operands(0),
                    in_axes=(None, None, 0, None, None, None, None))(
        regs, words[0], w["a"], *words[2:], 1)
    assert _reads_since(before) == (1, 0)
    for lane in range(w["a"].shape[0]):
        want = vm._slot_operands(0)(regs, words[0], w["a"][lane],
                                    *words[2:], 1)
        for g_, w_ in zip(some, want):
            np.testing.assert_array_equal(np.asarray(g_[lane]),
                                          np.asarray(w_))


def test_a_per_lane_bound_would_be_caught():
    """The check above is not vacuous: the loop it must never see (each
    lane bounded by its own ``n_ops``) trips it."""
    rng = np.random.default_rng(2)
    progs = [vm.compile_policy(c, N, G, capacity=256)
             for c in _mix_codes(["ff", "bf"])]
    stacked = vm.stack_programs(progs, capacity=256)
    pod, nodes = _rand_views(rng)
    per_lane = jax.vmap(
        lambda p, pod, nodes: vm._execute(p, pod, nodes, p.n_ops),
        in_axes=(0, None, None))
    with pytest.raises(AssertionError):
        _assert_unbatched_op_slot_loop(
            jax.make_jaxpr(per_lane)(stacked, pod, nodes), 256)
    shared = jax.vmap(vm.score, in_axes=(0, None, None))
    assert _assert_unbatched_op_slot_loop(
        jax.make_jaxpr(shared)(stacked, pod, nodes), 256) == 1


@pytest.mark.parametrize("engine,devices,lanes,seg_steps,mix", [
    ("flat", 1, 4, 0, ["ff", "champ"]),
    ("exact", 1, 4, 0, ["ff", "champ"]),
    ("flat", 1, 2, 2, ["champ", "bf"]),
    ("flat", 4, 8, 0, ["ff", "champ", "bf", "champ", "ff"]),
    ("exact", 4, 8, 0, ["champ", "ff", "bf"]),
    ("flat", 4, 8, 2, ["ff", "bf", "champ"]),
])
def test_mixed_length_stack_matches_each_program_alone(
        micro_workload, engine, devices, lanes, seg_steps, mix):
    """A stack of short and long programs plus pad lanes, capacity 512,
    through the population runner (one device) or the sharded code eval
    (four virtual devices): every lane's result is bit for bit what its
    program gives alone through ``vm.score`` and what the transpiled jit
    policy gives."""
    from fks_tpu.funsearch import transpiler
    from fks_tpu.parallel import make_sharded_code_eval, population_mesh
    from fks_tpu.sim import get_engine
    from fks_tpu.sim.engine import SimConfig

    wl = micro_workload
    c = wl.cluster
    mod = get_engine(engine)
    cfg = SimConfig()
    codes = _mix_codes(mix)
    progs = [vm.compile_policy(code, c.n_padded, c.g_padded, capacity=CAP)
             for code in codes]
    stacked = vm.stack_programs(progs + [progs[-1]] * (lanes - len(progs)),
                                capacity=CAP)
    s0 = mod.initial_state(wl, cfg)
    if devices > 1:
        mesh = population_mesh(jax.devices()[:devices])
        ev = make_sharded_code_eval(wl, mesh, cfg=cfg, elite_k=1,
                                    engine=engine, seg_steps=seg_steps)
        res, _, _ = ev(stacked, len(progs))
    elif seg_steps:
        res = mod.make_segmented_population_run(
            wl, vm.score, cfg, seg_steps=seg_steps)(stacked, s0)
    else:
        res = jax.jit(mod.make_population_run_fn(wl, vm.score, cfg))(
            stacked, s0)
    res = jax.device_get(res)
    alone = jax.jit(mod.make_param_run_fn(wl, vm.score, cfg))
    seen = {}
    for i, (code, prog) in enumerate(zip(codes, progs)):
        if code not in seen:
            seen[code] = (
                jax.device_get(alone(prog, s0)),
                jax.device_get(jax.jit(mod.make_run_fn(
                    wl, transpiler.transpile(code), cfg))(s0)))
        for want in seen[code]:
            for field in ("assigned_node", "assigned_gpus", "policy_score",
                          "scheduled_pods", "events_processed", "failed",
                          "truncated"):
                np.testing.assert_array_equal(
                    np.asarray(getattr(res, field))[i],
                    np.asarray(getattr(want, field)), err_msg=f"{i} {field}")
