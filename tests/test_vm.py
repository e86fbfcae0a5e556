"""The candidate VM: jaxpr->bytecode lowering + on-device interpretation
(fks_tpu.funsearch.vm). Contract: for every candidate it accepts, the VM's
scores EQUAL the directly-transpiled policy's scores (integer-exact), and
full-simulation fitness through the shared engine program equals the
per-candidate jit tier; candidates outside the vocabulary fall back."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fks_tpu.funsearch import (
    backend, llm, lower_pool, template, transpiler, vm,
)
from fks_tpu.sim.types import NodeView, PodView
from tests import lowering_corpus as corpus
from tests.conftest import FIXTURES

N, G = 16, 8


def _rand_views(rng):
    return _state(rng, N, G, "random")


def _corpus():
    fake = llm.FakeLLM(seed=3, junk_rate=0.0)
    return (list(template.seed_policies().values())
            + [template.fill_template(fake.complete("x")) for _ in range(30)])


@pytest.mark.slow
def test_corpus_lowers_and_matches_exactly():
    """Every seed + FakeLLM candidate lowers to the VM, and interpreted
    scores equal the transpiled policy's on randomized views."""
    rng = np.random.default_rng(7)
    score = jax.jit(vm.score)
    lowered = 0
    for code in _corpus():
        policy = transpiler.transpile(code)
        prog = vm.compile_policy(code, N, G, capacity=512)  # must not raise
        lowered += 1
        for _ in range(4):
            pod, nodes = _rand_views(rng)
            want = np.asarray(policy(pod, nodes))
            got = np.asarray(score(prog, pod, nodes))
            np.testing.assert_array_equal(got, want)
    assert lowered == len(_corpus())


@pytest.mark.slow
def test_full_simulation_fitness_matches_jit_tier(default_workload):
    """Seed candidates through the shared VM engine program reproduce the
    reference fitness table exactly (first_fit 0.4292, best_fit 0.4465)."""
    from fks_tpu.sim.engine import SimConfig, initial_state, make_param_run_fn

    wl = default_workload
    n, g = wl.cluster.n_padded, wl.cluster.g_padded
    cfg = SimConfig(cond_policy=True)
    run = jax.jit(make_param_run_fn(wl, vm.score, cfg))
    s0 = initial_state(wl, cfg)
    want = {"first_fit": 0.4292, "best_fit": 0.4465}
    for name, code in template.seed_policies().items():
        prog = vm.compile_policy(code, n, g, capacity=512)
        res = run(prog, s0)
        assert abs(float(res.policy_score) - want[name]) < 1e-4, name
        assert int(res.scheduled_pods) == wl.num_pods


def test_unsupported_construct_falls_back():
    code = template.fill_template(
        "gpus = sorted(g.gpu_milli_left for g in node.gpus)\n"
        "return max(1, gpus[0]) if pod.num_gpu == 0 else 1")
    transpiler.transpile(code)  # transpilable...
    with pytest.raises(vm.VMUnsupported):
        vm.compile_policy(code, N, G, capacity=512)  # ...but not VM-able


@pytest.mark.slow
def test_code_evaluator_uses_vm_tier(micro_workload_or_none=None):
    from fks_tpu.data.build import make_workload

    nodes = [{"node_id": "n0", "cpu_milli": 4000, "memory_mib": 8000,
              "gpus": [1000, 1000]},
             {"node_id": "n1", "cpu_milli": 2000, "memory_mib": 4000,
              "gpus": []}]
    pods = [{"pod_id": f"p{i}", "cpu_milli": 500, "memory_mib": 500,
             "num_gpu": i % 2, "gpu_milli": 300 * (i % 2),
             "creation_time": i, "duration_time": 5} for i in range(6)]
    wl = make_workload(nodes, pods, pad_nodes_to=2, pad_gpus_to=2,
                       pad_pods_to=8)
    ev = backend.CodeEvaluator(wl)
    seeds = list(template.seed_policies().values())
    recs = ev.evaluate(seeds)
    assert all(r.ok for r in recs)
    assert ev.vm_count == len(seeds)
    assert ev.compile_count == 0  # nothing hit the per-candidate jit tier

    # and the jit tier still answers for VM-unsupported candidates
    hard = template.fill_template(
        "gpus = sorted(g.gpu_milli_left for g in node.gpus)\n"
        "return max(1, gpus[0]) if pod.num_gpu == 0 else 1")
    rec = ev.evaluate([hard])[0]
    assert rec.ok
    assert ev.compile_count == 1


@pytest.mark.slow
def test_vm_matches_jit_tier_scores():
    """CodeEvaluator with and without the VM tier produce identical
    fitness for the same candidates."""
    from fks_tpu.data.build import make_workload

    nodes = [{"node_id": "n0", "cpu_milli": 9000, "memory_mib": 9000,
              "gpus": [1000] * 3},
             {"node_id": "n1", "cpu_milli": 5000, "memory_mib": 5000,
              "gpus": [1000]}]
    pods = [{"pod_id": f"q{i}", "cpu_milli": 700, "memory_mib": 600,
             "num_gpu": 1 if i % 3 else 0, "gpu_milli": 250 if i % 3 else 0,
             "creation_time": i // 2, "duration_time": 4} for i in range(10)]
    wl = make_workload(nodes, pods, pad_nodes_to=2, pad_gpus_to=3,
                       pad_pods_to=16)
    codes = _corpus()[:8]
    with_vm = backend.CodeEvaluator(wl, use_vm=True).scores(codes)
    without = backend.CodeEvaluator(wl, use_vm=False).scores(codes)
    np.testing.assert_array_equal(with_vm, without)


# ---------------------------------------------------------------------------
# One trace per source (PR 28): compile_policy traces the candidate's body
# once, at the workload's padded shape, and that trace is its validation;
# transpiler.transpile keeps its 2 x 2 dry trace for callers with no shape.
# The records were recorded from PR 28's PARENT, which ran both traces; the
# lowering pins were re-recorded by PR 30 (``python -m tests.lowering_corpus
# lowering``): they hash the SIMPLIFIED program, the errors are PR 28's
# parent's still, class and message.

with open(FIXTURES / "vm_lowering_pins.json") as _f:
    PINS = json.load(_f)
with open(FIXTURES / "mixed_batch_records.json") as _f:
    MIXED_RECORDS = json.load(_f)


def test_the_pins_cover_the_corpus():
    assert set(PINS) == set(corpus.sources()) and len(PINS) == 122
    bad = [k for k, v in PINS.items() if "error" in v["16x8"]]
    assert len(bad) == 31 and sum(k.startswith("champion:")
                                  for k in PINS) == 13


@pytest.mark.parametrize("shape", corpus.SHAPES,
                         ids=lambda s: corpus.shape_key(*s))
@pytest.mark.parametrize("name", sorted(PINS))
def test_lowering_is_the_pinned_program(name, shape):
    """Valid sources lower to the pinned program, leaf for leaf (opcode,
    a, b, c, imm, consts, n_ops, out_reg); invalid ones raise the pinned
    exception class with its message, from the one trace."""
    want = PINS[name][corpus.shape_key(*shape)]
    got = corpus.outcome(corpus.sources()[name], *shape)
    assert got == want


def test_compile_policy_traces_the_body_once_and_transpile_still_dry_traces(
        monkeypatch):
    code = template.seed_policies()["best_fit"]
    dry = []
    real_dry = transpiler._dry_trace
    monkeypatch.setattr(transpiler, "_dry_trace",
                        lambda policy: (dry.append(1), real_dry(policy)))
    r0 = transpiler.body_runs()
    vm.compile_policy(code, N, G, capacity=512)
    assert transpiler.body_runs() - r0 == 1 and not dry
    transpiler.transpile(code)
    assert transpiler.body_runs() - r0 == 2 and dry == [1]
    # the closure without the dry trace has not run at all
    transpiler.build_policy(code)
    assert transpiler.body_runs() - r0 == 2
    # a violation surfaces from the one trace, as the same class
    bad = template.fill_template("score = pod.nonexistent_field")
    transpiler.build_policy(bad)                         # not yet run
    with pytest.raises(transpiler.TranspileError, match="unknown pod attr"):
        vm.compile_policy(bad, N, G, capacity=512)
    assert transpiler.body_runs() - r0 == 3 and dry == [1]


def test_body_runs_counts_per_thread():
    import threading

    code = template.seed_policies()["first_fit"]
    r0 = transpiler.body_runs()
    t = threading.Thread(
        target=lambda: vm.compile_policy(code, N, G, capacity=512))
    t.start()
    t.join()
    assert transpiler.body_runs() == r0


@pytest.mark.parametrize("vm_batch,preflight", corpus.MIXED_MODES)
def test_mixed_generation_returns_the_parents_records(vm_batch, preflight):
    """valid, subset violation, VMUnsupported and syntax error in one
    generation: every field of every record is what the parent returned,
    through the batched tier's loop and through evaluate_one's VM branch."""
    want = MIXED_RECORDS[corpus.mode_key(vm_batch, preflight)]
    got = corpus.mixed_batch_records(vm_batch, preflight)
    assert len(got) == len(want) == len(corpus.MIXED)
    for g, w in zip(got, want):
        assert g == w, w["source"]
    errors = [w["error"] for w in want]
    assert sum(e is None for e in errors) == 6
    assert sum((e or "").startswith("transpile:") for e in errors) == \
        (1 if preflight else 3)


def test_transpile_stage_counts_sources_and_traces():
    """``tier/transpile`` carries ``sources`` and ``traces``: one trace per
    unique source on the batched tier; none in the stage when the lane
    count sends every source to the unbatched tier."""
    from fks_tpu.obs import spans

    src = corpus.sources()
    codes = [src[n] for n in corpus.MIXED]
    ev = backend.CodeEvaluator(corpus.mixed_workload(), vm_batch=True,
                               preflight=False)
    mark = len(spans.LOG.snapshot())
    ev.evaluate(codes)
    (sp,) = [r for r in spans.LOG.snapshot()[mark:]
             if r.name == "tier/transpile"]
    # 10 sources, one repeated, one a syntax error: 8 enter the stage
    assert {k: sp.fields[k] for k in ("sources", "traces")} == \
        {"sources": 8, "traces": 8}
    # six of the eight lower (the two subset violations raise inside the
    # trace): what simplify_ops was given and what it left, summed
    raw = [len(vm.lower_ops(src[n], 4, 4)[0]) for n in
           ("seed:first_fit", "seed:best_fit", "vm:unsupported", "fake3:00",
            "rebind:conditional", "block:gpu_loop_if") if n in LOWERS]
    assert sp.fields["ops_lowered"] == sum(raw) > sp.fields["ops_kept"] > 0
    assert sp.fields["chains_folded"] == 0       # no per-GPU generator here
    ev1 = backend.CodeEvaluator(corpus.mixed_workload(), vm_batch=False)
    mark = len(spans.LOG.snapshot())
    ev1.evaluate(codes[:1])
    (sp,) = [r for r in spans.LOG.snapshot()[mark:]
             if r.name == "tier/transpile"]
    assert sp.fields == {"sources": 1, "traces": 0, "ops_lowered": 0,
                         "ops_kept": 0, "chains_folded": 0, "pooled": 0,
                         "workers": 0, "clock_misfit": 0}


# ---------------------------------------------------------------------------
# The simplifier (PR 30): vm.simplify_ops between the lowering and the packed
# program. Every rule must leave what survives bit-identical for all inputs,
# so the corpus is scored three ways (raw lowering, simplified, transpiled
# closure) and each rule has a hand-made program with its negative.

LOWERS = sorted(k for k, v in PINS.items() if "hash" in v["16x8"])
EQUIV_SHAPES = ((2, 2), (16, 8), (64, 8), (4, 4))
#: (source, shape, precision, state) triples the equivalence test runs
EQUIV_STATES = 5


def _state(rng, n, g, kind):
    """Seeded views at (n, g). ``random``: as ``_rand_views``; ``cpu_pod``:
    ``num_gpu = 0``; ``edge``: zero totals and nothing left on half the
    nodes (x / 0, 0 / 0, log(0), -0.0 from x * -1), one node with every
    GPU masked, one node masked out, a pod that asks for more than any
    node has left (negative differences under log / sqrt)."""
    pod = [rng.integers(0, 5000), rng.integers(0, 8000), rng.integers(0, 4),
           rng.integers(0, 1001), rng.integers(0, 100), rng.integers(0, 50)]
    tot = rng.integers(1, 10000, n)
    left = (tot * rng.random(n)).astype(np.int64)
    mt = rng.integers(1, 20000, n)
    ml = (mt * rng.random(n)).astype(np.int64)
    ng = rng.integers(0, g + 1, n)
    node_mask = np.ones(n, bool)
    if kind == "cpu_pod":
        pod[2] = pod[3] = 0
    if kind == "edge":
        pod[0], pod[1] = 20000, 40000
        dead = np.arange(n) % 2 == 0
        tot, left = np.where(dead, 0, tot), np.where(dead, 0, left)
        mt, ml = np.where(dead, 0, mt), np.where(dead, 0, ml)
        ng[0] = 0
        node_mask[-1] = False
    gmask = np.arange(g)[None, :] < ng[:, None]
    gmt = np.where(gmask, 1000, 0)
    gml = (gmt * rng.random((n, g))).astype(np.int64)
    gmem = np.where(gmask, 16384, 0)
    i32 = lambda a: jnp.asarray(np.asarray(a, np.int32))
    return (PodView(*(jnp.int32(x) for x in pod)),
            NodeView(*(i32(a) for a in (left, tot, ml, mt, ng, ng, gml, gmt,
                                        gmem)),
                     jnp.asarray(gmask), jnp.asarray(node_mask)))


_SCORE = jax.jit(vm.score)


@pytest.mark.parametrize("shape", EQUIV_SHAPES,
                         ids=lambda s: corpus.shape_key(*s))
@pytest.mark.parametrize("name", LOWERS)
def test_simplified_program_scores_as_the_raw_lowering_and_the_closure(
        name, shape):
    """raw lowering == simplified == ``transpiler.build_policy``'s closure,
    score for score, under x64 and at f32, on random and on edge states."""
    n, g = shape
    code = corpus.sources()[name]
    for x64 in (True, False):
        with jax.enable_x64(x64):
            raw = vm.lower_ops(code, n, g)
            kept = vm.simplify_ops(*raw, g)
            assert len(kept[0]) <= len(vm.simplify_ops(*raw, None)[0]) \
                <= len(raw[0])
            p_raw = vm.pack_program(*raw, corpus.CAPACITY)
            p_new = vm.pack_program(*kept, corpus.CAPACITY)
            policy = transpiler.build_policy(code)
            rng = np.random.default_rng(n * 1000 + g)
            kinds = ("edge", "cpu_pod") + ("random",) * (EQUIV_STATES - 2)
            for kind in kinds:
                pod, nodes = _state(rng, n, g, kind)
                want = np.asarray(_SCORE(p_raw, pod, nodes))
                np.testing.assert_array_equal(
                    np.asarray(_SCORE(p_new, pod, nodes)), want, kind)
                np.testing.assert_array_equal(
                    np.asarray(policy(pod, nodes)), want, kind)


@pytest.mark.parametrize("shape", ((16, 8), (64, 8), (1528, 8)),
                         ids=lambda s: corpus.shape_key(*s))
def test_the_best_champion_keeps_238_of_370_ops(shape):
    code = corpus.sources()["champion:20260801_045536_score0.5365"]
    raw = vm.lower_ops(code, *shape)
    folds = vm.chains_folded()
    kept = vm.simplify_ops(*raw, shape[1])
    assert (len(raw[0]), len(kept[0])) == (370, 238)
    assert vm.chains_folded() - folds == 5
    count = lambda ops, op: sum(o[0] == op for o in ops)
    # half the products were a 0/1 mask times a factor it already held
    assert (count(raw[0], vm.OP_MUL), count(kept[0], vm.OP_MUL)) == (130, 59)
    # the five grids rebuilt column by column are the grids they rebuild:
    # no SETCOL is left, nor the COLs and MULs only a chain read
    assert (count(raw[0], vm.OP_SETCOL), count(kept[0], vm.OP_SETCOL)) \
        == (40, 0)
    assert (count(raw[0], vm.OP_COL), count(kept[0], vm.OP_COL)) == (24, 16)
    # told no width, the pass keeps every chain: PR 30's program
    plain = vm.simplify_ops(*raw, None)
    assert (len(plain[0]), count(plain[0], vm.OP_SETCOL)) == (292, 40)
    prog = vm.compile_policy(code, *shape)
    assert (int(prog.n_ops), prog.capacity) == (238, 256)
    low = lower_pool.lower_source(code, *shape)
    assert (low.ops_lowered, len(low.kept[0]), low.traces,
            low.chains_folded) == (370, 238, 1, 5)


# -- rule by rule, on hand-made op lists --------------------------------

R0 = vm.N_INPUTS + vm.CONST_POOL          # the first op's register
POOL = vm.N_INPUTS                        # the first pool register
CPU_LEFT, CPU_TOTAL, GPU_LEFT = 6, 7, 12  # cpu_milli_left/_total, gpu_milli_left
GPU_MASK, NODE_MASK = vm.N_INPUTS - 2, vm.N_INPUTS - 1


def _registers(ops, consts, pod, nodes):
    """Every register of an op list, by ``vm._branches`` one op at a time
    (no loop, no output mask): the bits ``_execute`` would hold."""
    n, g = nodes.gpu_mask.shape
    F = vm._ambient_float()
    branches = vm._branches(n, g)
    pool = np.zeros(vm.CONST_POOL)
    pool[:len(consts)] = consts
    regs = list(vm._inputs(pod, nodes)) + [
        jnp.full((n, g), v, F) for v in pool]
    for op, a, b, c, imm in ops:
        regs.append(branches[op](regs[a], regs[b], regs[c],
                                 jnp.asarray(imm, F)))
    return [np.asarray(r) for r in regs]


def _same_bits(x, y):
    return np.array_equal(x, y, equal_nan=True) and \
        np.array_equal(np.signbit(x), np.signbit(y))


def _poisoned(rng, nodes):
    """``nodes`` with NaN, inf, -inf and -0.0 strewn over its three GPU
    grids, as floats (``vm._inputs`` takes them as they are): what no
    engine hands a policy and a rule that holds for ALL inputs must
    survive."""
    def strew(grid):
        grid = np.asarray(grid, np.float64)
        bad = rng.choice([np.nan, np.inf, -np.inf, -0.0], grid.shape)
        return jnp.asarray(np.where(rng.random(grid.shape) < 0.4, bad, grid))

    return nodes._replace(**{f: strew(getattr(nodes, f))
                             for f in vm._NODE_GRIDS})


def _check(ops, consts, out_reg, n_kept, g=4, out_new=None, told="g"):
    """simplify_ops, told the width ``told`` (the states' own ``g`` unless
    given), keeps ``n_kept`` ops and the output register holds the raw
    program's bits on an edge state, a random one and a poisoned one."""
    kept = vm.simplify_ops(ops, consts, out_reg, g if told == "g" else told)
    assert len(kept[0]) == n_kept, kept
    if out_new is not None:
        assert kept[2] == out_new
    rng = np.random.default_rng(11)
    cap = vm.capacity_bucket(len(ops))
    for kind in ("edge", "random", "poisoned"):
        pod, nodes = _state(rng, 4, g, kind)
        if kind == "poisoned":
            nodes = _poisoned(rng, nodes)
        want = _registers(ops, consts, pod, nodes)[out_reg]
        got = _registers(*kept[:2], pod, nodes)[kept[2]]
        assert _same_bits(got, want), (kind, got, want)
        p_raw, p_new = (vm.pack_program(*t, cap)
                        for t in ((ops, consts, out_reg), kept))
        np.testing.assert_array_equal(np.asarray(_SCORE(p_new, pod, nodes)),
                                      np.asarray(_SCORE(p_raw, pod, nodes)))
    return kept


# pool: 0.0, 1.0, -0.0, 2.0
C = [0.0, 1.0, -0.0, 2.0]
ZERO, ONE, NEG_ZERO, TWO = POOL, POOL + 1, POOL + 2, POOL + 3
GT = (vm.OP_GT, 0, CPU_LEFT, 0, 0.0)              # a mask: r0
GE = (vm.OP_GE, 1, 8, 0, 0.0)                     # another mask
DIV0 = (vm.OP_DIV, CPU_LEFT, CPU_TOTAL, 0, 0.0)   # x/0, 0/0 on edge states


@pytest.mark.parametrize("ops,out,n_kept,out_new", [
    # 1 * m, m * 1, 0 * m, m * m on a mask
    ([GT, (vm.OP_MUL, ONE, R0, 0, 0.0)], R0 + 1, 1, R0),
    ([GT, (vm.OP_MUL, R0, ONE, 0, 0.0)], R0 + 1, 1, R0),
    ([GT, (vm.OP_MUL, ZERO, R0, 0, 0.0)], R0 + 1, 0, ZERO),
    ([GT, (vm.OP_MUL, R0, R0, 0, 0.0)], R0 + 1, 1, R0),
    # ... and NOT on an untyped operand: 0 * x, 1 * x, x * x stay
    ([DIV0, (vm.OP_MUL, ZERO, R0, 0, 0.0)], R0 + 1, 2, R0 + 1),
    ([DIV0, (vm.OP_MUL, ONE, R0, 0, 0.0)], R0 + 1, 2, R0 + 1),
    ([DIV0, (vm.OP_MUL, R0, R0, 0, 0.0)], R0 + 1, 2, R0 + 1),
    # a product absorbs a factor it holds: ((a * m) * m) * a -> a * m
    ([GT, GE, (vm.OP_MUL, R0, R0 + 1, 0, 0.0),
      (vm.OP_MUL, R0 + 2, R0 + 1, 0, 0.0),
      (vm.OP_MUL, R0 + 3, R0, 0, 0.0)], R0 + 4, 3, R0 + 2),
    # the same factors in another order are the same product
    ([GT, GE, (vm.OP_MUL, R0, R0 + 1, 0, 0.0),
      (vm.OP_MUL, R0 + 1, R0, 0, 0.0),
      (vm.OP_ADD, R0 + 2, R0 + 3, 0, 0.0)], R0 + 4, 4, R0 + 3),
    # an untyped factor is no factor: (x * m) * m stays
    ([GT, DIV0, (vm.OP_MUL, R0 + 1, R0, 0, 0.0),
      (vm.OP_MUL, R0 + 2, R0, 0, 0.0)], R0 + 3, 4, R0 + 3),
    # MAX(0, m), MAX(m, 1), 1 - 0, 1 - 1; MAX(0, x) stays
    ([GT, (vm.OP_MAX, ZERO, R0, 0, 0.0)], R0 + 1, 1, R0),
    ([GT, (vm.OP_MAX, R0, ONE, 0, 0.0)], R0 + 1, 0, ONE),
    ([(vm.OP_SUB, ONE, ZERO, 0, 0.0)], R0, 0, ONE),
    ([(vm.OP_SUB, ONE, ONE, 0, 0.0)], R0, 0, ZERO),
    ([DIV0, (vm.OP_MAX, ZERO, R0, 0, 0.0)], R0 + 1, 2, R0 + 1),
    # 1 - m is a mask (so 1 * (1 - m) goes); 2 - m is not
    ([GT, (vm.OP_SUB, ONE, R0, 0, 0.0),
      (vm.OP_MUL, ONE, R0 + 1, 0, 0.0)], R0 + 2, 2, R0 + 1),
    ([GT, (vm.OP_SUB, TWO, R0, 0, 0.0),
      (vm.OP_MUL, ONE, R0 + 1, 0, 0.0)], R0 + 2, 3, R0 + 2),
    # -0.0 is not the pool's 0: MAX(-0.0, m) and -0.0 * m stay
    ([GT, (vm.OP_MAX, NEG_ZERO, R0, 0, 0.0)], R0 + 1, 2, R0 + 1),
    ([GT, (vm.OP_MUL, NEG_ZERO, R0, 0, 0.0)], R0 + 1, 2, R0 + 1),
    # masks through SEL arms, COL, RMAX_G / RMIN_G, the mask inputs
    ([GT, GE, (vm.OP_SEL, DIV0[1], R0, R0 + 1, 0.0),
      (vm.OP_MUL, R0 + 2, R0 + 2, 0, 0.0)], R0 + 3, 3, R0 + 2),
    ([(vm.OP_COL, GPU_MASK, 0, 0, 1.0), (vm.OP_RMAX_G, GPU_MASK, 0, 0, 0.0),
      (vm.OP_MUL, R0, R0 + 1, 0, 0.0), (vm.OP_MUL, R0 + 2, NODE_MASK, 0, 0.0),
      (vm.OP_MUL, R0 + 3, R0, 0, 0.0)], R0 + 4, 4, R0 + 3),
    # RSUM_G of a mask is a count, not a mask
    ([(vm.OP_RSUM_G, GPU_MASK, 0, 0, 0.0),
      (vm.OP_MUL, R0, R0, 0, 0.0)], R0 + 1, 2, R0 + 1),
    # moves that hold for any value, NaN and inf included
    ([DIV0, (vm.OP_MAX, R0, R0, 0, 0.0)], R0 + 1, 1, R0),
    ([DIV0, (vm.OP_MIN, R0, R0, 0, 0.0)], R0 + 1, 1, R0),
    ([DIV0, (vm.OP_SEL, CPU_LEFT, R0, R0, 0.0)], R0 + 1, 1, R0),
    ([DIV0, (vm.OP_SEL, ONE, CPU_LEFT, R0, 0.0)], R0 + 1, 1, R0),
    ([DIV0, (vm.OP_SEL, ZERO, CPU_LEFT, R0, 0.0)], R0 + 1, 0, CPU_LEFT),
    ([DIV0, (vm.OP_SEL, TWO, CPU_LEFT, R0, 0.0)], R0 + 1, 2, R0 + 1),
    # value numbering on rewritten operands: 1*m and m are one operand
    ([GT, (vm.OP_MUL, ONE, R0, 0, 0.0), (vm.OP_ADD, R0, CPU_LEFT, 0, 0.0),
      (vm.OP_ADD, R0 + 1, CPU_LEFT, 0, 0.0),
      (vm.OP_SUB, R0 + 2, R0 + 3, 0, 0.0)], R0 + 4, 3, R0 + 2),
    # dead ops and a consumed stack placeholder go; a live NOP stays
    ([GT, GE, (vm.OP_NOP, R0, 0, 0, 0.0), DIV0,
      (vm.OP_MAX, R0, R0 + 1, 0, 0.0)], R0 + 4, 3, R0 + 2),
    ([GT, (vm.OP_NOP, R0, 0, 0, 0.0)], R0 + 1, 2, R0 + 1),
    # out_reg remapped to an input register
    ([(vm.OP_MAX, CPU_LEFT, CPU_LEFT, 0, 0.0)], R0, 0, CPU_LEFT),
])
def test_simplify_rule(ops, out, n_kept, out_new):
    _check(ops, C, out, n_kept, out_new=out_new)


# -- the whole-grid column chain (PR 53) ---------------------------------

GPU_TOTAL, GPU_MILLI = 13, 3   # gpu_milli_total; the pod's gpu_milli
W = 4                          # the width the hand-made chains are made at


def _chain(value, cols=range(W), base=ZERO, at=R0):
    """A SETCOL chain from ``base`` whose first op is register ``at``:
    for every ``j`` of ``cols`` in turn, ``value(j, r)`` gives the ops
    that compute column j's value, the first of them register ``r`` and
    the last the value (none: ``value`` returned a register), then the
    link. ``(ops, the last link's register)``."""
    ops, acc = [], base
    for j in cols:
        v = value(j, at + len(ops))
        if isinstance(v, list):
            ops += v
            v = at + len(ops) - 1
        ops.append((vm.OP_SETCOL, acc, v, 0, float(j)))
        acc = at + len(ops) - 1
    return ops, acc


def _col(x, shift=0):
    return lambda j, r: [(vm.OP_COL, x, 0, 0, float((j + shift) % W))]


def _over(op, *operands):
    """Column j's value: ``op`` over ``operands``, each a register (the
    same for every column) or a function like this one's result."""
    def value(j, r):
        ops, regs = [], []
        for x in operands:
            if callable(x):
                ops += x(j, r + len(ops))
                x = r + len(ops) - 1
            regs.append(x)
        return ops + [(op, *regs, *[0] * (3 - len(regs)), 0.0)]
    return value


MASKED_FIT = _over(vm.OP_MUL, _col(GPU_MASK),
                   _over(vm.OP_GE, _col(GPU_LEFT), GPU_MILLI))


@pytest.mark.parametrize("value,kw,n_kept,out_new", [
    # rule 1, one register into every column: a pool constant (what
    # ``sum(1 for gpu in node.gpus)`` stacks), a grid, an op's result
    (lambda j, r: TWO, {}, 0, TWO),
    (lambda j, r: GPU_LEFT, {}, 0, GPU_LEFT),
    (lambda j, r: R0, {"at": R0 + 1}, 1, R0),
    # rule 2, COL(x, j) into column j: the chain IS x, in any order of
    # the columns and from any base, which never shows
    (_col(GPU_LEFT), {}, 0, GPU_LEFT),
    (_col(GPU_MASK), {"cols": (2, 0, 3, 1)}, 0, GPU_MASK),
    (_col(GPU_TOTAL), {"base": CPU_LEFT}, 0, GPU_TOTAL),
    # ... and the newest W links say: a column written before them is
    # overwritten like the base
    (lambda j, r: TWO if r == R0 else _col(GPU_LEFT)(j, r),
     {"cols": (1, 0, 1, 2, 3)}, 0, GPU_LEFT),
    # rule 3, one elementwise opcode over columns: unary, binary with a
    # register all columns share, three operands, two levels
    (_over(vm.OP_SQRT, _col(GPU_LEFT)), {}, 1, R0),
    (_over(vm.OP_DIV, _col(GPU_LEFT), CPU_TOTAL), {}, 1, R0),
    (_over(vm.OP_SEL, _col(GPU_MASK), TWO, _col(GPU_LEFT)), {}, 1, R0),
    (_over(vm.OP_ADD, _over(vm.OP_MUL, _col(GPU_LEFT), TWO),
           _col(GPU_TOTAL)), {}, 2, R0 + 1),
    (MASKED_FIT, {}, 2, R0 + 1),
    (_over(vm.OP_SUB, _col(GPU_TOTAL, 0), R0), {"at": R0 + 1}, 2, R0 + 1),
])
def test_a_whole_column_chain_folds(value, kw, n_kept, out_new):
    ops, acc = _chain(value, **kw)
    if kw.get("at"):                  # R0: an op's result, NaN on the edge
        ops = [DIV0] + ops
    folds = vm.chains_folded()
    _check(ops, C, acc, n_kept, out_new=out_new)
    assert vm.chains_folded() - folds == 1
    # a reader of the grid reads the folded register
    _check(ops + [(vm.OP_RSUM_G, acc, 0, 0, 0.0)], C, R0 + len(ops),
           n_kept + 1)


@pytest.mark.parametrize("value,kw,n_kept", [
    (_col(GPU_LEFT), {"cols": (0, 1, 2)}, 6),       # column 3 never written
    (_col(GPU_LEFT), {"cols": (0, 1, 2, 2)}, 7),    # ... or 2 written twice
    (_col(GPU_LEFT), {"cols": (0, 1, 2, 4)}, 7),    # ... or no column at all
    # two grids; two opcodes; another column than the link's
    (lambda j, r: _col(GPU_MASK if j == 2 else GPU_LEFT)(j, r), {}, 8),
    (lambda j, r: _over(vm.OP_ABS if j == 2 else vm.OP_SQRT,
                        _col(GPU_LEFT))(j, r), {}, 12),
    (_col(GPU_LEFT, shift=1), {}, 8),
    # an operand that is a reduction over G, a COL of a COL, a link of
    # another chain: column j of these is not the op over column j
    (_over(vm.OP_RSUM_G, _col(GPU_LEFT)), {}, 12),
    (_over(vm.OP_MUL, _col(GPU_MASK), _over(vm.OP_RMAX_G, _col(GPU_LEFT))),
     {}, 20),
    (_over(vm.OP_COL, _col(GPU_LEFT)), {}, 12),     # COL(COL(x, j), 0)
    (lambda j, r: [(vm.OP_SETCOL, GPU_LEFT, TWO, 0, float(j))], {}, 8),
    # a stack placeholder has identity and is never lifted
    (_over(vm.OP_NOP, _col(GPU_LEFT)), {}, 12),
    # an operand that differs from column to column and is no COL
    (lambda j, r: _over(vm.OP_ADD, _col(GPU_LEFT), (CPU_LEFT, CPU_TOTAL,
                                                    CPU_LEFT, 8)[j])(j, r),
     {}, 12),
])
def test_a_chain_that_is_no_whole_grid_stays(value, kw, n_kept):
    ops, acc = _chain(value, **kw)
    folds = vm.chains_folded()
    _check(ops, C, acc, n_kept)
    _check(ops + [(vm.OP_RSUM_G, acc, 0, 0, 0.0)], C, R0 + len(ops),
           n_kept + 1)
    assert vm.chains_folded() == folds


@pytest.mark.parametrize("told", (None, 8, 5), ids=lambda t: f"told_{t}")
def test_a_chain_of_another_width_than_the_grid_stays(told):
    """The W columns of a chain are a whole grid only on a grid W wide:
    told no width, or another than the chain's, every link stays and the
    program is word for word what the pass made of it before PR 53."""
    ops, acc = _chain(MASKED_FIT)
    g = told or W
    kept = _check(ops, C, acc, len(ops), g=g, told=told)
    assert kept == (ops, C, acc)


def test_a_link_another_op_reads_keeps_its_value():
    """The fold replaces the LAST link; an earlier one is what it was for
    its other reader (here: columns 0-1 of gpu_milli_left over 0.0)."""
    ops, acc = _chain(_col(GPU_LEFT))
    half = R0 + 3                                   # the second link
    ops += [(vm.OP_RSUM_G, half, 0, 0, 0.0),
            (vm.OP_ADD, acc, R0 + len(ops), 0, 0.0)]
    kept = _check(ops, C, R0 + len(ops) - 1, 6)
    assert [op for op, *_ in kept[0]] == [
        vm.OP_COL, vm.OP_SETCOL, vm.OP_COL, vm.OP_SETCOL, vm.OP_RSUM_G,
        vm.OP_ADD]
    assert kept[0][-1][1] == GPU_LEFT               # ADD reads the grid


def test_a_folded_grid_meets_the_rules_of_the_0_1_domain():
    """The lifted op is entered like any other: typed 0/1, numbered, and
    a product that already holds a factor absorbs it."""
    ops, acc = _chain(MASKED_FIT)
    n = len(ops)
    ops += [(vm.OP_MUL, acc, GPU_MASK, 0, 0.0),     # holds gpu_mask already
            (vm.OP_GE, GPU_LEFT, GPU_MILLI, 0, 0.0),  # the lifted GE, again
            (vm.OP_MUL, R0 + n, R0 + n + 1, 0, 0.0)]  # ... and holds it
    _check(ops, C, R0 + n + 2, 2, out_new=R0 + 1)


def test_two_chains_over_the_same_columns_are_one_grid():
    ops, acc = _chain(MASKED_FIT)
    more, acc2 = _chain(MASKED_FIT, base=ONE, at=R0 + len(ops))
    ops += more + [(vm.OP_SUB, acc, acc2, 0, 0.0)]
    kept = _check(ops, C, R0 + len(ops) - 1, 3)
    assert kept[0][-1][:3] == (vm.OP_SUB, R0 + 1, R0 + 1)


def test_a_deep_column_expression_stays_column_by_column():
    """`_lift` recurses over a column's expression and a candidate is
    untrusted: past ``vm._LIFT_DEPTH`` the chain stays, and one level
    short of it the chain folds."""
    def nest(depth):
        value = _col(GPU_LEFT)
        for _ in range(depth):
            value = _over(vm.OP_ABS, _over(vm.OP_SUB, value, TWO))
        return _chain(value)

    ops, acc = nest(vm._LIFT_DEPTH // 2 - 1)        # COL at the last level
    assert len(_check(ops, C, acc, vm._LIFT_DEPTH - 2)[0]) < len(ops)
    ops, acc = nest(vm._LIFT_DEPTH // 2)
    _check(ops, C, acc, len(ops))


def test_a_shared_subexpression_is_lifted_once():
    """``x + x`` thirty levels deep is thirty ops a column and thirty
    lifted: the memo keeps the walk linear (2 ** 30 without it)."""
    def value(j, r):
        ops = [(vm.OP_COL, GPU_LEFT, 0, 0, float(j))]
        for k in range(30):
            ops.append((vm.OP_ADD, r + k, r + k, 0, 0.0))
        return ops

    ops, acc = _chain(value)
    _check(ops, C, acc, 30)


def test_simplify_adds_the_pool_zero_only_when_there_is_room():
    """``1 - 1`` needs the pool's 0.0: appended when absent, and the op
    stays when the pool is full."""
    ops = [(vm.OP_SUB, POOL, POOL, 0, 0.0)]
    kept = _check(ops, [1.0], R0, 0, out_new=POOL + 1)
    assert kept[1] == [1.0, 0.0]
    full = [1.0] + [float(i + 2) for i in range(vm.CONST_POOL - 1)]
    kept = _check(ops, full, R0, 1, out_new=R0)
    assert kept[1] == full


def test_positive_and_negative_zero_stay_distinct_pool_constants():
    code = template.fill_template(
        "score = 100 + 1.0 / (0.0 * node.gpu_left + -0.0) "
        "+ 1.0 / (0.0 * node.gpu_left + 0.0)")
    raw = vm.lower_ops(code, 4, 4)
    kept = vm.simplify_ops(*raw, 4)
    signs = sorted(np.signbit(v) for v in kept[1] if v == 0.0)
    assert signs == [False, True]


def test_a_program_with_stack_placeholders_drops_them():
    """A 1-D stack reduced at once (``_Lowerer._p_concatenate``): the
    pieces sit behind an OP_NOP placeholder that only the pairwise fold
    reads, so it is dead as soon as the fold is emitted."""
    closed = jax.make_jaxpr(lambda a, b, c: jax.lax.reduce_min(
        jnp.concatenate([a[None], b[None], c[None]]), (0,)) + a)(
            *(jnp.float32(x) for x in (1, 2, 3)))
    lo = vm._Lowerer(4, 4)
    (out,) = lo.lower_closed(closed, [CPU_LEFT, CPU_TOTAL, 8])
    assert [op for op, *_ in lo.ops] == [vm.OP_NOP, vm.OP_MIN, vm.OP_MIN,
                                         vm.OP_ADD]
    kept = _check(lo.ops, lo.consts, out, 3)
    assert vm.OP_NOP not in [op for op, *_ in kept[0]]
