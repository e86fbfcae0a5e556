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
    """NOP padding never changes scores: score_static over the padded
    capacity equals score over the live op count."""
    rng = np.random.default_rng(11)
    code = list(template.seed_policies().values())[0]
    prog = vm.compile_policy(code, N, G)
    padded = vm.pad_capacity(prog, 2 * prog.capacity)
    assert padded.capacity == 2 * prog.capacity
    for _ in range(3):
        pod, nodes = _rand_views(rng)
        np.testing.assert_array_equal(
            np.asarray(vm.score(prog, pod, nodes)),
            np.asarray(vm.score_static(padded, pod, nodes)))


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
    """vmapped score_static over a stacked generation == per-candidate
    score, integer-exact."""
    rng = np.random.default_rng(5)
    codes = _corpus()[:6]
    progs = [vm.compile_policy(c, N, G) for c in codes]
    stacked = vm.stack_programs(progs)
    pod, nodes = _rand_views(rng)
    batched = jax.jit(jax.vmap(vm.score_static, in_axes=(0, None, None)))
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
    ref = flat.make_population_run_fn(wl, vm.score_static, cfg)(
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
