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

from fks_tpu.funsearch import backend, llm, template, transpiler, vm
from fks_tpu.sim.types import NodeView, PodView
from tests import lowering_corpus as corpus
from tests.conftest import FIXTURES

N, G = 16, 8


def _rand_views(rng):
    pod = PodView(*(jnp.int32(x) for x in (
        rng.integers(0, 5000), rng.integers(0, 8000), rng.integers(0, 4),
        rng.integers(0, 1001), rng.integers(0, 100), rng.integers(0, 50))))
    tot = rng.integers(1, 10000, N).astype(np.int32)
    left = (tot * rng.random(N)).astype(np.int32)
    mt = rng.integers(1, 20000, N).astype(np.int32)
    ml = (mt * rng.random(N)).astype(np.int32)
    ng = rng.integers(0, G + 1, N).astype(np.int32)
    gmask = np.arange(G)[None, :] < ng[:, None]
    gmt = np.where(gmask, 1000, 0).astype(np.int32)
    gml = (gmt * rng.random((N, G))).astype(np.int32)
    gmem = np.where(gmask, 16384, 0).astype(np.int32)
    nodes = NodeView(*(jnp.asarray(a) for a in (
        left, tot, ml, mt, ng, ng, gml, gmt, gmem, gmask,
        np.ones(N, bool))))
    return pod, nodes


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
# The pins below were recorded from the PARENT, which ran both traces.

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
def test_lowering_is_the_parents(name, shape):
    """Valid sources lower to the parent's program, leaf for leaf (opcode,
    a, b, c, imm, consts, n_ops, out_reg); invalid ones raise the parent's
    exception class with the parent's message, from the one trace."""
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
    assert sp.fields == {"sources": 8, "traces": 8}
    ev1 = backend.CodeEvaluator(corpus.mixed_workload(), vm_batch=False)
    mark = len(spans.LOG.snapshot())
    ev1.evaluate(codes[:1])
    (sp,) = [r for r in spans.LOG.snapshot()[mark:]
             if r.name == "tier/transpile"]
    assert sp.fields == {"sources": 1, "traces": 0}
