"""Transpiler differential tests: the vectorized JAX lowering of candidate
source must agree, node for node, with plain scalar Python execution of the
SAME source in the sandbox (the per-(pod,node) interpretation the reference
uses, reference: funsearch/funsearch_integration.py:67-101). This oracle
check is the transpiler's correctness bar."""
import zlib

import numpy as np
import jax.numpy as jnp
import pytest

from fks_tpu.funsearch import sandbox, template, transpiler
from fks_tpu.sim.types import NodeView, PodView

# ----------------------------------------------------- state generators


def random_state(rng, n_nodes=5, g_max=4):
    """A random mid-simulation cluster + one pod, as (views, scalar objects)."""
    cpu_tot = rng.integers(2000, 96000, n_nodes)
    mem_tot = rng.integers(4000, 262144, n_nodes)
    cpu_left = rng.integers(0, cpu_tot + 1)
    mem_left = rng.integers(0, mem_tot + 1)
    num_gpus = rng.integers(0, g_max + 1, n_nodes)
    gpu_left = np.array([rng.integers(0, k + 1) for k in num_gpus])
    gmask = np.arange(g_max)[None, :] < num_gpus[:, None]
    gm_tot = np.where(gmask, 1000, 0).astype(np.int64)
    gm_left = np.where(gmask, rng.integers(0, 1001, (n_nodes, g_max)), 0)
    gmem = np.where(gmask, 16000, 0)

    nodes = NodeView(
        cpu_milli_left=jnp.asarray(cpu_left), cpu_milli_total=jnp.asarray(cpu_tot),
        memory_mib_left=jnp.asarray(mem_left), memory_mib_total=jnp.asarray(mem_tot),
        gpu_left=jnp.asarray(gpu_left), num_gpus=jnp.asarray(num_gpus),
        gpu_milli_left=jnp.asarray(gm_left), gpu_milli_total=jnp.asarray(gm_tot),
        gpu_mem_total=jnp.asarray(gmem), gpu_mask=jnp.asarray(gmask),
        node_mask=jnp.ones(n_nodes, bool))

    pod_vals = dict(
        cpu_milli=int(rng.integers(100, 16000)),
        memory_mib=int(rng.integers(100, 65536)),
        num_gpu=int(rng.integers(0, 3)),
        gpu_milli=int(rng.integers(0, 1001)))
    pod = PodView(creation_time=0, duration_time=100, **pod_vals)

    scalar_nodes = []
    for i in range(n_nodes):
        gpus = tuple(
            sandbox.ScalarGPU(int(gm_left[i, g]), int(gm_tot[i, g]),
                              int(gmem[i, g]), int(gmem[i, g]))
            for g in range(num_gpus[i]))
        scalar_nodes.append(sandbox.ScalarNode(
            int(cpu_left[i]), int(cpu_tot[i]), int(mem_left[i]),
            int(mem_tot[i]), int(gpu_left[i]), gpus))
    scalar_pod = sandbox.ScalarPod(**pod_vals)
    return pod, nodes, scalar_pod, scalar_nodes


# candidate logic blocks spanning the transpilable subset
LOGIC_BLOCKS = {
    "constant": "score = 1000",
    "linear": "score = node.cpu_milli_left - pod.cpu_milli + 7",
    "ratio": (
        "score = 10000 * (node.cpu_milli_left - pod.cpu_milli)"
        " / max(1, node.cpu_milli_total)"),
    "branchy": (
        "if node.cpu_milli_left > node.cpu_milli_total / 2:\n"
        "        score = 50\n"
        "    else:\n"
        "        score = 150\n"
        "    if pod.num_gpu > 0:\n"
        "        score = score + 25"),
    "gpu_loop": (
        "free = 0\n"
        "    for gpu in node.gpus:\n"
        "        free = free + gpu.gpu_milli_left\n"
        "    score = free / max(1, len(node.gpus)) + 1"),
    "gpu_loop_if": (
        "tight = 0\n"
        "    for gpu in node.gpus:\n"
        "        if gpu.gpu_milli_left >= pod.gpu_milli:\n"
        "            tight = tight + gpu.gpu_milli_left - pod.gpu_milli\n"
        "    score = 5000 - tight"),
    "genexp_sum": (
        "score = 1 + sum(gpu.gpu_milli_left for gpu in node.gpus"
        " if gpu.gpu_milli_left >= pod.gpu_milli)"),
    "boolops": (
        "ok = node.gpu_left > 0 and pod.num_gpu > 0 or pod.cpu_milli > 5000\n"
        "    score = 400 if ok else 80"),
    "math_fns": (
        "score = math.sqrt(max(1, node.cpu_milli_left))"
        " + math.log(max(1, node.memory_mib_left))"),
    "modfloor": (
        "score = 1 + (node.cpu_milli_left % max(1, pod.cpu_milli))"
        " + node.memory_mib_left // max(1, pod.memory_mib)"),
    "minmax_gen": (
        "best = min(gpu.gpu_milli_left for gpu in node.gpus)"
        " if len(node.gpus) > 0 else 0\n"
        "    score = best + 3"),
    "early_return": (
        "if node.gpu_left == 0:\n"
        "        return 7\n"
        "    score = 77"),
    "chained_compare": (
        "score = 900 if 0 < pod.num_gpu <= node.gpu_left else 12"),
}


@pytest.mark.parametrize("name", sorted(LOGIC_BLOCKS))
def test_transpiled_matches_scalar_oracle(name):
    code = template.fill_template(LOGIC_BLOCKS[name])
    assert sandbox.validate(code), name
    policy = transpiler.transpile(code)
    rng = np.random.default_rng(zlib.crc32(name.encode()))
    for trial in range(8):
        pod, nodes, spod, snodes = random_state(rng)
        got = np.asarray(policy(pod, nodes))
        fn = sandbox.compile_policy(code)
        want = [int(fn(spod, sn)) for sn in snodes]
        assert got.tolist() == want, f"{name} trial {trial}"


def test_transpiled_seeds_match_oracle():
    rng = np.random.default_rng(0)
    for name, code in template.seed_policies().items():
        policy = transpiler.transpile(code)
        fn = sandbox.compile_policy(code)
        for _ in range(5):
            pod, nodes, spod, snodes = random_state(rng)
            got = np.asarray(policy(pod, nodes)).tolist()
            want = [int(fn(spod, sn)) for sn in snodes]
            assert got == want, name


@pytest.mark.slow
def test_transpiled_policy_runs_in_engine():
    """End to end: a transpiled candidate drives the jitted simulator and
    produces the same fitness as the equivalent zoo policy."""
    from fks_tpu.models import zoo
    from fks_tpu.sim.engine import SimConfig, simulate
    from tests.test_engine_micro import micro_workload

    wl = micro_workload()
    cfg = SimConfig(score_dtype=jnp.float64)
    ref = simulate(wl, zoo.first_fit(dtype=jnp.float64), cfg)
    cand = simulate(wl, transpiler.transpile(template.seed_policies()["first_fit"]), cfg)
    assert np.asarray(cand.assigned_node).tolist() == \
        np.asarray(ref.assigned_node).tolist()
    assert float(cand.policy_score) == pytest.approx(float(ref.policy_score), abs=1e-12)


def test_nonfinite_lanes_refuse():
    code = template.fill_template("score = 1.0 / (pod.num_gpu * 0)")
    policy = transpiler.transpile(code)
    rng = np.random.default_rng(3)
    pod, nodes, _, _ = random_state(rng)
    got = np.asarray(policy(pod, nodes))
    assert (got == 0).all()  # inf lanes refuse rather than poison argmax


@pytest.mark.parametrize("bad_logic", [
    "score = sorted(node.gpus)",          # sorted() of a non-generator
    "for i in range(1000000):\n        score = 1",  # unbounded unroll
    "score = node.gpus[pod.num_gpu].gpu_milli_left",  # dynamic subscript
    "score = pod.nonexistent_field",
    "score = abs()",                      # wrong arity must not escape
    "score = min(5)",
    "score = math.sqrt(1, 2)",
    "for i in range():\n        score = 1",
])
def test_unsupported_subset_raises(bad_logic):
    code = template.fill_template(bad_logic)
    with pytest.raises(transpiler.TranspileError):
        transpiler.transpile(code)


def _lane_scores(logic, rng_seed=11):
    code = template.fill_template(logic)
    policy = transpiler.transpile(code)
    rng = np.random.default_rng(rng_seed)
    pod, nodes, spod, snodes = random_state(rng)
    return code, np.asarray(policy(pod, nodes)), spod, snodes


@pytest.mark.parametrize("logic", [
    # sorted() over a generator + static indexing, against the scalar
    # oracle (reference whitelists `sorted`, safe_execution.py:19-22)
    "gpus = sorted(g.gpu_milli_left for g in node.gpus)\n"
    "score = gpus[0] + 1",
    "gpus = sorted(g.gpu_milli_left for g in node.gpus)\n"
    "score = gpus[-1] + 2 * len(gpus)",
    "score = node.gpus[1].gpu_milli_left + 3",
])
def test_sorted_and_subscript_match_oracle(logic):
    """Lanes where Python would raise (IndexError on short lists) refuse;
    every other lane matches the reference-style scalar evaluation."""
    code, got, spod, snodes = _lane_scores(logic)
    fn = sandbox.compile_policy(code)
    for i, sn in enumerate(snodes):
        try:
            want = int(fn(spod, sn))
        except Exception:
            want = 0
        assert got[i] == want, (i, logic)


def test_sorted_list_overwritten_by_scalar():
    """Regression: rebinding a name that held a sorted() list must not
    crash the transpiler; unconditional rebinding works, conditional
    rebinding is cleanly rejected (outside the lowerable subset)."""
    code, got, spod, snodes = _lane_scores(
        "xs = sorted(g.gpu_milli_left for g in node.gpus)\n"
        "xs = 7.0\n"
        "score = xs")
    fn = sandbox.compile_policy(code)
    assert got.tolist() == [int(fn(spod, sn)) for sn in snodes]
    with pytest.raises(transpiler.TranspileError):
        transpiler.transpile(template.fill_template(
            "xs = sorted(g.gpu_milli_left for g in node.gpus)\n"
            "if pod.num_gpu > 0:\n"
            "        xs = 1.0\n"
            "score = 1"))


def test_empty_generator_minmax_poisons_lane():
    """min() over zero GPUs raises in Python (candidate -> fitness 0 in the
    reference); the lowered lane must refuse, never leak the int sentinel."""
    code, got, spod, snodes = _lane_scores(
        "score = min(gpu.gpu_milli_left for gpu in node.gpus)")
    for i, sn in enumerate(snodes):
        if len(sn.gpus) == 0:
            assert got[i] == 0
        else:
            fn = sandbox.compile_policy(code)
            assert got[i] == int(fn(spod, sn))


def test_untaken_ifexp_arm_does_not_poison():
    """int(inf) in the arm Python would never evaluate must not poison."""
    logic = ("score = int(100.0 / (node.gpu_left * 0)) "
             "if node.gpu_left > 9999 else 5")
    code, got, spod, snodes = _lane_scores(logic)
    fn = sandbox.compile_policy(code)
    want = [int(fn(spod, sn)) for sn in snodes]
    assert got.tolist() == want  # every feasible node scores 5


def test_conditionally_unbound_read_poisons():
    """Reading a variable only assigned on the untaken branch raises
    UnboundLocalError in Python; those lanes must refuse."""
    logic = ("if node.gpu_left > 0:\n"
             "        bonus = 5\n"
             "    score = 10 + bonus")
    code, got, spod, snodes = _lane_scores(logic)
    fn = sandbox.compile_policy(code)
    for i, sn in enumerate(snodes):
        try:
            want = int(fn(spod, sn))
        except sandbox.PolicyRuntimeError:
            want = 0  # reference: candidate aborts; our lane refuses
        except Exception:
            want = 0
        assert got[i] == want, i


def test_canonical_key_ignores_formatting():
    a = template.fill_template("score = 1 + 2")
    b = a.replace("score = 1 + 2", "score = 1   +    2")
    assert transpiler.canonical_key(a) == transpiler.canonical_key(b)


# ------------------- the body is staged as `lax` primitives (ISSUE 50)

def _parents_primitives():
    import json
    import pathlib

    path = pathlib.Path(__file__).parent / "fixtures" / "policy_primitives.json"
    with open(path) as f:
        return json.load(f)


@pytest.mark.parametrize("name", ["champion:20260801_045536_score0.5365",
                                  "seed:first_fit", "seed:best_fit"])
def test_policy_jaxpr_is_flat_and_holds_the_parents_primitives(name):
    """What ``make_jaxpr(build_policy(code))`` holds: no ``jit`` / ``pjit``
    / ``closed_call`` equation (35 around ``jax.numpy`` functions for a
    champion in PR 49's tree, each a trace of its own in Python), and
    otherwise that tree's primitives in that tree's order, less the two
    kinds that computed nothing: the conversions of scalar literals that
    sat inside a nested ``_where`` (the trace folds them now) and the
    empty ``jnp.zeros(0)`` that asked for the ambient float. A
    ``jax.numpy`` call that creeps back into `_Interp` shows here as a
    nested call or as a changed list."""
    from tests import lowering_corpus as corpus

    assert name in corpus.PRIMITIVE_SOURCES
    parent = _parents_primitives()[name]
    got = corpus.policy_primitives(corpus.sources()[name], *corpus.SHAPES[0])
    assert [note for _, note in got if note] == []
    assert [p for p, _ in got] == [p for p, note in parent if not note]
    # the fixture is the PARENT's: it did hold what this tree must not
    assert sum(note == "call" for _, note in parent) >= 20
