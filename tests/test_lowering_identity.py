"""The device gets the words it got (ISSUE 50): for every source the repo
holds, ``vm.lower_ops`` returns what PR 49's tree returned, element for
element, and so does the packed program, at (16, 8) and (64, 8), in f32
and under x64; and the policy closure under ``jit`` scores the same bits.

``tests/fixtures/lowering_identity.json`` was recorded from the PARENT of
PR 50 (``python -m tests.test_lowering_identity > tests/fixtures/
lowering_identity.json`` in a checkout of a0b9b3c with this file copied
in), whose ``_Interp`` computed with ``jax.numpy``; this tree's stages
`lax` primitives. The corpus: `tests.lowering_corpus`
(seed policies, the 13 ledger champions, every source tier-1 lowers, the
FakeLLM candidates), the generations the codegen drivers of the benchmark
build for seeds 0-5 (jittered champions: other constants, the same op
lists), and `EXTRA`, one source for each path of the interpreter that the
rest does not walk. A case is one source: four lowerings.

PR 53 re-recorded the ``packed`` digests, and those alone, from its own
tree: its ``vm.simplify_ops`` is told the lowering's GPU width and folds
a whole SETCOL chain into the grid it rebuilds (332 of the 752 digests,
83 sources; ``tests/test_vm_chain_corpus.py`` holds the new program to
the old one's bits). Every ``ops`` and
``scores`` digest is PR 50's parent's.
"""
import glob
import hashlib
import json
import os
import types

import jax
import numpy as np
import pytest

from fks_tpu.funsearch import template, transpiler, vm
from fks_tpu.sim.types import NodeView, PodView
from tests import lowering_corpus as lc

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "lowering_identity.json")

#: logic blocks (`template.fill_template`) for what the other sources leave
#: out: the transcendentals, ``**`` in its three forms, float ``//`` and
#: ``%``, ``round``, the casts, bools in arithmetic, static conditions,
#: loops over ``range`` and ``enumerate``, a GPU by index, generators of
#: floats and of constants
EXTRA = {
    "exp": "score = 1000 * math.exp(-node.cpu_milli_left"
           " / max(1, node.cpu_milli_total))",
    "trig": "score = 100 * (3 + math.sin(node.cpu_milli_left / 100.0)"
            " + math.cos(pod.cpu_milli) + math.tan(0.5))",
    "mathpow": "score = math.pow(node.gpu_left + 1, 2)"
               " + math.pow(2.0, pod.num_gpu) + math.sqrt(2)",
    "pow_static": "score = (node.gpu_left + 1) ** 2 + pod.num_gpu ** 3"
                  " + (node.cpu_milli_left / 1000.0) ** 2",
    "pow_float": "score = (node.cpu_milli_left / 1000.0) ** 1.5"
                 " + 2.0 ** pod.num_gpu"
                 " + (node.memory_mib_left / 1000.0) ** pod.num_gpu",
    "pow_int": "score = 1 + (node.gpu_left + 1) ** (pod.num_gpu + 1)"
               " + 2 ** pod.num_gpu",
    "floordiv_int": "score = 7 // 2 + 7 % 3 + node.gpu_left // 2"
                    " + 9 // max(1, pod.num_gpu)"
                    " + node.cpu_milli_left % 7"
                    " + 100 % max(1, node.gpu_left)",
    "floordiv_float": "score = 1 + (node.cpu_milli_left / 3.0) // 2"
                      " + (node.memory_mib_left % 2.5)"
                      " + 7.5 // max(1, node.gpu_left)",
    "round": "score = round(node.cpu_milli_left / 7.0)"
             " + round(node.memory_mib_left / 3.0, 1) + round(2.5)"
             " + round(node.gpu_left) + round(node.gpu_left, 2)",
    "casts": "score = float(node.gpu_left) + int(node.cpu_milli_left / 3)"
             " + bool(pod.num_gpu) + int(True) + float(pod.num_gpu > 0)"
             " + int(pod.num_gpu > 0) + int(node.gpu_left)",
    "unary": "x = -node.cpu_milli_left\n"
             "    score = abs(x) + (5 if not pod.num_gpu else 6)"
             " + (-pod.cpu_milli) + +node.gpu_left + abs(-3)"
             " + abs(node.gpu_left > 0)",
    "minmax_mixed": "score = min(node.cpu_milli_left, 5000,"
                    " pod.cpu_milli * 1.5) + max(1.5, node.gpu_left, 2)",
    "enumerate": "score = 1\n"
                 "    for i, gpu in enumerate(node.gpus):\n"
                 "        if gpu.gpu_milli_left >= pod.gpu_milli:\n"
                 "            score = score + i * gpu.gpu_milli_total",
    "range_loop": "score = 0\n"
                  "    for i in range(3):\n"
                  "        score = score + i * node.gpu_left",
    "gpu_index": "score = node.gpus[0].gpu_milli_left"
                 " + node.gpus[1].memory_mib_left + 1",
    "gpu_index_far": "score = node.gpus[99].gpu_milli_left + 1",
    "genexp_float": "score = 1 + sum(gpu.gpu_milli_left / 1000.0"
                    " for gpu in node.gpus)"
                    " + max(gpu.gpu_milli_left * 0.5 for gpu in node.gpus"
                    " if gpu.gpu_milli_left > 0)",
    "genexp_const": "score = 1 + sum(1 for gpu in node.gpus)"
                    " + sum(1.5 for gpu in node.gpus"
                    " if gpu.gpu_milli_left > 100)"
                    " + min(3 for gpu in node.gpus)",
    "genexp_bool": "score = 1 + sum(gpu.gpu_milli_left > 0"
                   " for gpu in node.gpus)",
    "bool_arith": "ok = pod.num_gpu > 0\n"
                  "    big = node.gpu_left > 2\n"
                  "    score = ok + 1 + (ok and node.gpu_left)"
                  " + (ok or 2.5) + ok * big + (ok + big)",
    "static_if": "if 1 > 0:\n"
                 "        score = 5\n"
                 "    score = score + (3 if 2 < 1 else node.gpu_left)"
                 " + (1 and node.gpu_left) + (0 or 2)",
    "static_else": "if 2 < 1:\n"
                   "        score = 5\n"
                   "    else:\n"
                   "        score = 6 + node.gpu_left",
    "div_zero": "score = 1 / 0 + node.gpu_left",
    "floordiv_zero": "score = 5 // 0 + 5 % 0 + node.gpu_left",
    "int_nonfinite": "score = int(1e400) + node.gpu_left",
    "truthy_float": "x = node.cpu_milli_left / 2\n"
                    "    if x:\n"
                    "        score = 3\n"
                    "    if 2.5:\n"
                    "        score = score + 1",
    "augassign": "score = 1\n"
                 "    score += node.gpu_left\n"
                 "    score *= 2\n"
                 "    score -= 1",
    "compare_all": "score = (node.gpu_left == pod.num_gpu) * 5"
                   " + (node.gpu_left != 0) * 3 + (pod.num_gpu <= 1)"
                   " + (pod.num_gpu < 1) + (1 < pod.num_gpu < 3)"
                   " + (0 < 1 < node.gpu_left)",
    "chain_float": "score = 1.5 if 0.5 < node.cpu_milli_left / 1000.0 <= 2"
                   " else 2.5",
    "scalar_select": "score = node.gpu_left"
                     " * (2.5 if pod.num_gpu > 0 else 3.5)",
    "sorted_float": "xs = sorted(g.gpu_milli_left / 2 for g in node.gpus"
                    " if g.gpu_milli_left > 0)\n"
                    "    score = xs[0] + xs[-2] + len(xs)",
}


def _generations():
    """The sources of the codegen cells' generations, seeds 0-5, less the
    seed policies (in `lowering_corpus` already) and repeats."""
    from chipbench.drivers import codegen

    out, seen = {}, set(template.seed_policies().values())
    for path in sorted(glob.glob(os.path.join(
            lc.ROOT, "chipbench", "traffic", "codegen8*.json"))):
        with open(path) as f:
            traffic = json.load(f)
        for seed in range(6):
            drv = codegen.Driver(types.SimpleNamespace(traffic=traffic),
                                 seed, {}, None, False)
            for i, code in enumerate(drv._sources()):
                if code not in seen:
                    seen.add(code)
                    out[f"generation:{seed}:{i}"] = code
    return out


def sources():
    out = dict(lc.sources())
    out.update(_generations())
    out.update({"extra:" + k: template.fill_template(v)
                for k, v in EXTRA.items()})
    return out


def _digest(text: str) -> str:
    return hashlib.sha256(text.encode()).hexdigest()[:20]


def _failure(e: Exception) -> str:
    return f"{type(e).__name__}: {str(e)[:160]}"


def _views(n, g, seed):
    """A pod and a cluster view of random sizes, int32: what the engines
    hand a policy."""
    rng = np.random.default_rng(seed)

    def ints(hi, *shape):
        return np.asarray(rng.integers(0, hi, shape), np.int32)

    mask = rng.random((n, g)) < 0.7
    total = np.where(mask, 1000, 0).astype(np.int32)
    left = (total * rng.random((n, g))).astype(np.int32)
    cpu, mem = ints(64000, n) + 1, ints(256000, n) + 1
    pod = PodView(*(np.int32(v) for v in (
        rng.integers(1, 8000), rng.integers(1, 32000), rng.integers(0, 3),
        rng.integers(0, 1001), rng.integers(0, 1000),
        rng.integers(1, 1000))))
    nodes = NodeView(
        (cpu * rng.random(n)).astype(np.int32), cpu,
        (mem * rng.random(n)).astype(np.int32), mem,
        (left == 1000).sum(1).astype(np.int32),
        mask.sum(1).astype(np.int32), left, total,
        np.where(mask, 16000, 0).astype(np.int32), mask,
        np.ones(n, bool))
    return pod, nodes


def _scores(code: str, n: int, g: int) -> str:
    """The jit tier's bits: the closure compiled once, three states."""
    try:
        policy = jax.jit(transpiler.build_policy(code))
        out = [np.asarray(policy(*_views(n, g, seed))) for seed in range(3)]
    except Exception as e:  # noqa: BLE001 — the class IS the outcome
        return _failure(e)
    assert all(o.dtype == np.int32 and o.shape == (n,) for o in out)
    return _digest(repr([o.tolist() for o in out]))


def outcome(code: str, scored: bool) -> dict:
    """``{"16x8/f32": {"ops": ..., "packed": ...[, "scores": ...]}, ...}``:
    a digest of ``lower_ops``' triple, of the packed program's eight
    leaves, and (``scored``, at (16, 8)) of the jitted closure's scores;
    an exception's class and message where one is raised."""
    out = {}
    for x64 in (False, True):
        with jax.enable_x64(x64):
            for n, g in lc.SHAPES:
                case = out[f"{lc.shape_key(n, g)}/{'x64' if x64 else 'f32'}"] \
                    = {}
                try:
                    raw = vm.lower_ops(code, n, g)
                except Exception as e:  # noqa: BLE001
                    case["ops"] = case["packed"] = _failure(e)
                else:
                    case["ops"] = _digest(repr(raw))
                    try:
                        case["packed"] = lc.program_hash(vm.pack_program(
                            *vm.simplify_ops(*raw, g), lc.CAPACITY))[:20]
                    except vm.VMUnsupported as e:
                        case["packed"] = _failure(e)
                if scored and (n, g) == lc.SHAPES[0]:
                    case["scores"] = _scores(code, n, g)
    return out


def scored(name: str) -> bool:
    """The sources whose closure is also compiled and run: not the FakeLLM
    candidates and the generations (dozens of variations of sources that
    are), for tier-1's time."""
    return not name.startswith(("fake", "generation:"))


PINS = {}
if os.path.exists(FIXTURE):  # absent only while the parent's are recorded
    with open(FIXTURE) as f:
        PINS = json.load(f)

#: the ONE source this tree lowers to other words than the parent, on
#: purpose, and why; its scores are the parent's
NOT_THE_PARENTS = {
    "extra:static_else":
        "the else branch of a condition that is a Python constant: the "
        "parent negated it with ``~False``, the Python int -1, and carried "
        "an int32 mask through the branch (AND, NE 0 at every select); "
        "`_not` negates a static condition in Python and the masks stay "
        "bool. No source of the ledger, the seeds, the generations or "
        "tier-1's corpus has such a branch",
}


def test_corpus_is_the_recorded_one():
    assert sorted(sources()) == sorted(PINS)
    assert len(PINS) >= 180


@pytest.mark.parametrize("name", sorted(PINS))
def test_lowering_is_the_parents(name):
    got = outcome(sources()[name], scored(name))
    want = PINS[name]
    if name in NOT_THE_PARENTS:
        got, want = ({case: v.get("scores") for case, v in x.items()}
                     for x in (got, want))
    assert got == want


def main():
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_enable_compilation_cache", False)
    pins = {name: outcome(code, scored(name))
            for name, code in sources().items()}
    print(json.dumps(pins, indent=0, sort_keys=True))


if __name__ == "__main__":
    main()
