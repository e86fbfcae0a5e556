"""The sources the lowering pins are recorded over (ISSUE 28): the seed
policies, the 13 ledger champions, the valid and invalid sources that
tests/test_transpiler.py, tests/test_funsearch_sandbox.py and
tests/test_vm*.py hold, and the FakeLLM completions behind
``vm.lower_fake_candidates`` and ``test_vm._corpus``.

``outcome`` is what ``tests/fixtures/vm_lowering_pins.json`` records per
source and shape: a hash of the lowered program's eight leaves, or the
exception's class and message. ``mixed_batch_records`` is what
``tests/fixtures/mixed_batch_records.json`` records: ``CodeEvaluator
.evaluate`` on a generation of valid sources, subset violations, a
VMUnsupported source and a syntax error, every field of every record.
``echo_generation`` is what ``tests/fixtures/echo_generation.json`` records
(``... echoes``, from PR 51's PARENT; PR 53 replaced the hash of the ONE
lane whose program its simplifier shortens, the champion's, and nothing
else: records, events, counters and lane order are that parent's).
``python -m tests.lowering_corpus lowering|records`` prints the pins of the
tree it runs on (``... primitives``: `flat_primitives` of three policies,
``tests/fixtures/policy_primitives.json``, recorded from PR 50's parent). The records were recorded from PR 28's PARENT, whose
``compile_policy`` still dry-traced at 2 x 2 before it traced at the real
shape; the lowering pins were re-recorded by PR 30, whose ``compile_policy``
packs what ``vm.simplify_ops`` keeps (182 of the 244 cases are programs and
all 182 got shorter; the 62 errors are unchanged), and again by PR 53, whose
``simplify_ops`` folds a whole column chain into its grid (88 of the 182
programs hold one and got shorter; the other 94 and the errors are
unchanged, hash for hash).
"""
import functools
import glob
import hashlib
import json
import os

import numpy as np

from fks_tpu.funsearch import llm, template, transpiler, vm

SHAPES = ((16, 8), (64, 8))
CAPACITY = 512
LEAVES = ("opcode", "a", "b", "c", "imm", "consts", "n_ops", "out_reg")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _champions():
    out = {}
    for path in sorted(glob.glob(os.path.join(
            ROOT, "policies", "discovered", "funsearch_*.json"))):
        with open(path) as f:
            out["champion:" + os.path.basename(path)[len("funsearch_"):-5]] \
                = json.load(f)["code"]
    return out


def _fake(seed, count):
    fake = llm.FakeLLM(seed=seed, junk_rate=0.0)
    return {f"fake{seed}:{i:02d}": template.fill_template(fake.complete("x"))
            for i in range(count)}


@functools.lru_cache(maxsize=None)
def sources():
    """name -> source, in a fixed order (one dict, shared: read only)."""
    from tests import test_funsearch_sandbox as ts
    from tests import test_transpiler as tt
    from tests import test_vm_serve as tv

    fill = template.fill_template
    out = {"seed:" + k: v for k, v in template.seed_policies().items()}
    out.update(_champions())
    out.update({"block:" + k: fill(v)
                for k, v in sorted(tt.LOGIC_BLOCKS.items())})
    marks = {m.name: m for m in tt.test_unsupported_subset_raises.pytestmark}
    out.update({f"subset:{i}": fill(v) for i, v in enumerate(
        marks["parametrize"].args[1])})
    marks = {m.name: m
             for m in tt.test_sorted_and_subscript_match_oracle.pytestmark}
    out.update({f"sorted:{i}": fill(v) for i, v in enumerate(
        marks["parametrize"].args[1])})
    out.update({
        "nonfinite": fill("score = 1.0 / (pod.num_gpu * 0)"),
        "rebind:plain": fill(
            "xs = sorted(g.gpu_milli_left for g in node.gpus)\n"
            "xs = 7.0\nscore = xs"),
        "rebind:conditional": fill(
            "xs = sorted(g.gpu_milli_left for g in node.gpus)\n"
            "if pod.num_gpu > 0:\n        xs = 1.0\nscore = 1"),
        "empty_min": fill(
            "score = min(gpu.gpu_milli_left for gpu in node.gpus)"),
        "untaken_ifexp": fill(
            "score = int(100.0 / (node.gpu_left * 0)) "
            "if node.gpu_left > 9999 else 5"),
        "unbound": fill("if node.gpu_left > 0:\n        bonus = 5\n"
                        "    score = 10 + bonus"),
        "sandbox:good": ts.GOOD,
        "sandbox:bomb": ts.BOMB,
        "sandbox:lambda": ("def priority_function(pod, node):\n"
                           "    f = lambda: 1\n    return 1"),
        "sandbox:slice": ("def priority_function(pod, node):\n"
                          "    x = node.gpus[0:1]\n    return 1"),
        "sandbox:starred": fill("score = max(*node.gpus)"),
        "sandbox:signature": "def priority_function(a, b):\n    return 1",
        "sandbox:name": "def other(pod, node):\n    return 1",
        "sandbox:print": fill("score = print(1)"),
        "sandbox:syntax": "def priority_function(pod, node:\n    return 1",
        "syntax:broken": "def broken(:",
    })
    marks = {m.name: m for m in ts.test_rejects_escapes.pytestmark}
    out.update({f"escape:{i}": fill(v) for i, v in enumerate(
        marks["parametrize"].args[1])})
    out.update({
        "serve:seed": fill(tv.SEED_LOGIC),
        "serve:better": fill(tv.BETTER_LOGIC),
        "serve:even_better": fill(tv.EVEN_BETTER_LOGIC),
        "vm:unsupported": fill(tv.UNSUPPORTED_LOGIC),
        "vm:const_pool": fill("score = 1.0\n    " + "\n".join(
            f"    score = score + {i}.{i:03d}1 * pod.cpu_milli"
            for i in range(vm.CONST_POOL + 2)).strip()),
    })
    out.update(_fake(3, 30))   # test_vm._corpus
    out.update(_fake(7, 24))   # vm.lower_fake_candidates' default seed
    return out


def _hash_leaves(named_leaves) -> str:
    h = hashlib.sha256()
    for name, leaf in named_leaves:
        arr = np.asarray(leaf)
        h.update(f"{name}:{arr.dtype}:{arr.shape}:".encode())
        h.update(arr.tobytes())
    return h.hexdigest()


def program_hash(prog) -> str:
    return _hash_leaves((leaf, getattr(prog, leaf)) for leaf in LEAVES)


def outcome(code: str, n: int, g: int) -> dict:
    try:
        prog = vm.compile_policy(code, n, g, capacity=CAPACITY)
    except Exception as e:  # noqa: BLE001 — the class IS the outcome
        return {"error": type(e).__name__, "message": str(e)[:160]}
    return {"hash": program_hash(prog), "n_ops": int(prog.n_ops)}


def shape_key(n: int, g: int) -> str:
    return f"{n}x{g}"


MIXED = ("seed:first_fit", "subset:2", "seed:best_fit", "vm:unsupported",
         "syntax:broken", "fake3:00", "subset:3", "seed:first_fit",
         "rebind:conditional", "block:gpu_loop_if")
#: (vm_batch, preflight): the batched tier's loop over compile_policy and
#: evaluate_one's VM branch, with and without the static pre-flight that
#: would catch two of the three violations before the transpile stage
MIXED_MODES = ((True, True), (True, False), (False, True), (False, False))


def mixed_workload():
    """3 nodes x 14 pods padded to 4 x 4 (not the dry trace's 2 x 2), with
    enough contention that the valid sources score differently."""
    from fks_tpu.data.build import make_workload

    nodes = [{"node_id": "n0", "cpu_milli": 4000, "memory_mib": 8000,
              "gpus": [1000, 1000]},
             {"node_id": "n1", "cpu_milli": 2000, "memory_mib": 4000,
              "gpus": []},
             {"node_id": "n2", "cpu_milli": 3000, "memory_mib": 4000,
              "gpus": [1000]}]
    pods = [{"pod_id": f"p{i}", "cpu_milli": 400 + 300 * (i % 3),
             "memory_mib": 500 + 700 * (i % 2), "num_gpu": i % 2,
             "gpu_milli": 300 + 200 * (i % 3) if i % 2 else 0,
             "creation_time": i // 2, "duration_time": 3 + i % 4}
            for i in range(14)]
    return make_workload(nodes, pods, pad_nodes_to=4, pad_gpus_to=4,
                         pad_pods_to=16)


def mixed_batch_records(vm_batch: bool, preflight: bool) -> list:
    """One dict per record of the MIXED generation, every field: the
    SimResult as a hash of its leaves."""
    from fks_tpu.funsearch import backend

    src = sources()
    codes = [src[n] for n in MIXED]
    ev = backend.CodeEvaluator(mixed_workload(), vm_batch=vm_batch,
                               preflight=preflight)
    out = []
    for name, code, r in zip(MIXED, codes, ev.evaluate(codes)):
        assert r.code == code
        res = r.result and _hash_leaves(
            (f, leaf) for f, leaf in zip(r.result._fields, r.result)
            if leaf is not None)
        out.append({"source": name, "score": r.score, "error": r.error,
                    "result": res, "scenario_scores": r.scenario_scores,
                    "aggregation": r.aggregation,
                    "budget_rung": r.budget_rung})
    return out


def mode_key(vm_batch: bool, preflight: bool) -> str:
    return f"vm_batch={int(vm_batch)},preflight={int(preflight)}"


#: a generation that holds every kind of echo and rejection (ISSUE 51):
#: an exact echo, a source that differs in text and agrees in canonical
#: key, a pair of fingerprint twins, a syntax error, a statically doomed
#: source and its exact echo, a subset violation only the trace finds, a
#: VMUnsupported source, and a ledger champion (another capacity bucket)
ECHOES = ("seed:first_fit", "seed:best_fit", "twin:a", "syntax:broken",
          "seed:first_fit", "doomed", "key_echo:best_fit", "fake3:00",
          "twin:b", "subset:2", "vm:unsupported", "doomed",
          "champion:20260801_045536_score0.5365", "block:gpu_loop_if")
#: (preflight, fp_dedup) of the evaluator
ECHO_MODES = ((True, True), (False, True), (False, False))


def echo_sources() -> dict:
    fill = template.fill_template
    return dict(sources(), **{
        "twin:a": fill("x = 1\n    score = x + pod.cpu_milli * 1.5"),
        "twin:b": fill("y = 1\n    score = y + pod.cpu_milli * 1.7"),
        "doomed": fill("score = str(pod.cpu_milli)"),
        "key_echo:best_fit": sources()["seed:best_fit"] + "\n# an echo\n"})


def echo_generation(preflight: bool, fp_dedup: bool) -> dict:
    """`ECHOES` as one generation of the batched tier: every field of
    every record, the evaluator's counters and ``last_eval_stats``, the
    ``candidate_rejected`` events in order, and the lanes of the launch
    (a hash of each lane's program, in lane order). Runs on any tree:
    ``tests/fixtures/echo_generation.json`` was recorded from PR 51's
    PARENT (``python -m tests.lowering_corpus echoes``), whose evaluator
    checked and keyed the sources itself, one after another."""
    from fks_tpu import obs
    from fks_tpu.funsearch import backend

    class Events(obs.NullRecorder):
        def __init__(self):
            self.seen = []

        def event(self, kind, **fields):
            self.seen.append({"kind": kind, **fields})

    src = echo_sources()
    codes = [src[n] for n in ECHOES]
    ev = backend.CodeEvaluator(mixed_workload(), vm_batch=True,
                               preflight=preflight, fp_dedup=fp_dedup)
    lanes, run = [], ev._run_vm_batch

    def launch(progs):
        lanes.extend(program_hash(vm.pad_capacity(p, CAPACITY))
                     for p in progs)
        return run(progs)

    ev._run_vm_batch = launch
    events = Events()
    with obs.recording(events):
        recs = ev.evaluate(codes)
    out = []
    for name, code, r in zip(ECHOES, codes, recs):
        assert r.code == code
        out.append({"source": name, "score": r.score, "error": r.error,
                    "result": r.result and _hash_leaves(
                        (f, leaf) for f, leaf in zip(r.result._fields,
                                                     r.result)
                        if leaf is not None)})
    return {"records": out, "events": events.seen, "lanes": lanes,
            "preflight_rejected": ev.preflight_rejected,
            "preflight_duplicates": ev.preflight_duplicates,
            "stats": ev.last_eval_stats}


#: the sources whose jaxpr's primitives are pinned (ISSUE 50): a ledger
#: champion and the two seed policies of every generation
PRIMITIVE_SOURCES = ("champion:20260801_045536_score0.5365",
                     "seed:first_fit", "seed:best_fit")
_CALLS = {"jit": "jaxpr", "pjit": "jaxpr", "closed_call": "call_jaxpr",
          "custom_jvp_call": "call_jaxpr"}


def flat_primitives(closed) -> list:
    """``[name, note]`` for every equation of a policy's jaxpr in order,
    nested calls walked in place. ``note`` is "" for an equation that
    computes something, and says why for the three kinds that do not: a
    "call" (the ``jit`` equation around a ``jax.numpy`` function), a
    ``convert_element_type`` that "folds" (its operand is a scalar literal:
    bound outside a nested call, the trace folds it into the literal), and
    the "x64 probe" (``jnp.zeros(0)``, whose dtype the old transpiler
    read to learn the ambient float)."""
    from jax.extend.core import Literal

    out = []

    def walk(jaxpr, literal):
        def is_literal(atom):
            return isinstance(atom, Literal) or id(atom) in literal

        for e in jaxpr.eqns:
            name = e.primitive.name
            if name in _CALLS:
                out.append([name, "call"])
                sub = e.params[_CALLS[name]].jaxpr
                outs = walk(sub, {id(v) for v, a in zip(sub.invars, e.invars)
                                  if is_literal(a)})
                literal.update(id(v) for v, lit in zip(e.outvars, outs)
                               if lit)
            elif name == "convert_element_type" and is_literal(e.invars[0]) \
                    and not e.invars[0].aval.shape:
                literal.add(id(e.outvars[0]))
                out.append([name, "folds"])
            elif name == "broadcast_in_dim" and e.params["shape"] == (0,):
                out.append([name, "x64 probe"])
            else:
                out.append([name, ""])
        return [is_literal(v) for v in jaxpr.outvars]

    walk(closed.jaxpr, set())
    return out


def policy_primitives(code: str, n: int, g: int) -> list:
    import jax

    return flat_primitives(jax.make_jaxpr(transpiler.build_policy(code))(
        *vm._dummy_views(n, g)))


def main(what: str):
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_enable_x64", True)  # as tests/conftest.py
    jax.config.update("jax_enable_compilation_cache", False)
    if what == "lowering":
        pins = {name: {shape_key(n, g): outcome(code, n, g)
                       for n, g in SHAPES}
                for name, code in sources().items()}
    elif what == "primitives":  # run in a checkout of PR 50's PARENT
        print(json.dumps({name: policy_primitives(sources()[name], *SHAPES[0])
                          for name in PRIMITIVE_SOURCES}))
        return
    elif what == "echoes":  # run in a checkout of PR 51's PARENT
        pins = {f"preflight={int(p)},fp_dedup={int(f)}": echo_generation(p, f)
                for p, f in ECHO_MODES}
    else:
        pins = {mode_key(*m): mixed_batch_records(*m) for m in MIXED_MODES}
    print(json.dumps(pins, indent=1, sort_keys=True))


if __name__ == "__main__":
    import sys

    main(sys.argv[1])
