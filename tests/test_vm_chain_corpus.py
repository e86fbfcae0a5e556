"""The whole-grid column chain (PR 53) over every source the repo holds:
``vm.simplify_ops`` told the lowering's GPU width against the same pass
told none (PR 30's program). The rule by rule cases, on hand-made op
lists, are in ``tests/test_vm.py``; this file is the corpus alone, a case
a source, so that the two share no worker's time (``--dist loadfile``)."""
import functools

import jax
import numpy as np
import pytest

from fks_tpu.funsearch import transpiler, vm
from tests import lowering_corpus as corpus
from tests import test_lowering_identity as identity
from tests.test_vm import _SCORE, _poisoned, _registers, _same_bits, _state

#: `identity.sources` builds the benchmark's generations anew at every call
_sources = functools.lru_cache(maxsize=None)(identity.sources)


@pytest.mark.parametrize("name", sorted(identity.PINS))
def test_folded_program_holds_the_parents_bits(name):
    """Over the corpus of ``tests/test_lowering_identity.py``, at both
    shapes, in f32 and under x64: the program with its whole chains folded
    holds in its output register the BITS PR 30's pass leaves there (the
    pass told no width), on states whose grids hold NaN, inf and -0.0 too,
    and scores what the transpiled closure scores on the states an engine
    hands it (the closure as `test_simplified_program_scores_as_the_raw_
    lowering_and_the_closure` runs it, op by op: under ``jit`` XLA's CPU
    fusion rounds one f32 product of one FakeLLM candidate another way
    than its own unfused ops, with or without this pass); a program
    without a whole chain is that pass's word for word."""
    code = _sources()[name]
    for x64 in (False, True):
        with jax.enable_x64(x64):
            for n, g in corpus.SHAPES:
                try:
                    raw = vm.lower_ops(code, n, g)
                except Exception:  # noqa: BLE001 — no program, then as now
                    continue
                folds = vm.chains_folded()
                old = vm.simplify_ops(*raw, None)
                new = vm.simplify_ops(*raw, g)
                if vm.chains_folded() == folds:
                    assert new == old
                    continue
                assert len(new[0]) < len(old[0])
                rng = np.random.default_rng(n + g)
                for kind in ("edge", "poisoned"):
                    pod, nodes = _state(rng, n, g, kind)
                    if kind == "poisoned":
                        nodes = _poisoned(rng, nodes)
                    want = _registers(*old[:2], pod, nodes)[old[2]]
                    got = _registers(*new[:2], pod, nodes)[new[2]]
                    assert _same_bits(got, want), (n, g, x64, kind)
                if (n, g) != corpus.SHAPES[0]:
                    continue
                policy = transpiler.build_policy(code)
                p_old, p_new = (vm.pack_program(*t, corpus.CAPACITY)
                                for t in (old, new))
                for kind in ("edge", "cpu_pod", "random"):
                    pod, nodes = _state(rng, n, g, kind)
                    want = np.asarray(policy(pod, nodes))
                    for prog in (p_old, p_new):
                        np.testing.assert_array_equal(
                            np.asarray(_SCORE(prog, pod, nodes)), want, kind)
