"""Static-analysis subsystem (fks_tpu.analysis): candidate pre-flight
(pillar A) and the repo linter + jaxpr-pin gate (pillar B).

The pre-flight contract under test is REPRODUCIBILITY: every static
rejection must correspond to a real failure of the actual pipeline
(sandbox.validate / transpiler.transpile), and everything the analyzer
accepts must actually transpile — the analyzer may be conservative about
COST, never about verdicts.
"""
import json
import os
import sys

import pytest

from fks_tpu import analysis, obs
from fks_tpu.analysis import candidate, lint
from fks_tpu.funsearch import backend, llm, sandbox, template, transpiler
from fks_tpu.sim.engine import SimConfig

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools"))
import check_jsonl_schema as cjs  # noqa: E402

sys.path.pop(0)


# ------------------------------------------------------------ table sync

def test_taxonomy_synced_with_schema_checker():
    """The schema checker is stdlib-only and carries a duplicated copy of
    the taxonomy; this is the pin that keeps the copies identical."""
    assert len(set(analysis.REJECT_TAXONOMY)) == len(analysis.REJECT_TAXONOMY)
    assert set(analysis.REJECT_TAXONOMY) == cjs.CANDIDATE_REJECT_TAXONOMY


def test_tables_derived_from_transpiler():
    """Pre-flight tables must be the transpiler's own, not re-hardcoded —
    a transpiler whitelist change must flow through automatically."""
    assert candidate.ARITY == transpiler._ARITY
    assert candidate.MATH_FNS == frozenset(transpiler._MATH_FNS)
    assert candidate.MAX_UNROLL == transpiler._Interp.MAX_UNROLL
    assert candidate.POD_FIELDS == frozenset(transpiler._Pod.FIELDS)
    assert candidate.NODE_FIELDS == (
        frozenset(transpiler._Node.FIELDS) | {"gpus"})
    # GPU fields are derived from _Gpu.attr's source; the two real fields
    # must be present (derivation returning garbage would break this)
    assert {"gpu_milli_left", "gpu_milli_total"} <= candidate.GPU_FIELDS


def test_math_table_entries_actually_transpile():
    """Every math.* name the arity table admits must lower for real."""
    for name in sorted(candidate.MATH_FNS):
        lo, _hi = candidate.ARITY[f"math.{name}"]
        args = ", ".join(["1.5"] * lo)
        code = template.fill_template(f"score = 1 + math.{name}({args})")
        rep = analysis.preflight_check(code)
        assert rep.ok, (name, rep.reason)
        assert callable(transpiler.transpile(code))


# ------------------------------------ rejections reproduce real failures

BAD = [
    ("syntax", "def priority_function(pod, node:\n    return 1"),
    ("bad_signature", "def priority_function(pod, nodes):\n    return 1"),
    ("bad_signature",
     "def priority_function(pod, node):\n    return 1\nx = 2"),
    ("forbidden_construct", None, "score = pod.__class__"),
    ("forbidden_construct",
     "def priority_function(pod, node):\n    x = node.gpus[0:1]\n"
     "    return 1"),
    ("unsupported_syntax", None, "x, y = 1, 2\n    score = x + y"),
    ("unsupported_syntax", None,
     "x = 1\n    for i in range(x):\n        x = x + 1\n    score = x"),
    ("unsupported_syntax", None, "score = node.gpus[pod.num_gpu]"
     ".gpu_milli_left"),
    ("unsupported_call", None, "score = str(pod.cpu_milli)"),
    ("unsupported_call", None, "score = sum(node.gpus)"),
    ("bad_arity", None, "score = math.sqrt(1.0, 2.0)"),
    ("bad_arity", None, "score = min(5)"),
    ("unknown_attribute", None, "score = pod.gpu_count"),
    ("unknown_attribute", None, "score = node.cpu_total"),
    ("unknown_attribute", None,
     "score = sum(g.volts for g in node.gpus)"),
    ("loop_too_long", None,
     "score = 0\n    for i in range(100):\n        score = score + 1"),
]
BAD = [(t, rest[-1] if rest[0] is None else rest[0])
       for t, *rest in BAD]


@pytest.mark.parametrize("taxonomy,form", BAD,
                         ids=[f"{t}-{i}" for i, (t, _) in enumerate(BAD)])
def test_rejection_reproduces_as_real_failure(taxonomy, form):
    code = (form if form.startswith("def ")
            else template.fill_template(form))
    rep = analysis.preflight_check(code)
    assert not rep.ok
    assert rep.taxonomy == taxonomy, (rep.taxonomy, rep.reason)
    # the static verdict must match the actual pipeline: transpile (which
    # runs sandbox.validate first) must fail on the same candidate
    with pytest.raises(transpiler.TranspileError):
        transpiler.transpile(code)


GOOD = [
    "score = 100 + pod.cpu_milli / max(1, node.cpu_milli_left)",
    template.SEED_LOGIC["best_fit"],
    # loop bound that is a loop index of an enclosing static range
    "score = 0\n    for i in range(2):\n        for j in range(i):\n"
    "            score = score + 1",
    # static int arithmetic in the bound
    "score = 0\n    for i in range(2 + 1):\n        score = score + i",
    # zero-trip loop: the body is dead and never lowered, so a call the
    # transpiler cannot lower is still fine there — the analyzer must not
    # reject guaranteed-dead code the pipeline accepts
    "score = 1\n    for i in range(0):\n        score = str(i)",
    "score = sum(g.gpu_milli_left for g in node.gpus"
    " if g.gpu_milli_left > 100)",
    "score = len(sorted(g.gpu_milli_left for g in node.gpus))",
    "score = sorted(g.gpu_milli_left for g in node.gpus)[0]",
    "score = 0\n    for i, g in enumerate(node.gpus):\n"
    "        score = score + g.gpu_milli_left * i",
]


@pytest.mark.parametrize("form", GOOD,
                         ids=[f"good-{i}" for i in range(len(GOOD))])
def test_accepted_forms_actually_transpile(form):
    code = template.fill_template(form)
    rep = analysis.preflight_check(code)
    assert rep.ok, rep.reason
    assert rep.cost is not None and rep.fingerprint is not None
    assert callable(transpiler.transpile(code))


def test_fakellm_stream_verdicts_reproduce():
    """Property check over the synthetic candidate stream: every pre-flight
    verdict (accept or reject, any taxonomy) matches the real pipeline."""
    gen = llm.FakeLLM(seed=11, junk_rate=0.5)
    rejected = 0
    for _ in range(40):
        code = template.fill_template(gen.complete(""))
        rep = analysis.preflight_check(code)
        if rep.ok:
            assert callable(transpiler.transpile(code))
        else:
            rejected += 1
            assert rep.taxonomy in analysis.REJECT_TAXONOMY
            with pytest.raises(transpiler.TranspileError):
                transpiler.transpile(code)
    assert rejected > 0  # junk_rate=0.5 must exercise the reject path


# ---------------------------------------------------------- fingerprints

def _fp(logic: str) -> str:
    return analysis.fingerprint(template.fill_template(logic))


def test_fingerprint_alpha_rename_invariant():
    assert _fp("x = 1\n    score = x") == _fp("y = 1\n    score = y")


def test_fingerprint_buckets_same_decade_constants():
    a = _fp("score = pod.cpu_milli * 1.5")
    b = _fp("score = pod.cpu_milli * 1.7")
    c = _fp("score = pod.cpu_milli * 150.0")
    assert a == b      # same sign+decade bucket -> near-duplicate
    assert a != c      # different decade is a different policy shape


def test_fingerprint_sees_structure():
    assert _fp("score = pod.cpu_milli + 1") != _fp("score = pod.cpu_milli * 2")


def test_fingerprint_ignores_docstring():
    a = analysis.fingerprint(
        'def priority_function(pod, node):\n    """a"""\n    return 1\n')
    b = analysis.fingerprint(
        'def priority_function(pod, node):\n    """totally new"""\n'
        '    return 1\n')
    assert a == b


# ------------------------------------------------------------- cost model

def test_cost_scales_with_gpu_loop_depth():
    flat = analysis.preflight_check(
        template.fill_template("score = pod.cpu_milli + 1"))
    loop = analysis.preflight_check(template.fill_template(
        "score = sum(g.gpu_milli_left for g in node.gpus)"))
    assert flat.ok and loop.ok
    # the template prologue already loops over node.gpus, so BOTH grow
    # with the padded GPU count — but the gpu-loop body must grow faster
    # (a larger per-GPU coefficient) and cost more at equal G
    assert loop.cost.work(2) < loop.cost.work(16)
    assert (loop.cost.work(16) - loop.cost.work(2)
            > flat.cost.work(16) - flat.cost.work(2))
    assert loop.cost.work(8) > flat.cost.work(8)


def test_cost_grows_with_more_ops():
    small = analysis.preflight_check(
        template.fill_template("score = pod.cpu_milli + 1"))
    big = analysis.preflight_check(template.fill_template(
        "score = pod.cpu_milli * 2 + pod.memory_mib * 3 + pod.num_gpu * 4"))
    assert small.cost.work(8) < big.cost.work(8)


# ------------------------------------------- evaluator integration proof

_FP_TWIN_A = "x = 1\n    score = x + pod.cpu_milli * 1.5"
_FP_TWIN_B = "y = 1\n    score = y + pod.cpu_milli * 1.7"


def test_statically_rejected_never_reaches_sandbox(micro_workload,
                                                   monkeypatch):
    """The acceptance criterion: a pre-flight-rejected candidate (and a
    fingerprint-duplicate echo) provably never reaches sandbox.validate —
    every source sandbox.validate actually sees is recorded."""
    seen = []
    real_validate = sandbox.validate

    def counting_validate(code, *a, **k):
        seen.append(code)
        return real_validate(code, *a, **k)

    monkeypatch.setattr(sandbox, "validate", counting_validate)

    good = template.fill_template(GOOD[0])
    twin_a = template.fill_template(_FP_TWIN_A)
    twin_b = template.fill_template(_FP_TWIN_B)
    bad = [code for _, form in BAD
           for code in [form if form.startswith("def ")
                        else template.fill_template(form)]]
    ev = backend.CodeEvaluator(micro_workload, SimConfig())
    recs = ev.evaluate([good, twin_a, *bad, twin_b])
    assert len(recs) == len(bad) + 3

    assert ev.preflight_rejected == len(bad)
    assert ev.preflight_duplicates == 1
    for code in bad:
        assert code not in seen  # never validated, never transpiled
    assert twin_b not in seen    # dup echo rides the twin_a representative
    assert recs[0].ok
    # the echo gets the representative's record, not a zero
    assert recs[-1].score == recs[1].score
    stats = ev.last_eval_stats
    assert stats["preflight_rejected"] == len(bad)
    assert stats["fingerprint_duplicates"] == 1
    assert stats["unique"] == 2
    assert stats["mean_static_work"] > 0


@pytest.mark.parametrize("taxonomy,form", BAD,
                         ids=[f"{t}-{i}" for i, (t, _) in enumerate(BAD)])
def test_a_doomed_source_comes_back_before_the_lowering(taxonomy, form,
                                                        monkeypatch):
    """The check rides the task that lowers the source (ISSUE 51):
    ``lower_pool.lower_source`` returns at once with the pre-flight's
    verdict where the evaluator asks for it, ``vm.lower_ops`` is never
    entered and no equation is traced; without ``preflight`` the same
    source goes on and fails where it always did."""
    from fks_tpu.funsearch import lower_pool, vm

    code = (form if form.startswith("def ")
            else template.fill_template(form))
    rep = analysis.preflight_check(code)
    eqns, lower_ops, entered = vm.eqns_traced(), vm.lower_ops, []
    monkeypatch.setattr(vm, "lower_ops", lambda *a: entered.append(a)
                        or lower_ops(*a))
    low = lower_pool.lower_source(lower_pool.Source(code, True, True), 16, 8)
    assert low.rejection == (taxonomy, rep.reason)
    assert (low.key, low.kept, low.words, low.error, low.fingerprint,
            low.work) == (None,) * 6
    assert (low.traces, low.eqns, low.ops_lowered) == (0, 0, 0)
    assert not entered and vm.eqns_traced() == eqns
    assert low.t0 < low.t_checked == low.t_traced == low.t1
    # the verdict is the evaluator's to ask for: without ``preflight``
    # the source is keyed and lowered, and the lowering fails on it
    low = lower_pool.lower_source(lower_pool.Source(code, False, True),
                                  16, 8)
    if taxonomy == "syntax":
        assert low.rejection.taxonomy is None and not entered
    else:
        assert low.rejection is None and len(entered) == 1
        assert low.key == transpiler.canonical_key(code)
        assert isinstance(low.error, transpiler.TranspileError)
    assert low.kept is None and low.words is None
    assert low.fingerprint is None and low.work is None


def test_an_accepted_source_brings_its_fingerprint_and_work_home():
    """What the evaluator's dedup and ``mean_static_work`` read comes back
    with the lowering: the pre-flight's fingerprint and its static work at
    the cluster's GPUs a node; none of it for a source that asks for no
    check (a bare string)."""
    from fks_tpu.funsearch import lower_pool

    code = template.fill_template(GOOD[0])
    rep = analysis.preflight_check(code)
    for asked in ((True, True), (True, False), (False, True)):
        low = lower_pool.lower_source(lower_pool.Source(code, *asked), 16, 8)
        assert low.rejection is None and low.error is None
        assert low.fingerprint == rep.fingerprint
        assert low.work == rep.cost.work(8) > rep.cost.work(1) > 0
        assert low.key == transpiler.canonical_key(code)
        assert int(low.words.n_ops) == len(low.kept[0]) > 0
    for bare in (code, lower_pool.Source(code)):
        low = lower_pool.lower_source(bare, 16, 8)
        assert (low.fingerprint, low.work) == (None, None)
        assert low.key == transpiler.canonical_key(code)
    check = lower_pool.check_source(lower_pool.Source(code, True, True), 8)
    assert (check.key, check.fingerprint, check.work) \
        == (low.key, rep.fingerprint, rep.cost.work(8))
    assert check.kept is None and check.words is None and check.traces == 0


def test_preflight_off_restores_legacy_path(micro_workload):
    """preflight=False / fp_dedup=False must fall back to the pre-analyzer
    pipeline: rejects still fail (downstream), duplicates evaluate twice."""
    ev = backend.CodeEvaluator(micro_workload, SimConfig(),
                               preflight=False, fp_dedup=False)
    recs = ev.evaluate([template.fill_template("score = str(pod.cpu_milli)"),
                        template.fill_template(_FP_TWIN_A),
                        template.fill_template(_FP_TWIN_B)])
    assert ev.preflight_rejected == 0 and ev.preflight_duplicates == 0
    assert not recs[0].ok and "preflight" not in recs[0].error
    assert recs[1].ok and recs[2].ok


def test_rejection_events_round_trip_through_schema_checker(
        micro_workload, tmp_path):
    """candidate_rejected events written by a real evaluate() batch must
    satisfy the ledger schema checker, taxonomy vocabulary included."""
    d = str(tmp_path / "run")
    with obs.recording(obs.FlightRecorder(d, meta={"command": "test"})):
        ev = backend.CodeEvaluator(micro_workload, SimConfig())
        ev.evaluate([
            template.fill_template("score = str(pod.cpu_milli)"),
            template.fill_template(_FP_TWIN_A),
            template.fill_template(_FP_TWIN_B),
            "def priority_function(pod, node:\n    return 1",
        ])
    with open(os.path.join(d, "events.jsonl")) as f:
        events = [json.loads(l) for l in f if l.strip()]
    rej = [e for e in events if e["kind"] == "candidate_rejected"]
    assert sorted(e["taxonomy"] for e in rej) == [
        "duplicate_fingerprint", "syntax", "unsupported_call"]
    assert {e["stage"] for e in rej} == {"preflight", "fp_dedup"}
    counts = cjs.check_run_dir(d)
    assert counts["events.jsonl"] == len(events)


def test_schema_checker_rejects_unknown_taxonomy(tmp_path):
    p = tmp_path / "events.jsonl"
    p.write_text(json.dumps({"ts": 1.0, "kind": "candidate_rejected",
                             "taxonomy": "vibes", "stage": "preflight"})
                 + "\n")
    recs = cjs.check_jsonl(str(p), required=("ts", "kind"))
    with pytest.raises(cjs.SchemaError, match="taxonomy"):
        cjs.check_kinds(str(p), recs, cjs.EVENT_KIND_REQUIRED)


# ------------------------------------------------------------- AST lints

_LINT_BAD = '''
import functools
import jax
import numpy as np
from functools import partial

@jax.jit
def f(x, cfg):
    while x > 0:
        x = x - 1
    if x > 0:
        x = np.ones(3)
    return x.item()

@partial(jax.jit, static_argnames=("mode",))
def g(x, mode):
    if mode:
        return x
    if x > 0:
        return -x
    return x

@jax.jit
def h(state, cfg: SimConfig):
    return state
'''


def test_lint_rules_fire():
    findings = lint.lint_source("mod.py", _LINT_BAD)
    codes = [f.code for f in findings]
    assert codes.count("FKS101") == 1   # while in f
    assert codes.count("FKS102") == 2   # if in f, traced if in g
    assert codes.count("FKS103") == 1   # .item() in f
    assert codes.count("FKS104") == 1   # np.ones in f
    assert codes.count("FKS105") == 1   # cfg: SimConfig traced in h
    # static_argnames excluded: `if mode:` in g must NOT be flagged
    g_hits = [f for f in findings if "'mode'" in f.message]
    assert not g_hits
    assert all(f.path == "mod.py" and f.line > 0 for f in findings)
    assert all(f.code in str(f) for f in findings)


def test_lint_ignores_unjitted_and_closures():
    src = (
        "import jax\n"
        "def plain(x):\n"
        "    while x > 0:\n"
        "        x = x - 1\n"
        "    return x.item()\n"
        "def build(cfg):\n"
        "    @jax.jit\n"
        "    def step(s):\n"
        "        if cfg.watchdog:\n"   # closure read: sanctioned pattern
        "            return s + 1\n"
        "        return s\n"
        "    return step\n")
    assert lint.lint_source("mod.py", src) == []


def test_lint_leaves_an_aot_compile_site_alone():
    """A ``.lower(...).compile()`` owes nothing to a ledger: the rule that
    made every compile site file a memory footprint went with the ledger
    nothing read."""
    src = (
        "import jax\n"
        "def bucket(fn, example):\n"
        "    return jax.jit(fn).lower(*example).compile()\n")
    assert lint.lint_source("mod.py", src) == []
    assert sorted(lint.LINT_CODES) == [
        "FKS101", "FKS102", "FKS103", "FKS104", "FKS105"]


def test_lint_syntax_error_is_a_finding():
    findings = lint.lint_source("broken.py", "def f(:\n")
    assert [f.code for f in findings] == ["FKS100"]


def test_repo_lints_clean():
    """The acceptance criterion: the package's own sources carry zero
    findings (``cli lint`` runs the same function)."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert lint.lint_paths([os.path.join(root, "fks_tpu")]) == []


# ------------------------------------------------------------ jaxpr pins

@pytest.fixture(scope="module")
def pins():
    """One sweep for the whole module — the lowering is trace-only but
    still seconds, so every pin test shares it via check_pins(current=)."""
    return lint.compute_pins()


def test_committed_manifest_matches_current_lowerings(pins):
    assert lint.check_pins(lint.PIN_MANIFEST, current=pins) == []


def test_pin_catches_traced_static_flag(pins):
    """A Python-static SimConfig flag turning into a traced read changes
    the lowered program — each single-flag variant must hash differently
    from baseline, so that regression is detectable as drift."""
    base = pins["pins"]["flat_step/baseline"]
    for name in ("watchdog", "decision_trace", "prefilter_k1",
                 "no_track_ctime", "state_pack", "cond_policy"):
        assert pins["pins"][f"flat_step/{name}"] != base, name
    # probe_score gates finalize, not the step — its pair is pinned there
    assert (pins["pins"]["flat_finalize/probe_score"]
            != pins["pins"]["flat_finalize/baseline"])
    assert pins["pins"]["flat_step/probe_score"] == base


def test_pin_drift_and_staleness_detected(pins, tmp_path):
    man = json.loads(json.dumps(pins))  # deep copy
    man["pins"]["flat_step/watchdog"] = "0" * 64
    man["pins"]["ghost/entry"] = "1" * 64
    del man["pins"]["serve_bucket/exact_l1_p16"]
    p = tmp_path / "pins.json"
    p.write_text(json.dumps(man))
    msgs = lint.check_pins(str(p), current=pins)
    assert any("drift" in m and "flat_step/watchdog" in m for m in msgs)
    assert any("stale" in m and "ghost/entry" in m for m in msgs)
    assert any("unpinned" in m and "serve_bucket" in m for m in msgs)


def test_missing_manifest_reported(pins, tmp_path):
    msgs = lint.check_pins(str(tmp_path / "nope.json"), current=pins)
    assert len(msgs) == 1 and "missing" in msgs[0]


def test_jax_version_change_reported(pins, tmp_path):
    man = json.loads(json.dumps(pins))
    man["jax"] = "9.9.9"
    p = tmp_path / "pins.json"
    p.write_text(json.dumps(man))
    msgs = lint.check_pins(str(p), current=pins)
    assert any("jax version" in m for m in msgs)


def test_write_pins_round_trips(pins, tmp_path, monkeypatch):
    monkeypatch.setattr(lint, "compute_pins", lambda: pins)
    p = str(tmp_path / "pins.json")
    man = lint.write_pins(p)
    assert man == pins
    assert lint.check_pins(p, current=pins) == []


def test_pinner_workload_matches_conftest_recipe():
    """lint._micro_workload is a copy of conftest.make_micro_workload
    (the pinner must run outside pytest); the copies must stay identical
    or the committed pins stop describing what the tests exercise."""
    import numpy as np
    from tests.conftest import make_micro_workload

    a = lint._micro_workload()
    b = make_micro_workload()
    import jax
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ----------------------------------------------------------- cli surface

def test_cli_lint_exit_codes(tmp_path):
    from fks_tpu import cli

    clean = tmp_path / "clean.py"
    clean.write_text("def f(x):\n    return x + 1\n")
    dirty = tmp_path / "dirty.py"
    dirty.write_text("import jax\n\n@jax.jit\ndef f(x):\n"
                     "    while x > 0:\n        x = x - 1\n    return x\n")
    assert cli.main(["lint", "--cpu", "--no-pins", str(clean)]) == 0
    assert cli.main(["lint", "--cpu", "--no-pins", str(dirty)]) == 1
    # missing manifest is drift (exit 1), reported before any lowering
    assert cli.main(["lint", "--cpu", "--pins",
                     str(tmp_path / "nope.json"), str(clean)]) == 1


def test_cli_lint_report_record(tmp_path):
    from fks_tpu import cli

    clean = tmp_path / "clean.py"
    clean.write_text("def f(x):\n    return x\n")
    d = str(tmp_path / "run")
    rc = cli.main(["lint", "--cpu", "--no-pins", "--run-dir", d,
                   str(clean)])
    assert rc == 0
    with open(os.path.join(d, "metrics.jsonl")) as f:
        recs = [json.loads(l) for l in f if l.strip()]
    rep = next(r for r in recs if r["kind"] == "lint_report")
    assert rep["ok"] and rep["findings"] == [] and rep["pin_drift"] == []
    counts = cjs.check_run_dir(d)
    assert counts["metrics.jsonl"] == len(recs)
