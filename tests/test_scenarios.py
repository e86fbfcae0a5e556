"""Scenario-suite subsystem tests (fks_tpu.scenarios).

Coverage map:
- generator determinism (byte-identical regeneration from seeds)
- fault-event construction (sorting, padding, kind validation)
- cordon semantics on BOTH engines (no placement onto a downed node
  during its window; placements resume after NODE_UP; no eviction)
- golden fault fixture (tools/make_golden.py --scenario-fault): exact AND
  flat engines held to the pinned scores (<= 1e-5) and the pinned
  per-CREATE placement vector — the score is aggregate-utilization and
  invariant to WHICH node hosts a pod, so the assignment sequence is the
  pin that actually catches fault-semantics regressions
- suite registry + vmapped suite eval == per-scenario sequential evals
- mesh-sharded suite eval == unsharded population eval, elites ranked by
  the composite robust score
- aggregation math + RobustConfig validation
- CodeEvaluator / FunSearch wiring (per-scenario breakdown in records,
  champion JSON, GenerationStats) and the fused-engine rejection
- cli scenarios / schema-checker acceptance of the new record kinds
"""
import dataclasses
import json
import pathlib
import subprocess
import sys

import jax
import numpy as np
import pytest

from fks_tpu.data.build import make_workload
from fks_tpu.data.synthetic import synthetic_workload
from fks_tpu.models import parametric, zoo
from fks_tpu.funsearch import tracing
from fks_tpu.ops.heap import KIND_NODE_DOWN, KIND_NODE_UP
from fks_tpu.scenarios import (
    RobustConfig, ScenarioSpec, aggregate, fault_events_for, get_suite,
    list_suites, make_fault_events, make_sharded_suite_eval, make_suite_eval,
    perturb_workload,
)
from fks_tpu.sim import get_engine
from fks_tpu.sim.engine import SimConfig

FIXTURES = pathlib.Path(__file__).parent / "fixtures"
REPO = pathlib.Path(__file__).parent.parent


def _assert_trees_identical(a, b):
    la, ta = jax.tree_util.tree_flatten(a)
    lb, tb = jax.tree_util.tree_flatten(b)
    assert ta == tb
    for xa, xb in zip(la, lb):
        assert np.array_equal(np.asarray(xa), np.asarray(xb))


def _assignments(wl, engine, policy):
    """Per-CREATE [pod, node] sequence from a decision-trace replay."""
    res = tracing.replay(wl, engine,
                         lambda _p, pod, nodes: policy(pod, nodes), None)
    rows = tracing.extract_trace(res)
    return res, rows, [[r["pod"], r["node"]] for r in rows
                       if r["kind"] == "CREATE"]


# ------------------------------------------------------------- generator

FULL_SPEC = ScenarioSpec(name="all", seed=5, arrival_jitter_frac=0.02,
                         demand_scale=1.1, gpu_milli_scale=0.9,
                         pod_mix_swap_frac=0.3, fault_nodes=2)


def test_perturb_deterministic_byte_identical():
    base = synthetic_workload(4, 24, seed=3)
    _assert_trees_identical(perturb_workload(base, FULL_SPEC),
                            perturb_workload(base, FULL_SPEC))


def test_perturb_seed_changes_output():
    base = synthetic_workload(4, 24, seed=3)
    a = perturb_workload(base, FULL_SPEC)
    b = perturb_workload(base, dataclasses.replace(FULL_SPEC, seed=6))
    assert not np.array_equal(np.asarray(a.pods.creation_time),
                              np.asarray(b.pods.creation_time))


def test_perturb_rejects_faulted_base():
    base = synthetic_workload(2, 8, seed=0)
    faulted = perturb_workload(base, ScenarioSpec(name="f", fault_nodes=1))
    assert faulted.faults is not None
    with pytest.raises(ValueError, match="already carries fault events"):
        perturb_workload(faulted, ScenarioSpec(name="g"))


def test_identity_spec_is_base_with_no_faults():
    base = synthetic_workload(3, 12, seed=1)
    out = perturb_workload(base, ScenarioSpec(name="base"))
    assert out.faults is None
    _assert_trees_identical(
        dataclasses.replace(out, faults=None),
        dataclasses.replace(base, faults=None))


def test_make_fault_events_sorts_pads_validates():
    fe = make_fault_events([(50, 1, KIND_NODE_UP), (10, 1, KIND_NODE_DOWN)],
                           pad_to=4)
    assert np.asarray(fe.time)[:2].tolist() == [10, 50]
    assert np.asarray(fe.mask).tolist() == [True, True, False, False]
    assert np.asarray(fe.time)[2:].tolist() == [np.iinfo(np.int32).max] * 2
    assert make_fault_events([]) is None
    with pytest.raises(ValueError, match="not NODE_DOWN/NODE_UP"):
        make_fault_events([(5, 0, 99)])


def test_fault_events_paired_and_in_span():
    base = synthetic_workload(4, 40, seed=3)
    ev = fault_events_for(base, ScenarioSpec(name="f", seed=9, fault_nodes=2))
    downs = [e for e in ev if e[2] == KIND_NODE_DOWN]
    ups = [e for e in ev if e[2] == KIND_NODE_UP]
    assert len(downs) == 2 and len(ups) == 2
    assert {d[1] for d in downs} == {u[1] for u in ups}
    for (td, nd, _), (tu, nu, _) in zip(sorted(downs, key=lambda e: e[1]),
                                        sorted(ups, key=lambda e: e[1])):
        assert tu > td


# ----------------------------------------------------------------- suite

def test_suite_registry_lists_default8():
    suites = list_suites()
    assert suites["default8"]["size"] == 8
    assert "base" in suites["default8"]["scenarios"]
    assert suites["smoke3"]["size"] == 3


def test_get_suite_unknown_name_raises():
    with pytest.raises(ValueError, match="unknown scenario suite"):
        get_suite("nope", synthetic_workload(2, 8, seed=0))


def test_suite_deterministic_and_uniformly_padded():
    base = synthetic_workload(4, 24, seed=3)
    s1 = get_suite("default8", base)
    s2 = get_suite("default8", base)
    assert s1.names == s2.names
    for wa, wb in zip(s1.workloads, s2.workloads):
        _assert_trees_identical(wa, wb)
    # every scenario carries a FaultEvents of the SAME padded length so the
    # suite stacks under vmap (parallel.traces.stack_traces requirement)
    shapes = {np.asarray(w.faults.time).shape for w in s1.workloads}
    assert shapes == {(s1.fault_pad,)}
    desc = s1.describe()
    assert desc["suite"] == "default8"
    assert len(desc["scenarios"]) == 8


# ------------------------------------------------------- cordon semantics

def _cordon_workload():
    """2 identical CPU nodes, 3 pods that all prefer node 0 under
    first_fit; node 0 is cordoned during pod 1's arrival only."""
    nodes = [{"node_id": f"n{i}", "cpu_milli": 4000, "memory_mib": 8000,
              "gpus": []} for i in range(2)]
    pods = [{"pod_id": f"p{i}", "cpu_milli": 500, "memory_mib": 500,
             "num_gpu": 0, "gpu_milli": 0, "creation_time": t,
             "duration_time": 500}
            for i, t in enumerate([0, 20, 60])]
    wl = make_workload(nodes, pods, pad_nodes_to=2, pad_gpus_to=1,
                       pad_pods_to=4)
    faults = make_fault_events([(15, 0, KIND_NODE_DOWN),
                                (50, 0, KIND_NODE_UP)])
    return wl, dataclasses.replace(wl, faults=faults)


@pytest.mark.parametrize("engine", ["exact", "flat"])
def test_cordon_reroutes_then_recovers(engine):
    clean, faulted = _cordon_workload()
    _, _, base_assign = _assignments(clean, engine, zoo.first_fit())
    assert base_assign == [[0, 0], [1, 0], [2, 0]]
    res, rows, assign = _assignments(faulted, engine, zoo.first_fit())
    # pod 1 (t=20) arrives inside the [15, 50) window: node 0 is cordoned,
    # first_fit falls through to node 1; pod 2 (t=60) lands on node 0 again
    assert assign == [[0, 0], [1, 1], [2, 0]]
    assert int(res.scheduled_pods) == 3
    # fault flips appear as trace rows with the new kinds
    kinds = [r["kind"] for r in rows]
    assert kinds.count("NODE_DOWN") == 1 and kinds.count("NODE_UP") == 1
    assert kinds.index("NODE_DOWN") < kinds.index("NODE_UP")


def test_cordon_does_not_evict_running_pods():
    clean, faulted = _cordon_workload()
    res, rows, assign = _assignments(faulted, "exact", zoo.first_fit())
    # pod 0 is RUNNING on node 0 when it goes down at t=15; it keeps its
    # placement (no eviction) and node 0's cpu stays committed through the
    # window — visible as free_cpu on the NODE_DOWN row
    down = next(r for r in rows if r["kind"] == "NODE_DOWN")
    assert assign[0] == [0, 0]
    assert down["free_cpu"] == 2 * 4000 - 500


def test_fused_engine_rejects_fault_workloads():
    from fks_tpu.sim import fused

    _, faulted = _cordon_workload()
    with pytest.raises(ValueError, match="not supported in the fused"):
        fused.make_fused_population_run(faulted)


# --------------------------------------------------------- golden fixture

@pytest.fixture(scope="module")
def golden_fault():
    with open(FIXTURES / "golden_scenario_fault.json") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def golden_fault_workload(golden_fault):
    base = synthetic_workload(**golden_fault["workload"])
    return perturb_workload(base, ScenarioSpec(**golden_fault["spec"]))


def test_golden_fault_timeline_regenerates(golden_fault,
                                           golden_fault_workload):
    fe = golden_fault_workload.faults
    m = np.asarray(fe.mask)
    got = [{"time": int(t), "node": int(nd), "kind": int(k)}
           for t, nd, k in zip(np.asarray(fe.time)[m],
                               np.asarray(fe.node)[m],
                               np.asarray(fe.kind)[m])]
    assert got == golden_fault["fault_timeline"]


@pytest.mark.parametrize("engine", ["exact", "flat"])
@pytest.mark.parametrize("policy", ["first_fit", "best_fit"])
def test_golden_fault_pin(golden_fault, golden_fault_workload, engine,
                          policy):
    pin = golden_fault["policies"][policy]
    res, rows, assign = _assignments(golden_fault_workload, engine,
                                     zoo.ZOO[policy]())
    assert abs(float(res.policy_score) - pin["policy_score"]) <= 1e-5
    assert int(res.scheduled_pods) == pin["scheduled_pods"]
    assert int(res.events_processed) == pin["events_processed"]
    assert assign == pin["assignments"]
    fault_rows = sum(1 for r in rows
                     if r["kind"] in ("NODE_DOWN", "NODE_UP"))
    assert fault_rows == pin["fault_rows"]


def test_golden_fault_assignments_are_fault_sensitive(golden_fault,
                                                      golden_fault_workload):
    # The pinned score alone cannot catch a broken cordon (aggregate
    # utilization doesn't see pod relocation between equal nodes); the
    # assignment vector must genuinely differ from a no-fault run of the
    # same perturbed demand.
    spec = ScenarioSpec(**golden_fault["spec"])
    nofault = perturb_workload(synthetic_workload(**golden_fault["workload"]),
                               dataclasses.replace(spec, fault_nodes=0))
    _, _, clean = _assignments(nofault, "exact", zoo.first_fit())
    pinned = golden_fault["policies"]["first_fit"]["assignments"]
    assert clean != pinned
    diffs = sum(1 for a, b in zip(clean, pinned) if a != b)
    assert diffs >= 5


# -------------------------------------------------- vmapped robust fitness

@pytest.fixture(scope="module")
def small_suite():
    return get_suite("smoke3", synthetic_workload(4, 24, seed=3))


def test_suite_eval_matches_sequential(small_suite):
    params = parametric.seed_weights("best_fit")
    per = np.asarray(make_suite_eval(small_suite)(params).policy_score)
    assert per.shape == (3,)
    pol = parametric.as_policy(params)
    mod = get_engine("exact")
    for i, wl in enumerate(small_suite.workloads):
        ref = float(mod.simulate(wl, pol, SimConfig()).policy_score)
        assert abs(float(per[i]) - ref) <= 1e-6


def test_suite_eval_exact_vs_flat_parity(small_suite):
    params = parametric.seed_weights("best_fit")
    exact = np.asarray(
        make_suite_eval(small_suite, engine="exact")(params).policy_score)
    flat = np.asarray(
        make_suite_eval(small_suite, engine="flat")(params).policy_score)
    assert np.max(np.abs(exact - flat)) <= 1e-5
    # suite index 2 ("fault1") is the fault-injected lane
    assert small_suite.names[2] == "fault1"
    assert small_suite.workloads[2].faults is not None


@pytest.mark.parametrize("policy", ["first_fit", "best_fit"])
def test_truncated_prefix_probe_parity(policy):
    """Budget probe contract (fks_tpu.funsearch.budget): a run stopped at
    ``probe_steps`` reports truncated=True and a fitness computed only
    from the consumed event prefix — identical between the exact and flat
    engines at 1e-5, nonzero (probe scoring lifts the zero-on-truncation
    gate), and distinct from the full-run fitness."""
    wl = synthetic_workload(4, 24, seed=3)
    pol = zoo.ZOO[policy]()
    probe_cfg = SimConfig(max_steps=16, probe_score=True)
    scores = {}
    for eng in ("exact", "flat"):
        res = get_engine(eng).simulate(wl, pol, probe_cfg)
        assert bool(res.truncated)
        assert int(res.events_processed) <= 16
        scores[eng] = float(res.policy_score)
        assert scores[eng] > 0.0
    assert abs(scores["exact"] - scores["flat"]) <= 1e-5
    # same truncated run WITHOUT probe scoring: the finalize gate zeroes it
    gated = get_engine("exact").simulate(wl, pol, SimConfig(max_steps=16))
    assert bool(gated.truncated)
    assert float(gated.policy_score) == 0.0
    # the probe fitness is prefix-only, not the full-run fitness
    full = get_engine("exact").simulate(wl, pol, SimConfig())
    assert not bool(full.truncated)
    assert abs(scores["exact"] - float(full.policy_score)) > 1e-6
    # probe scoring changes NOTHING on a run that finishes: same config
    # minus the step cap must reproduce the ungated full-run score
    done = get_engine("exact").simulate(wl, pol, SimConfig(probe_score=True))
    assert float(done.policy_score) == pytest.approx(
        float(full.policy_score), abs=1e-9)


def test_suite_population_eval_lane_isolation(small_suite):
    pop = parametric.init_population(jax.random.PRNGKey(0), 4, noise=0.3)
    per = np.asarray(
        make_suite_eval(small_suite, population=True)(pop).policy_score)
    assert per.shape == (4, 3)
    # each candidate lane must equal its own single-candidate eval
    single = make_suite_eval(small_suite)
    for c in range(4):
        params_c = jax.tree_util.tree_map(lambda x: x[c], pop)
        ref = np.asarray(single(params_c).policy_score)
        np.testing.assert_allclose(per[c], ref, atol=1e-6)


def test_sharded_suite_eval_matches_unsharded(small_suite):
    from fks_tpu.parallel.mesh import population_mesh

    mesh = population_mesh()
    pop = parametric.init_population(jax.random.PRNGKey(1), 8, noise=0.3)
    rc = RobustConfig(aggregation="cvar", cvar_alpha=0.5)
    ev = make_sharded_suite_eval(small_suite, mesh, rc=rc, elite_k=3)
    robust, per, elite_idx, elite_scores = ev(pop, 8)
    ref_per = np.asarray(
        make_suite_eval(small_suite, population=True)(pop).policy_score)
    ref_robust = np.asarray(aggregate(ref_per, rc))
    np.testing.assert_allclose(np.asarray(per), ref_per, atol=1e-6)
    np.testing.assert_allclose(np.asarray(robust), ref_robust, atol=1e-6)
    order = np.argsort(-ref_robust, kind="stable")[:3]
    np.testing.assert_allclose(np.asarray(elite_scores),
                               ref_robust[order], atol=1e-6)
    assert set(np.asarray(elite_idx).tolist()) == set(order.tolist())


# ------------------------------------------------------------ aggregation

def test_aggregate_modes():
    s = np.array([1.0, 4.0, 2.0, 3.0])
    assert float(aggregate(s, RobustConfig("mean"))) == pytest.approx(2.5)
    assert float(aggregate(s, RobustConfig("min"))) == pytest.approx(1.0)
    # cvar alpha=0.5 over 4 scenarios -> mean of the 2 worst
    assert float(aggregate(s, RobustConfig("cvar", cvar_alpha=0.5))
                 ) == pytest.approx(1.5)
    # tiny alpha degenerates to min (k clamps to 1)
    assert float(aggregate(s, RobustConfig("cvar", cvar_alpha=1e-6))
                 ) == pytest.approx(1.0)
    w = RobustConfig("mean", weights=(1.0, 0.0, 0.0, 1.0))
    assert float(aggregate(s, w)) == pytest.approx(2.0)
    # batched: aggregation folds the TRAILING axis
    b = np.stack([s, s + 1])
    np.testing.assert_allclose(np.asarray(aggregate(b, RobustConfig("min"))),
                               [1.0, 2.0])


def test_robust_config_validation():
    with pytest.raises(ValueError, match="unknown aggregation"):
        RobustConfig("median")
    with pytest.raises(ValueError, match="not in"):
        RobustConfig("cvar", cvar_alpha=0.0)
    with pytest.raises(ValueError, match="weights only apply"):
        RobustConfig("min", weights=(1.0, 2.0))
    with pytest.raises(ValueError, match="weights for"):
        aggregate(np.ones(3), RobustConfig("mean", weights=(1.0, 2.0)))


# --------------------------------------------------- evaluator / evolution

def _micro_workload():
    from tests.test_engine_micro import micro_workload
    return micro_workload()


def test_code_evaluator_suite_breakdown():
    from fks_tpu.funsearch import CodeEvaluator, seed_policies

    wl = _micro_workload()
    suite = get_suite("smoke3", wl)
    ev = CodeEvaluator(wl, suite=suite, robust=RobustConfig("min"))
    rec = ev.evaluate_one(next(iter(seed_policies().values())))
    assert rec.aggregation == "min"
    assert len(rec.scenario_scores) == 3
    assert rec.score == pytest.approx(min(rec.scenario_scores), abs=1e-6)
    assert rec.score > 0


def test_code_evaluator_suite_rejects_fused_engine():
    wl = _micro_workload()
    suite = get_suite("smoke3", wl)
    from fks_tpu.funsearch import CodeEvaluator

    with pytest.raises(ValueError, match="fused"):
        CodeEvaluator(wl, engine="fused", suite=suite)


def test_evolution_with_suite_persists_breakdown(tmp_path):
    from fks_tpu.funsearch import EvolutionConfig, FakeLLM
    from fks_tpu.funsearch import evolution as evo

    cfg = EvolutionConfig(population_size=6, generations=1, elite_size=2,
                          candidates_per_generation=3, max_workers=1,
                          seed=7, early_stop_threshold=1.1,
                          scenario_suite="smoke3",
                          robust_aggregation="cvar", robust_cvar_alpha=0.5)
    fs = evo.run(_micro_workload(), cfg, backend=FakeLLM(seed=7),
                 log=lambda _m: None)
    assert fs.evaluator.suite is not None
    assert fs.evaluator.robust.aggregation == "cvar"
    stats = fs.history[-1]
    assert stats.scenario_suite == "smoke3"
    assert stats.robust_aggregation == "cvar"
    assert len(stats.best_scenario_scores) == 3
    path = fs.save_best_policy(str(tmp_path / "discovered"))
    with open(path) as f:
        champ = json.load(f)
    assert champ["scenario_suite"] == "smoke3"
    assert champ["aggregation"] == "cvar"
    assert set(champ["scenario_scores"]) == {"base", "jitter", "fault1"}
    per = np.array([champ["scenario_scores"][n]
                    for n in fs.evaluator.suite.names])
    rc = RobustConfig("cvar", cvar_alpha=0.5)
    assert champ["score"] == pytest.approx(float(aggregate(per, rc)),
                                           abs=1e-5)


# ------------------------------------------------------------ cli / schema

def test_cli_scenarios_lists_suites(capsys):
    from fks_tpu import cli

    assert cli.main(["scenarios"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["default8"]["size"] == 8


def test_cli_scenarios_unknown_suite_errors(monkeypatch, capsys):
    from fks_tpu import cli

    monkeypatch.setattr(cli, "_parse_workload",
                        lambda args: ("micro", _micro_workload()))
    assert cli.main(["scenarios", "--suite", "nope"]) == 2


def test_cli_scenarios_describe_and_schema(monkeypatch, capsys, tmp_path):
    from fks_tpu import cli

    monkeypatch.setattr(cli, "_parse_workload",
                        lambda args: ("micro", _micro_workload()))
    run_dir = tmp_path / "run"
    rc = cli.main(["scenarios", "--suite", "smoke3", "--scenario", "2",
                   "--run-dir", str(run_dir)])
    assert rc == 0
    out = json.loads(capsys.readouterr().out)
    assert out["name"] == "fault1"
    assert any(e["kind"] == "NODE_DOWN" for e in out["fault_timeline"])
    # the flight-recorder output (scenario_suite metric record) must pass
    # the schema check
    chk = subprocess.run(
        [sys.executable, str(REPO / "tools" / "check_jsonl_schema.py"),
         "--run-dir", str(run_dir)], capture_output=True, text=True)
    assert chk.returncode == 0, chk.stdout + chk.stderr
