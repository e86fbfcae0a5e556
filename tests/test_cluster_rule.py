"""The large-cluster rule, chosen by shape (sim.engine.shape_prefilter_k):
one answer for every evaluation tier and for VM serving, no timing probe,
and below 256 nodes the programs compiled before the rule existed."""
import jax
import pytest

from fks_tpu import obs
from fks_tpu.funsearch import backend, template, vm
from fks_tpu.serve.artifact import ChampionSpec, ServeEngine
from fks_tpu.serve import ShapeEnvelope
from fks_tpu.serve.vm_engine import VMServeEngine
from fks_tpu.sim import engine as sim_engine
from fks_tpu.sim import flat
from fks_tpu.sim.engine import (
    PREFILTER_AUTO_K, PREFILTER_MIN_NODES, SimConfig, shape_prefilter_k,
)
from tests import pressure_traces as pt

CODE = template.seed_policies()["best_fit"]


@pytest.fixture(scope="module")
def big(tmp_path_factory):
    parser = pt.write_traces(str(tmp_path_factory.mktemp("rule")), seed=2)
    return parser.parse_workload(pt.NODE_FILE, pt.POD_FILE)


@pytest.fixture()
def no_probe(monkeypatch):
    def boom(*a, **kw):
        raise AssertionError("the timing probe ran")

    monkeypatch.setattr(sim_engine, "probe_policy_cost", boom)


@pytest.mark.parametrize("n_padded,override,want", [
    (255, None, 0),
    (256, None, 64),
    (1528, None, 64),
    (16, None, 0),
    (1528, 0, 0),            # serving's explicit 0 is the dense sweep
    (1528, 32, 32),
    (1528, 1528, 1528),      # what SimConfig turns into the dense sweep
    (16, 4, 4),
])
def test_rule_table(n_padded, override, want):
    assert (PREFILTER_MIN_NODES, PREFILTER_AUTO_K) == (256, 64)
    assert shape_prefilter_k(n_padded, override) == want


@pytest.mark.parametrize("kw", [{"vm_batch": True}, {"vm_batch": False},
                                {"use_vm": False}, {"engine": "flat"}],
                         ids=["vm_batch", "vm", "jit", "flat"])
def test_evaluator_applies_the_rule_to_every_tier(big, micro_workload,
                                                  no_probe, kw):
    assert big.cluster.n_padded >= PREFILTER_MIN_NODES
    ev = backend.CodeEvaluator(big, **kw)
    assert ev.cfg.node_prefilter_k == 64
    assert ev.cfg.resolve_prefilter_k(big.cluster.n_padded) == 64
    assert ev.prefilter_derived
    # a non-zero value wins, and the node count asks for the dense sweep
    # (0 is the field's default: it reads as "not set")
    small_k = backend.CodeEvaluator(big, SimConfig(node_prefilter_k=8), **kw)
    assert small_k.cfg.node_prefilter_k == 8
    dense = backend.CodeEvaluator(
        big, SimConfig(node_prefilter_k=big.cluster.n_padded), **kw)
    assert dense.cfg.resolve_prefilter_k(big.cluster.n_padded) == 0
    assert not small_k.prefilter_derived and not dense.prefilter_derived
    zero = backend.CodeEvaluator(big, SimConfig(node_prefilter_k=0), **kw)
    assert zero.cfg.node_prefilter_k == 64 and zero.prefilter_derived
    # the other fields of the caller's configuration are kept
    kept = backend.CodeEvaluator(big, SimConfig(max_steps=48), **kw).cfg
    assert (kept.max_steps, kept.node_prefilter_k) == (48, 64)
    # under 256 nodes nothing changes
    under = backend.CodeEvaluator(micro_workload, **kw)
    assert under.cfg == SimConfig() and not under.prefilter_derived


def test_exact_rerank_and_watchdog_inherit_the_rule(big, no_probe):
    """Both build their evaluator from ``evaluator.cfg``: the resolved
    configuration, so the re-rank scores under the search's semantics."""
    ev = backend.CodeEvaluator(big, engine="flat")
    rerank = backend.CodeEvaluator(ev.workload, ev.cfg, engine="exact")
    assert rerank.cfg.node_prefilter_k == ev.cfg.node_prefilter_k == 64


def test_serving_a_vm_champion_resolves_the_same_rule(big, micro_workload,
                                                      no_probe):
    env = ShapeEnvelope(max_pods=8, min_pod_bucket=8, max_batch=2,
                        max_gpu_milli=1000)
    champ = ChampionSpec(code=CODE, score=0.4, source="<test>")
    ev = backend.CodeEvaluator(big, engine="flat")
    eng = VMServeEngine(champ, big, envelope=env, engine="flat")
    assert eng.prefilter_k == ev.cfg.node_prefilter_k == 64
    assert eng.bucket_config(8).node_prefilter_k == 64
    assert VMServeEngine(champ, big, envelope=env, engine="flat",
                         prefilter_k=0).prefilter_k == 0
    small = VMServeEngine(champ, micro_workload, envelope=env, engine="flat")
    assert small.prefilter_k == \
        backend.CodeEvaluator(micro_workload).cfg.node_prefilter_k == 0


def test_aot_serving_keeps_its_probe(big, monkeypatch):
    """Retiring the probe for baked-in champions is ROADMAP D4's."""
    calls = []
    monkeypatch.setattr(sim_engine, "probe_policy_cost",
                        lambda *a, **kw: calls.append(a) or 1.0)
    env = ShapeEnvelope(max_pods=8, min_pod_bucket=8, max_batch=2,
                        max_gpu_milli=1000)
    eng = ServeEngine(ChampionSpec(code=CODE, score=0.4, source="<test>"),
                      big, envelope=env, engine="flat")
    assert len(calls) == 1 and eng.prefilter_k == 64


def test_no_probe_and_no_extra_compile_on_the_evaluation_path(big,
                                                              no_probe):
    """Building the evaluator under the rule compiles what building it
    with the explicit value compiles (the probe was one program more),
    and an evaluation reports the rule it ran under."""
    with obs.CompileWatcher(obs.NULL) as explicit:
        backend.CodeEvaluator(big, SimConfig(max_steps=8,
                                             node_prefilter_k=64),
                              engine="flat", vm_batch=True)
    with obs.CompileWatcher(obs.NULL) as ruled:
        ev = backend.CodeEvaluator(big, SimConfig(max_steps=8),
                                   engine="flat", vm_batch=True)
    assert ruled.backend_compile_count == explicit.backend_compile_count
    seeds = template.seed_policies()
    recs = ev.evaluate([seeds["first_fit"], seeds["best_fit"]])
    assert [int(r.result.events_processed) for r in recs] == [8, 8]
    stats = ev.last_eval_stats
    assert (stats["prefilter_k"], stats["vm_batch_lanes"]) == (64, 2)
    assert stats["prefilter_derived"] is True


def test_launch_span_says_what_the_interpreter_carries(big, micro_workload):
    from fks_tpu.obs import spans

    seeds = template.seed_policies()
    codes = [seeds["first_fit"], seeds["best_fit"]]
    for wl, view in ((big, 64), (micro_workload, 2)):
        ev = backend.CodeEvaluator(wl, SimConfig(max_steps=4), engine="flat",
                                   vm_batch=True)
        ev.evaluate(codes)
        launch = [r for r in spans.LOG.snapshot()
                  if r.name == "tier/vm_batch/launch"][-1].fields
        c = wl.cluster
        assert (launch["nodes"], launch["view"]) == (c.n_padded, view)
        itemsize = 8 if jax.config.jax_enable_x64 else 4
        assert launch["register_bytes"] == (
            launch["lanes"] * vm.register_rows(launch["capacity"]) * view
            * c.g_padded * itemsize)
    assert vm.register_rows(512) == 561


def test_under_256_nodes_the_program_is_the_explicit_zeros(micro_workload):
    """The lowered text of the evaluator's own VM program equals the one
    built from ``node_prefilter_k=0`` by hand."""
    import dataclasses

    c = micro_workload.cluster
    prog = vm.compile_policy(CODE, c.n_padded, c.g_padded,
                             capacity=backend.CodeEvaluator.VM_CAPACITY)
    ev = backend.CodeEvaluator(micro_workload, engine="flat")
    mine = ev._vm_runner().lower(prog, ev.state0).as_text()
    cfg = dataclasses.replace(SimConfig(node_prefilter_k=0),
                              cond_policy=True)
    by_hand = jax.jit(flat.make_param_run_fn(
        micro_workload, vm.score, cfg)).lower(
        prog, flat.initial_state(micro_workload, cfg)).as_text()
    assert mine == by_hand


def test_cli_evolve_reaches_the_rule_without_a_flag(no_probe, monkeypatch,
                                                    capsys):
    """``cli evolve`` on the real cluster and the inflated list: every
    evaluator the run builds (the search's and the exact re-rank's) holds
    the rule, with no timing probe. The device work is stubbed out: a
    generation on 1,523 nodes is the chip's to run."""
    from fks_tpu import cli

    seen = []

    def stub_batch(self, codes):
        seen.append((self.engine, self.cfg.node_prefilter_k,
                     self.workload.cluster.n_padded))
        return [backend.EvalRecord(code, 0.0, "stubbed") for code in codes]

    def stub_one(self, code, **kw):
        return stub_batch(self, [code])[0]

    monkeypatch.setattr(backend.CodeEvaluator, "_evaluate", stub_batch)
    monkeypatch.setattr(backend.CodeEvaluator, "evaluate_one", stub_one)
    rc = cli.main(["evolve", "--fake-llm", "--generations", "1",
                   "--engine", "flat", "--cpu",
                   "--nodes", "openb_node_list_all_node.csv",
                   "--trace", "openb_pod_list_inflated080.csv"])
    out = capsys.readouterr().out
    assert rc == 0
    assert seen and {s[1:] for s in seen} == {(64, 1528)}
    # the run says that the rule engaged, and how to ask for dense
    note = [ln for ln in out.splitlines() if ln.startswith("note: 1528 ")]
    assert len(note) == 1 and "first 64 feasible nodes" in note[0] \
        and "node_prefilter_k=1528" in note[0]
