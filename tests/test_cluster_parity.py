"""Whole inflated runs on a cluster over 256 nodes (fill, pressure, drain,
retries, no step cap): every evaluation tier of ``CodeEvaluator`` under
the rule it chose against the plain reference's free run with
``prefilter_k=64``. Placements, GPU picks, counts and flags identical,
fitness within 16 f32 ulps."""
import numpy as np
import pytest

from chipbench.reference import plain_sim, policies
from chipbench.reference.compare import Output, compare
from fks_tpu.funsearch.backend import CodeEvaluator
from tests import pressure_traces as pt

GUARANTEES = {"fitness_rtol": 16 * 2.0 ** -23}
RETRY = {"flat": "earliest_delete", "exact": "heap_array"}
TIERS = {"vm_batch": {"vm_batch": True}, "vm": {"vm_batch": False},
         "jit": {"use_vm": False}}


@pytest.fixture(scope="module")
def deployments(tmp_path_factory):
    """seed -> (workload, reference cluster, reference pods, sources)."""
    out = {}
    for seed in pt.SEEDS:
        d = str(tmp_path_factory.mktemp(f"parity{seed}"))
        wl = pt.write_traces(d, seed).parse_workload(pt.NODE_FILE,
                                                     pt.POD_FILE)
        out[seed] = (wl, *pt.reference_inputs(d), pt.policy_sources())
    return out


@pytest.fixture(scope="module")
def references(deployments):
    """(seed, retry rule) -> the reference's four whole runs."""
    return {(seed, retry): [
        plain_sim.simulate(cluster, pods, policies.source_policy(code),
                           retry=retry, prefilter_k=64)
        for code in codes]
        for seed, (_, cluster, pods, codes) in deployments.items()
        for retry in RETRY.values()}


def test_the_deployments_press_every_policy(deployments, references):
    """What makes the comparison below worth its time: every policy
    retries, places every pod in the end and ends with its own fitness,
    the two retry rules part ways, and the rule binds (the dense sweep
    gives best_fit other placements)."""
    for seed, (wl, cluster, pods, codes) in deployments.items():
        assert wl.cluster.n_padded >= 256 and cluster.n == pt.NODES
        flat_runs = references[seed, "earliest_delete"]
        for r in flat_runs:
            assert r.num_frag_events > 0 and not r.truncated
            assert r.scheduled_pods == pods.p and r.policy_score > 0
        assert len({r.policy_score for r in flat_runs}) == len(codes)
        heap_runs = references[seed, "heap_array"]
        assert any(a.events_processed != b.events_processed
                   for a, b in zip(flat_runs, heap_runs))
        dense = plain_sim.simulate(cluster, pods,
                                   policies.source_policy(codes[1]),
                                   retry="earliest_delete")
        assert (dense.assigned_node != flat_runs[1].assigned_node).any()


@pytest.mark.parametrize("seed", pt.SEEDS)
@pytest.mark.parametrize("tier", list(TIERS))
@pytest.mark.parametrize("engine", list(RETRY))
def test_whole_runs_equal_the_reference(deployments, references, engine,
                                        tier, seed):
    wl, _, pods, codes = deployments[seed]
    ev = CodeEvaluator(wl, engine=engine, fp_dedup=False, **TIERS[tier])
    recs = ev.evaluate(codes)
    stats = ev.last_eval_stats
    assert stats["prefilter_k"] == 64
    served = stats["vm_batch_lanes"] if tier == "vm_batch" \
        else stats["fallback_lanes"]
    assert served == len(codes)
    assert (ev.vm_count > 0) == (tier != "jit")
    bad = []
    for lane, (rec, ref) in enumerate(zip(recs, references[seed,
                                                           RETRY[engine]])):
        assert rec.error is None and rec.score > 0
        numbers = compare(f"lane{lane}", ref,
                          Output.of_lane(rec.result, pods.p), GUARANTEES)
        assert len(numbers) == 6        # the fitness is compared too
        bad += [(n.name, n.value) for n in numbers if not n.ok]
        assert np.isclose(rec.score, ref.policy_score, rtol=1e-5)
    assert not bad
