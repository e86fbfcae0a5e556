"""Every runner built on the flat engine forks from a workload's snapshot
(``fks_tpu.data.snapshot``) with no option: the three tiers of
``CodeEvaluator``, the parametric population and the sharded runners
against the plain reference's ``simulate_from``. Device-heavy, and under
ten items: the suite's scheduler queues the files with the most items
first, so this one runs in the tail, beside ``test_cluster_parity.py``,
after the latency-gated promotion tests of ``test_portfolio.py``,
``test_vm_serve.py`` and ``test_pipeline.py`` are through. The loaded
carry itself, leaf by leaf, is ``tests/test_snapshot_carry.py``."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import plain_sim_loaded, policies
from chipbench.reference.compare import Output, compare
from fks_tpu.data import snapshot as snap_mod
from fks_tpu.funsearch.backend import CodeEvaluator
from fks_tpu.models import parametric, zoo
from fks_tpu.sim import flat
from fks_tpu.sim.engine import SimConfig
from tests import pressure_traces as pt

GUARANTEES = {"fitness_rtol": 16 * 2.0 ** -23}

E0_SMALL = 150
TIERS = {"vm_batch": {"vm_batch": True}, "vm": {"vm_batch": False},
         "jit": {"use_vm": False}}


@pytest.fixture(scope="module")
def forked(tmp_path_factory):
    """seed 2's deployment forked at 150 arrivals placed by best_fit: the
    workload parsed WITH the snapshot file, the reference's inputs and
    rows (its own parse of the file the program wrote), the four sources
    and the reference's forked whole runs."""
    d = str(tmp_path_factory.mktemp("snap2"))
    wl = pt.write_traces(d, 2).parse_workload(pt.NODE_FILE, pt.POD_FILE)
    cfg = SimConfig(node_prefilter_k=64)
    snap = flat.make_snapshot(wl, zoo.best_fit(), E0_SMALL, cfg)
    path = os.path.join(d, "csv", "snapshot.csv.gz")
    snap_mod.write_snapshot_csv_gz(wl, snap, path)
    from fks_tpu.data import TraceParser
    wls = TraceParser(d).parse_workload(pt.NODE_FILE, pt.POD_FILE,
                                        snapshot_file="snapshot.csv")
    for a, b in zip(jax.tree_util.tree_leaves(wls.snapshot),
                    jax.tree_util.tree_leaves(snap)):
        assert np.array_equal(a, b)
    cluster, pods = pt.reference_inputs(d)
    rows = plain_sim_loaded.load_rows(
        path, os.path.join(d, "csv", pt.NODE_FILE),
        os.path.join(d, "csv", pt.POD_FILE))
    assert len(rows) == E0_SMALL
    codes = pt.policy_sources()
    refs = [plain_sim_loaded.simulate_from(
        cluster, pods, rows, policies.source_policy(c),
        retry="earliest_delete", prefilter_k=64) for c in codes]
    # the fork leaves the policies something to decide: every one retries,
    # places every pod in the end and ends with its own fitness, and the
    # residents sit where the snapshot put them in every run
    for r in refs:
        assert r.num_frag_events > 0 and not r.truncated
        assert r.policy_score > 0 and r.events_processed > 2 * E0_SMALL
        for i, (node, bits) in rows.items():
            assert (r.assigned_node[i], r.assigned_gpus[i]) == (node, bits)
    assert len({r.policy_score for r in refs}) == len(codes)
    return wls, cluster, pods, rows, codes, refs


def _assert_equal(tag, ref, res, pods, lane=None):
    numbers = compare(tag, ref, Output.of_lane(res, pods.p, lane),
                      GUARANTEES)
    assert all(n.ok for n in numbers), [n for n in numbers if not n.ok]
    assert any(n.name.endswith("fitness_rel_err") for n in numbers), tag


@pytest.mark.parametrize("tier", list(TIERS))
def test_every_code_tier_forks(forked, tier):
    """A workload parsed with a snapshot forks in every tier of
    ``CodeEvaluator`` with no option: whole forked runs, placements and
    GPU picks the reference's, fitness within 16 f32 ulps, counts of the
    WHOLE run."""
    from fks_tpu.obs import spans

    wls, cluster, pods, rows, codes, refs = forked
    spans.LOG.clear()
    ev = CodeEvaluator(wls, engine="flat", **TIERS[tier])
    assert ev.cfg.node_prefilter_k == 64 and ev.start_event == E0_SMALL
    recs = ev.evaluate(codes)
    for lane, (rec, ref) in enumerate(zip(recs, refs)):
        assert rec.error is None, rec.error
        _assert_equal(f"{tier}.lane{lane}", ref, rec.result, pods)
        assert int(rec.result.num_fragmentation_events) \
            == ref.num_frag_events
        assert int(rec.result.num_snapshots) == ref.num_snapshots
        assert int(rec.result.max_nodes) == ref.max_nodes
    stats = ev.last_eval_stats
    assert stats["start_event"] == E0_SMALL
    assert stats["frag_events"] == sum(r.num_frag_events for r in refs)
    log = spans.LOG.snapshot()
    (fork,) = [r for r in log if r.name == "tier/fork_state"]
    assert fork.fields["start_event"] == fork.fields["residents"] \
        == E0_SMALL
    assert fork.fields["nodes_loaded"] == len({n for n, _ in rows.values()})
    assert fork.fields["bytes"] > 0
    (root,) = [r for r in log if r.name == "tier/evaluate"]
    assert root.fields["start_event"] == E0_SMALL
    launches = [r for r in log if r.name == "tier/vm_batch/launch"]
    assert len(launches) == (1 if tier == "vm_batch" else 0)
    assert all(r.fields["start_event"] == E0_SMALL for r in launches)


def test_the_step_cap_stays_absolute(forked):
    """A window of k events after the fork is ``max_steps = E0 + k``."""
    wls, cluster, pods, rows, codes, _ = forked
    ev = CodeEvaluator(wls, cfg=SimConfig(max_steps=E0_SMALL + 40),
                       engine="flat", vm_batch=True)
    recs = ev.evaluate(codes)
    for lane, rec in enumerate(recs):
        assert int(rec.result.events_processed) == E0_SMALL + 40
        ref = plain_sim_loaded.simulate_from(
            cluster, pods, rows, policies.source_policy(codes[lane]),
            retry="earliest_delete", prefilter_k=64,
            max_steps=E0_SMALL + 40)
        numbers = compare(f"lane{lane}", ref,
                          Output.of_lane(rec.result, pods.p), GUARANTEES)
        assert all(n.ok for n in numbers)
        assert ref.truncated and bool(rec.result.truncated)


def _weights():
    return np.stack([np.asarray(parametric.seed_weights(n), np.float32)
                     for n in ("first_fit", "best_fit", "packing",
                               "worst_fit")])


def test_the_parametric_population_forks(forked):
    from fks_tpu.parallel import make_population_eval

    wls, cluster, pods, rows, _, _ = forked
    w = _weights()
    res = jax.device_get(make_population_eval(
        wls, cfg=SimConfig(node_prefilter_k=64), engine="flat")(
            jnp.asarray(w)))
    assert res.events_processed.min() > E0_SMALL
    for lane in range(len(w)):
        ref = plain_sim_loaded.simulate_from(
            cluster, pods, rows, policies.parametric_policy(w[lane]),
            retry="earliest_delete", prefilter_k=64)
        numbers = compare(f"param{lane}", ref,
                          Output.of_lane(res, pods.p, lane), GUARANTEES)
        assert all(n.ok for n in numbers), [n for n in numbers if not n.ok]


@pytest.mark.parametrize("seg_steps", [0, 64])
def test_sharded_code_eval_forks_as_one_device_does(forked, seg_steps):
    """Four CPU devices: the sharded runner (single dispatch and
    segmented) gives one device's results, leaf for leaf."""
    from fks_tpu.funsearch import vm
    from fks_tpu.parallel import population_mesh
    from fks_tpu.parallel.mesh import make_sharded_code_eval

    wls, _, _, _, codes, _ = forked
    cfg = SimConfig(node_prefilter_k=64, max_steps=E0_SMALL + 96)
    c = wls.cluster
    stacked = vm.stack_programs(
        [vm.compile_policy(code, c.n_padded, c.g_padded) for code in codes])
    one = jax.device_get(jax.jit(flat.make_population_run_fn(
        wls, vm.score, cfg))(stacked, flat.initial_state(wls, cfg)))
    mesh = population_mesh(jax.devices()[:4])
    many, _, _ = make_sharded_code_eval(
        wls, mesh, cfg=cfg, elite_k=1, engine="flat",
        seg_steps=seg_steps)(stacked, len(codes))
    many = jax.device_get(many)
    assert (one.events_processed == E0_SMALL + 96).all()
    for a, b in zip(jax.tree_util.tree_leaves(one),
                    jax.tree_util.tree_leaves(many)):
        assert np.array_equal(a, b)


def test_sharded_parametric_eval_forks_as_one_device_does(forked):
    from fks_tpu.parallel import make_population_eval, population_mesh
    from fks_tpu.parallel.mesh import make_sharded_eval

    wls = forked[0]
    cfg = SimConfig(node_prefilter_k=64, max_steps=E0_SMALL + 96)
    w = jnp.asarray(_weights())
    one = make_population_eval(wls, cfg=cfg, engine="flat")(w)
    mesh = population_mesh(jax.devices()[:4])
    scores, _, _ = make_sharded_eval(wls, mesh, cfg=cfg, elite_k=1,
                                     engine="flat")(w, len(w))
    assert np.array_equal(np.asarray(one.policy_score), np.asarray(scores))


# ------------------------- forks from a moment of a run (PR 42): the
# mid-run cell's tiny deployment, whose prefixes hold departures, refused
# placements and a waiting pod

#: (departures only) and (9 refusals, a pod waiting with its retry queued)
MIDRUN_E0 = (128, 320)


@pytest.fixture(scope="module")
def midrun(tmp_path_factory):
    """The tiny mid-run deployment's workload, the reference's inputs and
    the sources: upstream's first_fit, whose run the snapshots are cut
    from, and a ledger champion."""
    from chipbench.selftest import midrun as tiny
    from fks_tpu.data import TraceParser
    from fks_tpu.funsearch import template

    d = str(tmp_path_factory.mktemp("midrun"))
    tiny.tiny_deployment(d)
    wl = TraceParser(d).parse_workload("nodes.csv", "pods.csv")
    cluster, pods = pt.reference_inputs(d)
    codes = [template.seed_policies()["first_fit"], pt.policy_sources()[2]]
    return d, wl, cluster, pods, codes


def _forked_at(d, wl, e0, policy):
    """(forked workload, the reference's log of the same file)."""
    import dataclasses

    from chipbench.reference import plain_sim_midrun

    snap = flat.make_snapshot(wl, policy, e0)
    path = os.path.join(d, "csv", f"snapshot_e{e0}.csv.gz")
    snap_mod.write_snapshot_csv_gz(wl, snap, path)
    log = plain_sim_midrun.load_log(path, os.path.join(d, "csv", "nodes.csv"),
                                    os.path.join(d, "csv", "pods.csv"))
    return dataclasses.replace(wl, snapshot=snap), log


@pytest.mark.parametrize("tier", list(TIERS))
def test_every_code_tier_forks_mid_run(midrun, tier):
    """A whole run forked at an event of first_fit's run IS first_fit's
    unforked run, every leaf of the result bit for bit, in every tier and
    from either prefix; another source's forked run is the plain
    reference's (``plain_sim_midrun``), and the counters say what the
    prefix held."""
    from chipbench.reference import plain_sim_midrun
    from fks_tpu.obs import spans

    d, wl, cluster, pods, codes = midrun
    whole = CodeEvaluator(wl, engine="flat", **TIERS[tier]).evaluate(codes)
    assert float(whole[0].result.policy_score) > 0
    for e0 in MIDRUN_E0:
        forked, log = _forked_at(d, wl, e0, zoo.first_fit())
        plain_sim_midrun.validate(cluster, pods, log, "earliest_delete")
        spans.LOG.clear()
        ev = CodeEvaluator(forked, engine="flat", **TIERS[tier])
        assert ev.start_event == e0
        recs = ev.evaluate(codes)
        assert recs[0].error is None and recs[1].error is None
        for a, b in zip(jax.tree_util.tree_leaves(whole[0].result),
                        jax.tree_util.tree_leaves(recs[0].result)):
            assert np.array_equal(np.asarray(a), np.asarray(b)), (tier, e0)
        ref = plain_sim_midrun.simulate_from(
            cluster, pods, log, policies.source_policy(codes[1]))
        _assert_equal(f"{tier}.e{e0}", ref, recs[1].result, pods)
        assert int(recs[1].result.num_fragmentation_events) \
            == ref.num_frag_events
        assert int(recs[1].result.num_snapshots) == ref.num_snapshots
        (fork,) = [r for r in spans.LOG.snapshot()
                   if r.name == "tier/fork_state"]
        refused = sum(1 for _, node, _ in log.attempts if node < 0)
        want = {128: (44, 0, 0, ""), 320: (139, 1, 9, "earliest_delete")}
        assert (fork.fields["departed"], fork.fields["waiting"],
                fork.fields["prefix_failed"], fork.fields["rule"]) \
            == want[e0] and refused == want[e0][2]
        assert fork.fields["residents"] == len(
            {i for i, node, _ in log.attempts if node >= 0}) - want[e0][0]
        # the counter sim.retry_share divides: failed placements the
        # policies made, not the prefix's
        assert ev.last_eval_stats["frag_events"] == sum(
            int(r.result.num_fragmentation_events) - refused for r in recs)


def test_the_parametric_population_forks_mid_run(midrun):
    """The same on the parametric tier: lane 0's weights made the
    snapshot, so its forked run is its unforked one bit for bit."""
    import dataclasses

    from fks_tpu.parallel import make_population_eval

    _, wl, _, _, _ = midrun
    w = _weights()

    def placing(pod, nodes):
        return parametric.score(jnp.asarray(w[0]), pod, nodes)

    whole = jax.device_get(make_population_eval(wl, engine="flat")(
        jnp.asarray(w)))
    for e0 in MIDRUN_E0:
        forked = dataclasses.replace(
            wl, snapshot=flat.make_snapshot(wl, placing, e0))
        res = jax.device_get(make_population_eval(forked, engine="flat")(
            jnp.asarray(w)))
        assert res.events_processed.min() > e0
        for a, b in zip(jax.tree_util.tree_leaves(whole),
                        jax.tree_util.tree_leaves(res)):
            assert np.array_equal(np.asarray(a)[0], np.asarray(b)[0]), e0
