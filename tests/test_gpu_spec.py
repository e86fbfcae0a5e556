"""GPU-type constraints (PR 45): a pod's ``gpu_spec`` narrows the nodes
it may take, where the workload was parsed to honour it.

*A pod with a non-empty ``gpu_spec`` may be placed only on a node whose
``model`` is in the set; for that pod every other node is as a cordoned
node is: no candidate of the large-cluster rule, score 0 whatever the
policy returns, and nothing else knows of it.* The parser with and
without the choice, the one rule and the candidate cut under it, both
engines and every tier of ``CodeEvaluator`` against the plain reference
(``chipbench/reference/plain_sim_gpuspec.py``), a typed workload without
constraints against the untyped one bit for bit, the fork under
constraints, who refuses, and the committed list and snapshot. The cell
is ``tests/test_chipbench_gpuspec_cell.py``."""
import dataclasses
import gzip
import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import cells
from chipbench.reference import data, plain_sim, policies
from chipbench.reference import plain_sim_gpuspec as gs
from chipbench.reference.compare import Output, compare
from fks_tpu.data import TraceParser
from fks_tpu.data import snapshot as snap_mod
from fks_tpu.data.build import make_workload
from fks_tpu.data.entities import (
    GPU_SPEC_NO_NODE, gpu_spec_allows, gpu_spec_bits)
from fks_tpu.funsearch.backend import CodeEvaluator
from fks_tpu.models import parametric, zoo
from fks_tpu.sim import engine as exact
from fks_tpu.sim import flat
from fks_tpu.sim.engine import SimConfig
from fks_tpu.sim.types import NodeView, PodView
from tests import pressure_traces as pt

GUARANTEES = {"fitness_rtol": 16 * 2.0 ** -23}
CONFIG = json.load(open(os.path.join(
    cells.HERE, "configs", "openb1523-gpuspec25-loaded.json")))
SEED = 5


# ------------------------------------------------------------ the parser

NODES_CSV = """sn,cpu_milli,memory_mib,gpu,model
n0,32000,65536,0,
n1,32000,65536,2,T4
n2,32000,65536,2,G2
n3,32000,65536,4,V100M16
n4,32000,65536,2,T4
"""
PODS_CSV = """name,cpu_milli,memory_mib,num_gpu,gpu_milli,gpu_spec,qos,pod_phase,creation_time,deletion_time,scheduled_time
p0,1000,1024,1,500,,LS,Running,0,50,0
p1,1000,1024,1,500,T4,LS,Running,1,50,1
p2,1000,1024,1,500,V100M16|V100M32,LS,Running,2,50,2
p3,1000,1024,1,500,G2|G2|T4,LS,Running,3,50,3
p4,1000,1024,1,500,A100,LS,Running,4,50,4
p5,1000,1024,0,0,G2|A100,LS,Running,5,50,5
"""


@pytest.fixture()
def tiny_dir(tmp_path):
    (tmp_path / "csv").mkdir()
    (tmp_path / "csv" / "nodes.csv").write_text(NODES_CSV)
    (tmp_path / "csv" / "pods.csv").write_text(PODS_CSV)
    (tmp_path / "gpu_mem_mapping.json").write_text(
        json.dumps({"T4": 15109, "G2": 15109, "V100M16": 16384}))
    return tmp_path


def test_parser_with_the_choice_keeps_models_and_sets(tiny_dir):
    wl = TraceParser(tiny_dir).parse_workload("nodes.csv", "pods.csv",
                                              gpu_spec="honor")
    c, p = wl.cluster, wl.pods
    assert wl.typed and c.gpu_models == ("G2", "T4", "V100M16")
    assert np.asarray(c.gpu_model).dtype == np.int32
    # sorted vocabulary, -1 for a node without a model and for padding
    assert np.asarray(c.gpu_model).tolist() == [-1, 1, 0, 2, 1, -1, -1, -1]
    spec = np.asarray(p.gpu_spec)
    assert spec.dtype == np.int32 and spec.shape == np.asarray(p.cpu).shape
    # bit m = model m; a repeated name means nothing; a name no node has
    # is the sign bit: alone it allows nothing, beside others it adds none
    no = int(GPU_SPEC_NO_NODE)
    assert spec[:6].tolist() == [0, 0b010, no | 0b100, 0b011, no,
                                 no | 0b001]
    assert not spec[6:].any()
    allowed = gpu_spec_allows(spec[:6, None],
                              np.asarray(c.gpu_model)[None, :5])
    assert allowed.tolist() == [
        [True] * 5,                              # empty: any node
        [False, True, False, False, True],       # T4
        [False, False, False, True, False],      # V100M16|V100M32
        [False, True, True, False, True],        # G2|G2|T4
        [False] * 5,                             # A100: no node's model
        [False, False, True, False, False]]      # G2|A100
    # the reference's own read of the two columns says the same
    ref = gs.load_allowed(str(tiny_dir / "csv" / "nodes.csv"),
                          str(tiny_dir / "csv" / "pods.csv"))
    assert ref.tolist() == allowed.tolist()


def test_parser_without_the_choice_ignores_the_column(tiny_dir):
    parser = TraceParser(tiny_dir)
    default = parser.parse_workload("nodes.csv", "pods.csv")
    ignore = parser.parse_workload("nodes.csv", "pods.csv",
                                   gpu_spec="ignore")
    honor = parser.parse_workload("nodes.csv", "pods.csv", gpu_spec="honor")
    for wl in (default, ignore):
        assert not wl.typed
        assert wl.cluster.gpu_model is None and wl.pods.gpu_spec is None
        assert wl.cluster.gpu_models == ()
    a, b = (jax.tree_util.tree_structure(w) for w in (default, ignore))
    assert a == b
    # two leaves more, every other leaf the same
    la, lh = (jax.tree_util.tree_leaves(w) for w in (default, honor))
    assert len(lh) == len(la) + 2
    for field in ("cpu_total", "gpu_milli_total", "node_mask"):
        assert np.array_equal(getattr(default.cluster, field),
                              getattr(honor.cluster, field))
    for field in ("cpu", "num_gpu", "tie_rank", "duration"):
        assert np.array_equal(getattr(default.pods, field),
                              getattr(honor.pods, field))
    with pytest.raises(ValueError, match="gpu_spec: 'prefer' is none of"):
        parser.parse_workload("nodes.csv", "pods.csv", gpu_spec="prefer")


def test_more_than_31_models_is_refused_by_name(tiny_dir):
    rows = ["sn,cpu_milli,memory_mib,gpu,model"] + [
        f"n{i},1000,1024,1,M{i:02d}" for i in range(32)]
    (tiny_dir / "csv" / "many.csv").write_text("\n".join(rows) + "\n")
    parser = TraceParser(tiny_dir)
    assert parser.parse_cluster("many.csv").gpu_model is None   # default
    with pytest.raises(ValueError, match="32 GPU models.*at most 31"):
        parser.parse_cluster("many.csv", gpu_models=True)
    (tiny_dir / "csv" / "most.csv").write_text("\n".join(rows[:-1]) + "\n")
    c = parser.parse_cluster("most.csv", gpu_models=True)
    assert len(c.gpu_models) == 31
    assert gpu_spec_bits("M30", c.gpu_models) == 1 << 30


def test_make_workload_takes_the_same_choice():
    nodes = [{"node_id": "a", "cpu_milli": 1, "memory_mib": 1,
              "gpu_count": 1, "model": "T4"},
             {"node_id": "b", "cpu_milli": 1, "memory_mib": 1,
              "gpu_count": 0}]
    pods = [{"pod_id": "p", "cpu_milli": 1, "memory_mib": 1, "num_gpu": 1,
             "gpu_milli": 1, "creation_time": 0, "duration_time": 1,
             "gpu_spec": "T4|X"},
            {"pod_id": "q", "cpu_milli": 1, "memory_mib": 1, "num_gpu": 0,
             "gpu_milli": 0, "creation_time": 0, "duration_time": 1}]
    assert not make_workload(nodes, pods).typed
    wl = make_workload(nodes, pods, gpu_spec="honor", pad_pods_to=4)
    assert wl.typed and wl.cluster.gpu_models == ("T4",)
    assert np.asarray(wl.cluster.gpu_model).tolist() == [0, -1]
    assert np.asarray(wl.pods.gpu_spec).tolist() == [
        int(GPU_SPEC_NO_NODE) | 1, 0, 0, 0]


# --------------------------------------- the rule and the candidate cut

def _views(n=12, seed=0):
    rng = np.random.default_rng(seed)
    g = 2
    nodes = NodeView(
        cpu_milli_left=jnp.asarray(rng.integers(0, 8, n) * 1000, jnp.int32),
        cpu_milli_total=jnp.full(n, 8000, jnp.int32),
        memory_mib_left=jnp.full(n, 4096, jnp.int32),
        memory_mib_total=jnp.full(n, 4096, jnp.int32),
        gpu_left=jnp.full(n, g, jnp.int32),
        num_gpus=jnp.full(n, g, jnp.int32),
        gpu_milli_left=jnp.full((n, g), 1000, jnp.int32),
        gpu_milli_total=jnp.full((n, g), 1000, jnp.int32),
        gpu_mem_total=jnp.zeros((n, g), jnp.int32),
        gpu_mask=jnp.ones((n, g), bool), node_mask=jnp.ones(n, bool))
    pod = PodView(jnp.int32(3000), jnp.int32(1024), jnp.int32(1),
                  jnp.int32(500), jnp.int32(0), jnp.int32(10))
    return pod, nodes


@pytest.mark.parametrize("spec,k", [(0b001, 3), (0b110, 4), (0b010, 8),
                                    (0, 4), (int(GPU_SPEC_NO_NODE), 4)])
def test_candidates_are_the_first_k_allowed_and_fitting_nodes(spec, k):
    """``place_mask_of`` feeds ``_prefilter_candidates``: the first k
    nodes, in node order, that fit AND are allowed; fewer repeat the
    first; none allowed degrades to node 0 as none feasible does, and
    the re-mask through the gather refuses it."""
    pod, nodes = _views()
    model = np.asarray([0, 1, 2, -1] * 3, np.int32)
    wl = make_workload(
        [{"node_id": f"n{i}", "cpu_milli": 8000, "memory_mib": 4096,
          "gpu_count": 2, "model": ("A", "B", "C", "")[i % 4]}
         for i in range(12)], [], gpu_spec="honor")
    c = jax.tree_util.tree_map(jnp.asarray, wl.cluster)
    assert np.array_equal(np.asarray(c.gpu_model), model)
    mask = exact.place_mask_of(c, None, jnp.int32(spec))
    want_allowed = gpu_spec_allows(np.int32(spec), model)
    assert np.array_equal(np.asarray(mask), want_allowed)
    fits = np.asarray(nodes.cpu_milli_left) >= 3000
    want = np.flatnonzero(fits & want_allowed)[:k]
    cand = np.asarray(exact._prefilter_candidates(pod, nodes, mask, k))
    if len(want):
        assert cand[:len(want)].tolist() == want.tolist()
        assert (cand[len(want):] == want[0]).all()
        assert np.asarray(mask)[cand].all()
    else:
        assert (cand == 0).all() and not np.asarray(mask)[cand].any()
    # a cordon and a type constraint are one mask
    avail = jnp.asarray(np.arange(12) % 2 == 0)
    both = exact.place_mask_of(c, avail, jnp.int32(spec))
    assert np.array_equal(np.asarray(both),
                          want_allowed & np.asarray(avail))
    # and the re-mask through the candidate gather, which evaluates the
    # type term AT the candidates, is the mask gathered
    for m, a in ((mask, None), (both, avail)):
        assert np.array_equal(
            np.asarray(exact.place_mask_at(c, m, a, jnp.int32(spec), cand)),
            np.asarray(m)[cand])
    assert np.array_equal(
        np.asarray(exact.place_mask_at(c, c.node_mask, None, None, cand)),
        np.ones(k, bool))
    # and without the leaves the mask is what it always was
    assert exact.place_mask_of(c, None, None) is c.node_mask


# ----------------------------------- a small deployment under pressure

@pytest.fixture(scope="module")
def deployment(tmp_path_factory):
    """320 nodes of six GPU models under an inflated draw of gpuspec25
    (``pressure_traces.write_typed_traces``): the program's two parses,
    the reference's inputs, and the reference's whole runs of the four
    test sources under rule 64."""
    d = str(tmp_path_factory.mktemp("typed"))
    parser = pt.write_typed_traces(d, SEED)
    typed = parser.parse_workload(pt.NODE_FILE, pt.POD_FILE,
                                  gpu_spec="honor")
    untyped = parser.parse_workload(pt.NODE_FILE, pt.POD_FILE)
    cluster, pods = pt.reference_inputs(d)
    allowed = gs.load_allowed(os.path.join(d, "csv", pt.NODE_FILE),
                              os.path.join(d, "csv", pt.POD_FILE))
    codes = pt.policy_sources()
    refs = [gs.simulate(cluster, pods, allowed, policies.source_policy(c),
                        retry="earliest_delete", prefilter_k=64)
            for c in codes]
    free = [plain_sim.simulate(cluster, pods, policies.source_policy(c),
                               retry="earliest_delete", prefilter_k=64)
            for c in codes]
    # the constraints leave every policy something to decide: each fails
    # placements the unconstrained run does not, places every pod in the
    # end and ends with a fitness of its own
    assert typed.typed and not untyped.typed
    assert int((~allowed.all(axis=1)).sum()) == int(np.count_nonzero(
        np.asarray(typed.pods.gpu_spec))) == 72
    for r, u in zip(refs, free):
        assert r.num_frag_events > u.num_frag_events > 0
        assert r.policy_score > 0 and not r.truncated
        assert (r.assigned_node != u.assigned_node).any()
        placed = r.assigned_node >= 0
        assert allowed[np.flatnonzero(placed), r.assigned_node[placed]].all()
    assert len({r.policy_score for r in refs}) == len(codes)
    return d, typed, untyped, cluster, pods, allowed, codes, refs


def _assert_equal(tag, ref, res, pods, lane=None):
    numbers = compare(tag, ref, Output.of_lane(res, pods.p, lane),
                      GUARANTEES)
    assert all(n.ok for n in numbers), [n for n in numbers if not n.ok]
    assert any(n.name.endswith("fitness_rel_err") for n in numbers), tag
    frag = np.asarray(res.num_fragmentation_events)
    assert int(frag if lane is None else frag[lane]) == ref.num_frag_events


@pytest.mark.parametrize("engine,k", [("exact", 0), ("exact", 64),
                                      ("flat", 0), ("flat", 64)])
def test_both_engines_are_the_reference(deployment, engine, k):
    """Placements, counters and fitness of whole runs, dense and under
    the large-cluster rule, each engine under its own retry rule."""
    _, typed, _, cluster, pods, allowed, _, _ = deployment
    mod, retry = {"exact": (exact, "heap_array"),
                  "flat": (flat, "earliest_delete")}[engine]
    for name in ("first_fit", "best_fit"):
        res = mod.simulate(typed, zoo.ZOO[name](),
                           SimConfig(node_prefilter_k=k))
        ref = gs.simulate(cluster, pods, allowed, getattr(policies, name),
                          retry=retry, prefilter_k=k)
        assert ref.num_frag_events > 0
        _assert_equal(f"{engine}.k{k}.{name}", ref, res, pods)


TIERS = {"vm_batch": {"vm_batch": True}, "vm": {"vm_batch": False},
         "jit": {"use_vm": False}}


@pytest.mark.parametrize("tier", list(TIERS))
def test_every_code_tier_applies_the_constraints(deployment, tier):
    from fks_tpu.obs import spans

    _, typed, _, _, pods, _, codes, refs = deployment
    spans.LOG.clear()
    ev = CodeEvaluator(typed, engine="flat", **TIERS[tier])
    assert ev.cfg.node_prefilter_k == 64
    for lane, (rec, ref) in enumerate(zip(ev.evaluate(codes), refs)):
        assert rec.error is None, rec.error
        _assert_equal(f"{tier}.lane{lane}", ref, rec.result, pods)
    (root,) = [r for r in spans.LOG.snapshot()
               if r.name == "tier/evaluate"]
    assert (root.fields["typed_pods"], root.fields["node_models"]) \
        == (72, 6)


def test_the_parametric_population_applies_the_constraints(deployment):
    from fks_tpu.parallel import make_population_eval

    _, typed, _, cluster, pods, allowed, _, _ = deployment
    w = np.stack([np.asarray(parametric.seed_weights(n), np.float32)
                  for n in ("first_fit", "best_fit", "packing")])
    res = jax.device_get(make_population_eval(
        typed, cfg=SimConfig(node_prefilter_k=64), engine="flat")(
            jnp.asarray(w)))
    for lane in range(len(w)):
        ref = gs.simulate(cluster, pods, allowed,
                          policies.parametric_policy(w[lane]),
                          retry="earliest_delete", prefilter_k=64)
        _assert_equal(f"param{lane}", ref, res, pods, lane)


@pytest.mark.parametrize("engine", ["exact", "flat"])
def test_a_typed_workload_without_constraints_is_the_untyped_one(
        deployment, engine):
    """Every ``gpu_spec`` empty: the leaves are there, the term is
    emitted, and every field of the result is the untyped run's bit for
    bit; the untyped workload's own result is not the constrained one's."""
    _, typed, untyped, _, _, _, _, _ = deployment
    mod = {"exact": exact, "flat": flat}[engine]
    empty = dataclasses.replace(typed, pods=dataclasses.replace(
        typed.pods, gpu_spec=np.zeros_like(np.asarray(typed.pods.gpu_spec))))
    assert empty.typed
    cfg = SimConfig(node_prefilter_k=64)
    a, b, c = (jax.device_get(mod.simulate(w, zoo.best_fit(), cfg))
               for w in (empty, untyped, typed))
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        assert np.array_equal(x, y)
    assert not np.array_equal(b.assigned_node, c.assigned_node)


def test_reference_without_constraints_is_plain_sim(deployment):
    """``plain_sim_gpuspec.simulate`` with every node allowed equals
    ``plain_sim.simulate`` field for field, under both retry rules."""
    _, _, _, cluster, pods, allowed, codes, _ = deployment
    free = np.ones_like(allowed)
    for retry, k, code in (("earliest_delete", 64, codes[1]),
                           ("heap_array", 0, codes[2])):
        a = gs.simulate(cluster, pods, free, policies.source_policy(code),
                        retry=retry, prefilter_k=k)
        b = plain_sim.simulate(cluster, pods, policies.source_policy(code),
                               retry=retry, prefilter_k=k)
        for f in dataclasses.fields(a):
            x, y = getattr(a, f.name), getattr(b, f.name)
            assert np.array_equal(x, y), (retry, f.name)


# ------------------------------------------------- fork and snapshot

def test_a_snapshot_under_constraints_forks_to_the_engines_own_carry(
        deployment):
    """The prefix holds refusals the constraints made and constrained
    pods waiting; the forked carry is the step's own leaf for leaf, and
    the reference reads the written file to the same log."""
    from tests.test_snapshot_carry import _leaf_by_leaf

    d, typed, _, cluster, pods, allowed, _, _ = deployment
    e0 = 260
    cfg = SimConfig(node_prefilter_k=64)
    snap = flat.make_snapshot(typed, zoo.best_fit(), e0, cfg)
    refused = np.asarray(snap.node) < 0
    assert refused.sum() >= 3 and snap.rule == "earliest_delete"
    assert np.asarray(typed.pods.gpu_spec)[
        np.asarray(snap.pod)[refused]].all()
    _leaf_by_leaf(typed, "typed", e0, "best_fit", False)
    forked = dataclasses.replace(typed, snapshot=snap)
    counts = flat.fork_counts(forked, flat.initial_state(forked, cfg))
    assert counts["typed_waiting"] == counts["waiting"] >= 1
    path = os.path.join(d, "csv", "snapshot.csv.gz")
    snap_mod.write_snapshot_csv_gz(typed, snap, path)
    log = gs.load_log(path, os.path.join(d, "csv", pt.NODE_FILE),
                      os.path.join(d, "csv", pt.POD_FILE))
    at_fork = gs.validate(cluster, pods, allowed, log, "earliest_delete")
    assert at_fork.num_frag_events == int(refused.sum())
    ref = gs.simulate_from(cluster, pods, allowed, log, policies.best_fit,
                           retry="earliest_delete", prefilter_k=64)
    res = flat.simulate(forked, zoo.best_fit(), cfg)
    _assert_equal("forked", ref, res, pods)


def test_a_log_that_breaks_a_constraint_is_no_snapshot(deployment):
    """A logged placement on a node the pod may not take: ``replay``
    raises by name before any device program, and so does the
    reference's ``validate``; the same log is a valid snapshot of the
    workload parsed WITHOUT the choice."""
    d, typed, untyped, cluster, pods, allowed, _, _ = deployment
    e0 = 40
    snap = flat.make_snapshot(untyped, zoo.first_fit(), e0,
                              SimConfig(node_prefilter_k=64))
    pod, node = np.asarray(snap.pod), np.asarray(snap.node)
    broken = ~allowed[pod, node]
    assert broken.any() and (node >= 0).all()
    snap_mod.replay(untyped, snap)
    with pytest.raises(ValueError, match="gpu_spec does not name"):
        snap_mod.replay(typed, snap)
    with pytest.raises(ValueError, match="gpu_spec does not name"):
        flat.initial_state(dataclasses.replace(typed, snapshot=snap),
                           SimConfig())
    log = gs.Log([(int(i), int(n), int(b)) for i, n, b in zip(
        pod, node, np.asarray(snap.gpus))], e0, "")
    with pytest.raises(ValueError, match="gpu_spec does not name"):
        gs.validate(cluster, pods, allowed, log, "earliest_delete")


# ----------------------------------------------------------- who refuses

def test_fused_and_portfolio_refuse_by_name(deployment):
    from fks_tpu.portfolio.engine import PortfolioEngine
    from fks_tpu.sim import fused

    _, typed, _, _, _, _, _, _ = deployment
    with pytest.raises(ValueError, match="gpu_spec: GPU-type constraints "
                       "are not supported in the fused kernel"):
        fused._build_plan(typed, SimConfig())
    # serving's two plain engines honour a query's gpu_spec since PR 49;
    # the portfolio's slot table still does not, and says who does
    with pytest.raises(ValueError, match="gpu_spec: the portfolio's "
                       "slot-table executables .* honoured by ServeEngine"):
        PortfolioEngine([object()], typed)


@pytest.mark.parametrize("kind", ["aot", "vm"])
def test_the_two_plain_serve_engines_honour_a_typed_workload(deployment,
                                                             kind):
    """Since PR 49 (before it they answered a typed workload as if no pod
    named a GPU, and said nothing): the engine's queries carry the leaf
    where its workload is typed, and an engine on the untyped parse
    refuses a pod that names models. The paths against the reference are
    tests/test_vm_serve.py and tests/test_serve_fork.py."""
    from fks_tpu.serve import (ChampionSpec, ServeEngine, ShapeEnvelope,
                               VMServeEngine)
    from fks_tpu.serve.batcher import build_query_workload

    _, typed, untyped, _, _, _, codes, _ = deployment
    cls = VMServeEngine if kind == "vm" else ServeEngine
    env = ShapeEnvelope(max_pods=16, max_batch=2)
    pod = {"cpu_milli": 1000, "memory_mib": 1024, "num_gpu": 1,
           "gpu_milli": 500, "gpu_spec": "T4|P100"}
    on = cls(ChampionSpec(code=codes[0]), typed, envelope=env,
             engine="exact", prefilter_k=64)
    off = cls(ChampionSpec(code=codes[0]), untyped, envelope=env,
              engine="exact", prefilter_k=64)
    assert on.typed and not off.typed
    on.validate_query([pod])
    with pytest.raises(ValueError, match="parsed without GPU models"):
        off.validate_query([pod])
    wl = build_query_workload(on.cluster, [pod], 16)
    vocab = typed.cluster.gpu_models
    assert wl.typed and int(wl.pods.gpu_spec[0]) == gpu_spec_bits(
        "T4|P100", vocab) == (1 << vocab.index("T4")
                              | 1 << vocab.index("P100"))
    assert build_query_workload(off.cluster, [{"cpu_milli": 1}],
                                16).pods.gpu_spec is None


def test_trace_batching_carries_the_leaves(deployment):
    from fks_tpu.parallel.traces import strip_ids

    _, typed, untyped, _, _, _, _, _ = deployment
    s = strip_ids(typed)
    assert s.typed and s.cluster.gpu_models == typed.cluster.gpu_models
    assert np.array_equal(s.pods.gpu_spec, typed.pods.gpu_spec)
    assert not strip_ids(untyped).typed


# ------------------------------------- the committed list and snapshot

def test_the_committed_snapshot_is_what_the_command_writes(tmp_path):
    from fks_tpu import cli

    name = "openb_snapshot_gpuspec25_inflated080_e4864.csv.gz"
    path, snap = cli.write_snapshot(tmp_path / "snap.csv.gz", name=name)
    committed = os.path.join(cells.ROOT, CONFIG["snapshot"]["file"])
    assert committed.endswith(name)
    with open(path, "rb") as a, open(committed, "rb") as b:
        got, want = a.read(), b.read()
    assert got == want
    assert hashlib.sha256(want).hexdigest() == CONFIG["snapshot"]["sha256"]
    # outside chipbench/: a parent checkout ends in verify_files
    assert not CONFIG["snapshot"]["file"].startswith("chipbench/")
    assert not CONFIG["trace"]["file"].startswith("chipbench/")
    node = np.asarray(snap.node)
    assert (snap.e0, snap.rule, len(node), int((node < 0).sum())) \
        == (4864, "earliest_delete", 4864, 1) \
        == (CONFIG["start_event"], CONFIG["retry_rule"], 4864, 1)
    text = gzip.decompress(want).decode().splitlines()
    assert text[0] == "name,node_sn,gpus,event,rule"
    assert text[-1] == ",,,4864,earliest_delete"
    assert "inflated-pod-3568,,,3568," in text


def test_the_reference_makes_the_committed_log_and_counts_the_state():
    """The reference's own float32 ``best_fit`` run of the list under the
    constraints and rule 64 makes the committed file's log, and the
    program's fork holds what the configuration says of the state."""
    files = cells.verify_files(CONFIG)
    cluster = data.load_cluster(files["cluster"], files["gpu_mem_mapping"])
    pods = data.load_pods(files["trace"])
    allowed = gs.load_allowed(files["cluster"], files["trace"])
    assert int((~allowed.all(axis=1)).sum()) == CONFIG["typed_pods"] == 1375
    log = gs.load_log(files["snapshot"], files["cluster"], files["trace"])
    assert (log.e0, log.rule) == (4864, "earliest_delete")
    own = gs.simulate(cluster, pods, allowed, policies.best_fit,
                      retry="earliest_delete", prefilter_k=64,
                      max_steps=4864)
    for i, node, bits in log.attempts:
        if node >= 0:
            assert (own.assigned_node[i], own.assigned_gpus[i]) \
                == (node, bits)
    assert own.num_frag_events == 1 and own.scheduled_pods == 4863
    assert gs.validate(cluster, pods, allowed, log,
                       "earliest_delete").num_frag_events == 1
    wl = TraceParser().parse_workload(
        "openb_node_list_all_node.csv",
        "openb_pod_list_gpuspec25_inflated080.csv",
        snapshot_file="openb_snapshot_gpuspec25_inflated080_e4864.csv",
        gpu_spec="honor")
    assert list(wl.cluster.gpu_models) == CONFIG["node_models"]
    s = flat.initial_state(wl, SimConfig(node_prefilter_k=64))
    assert flat.fork_counts(wl, s) == {
        "residents": 4863, "nodes_loaded": 1074, "departed": 0,
        "waiting": 1, "typed_waiting": 1, "prefix_failed": 1}
    assert (int(s.snap_idx), int(s.pending)) == (14, 6695)
