"""Population vmap + mesh shard_map layer tests (micro workload).

Property under test: batched/sharded evaluation is bit-identical to running
each candidate through the single-policy engine — the TPU replacement for
the reference's per-candidate subprocess fan-out must not change fitness
(reference: funsearch/funsearch_integration.py:30-64, 535-562).
"""
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fks_tpu.data.build import make_workload
from fks_tpu.models import parametric
from fks_tpu.parallel.mesh import (
    POP_AXIS, make_sharded_eval, make_sharded_generation_step, pad_population,
    population_mesh,
)
from fks_tpu.parallel.population import make_population_eval
from fks_tpu.sim.engine import SimConfig, simulate

FIXTURES = pathlib.Path(__file__).parent / "fixtures"


def micro_workload():
    nodes = [
        {"node_id": "node1", "cpu_milli": 8000, "memory_mib": 16000,
         "gpus": [1000, 1000], "gpu_memory_mib": 8000},
        {"node_id": "node2", "cpu_milli": 4000, "memory_mib": 8000, "gpus": []},
    ]
    pods = [
        {"pod_id": "pod1", "cpu_milli": 1000, "memory_mib": 2000, "num_gpu": 0,
         "gpu_milli": 0, "creation_time": 0, "duration_time": 10},
        {"pod_id": "pod2", "cpu_milli": 2000, "memory_mib": 4000, "num_gpu": 1,
         "gpu_milli": 500, "creation_time": 5, "duration_time": 15},
        {"pod_id": "pod3", "cpu_milli": 3000, "memory_mib": 6000, "num_gpu": 0,
         "gpu_milli": 0, "creation_time": 10, "duration_time": 8},
        {"pod_id": "pod4", "cpu_milli": 1500, "memory_mib": 3000, "num_gpu": 2,
         "gpu_milli": 400, "creation_time": 15, "duration_time": 12},
    ]
    return make_workload(nodes, pods, pad_nodes_to=4, pad_gpus_to=4, pad_pods_to=8)


@pytest.fixture(scope="module")
def wl():
    return micro_workload()


@pytest.fixture(scope="module")
def pop8():
    key = jax.random.PRNGKey(0)
    return parametric.init_population(key, 8, noise=0.2)


@pytest.mark.slow
def test_vmap_matches_single(wl, pop8):
    res = make_population_eval(wl)(pop8)
    for i in range(pop8.shape[0]):
        single = simulate(wl, parametric.as_policy(pop8[i]))
        assert np.asarray(res.policy_score)[i] == pytest.approx(
            float(single.policy_score), abs=0)
        assert int(np.asarray(res.scheduled_pods)[i]) == int(single.scheduled_pods)
        np.testing.assert_array_equal(
            np.asarray(res.assigned_node)[i], np.asarray(single.assigned_node))


@pytest.mark.slow
def test_seed_policies_schedule_micro(wl):
    for name in ("first_fit", "best_fit", "worst_fit", "packing"):
        res = simulate(wl, parametric.as_policy(parametric.seed_weights(name)))
        assert int(res.scheduled_pods) == 4, name
        assert float(res.policy_score) > 0, name


@pytest.mark.slow
def test_sharded_eval_matches_vmap(wl, pop8):
    mesh = population_mesh()
    assert mesh.shape[POP_AXIS] == 8  # conftest forces 8 virtual devices
    padded, real = pad_population(pop8, mesh.shape[POP_AXIS])
    scores, elite_idx, elite_scores = make_sharded_eval(
        wl, mesh, elite_k=4)(padded)
    ref = make_population_eval(wl)(pop8).policy_score
    np.testing.assert_array_equal(np.asarray(scores)[:real], np.asarray(ref))
    # elites are the true global top-k
    order = np.argsort(-np.asarray(scores), kind="stable")
    np.testing.assert_allclose(
        np.sort(np.asarray(elite_scores))[::-1],
        np.sort(np.asarray(scores)[order[:4]])[::-1])


def test_padded_population_excludes_pad_from_elites(wl):
    """A non-divisible population is padded with copies of the last
    candidate; those pad slots must not enter the elite ranking."""
    mesh = population_mesh()
    # 6 real candidates; make the LAST one the best so its pad duplicates
    # would win elite slots if not masked.
    key = jax.random.PRNGKey(2)
    pop6 = parametric.init_population(key, 6, noise=0.3)
    pop6 = pop6.at[5].set(parametric.seed_weights("best_fit"))
    padded, real = pad_population(pop6, mesh.shape[POP_AXIS])
    assert padded.shape[0] == 8 and real == 6
    scores, elite_idx, elite_scores = make_sharded_eval(
        wl, mesh, elite_k=4)(padded, real)
    assert np.all(np.asarray(elite_idx) < real)
    assert len(set(np.asarray(elite_idx).tolist())) == 4


@pytest.mark.slow
def test_generation_step_preserves_elites(wl, pop8):
    mesh = population_mesh()
    step = make_sharded_generation_step(wl, mesh, elite_k=4, noise=0.05)
    new_params, scores, elite_scores = step(pop8, jax.random.PRNGKey(1))
    assert new_params.shape == pop8.shape
    # top-k elites occupy the first k slots of the new population, unchanged
    top = np.asarray(jax.lax.top_k(scores, 4)[1])
    np.testing.assert_allclose(
        np.asarray(new_params)[:4], np.asarray(pop8)[top], rtol=0, atol=0)
    # and a second evaluation of the elites reproduces their scores
    res2 = make_population_eval(wl)(new_params[:4])
    np.testing.assert_allclose(
        np.asarray(res2.policy_score),
        np.sort(np.asarray(elite_scores))[::-1])


# ---------------------------------------------------------------- hybrid mesh

@pytest.mark.slow
def test_hybrid_mesh_matches_flat_mesh(wl, pop8):
    """2-D ("dcn","pop") mesh (multi-slice topology modeled on the 8 virtual
    devices as 2 slices x 4 chips) must produce identical fitness and elite
    selection to the 1-D mesh and to plain vmap."""
    from fks_tpu.parallel import DCN_AXIS, hybrid_population_mesh

    mesh = hybrid_population_mesh(num_slices=2)
    assert mesh.shape[DCN_AXIS] == 2 and mesh.shape[POP_AXIS] == 4
    padded, real = pad_population(pop8, mesh)
    assert real == 8
    scores, elite_idx, elite_scores = make_sharded_eval(
        wl, mesh, elite_k=4)(padded)
    ref = make_population_eval(wl)(pop8).policy_score
    np.testing.assert_array_equal(np.asarray(scores), np.asarray(ref))

    flat = make_sharded_eval(wl, population_mesh(), elite_k=4)(pop8)
    np.testing.assert_array_equal(np.asarray(elite_idx), np.asarray(flat[1]))
    np.testing.assert_array_equal(np.asarray(elite_scores), np.asarray(flat[2]))


@pytest.mark.slow
def test_hybrid_generation_step_runs_and_preserves_elites(wl, pop8):
    from fks_tpu.parallel import hybrid_population_mesh

    mesh = hybrid_population_mesh(num_slices=2)
    step = make_sharded_generation_step(wl, mesh, elite_k=4, noise=0.05)
    new_params, scores, elite_scores = step(pop8, jax.random.PRNGKey(1))
    assert new_params.shape == pop8.shape
    top = np.asarray(jax.lax.top_k(scores, 4)[1])
    np.testing.assert_allclose(
        np.asarray(new_params)[:4], np.asarray(pop8)[top], rtol=0, atol=0)


def test_hybrid_mesh_rejects_indivisible_slices():
    from fks_tpu.parallel import hybrid_population_mesh

    with pytest.raises(ValueError, match="divisible"):
        hybrid_population_mesh(num_slices=3)


# ------------------------------------------- the mesh layer's own lowering

def test_default_layout_lowers_bit_identically():
    """``make_sharded_eval`` hands back the jitted program itself, and
    built the pinner's way (micro workload, one-device mesh) it lowers to
    the program the manifest has pinned since before the mesh layer
    decided its own sharding (``sharded_eval/default_layout``)."""
    from fks_tpu.analysis import lint

    mesh = population_mesh(jax.devices()[:1])
    params = parametric.init_population(jax.random.PRNGKey(0), 2)
    ev = make_sharded_eval(lint._micro_workload(), mesh, cfg=SimConfig(),
                           elite_k=2, engine="flat")
    doc = json.loads((FIXTURES / "jaxpr_pins.json").read_text())
    assert (lint._jaxpr_hash(ev, params)
            == doc["pins"]["sharded_eval/default_layout"])
    # the AOT seam and the (params, real_count=None) call signature
    assert ev.lower(params).compile() is not None
    np.testing.assert_array_equal(np.asarray(ev(params)[0]),
                                  np.asarray(ev(params, 2)[0]))


def test_default_layout_pin_present():
    doc = json.loads((FIXTURES / "jaxpr_pins.json").read_text())
    assert "sharded_eval/default_layout" in doc["pins"]
