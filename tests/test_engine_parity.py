"""Full-trace parity vs the reference implementation (recorded fixtures).

The bar (SURVEY.md, BASELINE.json north star): fitness to 1e-5. With the
exact heap replica + float64 policy arithmetic we require far tighter:
identical event counts, snapshot counts, fragmentation events, per-pod
assignments, and fitness to ~1e-9.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from fks_tpu.data import TraceParser
from fks_tpu.models import zoo
from fks_tpu.sim.engine import SimConfig, simulate

# best_fit stays in the fast tier as the default-trace parity sentinel;
# the other four run with the slow tier (-m slow)
POLICIES = [pytest.param("first_fit", marks=pytest.mark.slow),
            "best_fit",
            pytest.param("funsearch_4901", marks=pytest.mark.slow),
            pytest.param("funsearch_4816", marks=pytest.mark.slow),
            pytest.param("funsearch_4800", marks=pytest.mark.slow)]


def check_parity(res, ref, wl, tol=1e-9):
    assert not bool(res.failed)
    assert not bool(res.truncated)
    assert int(res.events_processed) == ref["events_processed"]
    assert int(res.num_snapshots) == ref["num_snapshots"]
    assert int(res.num_fragmentation_events) == ref["num_fragmentation_events"]
    assert int(res.scheduled_pods) == ref["scheduled_pods"]
    assert int(res.max_nodes) == ref["max_nodes"]
    n_pods = wl.num_pods
    np.testing.assert_array_equal(
        np.asarray(res.assigned_node)[:n_pods], np.array(ref["assignments"]))
    np.testing.assert_array_equal(
        np.asarray(res.pod_ctime)[:n_pods], np.array(ref["final_creation_time"]))
    n = wl.num_nodes
    np.testing.assert_array_equal(np.asarray(res.cpu_left)[:n],
                                  np.array(ref["final_cpu_left"]))
    gml = np.asarray(res.gpu_milli_left)
    for i, row in enumerate(ref["final_gpu_milli_left"]):
        assert gml[i, :len(row)].tolist() == row
    assert abs(float(res.policy_score) - ref["policy_score"]) < tol
    for k in ("avg_cpu_utilization", "avg_memory_utilization",
              "avg_gpu_count_utilization", "avg_gpu_memory_utilization",
              "gpu_fragmentation_score"):
        assert abs(float(getattr(res, k)) - ref[k]) < tol, k


@pytest.mark.parametrize("name", POLICIES)
def test_default_trace_parity(name, default_workload, golden_default):
    policy = zoo.ZOO[name](dtype=jnp.float64)
    res = simulate(default_workload, policy, SimConfig(score_dtype=jnp.float64))
    check_parity(res, golden_default["policies"][name], default_workload)


@pytest.mark.parametrize("pod_file,name", [
    ("openb_pod_list_gpushare40.csv", "best_fit"),
    ("openb_pod_list_gpuspec33.csv", "first_fit"),
    ("openb_pod_list_cpu250.csv", "best_fit"),
])
@pytest.mark.slow
def test_alt_trace_parity(pod_file, name, golden_alt):
    wl = TraceParser().parse_workload(pod_file=pod_file)
    policy = zoo.ZOO[name](dtype=jnp.float64)
    res = simulate(wl, policy, SimConfig(score_dtype=jnp.float64))
    check_parity(res, golden_alt[pod_file][name], wl)


def test_gpuspec33_parsed_by_default_is_upstreams_run(golden_alt):
    """Upstream ignores ``gpu_spec`` (its golden ``gpuspec33`` run places
    T4 pods on 16 nodes none of which is a T4), so the DEFAULT parse must:
    no type leaves, upstream's run to every count. Honoured, the same
    list is another run."""
    pod_file = "openb_pod_list_gpuspec33.csv"
    wl = TraceParser().parse_workload(pod_file=pod_file)
    assert not wl.typed and wl.pods.gpu_spec is None \
        and wl.cluster.gpu_model is None
    cfg = SimConfig(score_dtype=jnp.float64)
    policy = zoo.ZOO["first_fit"](dtype=jnp.float64)
    check_parity(simulate(wl, policy, cfg), golden_alt[pod_file]["first_fit"],
                 wl)
    honoured = TraceParser().parse_workload(pod_file=pod_file,
                                            gpu_spec="honor")
    assert honoured.typed
    assert int(np.count_nonzero(np.asarray(honoured.pods.gpu_spec))) == 2388
    short = SimConfig(score_dtype=jnp.float64, max_steps=2000)
    a, b = (simulate(w, policy, short) for w in (wl, honoured))
    assert int(a.num_fragmentation_events) == 0 \
        < int(b.num_fragmentation_events)


@pytest.mark.slow
def test_float32_fitness_within_1e5(default_workload, golden_default):
    """The TPU-fast dtype must still meet the 1e-5 north-star bar on the
    default trace (placement decisions are integer; only evaluator sums and
    policy float math differ)."""
    res = simulate(default_workload, zoo.best_fit(dtype=jnp.float32),
                   SimConfig(score_dtype=jnp.float32))
    ref = golden_default["policies"]["best_fit"]
    assert int(res.num_snapshots) == ref["num_snapshots"]
    assert abs(float(res.policy_score) - ref["policy_score"]) < 1e-5
