"""Test harness configuration.

Tests run on a virtual 8-device CPU mesh (the reference offers no
distributed-test pattern; this is the TPU-mesh stand-in per SURVEY.md §4) and
with x64 enabled so parity tests can evaluate policy arithmetic in float64,
matching the reference's Python-float semantics. Framework code pins its own
dtypes (int32/float32 by default) and accepts a dtype override.

Env must be set before the first jax import.
"""
import os

# Tests run on the CPU whatever the environment points at: they model the
# mesh with 8 virtual CPU devices and compare in float64. The chip is
# reached only by chip_smoke.py and chipbench, never from here.
# jax.config.update, not the environment: it wins over an inherited
# JAX_PLATFORMS and holds until the first backend initialization.
# XLA_FLAGS is read at backend creation, so setting it here still works.
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)
# cli.main places the persistent compile cache inside the checkout; tier-1
# must not depend on what an earlier run left there (and XLA:CPU logs a
# machine-feature warning on every cache load), so tests never use it
jax.config.update("jax_enable_compilation_cache", False)

import json  # noqa: E402
import pathlib  # noqa: E402

from fks_tpu.funsearch import lower_pool  # noqa: E402

# the driver runs tier-1 in six pytest processes side by side: each keeps
# its pool of lowering workers (one per process, started by its first
# batched-VM CodeEvaluator) to two, whatever the machine's cores
lower_pool.MAX_WORKERS = 2

import pytest  # noqa: E402

FIXTURES = pathlib.Path(__file__).parent / "fixtures"


def pytest_collection_modifyitems(config, items):
    """Default to the fast tier by DESELECTING `slow` items — unless the
    user passed -m (their marker expression wins) or named a test file
    explicitly (running `pytest tests/test_differential.py`, an all-slow
    module, means "run it", not "collect 0 tests and exit green" — the
    footgun an addopts-level `-m "not slow"` default had)."""
    if config.option.markexpr:
        return
    named = {
        pathlib.Path(a.split("::")[0]).resolve()
        for a in config.args if a.split("::")[0].endswith(".py")
    }
    selected, deselected = [], []
    for item in items:
        if ("slow" in item.keywords
                and pathlib.Path(str(item.fspath)).resolve() not in named):
            deselected.append(item)
        else:
            selected.append(item)
    if deselected:
        config.hook.pytest_deselected(items=deselected)
        items[:] = selected


@pytest.fixture(scope="session")
def golden_default():
    with open(FIXTURES / "golden_default.json") as f:
        return json.load(f)


@pytest.fixture(scope="session")
def golden_micro():
    with open(FIXTURES / "golden_micro.json") as f:
        return json.load(f)


@pytest.fixture(scope="session")
def golden_alt():
    with open(FIXTURES / "golden_alt_traces.json") as f:
        return json.load(f)


@pytest.fixture(scope="session")
def default_workload():
    from fks_tpu.data import TraceParser
    return TraceParser().parse_workload()


def make_micro_workload():
    """Tiny 2-node x 6-pod cluster for fast-tier end-to-end tests (one
    GPU node, one CPU-only node, alternating GPU/CPU pods)."""
    from fks_tpu.data.build import make_workload

    nodes = [{"node_id": "n0", "cpu_milli": 4000, "memory_mib": 8000,
              "gpus": [1000, 1000]},
             {"node_id": "n1", "cpu_milli": 2000, "memory_mib": 4000,
              "gpus": []}]
    pods = [{"pod_id": f"p{i}", "cpu_milli": 500, "memory_mib": 500,
             "num_gpu": i % 2, "gpu_milli": 300 * (i % 2),
             "creation_time": i, "duration_time": 5} for i in range(6)]
    return make_workload(nodes, pods, pad_nodes_to=2, pad_gpus_to=2,
                         pad_pods_to=8)


@pytest.fixture(scope="session")
def micro_workload():
    return make_micro_workload()
