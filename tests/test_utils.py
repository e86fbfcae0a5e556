"""Observability utilities: timing respects device sync, throughput math,
JSONL metrics schema, logger configuration. (These subsystems are framework
additions — the reference has neither profiler hooks nor ``logging``,
SURVEY.md §5 — so the tests define their contract.)"""
import json
import logging

import pytest
import jax
import jax.numpy as jnp

from fks_tpu.obs import span
from fks_tpu.utils import (
    MetricsWriter, ThroughputMeter, block_timed, get_logger, result_record,
)


def test_span_syncs_registered_value_at_exit(monkeypatch):
    """The one scoped timer (``obs.span``, which took over ``timed``): the
    clock must stop only after the value registered via t.sync() is
    materialized — i.e. block_until_ready is invoked on exactly that value
    at context exit (deleting the sync would regress to enqueue timing)."""
    synced = []
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda v: synced.append(v))
    sentinel = object()
    with span("eval") as t:
        got = t.sync(sentinel)
        assert synced == []  # not yet: only at context exit
    assert got is sentinel
    assert synced == [sentinel]
    assert t.seconds >= 0 and t.t1 >= t.t0 > 0

    pre = object()
    with span("pre-existing", sync=pre):
        pass
    assert synced == [sentinel, pre]


def test_block_timed_returns_materialized_result(monkeypatch):
    from fks_tpu.utils import profiling

    synced = []
    real = jax.block_until_ready
    monkeypatch.setattr(profiling.jax, "block_until_ready",
                        lambda v: (synced.append(v), real(v))[1])
    r, secs = block_timed(lambda a: a + 1, jnp.ones(8))
    assert float(r[0]) == 2.0
    assert secs > 0
    assert len(synced) == 1 and synced[0] is r


def test_throughput_meter_rate_is_total_over_total():
    m = ThroughputMeter()
    assert m.rate is None
    m.add(10, 1.0)
    m.add(30, 1.0)
    assert m.rate == 20.0  # 40 items / 2 s, not mean(10, 30)
    assert "40 in 2.00s" in m.summary()


def test_metrics_writer_jsonl(tmp_path):
    path = tmp_path / "m" / "run.jsonl"
    with MetricsWriter(str(path)) as w:
        w.write("bench", {"policy_score": 0.5}, policy="best_fit")
        w.write("generation", generation=1, best_score=0.9)
    lines = [json.loads(line) for line in path.read_text().splitlines()]
    assert len(lines) == 2
    assert lines[0]["kind"] == "bench"
    assert lines[0]["policy"] == "best_fit"
    assert lines[0]["policy_score"] == 0.5
    assert "ts" in lines[0]
    assert lines[1]["best_score"] == 0.9


def test_throughput_meter_rate_none_at_zero_seconds():
    """Zero accumulated time must yield None, not ZeroDivisionError — a
    sub-resolution timed rep (perf_counter delta 0.0) feeds this."""
    m = ThroughputMeter()
    m.add(10, 0.0)
    assert m.rate is None
    assert m.summary() == "10 in 0.00s"
    m.add(10, 2.0)
    assert m.rate == 10.0  # 20 items / 2 s total


def test_block_timed_pytree_result(monkeypatch):
    """block_timed must materialize EVERY leaf of a pytree result (dicts/
    tuples of arrays), not just a lone array."""
    from fks_tpu.utils import profiling

    synced = []
    real = jax.block_until_ready
    monkeypatch.setattr(profiling.jax, "block_until_ready",
                        lambda v: (synced.append(v), real(v))[1])
    tree, secs = block_timed(
        lambda a: {"x": a + 1, "pair": (a * 2, a.sum())}, jnp.ones(4))
    assert float(tree["x"][0]) == 2.0
    assert float(tree["pair"][0][0]) == 2.0
    assert float(tree["pair"][1]) == 4.0
    assert secs > 0
    assert len(synced) == 1 and synced[0] is tree  # whole tree, one call


def test_metrics_writer_coerces_accelerator_scalars(tmp_path):
    """Satellite fix: numpy/jax scalar fields must serialize instead of
    crashing json.dumps (device results leak into metric records)."""
    import numpy as np

    path = tmp_path / "m.jsonl"
    with MetricsWriter(str(path)) as w:
        w.write("bench", score=np.float32(0.5), n=np.int64(7),
                arr=np.arange(3), jscore=jnp.float32(0.25),
                jarr=jnp.arange(2))
    row = json.loads(path.read_text().splitlines()[0])
    assert row["score"] == 0.5 and row["n"] == 7
    assert row["arr"] == [0, 1, 2]
    assert row["jscore"] == 0.25 and row["jarr"] == [0, 1]


def test_metrics_writer_rejects_unserializable():
    from fks_tpu.utils.logging import json_ready

    with pytest.raises(TypeError):
        json_ready(object())


@pytest.mark.slow
def test_result_record_schema(default_workload):
    from fks_tpu.models import zoo
    from fks_tpu.sim.engine import SimConfig, simulate

    res = simulate(default_workload, zoo.ZOO["best_fit"](),
                   SimConfig(max_steps=500))
    rec = result_record(res, policy="best_fit")
    # reference metric schema (evaluator.py:16-25 + main.py:42,67-72)
    for key in ("policy_score", "avg_cpu_utilization", "avg_memory_utilization",
                "avg_gpu_count_utilization", "avg_gpu_memory_utilization",
                "gpu_fragmentation_score", "num_snapshots",
                "num_fragmentation_events", "events_processed",
                "scheduled_pods", "max_nodes"):
        assert key in rec
    json.dumps(rec)  # JSON-ready: plain python scalars only
    assert rec["policy"] == "best_fit"


def test_get_logger_single_handler():
    a = get_logger()
    b = get_logger("evolution")
    assert b.name == "fks_tpu.evolution"
    root = logging.getLogger("fks_tpu")
    assert len(root.handlers) == 1
    get_logger("again")
    assert len(root.handlers) == 1


@pytest.mark.slow
def test_cli_metrics_flag(tmp_path, default_workload):
    from fks_tpu.cli import main

    path = tmp_path / "bench.jsonl"
    rc = main(["bench", "--policies", "first_fit", "--metrics", str(path),
               "--trace", "openb_pod_list_default.csv"])
    assert rc == 0
    recs = [json.loads(line) for line in path.read_text().splitlines()]
    assert recs and recs[0]["kind"] == "bench"
    assert recs[0]["policy"] == "first_fit"
    assert abs(recs[0]["policy_score"] - 0.4292) < 1e-3


def test_compile_cache_is_placed_from_outside_or_at_the_fixed_path(
        monkeypatch, tmp_path):
    """One cache for every entry point: a set JAX_COMPILATION_CACHE_DIR
    is left alone (JAX reads it itself); otherwise the fixed directory
    inside the checkout, never a per-artifact or temporary one."""
    from fks_tpu.utils import cache

    before = jax.config.jax_compilation_cache_dir
    floors = ("jax_persistent_cache_min_compile_time_secs",
              "jax_persistent_cache_min_entry_size_bytes")
    floors_before = [getattr(jax.config, k) for k in floors]
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert cache.place_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before  # untouched
        # sub-second programs (most of this system's) are persisted too,
        # wherever the directory came from
        assert [getattr(jax.config, k) for k in floors] == [0, -1]
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        placed = cache.place_compile_cache()
        assert placed == cache.DEFAULT_CACHE_DIR
        assert placed.endswith("benchmarks/results/.jax_cache")
        assert jax.config.jax_compilation_cache_dir == placed
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        for k, v in zip(floors, floors_before):
            jax.config.update(k, v)
