"""What the drivers share: the clock, the benchmark's own host spans, the
program's parse of a configuration's files and the reference's parse of
the same files."""
from __future__ import annotations

import contextlib
import os
import time

now = time.perf_counter

TRACE_WINDOW = "bench/trace_window"


def annotate(name: str):
    """A host span in the profiler's own trace (free when no trace runs)."""
    import jax

    return jax.profiler.TraceAnnotation(name)


@contextlib.contextmanager
def trace_window():
    """The span that bounds the traced window (``chipbench.reduce``)."""
    with annotate(TRACE_WINDOW):
        yield


def _csv_name(path: str) -> str:
    name = os.path.basename(path)
    return name[:-3] if name.endswith(".gz") else name


def parse_workload(config: dict, files: dict):
    """The program's own loader on the configuration's (hash-checked)
    files; ``pod_limit`` (selftest only) keeps the first pods."""
    from fks_tpu.data import TraceParser

    traces_dir = os.path.dirname(os.path.dirname(files["cluster"]))
    wl = TraceParser(traces_dir).parse_workload(
        node_file=_csv_name(files["cluster"]),
        pod_file=_csv_name(files["trace"]))
    limit = config.get("pod_limit")
    if limit:
        from fks_tpu.data.build import make_pods
        from fks_tpu.data.entities import Workload
        from fks_tpu.serve.batcher import pods_to_dicts
        rows = pods_to_dicts(wl.pods, limit=int(limit))
        ids = wl.pods.pod_ids
        wl = Workload(cluster=wl.cluster, pods=make_pods(
            [{"pod_id": ids[i], **r} for i, r in enumerate(rows)],
            pad_pods_to=-(-len(rows) // 128) * 128))
    return wl


def reference_inputs(config: dict, files: dict):
    """(Cluster, Pods) from the reference's own parse of the same files."""
    from chipbench.reference import data

    cluster = data.load_cluster(files["cluster"], files["gpu_mem_mapping"])
    pods = data.load_pods(files["trace"])
    limit = config.get("pod_limit")
    if limit:
        pods = pods.take(range(int(limit)))
        # ranks must stay a dense order for the flat queue's slot order
        order = pods.rank.argsort()
        pods.rank[order] = range(len(order))
    return cluster, pods
