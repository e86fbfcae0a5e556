"""The benchmark's own parse of the OpenB CSV files (gzip or plain).

Independent of ``fks_tpu.data``: upstream's rules (``benchmarks/parser.py``
as SURVEY.md records them) written again. Node CSV ``sn,cpu_milli,
memory_mib,gpu,model``: a node's GPUs exist only when its model is in
``gpu_mem_mapping.json``, each with 1000 milli, while ``gpu_left`` starts
at the declared count. Pod CSV: ``duration = deletion - creation``; an
empty ``gpu_milli`` is 0; equal-time events order by the pod name as a
string.
"""
from __future__ import annotations

import csv
import gzip
import io
import json

import numpy as np

from chipbench.reference.plain_sim import Cluster, Pods

GPU_MILLI = 1000


def _rows(path: str) -> list:
    if path.endswith(".gz"):
        f = io.TextIOWrapper(gzip.open(path, "rb"), newline="")
    else:
        f = open(path, newline="")
    with f:
        return list(csv.DictReader(f))


def load_cluster(csv_path: str, mapping_path: str) -> Cluster:
    with open(mapping_path) as f:
        mapping = json.load(f)
    rows = _rows(csv_path)
    n = len(rows)
    declared = np.array([int(r["gpu"]) for r in rows], np.int64)
    real = np.array([int(r["gpu"]) if int(r["gpu"]) > 0
                     and r.get("model", "") in mapping else 0
                     for r in rows], np.int64)
    g = max(1, int(real.max(initial=0)))
    mask = np.arange(g)[None, :] < real[:, None]
    return Cluster(
        cpu_total=np.array([int(r["cpu_milli"]) for r in rows], np.int64),
        mem_total=np.array([int(r["memory_mib"]) for r in rows], np.int64),
        gpu_declared=declared, num_gpus=real,
        gpu_milli_total=np.where(mask, GPU_MILLI, 0).astype(np.int64),
        gpu_mask=mask)


def load_pods(csv_path: str) -> Pods:
    rows = _rows(csv_path)
    names = [r["name"] for r in rows]
    order = sorted(range(len(rows)), key=lambda i: names[i])
    rank = np.zeros(len(rows), np.int64)
    rank[order] = np.arange(len(rows))
    col = lambda k: np.array([int(r.get(k) or 0) for r in rows], np.int64)  # noqa: E731
    creation = col("creation_time")
    return Pods(cpu=col("cpu_milli"), mem=col("memory_mib"),
                num_gpu=col("num_gpu"), gpu_milli=col("gpu_milli"),
                creation_time=creation,
                duration=col("deletion_time") - creation, rank=rank)


def pods_from_dicts(pods: list) -> Pods:
    """A what-if query (the service's pod dicts) as ``Pods``: ordinal
    names, so rank is position; a missing ``duration_time`` is the
    service's documented default of 1,000,000."""
    col = lambda k, d=0: np.array([int(p.get(k, d)) for p in pods], np.int64)  # noqa: E731
    return Pods(cpu=col("cpu_milli"), mem=col("memory_mib"),
                num_gpu=col("num_gpu"), gpu_milli=col("gpu_milli"),
                creation_time=col("creation_time"),
                duration=col("duration_time", 1_000_000),
                rank=np.arange(len(pods), dtype=np.int64))
