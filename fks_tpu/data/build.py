"""Programmatic workload construction (tests, synthetic traces).

Builds the same padded array structures the CSV parser emits, from plain
Python specs. Mirrors what hand-built entity graphs do in the reference's
micro tests (reference: tests/test_simulator.py:40-85).
"""
from __future__ import annotations

from typing import Optional, Sequence

import numpy as np

from fks_tpu.data.entities import (
    ClusterArrays, PodArrays, Workload, gpu_model_leaves, gpu_spec_bits)


def make_cluster(nodes: Sequence[dict], pad_nodes_to: Optional[int] = None,
                 pad_gpus_to: Optional[int] = None,
                 gpu_models: bool = False) -> ClusterArrays:
    """nodes: dicts with node_id, cpu_milli, memory_mib, and either
    ``gpus`` (list of per-GPU milli capacities) or ``gpu_count`` +
    ``gpu_milli_capacity``; optional ``gpu_memory_mib``, ``gpu_declared``.
    ``gpu_models``: keep each node's ``model`` (a name, empty or absent
    for none) as the CSV parser does for a workload that honours
    ``gpu_spec``."""
    n = len(nodes)
    n_pad = pad_nodes_to or max(1, n)
    caps = []
    for spec in nodes:
        if "gpus" in spec:
            caps.append(list(spec["gpus"]))
        else:
            caps.append([spec.get("gpu_milli_capacity", 1000)] * spec.get("gpu_count", 0))
    g_pad = pad_gpus_to or max(1, max((len(c) for c in caps), default=1))

    cpu = np.zeros(n_pad, np.int32)
    mem = np.zeros(n_pad, np.int32)
    declared = np.zeros(n_pad, np.int32)
    num = np.zeros(n_pad, np.int32)
    gmt = np.zeros((n_pad, g_pad), np.int32)
    gmem = np.zeros((n_pad, g_pad), np.int32)
    gmask = np.zeros((n_pad, g_pad), bool)
    nmask = np.zeros(n_pad, bool)
    for i, spec in enumerate(nodes):
        cpu[i] = spec["cpu_milli"]
        mem[i] = spec["memory_mib"]
        k = len(caps[i])
        declared[i] = spec.get("gpu_declared", k)
        num[i] = k
        gmt[i, :k] = caps[i]
        gmem[i, :k] = spec.get("gpu_memory_mib", 0)
        gmask[i, :k] = True
        nmask[i] = True
    typed = gpu_model_leaves((s.get("model") or "" for s in nodes),
                             n_pad) if gpu_models else {}
    return ClusterArrays(
        cpu_total=cpu, mem_total=mem, gpu_declared=declared, num_gpus=num,
        gpu_milli_total=gmt, gpu_mem_total=gmem, gpu_mask=gmask,
        node_mask=nmask, node_ids=tuple(s["node_id"] for s in nodes),
        **typed)


def make_pods(pods: Sequence[dict], pad_pods_to: Optional[int] = None,
              gpu_models: Optional[Sequence[str]] = None) -> PodArrays:
    """pods: dicts with pod_id, cpu_milli, memory_mib, num_gpu, gpu_milli,
    creation_time, duration_time. ``gpu_models`` (the cluster's model
    names): keep each pod's ``gpu_spec``, names joined by ``|`` or the
    bit word itself (absent: any node); None ignores it."""
    p = len(pods)
    p_pad = pad_pods_to or max(1, p)
    arr = {k: np.zeros(p_pad, np.int32) for k in
           ("cpu", "mem", "num_gpu", "gpu_milli", "creation_time", "duration")}
    mask = np.zeros(p_pad, bool)
    ids = [s["pod_id"] for s in pods]
    for i, spec in enumerate(pods):
        arr["cpu"][i] = spec["cpu_milli"]
        arr["mem"][i] = spec["memory_mib"]
        arr["num_gpu"][i] = spec["num_gpu"]
        arr["gpu_milli"][i] = spec["gpu_milli"]
        arr["creation_time"][i] = spec["creation_time"]
        arr["duration"][i] = spec["duration_time"]
        mask[i] = True
    order = sorted(range(p), key=lambda i: ids[i])
    rank = np.zeros(p_pad, np.int32)
    for r, i in enumerate(order):
        rank[i] = r
    if gpu_models is not None:
        vocab = tuple(gpu_models)
        arr["gpu_spec"] = np.zeros(p_pad, np.int32)
        for i, spec in enumerate(s.get("gpu_spec", 0) for s in pods):
            arr["gpu_spec"][i] = spec if isinstance(spec, (int, np.integer)) \
                else gpu_spec_bits(spec, vocab)
    return PodArrays(tie_rank=rank, pod_mask=mask, pod_ids=tuple(ids), **arr)


def make_workload(nodes: Sequence[dict], pods: Sequence[dict],
                  gpu_spec: str = "ignore", **pad) -> Workload:
    """``gpu_spec="honor"`` as ``TraceParser.parse_workload``'s."""
    honor = gpu_spec == "honor"
    cluster = make_cluster(nodes, pad.get("pad_nodes_to"),
                           pad.get("pad_gpus_to"), gpu_models=honor)
    return Workload(cluster=cluster, pods=make_pods(
        pods, pad.get("pad_pods_to"),
        gpu_models=cluster.gpu_models if honor else None))
