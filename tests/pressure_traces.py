"""A small large-cluster deployment for the CPU tests: a traces directory
(node CSV, inflated pod CSV, the GPU memory map) that both the program's
parser and the plain reference's read.

320 synthetic nodes of the real OpenB park's archetypes, mostly CPU-only,
so that the node axis is over ``PREFILTER_MIN_NODES`` while the GPU
capacity (about 300 GPUs) keeps an inflated list at share 0.80 to about
350 pods: whole runs of about 700 events. Seeds 2 and 5 put every one of
the four test policies under pressure (each retries, each places every
pod in the end, the fitnesses differ); chosen with the plain reference.
"""
from __future__ import annotations

import json
import os
import shutil

import numpy as np

from fks_tpu.data import TraceParser, default_traces_dir
from fks_tpu.data.inflate import arrival_picks, inflate_pods, pods_csv

NODES = 320
SHARE = 0.80
SEEDS = (2, 5)
NODE_FILE, POD_FILE = "nodes.csv", "pods.csv"
#: (weight, GPUs, model, cpu_milli, memory_mib): rows of
#: openb_node_list_all_node.csv
ARCHETYPES = (
    (0.62, 0, "", 32000, 262144),
    (0.10, 1, "V100M16", 8000, 32768),
    (0.14, 2, "T4", 104000, 524288),
    (0.06, 2, "P100", 16000, 122880),
    (0.04, 4, "V100M16", 32000, 131072),
    (0.04, 8, "G2", 96000, 393216),
)
CHAMPIONS = ("funsearch_20260801_045536_score0.5365.json",
             "funsearch_20260801_134224_score0.4430.json")


#: the same park with every GPU model the shipped ``gpuspec*`` lists name
#: but A10 (which only ``A10|T4`` and longer sets name), for
#: ``write_typed_traces``
TYPED_ARCHETYPES = (
    (0.62, 0, "", 32000, 262144),
    (0.07, 1, "V100M16", 8000, 32768),
    (0.03, 1, "V100M32", 8000, 32768),
    (0.14, 2, "T4", 104000, 524288),
    (0.06, 2, "P100", 16000, 122880),
    (0.02, 4, "V100M16", 32000, 131072),
    (0.02, 4, "G3", 32000, 131072),
    (0.04, 8, "G2", 96000, 393216),
)


def write_traces(traces_dir: str, seed: int, nodes: int = NODES,
                 share: float = SHARE, archetypes=ARCHETYPES) -> TraceParser:
    """Write the deployment of ``seed`` under ``traces_dir`` and return
    the program's parser on it."""
    src = default_traces_dir()
    os.makedirs(os.path.join(traces_dir, "csv"), exist_ok=True)
    shutil.copy(src / "gpu_mem_mapping.json", traces_dir)
    rng = np.random.default_rng(seed)
    kinds = rng.choice(len(archetypes), size=nodes,
                       p=[a[0] for a in archetypes])
    with open(os.path.join(traces_dir, "csv", NODE_FILE), "w") as f:
        f.write("sn,cpu_milli,memory_mib,gpu,model\n")
        for i, k in enumerate(kinds):
            _, gpus, model, cpu, mem = archetypes[k]
            f.write(f"node-{i:04d},{cpu},{mem},{gpus},{model}\n")
    parser = TraceParser(traces_dir)
    pods = inflate_pods(parser.parse_cluster(NODE_FILE),
                        TraceParser().parse_pods(), share, seed)
    with open(os.path.join(traces_dir, "csv", POD_FILE), "w") as f:
        f.write(pods_csv(pods))
    return parser


def write_typed_traces(traces_dir: str, seed: int,
                       source: str = "openb_pod_list_gpuspec25.csv"
                       ) -> TraceParser:
    """``write_traces``' deployment with OpenB's GPU-type constraints:
    nodes of ``TYPED_ARCHETYPES`` and the draw ``write_traces`` makes
    (the shipped ``gpuspec*`` lists are the default list row for row but
    for ``gpu_spec``), each arrival with its source row's ``gpu_spec``.
    Seeds 3, 5 and 7 of gpuspec25 put the four test policies under a
    type's scarcity and let each place every pod in the end (310 pods, 72
    of them constrained, 34-271 failed placements at seed 5; chosen with
    the plain reference)."""
    parser = write_traces(traces_dir, seed, archetypes=TYPED_ARCHETYPES)
    shipped = TraceParser()
    cluster = parser.parse_cluster(NODE_FILE)
    pods = shipped.parse_pods(source)
    column = [r.get("gpu_spec") or ""
              for r in shipped._read_csv(shipped.csv_dir / source)]
    picks = arrival_picks(cluster, pods, SHARE, seed)
    with open(os.path.join(traces_dir, "csv", POD_FILE), "w") as f:
        f.write(pods_csv(inflate_pods(cluster, pods, SHARE, seed),
                         [column[j] for j in picks]))
    return parser


def reference_inputs(traces_dir: str):
    """(Cluster, Pods) from the plain reference's own parse."""
    from chipbench.reference import data

    return (data.load_cluster(
        os.path.join(traces_dir, "csv", NODE_FILE),
        os.path.join(traces_dir, "gpu_mem_mapping.json")),
        data.load_pods(os.path.join(traces_dir, "csv", POD_FILE)))


def policy_sources() -> list:
    """first_fit, best_fit and two ledger champions, as sources."""
    from fks_tpu.funsearch import template

    seeds = template.seed_policies()
    out = [seeds["first_fit"], seeds["best_fit"]]
    root = default_traces_dir().parent.parent / "policies" / "discovered"
    for name in CHAMPIONS:
        with open(root / name) as f:
            out.append(json.load(f)["code"])
    return out
