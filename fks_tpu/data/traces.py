"""Host-side trace ingest: OpenB/Alibaba CSVs -> padded numpy arrays.

Semantics-compatible redesign of the reference parser
(benchmarks/parser.py:9-122):
- node CSV schema ``sn,cpu_milli,memory_mib,gpu,model`` + gpu_mem_mapping.json
  (model -> MiB); every GPU gets 1000 milli capacity (parser.py:45-46);
  GPUs are only materialized when the model is in the mapping (parser.py:39)
  while ``gpu_left`` still starts at the declared count (parser.py:56).
- pod CSV schema ``name,cpu_milli,memory_mib,num_gpu,gpu_milli,...``;
  ``duration = deletion_time - creation_time`` (parser.py:95); empty
  ``gpu_milli`` -> 0 (parser.py:82).
- Node iteration order == CSV row order (dict insertion order, parser.py:59);
  we keep that order as the node index axis, which preserves the reference's
  argmax tie-breaking.

Differences (deliberate):
- Files may be gzip-compressed (``*.csv.gz``); the shipped dataset is stored
  compressed in-repo.
- Traces missing optional columns (creation/deletion times, gpu_spec -- e.g.
  the multigpu* traces, which the reference parser crashes on) parse with
  defaults of 0.
- ``gpu_spec`` (the ``|``-joined GPU models a pod accepts; OpenB's
  ``gpuspec*`` lists fill it) is IGNORED by default, as upstream ignores
  it. ``parse_workload(..., gpu_spec="honor")`` keeps it: the cluster then
  carries each node's ``model`` as an index into the node list's sorted
  model names (``ClusterArrays.gpu_model`` / ``gpu_models``) and the pods
  the accepted set as a bit word (``PodArrays.gpu_spec``), and the engines
  place such a pod only on a node whose model is in its set
  (``fks_tpu.sim.engine.place_mask_of``). Parsed without the choice a
  workload has neither leaf and compiles to the programs it always had.
- Output is numpy struct-of-arrays (see fks_tpu.data.entities), padded to
  caller-chosen sizes.
"""
from __future__ import annotations

import csv
import dataclasses
import gzip
import io
import json
from pathlib import Path
from typing import Dict, List, Optional, Sequence

import numpy as np

from fks_tpu.data.entities import (
    ClusterArrays, PodArrays, Workload, gpu_model_leaves, gpu_spec_bits)

#: what ``parse_workload`` may do with the pod lists' ``gpu_spec`` column
GPU_SPEC_CHOICES = ("ignore", "honor")

def default_traces_dir() -> Path:
    """benchmarks/traces next to the package root (source checkout), falling
    back to the current working directory (the dataset is repo data, not
    package data -- an installed wheel must point at a checkout or cwd).
    Resolved at CALL time, so an installed package picks up the caller's
    cwd rather than freezing whatever cwd the first import happened in."""
    checkout = Path(__file__).resolve().parent.parent.parent / "benchmarks" / "traces"
    if checkout.is_dir():
        return checkout
    return Path.cwd() / "benchmarks" / "traces"

GPU_MILLI_CAPACITY = 1000  # per-GPU compute capacity (reference: parser.py:45-46)


def _open_text(path: Path):
    """Open a csv that may exist as plain or .gz."""
    if path.exists():
        return open(path, "r", newline="")
    gz = path.with_name(path.name + ".gz")
    if gz.exists():
        return io.TextIOWrapper(gzip.open(gz, "rb"), newline="")
    raise FileNotFoundError(f"{path} (or {gz})")


def _pad_to(n: int, multiple: int) -> int:
    return max(multiple, ((n + multiple - 1) // multiple) * multiple)


def _parse_cpu_milli(v: str) -> int:
    """k8s CPU quantity -> milli-cores: ``64000m`` or bare cores."""
    v = v.strip().strip("'\"")
    if v.endswith("m"):
        return int(v[:-1])
    return int(float(v) * 1000)


def _parse_memory_mib(v: str) -> int:
    """k8s memory quantity -> MiB: ``262144Mi`` plus the Ki/Gi/Ti scales."""
    v = v.strip().strip("'\"")
    for suffix, scale in (("Mi", 1.0), ("Gi", 1024.0), ("Ti", 1024.0 * 1024),
                          ("Ki", 1.0 / 1024)):
        if v.endswith(suffix):
            return int(float(v[: -len(suffix)]) * scale)
    return int(v)


#: node-YAML keys we lift (allocatable block first -> first-seen wins)
_NODE_YAML_KEYS = {
    "alibabacloud.com/gpu-card-model": "model",
    "kubernetes.io/hostname": "hostname",
    "alibabacloud.com/gpu-count": "gpu_count",
    "alibabacloud.com/gpu-milli": "gpu_milli",
    "cpu": "cpu",
    "memory": "memory",
}


def parse_node_yaml(path: str | Path | None = None,
                    traces_dir: str | Path | None = None) -> List[dict]:
    """The FULL OpenB node park (1,213 nodes) from the vendored k8s node
    manifests at ``benchmarks/traces/node_yaml/`` — the large-cluster
    scale tier's real node list (``cli scale --openb-nodes``,
    ``data.synthetic.synthetic_workload(nodes=...)``).

    Returns node dicts in ``fks_tpu.data.build.make_cluster`` schema
    (``node_id``/``cpu_milli``/``memory_mib``/``gpus``/``gpu_memory_mib``)
    in manifest order, which becomes the node index axis like CSV row
    order does for the csv traces. Per-GPU milli capacity is
    ``gpu-milli / gpu-count`` (1000 for every OpenB node); GPU memory
    comes from the same ``gpu_mem_mapping.json`` the CSV parser uses,
    keyed by the ``gpu-card-model`` label (0 for unmapped models,
    matching ``parse_cluster``'s treatment).

    The manifests are flat two-level YAML, parsed with line scanning so
    the loader needs no yaml dependency; files may be gzipped like the
    CSVs. Paths resolve against ``default_traces_dir()`` — repo-root-
    relative, NOT cwd-relative — so ``cli scale`` works from any cwd
    (the dataset lives at ``benchmarks/traces/node_yaml/``)."""
    base = Path(traces_dir) if traces_dir is not None else default_traces_dir()
    if path is None:
        path = base / "node_yaml" / "openb_node_list_gpu_node.yaml"
    with open(base / "gpu_mem_mapping.json") as f:
        gpu_mem = json.load(f)

    nodes: List[dict] = []

    def flush(rec: Dict[str, str]) -> None:
        if "cpu" not in rec:  # blank separator docs
            return
        count = int(rec.get("gpu_count", "0").strip("'\""))
        milli = int(rec.get("gpu_milli", "0").strip("'\""))
        per_gpu = milli // count if count else 0
        nodes.append({
            "node_id": rec.get("hostname", f"openb-node-{len(nodes):04d}"),
            "cpu_milli": _parse_cpu_milli(rec["cpu"]),
            "memory_mib": _parse_memory_mib(rec["memory"]),
            "gpus": [per_gpu] * count,
            "gpu_memory_mib": int(gpu_mem.get(rec.get("model", ""), 0)),
        })

    rec: Dict[str, str] = {}
    with _open_text(Path(path)) as f:
        for line in f:
            stripped = line.strip()
            if stripped.startswith("---"):
                flush(rec)
                rec = {}
                continue
            key, sep, value = stripped.partition(":")
            if not sep:
                continue
            name = _NODE_YAML_KEYS.get(key.strip())
            # first-seen wins: the allocatable block precedes capacity
            if name is not None and value.strip() and name not in rec:
                rec[name] = value.strip()
    flush(rec)
    return nodes


class TraceParser:
    """Parse OpenB dataset traces into array-based simulation inputs.

    API mirrors the reference ``TraceParser`` (benchmarks/parser.py:9-122):
    ``parse_cluster`` / ``parse_pods`` / ``parse_workload`` plus the file
    discovery helpers.
    """

    def __init__(self, traces_dir: str | Path | None = None):
        self.traces_dir = Path(traces_dir) if traces_dir is not None \
            else default_traces_dir()
        self.csv_dir = self.traces_dir / "csv"
        self.gpu_mem_mapping = self._load_gpu_memory_mapping()

    def _load_gpu_memory_mapping(self) -> Dict[str, int]:
        with open(self.traces_dir / "gpu_mem_mapping.json") as f:
            return json.load(f)

    # ---------------------------------------------------------------- nodes
    def parse_cluster(self, node_file: str = "openb_node_list_gpu_node.csv",
                      pad_nodes_to: Optional[int] = None,
                      pad_gpus_to: Optional[int] = None,
                      gpu_models: bool = False) -> ClusterArrays:
        """``gpu_models``: keep each node's ``model`` (``gpu_model``, an
        index into the sorted names, -1 where the column is empty) for a
        workload that honours ``gpu_spec``."""
        rows = self._read_csv(self.csv_dir / node_file)
        node_ids: List[str] = []
        cpu, mem, declared, materialized, gpu_mem = [], [], [], [], []
        models = [row.get("model", "") for row in rows]
        for row in rows:
            node_ids.append(row["sn"])
            cpu.append(int(row["cpu_milli"]))
            mem.append(int(row["memory_mib"]))
            gcount = int(row["gpu"])
            model = row.get("model", "")
            declared.append(gcount)
            if gcount > 0 and model in self.gpu_mem_mapping:
                materialized.append(gcount)
                gpu_mem.append(self.gpu_mem_mapping[model])
            else:
                materialized.append(0)
                gpu_mem.append(0)

        n = len(node_ids)
        g_needed = max(materialized, default=0)
        n_pad = pad_nodes_to or _pad_to(n, 8)
        g_pad = pad_gpus_to or max(1, g_needed)
        if n_pad < n or g_pad < g_needed:
            raise ValueError(f"padding too small: nodes {n}>{n_pad} or gpus {g_needed}>{g_pad}")

        def vec(xs, dtype=np.int32):
            out = np.zeros(n_pad, dtype=dtype)
            out[:n] = xs
            return out

        gpu_mask = np.zeros((n_pad, g_pad), dtype=bool)
        gpu_milli_total = np.zeros((n_pad, g_pad), dtype=np.int32)
        gpu_mem_total = np.zeros((n_pad, g_pad), dtype=np.int32)
        for i in range(n):
            k = materialized[i]
            gpu_mask[i, :k] = True
            gpu_milli_total[i, :k] = GPU_MILLI_CAPACITY
            gpu_mem_total[i, :k] = gpu_mem[i]

        node_mask = np.zeros(n_pad, dtype=bool)
        node_mask[:n] = True

        typed = gpu_model_leaves(models, n_pad) if gpu_models else {}

        return ClusterArrays(
            cpu_total=vec(cpu),
            mem_total=vec(mem),
            gpu_declared=vec(declared),
            num_gpus=vec(materialized),
            gpu_milli_total=gpu_milli_total,
            gpu_mem_total=gpu_mem_total,
            gpu_mask=gpu_mask,
            node_mask=node_mask,
            node_ids=tuple(node_ids),
            **typed,
        )

    # ----------------------------------------------------------------- pods
    def parse_pods(self, pod_file: str = "openb_pod_list_default.csv",
                   pad_pods_to: Optional[int] = None,
                   gpu_models: Optional[Sequence[str]] = None) -> PodArrays:
        """``gpu_models``: the cluster's model names
        (``ClusterArrays.gpu_models``) to read ``gpu_spec`` against; None,
        the default, ignores the column as upstream does."""
        rows = self._read_csv(self.csv_dir / pod_file)
        ids, cpu, mem, ngpu, gmilli, ctime, dur = [], [], [], [], [], [], []
        vocab = None if gpu_models is None else tuple(gpu_models)
        spec = None if vocab is None else [
            gpu_spec_bits(row.get("gpu_spec") or "", vocab) for row in rows]
        for row in rows:
            ids.append(row["name"])
            cpu.append(int(row["cpu_milli"]))
            mem.append(int(row["memory_mib"]))
            ngpu.append(int(row["num_gpu"]))
            gmilli.append(int(row["gpu_milli"]) if row.get("gpu_milli") else 0)
            creation = int(row.get("creation_time") or 0)
            deletion = int(row.get("deletion_time") or 0)
            ctime.append(creation)
            dur.append(deletion - creation)

        p = len(ids)
        p_pad = pad_pods_to or _pad_to(p, 128)
        if p_pad < p:
            raise ValueError(f"padding too small: pods {p}>{p_pad}")

        def vec(xs):
            out = np.zeros(p_pad, dtype=np.int32)
            out[:p] = xs
            return out

        # Rank of pod_id in lexicographic order reproduces the reference's
        # string tie-break (event_simulator.py:16-17) as integer compares.
        order = sorted(range(p), key=lambda i: ids[i])
        rank = np.zeros(p_pad, dtype=np.int32)
        for r, i in enumerate(order):
            rank[i] = r

        pod_mask = np.zeros(p_pad, dtype=bool)
        pod_mask[:p] = True

        return PodArrays(
            cpu=vec(cpu), mem=vec(mem), num_gpu=vec(ngpu), gpu_milli=vec(gmilli),
            creation_time=vec(ctime), duration=vec(dur), tie_rank=rank,
            pod_mask=pod_mask, pod_ids=tuple(ids),
            gpu_spec=None if spec is None else vec(spec),
        )

    # ------------------------------------------------------------- combined
    def parse_workload(self, node_file: str = "gpu_models_filtered.csv",
                       pod_file: str = "openb_pod_list_default.csv",
                       pad_nodes_to: Optional[int] = None,
                       pad_gpus_to: Optional[int] = None,
                       pad_pods_to: Optional[int] = None,
                       snapshot_file: Optional[str] = None,
                       gpu_spec: str = "ignore") -> Workload:
        """Defaults match the reference benchmark workload (parser.py:117-118).
        ``gpu_spec="honor"`` keeps the pods' GPU-type constraints and the
        nodes' models (module docstring); the default ignores them.
        ``snapshot_file`` (a CSV beside the traces,
        ``fks_tpu.data.snapshot``) pins a moment of its run: an engine
        then starts after the snapshot's events. An invalid snapshot
        raises ``ValueError`` here."""
        if gpu_spec not in GPU_SPEC_CHOICES:
            raise ValueError(f"gpu_spec: {gpu_spec!r} is none of "
                             f"{GPU_SPEC_CHOICES}")
        honor = gpu_spec == "honor"
        cluster = self.parse_cluster(node_file, pad_nodes_to, pad_gpus_to,
                                     gpu_models=honor)
        pods = self.parse_pods(pod_file, pad_pods_to,
                               gpu_models=cluster.gpu_models if honor
                               else None)
        wl = Workload(cluster=cluster, pods=pods)
        if snapshot_file:
            from fks_tpu.data.snapshot import load_snapshot
            wl = dataclasses.replace(wl, snapshot=load_snapshot(
                self.csv_dir / snapshot_file, wl))
        return wl

    # ------------------------------------------------------------ discovery
    def get_available_node_files(self) -> List[str]:
        return sorted({f.name.removesuffix(".gz")
                       for f in self.csv_dir.glob("openb_node_list_*.csv*")})

    def get_available_pod_files(self) -> List[str]:
        return sorted({f.name.removesuffix(".gz")
                       for f in self.csv_dir.glob("openb_pod_list_*.csv*")})

    @staticmethod
    def _read_csv(path: Path) -> List[dict]:
        with _open_text(path) as f:
            return list(csv.DictReader(f))
