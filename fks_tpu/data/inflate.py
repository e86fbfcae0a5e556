"""Monte-Carlo workload inflation: a pod list drawn up to a share of a
cluster's GPU capacity.

The shipped OpenB pod lists replayed with their own timestamps never load
the real 1,523-node cluster (the default list peaks at 65.6 of 6,212 GPUs
requested at once), so a policy evaluated there sees an empty cluster. The
evaluation set-up of Weng et al., "Beware of Fragmentation" (USENIX
ATC'23), inflates instead: tasks are sampled from the list WITH
replacement and submitted in sample order until the arrived GPU request
reaches a stated share of the cluster's GPU capacity.

``inflate_pods`` is that draw, a pure function of (cluster, list, share,
seed). ATC'23 ignores durations; this simulator needs one, so every pod
holds for as long as the whole arrival phase lasts (``duration = number of
pods``): the cluster fills, then drains in arrival order. Arrival ``i``
is created at second ``i`` and named so that the names' lexicographic
order (the engines' equal-time tie-break) is the arrival order.

``write_pods_csv_gz`` writes the result in the pod lists' own schema,
byte for byte reproducible (gzip mtime 0), so that it is committed and
hash-pinned like every other trace and read through the ``--nodes`` /
``--trace`` every entry point already has. ``gpu_spec`` is the sampled
source row's own (empty where the source's is, so a list drawn from one
without constraints is written as it always was); the columns no parse
reads (``qos``, ``pod_phase``) are written empty. Two draws from lists
that differ in ``gpu_spec`` alone make the same picks: the draw reads the
request columns only.

    python -m fks_tpu.data.inflate   # rewrites the committed lists
"""
from __future__ import annotations

import gzip
import io
from pathlib import Path

import numpy as np

from fks_tpu.data.build import make_pods
from fks_tpu.data.entities import ClusterArrays, PodArrays
from fks_tpu.data.traces import GPU_MILLI_CAPACITY, _pad_to

#: the pod lists' header (benchmarks/traces/csv/openb_pod_list_*.csv)
POD_COLUMNS = ("name", "cpu_milli", "memory_mib", "num_gpu", "gpu_milli",
               "gpu_spec", "qos", "pod_phase", "creation_time",
               "deletion_time", "scheduled_time")


def sample_arrivals(request_milli: np.ndarray, capacity_milli: int,
                    share: float, seed: int) -> np.ndarray:
    """Indices into the list, drawn one at a time with replacement until
    the summed GPU request reaches ``share`` x capacity (so the target is
    passed by less than one pod's request)."""
    request_milli = np.asarray(request_milli, np.int64)
    if share <= 0 or request_milli.sum() <= 0:
        raise ValueError("inflation needs share > 0 and a list that "
                         "requests GPUs")
    target = share * capacity_milli
    rng = np.random.default_rng(seed)
    picks, arrived = [], 0
    while arrived < target:
        i = int(rng.integers(len(request_milli)))
        picks.append(i)
        arrived += int(request_milli[i])
    return np.asarray(picks, np.int64)


def arrival_picks(cluster: ClusterArrays, pods: PodArrays, share: float,
                  seed: int) -> np.ndarray:
    """The draw: for each arrival, the index of its source pod among the
    list's real pods (input order)."""
    real = np.flatnonzero(np.asarray(pods.pod_mask))
    ngpu, milli = (np.asarray(x, np.int64)[real]
                   for x in (pods.num_gpu, pods.gpu_milli))
    capacity = int(np.asarray(cluster.num_gpus, np.int64).sum()) \
        * GPU_MILLI_CAPACITY
    return sample_arrivals(ngpu * milli, capacity, share, seed)


def inflate_pods(cluster: ClusterArrays, pods: PodArrays, share: float,
                 seed: int) -> PodArrays:
    """The inflated arrival list as ``PodArrays``, padded as the CSV
    parser pads; ``tie_rank`` comes from the names, as it does there. A
    list parsed to honour ``gpu_spec`` keeps each source pod's set."""
    real = np.flatnonzero(np.asarray(pods.pod_mask))
    cpu, mem, ngpu, milli = (np.asarray(x, np.int64)[real] for x in (
        pods.cpu, pods.mem, pods.num_gpu, pods.gpu_milli))
    src = arrival_picks(cluster, pods, share, seed)
    n = len(src)
    width = max(4, len(str(n - 1)))
    typed = pods.gpu_spec is not None
    spec = np.asarray(pods.gpu_spec)[real] if typed else None
    return make_pods(
        [{"pod_id": f"inflated-pod-{i:0{width}d}", "cpu_milli": cpu[j],
          "memory_mib": mem[j], "num_gpu": ngpu[j], "gpu_milli": milli[j],
          "creation_time": i, "duration_time": n,
          **({"gpu_spec": spec[j]} if typed else {})}
         for i, j in enumerate(src)], pad_pods_to=_pad_to(n, 128),
        gpu_models=cluster.gpu_models if typed else None)


def pods_csv(pods: PodArrays, gpu_spec=()) -> str:
    """The pod list as CSV text in the shipped lists' schema;
    ``gpu_spec``: the column's text per pod (none: empty)."""
    out = io.StringIO()
    out.write(",".join(POD_COLUMNS) + "\n")
    cols = [np.asarray(x) for x in (pods.cpu, pods.mem, pods.num_gpu,
                                    pods.gpu_milli, pods.creation_time,
                                    pods.duration)]
    for i, name in enumerate(pods.pod_ids):
        cpu, mem, ngpu, milli, t, dur = (int(c[i]) for c in cols)
        spec = gpu_spec[i] if len(gpu_spec) else ""
        out.write(f"{name},{cpu},{mem},{ngpu},{milli},{spec},,,{t},"
                  f"{t + dur},{t}\n")
    return out.getvalue()


def write_pods_csv_gz(pods: PodArrays, path, gpu_spec=()) -> None:
    """gzip with mtime 0 and no file name in the header: the same pods
    give the same bytes, so the file can be pinned by its hash."""
    with open(path, "wb") as raw, gzip.GzipFile(
            filename="", mode="wb", fileobj=raw, mtime=0) as gz:
        gz.write(pods_csv(pods, gpu_spec).encode())


#: the committed inflated lists: (file, node list, pod list, share, seed)
INFLATED080 = ("openb_pod_list_inflated080.csv",
               "openb_node_list_all_node.csv",
               "openb_pod_list_default.csv", 0.80, 0)
#: the same draw from the list whose GPU pods name their models (24.9 %)
GPUSPEC25_INFLATED080 = ("openb_pod_list_gpuspec25_inflated080.csv",
                         "openb_node_list_all_node.csv",
                         "openb_pod_list_gpuspec25.csv", 0.80, 0)
COMMITTED = (INFLATED080, GPUSPEC25_INFLATED080)


def write_inflated(parser, spec, directory=None) -> Path:
    """Draw one committed list and write it under ``directory`` (the
    parser's CSV directory by default), ``gpu_spec`` from the source
    rows. Returns the file's path."""
    name, node_file, pod_file, share, seed = spec
    cluster, source = parser.parse_cluster(node_file), \
        parser.parse_pods(pod_file)
    column = [r.get("gpu_spec") or ""
              for r in parser._read_csv(parser.csv_dir / pod_file)]
    picks = arrival_picks(cluster, source, share, seed)
    path = Path(directory or parser.csv_dir) / (name + ".gz")
    write_pods_csv_gz(inflate_pods(cluster, source, share, seed), path,
                      [column[j] for j in picks])
    return path


def main() -> None:
    from fks_tpu.data.traces import TraceParser

    parser = TraceParser()
    for spec in COMMITTED:
        path = write_inflated(parser, spec)
        print(f"{path}: {parser.parse_pods(spec[0]).num_pods} pods")


if __name__ == "__main__":
    main()
