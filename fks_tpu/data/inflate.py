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
``--trace`` every entry point already has. Columns the simulator does not
read (``gpu_spec``, ``qos``, ``pod_phase``) are written empty.

    python -m fks_tpu.data.inflate   # rewrites openb_pod_list_inflated080
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


def inflate_pods(cluster: ClusterArrays, pods: PodArrays, share: float,
                 seed: int) -> PodArrays:
    """The inflated arrival list as ``PodArrays``, padded as the CSV
    parser pads; ``tie_rank`` comes from the names, as it does there."""
    real = np.flatnonzero(np.asarray(pods.pod_mask))
    cpu, mem, ngpu, milli = (np.asarray(x, np.int64)[real] for x in (
        pods.cpu, pods.mem, pods.num_gpu, pods.gpu_milli))
    capacity = int(np.asarray(cluster.num_gpus, np.int64).sum()) \
        * GPU_MILLI_CAPACITY
    src = sample_arrivals(ngpu * milli, capacity, share, seed)
    n = len(src)
    width = max(4, len(str(n - 1)))
    return make_pods(
        [{"pod_id": f"inflated-pod-{i:0{width}d}", "cpu_milli": cpu[j],
          "memory_mib": mem[j], "num_gpu": ngpu[j], "gpu_milli": milli[j],
          "creation_time": i, "duration_time": n}
         for i, j in enumerate(src)], pad_pods_to=_pad_to(n, 128))


def pods_csv(pods: PodArrays) -> str:
    """The pod list as CSV text in the shipped lists' schema."""
    out = io.StringIO()
    out.write(",".join(POD_COLUMNS) + "\n")
    cols = [np.asarray(x) for x in (pods.cpu, pods.mem, pods.num_gpu,
                                    pods.gpu_milli, pods.creation_time,
                                    pods.duration)]
    for i, name in enumerate(pods.pod_ids):
        cpu, mem, ngpu, milli, t, dur = (int(c[i]) for c in cols)
        out.write(f"{name},{cpu},{mem},{ngpu},{milli},,,,{t},{t + dur},"
                  f"{t}\n")
    return out.getvalue()


def write_pods_csv_gz(pods: PodArrays, path) -> None:
    """gzip with mtime 0 and no file name in the header: the same pods
    give the same bytes, so the file can be pinned by its hash."""
    with open(path, "wb") as raw, gzip.GzipFile(
            filename="", mode="wb", fileobj=raw, mtime=0) as gz:
        gz.write(pods_csv(pods).encode())


#: the committed inflated list: (file, node list, pod list, share, seed)
INFLATED080 = ("openb_pod_list_inflated080.csv",
               "openb_node_list_all_node.csv",
               "openb_pod_list_default.csv", 0.80, 0)


def main() -> None:
    from fks_tpu.data.traces import TraceParser

    name, node_file, pod_file, share, seed = INFLATED080
    parser = TraceParser()
    pods = inflate_pods(parser.parse_cluster(node_file),
                        parser.parse_pods(pod_file), share, seed)
    path = Path(parser.csv_dir) / (name + ".gz")
    write_pods_csv_gz(pods, path)
    print(f"{path}: {pods.num_pods} pods")


if __name__ == "__main__":
    main()
