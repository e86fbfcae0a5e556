"""Trace-parser parity: totals and structure match the reference dataset facts.

Ground truth from SURVEY.md §2 fine print 11-12 and the recorded fixtures:
16 nodes / 48 GPUs / 48,000 gpu_milli; 8,152 pods (7,064 GPU pods).
"""
import numpy as np

from fks_tpu.data import TraceParser


def test_default_workload_totals(default_workload):
    wl = default_workload
    assert wl.num_nodes == 16
    assert wl.num_pods == 8152
    totals = wl.cluster.totals()
    # NOTE: SURVEY.md says 48 GPUs but running the reference gives 64
    # (10x2 P100 + 8 G3 + 2x8 V100M32 + 4 V100M16 + 2x8 G2 = 64).
    assert totals["gpu_count"] == 64
    assert totals["gpu_milli"] == 64_000
    ngpu = np.asarray(wl.pods.num_gpu)[np.asarray(wl.pods.pod_mask)]
    assert int((ngpu > 0).sum()) == 7064
    # num_gpu distribution (SURVEY fine print 11)
    vals, counts = np.unique(ngpu, return_counts=True)
    dist = dict(zip(vals.tolist(), counts.tolist()))
    assert dist == {0: 1088, 1: 6989, 2: 16, 4: 15, 8: 44}


def test_padding_masks(default_workload):
    wl = default_workload
    c, p = wl.cluster, wl.pods
    assert c.node_mask.sum() == 16
    assert p.pod_mask.sum() == 8152
    # padded slots contribute nothing
    assert c.cpu_total[~c.node_mask].sum() == 0
    assert c.gpu_milli_total[~c.gpu_mask].sum() == 0
    assert p.cpu[~p.pod_mask].sum() == 0


def test_tie_rank_matches_lexicographic_order(default_workload):
    p = default_workload.pods
    ids = list(p.pod_ids)
    rank = np.asarray(p.tie_rank)[: len(ids)]
    order_by_rank = [ids[i] for i in np.argsort(rank)]
    assert order_by_rank == sorted(ids)


def test_gpu_memory_mapping_applied(default_workload):
    c = default_workload.cluster
    # gpu_models_filtered.csv row 0 is a 2-GPU P100 node (16280 MiB per GPU)
    assert c.gpu_mem_total[0, 0] == 16280
    assert c.num_gpus[0] == 2


def test_node_and_pod_file_discovery():
    parser = TraceParser()
    # matches reference glob semantics (parser.py:103-115): openb_* only
    assert parser.get_available_node_files() == [
        "openb_node_list_all_node.csv", "openb_node_list_gpu_node.csv"]
    # 23 of OpenB + the two inflated lists (default, gpuspec25)
    assert len(parser.get_available_pod_files()) == 25


def test_duration_derivation(default_workload):
    p = default_workload.pods
    # pod 0: creation 0, deletion 12537496 (CSV row 1)
    assert int(p.creation_time[0]) == 0
    assert int(p.duration[0]) == 12537496


def test_multigpu_trace_parses_with_defaults():
    # The reference parser crashes on these (missing columns); we accept them.
    pods = TraceParser().parse_pods("openb_pod_list_multigpu50.csv")
    assert pods.num_pods > 0
    assert int(np.asarray(pods.creation_time).max()) == 0
