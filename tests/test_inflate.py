"""The inflated arrival workload (fks_tpu.data.inflate): a function of
(cluster, list, share, seed), committed as a hash-pinned trace."""
import gzip
import hashlib

import numpy as np
import pytest

from fks_tpu.data import TraceParser
from fks_tpu.data import inflate
from fks_tpu.data.traces import GPU_MILLI_CAPACITY

NAME, NODE_FILE, POD_FILE, SHARE, SEED = inflate.INFLATED080


@pytest.fixture(scope="module")
def parser():
    return TraceParser()


@pytest.fixture(scope="module")
def cluster(parser):
    return parser.parse_cluster(NODE_FILE)


@pytest.fixture(scope="module")
def source(parser):
    return parser.parse_pods(POD_FILE)


@pytest.fixture(scope="module")
def inflated(cluster, source):
    return inflate.inflate_pods(cluster, source, SHARE, SEED)


def _arrays(p):
    return [np.asarray(x) for x in (p.cpu, p.mem, p.num_gpu, p.gpu_milli,
                                    p.creation_time, p.duration, p.tie_rank,
                                    p.pod_mask)]


def test_generator_is_a_function_of_its_inputs(cluster, source, inflated):
    again = inflate.inflate_pods(cluster, source, SHARE, SEED)
    assert again.pod_ids == inflated.pod_ids
    for a, b in zip(_arrays(again), _arrays(inflated)):
        np.testing.assert_array_equal(a, b)
    other_seed = inflate.inflate_pods(cluster, source, SHARE, SEED + 1)
    assert not np.array_equal(np.asarray(other_seed.cpu)[:64],
                              np.asarray(inflated.cpu)[:64])
    smaller = inflate.inflate_pods(cluster, source, 0.40, SEED)
    assert smaller.num_pods < inflated.num_pods
    # the same draw, stopped earlier
    np.testing.assert_array_equal(
        np.asarray(smaller.cpu)[:smaller.num_pods],
        np.asarray(inflated.cpu)[:smaller.num_pods])


def test_committed_trace_is_what_the_generator_writes(parser, inflated,
                                                      tmp_path):
    path = parser.csv_dir / (NAME + ".gz")
    with gzip.open(path, "rb") as f:
        committed = f.read()
    assert committed == inflate.pods_csv(inflated).encode()
    # written again, the gzip itself is the same bytes (mtime 0, no name)
    inflate.write_pods_csv_gz(inflated, tmp_path / "again.gz")
    with open(path, "rb") as f, open(tmp_path / "again.gz", "rb") as g:
        assert hashlib.sha256(f.read()).hexdigest() == \
            hashlib.sha256(g.read()).hexdigest()


@pytest.mark.parametrize("share", [0.40, SHARE])
def test_share_is_reached_and_passed_by_less_than_one_pod(cluster, source,
                                                          share):
    pods = inflate.inflate_pods(cluster, source, share, SEED)
    n = pods.num_pods
    request = (np.asarray(pods.num_gpu, np.int64)
               * np.asarray(pods.gpu_milli, np.int64))[:n]
    target = share * int(np.asarray(cluster.num_gpus).sum()) \
        * GPU_MILLI_CAPACITY
    assert request.sum() >= target
    assert request.sum() - request[-1] < target
    if share == SHARE:
        assert n == 6695      # ISSUE 26's sizing table


def test_arrival_order_names_and_holding_time(inflated):
    n = inflated.num_pods
    ids = list(inflated.pod_ids)
    assert sorted(ids) == ids and len(set(ids)) == n
    np.testing.assert_array_equal(np.asarray(inflated.creation_time)[:n],
                                  np.arange(n))
    np.testing.assert_array_equal(np.asarray(inflated.tie_rank)[:n],
                                  np.arange(n))
    assert (np.asarray(inflated.duration)[:n] == n).all()
    assert not np.asarray(inflated.pod_mask)[n:].any()


def test_both_parsers_read_the_same_pods(parser, inflated):
    from chipbench.reference import data

    mine = parser.parse_pods(NAME)
    assert mine.pod_ids == inflated.pod_ids
    for a, b in zip(_arrays(mine), _arrays(inflated)):
        np.testing.assert_array_equal(a, b)
    ref = data.load_pods(str(parser.csv_dir / (NAME + ".gz")))
    n = mine.num_pods
    assert ref.p == n
    for theirs, ours in ((ref.cpu, mine.cpu), (ref.mem, mine.mem),
                         (ref.num_gpu, mine.num_gpu),
                         (ref.gpu_milli, mine.gpu_milli),
                         (ref.creation_time, mine.creation_time),
                         (ref.duration, mine.duration),
                         (ref.rank, mine.tie_rank)):
        np.testing.assert_array_equal(theirs, np.asarray(ours)[:n])


def test_rejects_a_list_without_gpu_requests(cluster, source):
    import dataclasses

    cpu_only = dataclasses.replace(
        source, num_gpu=np.zeros_like(np.asarray(source.num_gpu)))
    with pytest.raises(ValueError, match="requests GPUs"):
        inflate.inflate_pods(cluster, cpu_only, SHARE, SEED)
