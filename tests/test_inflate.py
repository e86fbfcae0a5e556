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


# --------------------- the list whose pods name their GPU models (PR 45)

SHA256 = {
    "openb_pod_list_inflated080.csv":
        "9bfa42cf16e4958a5ef7c9a8bb54cb9b435e35999c7aad36045e5b74147d584c",
    "openb_pod_list_gpuspec25_inflated080.csv":
        "45610eb37086497b3af4168f324d086e65be883203518d7b6c9c7b0d3af922fe",
}


@pytest.mark.parametrize("spec", inflate.COMMITTED, ids=lambda s: s[0])
def test_the_command_rewrites_each_committed_list_byte_for_byte(
        parser, spec, tmp_path):
    """``python -m fks_tpu.data.inflate`` writes both lists; the one
    three accepted configurations pin keeps its bytes although the writer
    now fills ``gpu_spec`` from the source row (the default list's is
    empty everywhere)."""
    again = inflate.write_inflated(parser, spec, tmp_path)
    with open(again, "rb") as f, \
            open(parser.csv_dir / (spec[0] + ".gz"), "rb") as g:
        got, want = f.read(), g.read()
    assert got == want
    assert hashlib.sha256(want).hexdigest() == SHA256[spec[0]]


def test_the_typed_list_is_the_same_draw_with_the_source_rows_gpu_spec(
        parser, cluster, source, inflated):
    name, node_file, pod_file, share, seed = inflate.GPUSPEC25_INFLATED080
    assert (node_file, share, seed) == (NODE_FILE, SHARE, SEED)
    # the draw reads the request columns, which the two sources share
    typed_source = parser.parse_pods(pod_file)
    picks = inflate.arrival_picks(cluster, typed_source, share, seed)
    np.testing.assert_array_equal(
        picks, inflate.arrival_picks(cluster, source, SHARE, SEED))
    mine = parser.parse_pods(name)
    assert mine.pod_ids == inflated.pod_ids and mine.gpu_spec is None
    for a, b in zip(_arrays(mine), _arrays(inflated)):
        np.testing.assert_array_equal(a, b)
    # the column is the sampled source row's own
    raw = [r["gpu_spec"] for r in parser._read_csv(parser.csv_dir / pod_file)]
    got = [r["gpu_spec"] for r in parser._read_csv(parser.csv_dir / name)]
    assert got == [raw[j] for j in picks]
    assert sum(1 for s in got if s) == 1375
    assert "V100M16|V100M32|V100M32" in got      # written as the source has it
    # and a list parsed to honour it keeps each source pod's set through
    # the draw
    models = parser.parse_cluster(node_file, gpu_models=True)
    drawn = inflate.inflate_pods(
        models, parser.parse_pods(pod_file, gpu_models=models.gpu_models),
        share, seed)
    read = parser.parse_pods(name, gpu_models=models.gpu_models)
    np.testing.assert_array_equal(drawn.gpu_spec, read.gpu_spec)
    assert int(np.count_nonzero(np.asarray(read.gpu_spec))) == 1375
    # no pod without a GPU carries one
    assert not np.asarray(read.gpu_spec)[np.asarray(read.num_gpu) == 0].any()
