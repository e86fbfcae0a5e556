"""The lowering pool (``fks_tpu.funsearch.lower_pool``, ISSUE 39): a
generation's sources lowered side by side by worker processes give what
the in-process loop gives, bit for bit and record for record; the pool is
one a process, adapts to the cores it sees, never makes a generation wait
for its start, survives a lost worker and never outlives its parent.

``conftest.py`` caps a pytest process at two workers; the tests that need
the pool to engage tell it there are cores for that (``usable_cores``),
whatever this machine has, and wait for its workers themselves (``up``).
"""
import json
import os
import struct
import subprocess
import sys
import threading
import time

import jax
import numpy as np
import pytest

from fks_tpu.funsearch import backend, lower_pool, transpiler, vm
from fks_tpu.obs import spans
from tests import lowering_corpus as corpus

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def up(n: int = 2, within: float = 120.0) -> list:
    """The live pool's workers once ``n`` of them are through their
    start: a generation does not wait for them, a test has to."""
    deadline = time.perf_counter() + within
    while len(lower_pool.workers()) < n and time.perf_counter() < deadline:
        time.sleep(0.05)
    ws = lower_pool.workers()
    assert len(ws) == n, ws
    return ws


@pytest.fixture
def cores(monkeypatch):
    """A host with four usable cores and the process's pool (two workers,
    ``conftest.py``) up."""
    monkeypatch.setattr(lower_pool, "usable_cores", lambda: 4)
    lower_pool.start(16, 8)
    up()


def bits(x):
    """``x`` with every float as its eight bytes: -0.0, NaN and the last
    digit all tell."""
    if isinstance(x, float):
        return struct.pack("<d", x)
    if isinstance(x, (list, tuple)):
        return tuple(bits(v) for v in x)
    return x


def words(prog):
    """A hash of a program's leaves (dtype, shape, bytes), or None."""
    return prog and corpus.program_hash(prog)


def same(a: lower_pool.Lowered, b: lower_pool.Lowered) -> bool:
    return (bits(a.kept) == bits(b.kept)
            and (a.ops_lowered, a.traces) == (b.ops_lowered, b.traces)
            and type(a.error) is type(b.error)
            and str(a.error) == str(b.error)
            and (a.key, a.fingerprint, a.work, a.rejection)
            == (b.key, b.fingerprint, b.work, b.rejection)
            and words(a.words) == words(b.words))


def transpile_span(mark: int):
    (sp,) = [r for r in spans.LOG.snapshot()[mark:]
             if r.name == "tier/transpile"]
    return sp.fields


CHAMPION = "champion:20260801_045536_score0.5365"


def mixed_records(capacity: int, monkeypatch):
    """``corpus.MIXED`` and a ledger champion (220 ops at 4 x 4, the 256
    bucket) as one generation on the batched tier with the op budget at
    ``capacity``: every field of every record (the result as a hash of its
    leaves), how the generation was served, and the stage's span fields."""
    monkeypatch.setattr(backend.CodeEvaluator, "VM_CAPACITY", capacity)
    src = corpus.sources()
    codes = [src[n] for n in corpus.MIXED + (CHAMPION,)]
    ev = backend.CodeEvaluator(corpus.mixed_workload(), vm_batch=True,
                               preflight=False)
    mark = len(spans.LOG.snapshot())
    recs = [(r.code, r.score, r.error, r.result and corpus._hash_leaves(
        (f, leaf) for f, leaf in zip(r.result._fields, r.result)
        if leaf is not None)) for r in ev.evaluate(codes)]
    served = {k: ev.last_eval_stats[k]
              for k in ("vm_batch_lanes", "fallback_lanes", "unique")}
    return recs, served, transpile_span(mark)


# ----------------------------------------------- (a) pooled == in process

@pytest.mark.parametrize("shape,x64,whole", [
    ((16, 8), False, True), ((16, 8), True, False),
    ((1528, 8), False, False), ((1528, 8), True, True)],
    ids=("16x8-f32-corpus", "16x8-x64", "1528x8-f32", "1528x8-x64-corpus"))
def test_pooled_lowering_is_the_in_process_lowering(shape, x64, whole,
                                                    cores):
    """The 13 ledger champions and the seed policies at both shapes in
    both precisions, the whole lowering corpus in two of the four:
    ``(ops, consts, out_reg)``, the counts, the exception's class and
    message, the key, and every array of the packed program: the words
    that came back are the ones ``vm.pack_program`` uploads."""
    named = {k: v for k, v in corpus.sources().items()
             if whole or k.startswith(("champion:", "seed:"))}
    codes = list(named.values())
    with jax.enable_x64(x64):
        got, stats = lower_pool.lower_all(codes, *shape)
        want = [lower_pool.lower_source(c, *shape) for c in codes]
        assert stats["pooled"] == len(codes) and stats["workers"] == 2
        lowered = 0
        for name, g, w in zip(named, got, want):
            assert same(g, w), name
            if w.kept is None:
                continue
            lowered += 1
            pg, pw = vm.pack_program(*g.kept), vm.pack_program(*w.kept)
            assert pg.capacity == pw.capacity \
                == vm.capacity_bucket(len(w.kept[0])), name
            assert int(pg.n_ops) == int(pw.n_ops) == len(w.kept[0]), name
            assert corpus.program_hash(pg) == corpus.program_hash(pw) \
                == corpus.program_hash(g.words), name
            assert vm._on_host(g.words) and not vm._on_host(pg)
            assert pg.imm.dtype == g.words.imm.dtype \
                == (np.float64 if x64 else np.float32)
        # every champion and seed policy lowers, the violations do not
        assert (80 < lowered < len(codes)) if whole \
            else (13 < lowered == len(codes))


def checked_sources():
    """The champions and seed policies (they lower) and every source the
    static pre-flight or the parser stops (they must not)."""
    from fks_tpu import analysis

    src = corpus.echo_sources()
    return {k: v for k, v in src.items()
            if k.startswith(("champion:", "seed:", "twin:", "key_echo:"))
            or not analysis.preflight_check(v).ok}


@pytest.mark.parametrize("shape,x64", [
    ((16, 8), False), ((16, 8), True), ((1528, 8), False),
    ((1528, 8), True)],
    ids=("16x8-f32", "16x8-x64", "1528x8-f32", "1528x8-x64"))
def test_pooled_check_is_the_in_process_check(shape, x64, cores):
    """With the checks an evaluator asks for: the key, the fingerprint,
    the static work, the rejection and the packed words of every source
    are the same from a worker and from this process, and a source that
    is stopped is lowered by neither."""
    named = checked_sources()
    asked = [lower_pool.Source(c, True, True) for c in named.values()]
    with jax.enable_x64(x64):
        got, stats = lower_pool.lower_all(asked, *shape)
        eqns = vm.eqns_traced()
        want = [lower_pool.lower_source(a, *shape) for a in asked]
        traced_here = vm.eqns_traced() - eqns
    assert stats["pooled"] == len(asked) and stats["workers"] == 2
    stopped = 0
    for name, g, w in zip(named, got, want):
        assert same(g, w), name
        assert g.sent is not None and w.sent is None
        assert g.pid != w.pid == os.getpid()
        if w.rejection is not None:
            stopped += 1
            assert (w.kept, w.words, w.key, w.error) == (None,) * 4, name
            assert (w.traces, w.eqns, g.traces, g.eqns) == (0,) * 4, name
            assert w.t_checked == w.t_traced == w.t1
        else:
            assert w.key == transpiler.canonical_key(named[name])
            assert w.fingerprint and w.work > 0, name
            assert w.words.imm.dtype == (np.float64 if x64 else np.float32)
            assert w.words.opcode.dtype == w.words.n_ops.dtype == np.int32
            assert w.words.capacity == vm.capacity_bucket(len(w.kept[0]))
            assert int(w.words.n_ops) == len(w.kept[0])
    assert stopped > 20 and len(asked) - stopped >= 17
    # only the sources that got through the check were traced at all
    assert traced_here == sum(w.eqns for w in want)
    # text and key differ, the program does not
    by = dict(zip(named, want))
    assert by["key_echo:best_fit"].key == by["seed:best_fit"].key
    assert by["twin:a"].fingerprint == by["twin:b"].fingerprint \
        and by["twin:a"].key != by["twin:b"].key


@pytest.mark.parametrize("pooled", (1, 0), ids=("pooled", "in_process"))
@pytest.mark.parametrize("mode", corpus.ECHO_MODES,
                         ids=lambda m: "preflight=%d,fp_dedup=%d" % m)
def test_a_generation_of_echoes_and_rejections_is_the_parents(
        mode, pooled, cores, monkeypatch):
    """An exact echo, a canonical-key echo, fingerprint twins, a syntax
    error, a doomed source twice, a subset violation and a VMUnsupported
    source in one generation: the records, the counters, ``last_eval_
    stats``, the ``candidate_rejected`` events in order and the lanes of
    the launch in order are what PR 51's parent gave, whose evaluator
    checked and keyed every source itself (``tests/fixtures/echo_
    generation.json``), wherever the sources are checked now."""
    with open(os.path.join(ROOT, "tests", "fixtures",
                           "echo_generation.json")) as f:
        want = json.load(f)["preflight=%d,fp_dedup=%d" % mode]
    monkeypatch.setattr(lower_pool, "usable_cores",
                        lambda: 4 if pooled else 1)
    mark = len(spans.LOG.snapshot())
    got = json.loads(json.dumps(corpus.echo_generation(*mode)))
    assert got == want
    new = spans.LOG.snapshot()[mark:]
    fields = transpile_span(mark)
    texts = len(set(corpus.ECHOES))
    checks = [r for r in new if r.name == "tier/transpile/check"]
    assert len(checks) == texts
    assert {r.fields["pooled"] for r in checks} == {pooled}
    # every source that got through its check was lowered once, an echo
    # of a key or a fingerprint too; the stage counts the representatives
    ok = sum(r.fields["ok"] for r in checks)
    assert ok == texts - (3 if mode[0] else 1)
    assert fields["traces"] == ok
    assert fields["sources"] == want["stats"]["unique"]
    assert fields["pooled"] == pooled * fields["sources"]
    assert len([r for r in new if r.name == "tier/transpile/lower"]) == ok


def test_the_unbatched_tier_checks_in_process_and_lowers_for_itself(
        cores, monkeypatch):
    """An evaluator that does not batch needs the check alone: no task
    goes to the pool, every text leaves its check span, none a lower
    span, and the records are the batched tier's."""
    monkeypatch.setattr(lower_pool, "lower_all", None)   # must not be called
    src = corpus.echo_sources()
    codes = [src[n] for n in corpus.ECHOES]
    ev = backend.CodeEvaluator(corpus.mixed_workload(), vm_batch=False)
    mark = len(spans.LOG.snapshot())
    recs = ev.evaluate(codes)
    new = spans.LOG.snapshot()[mark:]
    fields = transpile_span(mark)
    assert (fields["sources"], fields["pooled"], fields["workers"],
            fields["traces"]) == (7, 0, 0, 0)
    checks = [r for r in new if r.name == "tier/transpile/check"]
    assert len(checks) == len(set(codes))
    assert {r.fields["pid"] for r in checks} == {os.getpid()}
    assert not [r for r in new if r.name == "tier/transpile/lower"]
    with open(os.path.join(ROOT, "tests", "fixtures",
                           "echo_generation.json")) as f:
        want = json.load(f)["preflight=1,fp_dedup=1"]
    assert [(r.score, r.error) for r in recs] \
        == [(w["score"], w["error"]) for w in want["records"]]
    assert ev.preflight_rejected == want["preflight_rejected"]
    assert ev.preflight_duplicates == want["preflight_duplicates"]


# ------------------- the programs stay on the host until they are stacked

def program_pairs(x64: bool):
    """Three programs of two capacity buckets (126 ops, 238 and 201), as
    NumPy words and as device arrays."""
    src = corpus.sources()
    names = ("seed:first_fit", CHAMPION, "block:gpu_loop_if")
    with jax.enable_x64(x64):
        kept = [lower_pool.lower_source(src[n], 16, 8).kept for n in names]
        return ([vm.pack_words(*k) for k in kept],
                [vm.pack_program(*k) for k in kept])


@pytest.mark.parametrize("capacity", (None, 512, 1024),
                         ids=("own_bucket", "cap512", "cap1024"))
@pytest.mark.parametrize("x64", (False, True), ids=("f32", "x64"))
def test_a_stack_of_numpy_programs_is_the_stack_of_device_programs(
        x64, capacity):
    host, device = program_pairs(x64)
    assert len({p.capacity for p in host}) > 1     # the stack has to pad
    with jax.enable_x64(x64):
        a = vm.stack_programs(host, capacity)
        b = vm.stack_programs(device, capacity)
        mixed = vm.stack_programs([host[0], *device[1:]], capacity)
    assert vm._on_host(a) and not vm._on_host(b) and not vm._on_host(mixed)
    for leaf, x, y, z in zip(a._fields, a, b, mixed):
        assert isinstance(y, jax.Array) and isinstance(z, jax.Array), leaf
        assert x.dtype == y.dtype == z.dtype, leaf
        assert x.shape == y.shape == z.shape, leaf
        assert x.tobytes() == np.asarray(y).tobytes() \
            == np.asarray(z).tobytes(), leaf
    want = capacity or max(32, 1 << (max(
        int(p.n_ops) for p in host) - 1).bit_length())
    assert a.opcode.shape == (3, want) and a.n_ops.shape == (3,)
    assert a.imm.dtype == (np.float64 if x64 else np.float32)
    # one program, re-padded up and cut down: the same words either way
    for p, q in zip(host, device):
        for cap in (p.capacity * 2, vm.capacity_bucket(int(p.n_ops))):
            hp, dp = vm.pad_capacity(p, cap), vm.pad_capacity(q, cap)
            assert vm._on_host(hp) and not vm._on_host(dp)
            assert corpus.program_hash(hp) == corpus.program_hash(dp)
    with pytest.raises(vm.VMUnsupported):
        vm.pad_capacity(host[1], 64)


@pytest.mark.parametrize("devices", (1, 4), ids=("one_device", "mesh4"))
def test_a_generation_uploads_its_programs_once_and_reads_nothing_back(
        devices, cores, monkeypatch):
    """The batched tier hands the device ONE ``device_put`` of program
    words a generation, the stacked batch of eight leaves (on a mesh: the
    runner's one sharded put, from the host batch), and nothing is read
    back from a device before the launch."""
    from jax._src import array as jarray

    from fks_tpu.parallel import population_mesh

    src = corpus.sources()
    codes = [src[n] for n in corpus.MIXED]
    mesh = population_mesh(jax.devices()[:4]) if devices == 4 else None
    # bounded segments, as a TPU host picks: the mesh runner then shards
    # the batch itself, in Python (``mesh.shard_population``)
    monkeypatch.setenv("FKS_VM_SEG_STEPS", "8")
    ev = backend.CodeEvaluator(corpus.mixed_workload(), vm_batch=True,
                               mesh=mesh, engine="flat")
    want = ev.evaluate(codes)                              # warm
    puts, reads, launched = [], [], []
    put, value = jax.device_put, jarray.ArrayImpl._value

    def counting_put(x, *a, **k):
        if isinstance(x, vm.VMProgram):
            puts.append((vm._on_host(x), len(x), bool(a or k),
                         bool(launched)))
        return put(x, *a, **k)

    def counting_value(self):
        reads.append(bool(launched))
        return value.fget(self)

    runner = "_vm_mesh_run" if devices == 4 else "_vm_pop_run"
    run = getattr(ev, runner)

    def launch(*a):
        launched.append(1)
        return run(*a)

    monkeypatch.setattr(ev, runner, launch)
    monkeypatch.setattr(jax, "device_put", counting_put)
    monkeypatch.setattr(jarray.ArrayImpl, "_value", property(counting_value))
    mark = len(spans.LOG.snapshot())
    got = ev.evaluate(codes)
    monkeypatch.undo()
    assert [(r.score, r.error) for r in got] \
        == [(r.score, r.error) for r in want]
    # NumPy leaves, eight of them, once: with the default placement
    # before the launch, or sharded by the mesh runner inside it
    assert puts == [(True, 8, devices == 4, devices == 4)]
    assert len(launched) == 1 and not reads.count(False)
    assert reads                      # the harvest does read, afterwards
    new = spans.LOG.snapshot()[mark:]
    (stack,) = [r for r in new if r.name == "tier/vm_batch/stack_programs"]
    (pack,) = [r for r in new if r.name == "tier/transpile/pack"]
    assert stack.fields["uploads"] == 8 and pack.fields["uploads"] == 0
    assert pack.fields["programs"] == stack.fields["candidates"] >= 4


# --------------------------- (b), (c) records, routing, the span's fields

@pytest.mark.parametrize("capacity", (512, 128), ids=("cap512", "cap128"))
def test_generation_records_and_routing_are_the_in_process_ones(
        capacity, cores, monkeypatch):
    """TranspileError, VMUnsupported and (at an op budget of 128, which
    the champion overruns) an over-capacity source, beside valid ones:
    every field of every record and the tier each source went to, pooled
    and in process; the stage's span says where the sources were lowered
    and counts alike."""
    pooled, served_p, fp = mixed_records(capacity, monkeypatch)
    monkeypatch.setattr(lower_pool, "usable_cores", lambda: 1)
    serial, served_s, fs = mixed_records(capacity, monkeypatch)
    assert pooled == serial and served_p == served_s
    errors = [e or "" for _, _, e, _ in pooled]
    assert sum(e.startswith("transpile:") for e in errors) == 3
    assert sum(e.startswith("syntax:") for e in errors) == 1
    assert sum(not e for e in errors) == 7
    # VMUnsupported goes to the jit tier, and over the budget the champion
    assert served_p == {"unique": 9, "vm_batch_lanes": 5 - (capacity < 256),
                        "fallback_lanes": 1 + (capacity < 256)}
    # 9 unique sources enter the stage; each is traced once, somewhere
    assert (fp["sources"], fp["pooled"], fp["traces"]) == (9, 9, 9)
    assert 1 <= fp["workers"] <= 2
    assert (fs["sources"], fs["pooled"], fs["workers"], fs["traces"]) \
        == (9, 0, 0, 9)
    assert fp["ops_lowered"] == fs["ops_lowered"] > fp["ops_kept"] \
        == fs["ops_kept"] > 0


# ------------------------ the workers' own spans come home (ISSUE 40)

def generation_spans(cores_now, monkeypatch, lower_all=None):
    """One generation of ``corpus.MIXED`` (eight unique sources reach the
    stage) with ``cores_now`` usable cores: its ``tier/transpile`` record
    and that record's children."""
    monkeypatch.setattr(lower_pool, "usable_cores", lambda: cores_now)
    if lower_all is not None:
        monkeypatch.setattr(lower_pool, "lower_all", lower_all)
    src = corpus.sources()
    ev = backend.CodeEvaluator(corpus.mixed_workload(), vm_batch=True,
                               preflight=False)
    mark = len(spans.LOG.snapshot())
    ev.evaluate([src[n] for n in corpus.MIXED])
    new = spans.LOG.snapshot()[mark:]
    (stage,) = [r for r in new if r.name == "tier/transpile"]
    return stage, [r for r in new if r.parent_id == stage.span_id]


@pytest.mark.parametrize("pooled", (1, 0), ids=("pooled", "in_process"))
def test_every_source_leaves_a_lower_span_inside_the_stage(
        pooled, cores, monkeypatch):
    """Wherever a source is checked and lowered it writes one
    ``tier/transpile/check`` and, if it got that far, one
    ``tier/transpile/lower`` on the parent's clock, nested in the stage,
    with the process that did it; ``tier/transpile/pack`` is the dedup
    and the programs, and uploads nothing."""
    stage, kids = generation_spans(4 if pooled else 1, monkeypatch)
    assert stage.fields["sources"] == 8
    assert stage.fields["pooled"] == 8 * pooled
    assert stage.fields["clock_misfit"] == 0
    checks = [r for r in kids if r.name == "tier/transpile/check"]
    lowers = [r for r in kids if r.name == "tier/transpile/lower"]
    (pack,) = [r for r in kids if r.name == "tier/transpile/pack"]
    # ten sources, nine texts (one exact echo), one of them a syntax
    # error, which is checked and never lowered
    broken = corpus.MIXED.index("syntax:broken")
    assert len(kids) == 18 and len(checks) == 9 and len(lowers) == 8
    assert sorted(r.fields["source"] for r in checks) == list(range(9))
    assert sorted(r.fields["source"] for r in lowers) \
        == [i for i in range(9) if i != broken]
    assert [r.fields["ok"] for r in sorted(
        checks, key=lambda r: r.fields["source"])] \
        == [int(i != broken) for i in range(9)]
    by_source = {r.fields["source"]: r for r in checks}
    for r in checks:
        assert stage.t0 <= r.t0 < r.t1 <= pack.t0
        assert r.trace_id == stage.trace_id
        assert set(r.fields) == {"source", "pid", "pooled", "ok"}
    for r in lowers:
        assert stage.t0 <= r.t0 < r.t1 <= stage.t1
        assert r.trace_id == stage.trace_id
        assert r.fields["pooled"] == pooled
        assert 0 <= r.fields["trace_ms"] <= (r.t1 - r.t0) * 1e3
        # a traced source says how many equations its jaxpr held (a
        # subset violation raises inside the trace: none)
        assert r.fields["eqns"] >= r.fields["ops_lowered"] >= 0
        # checked, then lowered, by one process; then the dedup
        check = by_source[r.fields["source"]]
        assert check.t1 == r.t0 and check.fields["pid"] == r.fields["pid"]
        assert r.t1 <= pack.t0
    assert sum(r.fields["eqns"] > r.fields["ops_lowered"] > 0
               for r in lowers) >= 4
    pids = {r.fields["pid"] for r in checks}
    if pooled:
        assert pids <= {w["pid"] for w in up()} and os.getpid() not in pids
    else:
        assert pids == {os.getpid()}
        # one after another on one core: no two overlap
        ordered = sorted(checks + lowers, key=lambda r: r.t0)
        assert all(a.t1 <= b.t0 for a, b in zip(ordered, ordered[1:]))
    assert stage.fields["ops_lowered"] \
        == sum(r.fields["ops_lowered"] for r in lowers)
    assert stage.fields["ops_kept"] \
        == sum(r.fields["ops_kept"] for r in lowers)
    # three of the eight do not lower (TranspileError, VMUnsupported ...)
    packed = sum(r.fields["ops_kept"] > 0 for r in lowers)
    assert pack.fields == {"programs": packed, "uploads": 0}
    assert stage.t0 <= pack.t0 <= pack.t1 <= stage.t1


def test_a_worker_on_another_clock_leaves_no_lower_span(cores, monkeypatch):
    """Stamps that do not lie inside the parent's own send and receive
    stamps are refused: the stage says so and writes no child from them
    (the dedup is the parent's own and stays)."""
    real = lower_pool.lower_all

    def shifted(codes, n, g):
        out, stats = real(codes, n, g)
        assert stats["pooled"] == len(codes)
        return [low._replace(
            t0=low.t0 + 3600.0, t_checked=low.t_checked + 3600.0,
            t_traced=low.t_traced + 3600.0, t1=low.t1 + 3600.0)
            for low in out], stats

    stage, kids = generation_spans(4, monkeypatch, shifted)
    assert stage.fields["clock_misfit"] == 1 and stage.fields["pooled"] == 8
    assert [r.name for r in kids] == ["tier/transpile/pack"]


def test_clock_misfit_reads_the_parents_window():
    low = lower_pool.lower_source(corpus.sources()["seed:best_fit"], 16, 8)
    assert low.pid == os.getpid() and low.sent is None
    assert low.t0 < low.t_checked < low.t_traced < low.t1
    assert not lower_pool.clock_misfit([low])        # in process: no window
    inside = low._replace(sent=low.t0 - 1e-3, received=low.t1 + 1e-3)
    assert not lower_pool.clock_misfit([low, inside])
    for bad in (inside._replace(sent=low.t0 + 1e-6),
                inside._replace(received=low.t1 - 1e-6)):
        assert lower_pool.clock_misfit([inside, bad])
    # a source that does not lower is stamped all the same
    bad = lower_pool.lower_source(corpus.sources()["subset:2"], 16, 8)
    assert bad.error is not None \
        and bad.t0 < bad.t_checked < bad.t_traced == bad.t1


# ----------------------------------------------------- (d) a lost worker

def test_a_killed_worker_costs_one_generation_its_pool_not_its_records(
        cores):
    src = corpus.sources()
    codes = [src[n] for n in corpus.MIXED]
    ev = backend.CodeEvaluator(corpus.mixed_workload(), vm_batch=True,
                               preflight=False)
    want = ev.evaluate(codes)
    before, drops = up(), lower_pool.drops()
    os.kill(before[0]["pid"], 9)
    mark = len(spans.LOG.snapshot())
    got = ev.evaluate(codes)
    fields = transpile_span(mark)
    assert (fields["pooled"], fields["workers"], fields["traces"]) \
        == (0, 0, 8)
    assert lower_pool.drops() == drops + 1
    assert not any(alive(w["pid"]) for w in before)
    # the next generation starts a pool of its own (and does not wait for
    # it); once that is up a generation is pooled again
    mark = len(spans.LOG.snapshot())
    mid = ev.evaluate(codes)
    assert transpile_span(mark)["traces"] == 8
    after = up()
    assert not {w["pid"] for w in after} & {w["pid"] for w in before}
    mark = len(spans.LOG.snapshot())
    again = ev.evaluate(codes)
    fields = transpile_span(mark)
    assert (fields["pooled"], fields["traces"]) == (8, 8)
    assert lower_pool.drops() == drops + 1
    for a, b, c, d in zip(want, got, mid, again):
        assert (a.score, a.error) == (b.score, b.error) \
            == (c.score, c.error) == (d.score, d.error)


def test_a_generation_does_not_wait_for_workers_that_are_starting(
        monkeypatch):
    """A fresh pool is two seconds from its first worker: the generation
    that starts it is lowered here, whole, and comes back before any
    worker is up; the same sources later go to the workers."""
    monkeypatch.setattr(lower_pool, "usable_cores", lambda: 4)
    monkeypatch.setattr(lower_pool, "_pool", None)
    codes = [corpus.sources()[n] for n in
             ("seed:first_fit", "seed:best_fit", "fake3:00")]
    try:
        out, stats = lower_pool.lower_all(codes, 16, 8)
        assert lower_pool._pool is not None and not lower_pool._pool.ready
        assert stats == lower_pool.NOT_POOLED
        assert [o.traces for o in out] == [1, 1, 1]
        up()
        again, stats = lower_pool.lower_all(codes, 16, 8)
        assert stats == {"pooled": 3, "workers": 2}
        assert all(same(a, b) for a, b in zip(out, again))
    finally:
        lower_pool._pool.close()   # this test's own; the process's returns


# ------------------------------------------ (e) what a worker runs under

def test_a_worker_is_on_the_cpu_in_the_parents_x64(cores):
    ws = up()
    assert len({w["pid"] for w in ws}) == 2
    assert os.getpid() not in {w["pid"] for w in ws}
    for w in ws:
        assert w["backend"] == "cpu"
        assert w["x64"] is bool(jax.config.jax_enable_x64) is True


# --------------------------------- adapting: cores, a single source, size

def test_one_usable_core_or_one_source_is_lowered_in_process(monkeypatch):
    codes = list(corpus.sources().values())[:3]
    monkeypatch.setattr(lower_pool, "_Pool", None)  # must not be built
    monkeypatch.setattr(lower_pool, "_pool", None)
    monkeypatch.setattr(lower_pool, "usable_cores", lambda: 1)
    out, stats = lower_pool.lower_all(codes, 16, 8)
    assert stats == lower_pool.NOT_POOLED and len(out) == 3
    lower_pool.start(16, 8)
    monkeypatch.setattr(lower_pool, "usable_cores", lambda: 12)
    out, stats = lower_pool.lower_all(codes[:1], 16, 8)
    assert stats == lower_pool.NOT_POOLED and out[0].traces == 1
    assert lower_pool._pool is None


@pytest.mark.parametrize("sources,usable,cap,want", [
    (8, 12, 16, 8), (8, 7, 16, 7), (64, 29, 16, 16), (8, 12, 2, 2),
    (8, 1, 16, 1), (1, 12, 16, 1), (8, 0, 16, 0)])
def test_pool_size_is_the_least_of_sources_cores_and_cap(
        sources, usable, cap, want, monkeypatch):
    monkeypatch.setattr(lower_pool, "usable_cores", lambda: usable)
    monkeypatch.setattr(lower_pool, "MAX_WORKERS", cap)
    assert lower_pool._size(sources) == want


def test_a_pool_that_cannot_start_is_counted_and_the_generation_lowered(
        monkeypatch):
    def no_pool(*a):
        raise OSError("no more processes")

    monkeypatch.setattr(lower_pool, "usable_cores", lambda: 4)
    monkeypatch.setattr(lower_pool, "_pool", None)
    monkeypatch.setattr(lower_pool, "_Pool", no_pool)
    drops = lower_pool.drops()
    lower_pool.start(16, 8)
    codes = list(corpus.sources().values())[:3]
    out, stats = lower_pool.lower_all(codes, 16, 8)
    assert lower_pool.drops() == drops + 2
    assert stats == lower_pool.NOT_POOLED
    assert [o.traces for o in out] == [1, 1, 1]
    assert lower_pool._pool is None


def test_lower_source_returns_what_compile_policy_raises():
    src = corpus.sources()
    low = lower_pool.lower_source(src["vm:unsupported"], 16, 8)
    assert type(low.error) is vm.VMUnsupported and low.kept is None
    with pytest.raises(vm.VMUnsupported) as e:
        vm.compile_policy(src["vm:unsupported"], 16, 8)
    assert str(e.value) == str(low.error)
    low = lower_pool.lower_source(src["subset:2"], 16, 8)
    assert type(low.error) is transpiler.TranspileError and low.traces == 1
    with pytest.raises(transpiler.TranspileError) as e:
        vm.compile_policy(src["subset:2"], 16, 8)
    assert str(e.value) == str(low.error)
    # a source that does not parse stops at the key, as a rejection
    low = lower_pool.lower_source(src["syntax:broken"], 16, 8)
    assert low.error is None and low.kept is None and low.traces == 0
    assert low.rejection.taxonomy is None and low.key is None
    with pytest.raises(SyntaxError) as e:
        transpiler.canonical_key(src["syntax:broken"])
    assert str(e.value) == low.rejection.reason
    assert low.t0 < low.t_checked == low.t_traced == low.t1


# --------------------- (f) one pool a process; none outlives its process

def test_two_evaluators_and_two_threads_share_one_pool(cores):
    src = corpus.sources()
    codes = [src[n] for n in corpus.MIXED]
    evs = [backend.CodeEvaluator(corpus.mixed_workload(), vm_batch=True,
                                 preflight=False) for _ in range(2)]
    want = evs[0].evaluate(codes)
    pids = {w["pid"] for w in up()}
    got, marks = {}, len(spans.LOG.snapshot())

    def run(i):
        got[i] = [evs[i].evaluate(codes) for _ in range(3)]

    threads = [threading.Thread(target=run, args=(i,)) for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    assert not any(t.is_alive() for t in threads)
    assert {w["pid"] for w in lower_pool.workers()} == pids
    stages = [r.fields for r in spans.LOG.snapshot()[marks:]
              if r.name == "tier/transpile"]
    assert len(stages) == 6 and all(f["pooled"] == 8 for f in stages)
    for recs in got[0] + got[1]:
        assert [(r.score, r.error) for r in recs] \
            == [(r.score, r.error) for r in want]


def alive(pid: int) -> bool:
    """A process that runs (a zombie nobody reaped does not)."""
    try:
        with open(f"/proc/{pid}/stat") as f:
            return f.read().rpartition(")")[2].split()[0] != "Z"
    except (FileNotFoundError, ProcessLookupError):
        return False


def group_alive(pgid: int) -> bool:
    """Any running process of the process group (a pool's nursery leads
    one, its workers are in it)."""
    for pid in filter(str.isdigit, os.listdir("/proc")):
        try:
            with open(f"/proc/{pid}/stat") as f:
                state, _, pgrp = f.read().rpartition(")")[2].split()[:3]
        except (FileNotFoundError, ProcessLookupError):
            continue
        if int(pgrp) == pgid and state != "Z":
            return True
    return False


CHILD = """
import time
import jax
jax.config.update("jax_platforms", "cpu")
from fks_tpu.funsearch import backend, lower_pool
from tests import lowering_corpus as corpus
lower_pool.usable_cores = lambda: 4
lower_pool.MAX_WORKERS = 2
src = corpus.sources()
ev = backend.CodeEvaluator(corpus.mixed_workload(), vm_batch=True,
                           preflight=False)
{work}
print("group", lower_pool._pool.proc.pid, len(lower_pool.workers()),
      flush=True)
raise SystemExit(1)
"""


@pytest.mark.parametrize("work,ready", [
    # a pooled generation, then the exit: idle workers
    ("while len(lower_pool.workers()) < 2: time.sleep(0.05)\n"
     "ev.evaluate([src[n] for n in corpus.MIXED])", 2),
    # the exit while the nursery still imports (nothing waited for it)
    ("", 0),
], ids=("after_a_generation", "during_the_start"))
def test_a_process_that_exits_leaves_no_worker_behind(work, ready):
    env = dict(os.environ, PYTHONPATH=ROOT)
    proc = subprocess.Popen([sys.executable, "-c", CHILD.format(work=work)],
                            stdout=subprocess.PIPE, cwd=ROOT, env=env,
                            text=True)
    try:
        line = proc.stdout.readline().split()
        t0 = time.perf_counter()
        assert line[0] == "group" and int(line[2]) == ready, line
        pgid = int(line[1])
        assert proc.wait(timeout=5) == 1
        while group_alive(pgid) and time.perf_counter() - t0 < 5:
            time.sleep(0.05)
        assert not group_alive(pgid)
        assert time.perf_counter() - t0 < 5
    finally:
        proc.kill()
        proc.wait()


def test_serving_starts_no_worker(monkeypatch):
    """``VMServeEngine`` compiles its one champion in process."""
    from tests import test_vm_serve as tv
    from fks_tpu.data.synthetic import synthetic_workload
    from fks_tpu.serve import ShapeEnvelope, VMServeEngine

    def no_pool(*a):
        raise AssertionError("serving built a lowering pool")

    monkeypatch.setattr(lower_pool, "_pool", None)
    monkeypatch.setattr(lower_pool, "_Pool", no_pool)
    monkeypatch.setattr(lower_pool, "usable_cores", lambda: 12)
    eng = VMServeEngine(
        tv._champ(tv.BETTER_LOGIC), synthetic_workload(8, 16, seed=0),
        envelope=ShapeEnvelope(max_pods=8, min_pod_bucket=8, max_batch=2,
                               max_gpu_milli=1000), engine="flat")
    assert len(eng.answer_batch([tv._query(0)])) == 1
    assert lower_pool._pool is None
