"""The seven per-layer metrics that read the ring's other writers (PR 40:
``tier/transpile/lower`` and ``/pack``, ``host/gc``, ``obs/slow_root``):
``chipbench/reduce/hostspans.py`` over hand-made rings, the None of a
program without the mechanism, the sum rule of the transpile stage, their
declaration, and ``python3 -m chipbench.selftest.hostspans`` (the traced
cells at tiny sizes and a generation made to wait), which runs ONCE in a
process of its own with each of its tests one case here."""
import json
import os
import pathlib
import re
import subprocess
import sys

import pytest

from chipbench import cells
from chipbench.reduce import hostspans as hs
from chipbench.reduce import spans as rs
from fks_tpu.obs import spans as program
from fks_tpu.obs.spans import SpanRecord

REPO = pathlib.Path(__file__).resolve().parent.parent
CODE = ["openb16.codegen8", "openb16.codegen8x4",
        "openb1523-inflated.codegen8", "openb1523-loaded.codegen8",
        # appended by PR 42, with its cell
        "openb16-cpu250-midrun.codegen8",
        "openb1523-gpuspec25-loaded.codegen8"]
WHATIF = ["openb1523.whatif8", "openb1523-loaded.whatif8",
          # appended by PR 49 and by PR 52, each with its cell
          "openb1523-gpuspec25-loaded.whatif8",
          "openb16-cpu250-midrun.whatif8"]
TIER = "candidate tiers funsearch/backend.py"
METRICS = {
    "tier.lower_ms_per_source": ("ms", "program_span", TIER, CODE),
    "tier.pack_ms_per_call": ("ms", "program_span", TIER, CODE),
    "tier.pool_overhead_ms_per_call": ("ms", "program_span", TIER, CODE),
    "tier.gc_ms_per_call": ("ms", "program_span", TIER, CODE),
    "serve.gc_ms_per_call": ("ms", "program_span", "serving serve/", WHATIF),
    "tier.slow_call_share": ("%", "program_counter", TIER, CODE),
    "serve.slow_call_share": ("%", "program_counter", "serving serve/",
                              WHATIF),
}


def read(name, calls):
    return cells.metric_reader(name)({"_span_calls": calls})


def rec(seq, name, t0, t1, sid, parent=None, trace="t", **fields):
    return SpanRecord(seq, name, t0, t1, sid, parent, trace, 1,
                      fields or None)


def generations(lowers=((0.00, 0.11), (0.00, 0.12), (0.01, 0.10)),
                pack=(0.125, 0.135), stage=(0.1, 0.25), gc=(), slow=(),
                checks=(), uploads=24, stack=None, **stage_fields):
    """Three generations of 2 s (the first is the warm-up), each with a
    ``tier/transpile`` of ``stage`` seconds after the call's start whose
    children are ``lowers`` (offsets from the stage's start) and ``pack``;
    ``gc``: pauses as offsets into generation 1; ``slow``: the window
    calls (0, 1) the program found slow. Since PR 51 the stage may hold
    ``checks`` (``tier/transpile/check``, offsets like ``lowers``), its
    pack says ``uploads`` (None: no such field) and the launch's
    ``tier/vm_batch/stack_programs`` span (``stack``: its fields, or None
    for no span) may say how many leaves it put."""
    recs, t, seq = [], 0.0, 0
    for i in range(3):
        g, x = f"g{i}", f"x{i}"
        a = t + stage[0]
        for j, (l0, l1) in enumerate(lowers):
            recs.append(rec(seq, "tier/transpile/lower", a + l0, a + l1,
                            f"{x}l{j}", x, g, source=j, pid=100 + j,
                            pooled=1, trace_ms=(l1 - l0) * 900.0,
                            eqns=770))  # as the program writes them
            seq += 1
        for j, (c0, c1) in enumerate(checks):
            recs.append(rec(seq, "tier/transpile/check", a + c0, a + c1,
                            f"{x}c{j}", x, g, source=j, pid=100 + j,
                            pooled=1, ok=1))
            seq += 1
        if pack:
            said = {} if uploads is None else {"uploads": uploads}
            recs.append(rec(seq, "tier/transpile/pack", a + pack[0],
                            a + pack[1], f"{x}p", x, g, programs=3, **said))
            seq += 1
        if stack is not None:
            recs.append(rec(seq, "tier/vm_batch/stack_programs",
                            t + stage[1], t + stage[1] + 0.001, f"{x}s", g,
                            g, candidates=3, lanes=4, **stack))
            seq += 1
        recs.append(rec(seq, "tier/transpile", a, t + stage[1], x, g, g,
                        sources=3, pooled=3, **stage_fields))
        recs.append(rec(seq + 1, "tier/evaluate", t, t + 2.0, g, None, g,
                        candidates=3, start_event=0))
        seq += 2
        if i - 1 in slow:
            recs.append(rec(seq, "obs/slow_root", t, t + 2.0, f"s{i}", None,
                            f"s{i}", root="tier/evaluate", seconds=2.0,
                            median=1.5, grew="tier/transpile",
                            excess_s=0.5, gc_s=0.0))
            seq += 1
        t += 2.01
    for k, (g0, g1) in enumerate(gc):
        recs.append(rec(seq + k, "host/gc", 2.01 + g0, 2.01 + g1, f"gc{k}",
                        None, f"gc{k}", generation=2, collected=0))
    calls = rs.select_generations(recs, 0, 2, 4.0)
    assert calls and len(calls) == 2
    return calls


# ------------------------------------------------------ the code cells

def test_lower_pack_and_overhead_add_up_to_the_stage():
    calls = generations()
    # (0.11 + 0.12 + 0.09) / 3 a source
    assert read("tier.lower_ms_per_source", calls) \
        == pytest.approx(320 / 3)
    assert read("tier.pack_ms_per_call", calls) == pytest.approx(10.0)
    # the stage is 150 ms; its children cover 0-120 and 125-135 ms of it
    assert read("tier.pool_overhead_ms_per_call", calls) \
        == pytest.approx(20.0)
    stage = rs.sum_ms_per_call({"_span_calls": calls}, "tier/transpile")
    longest = sum(max(r.t1 - r.t0 for r in c.spans if r.name in hs.LOWER)
                  for c in calls) / len(calls) * 1e3
    assert (stage, longest) == (pytest.approx(150.0), pytest.approx(120.0))
    # side by side and started with the stage: the three parts are it
    assert longest + 10.0 + 20.0 == pytest.approx(stage)


def test_overhead_is_a_union_so_it_holds_for_serial_lowering_too():
    """A dropped pool: the sources one after another in the parent."""
    calls = generations(lowers=((0.0, 0.04), (0.04, 0.08), (0.08, 0.12)))
    assert read("tier.lower_ms_per_source", calls) == pytest.approx(40.0)
    assert read("tier.pool_overhead_ms_per_call", calls) \
        == pytest.approx(20.0)
    # children that reach past their stage are clipped to it
    calls = generations(lowers=((-0.05, 0.12),), pack=(0.125, 0.2))
    assert read("tier.pool_overhead_ms_per_call", calls) \
        == pytest.approx(5.0)


@pytest.mark.parametrize("name", ["tier.lower_ms_per_source",
                                  "tier.pack_ms_per_call",
                                  "tier.pool_overhead_ms_per_call"])
def test_a_ring_without_the_children_reads_as_nothing(name):
    """The parent's ``tier/transpile`` has no child span."""
    calls = generations(lowers=(), pack=())
    assert read(name, calls) is None
    assert rs.sum_ms_per_call({"_span_calls": calls}, "tier/transpile") \
        == pytest.approx(150.0)


def test_refused_stamps_leave_no_overhead_reading():
    """``clock_misfit``: the lower spans were not written; the stage less
    its uploads is NOT the pool's overhead."""
    calls = generations(lowers=(), clock_misfit=1)
    assert read("tier.pack_ms_per_call", calls) == pytest.approx(10.0)
    assert read("tier.lower_ms_per_source", calls) is None
    assert read("tier.pool_overhead_ms_per_call", calls) is None
    calls = generations(clock_misfit=0)
    assert read("tier.pool_overhead_ms_per_call", calls) \
        == pytest.approx(20.0)


# ------------------- the check a source and the uploads a call (PR 51)

def test_check_ms_is_the_mean_of_the_windows_check_spans():
    """One span a distinct text, on the stamps of the process that ran
    the check before it lowered the source."""
    calls = generations(checks=((0.000, 0.004), (0.000, 0.006),
                                (0.010, 0.012)),
                        lowers=((0.004, 0.11), (0.006, 0.12), (0.012, 0.10)))
    assert read("tier.check_ms_per_source", calls) == pytest.approx(4.0)
    # the check is neither a lower nor a pack: the overhead's reader
    # leaves the longest source's in the stage's remainder (0-4 ms of
    # the first source's, which starts the stage)
    assert read("tier.lower_ms_per_source", calls) \
        == pytest.approx((106 + 114 + 88) / 3)
    assert read("tier.pool_overhead_ms_per_call", calls) \
        == pytest.approx(150.0 - (120.0 - 4.0) - 10.0)
    # a program that checks in its own thread (older than PR 51), and one
    # that refused its workers' stamps, write no such span
    assert read("tier.check_ms_per_source", generations()) is None
    assert read("tier.check_ms_per_source",
                generations(lowers=(), clock_misfit=1)) is None


@pytest.mark.parametrize("uploads,stack,want", [
    (24, {}, 24.0),              # PR 50: eight arrays a program, uploaded
    (24, None, 24.0),            # ... and a ring that holds no launch
    (0, {"uploads": 8}, 8.0),    # PR 51: one put of the batch's leaves
    (0, {}, 0.0),                # a 0 is a reading
    (None, {}, None),            # older than PR 40: nobody counted
    (None, {"uploads": 8}, 8.0)],
    ids=("parent", "parent_no_launch", "change", "zero", "unsaid",
         "stack_alone"))
def test_uploads_are_the_packs_and_the_stacks_fields_summed(uploads, stack,
                                                            want):
    calls = generations(uploads=uploads, stack=stack)
    got = read("tier.uploads_per_call", calls)
    assert got == want if want is None else got == pytest.approx(want)
    # no pack span and no stack span: nothing to read
    assert read("tier.uploads_per_call",
                generations(lowers=(), pack=())) is None


def test_the_two_of_pr51_are_declared_with_their_files():
    bench = json.load(open(os.path.join(cells.ROOT, "BENCHMARK.json")))
    two = bench["per_layer"][-5:-3]     # PR 52 appended its three after
    assert [m["name"] for m in two] == ["tier.check_ms_per_source",
                                        "tier.uploads_per_call"]
    for m, (unit, source) in zip(two, (("ms", "program_span"),
                                       ("uploads", "program_counter"))):
        assert m == {"name": m["name"], "unit": unit, "better": "lower",
                     "source": source, "layer": TIER,
                     "moves": "lane_events_per_s", "workloads": CODE}
        meta = json.load(open(os.path.join(cells.HERE, "metrics",
                                           m["name"] + ".json")))
        for key in ("name", "unit", "better", "source", "layer", "moves"):
            assert meta[key] == m[key], (m["name"], key)
        assert "PR 51" in meta["doc"]
        assert cells.metric_reader(m["name"])({}) is None
    for name in CODE:
        listed = [m["name"] for m in cells.load_cell(name).per_layer]
        assert listed[-2:] == [m["name"] for m in two]   # no serving metric
    for name in WHATIF + ["openb16.param256"]:
        assert not {m["name"] for m in two} & {
            m["name"] for m in cells.load_cell(name).per_layer}


def test_gc_is_the_union_of_the_pauses_inside_the_windows_calls():
    assert read("tier.gc_ms_per_call", generations()) == 0.0
    # 30 ms and 20 ms inside generation 1; a pause between two calls and
    # one in the warm-up are no call's
    calls = generations(gc=((0.50, 0.53), (1.00, 1.02), (2.001, 2.008),
                            (-1.0, -0.9)))
    assert read("tier.gc_ms_per_call", calls) == pytest.approx(25.0)
    # the whatif twin reads serving calls: nothing here
    assert read("serve.gc_ms_per_call", calls) is None


def test_slow_share_counts_the_rings_own_records():
    assert read("tier.slow_call_share", generations()) == 0.0
    assert read("tier.slow_call_share", generations(slow=(1,))) == 50.0
    assert read("tier.slow_call_share", generations(slow=(0, 1))) == 100.0
    # a slow WARM-UP call (index -1) is outside the window
    assert read("tier.slow_call_share", generations(slow=(-1,))) == 0.0
    assert read("serve.slow_call_share", generations(slow=(1,))) is None


# ----------------------------------------------------- the whatif cells

def whatif_calls(gc=(), slow=()):
    from tests.test_chipbench_spans import whatif_ring

    ring = whatif_ring()
    seq = len(ring)
    batches = [r for r in ring if r.name == "serve/batch"]
    for k, (g0, g1) in enumerate(gc):
        ring.append(rec(seq, "host/gc", batches[0].t0 + g0,
                        batches[0].t0 + g1, f"gc{k}", None, f"gc{k}",
                        generation=2, collected=0))
        seq += 1
    for i in slow:
        b = batches[i]
        ring.append(rec(seq, "obs/slow_root", b.t0, b.t1, f"s{i}", None,
                        f"s{i}", root="serve/batch", seconds=b.t1 - b.t0,
                        median=0.05, grew="serve/chunk/wait_device",
                        excess_s=0.025, gc_s=0.0))
        seq += 1
    calls = rs.select_whatif(ring, 0, 2, 4, 0.160)
    assert calls
    return calls


def test_the_serving_twins_read_the_serving_calls():
    assert read("serve.gc_ms_per_call", whatif_calls()) == 0.0
    assert read("serve.slow_call_share", whatif_calls()) == 0.0
    calls = whatif_calls(gc=((0.010, 0.014),), slow=(1,))
    assert read("serve.gc_ms_per_call", calls) == pytest.approx(2.0)
    assert read("serve.slow_call_share", calls) == 50.0
    # a generation's verdict is not a serving call's, nor the other way
    assert read("tier.slow_call_share", calls) is None
    assert read("tier.gc_ms_per_call", calls) is None


# ------------------------------------- a program without the mechanism

@pytest.mark.parametrize("name,gone", [
    ("tier.gc_ms_per_call", "GC_MIN_PAUSE_S"),
    ("serve.gc_ms_per_call", "GC_MIN_PAUSE_S"),
    ("tier.slow_call_share", "slow_roots"),
    ("serve.slow_call_share", "slow_roots")])
def test_a_program_older_than_the_mechanism_reads_as_nothing(
        name, gone, monkeypatch):
    """No pause and no slow call read 0 only where the program could have
    written one: the parent's ring has neither writer."""
    calls = generations() if name.startswith("tier") else whatif_calls()
    assert read(name, calls) == 0.0
    monkeypatch.delattr(program, gone)
    assert read(name, calls) is None


def test_without_a_ring_or_a_selection_every_reader_returns_none(
        monkeypatch):
    for name in METRICS:
        assert cells.metric_reader(name)({}) is None
    monkeypatch.delattr(program, "LOG")
    ctx = {"queries": 4, "calls": 2, "call_seconds": 0.16}
    for name in METRICS:
        assert cells.metric_reader(name)(dict(ctx)) is None


# ------------------------------------------------------------ declaration

def test_the_seven_are_declared_at_the_end_with_their_files():
    bench = json.load(open(os.path.join(cells.ROOT, "BENCHMARK.json")))
    # at the end as PR 40 left it; PR 42 appended its cell's two after,
    # PR 44 the interpreter's merged-read share, PR 45 the typed pods',
    # PR 46 the interpreter's slots a turn, PR 47 its narrow turns' share,
    # PR 49 the typed query pods' share, PR 51 the check a source and the
    # uploads a call, PR 52 the mid-run what-if cell's three
    seven = bench["per_layer"][41:41 + 7]
    assert [m["name"] for m in seven] == list(METRICS)
    assert [m["name"] for m in bench["per_layer"][41 + 7:]] == [
        "sim.fork_replay_us_per_event", "sim.fork_waiting_pods",
        "vm.merged_read_share", "sim.typed_pod_share", "vm.slots_per_turn",
        "vm.narrow_turn_share", "serve.typed_pod_share",
        "tier.check_ms_per_source", "tier.uploads_per_call",
        "serve.heap_replay_ms_per_call", "serve.fork_waiting_pods",
        "serve.finished_lane_share"]
    layers = {m["layer"] for m in bench["per_layer"][:41]}
    for m in seven:
        unit, source, layer, workloads = METRICS[m["name"]]
        assert m == {"name": m["name"], "unit": unit, "better": "lower",
                     "source": source, "layer": layer,
                     "moves": "whatif_pods_per_s" if workloads is WHATIF
                     else "lane_events_per_s", "workloads": workloads}
        assert layer in layers
        meta = json.load(open(os.path.join(cells.HERE, "metrics",
                                           m["name"] + ".json")))
        for key in ("unit", "better", "source", "layer", "moves"):
            assert meta[key] == m[key], (m["name"], key)
        assert "PR 40" in meta["doc"]
        assert os.path.exists(os.path.join(cells.HERE, "metrics",
                                           m["name"] + ".py"))
    # the flat engine's cell has no host stage and gets none
    assert not set(METRICS) & {
        m["name"] for m in cells.load_cell("openb16.param256").per_layer}


# ------------------------------------------- the selftest, in tier-1

NAMES = re.findall(
    r"^def (test_\w+)\(",
    (REPO / "chipbench" / "selftest" / "hostspans.py").read_text(), re.M)


@pytest.fixture(scope="module")
def selftest_lines():
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_ENABLE_X64")}
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench.selftest.hostspans"], cwd=REPO,
        env=env, capture_output=True, text=True, timeout=900)
    lines = proc.stdout.splitlines()
    assert lines, f"the selftest printed nothing:\n{proc.stderr[-2000:]}"
    return lines, proc.stderr


def test_the_selftest_has_its_three_tests():
    assert len(NAMES) == 3


@pytest.mark.parametrize("name", NAMES)
def test_selftest_case_passes(selftest_lines, name):
    lines, stderr = selftest_lines
    assert f"PASS {name}" in lines, (
        f"chipbench.selftest.hostspans did not pass {name}:\n"
        f"{stderr[-4000:]}")
