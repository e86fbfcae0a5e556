"""The benchmark's span reducer and the per-layer metrics that read it.

``chipbench/reduce/spans.py`` on hand-made span lists (union, self time,
per-call sums, the self-check failing on a missing root and on a ring that
dropped), and every span metric present and finite after a tiny traced run
of whatif8 and of codegen8x4 on four virtual CPU devices. Presence only:
no CPU time is written under a device metric's name.
"""
import contextlib
import io
import json
import math
import os

import pytest

from chipbench import cells, run
from chipbench.reduce import spans as rs
from fks_tpu.obs.spans import SpanRecord

SPAN_METRICS = sorted(
    os.path.basename(p)[:-3]
    for p in os.listdir(os.path.join(cells.HERE, "metrics"))
    if p.endswith(".py") and "chipbench.reduce import spans" in open(
        os.path.join(cells.HERE, "metrics", p)).read())


def rec(seq, name, t0, t1, sid, parent=None, trace="t", **fields):
    return SpanRecord(seq, name, t0, t1, sid, parent, trace, 1,
                      fields or None)


def whatif_ring(calls=2, gap=0.001):
    """``calls`` calls of two requests each; per call one batch of two
    chunks: stack 10 ms, wait 30 ms, stack 10 ms (hidden), wait 20 ms."""
    out, seq = [rec(0, "serve/request", 0.0, 0.5, "w", request="c-1-0")], 1
    t = 1.0
    for i in range(calls):
        b = f"b{i}"
        kids = [("serve/chunk/stack", 0.000, 0.010, {"chunk": 0}),
                ("serve/chunk/h2d", 0.010, 0.012, {"bytes": 2000}),
                ("serve/chunk/wait_device", 0.012, 0.042, {}),
                ("serve/chunk/stack", 0.042, 0.052, {"chunk": 1}),
                ("serve/chunk/h2d", 0.052, 0.054, {"bytes": 1000}),
                ("serve/chunk/wait_device", 0.054, 0.074, {})]
        for name, a, z, f in kids:
            out.append(rec(seq, name, t + a, t + z, f"{b}k{seq}", b, b, **f))
            seq += 1
        out.append(rec(seq, "serve/batch", t, t + 0.075, b, None, b))
        seq += 1
        for j in range(2):
            tid = f"r{i}{j}"
            out.append(rec(seq, "serve/request/queue_wait", t - 0.002,
                           t - 0.001, f"{tid}q", tid, tid))
            out.append(rec(seq + 1, "serve/request", t - 0.002, t + 0.078,
                           tid, None, tid, request=f"c{i}-{j}", batch=b))
            seq += 2
        t += 0.080 + gap
    return out


# ------------------------------------------------------------- arithmetic

def test_union_merges_overlaps_and_keeps_gaps():
    assert rs.union([(0, 1), (0.5, 2), (3, 4)]) == pytest.approx(3.0)
    assert rs.union([(3, 4), (0, 1), (0.2, 0.4)]) == pytest.approx(2.0)
    assert rs.union([]) == 0.0


def test_self_time_looks_through_stage_spans():
    root = rec(9, "tier/evaluate", 0.0, 10.0, "root")
    recs = [rec(0, "tier/preflight", 0.0, 1.0, "a", "root"),
            rec(1, "stage/device-eval", 1.0, 10.0, "st", "root"),
            rec(2, "tier/vm_batch/launch", 1.5, 9.0, "b", "st"),
            # a grandchild of a real span does not count twice
            rec(3, "mesh/segment", 2.0, 3.0, "c", "b"), root]
    kids = rs.children(recs)
    # 10 s minus preflight (1 s) minus launch (7.5 s): the stage span only
    # groups, so the 0.5 + 1 s around the launch stay unattributed
    assert rs.self_time(root, kids) == pytest.approx(1.5)


def test_whatif_selection_and_per_call_sums():
    ring = whatif_ring()
    calls = rs.select_whatif(ring, 0, 2, 4, 0.160)
    assert len(calls) == 2
    assert rs.extent_s(calls) == pytest.approx(0.160)
    assert rs.union_s(calls, ("serve/chunk/wait_device",)) \
        == pytest.approx(0.100)
    assert rs.sum_s(calls, ("serve/chunk/stack",)) == pytest.approx(0.040)
    assert rs.field_sum(calls, ("serve/chunk/h2d",), "bytes") == 6000
    ctx = {"_span_calls": calls}
    assert rs.exposed_ms_per_call(ctx, "serve/chunk/wait_device") \
        == pytest.approx(30.0)
    assert rs.union_ms_per_call(ctx, "serve/chunk/wait_device") \
        == pytest.approx(50.0)
    assert rs.sum_ms_per_call(ctx, "serve/chunk/stack") \
        == pytest.approx(20.0)
    assert rs.kb_per_call(ctx, "serve/chunk/h2d") == pytest.approx(3.0)
    assert rs.request_wait_p50_ms(ctx, "serve/request/queue_wait") \
        == pytest.approx(1.0)
    # the batch root is 75 ms, its chunk spans cover 74
    assert rs.unattributed_share(ctx, "serve/batch") \
        == pytest.approx(100 / 75)
    assert rs.sum_ms_per_call(ctx, "serve/chunk/extract") is None


def test_selection_fails_on_a_missing_root_and_on_a_wrong_clock():
    ring = whatif_ring(calls=3)
    assert rs.select_whatif(ring, 0, 3, 6, 0.240) is not None
    no_c1 = [r for r in ring
             if (r.fields or {}).get("request") not in ("c1-0", "c1-1")]
    assert rs.select_whatif(no_c1, 0, 3, 6, 0.240) is None
    one_gone = [r for r in ring if (r.fields or {}).get("request") != "c1-1"]
    assert rs.select_whatif(one_gone, 0, 3, 6, 0.240) is None
    # the driver's clock disagrees by more than 0.5 %
    assert rs.select_whatif(ring, 0, 3, 6, 0.240 * 1.006) is None
    assert rs.select_whatif(ring, 0, 3, 6, 0.240 * 1.004) is not None


def test_selection_fails_when_the_ring_dropped_inside_the_window():
    ring = whatif_ring()
    # the warm-up span (ended before the window) is still held: whatever
    # was dropped is older than the window
    assert rs.select_whatif(ring, 7, 2, 4, 0.160) is not None
    # it is gone: the oldest held record is the window's own
    assert rs.select_whatif(ring[1:], 7, 2, 4, 0.160) is None
    assert rs.select_whatif(ring[1:], 0, 2, 4, 0.160) is not None


def test_generations_are_taken_in_order_after_the_warm_up():
    recs, t = [], 0.0
    for i, length in enumerate([9.0, 2.0, 2.0, 2.0, 2.0]):
        recs.append(rec(2 * i, "tier/preflight", t, t + 0.5, f"p{i}",
                        f"g{i}", f"g{i}"))
        recs.append(rec(2 * i + 1, "tier/evaluate", t, t + length, f"g{i}",
                        None, f"g{i}"))
        t += length + 0.01
    calls = rs.select_generations(recs, 0, 3, 6.0)
    assert [round(c.t0, 2) for c in calls] == [9.01, 11.02, 13.03]
    assert rs.sum_s(calls, ("tier/preflight",)) == pytest.approx(1.5)
    # the warm-up call is not a window call: with it the clock disagrees
    assert rs.select_generations(recs, 0, 3, 13.0) is None
    assert rs.select_generations(recs, 0, 5, 17.0) is None  # too few roots



@pytest.mark.parametrize("metric,span,make", [
    ("vm.live_slot_share", "tier/vm_batch/launch", "generations"),
    ("vm.live_slot_share.serve", "serve/chunk/enqueue", "whatif"),
])
@pytest.mark.parametrize("fields,want", [
    ({"slots": 292, "capacity": 512}, 100.0 * 292 / 512),
    ({"slots": 64, "capacity": 64}, 100.0),   # the program fills its bucket
    ({}, None),                              # a parent without the fields
])
def test_live_slot_share_reads_slots_over_capacity(metric, span, make,
                                                    fields, want):
    """``slots / capacity`` over the window's calls from the span the
    program puts them on; missing when the fields are."""
    if make == "whatif":
        ring = whatif_ring()
        extra, seq = [], len(ring)
        for r in [r for r in ring if r.name == "serve/chunk/h2d"]:
            extra.append(rec(seq, span, r.t1, r.t1 + 0.0001,
                             f"e{seq}", r.parent_id, r.trace_id,
                             chunk=0, **fields))
            seq += 1
        calls = rs.select_whatif(ring + extra, 0, 2, 4, 0.160)
    else:
        recs, t = [], 0.0
        for i in range(3):
            recs.append(rec(2 * i, span, t + 0.5, t + 1.5, f"l{i}", f"g{i}",
                            f"g{i}", lanes=8, shards=1, **fields))
            recs.append(rec(2 * i + 1, "tier/evaluate", t, t + 2.0, f"g{i}",
                            None, f"g{i}"))
            t += 2.01
        calls = rs.select_generations(recs, 0, 2, 4.0)
    assert calls
    got = cells.metric_reader(metric)({"_span_calls": calls})
    assert got == (want if want is None else pytest.approx(want))


@pytest.mark.parametrize("fields,want", [
    # the population runner: one vmap that batches the programs
    ({"merged_reads": 1, "split_reads": 0}, 100.0),
    ({"merged_reads": 2, "split_reads": 0}, 100.0),   # two passes, nested
    ({"merged_reads": 0, "split_reads": 1}, 0.0),     # a batched counter
    ({"merged_reads": 1, "split_reads": 1}, 50.0),
    ({"merged_reads": 0, "split_reads": 0}, None),    # the rule never ran
    # a parent without the fields (older than PR 44)
    ({"slots": 292, "capacity": 512, "slice_writes": 1,
      "scatter_writes": 0}, None),
    ({}, None),
])
def test_merged_read_share_reads_the_launch_spans_fields(fields, want):
    got = cells.metric_reader("vm.merged_read_share")(
        {"_span_calls": _launch_calls(fields)})
    assert got == (want if want is None else pytest.approx(want))


@pytest.mark.parametrize("fields,want", [
    # the blocked loop: 292 live slots in 37 turns of 8
    ({"slots": 292, "turns": 37, "blocked_loops": 1, "plain_loops": 0},
     292 / 37),
    ({"slots": 292, "turns": 73}, 4.0),               # a block of 4
    ({"slots": 292, "turns": 292, "blocked_loops": 0, "plain_loops": 1},
     1.0),                                            # the one-slot turn
    ({"slots": 0, "turns": 0}, None),                 # nothing ran
    # a parent without the field (older than PR 46)
    ({"slots": 292, "capacity": 512, "merged_reads": 1, "split_reads": 0},
     None),
    ({}, None),
])
def test_slots_per_turn_reads_the_launch_spans_fields(fields, want):
    got = cells.metric_reader("vm.slots_per_turn")(
        {"_span_calls": _launch_calls(fields)})
    assert got == (want if want is None else pytest.approx(want))


@pytest.mark.parametrize("fields,want", [
    # the cells' generations: one block of 37 holds a champion's REM
    ({"slots": 292, "turns": 37, "wide_turns": 1}, 100.0 * 36 / 37),
    ({"slots": 292, "turns": 37, "wide_turns": 0}, 100.0),   # no WIDE opcode
    ({"slots": 292, "turns": 37, "wide_turns": 37}, 0.0),    # in every block
    # the one-slot turn knows the whole table only
    ({"slots": 292, "turns": 292, "wide_turns": 292}, 0.0),
    ({"slots": 0, "turns": 0, "wide_turns": 0}, None),       # nothing ran
    # a parent without the field (older than PR 47)
    ({"slots": 292, "turns": 37, "blocked_loops": 1, "plain_loops": 0},
     None),
    ({}, None),
])
def test_narrow_turn_share_reads_the_launch_spans_fields(fields, want):
    got = cells.metric_reader("vm.narrow_turn_share")(
        {"_span_calls": _launch_calls(fields)})
    assert got == (want if want is None else pytest.approx(want))


def _transpile_calls(fields):
    """The window's calls of three generations (the first is the warm-up),
    each with a ``tier/transpile`` span that carries ``fields``."""
    recs, t = [], 0.0
    for i in range(3):
        recs.append(rec(2 * i, "tier/transpile", t + 0.1, t + 1.0, f"x{i}",
                        f"g{i}", f"g{i}", **fields))
        recs.append(rec(2 * i + 1, "tier/evaluate", t, t + 2.0, f"g{i}",
                        None, f"g{i}"))
        t += 2.01
    calls = rs.select_generations(recs, 0, 2, 4.0)
    assert calls
    return calls


@pytest.mark.parametrize("fields,want", [
    ({"sources": 8, "traces": 8}, 1.0),     # the one trace at the real shape
    ({"sources": 8, "traces": 16}, 2.0),    # a dry trace came back
    ({"sources": 8, "traces": 0}, 0.0),     # a memo answered: not this cell's
    ({"sources": 0, "traces": 0}, None),    # nothing entered the stage
    ({}, None),                             # a parent without the fields
])
def test_traces_per_source_reads_the_transpile_spans_fields(fields, want):
    got = cells.metric_reader("tier.traces_per_source")(
        {"_span_calls": _transpile_calls(fields)})
    assert got == want


@pytest.mark.parametrize("fields,want", [
    # a generation of 8: six champions, first_fit, best_fit
    ({"sources": 8, "traces": 8, "ops_lowered": 6 * 370 + 185 + 209,
      "ops_kept": 6 * 292 + 126 + 148}, 100.0 * 2026 / 2614),
    ({"sources": 8, "traces": 8, "ops_lowered": 400, "ops_kept": 400},
     100.0),                                # a lowering that keeps every op
    ({"sources": 8, "traces": 8, "ops_lowered": 0, "ops_kept": 0}, None),
    ({"sources": 8, "traces": 8}, None),    # a parent without the fields
    ({}, None),
])
def test_ops_kept_share_reads_the_transpile_spans_fields(fields, want):
    got = cells.metric_reader("vm.ops_kept_share")(
        {"_span_calls": _transpile_calls(fields)})
    assert got == (want if want is None else pytest.approx(want))


def _launch_calls(fields):
    """The window's calls of three generations (the first is the warm-up),
    each with a ``tier/vm_batch/launch`` span that carries ``fields``."""
    recs, t = [], 0.0
    for i in range(3):
        recs.append(rec(2 * i, "tier/vm_batch/launch", t + 0.5, t + 1.5,
                        f"l{i}", f"g{i}", f"g{i}", lanes=8, shards=1,
                        **fields))
        recs.append(rec(2 * i + 1, "tier/evaluate", t, t + 2.0, f"g{i}",
                        None, f"g{i}"))
        t += 2.01
    calls = rs.select_generations(recs, 0, 2, 4.0)
    assert calls
    return calls


@pytest.mark.parametrize("fields,want", [
    # the population runner: one vmap around the op-slot loop's write
    ({"slice_writes": 1, "scatter_writes": 0}, 0.0),
    ({"slice_writes": 2, "scatter_writes": 0}, 0.0),   # suite x population
    ({"slice_writes": 0, "scatter_writes": 1}, 100.0),  # a batched row
    ({"slice_writes": 1, "scatter_writes": 1}, 50.0),
    ({"slice_writes": 0, "scatter_writes": 0}, None),   # the rule never ran
    ({"slots": 292, "capacity": 512}, None),   # a parent without the fields
    ({}, None),
])
def test_scatter_write_share_reads_the_launch_spans_fields(fields, want):
    got = cells.metric_reader("vm.scatter_write_share")(
        {"_span_calls": _launch_calls(fields)})
    assert got == (want if want is None else pytest.approx(want))


def _transpile_calls(fields):
    """The window's calls of three generations (the first is the warm-up),
    each with a ``tier/transpile`` span that carries ``fields``."""
    recs, t = [], 0.0
    for i in range(3):
        recs.append(rec(2 * i, "tier/transpile", t + 0.1, t + 0.3,
                        f"x{i}", f"g{i}", f"g{i}", **fields))
        recs.append(rec(2 * i + 1, "tier/evaluate", t, t + 2.0, f"g{i}",
                        None, f"g{i}"))
        t += 2.01
    calls = rs.select_generations(recs, 0, 2, 4.0)
    assert calls
    return calls


@pytest.mark.parametrize("fields,want", [
    # every source of every generation lowered by a worker
    ({"sources": 8, "traces": 8, "pooled": 8, "workers": 7}, 100.0),
    # lowered in process (one usable core, a broken pool): a reading
    ({"sources": 8, "traces": 8, "pooled": 0, "workers": 0}, 0.0),
    # workers that were still starting: the rest was lowered in process
    ({"sources": 8, "pooled": 4, "workers": 2}, 50.0),
    ({"sources": 0, "pooled": 0, "workers": 0}, None),
    # a parent without the fields (older than PR 39)
    ({"sources": 8, "traces": 8, "ops_lowered": 2614, "ops_kept": 2026},
     None),
    ({}, None),
])
def test_pooled_source_share_reads_the_transpile_spans_fields(fields, want):
    got = cells.metric_reader("tier.pooled_source_share")(
        {"_span_calls": _transpile_calls(fields)})
    assert got == (want if want is None else pytest.approx(want))


def test_a_program_without_the_ring_reads_as_nothing(monkeypatch):
    from fks_tpu.obs import spans

    monkeypatch.delattr(spans, "LOG")
    assert rs.ring() is None
    ctx = {"queries": 4, "calls": 2, "call_seconds": 0.16}
    assert rs.window_calls(ctx) is None
    for name in SPAN_METRICS:
        assert cells.metric_reader(name)(dict(ctx)) is None


def test_every_span_metric_is_declared_with_its_files():
    bench = json.load(open(os.path.join(cells.ROOT, "BENCHMARK.json")))
    declared = {m["name"]: m for m in bench["per_layer"]}
    # + vm.scatter_write_share (PR 35), serve.fork_state_ms and
    # serve.retry_share (PR 37), tier.pooled_source_share (PR 39),
    # sim.fork_replay_us_per_event and sim.fork_waiting_pods (PR 42),
    # vm.merged_read_share (PR 44), sim.typed_pod_share (PR 45),
    # vm.slots_per_turn (PR 46), vm.narrow_turn_share (PR 47),
    # serve.typed_pod_share (PR 49), tier.check_ms_per_source and
    # tier.uploads_per_call (PR 51), serve.heap_replay_ms_per_call,
    # serve.fork_waiting_pods and serve.finished_lane_share (PR 52)
    assert len(SPAN_METRICS) == 39
    for name in SPAN_METRICS:
        assert name in declared, name
        meta = json.load(open(os.path.join(cells.HERE, "metrics",
                                           name + ".json")))
        for key in ("unit", "better", "source", "layer", "moves"):
            assert meta[key] == declared[name][key], (name, key)
        assert declared[name]["workloads"], name


# ------------------------------------------------- the cells, end to end

def _traced_cell(name, tmp_path, monkeypatch):
    from chipbench.selftest.tests import TINY, batched_vm_on_cpu
    from fks_tpu import utils
    from fks_tpu.obs import spans

    # the benchmark is one fresh process: generations are counted from
    # its warm-up call, so what earlier tests of this worker left in the
    # ring has to go
    spans.LOG.clear()
    # tier-1 keeps the persistent compile cache off (conftest)
    monkeypatch.setattr(utils, "place_compile_cache", lambda: str(tmp_path))
    # what the chip picks by itself: the batched VM tier, bounded segments
    monkeypatch.setenv("FKS_VM_SEG_STEPS", "32")
    # the reducer's self-check holds the spans' extent to the driver's
    # clock within 0.5 %: of a 1.6 s call on the chip, but of a 50 ms call
    # here, where a thread hand-off under a loaded test run is more than
    # that. This test is about presence, so it gets room
    monkeypatch.setattr(rs, "TOLERANCE", 0.05)
    with batched_vm_on_cpu(), contextlib.redirect_stdout(io.StringIO()):
        return run.run_cell(name, 2 ** 31 + 5, 0.5, True, require_tpu=False,
                            overrides=TINY)


@pytest.mark.parametrize("name", ["openb1523.whatif8", "openb16.codegen8x4"])
def test_traced_cell_reports_every_span_metric(name, tmp_path, monkeypatch):
    res = _traced_cell(name, tmp_path, monkeypatch)
    assert res["correct"] and res["failed"] == 0
    cell = cells.load_cell(name)
    want = [m["name"] for m in cell.per_layer if m["name"] in SPAN_METRICS]
    assert want
    for m in want:
        assert m in res["metrics"], m
        assert math.isfinite(res["metrics"][m]["value"]), m
    if name == "openb1523.whatif8":
        assert len(want) == 11
        assert 0 < res["metrics"]["vm.live_slot_share.serve"]["value"] <= 100
        v = {m: res["metrics"][m]["value"] for m in want}
        # exposed + waited is the call, as the driver's own clock has it
        per_call = v["serve.exposed_host_ms_per_call"] \
            + v["serve.wait_device_ms_per_call"]
        assert v["serve.wait_device_ms_per_call"] > 0
        assert per_call > v["serve.stack_ms_per_call"] \
            + v["serve.h2d_ms_per_call"] + v["serve.harvest_ms_per_call"]
        assert v["serve.h2d_kb_per_call"] > 0 and v["serve.d2h_kb_per_call"] > 0
    else:
        assert len(want) == 18
        # the checks ran with the lowerings (PR 51), and the programs of
        # a generation reach the devices in one sharded put of 8 leaves
        assert res["metrics"]["tier.check_ms_per_source"]["value"] > 0
        assert res["metrics"]["tier.uploads_per_call"]["value"] == 8.0
        # a recorded generation: every source traced once, where it runs,
        # and the simplifier dropped part of what the lowering emitted
        assert res["metrics"]["tier.traces_per_source"]["value"] == 1.0
        assert 0 < res["metrics"]["vm.ops_kept_share"]["value"] < 100
        assert 0 < res["metrics"]["vm.live_slot_share"]["value"] <= 100
        assert res["metrics"]["mesh.host_ms_per_call"]["value"] > 0
        # the mesh runner kept every register write one slice
        assert res["metrics"]["vm.scatter_write_share"]["value"] == 0.0
        # and fetched every slot's three rows with one gather
        assert res["metrics"]["vm.merged_read_share"]["value"] == 100.0
        # and turned its op-slot loop once a block of slots
        from fks_tpu.funsearch import vm
        from fks_tpu.obs import spans

        launch = [r.fields for r in spans.LOG.snapshot()
                  if r.name == "tier/vm_batch/launch"][-1]
        assert launch["blocked_loops"] >= 1 and launch["plain_loops"] == 0
        assert launch["turns"] == -(-launch["slots"] // vm.SLOT_BLOCK)
        assert res["metrics"]["vm.slots_per_turn"]["value"] \
            == pytest.approx(launch["slots"] / launch["turns"])
        # and ran the narrow opcode table but where a lane holds a WIDE
        # opcode (a ledger champion holds one REM)
        assert 0 <= launch["wide_turns"] <= 1
        assert res["metrics"]["vm.narrow_turn_share"]["value"] \
            == pytest.approx(100.0 * (1 - launch["wide_turns"]
                                      / launch["turns"]))
        # the generations went through the process's lowering pool as
        # far as this machine has the cores and the workers were up
        assert 0.0 <= res["metrics"]["tier.pooled_source_share"]["value"] \
            <= 100.0
