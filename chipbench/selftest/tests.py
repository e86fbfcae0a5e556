"""The selftest's tests (see ``__main__``). Tiny sizes, CPU only."""
from __future__ import annotations

import collections
import contextlib
import glob
import io
import json
import os
import subprocess
import sys
import time
from unittest import mock

import numpy as np

from chipbench import cells, window
from chipbench.reduce import anchor, xplane
from chipbench.reduce.spans import Call
from chipbench.reference import data, policies
from chipbench.reference import plain_sim as ps
from chipbench.reference.compare import Output, compare
from chipbench.reference.nearties import admit

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURES = os.path.join(cells.ROOT, "tests", "fixtures")
CELLS = ("openb16.param256", "openb1523.whatif8", "openb16.codegen8",
         "openb16.codegen8x4")
#: tiny sizes for the CPU: the first 150 pods, 64-event generations of 4
TINY = {"config": {"pod_limit": 150, "code_eval_max_steps": 64},
        "traffic": {"lanes": 4, "sizes": [4, 8, 20], "max_batch": 3,
                    "trace_for_s": 0.05,
                    "max_wait_s": 2.0}}
GUARANTEES = {"fitness_rtol": 16 * 2.0 ** -23, "score_near_tie_units": 1,
              "near_ties_per_run": 2}


@contextlib.contextmanager
def batched_vm_on_cpu():
    """``CodeEvaluator`` picks its batched VM tier where the backend is an
    accelerator; the selftest's CPU has to ask for it. The benchmark's
    driver passes no such option."""
    from fks_tpu.funsearch.backend import CodeEvaluator

    real = CodeEvaluator.__init__

    def init(self, *a, **kw):
        kw.setdefault("vm_batch", True)
        real(self, *a, **kw)

    with mock.patch.object(CodeEvaluator, "__init__", init):
        yield


def _quiet(fn, *a, **kw):
    with contextlib.redirect_stdout(io.StringIO()):
        return fn(*a, **kw)


# ------------------------------------------------------- trace reduction

def test_reduce_recorded_v5e_trace():
    """The recorded v5e trace (3 launches of one small program inside a
    ``bench/call`` span): busy time is the sum of its 9 instructions."""
    red = xplane.reduce_trace(os.path.join(HERE, "fixtures",
                                           "tiny.xplane.pb"),
                              window="bench/call")
    assert red["chips"] == 1
    assert abs(red["busy_s"] - 5.519e-06) < 1e-10, red
    assert abs(red["window_s"] - 0.01044296) < 1e-9
    assert red["device_ops"][0][0] == "fusion f32[]"
    assert red["device_events"] == 9
    assert red["idle_gaps"][0][0] == "bench/call over_1ms"
    assert abs(red["busy_s"] + sum(g[1] for g in red["idle_gaps"])
               - red["window_s"]) < 1e-9


def test_reduce_leaves_loop_containers_out():
    """A ``%while`` event lasts its whole loop; only its body is work."""
    devices = {0: [(0, 100, "%while.3 = (s32[4]) while(...)"),
                   (5, 45, "%cond.17.clone.9 = f32[4,1,64,8]{3,2,1,0} "
                           "conditional(%p, %a, %b), branch_computations={}"),
                   (10, 20, "%fusion.1 = f32[8]{0} fusion(...)"),
                   (15, 40, "%fusion.2 = f32[8]{0} fusion(...)")],
               1: [(0, 50, "%fusion.1 = f32[8]{0} fusion(...)")]}
    spans = [(0, 200, "bench/trace_window"), (50, 200, "bench/x")]
    red = xplane.reduce_events(devices, spans)
    assert red["chips"] == 2
    assert abs(red["busy_s"] - (30e-9 + 50e-9) / 2) < 1e-15   # mean of chips
    assert red["window_s"] == 200e-9
    gaps = dict(map(tuple, red["idle_gaps"]))     # of chip 0: 0-10, 40-200
    assert abs(gaps["bench/x under_10us"] - 160e-9) < 1e-15
    assert abs(gaps["bench/trace_window under_10us"] - 10e-9) < 1e-15
    # a gap is named by its length too: microseconds lie between the
    # instructions of one program, milliseconds are the host's
    devices[0].append((5_000_000, 5_000_010, "%fusion.1 = f32[8]{0} fusion(...)"))
    spans[:] = [(0, 6_000_000, "bench/trace_window")]
    long = dict(map(tuple, xplane.reduce_events(devices, spans)["idle_gaps"]))
    assert "bench/trace_window over_1ms" in long
    del devices[0][-1]
    spans[:] = [(0, 200, "bench/trace_window"), (50, 200, "bench/x")]
    # what the device ran long before the span opened (a warm-up inside
    # the same trace) neither counts nor moves the clocks' alignment
    devices[0].insert(0, (-10 ** 10, -10 ** 10 + 500, "%fusion.0 = f32[1] fusion()"))
    again = xplane.reduce_events(devices, spans)
    assert again["busy_s"] == red["busy_s"]
    assert xplane.reduce_events({}, spans) is None             # a CPU trace


# ------------------------------------------- where the traced slice goes

Rec = collections.namedtuple("Rec", "name t0 t1 trace_id fields",
                             defaults=("t", None))


def _generation(t: float, host_s: float, device_s: float) -> Call:
    """One generation's spans as the program leaves them: ``host_s`` of
    preflight, transpile and stacking, ``device_s`` of launch + wait, then
    40 ms of transfer and records."""
    d0, d1 = t + host_s, t + host_s + device_s
    end = d1 + 0.04
    recs = [Rec("tier/preflight", t, t + 0.05),
            Rec("tier/transpile", t + 0.05, d0 - 0.02),
            Rec("tier/vm_batch/stack_programs", d0 - 0.02, d0),
            Rec("tier/vm_batch/launch", d0, d0 + 0.001),
            Rec("tier/vm_batch/wait_device", d0 + 0.001, d1),
            Rec("tier/vm_batch/d2h", d1, d1 + 0.03),
            Rec("tier/record", d1 + 0.03, end),
            Rec("tier/evaluate", t, end)]
    return Call(t, end, recs)


def test_the_slice_follows_the_generation_where_three_seconds_do_not():
    """The four-chip generation as the ledger has it (0.9 s of host stages,
    2.26 s of device loop), the same with the loop a quarter shorter and
    with the transpile 0.25 s shorter: the slice lies inside the device
    stage every time; ``3.0 s after the first call's start``, the accepted
    harness's constant, only the first time (it then falls into the NEXT
    call's host stages). Cut further (a loop of 1.1 s, a transpile of
    0.2 s) the constant lands in the next call's loop by luck; the slice
    placed from the calls does not care."""
    from chipbench.drivers import codegen

    for_s = 0.01
    for host_s, device_s, fixed_is_inside in (
            (0.9, 2.26, True), (0.9, 1.7, False), (0.65, 2.26, False),
            (0.9, 1.1, True), (0.27, 2.26, True)):
        calls, t = [], 100.0
        for _ in range(5):
            calls.append(_generation(t, host_s, device_s))
            t = calls[-1].t1
        stage = anchor.stage_of(calls, codegen.Driver.device_stage, for_s)
        assert stage.source == anchor.FROM_DRIVER
        assert abs(stage.d0 - host_s) < 1e-9, stage
        assert abs(stage.d1 - host_s - device_s) < 1e-9, stage
        begin = anchor.place(stage, for_s, 0.5)
        assert stage.d0 <= begin and begin + for_s <= stage.d1
        assert abs(begin + for_s / 2 - (stage.d0 + stage.d1) / 2) < 1e-9
        # where 3.0 s of wall clock after the first call's start falls
        fixed = 3.0 % stage.call_s
        assert (stage.d0 <= fixed and fixed + for_s <= stage.d1) \
            is fixed_is_inside, (host_s, device_s, fixed)
        if not fixed_is_inside:      # it is in the next call's host stages
            assert 3.0 >= stage.call_s and fixed + for_s < host_s
        # a phase near the end is clipped to end before the stage does
        late = anchor.place(stage, for_s, 1.0)
        assert abs(late + for_s - stage.d1) < 1e-9
        assert anchor.place(stage, for_s, 0.0) == stage.d0


def test_without_spans_the_slice_is_placed_in_the_calls_extent():
    """A program without the ring (or a selection that failed its check)
    leaves the window's rows: the stage is the call, and the row says so;
    a stage shorter than the slice is covered from its start."""
    from chipbench.drivers import codegen, population

    rows = [{"t0": 0.0, "t1": 3.2}, {"t0": 3.2, "t1": 6.5},
            {"t0": 6.5, "t1": 9.7}]
    calls = anchor.calls_of_rows(rows)
    stage = anchor.stage_of(calls, codegen.Driver.device_stage, 0.03)
    assert stage.source == anchor.FROM_EXTENT
    assert (stage.d0, round(stage.d1, 9), round(stage.call_s, 9)) \
        == (0.0, 3.2, 3.2)
    assert abs(anchor.place(stage, 0.03, 0.5) - (1.6 - 0.015)) < 1e-9
    # one call without the stage among calls that have it: the extent too
    mixed = [_generation(0.0, 0.9, 2.26), calls[1]]
    assert anchor.stage_of(mixed, codegen.Driver.device_stage,
                           0.03).source == anchor.FROM_EXTENT
    # the population's call is one device program: its extent by intent
    whole = anchor.stage_of(calls, population.Driver.device_stage, 0.1)
    assert whole.source == anchor.FROM_DRIVER and whole.d0 == 0.0
    short = anchor.Stage(0.5, 0.52, 1.0, anchor.FROM_DRIVER)
    assert anchor.place(short, 0.25, 0.5) == 0.5
    assert anchor.innermost([Rec("a", 0, 10), Rec("b", 2, 3)], 2.5) == "b"
    assert anchor.innermost([Rec("a", 0, 10)], 11) is None


def _coalesced(t: float, waits) -> Call:
    """A whatif call of three chunks (2 x 16, 4 x 64, 2 x 256) whose
    ``serve/chunk/wait_device`` spans last ``waits``."""
    recs, at = [Rec("serve/batch", t, t + 1.05, "b")], t
    for chunk, ((bucket, lanes), wait) in enumerate(zip(
            ((16, 2), (64, 4), (256, 2)), waits)):
        f = {"chunk": chunk}
        recs += [Rec("serve/chunk/stack", at, at + 0.2, "b",
                     {**f, "bucket": bucket, "lanes": lanes}),
                 Rec("serve/chunk/enqueue", at + 0.2, at + 0.21, "b", f),
                 Rec("serve/chunk/wait_device", at + 0.25, at + 0.25 + wait,
                     "b", f)]
        at += 0.25
    # another batch's chunk 2 in the same extent is not this call's
    recs.append(Rec("serve/chunk/wait_device", t, t + 0.9, "other",
                    {"chunk": 2}))
    return Call(t, t + 1.05, recs)


def test_the_serving_slice_reads_the_largest_chunks_loop():
    from chipbench.drivers import whatif

    call = _coalesced(50.0, (0.001, 0.002, 0.55))
    t0, t1 = whatif.Driver.device_stage(call, 0.25)
    assert (round(t0 - 50.0, 9), round(t1 - 50.0, 9)) == (0.75, 1.3)
    stage = anchor.stage_of([call], whatif.Driver.device_stage, 0.25)
    begin = anchor.place(stage, 0.25, 0.5)
    assert stage.d0 <= begin and begin + 0.25 <= stage.d1
    # a wait shorter than the slice: from the chunk's enqueue on
    call = _coalesced(50.0, (0.001, 0.002, 0.2))
    t0, t1 = whatif.Driver.device_stage(call, 0.25)
    assert (round(t0 - 50.0, 9), round(t1 - 50.0, 9)) == (0.7, 0.95)
    assert whatif.Driver.device_stage(Call(0.0, 1.0, []), 0.25) is None


class _StubDriver:
    """Whole calls of 100 ms without a span: the slice is placed in the
    call's extent."""
    span = "bench/stub_call"

    def __init__(self):
        self.made = []

    def call(self, i: int) -> dict:
        self.made.append(i)
        time.sleep(0.1)
        return {}

    @staticmethod
    def device_stage(call, for_s):
        return None


def test_a_missed_slice_is_retaken_then_named():
    """A slice in which no device instruction worked is taken again in
    the next call, three times in all; after the third miss the run fails
    and says where each fell. Few device events are no miss."""
    from chipbench import run

    hit = {"busy_s": 1e-6, "window_s": 0.01, "chips": 1, "device_events": 1,
           "device_ops": [], "idle_gaps": []}
    window = [Call(0.0, 0.1, [])]
    for answers, attempts in (([hit], 1), ([None, dict(hit, device_events=0),
                                            hit], 3)):
        reduced = mock.Mock(side_effect=answers)
        d = _StubDriver()
        with mock.patch.object(xplane, "reduce_trace", reduced), \
                mock.patch.object(glob, "glob", lambda *a: ["a trace"]):
            got = run.trace_slice(d, window, 0.01, 0.5, 0.001, True)
        assert got["attempts"] == attempts == reduced.call_count, got
        assert got["device"] is hit and got["stage_from"] == "call_extent"
        assert got["calls_during_trace"] == len(d.made) >= attempts
        assert d.made == [-2 - i for i in range(len(d.made))]
        assert got["call_offset_s"][1] - got["call_offset_s"][0] >= 0.01
        assert got["profiler_start_s"] > 0 and got["stage_s"][0] == 0.0
        assert got["inside"] in ("bench/stub_call", "_between_calls_")
    reduced = mock.Mock(side_effect=[None, None, None])
    with mock.patch.object(xplane, "reduce_trace", reduced), \
            mock.patch.object(glob, "glob", lambda *a: ["a trace"]):
        try:
            run.trace_slice(_StubDriver(), window, 0.01, 0.5, 0.001, True)
        except SystemExit as e:
            said = str(e)
        else:
            raise AssertionError("three misses gave a result")
    assert reduced.call_count == 3
    assert said.count("s of a call") == 3 and said.count("phase") == 3, said
    assert "from call_extent" in said and "inside " in said, said
    # device events, but outside the device stage its own call turned out
    # to have (the window's calls showed it at 0-20 ms, the calls made
    # here have it at 100-200 ms of 200): retaken, placed by those
    class Moved(_StubDriver):
        def call(self, i):
            self.made.append(i)
            time.sleep(0.2)
            return {}

        @staticmethod
        def device_stage(call, for_s):
            return (call.t0, call.t0 + 0.02) if call.t1 < 1.0 \
                else (call.t0 + 0.1, call.t0 + 0.2)

    reduced = mock.Mock(side_effect=[hit, hit, hit])
    with mock.patch.object(xplane, "reduce_trace", reduced), \
            mock.patch.object(glob, "glob", lambda *a: ["a trace"]):
        got = run.trace_slice(Moved(), [Call(0.0, 0.2, [])], 0.02, 0.5, 0.001,
                              True)
    assert got["attempts"] == 2 and got["in_stage"], got
    assert got["stage_from"] == "driver" and got["stage_s"][0] > 0.09, got
    # the selftest's CPU runs: no device plane is a legal answer, once
    reduced = mock.Mock(side_effect=[None])
    with mock.patch.object(xplane, "reduce_trace", reduced):
        got = run.trace_slice(_StubDriver(), window, 0.01, 0.5, 0.001, False)
    assert got["attempts"] == 1 and got["device"] is None


# ------------------------------------------------------- rate arithmetic

def test_rates_count_whole_calls_only():
    """Calls of 2 s in a 5 s window: three whole calls, the clock stops
    at 6 s, and the rate is the same wherever the edge falls."""
    for seconds in (4.1, 5.0, 5.9):
        clock = iter(float(x) for x in (0, 0, 2, 2, 4, 4, 6, 6, 8, 8))
        rows, elapsed = window.run_window(lambda i: {"work": 10}, seconds,
                                          clock=lambda: next(clock))
        assert (len(rows), elapsed) == (3, 6.0), (seconds, rows)
        assert window.rate(rows, "work", elapsed) == 5.0


# --------------------------------------------- plain reference vs upstream

def _fuzz_case(case):
    nodes, pods = case["nodes"], case["pods"]
    g = max(1, max(len(n["gpus"]) for n in nodes))
    mask = np.array([[j < len(n["gpus"]) for j in range(g)] for n in nodes])
    cl = ps.Cluster(
        np.array([n["cpu_milli"] for n in nodes]),
        np.array([n["memory_mib"] for n in nodes]),
        np.array([n.get("gpu_declared", len(n["gpus"])) for n in nodes]),
        np.array([len(n["gpus"]) for n in nodes]),
        np.array([[n["gpus"][j] if j < len(n["gpus"]) else 0
                   for j in range(g)] for n in nodes]), mask)
    ids = [q["pod_id"] for q in pods]
    rank = np.zeros(len(ids), np.int64)
    rank[sorted(range(len(ids)), key=lambda i: ids[i])] = np.arange(len(ids))
    col = lambda k: np.array([q[k] for q in pods])  # noqa: E731
    return cl, ps.Pods(col("cpu_milli"), col("memory_mib"), col("num_gpu"),
                       col("gpu_milli"), col("creation_time"),
                       col("duration_time"), rank)


def test_plain_sim_matches_upstream_fuzz_goldens():
    """All 48 adversarial micro workloads recorded from upstream, both of
    its baseline scorers: counts exact, utilizations and fitness to 1e-6."""
    with open(os.path.join(FIXTURES, "golden_fuzz.json")) as f:
        doc = json.load(f)
    for case in doc["cases"]:
        cl, pods = _fuzz_case(case)
        for name in ("first_fit", "best_fit"):
            want = case["results"][name]
            r = ps.simulate(cl, pods, getattr(policies, name),
                            max_steps=10 ** 9)
            got = (r.events_processed, r.num_snapshots, r.scheduled_pods,
                   r.num_frag_events, r.max_nodes, bool(r.failed))
            exp = tuple(want[k] for k in (
                "events_processed", "num_snapshots", "scheduled_pods",
                "num_fragmentation_events", "max_nodes")) \
                + (bool(want["aborted"]),)
            assert got == exp, (case["id"], name, got, exp)
            assert r.assigned_node.tolist() == want["assignments"]
            assert [[j for j in range(8) if b >> j & 1]
                    for b in r.assigned_gpus] == want["assigned_gpus"]
            for k, v in zip(("avg_cpu_utilization",
                             "avg_memory_utilization",
                             "avg_gpu_count_utilization",
                             "avg_gpu_memory_utilization"), r.avg_util):
                assert abs(float(v) - want[k]) < 1e-6, (case["id"], name, k)
            assert abs(r.policy_score - want["policy_score"]) < 1e-6
            assert abs(r.frag_mean - want["gpu_fragmentation_score"]) < 1e-6


def test_plain_sim_matches_upstream_micro_golden():
    with open(os.path.join(FIXTURES, "golden_micro.json")) as f:
        want = json.load(f)
    cl = ps.Cluster(np.array([8000, 4000]), np.array([16000, 8000]),
                    np.array([2, 0]), np.array([2, 0]),
                    np.array([[1000, 1000], [0, 0]]),
                    np.array([[True, True], [False, False]]))
    rows = want["pods"]
    col = lambda k: np.array([q[k] for q in rows])  # noqa: E731
    pods = ps.Pods(col("cpu_milli"), col("memory_mib"), col("num_gpu"),
                   col("gpu_milli"), col("creation_time"),
                   col("duration_time"), np.arange(len(rows)))

    def micro_best_fit(pod, s, cand):   # upstream tests/test_simulator.py
        feas = ps._feasible(pod, s)
        return [1_000_000 // max(1, int(
            s.cpu_left[i] - pod.cpu_milli + s.mem_left[i] - pod.memory_mib
            + s.gpu_left[i] - pod.num_gpu + 1)) if feas[i] else 0
            for i in cand]

    r = ps.simulate(cl, pods, micro_best_fit)
    assert r.assigned_node.tolist() == want["assignments"]
    assert [[j for j in range(8) if b >> j & 1] for b in r.assigned_gpus] \
        == want["assigned_gpus"]
    assert (r.scheduled_pods, r.max_nodes) == (want["scheduled_pods"],
                                               want["max_nodes"])


# ------------------------------------------------------------- data files

def test_edited_trace_fails_the_run():
    cell = cells.load_cell("openb16.param256")
    cells.verify_files(cell.config)
    cell.config["trace"]["sha256"] = "0" * 64
    try:
        cells.verify_files(cell.config)
    except SystemExit as e:
        assert "pins" in str(e)
    else:
        raise AssertionError("a wrong hash passed")


def test_pinned_population_is_the_e1_population():
    import jax
    from fks_tpu.models import parametric

    t = cells.load_cell("openb16.param256").traffic
    pop = np.loadtxt(os.path.join(cells.ROOT, t["population_file"]),
                     delimiter=",", skiprows=1, dtype=np.float32)
    want = np.asarray(parametric.init_population(
        jax.random.PRNGKey(0), 256, noise=0.1))
    assert pop.shape == want.shape == (256, 16)
    assert np.abs(pop - want).max() < 1e-6


def test_every_metric_and_cell_has_its_files():
    with open(os.path.join(cells.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    e2e = {m["name"] for m in bench["end_to_end"]}
    names = {w["name"] for w in bench["workloads"]}
    for m in bench["per_layer"] + bench["end_to_end"]:
        doc = cells._json(os.path.join(cells.HERE, "metrics",
                                       m["name"] + ".json"))
        for k in set(m) - {"name", "workloads", "bound"}:
            assert doc[k] == m[k], (m["name"], k)
        assert m.get("moves", m["name"]) in e2e
        assert set(m.get("workloads", ())) <= names
        # a reader with nothing to read reports nothing
        assert cells.metric_reader(m["name"])({}) is None
    rows = [{"t0": 0.0, "t1": 2.0, "evals": 3}, {"t0": 2.0, "t1": 4.0,
                                                 "evals": 5}]
    assert cells.metric_reader("evals_per_s")(
        {"rows": rows, "elapsed_s": 4.0}) == 2.0
    assert cells.metric_reader("setup_s")({"setup_s": 12.5}) == 12.5
    for w in bench["workloads"]:
        cell = cells.load_cell(w["name"])
        cells.load_driver(cell.traffic["driver"]).Driver
    # the four-chip cell runs the one-chip cell's mix under its own name
    a, b = (cells.load_cell(n).traffic for n in ("openb16.codegen8",
                                                 "openb16.codegen8x4"))
    b.pop("same_as")
    # four chips write four times the device events, and a shard that
    # holds both seed policies is done at half of the stage
    assert b.pop("trace_phase") < 0.5 and "trace_phase" not in a
    assert b.pop("trace_phase_why")
    for t in (a, b):
        t.pop("trace_for_s"), t.pop("traced")
    assert a == b


# -------------------------------------------------------- seed invariance

def test_work_per_call_is_the_same_for_every_seed():
    """``--seed`` chooses which data, never how much work: lanes,
    lane-events, pods and chunk shapes per call for seeds 0-3."""
    from fks_tpu.funsearch import vm
    from fks_tpu.serve import ShapeEnvelope

    seen = {c: set() for c in CELLS}
    longest = 0        # live ops of the longest program any seed compiles
    for seed in range(4):
        for name in CELLS:
            cell = cells.load_cell(name)
            files = cells.verify_files(cell.config)
            d = cells.load_driver(cell.traffic["driver"]).Driver(
                cell, seed, files, None, False)
            if cell.traffic["driver"] == "codegen":
                srcs = d._sources()
                ops = [int(vm.compile_policy(s, 16, 8).n_ops) for s in srcs]
                longest = max(longest, *ops)
                assert len(set(srcs)) == len(srcs) == 8
                shorts = sum(len(s) < 2000 for s in srcs)   # seed policies
                seen[name].add((len(srcs), vm.capacity_bucket(max(ops)),
                                shorts, len(srcs)
                                * int(cell.config["code_eval_max_steps"])))
            elif cell.traffic["driver"] == "whatif":
                _, d.pods = None, data.load_pods(files["trace"])
                d.rng = np.random.default_rng(seed)
                env = ShapeEnvelope(max_batch=cell.traffic["max_batch"])
                q = d._queries(cell.traffic["sizes"])
                chunks = {}
                for _, rows in q:
                    b = env.pod_bucket_for(len(rows))
                    chunks[b] = chunks.get(b, 0) + 1
                seen[name].add((sum(len(r) for _, r in q),
                                tuple(sorted(chunks.items()))))
            else:
                seen[name].add((int(cell.traffic["lanes"]),
                                cell.config["param_eval_max_steps_factor"]))
    assert all(len(v) == 1 for v in seen.values()), seen
    assert seen["openb1523.whatif8"] == {(416, ((16, 2), (64, 4), (256, 2)))}
    # the generation's bucket is the program's: a lowering that packs the
    # champions into a smaller one moves it for every seed alike
    assert seen["openb16.codegen8"] == {
        (8, vm.capacity_bucket(longest), 2, 8 * 2048)}


def test_population_driver_permutes_the_pinned_lanes():
    cell = cells.load_cell("openb16.param256", TINY)
    files = cells.verify_files(cell.config)
    sets = []
    for seed in (0, 1, 2 ** 31 + 5):
        d = cells.load_driver("population").Driver(cell, seed, files, None,
                                                   False)
        _quiet(d.setup)
        sets.append(d.weights)
        r0, r1 = d.call(0), d.call(1)
        assert r0 == r1
    assert not np.array_equal(sets[0], sets[1])
    key = lambda w: sorted(map(tuple, w.tolist()))  # noqa: E731
    assert key(sets[0]) == key(sets[1]) == key(sets[2])


# ------------------------------------------------- every cell, end to end

def _run(name, seed=3, trace=False, seconds=0.5):
    from chipbench import run
    with batched_vm_on_cpu():
        return _quiet(run.run_cell, name, seed, seconds, trace,
                      require_tpu=False, overrides=TINY)


def test_cells_run_end_to_end_on_four_cpu_devices():
    import jax
    assert len(jax.devices()) >= 4, "selftest needs 4 virtual CPU devices"
    for name in CELLS:
        for trace in (False, True):
            r = _run(name, trace=trace)
            assert r["correct"] is True and r["failed"] == 0, (name, r)
            assert r["attempted"] > 0 and r["metrics"], (name, r)
            assert r["device"]["platform"] == "cpu"   # never a device number
            if not trace:
                assert set(r["metrics"]) == {
                    m["name"] for m in cells.load_cell(name).end_to_end}
        if name.endswith("x4"):
            assert r["device"]["count"] == 4
            assert r["metrics"]["mesh.min_lanes_per_device"]["value"] >= 1


def test_a_broken_timed_path_comes_out_not_correct():
    """Everything but the look for a chip, with an answer altered where it
    is produced: one lane's placement of one pod moved to another node."""
    import fks_tpu.parallel as par
    from fks_tpu.serve.artifact import ServeEngine

    real = par.make_population_eval

    def broken(*a, **kw):
        ev = real(*a, **kw)

        def run(params):
            res = ev(params)
            nodes = np.array(res.assigned_node)
            nodes[:, 0] = (nodes[:, 0] + 1) % 16
            return res._replace(assigned_node=nodes) \
                if hasattr(res, "_replace") else \
                __import__("dataclasses").replace(res, assigned_node=nodes)
        return run

    with mock.patch.object(par, "make_population_eval", broken):
        assert _run("openb16.param256")["correct"] is False

    extract = ServeEngine._extract

    def moved(self, *a, **kw):
        ans = extract(self, *a, **kw)
        ans["placements"][0]["node"] += 1
        return ans

    with mock.patch.object(ServeEngine, "_extract", moved):
        assert _run("openb1523.whatif8")["correct"] is False


def test_a_tie_broken_another_way_is_a_difference():
    """first_fit scores every feasible node alike, so the reference takes
    the lowest index (upstream's rule). An output that took another of the
    tied nodes for one pod scores no worse anywhere and is still not the
    reference's trajectory: ``compare`` has no tolerance of its own (what
    ``nearties.admit`` does before it is tested further down)."""
    with open(os.path.join(FIXTURES, "golden_fuzz.json")) as f:
        cases = json.load(f)["cases"]
    checked = 0
    for case in cases:
        cl, pods = _fuzz_case(case)
        # the first event of the run meets the empty cluster
        pod = min(range(pods.p), key=lambda i: (pods.creation_time[i],
                                                pods.rank[i]))
        first = ps.PodObj()
        first.cpu_milli, first.memory_mib = (int(pods.cpu[pod]),
                                             int(pods.mem[pod]))
        first.num_gpu, first.gpu_milli = (int(pods.num_gpu[pod]),
                                          int(pods.gpu_milli[pod]))
        tied = np.nonzero(ps._feasible(first, ps.State(cl)))[0]
        if len(tied) < 2:
            continue
        ref = ps.simulate(cl, pods, policies.first_fit, max_steps=10 ** 9)
        got = Output.of_lane(ref, pods.p)
        assert all(n.ok for n in compare("same", ref, got, GUARANTEES))
        assert ref.assigned_node[pod] == tied[0]
        got.assigned_node = got.assigned_node.copy()
        got.assigned_node[pod] = tied[1]
        bad = compare("tie", ref, got, GUARANTEES)
        assert [n.name for n in bad if not n.ok] == ["tie.placements_differ"]
        checked += 1
    assert checked >= 10, checked


# ------------------------------------------------ the control (low precision)

def _control(cluster, pods, make_policy, **kw):
    """The reference in bfloat16 (scores and evaluator sums) put in the
    program's place: its output goes through the same comparison with the
    reference's free run and has to fail it."""
    import ml_dtypes
    low = ps.simulate(cluster, pods, make_policy(True),
                      acc_dtype=ml_dtypes.bfloat16, **kw)
    ref, ties = admit(
        lambda decide: ps.simulate(cluster, pods, make_policy(False),
                                   decide=decide, **kw),
        low.assigned_node, GUARANTEES, "control")
    return [ties] + compare("control", ref, Output.of_lane(low, pods.p),
                            GUARANTEES)


def test_control_lower_precision_is_not_correct():
    t = os.path.join(cells.ROOT, "benchmarks", "traces")
    cluster = data.load_cluster(t + "/csv/gpu_models_filtered.csv.gz",
                                t + "/gpu_mem_mapping.json")
    pods = data.load_pods(t + "/csv/openb_pod_list_default.csv.gz")
    pods = pods.take(range(600))
    pods.rank[pods.rank.argsort()] = np.arange(600)
    pop = np.loadtxt(os.path.join(cells.ROOT, "chipbench", "traffic",
                                  "param256_population.csv"),
                     delimiter=",", skiprows=1, dtype=np.float32)
    with open(sorted(__import__("glob").glob(os.path.join(
            cells.ROOT, "policies", "discovered", "funsearch_*.json")))[-1]
    ) as f:
        code = json.load(f)["code"]
    makers = [lambda lp: policies.parametric_policy(pop[7], lp),
              lambda lp: policies.parametric_policy(pop[1], lp),
              lambda lp: policies.source_policy(code, lp, dtype="float32")]
    for make in makers:
        control = _control(cluster, pods, make, retry="earliest_delete",
                           max_steps=10 ** 6)
        assert not all(n.ok for n in control), control


# ------------------------------- the reference's precision is the cell's

#: a weighted sum of two ratios, a count over a length and a literal
#: product, as the ledger's champions are written
ROUNDING_SOURCE = """
def priority_function(pod, node):
    fit = sum(1 for gpu in node.gpus if gpu.gpu_milli_left >= pod.gpu_milli)
    score = SCALE * ((0.101951) * (1.0)
    + (0.344618) * (1.0 - node.cpu_milli_left / max(1, node.cpu_milli_total))
    + (0.141436) * ((node.memory_mib_left - pod.memory_mib) / max(1, node.memory_mib_total))
    + (0.384283) * (fit / max(1, len(node.gpus))))
    return max(1, int(score))
"""


def _one_node(cpu_left, cpu_total, mem_left, mem_total, gpus):
    nd = ps.NodeObj()
    nd.cpu_milli_left, nd.cpu_milli_total = cpu_left, cpu_total
    nd.memory_mib_left, nd.memory_mib_total = mem_left, mem_total
    nd.gpu_left, nd.gpus = len(gpus), []
    for left in gpus:
        g = ps.GPUObj(1000)
        g.gpu_milli_left = left
        nd.gpus.append(g)
    return nd


def test_float32_source_policy_rounds_every_operation_to_float32():
    """``source_policy(dtype="float32")`` is the source with one float32
    rounding an operation, in the source's order, constants folded in
    Python first: written out by hand in ``numpy.float32`` for a source of
    the champions' form, it gives the same integer on every state tried;
    a count over a length (two Python ints in binary64) divides in
    float32 too. At the champions' scale of 10,000 the float64 policy
    parts from it by one, once in some ten thousand scores (seed
    451715641 met one that decided an argmax); at a scale of 10 million,
    where a float32 has no digit to spare, it parts on a quarter of the
    states: the two references are not the same reference, and the one a
    driver takes is its configuration's ``score_dtype``."""
    f = np.float32
    differ = {}
    for scale in (10000.0, 1.0e7):
        differ[scale] = _float32_against_hand(
            ROUNDING_SOURCE.replace("SCALE", repr(scale)), f(scale))
    assert differ[10000.0][0] < 3, differ
    assert differ[1.0e7][0] > differ[1.0e7][1] // 5, differ


def _float32_against_hand(source: str, scale) -> tuple:
    """(states on which float64 gives another integer, states tried);
    every state's float32 score is held to the hand-written one."""
    f = np.float32
    p32 = policies.source_policy(source, dtype="float32")
    p64 = policies.source_policy(source)
    pod = ps.PodObj()
    pod.cpu_milli, pod.memory_mib, pod.num_gpu, pod.gpu_milli = 500, 64, 1, 300
    pod.creation_time = pod.duration_time = 0
    State = collections.namedtuple("State", "nodes")
    differ = states = 0
    for cpu_left in range(1000, 96000, 997):
        for gpus in ((1000, 200, 700), (1000,) * 6 + (100,), ()):
            nd = _one_node(cpu_left, 96000, 3 * cpu_left + 17, 393216, gpus)
            got32 = p32(pod, State([nd]), [0])[0]
            got64 = p64(pod, State([nd]), [0])[0]
            fit = f(sum(1 for g in gpus if g >= 300))
            want = scale * (
                f(0.101951)
                + f(0.344618) * (f(1.0) - f(cpu_left) / f(96000))
                + f(0.141436) * (f(3 * cpu_left + 17 - 64) / f(393216))
                + f(0.384283) * (fit / f(max(1, len(gpus)))))
            assert isinstance(want, np.float32)
            assert got32 == max(1, int(want)), (cpu_left, gpus, got32, want)
            assert abs(got32 - got64) <= 2e-6 * got64 + 1
            differ += got32 != got64
            states += 1
    return differ, states


def test_float32_reference_scores_are_the_vm_programs():
    """``chipbench.selftest.score_parity`` at the 16-node cell's size on
    the CPU backend (this process runs float32, as the chip does): the
    plain reference in the configuration's float32 gives the batched VM's
    score for every node of every kept state, bit for bit; the same
    instrument sees upstream's float64 part from the program somewhere in
    the cluster cell's scores (the reading's own control). On the chip,
    where the builder runs the module itself, neither count is 0 and every
    difference is one unit (PERF.md section 2): hence ``nearties``."""
    from chipbench.selftest import score_parity

    for cell_name, every, some64 in (("openb16.codegen8", 16, False),
                                     ("openb1523-loaded.codegen8", 256,
                                      True)):
        with contextlib.redirect_stdout(io.StringIO()) as out:
            rc = score_parity.main(["--workload", cell_name, "--seeds",
                                    "451715641", "--lanes", "2", "--every",
                                    str(every), "--cpu"])
        total = json.loads(out.getvalue().splitlines()[-1])
        assert rc == 0 and total["differ_float32"] == 0, total
        assert total["scores"] > 1000, total
        if some64:
            assert total["differ_float64"] > 0, total


# ------------------------------------------- a decision one unit decides

def _loaded_lane(seed: int, lane: int, events: int):
    """The real ``openb1523-loaded.codegen8`` lane of a seed, cut to
    ``events`` after the fork: ``run(policy, decide)`` and the source."""
    from chipbench.drivers import common
    from chipbench.reference.plain_sim_loaded import simulate_from

    cell = cells.load_cell("openb1523-loaded.codegen8")
    files = cells.verify_files(cell.config)
    d = cells.load_driver(cell.traffic["driver"]).Driver(
        cell, seed, files, None, False)
    d.e0 = int(cell.config["start_event"])
    rows = d.rows()
    cluster, pods = common.reference_inputs(cell.config, files)

    def run(policy, decide=None):
        return simulate_from(cluster, pods, rows, policy,
                             retry=cell.config["retry_rule"],
                             max_steps=len(rows) + events,
                             prefilter_k=int(cell.config["node_prefilter_k"]),
                             decide=decide)

    return run, d._sources()[lane], dict(cell.config["guarantees"])


def test_a_decision_that_one_unit_decides_is_admitted_and_counted():
    """The refused run itself (seed 451715641, lane 1, the cell's real
    size): upstream's float64 arithmetic and the configuration's float32
    part at ONE decision of 1,024, where the reference's two best nodes
    are a unit apart, and 311 placements differ after it. Put in the
    program's place, the float64 run is the float32 reference's once that
    one decision is admitted: count 1 of 2 allowed, nothing else differs.
    With no unit stated the difference stands."""
    run, src, g = _loaded_lane(451715641, 1, 1024)
    p32 = policies.source_policy(src, dtype="float32")
    got = run(policies.source_policy(src))
    free = run(p32)
    assert int((free.assigned_node != got.assigned_node).sum()) == 311
    ref, ties = admit(lambda decide: run(p32, decide), got.assigned_node,
                      g, "lane1")
    assert (ties.value, ties.limit, ties.ok) == (1.0, 2.0, True)
    assert (ref.assigned_node == got.assigned_node).all()
    assert (ref.assigned_gpus == got.assigned_gpus).all()
    assert ref.num_frag_events == got.num_frag_events
    ref, ties = admit(lambda decide: run(p32, decide), got.assigned_node,
                      {}, "lane1")
    assert ties.value == 0.0
    assert int((ref.assigned_node != got.assigned_node).sum()) == 311


def test_what_is_a_unit_off_at_every_decision_fails_the_count():
    """The two controls of the count, each within one unit at every single
    decision: ties among equal nodes to the HIGHEST index (upstream's rule
    is the lowest), and every score moved by -1, 0 or +1. Each needs more
    admissions than a run may have within its first 128 decisions (at the
    real 1,024: over 40 in all 7 lanes tried, PERF.md section 2), so the
    count reads 3 against 2 and the lane is not correct."""
    run, src, g = _loaded_lane(451715641, 2, 128)
    p32 = policies.source_policy(src, dtype="float32")
    rng = np.random.default_rng(7)

    def jittered(pod, s, cand):
        sc = np.asarray(p32(pod, s, cand), np.int64)
        return np.where(sc > 0,
                        np.maximum(1, sc + rng.integers(-1, 2, len(sc))), sc)

    last = lambda i, cand, sc: len(sc) - 1 - int(np.argmax(sc[::-1]))  # noqa: E731
    for got in (run(p32, last), run(jittered)):
        ref, ties = admit(lambda decide: run(p32, decide),
                          got.assigned_node, g, "lane2")
        assert (ties.value, ties.ok) == (3.0, False), ties


# ------------------------------------------------------------ no chip

def test_without_a_tpu_the_command_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload",
         "openb16.param256", "--seed", "1", "--seconds", "1", "--trace",
         "0"], cwd=cells.ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert "correct" not in p.stdout, p.stdout
