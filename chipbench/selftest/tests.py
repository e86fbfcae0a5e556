"""The selftest's tests (see ``__main__``). Tiny sizes, CPU only."""
from __future__ import annotations

import contextlib
import io
import json
import os
import subprocess
import sys
from unittest import mock

import numpy as np

from chipbench import cells, window
from chipbench.reduce import xplane
from chipbench.reference import data, policies
from chipbench.reference import plain_sim as ps
from chipbench.reference.compare import Output, compare

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURES = os.path.join(cells.ROOT, "tests", "fixtures")
CELLS = ("openb16.param256", "openb1523.whatif8", "openb16.codegen8",
         "openb16.codegen8x4")
#: tiny sizes for the CPU: the first 150 pods, 64-event generations of 4
TINY = {"config": {"pod_limit": 150, "code_eval_max_steps": 64},
        "traffic": {"lanes": 4, "sizes": [4, 8, 20], "max_batch": 3,
                    "trace_at_s": 0.0, "trace_for_s": 0.05,
                    "max_wait_s": 2.0}}
GUARANTEES = {"fitness_rtol": 16 * 2.0 ** -23}


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
    for t in (a, b):    # four chips write four times the device events
        t.pop("trace_for_s"), t.pop("traced")
    assert a == b


# -------------------------------------------------------- seed invariance

def test_work_per_call_is_the_same_for_every_seed():
    """``--seed`` chooses which data, never how much work: lanes,
    lane-events, pods and chunk shapes per call for seeds 0-3."""
    from fks_tpu.funsearch import vm
    from fks_tpu.serve import ShapeEnvelope

    seen = {c: set() for c in CELLS}
    for seed in range(4):
        for name in CELLS:
            cell = cells.load_cell(name)
            files = cells.verify_files(cell.config)
            d = cells.load_driver(cell.traffic["driver"]).Driver(
                cell, seed, files, None, False)
            if cell.traffic["driver"] == "codegen":
                srcs = d._sources()
                caps = tuple(sorted(
                    vm.capacity_bucket(int(vm.compile_policy(s, 16, 8).n_ops))
                    for s in srcs))
                assert len(set(srcs)) == len(srcs) == 8
                shorts = sum(len(s) < 2000 for s in srcs)   # seed policies
                seen[name].add((len(srcs), max(caps), shorts, len(srcs)
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
    assert seen["openb16.codegen8"] == {(8, 512, 2, 8 * 2048)}


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
    reference's trajectory: the reference runs free and follows nothing."""
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
    ref = ps.simulate(cluster, pods, make_policy(False), **kw)
    return compare("control", ref, Output.of_lane(low, pods.p), GUARANTEES)


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
              lambda lp: policies.source_policy(code, lp)]
    for make in makers:
        control = _control(cluster, pods, make, retry="earliest_delete",
                           max_steps=10 ** 6)
        assert not all(n.ok for n in control), control


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
