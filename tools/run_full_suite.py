"""Run the FULL test suite (fast + slow tiers) and append one evidence row
to benchmarks/results/full_suite.jsonl — the per-round CI stand-in the
README's "CI story for the slow tier" section points at. One row per run:
pass/fail/deselected counts, wall time, git revision.

Before pytest, an OBSERVABILITY GATE runs against the golden run-dir
fixture (tests/fixtures/golden_run): the JSONL schema checker must pass
it, and ``cli compare`` of the fixture against itself must exit 0 — the
two tools CI leans on must agree that a known-good run dir is good
before their verdicts on real runs mean anything. A gate failure is
recorded in the evidence row (``obs_gate``) and fails the suite run.

A TRACE GATE follows: ``cli trace-diff`` of the exact engine against
itself on the default trace must report zero divergence (exit 0). The
decision-trace instrument comparing an engine to itself and finding a
difference means the trace capture or alignment is broken — its verdicts
on real engine pairs would be noise. Recorded as ``trace_gate``.

A SCALE GATE follows: a small ``cli scale`` run with the scale-tier
knobs on (top-k node prefiltering + packed state dtypes, flat engine)
must complete and exit 0 — the cheap end-to-end check that the
large-cluster path stays wired before the slow-marked 1k-node smoke
test (tests/test_scale_tier.py) pays for the real shape. Recorded as
``scale_gate``.

A SERVE GATE follows: ``cli serve --selftest`` — batched warm-path
answers for queries sliced from the golden trace must match the
unbatched exact engine (score drift <= 1e-5, placements identical,
exit 0). A drift here means the serving tier's lane stacking or
scatter-back is corrupting answers. Recorded as ``serve_gate``.

A SHARDED SERVE GATE follows: the same selftest on an 8-virtual-device
dryrun mesh (``cli serve --cpu --devices 8 --state-pack --selftest``) —
mesh-sharded, 16-bit-packed batched answers must still match the exact
engine with 0.0 drift and identical placements. A drift here means the
batch-axis pad/shard specs, the device-resident snapshot cache, or the
pack/unpack pair is corrupting answers. Recorded as
``sharded_serve_gate``.

A LINT GATE follows: ``cli lint --cpu`` — the repo-wide JAX-invariant
AST lints must be clean AND the pinned-jaxpr manifest
(tests/fixtures/jaxpr_pins.json) must match the currently lowered
programs (exit 0). Pin drift means a key entry point compiles a
different program than the one the evidence was gathered on — re-pin
with ``cli lint --write-pins`` only when the change is intentional.
Recorded as ``lint_gate``.

A TRENDS GATE follows: ``cli trends`` over two synthetic bench-result
histories written to a temp dir — a 10-run series with an injected 30%
throughput drop must raise EXACTLY one alert (exit 1 under
``--fail-on-alert``), and the same series without the drop must raise
none (exit 0). A miss either way means the robust-z change-point pass
is broken — its alerts on the real archive would be noise or silence.
Recorded as ``trends_gate``. Pure-host (no jax import needed).

A SPAN TRACE GATE follows: a recorded ``cli serve --selftest`` run must
yield a COMPLETE causal waterfall (queue_wait / batch_wait and the carrying
chunk's stack / pack / h2d / enqueue / wait_device / d2h / extract under
one root) for 100% of its served requests
(``cli spans <dir> --check-complete``), and a recorded 1-generation
fake-LLM evolve must attribute >= 95% of the generation wall to traced
stages (``cli spans <dir> --critical-path --min-fraction 0.95``). A
failure means the trace-context propagation across the batcher / evolve
threads tore somewhere — per-request waterfalls and critical-path
attribution would silently lie. Recorded as ``span_trace_gate``.

A RESILIENCE GATE follows: the deterministic resilience drills
(deadline storm, queue overload, device loss mid-batch,
degrade-then-recover, SIGTERM drain, WAL resume mid-generation) from
``fks_tpu/resilience/drills.py`` must all pass via
``cli pipeline --drill --only <resilience drills>`` (exit 0). A failure
means the shed/degrade/drain/WAL machinery the serve and evolve loops
lean on under faults no longer holds its invariants. Recorded as
``resilience_gate``.

A VM SERVE GATE follows: the champion-as-data serving path —
``cli serve --serve-engine vm --selftest`` must answer with exact
parity against the unbatched reference (exit 0), and the double
hot-swap drill (``cli pipeline --drill --only vm_double_swap``) must
promote TWICE through the live controller with zero XLA compiles on
the serving process. A failure means the VM engine's program tables,
the shared executables, or the zero-rebuild swap path regressed to
recompiling. Recorded as ``vm_serve_gate``.

A MEMORY GATE follows: the deterministic memory drills
(fks_tpu.obs.memory) on an 8-virtual-device dryrun mesh —
``cli mem --cpu --devices 8 --drill vm_swap_leak`` must show ZERO net
``jax.live_arrays()`` growth across 50 swap_program promotions
interleaved with 200 served batches (every swap frees the displaced
program tables, every batch's buffers are donated or cache-hits), and
``--drill snapshot_cache_bound`` must show the device snapshot cache
holding a byte ceiling (evicts under pressure, never exceeds the cap,
still re-hits recent entries). A failure means the serving tier is
accreting device memory per promotion or the cache bound broke — the
exact leak class that kills a long-lived serving process. Recorded as
``memory_gate``.

A LOADGEN GATE follows: a short deterministic two-tenant closed-loop
run through the concurrent HTTP front (``bench.py --stage loadgen``)
with per-tenant accounting on — shed rate must stay bounded, the Jain
fairness index over tenant goodput must stay >= 0.8, and the steady
state must serve with ZERO recompiles. A failure means the tenant
accounting, the threaded HTTP front, or the warm serving path
regressed under overlapping clients. Recorded as ``loadgen_gate``.

A PORTFOLIO GATE follows: multi-tenant champion-portfolio serving —
``cli portfolio --cpu --devices 8 --selftest 4`` builds four resident
champions into ONE slot-vmapped VM executable on the 8-device dryrun
mesh, and must show every slot's answers matching a single-champion VM
engine (score drift <= 1e-5, placements identical), a mixed-slot batch
matching the per-slot answers, and one slot promoted mid-traffic with
ZERO XLA compiles. A failure means the slot-gather dispatch, the
replicated slot-table sharding, or the swap-under-traffic lock
regressed. Recorded as ``portfolio_gate``.
"""
from __future__ import annotations

import json
import os
import re
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT = os.path.join(REPO, "benchmarks", "results", "full_suite.jsonl")
GOLDEN = os.path.join(REPO, "tests", "fixtures", "golden_run")


def obs_gate() -> dict:
    """Schema-check the golden run dir and self-compare it (exit 0
    expected). Returns {"ok": bool, "detail": ...}."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    schema = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "check_jsonl_schema.py"),
         "--run-dir", GOLDEN],
        capture_output=True, text=True, cwd=REPO)
    compare = subprocess.run(
        [sys.executable, "-m", "fks_tpu.cli", "compare", GOLDEN, GOLDEN],
        capture_output=True, text=True, cwd=REPO, env=env)
    ok = schema.returncode == 0 and compare.returncode == 0
    detail = {"schema_rc": schema.returncode, "compare_rc": compare.returncode}
    if not ok:
        detail["schema_err"] = (schema.stderr or "")[-500:]
        detail["compare_err"] = (compare.stderr or compare.stdout or "")[-500:]
    return {"ok": ok, **detail}


def trace_gate() -> dict:
    """Trace-diff self-consistency: exact-vs-exact on the default trace
    must exit 0 (no divergence). Returns {"ok": bool, ...}."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "fks_tpu.cli", "trace-diff", "--cpu",
         "--engines", "exact,exact", "--policy", "first_fit",
         "--max-steps", "256"],
        capture_output=True, text=True, cwd=REPO, env=env)
    ok = proc.returncode == 0
    detail = {"rc": proc.returncode}
    if not ok:
        detail["err"] = (proc.stderr or proc.stdout or "")[-500:]
    return {"ok": ok, **detail}


def scale_gate() -> dict:
    """Scale-tier smoke: a small ``cli scale`` run with prefiltering and
    packed state dtypes must complete (exit 0). Returns {"ok": bool, ...}."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "fks_tpu.cli", "scale", "--cpu",
         "--nodes", "64", "--pods", "512", "--pop", "2",
         "--prefilter-k", "8", "--state-pack", "--engine", "flat"],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=600)
    ok = proc.returncode == 0
    detail = {"rc": proc.returncode}
    if not ok:
        detail["err"] = (proc.stderr or proc.stdout or "")[-500:]
    return {"ok": ok, **detail}


def serve_gate() -> dict:
    """Serving parity: the champion-serving selftest (batched warm-path
    answers vs the unbatched exact engine, golden-trace queries) must
    exit 0. Returns {"ok": bool, ...}."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "fks_tpu.cli", "serve", "--cpu",
         "--selftest", "4", "--pods-per-query", "3",
         "--max-pods", "16", "--max-batch", "4"],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=600)
    ok = proc.returncode == 0
    detail = {"rc": proc.returncode}
    if not ok:
        detail["err"] = (proc.stderr or proc.stdout or "")[-500:]
    return {"ok": ok, **detail}


def sharded_serve_gate() -> dict:
    """Sharded-serving parity: the same selftest on an 8-virtual-device
    dryrun mesh with 16-bit packed uploads — batched mesh-sharded answers
    must match the unbatched exact engine with 0.0 drift and identical
    placements. Exercises the whole round-17 path: pad/shard specs,
    device-resident snapshot cache, packed H2D. Returns {"ok": bool, ...}."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "fks_tpu.cli", "serve", "--cpu",
         "--devices", "8", "--state-pack",
         "--selftest", "4", "--pods-per-query", "3",
         "--max-pods", "16", "--max-batch", "4"],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=600)
    ok = proc.returncode == 0
    detail = {"rc": proc.returncode}
    if not ok:
        detail["err"] = (proc.stderr or proc.stdout or "")[-500:]
    return {"ok": ok, **detail}


def lint_gate() -> dict:
    """Repo lint + jaxpr-pin drift: ``cli lint --cpu`` must exit 0
    (clean findings, no pin drift). Returns {"ok": bool, ...}."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "fks_tpu.cli", "lint", "--cpu"],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=600)
    ok = proc.returncode == 0
    detail = {"rc": proc.returncode}
    if not ok:
        detail["err"] = (proc.stderr or proc.stdout or "")[-500:]
    return {"ok": ok, **detail}


def promote_gate() -> dict:
    """Promotion-drill matrix: every deterministic fault-injection drill
    (corrupt champion, device-eval error, p99 regression, kill -9 per
    state, rollback on burn, zero-recompile swap, llm outage) must pass
    — ``cli pipeline --drill`` exits 0. Returns {"ok": bool, ...}."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "fks_tpu.cli", "pipeline", "--cpu",
         "--drill"],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=900)
    ok = proc.returncode == 0
    detail = {"rc": proc.returncode}
    if not ok:
        detail["err"] = (proc.stderr or proc.stdout or "")[-500:]
    return {"ok": ok, **detail}


def span_trace_gate() -> dict:
    """Causal-trace completeness: a recorded serve selftest must produce
    a complete waterfall for every served request, and a recorded 1-gen
    fake-LLM evolve must attribute >= 95% of the generation wall to
    traced stages. Returns {"ok": bool, ...}."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    detail = {}
    ok = True
    with tempfile.TemporaryDirectory() as tmp:
        serve_dir = os.path.join(tmp, "serve")
        evolve_dir = os.path.join(tmp, "evolve")
        steps = (
            ("serve", [sys.executable, "-m", "fks_tpu.cli", "serve",
                       "--cpu", "--selftest", "4", "--pods-per-query", "3",
                       "--max-pods", "16", "--max-batch", "4",
                       "--run-dir", serve_dir]),
            ("serve_waterfalls", [sys.executable, "-m", "fks_tpu.cli",
                                  "spans", serve_dir, "--check-complete"]),
            ("evolve", [sys.executable, "-m", "fks_tpu.cli", "evolve",
                        "--cpu", "--fake-llm", "--generations", "1",
                        "--run-dir", evolve_dir]),
            ("critical_path", [sys.executable, "-m", "fks_tpu.cli",
                               "spans", evolve_dir, "--critical-path",
                               "--min-fraction", "0.95"]),
        )
        for name, cmd in steps:
            proc = subprocess.run(cmd, capture_output=True, text=True,
                                  cwd=REPO, env=env, timeout=900)
            detail[f"{name}_rc"] = proc.returncode
            if proc.returncode != 0:
                ok = False
                detail[f"{name}_err"] = (proc.stderr
                                         or proc.stdout or "")[-500:]
                break
    return {"ok": ok, **detail}


def resilience_gate() -> dict:
    """Resilience-drill matrix: the deterministic failure drills from
    fks_tpu/resilience/drills.py (deadline storm, queue overload, device
    loss mid-batch, degrade-then-recover, SIGTERM drain, WAL resume) must
    pass — ``cli pipeline --drill --only <resilience>`` exits 0.
    Returns {"ok": bool, ...}."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    only = ("deadline_storm,queue_overload,device_loss,degrade,"
            "sigterm,wal_resume")
    proc = subprocess.run(
        [sys.executable, "-m", "fks_tpu.cli", "pipeline", "--cpu",
         "--drill", "--only", only],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=900)
    ok = proc.returncode == 0
    detail = {"rc": proc.returncode}
    if not ok:
        detail["err"] = (proc.stderr or proc.stdout or "")[-500:]
    return {"ok": ok, **detail}


def vm_serve_gate() -> dict:
    """VM-native serving: the champion-as-data selftest (engine_kind
    "vm", exact parity vs the unbatched reference) must exit 0, and the
    double hot-swap drill must perform two in-place promotions with
    ZERO XLA compiles (``pipeline --drill --only vm_double_swap``).
    Returns {"ok": bool, ...}."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    detail = {}
    ok = True
    steps = (
        ("selftest", [sys.executable, "-m", "fks_tpu.cli", "serve",
                      "--cpu", "--serve-engine", "vm",
                      "--selftest", "4", "--pods-per-query", "3",
                      "--max-pods", "16", "--max-batch", "4"]),
        ("double_swap", [sys.executable, "-m", "fks_tpu.cli", "pipeline",
                         "--cpu", "--drill", "--only", "vm_double_swap"]),
    )
    for name, cmd in steps:
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              cwd=REPO, env=env, timeout=900)
        detail[f"{name}_rc"] = proc.returncode
        if proc.returncode != 0:
            ok = False
            detail[f"{name}_err"] = (proc.stderr
                                     or proc.stdout or "")[-500:]
            break
    return {"ok": ok, **detail}


def memory_gate() -> dict:
    """Memory drills: ``cli mem --drill vm_swap_leak`` on an 8-device
    dryrun mesh must show zero net ``jax.live_arrays()`` growth across
    repeated swap+serve cycles, and ``--drill snapshot_cache_bound``
    must show the snapshot cache evicting under a byte cap while still
    re-hitting recent entries. Returns {"ok": bool, ...}."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    detail = {}
    ok = True
    steps = (
        ("vm_swap_leak", [sys.executable, "-m", "fks_tpu.cli", "mem",
                          "--cpu", "--devices", "8",
                          "--drill", "vm_swap_leak"]),
        ("snapshot_cache_bound", [sys.executable, "-m", "fks_tpu.cli",
                                  "mem", "--cpu",
                                  "--drill", "snapshot_cache_bound"]),
    )
    for name, cmd in steps:
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              cwd=REPO, env=env, timeout=900)
        detail[f"{name}_rc"] = proc.returncode
        if proc.returncode != 0:
            ok = False
            detail[f"{name}_err"] = (proc.stderr
                                     or proc.stdout or "")[-500:]
            break
    return {"ok": ok, **detail}


def loadgen_gate() -> dict:
    """Multi-tenant load generation: a short deterministic two-tenant
    closed-loop run through the concurrent HTTP front
    (``bench.py --stage loadgen``) must complete with a bounded shed
    rate, a Jain fairness index at or above threshold, and ZERO
    steady-state recompiles. A failure means the tenant accounting,
    the concurrent front, or the warm serving path regressed under
    overlapping clients. Returns {"ok": bool, ...}."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               FKS_BENCH_LOADGEN_S="2",
               FKS_BENCH_LOADGEN_TENANTS="a:closed:2,b:closed:2",
               FKS_BENCH_LOADGEN_FAIRNESS_MIN="0.8")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"),
         "--stage", "loadgen"],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=900)
    ok = proc.returncode == 0
    detail = {"rc": proc.returncode}
    if not ok:
        detail["err"] = (proc.stderr or proc.stdout or "")[-500:]
    return {"ok": ok, **detail}


def portfolio_gate() -> dict:
    """Portfolio serving: ``cli portfolio --selftest`` on the 8-device
    dryrun mesh — four resident champions in one slot-vmapped VM
    executable, per-slot + mixed-batch parity vs single-champion VM
    engines (<= 1e-5), then one slot promoted mid-traffic with zero XLA
    compiles. Returns {"ok": bool, ...}."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "fks_tpu.cli", "portfolio", "--cpu",
         "--devices", "8", "--selftest", "4"],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=900)
    ok = proc.returncode == 0
    detail = {"rc": proc.returncode}
    try:
        summary = json.loads(proc.stdout)
        detail["max_drift"] = summary.get("max_drift")
        detail["mixed_max_drift"] = summary.get("mixed_max_drift")
        detail["swap_compiles"] = summary.get("swap", {}).get("compiles")
        detail["n_slots"] = summary.get("n_slots")
    except json.JSONDecodeError:
        ok = False
    if not ok:
        detail["err"] = (proc.stderr or proc.stdout or "")[-500:]
    return {"ok": ok, **detail}


def layout_gate() -> dict:
    """Layout observability: ``cli layout --explore`` on the 8-device
    dryrun mesh must find >= 2 distinct valid layouts of pop-16 x
    suite-8 with every layout's robust scores parity-equal to the
    default (<= 1e-5), and the pinned default-spec jaxpr must be
    unchanged (``cli lint``'s sharded_eval/default_layout pin, checked
    by the lint gate). The explore run itself must NOT fail on
    dominance — the dryrun mesh time-slices one host, so the default
    being beaten there is expected and informational; the gate asserts
    the measurement machinery, not a schedule. Returns
    {"ok": bool, ...}."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    detail = {}
    ok = True
    with tempfile.TemporaryDirectory() as tmp:
        proc = subprocess.run(
            [sys.executable, "-m", "fks_tpu.cli", "layout", "--explore",
             "--cpu", "--devices", "8", "--pop", "16",
             "--suite", "default8", "--history-root", tmp],
            capture_output=True, text=True, cwd=REPO, env=env,
            timeout=900)
        # rc 1 is the dominance verdict, not a machinery failure
        detail["rc"] = proc.returncode
        if proc.returncode not in (0, 1):
            detail["err"] = (proc.stderr or proc.stdout or "")[-500:]
            return {"ok": False, **detail}
        try:
            summary = json.loads(proc.stdout)
        except json.JSONDecodeError:
            detail["err"] = (proc.stdout or "")[-500:]
            return {"ok": False, **detail}
        detail["layouts_probed"] = summary.get("layouts_probed", 0)
        detail["parity_max_abs"] = summary.get("parity_max_abs")
        detail["best_mesh_shape"] = summary.get("best_mesh_shape")
        if summary.get("layouts_probed", 0) < 2:
            ok = False
            detail["err"] = "fewer than 2 distinct valid layouts probed"
        if float(summary.get("parity_max_abs", 1.0)) > 1e-5:
            ok = False
            detail["err"] = (f"layout parity {summary.get('parity_max_abs')}"
                             " > 1e-5")
        prior = os.path.join(tmp, "layouts.json")
        detail["prior_written"] = os.path.exists(prior)
        ok = ok and detail["prior_written"]
    return {"ok": ok, **detail}


def _write_history(root: str, values) -> None:
    now = time.time()
    for i, v in enumerate(values):
        p = os.path.join(root, f"BENCH_r{i:02d}.json")
        with open(p, "w") as f:
            json.dump({"metric": "evals/s", "value": v, "unit": "evals/s",
                       "vs_baseline": round(v / 40.0, 3)}, f)
        ts = now - (len(values) - i) * 3600
        os.utime(p, (ts, ts))


def trends_gate() -> dict:
    """Regression-flagging self-test: an injected 30% drop in a synthetic
    10-run history must alert (rc 1 with --fail-on-alert, exactly one
    alert); the clean series must not (rc 0). Returns {"ok": bool, ...}."""
    clean = [100.0, 101.5, 99.2, 100.8, 98.9, 101.1, 99.7, 100.4, 99.9,
             100.6]
    regressed = clean[:7] + [70.0, 69.5, 70.3]
    detail = {}
    ok = True
    with tempfile.TemporaryDirectory() as tmp:
        for name, series, want_rc in (("clean", clean, 0),
                                      ("regressed", regressed, 1)):
            root = os.path.join(tmp, name)
            os.makedirs(root)
            _write_history(root, series)
            proc = subprocess.run(
                [sys.executable, "-m", "fks_tpu.cli", "trends", root,
                 "--metric", "evals_per_sec", "--fail-on-alert"],
                capture_output=True, text=True, cwd=REPO, timeout=300)
            detail[f"{name}_rc"] = proc.returncode
            if proc.returncode != want_rc:
                ok = False
                detail[f"{name}_err"] = (proc.stderr
                                         or proc.stdout or "")[-500:]
            if name == "regressed":
                n = (proc.stdout or "").count("ALERT")
                detail["alerts"] = n
                ok = ok and n == 1
    return {"ok": ok, **detail}


def main() -> int:
    rev = subprocess.run(["git", "rev-parse", "--short", "HEAD"],
                         capture_output=True, text=True, cwd=REPO
                         ).stdout.strip()
    gate = obs_gate()
    if not gate["ok"]:
        print(f"OBS GATE FAILED: {gate}", file=sys.stderr)
    tgate = trace_gate()
    if not tgate["ok"]:
        print(f"TRACE GATE FAILED: {tgate}", file=sys.stderr)
    sgate = scale_gate()
    if not sgate["ok"]:
        print(f"SCALE GATE FAILED: {sgate}", file=sys.stderr)
    vgate = serve_gate()
    if not vgate["ok"]:
        print(f"SERVE GATE FAILED: {vgate}", file=sys.stderr)
    hgate = sharded_serve_gate()
    if not hgate["ok"]:
        print(f"SHARDED SERVE GATE FAILED: {hgate}", file=sys.stderr)
    lgate = lint_gate()
    if not lgate["ok"]:
        print(f"LINT GATE FAILED: {lgate}", file=sys.stderr)
    ngate = trends_gate()
    if not ngate["ok"]:
        print(f"TRENDS GATE FAILED: {ngate}", file=sys.stderr)
    pgate = promote_gate()
    if not pgate["ok"]:
        print(f"PROMOTE GATE FAILED: {pgate}", file=sys.stderr)
    rgate = resilience_gate()
    if not rgate["ok"]:
        print(f"RESILIENCE GATE FAILED: {rgate}", file=sys.stderr)
    mgate = vm_serve_gate()
    if not mgate["ok"]:
        print(f"VM SERVE GATE FAILED: {mgate}", file=sys.stderr)
    wgate = span_trace_gate()
    if not wgate["ok"]:
        print(f"SPAN TRACE GATE FAILED: {wgate}", file=sys.stderr)
    ygate = memory_gate()
    if not ygate["ok"]:
        print(f"MEMORY GATE FAILED: {ygate}", file=sys.stderr)
    dgate = loadgen_gate()
    if not dgate["ok"]:
        print(f"LOADGEN GATE FAILED: {dgate}", file=sys.stderr)
    fgate = portfolio_gate()
    if not fgate["ok"]:
        print(f"PORTFOLIO GATE FAILED: {fgate}", file=sys.stderr)
    ogate = layout_gate()
    if not ogate["ok"]:
        print(f"LAYOUT GATE FAILED: {ogate}", file=sys.stderr)
    t0 = time.time()
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "tests/", "-q",
         "-m", "slow or not slow"],
        capture_output=True, text=True, cwd=REPO)
    wall = round(time.time() - t0, 1)
    tail = (proc.stdout or "").strip().splitlines()[-1:]
    summary = tail[0] if tail else ""
    counts = {k: int(v) for v, k in re.findall(
        r"(\d+) (passed|failed|error|skipped|deselected|xfailed)", summary)}
    gates_ok = (gate["ok"] and tgate["ok"] and sgate["ok"] and vgate["ok"]
                and hgate["ok"] and lgate["ok"] and ngate["ok"]
                and pgate["ok"] and rgate["ok"] and wgate["ok"]
                and mgate["ok"] and ygate["ok"] and dgate["ok"]
                and fgate["ok"] and ogate["ok"])
    rc = proc.returncode if gates_ok else (proc.returncode or 1)
    row = {"ts": round(time.time(), 1), "rev": rev, "rc": rc,
           "wall_s": wall, **counts, "obs_gate": gate,
           "trace_gate": tgate, "scale_gate": sgate, "serve_gate": vgate,
           "sharded_serve_gate": hgate, "lint_gate": lgate,
           "trends_gate": ngate, "promote_gate": pgate,
           "resilience_gate": rgate, "span_trace_gate": wgate,
           "vm_serve_gate": mgate, "memory_gate": ygate,
           "loadgen_gate": dgate, "portfolio_gate": fgate,
           "layout_gate": ogate, "summary": summary}
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    with open(OUT, "a") as f:
        f.write(json.dumps(row) + "\n")
    print(json.dumps(row))
    sys.stderr.write((proc.stdout or "")[-2000:])
    return rc


if __name__ == "__main__":
    sys.exit(main())
