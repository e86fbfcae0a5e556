#!/usr/bin/env python3
"""Bring-up smoke: evaluate, evolve and serve end to end on the chip.

    python3 chip_smoke.py [--steps parity,serve,...]

One process. It imports JAX once, requires ``jax.devices()[0].platform ==
"tpu"`` (anything else is a non-zero exit with no result — there is no CPU
continuation), then drives the system's main path through the entry points
users call, on two deployments at full size:

- **A**, upstream's own benchmark: ``gpu_models_filtered.csv`` (16 nodes,
  48 GPUs) x ``openb_pod_list_default.csv`` (8,152 pods);
- **B**, the real Alibaba OpenB cluster (``cluster-trace-gpu-v2023``):
  ``openb_node_list_all_node.csv`` (1,523 nodes, 6,212 GPUs) x the same
  pod list.

Steps, each printing one JSON line (name, ok, wall seconds, backend
compile seconds and count from ``obs.CompileWatcher``, and its check):
``parity`` (``cli bench``, exact engine), ``evaluate_parametric``
(``parallel.make_population_eval``, flat engine, lanes re-run on the host
CPU), ``evaluate_code`` (``funsearch.backend.CodeEvaluator`` with the
defaults the chip selects, against the recorded divergence audit),
``evaluate_forked`` (two lanes forked from the pinned snapshot of the
loaded cluster B, 1,024 events past the fork, against the plain
reference's counts), ``evolve`` (``cli evolve --fake-llm``), ``serve`` (the best ledger
champion behind ``serve.service.make_http_server``, VM engine, plus the
two-slot portfolio selftest) and ``fused`` (the Mosaic-compiled Pallas
kernel gated against flat). With more than one device visible the
evaluate and serve steps go through the mesh entry points and fail if
any device held no lanes.

The run stops at the first failed step with a non-zero exit. The last
two lines of stdout are the summary JSON, which ends with ``"claim":
null`` — every timing here is a BRING-UP timing (cold compiles included,
one reading), never a benchmark number — and then the verdict, exactly
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``
with the device as JAX reports it (``"ok": false`` when a step failed).
``--steps`` runs a subset (a builder with a chip budget re-runs the step
they touched); the summary then says ``"partial": true`` and proves
nothing about the steps left out.

The step functions take their workloads and sizes as arguments so that
tier-1 runs each of them at micro size on the CPU
(``tests/test_chip_smoke.py``).
"""
from __future__ import annotations

import contextlib
import glob
import json
import os
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))

PODS = "openb_pod_list_default.csv"
NODES_A = "gpu_models_filtered.csv"
NODES_B = "openb_node_list_all_node.csv"
#: reference parity on deployment A (.claude/skills/verify/SKILL.md):
#: policy -> (fitness to 1e-4, snapshot count)
PARITY_A = {"first_fit": (0.4292, 47), "best_fit": (0.4465, 40),
            "funsearch_4901": (0.4901, 67)}
#: best_fit on deployment B: flat == exact to the last digit, zero retries
BEST_FIT_B = 0.00492986
#: deployment B loaded: the inflated arrival list forked from the pinned
#: snapshot of its first 5,888 arrivals; policy -> (scheduled pods, failed
#: placements) at event 6,912 by the plain reference's ``simulate_from``
PODS_LOADED = "openb_pod_list_inflated080.csv"
SNAPSHOT = "openb_snapshot_inflated080_e5888.csv"
FORKED_B = {"first_fit": (6694, 73), "best_fit": (6695, 0)}
#: served fitness against the unbatched exact reference, as a bound
#: RELATIVE to the score: 4 ulps of f32 (2**-23 each). Placements must be
#: identical; the fitness is the same f32 arithmetic compiled twice, and on
#: four chips the shard_map program landed one ulp (2.3e-10 at 0.0034) from
#: the single-device reference on B. On one chip the drift is exactly 0.0.
SCORE_RTOL = 4 * 2.0 ** -23
AUDIT = os.path.join(REPO, "benchmarks", "results", "divergence_audit.jsonl")
LEDGER = os.path.join(REPO, "policies", "discovered")


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def _post(port: int, query: dict, timeout_s: float = 600.0) -> dict:
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/query", data=json.dumps(query).encode(),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout_s) as resp:
            return json.loads(resp.read())
    except urllib.error.HTTPError as e:  # say what the front answered
        raise RuntimeError(f"POST /query -> {e.code}: "
                           f"{e.read().decode(errors='replace')}") from e


def _require_every_device(lanes: dict, mesh) -> dict:
    """``{device id: lanes}`` as JSON, failing when a mesh device held
    none (lanes that never left the first chip look fine in the result)."""
    if mesh is not None:
        empty = [d.id for d in mesh.devices.flat if not lanes.get(d.id)]
        if empty:
            raise AssertionError(f"devices {empty} held no lanes: {lanes}")
    return {str(k): int(v) for k, v in sorted(lanes.items())}


# ------------------------------------------------------------------- steps


def step_parity(trace: str, nodes: str, expect: dict, n_pods: int,
                out_dir: str) -> dict:
    """``cli bench``'s path (``cmd_bench``) with the exact engine on the
    default device. ``expect``: policy -> (fitness to 1e-4, snapshots),
    or None to require only a finite score."""
    from fks_tpu import cli

    metrics = os.path.join(out_dir, "parity_metrics.jsonl")
    with contextlib.redirect_stdout(sys.stderr):
        rc = cli.main(["bench", "--trace", trace, "--nodes", nodes,
                       "--policies", ",".join(expect),
                       "--metrics", metrics])
    if rc != 0:
        return {"ok": False, "error": f"cli bench returned {rc}"}
    with open(metrics) as f:
        rows = {r["policy"]: r for r in map(json.loads, f)
                if r.get("kind") == "bench"}
    got, bad = {}, []
    for name, want in expect.items():
        r = rows[name]
        score = float(r["policy_score"])
        got[name] = {"score": round(score, 6),
                     "scheduled": int(r["scheduled_pods"]),
                     "snapshots": int(r["num_snapshots"])}
        if int(r["scheduled_pods"]) != n_pods or not score == score:
            bad.append(name)
        elif want is not None and (abs(score - want[0]) > 1e-4
                                   or int(r["num_snapshots"]) != want[1]):
            bad.append(name)
    return {"ok": not bad, "engine": "exact", "policies": got,
            "mismatch": bad}


def step_evaluate_parametric(wl, pop: int, mesh=None, seed: int = 0,
                             exact_best_fit=None) -> dict:
    """``parallel.make_population_eval(engine="flat")`` at ``pop`` lanes
    (the throughput config: step cap 4x pods, ctime tracking off). Two lanes — the best_fit seed weights and one random lane — are
    re-run UNBATCHED on the host CPU and must agree with the device in
    ``scheduled_pods``/``events_processed``/``assigned_node`` exactly and
    in ``policy_score`` to 1e-5. With a mesh the same population also
    goes through ``make_sharded_eval`` and every device must hold lanes.
    ``exact_best_fit``: the exact engine's best_fit fitness the flat
    lane must equal (deployments without retries), checked against a run
    of the exact engine on the default device too."""
    import jax
    import numpy as np

    from fks_tpu.models import parametric
    from fks_tpu.parallel import (
        make_population_eval, make_sharded_eval, pad_population,
    )
    from fks_tpu.sim import engine as exact
    from fks_tpu.sim import flat
    from fks_tpu.sim.engine import SimConfig

    cfg = SimConfig(max_steps=4 * wl.num_pods, track_ctime=False)
    params = parametric.init_population(jax.random.PRNGKey(seed), pop,
                                        noise=0.1)
    res = jax.device_get(
        make_population_eval(wl, cfg=cfg, engine="flat")(params))
    out = {"population": pop,
           "truncated_lanes": int(np.asarray(res.truncated).sum()),
           "best_score": round(float(np.max(res.policy_score)), 6)}
    bad = []

    # init_population keeps lanes 0-3 as the pure seeds; lane 1 is best_fit
    picks = [1, 4 + int(np.random.default_rng(seed).integers(pop - 4))]
    host = np.asarray(params)
    # the host CPU backend, with no stand-in: a process that exposes none
    # (JAX_PLATFORMS naming only the TPU) cannot run the comparison
    with jax.default_device(jax.devices("cpu")[0]):
        run = jax.jit(flat.make_param_run_fn(wl, parametric.score, cfg))
        state0 = flat.initial_state(wl, cfg)
        for lane in picks:
            ref = jax.device_get(run(host[lane], state0))
            same = all(
                np.array_equal(np.asarray(getattr(ref, f)),
                               np.asarray(getattr(res, f))[lane])
                for f in ("scheduled_pods", "events_processed",
                          "assigned_node"))
            drift = abs(float(ref.policy_score)
                        - float(res.policy_score[lane]))
            if not same or drift > 1e-5:
                bad.append({"lane": lane, "trajectory_equal": same,
                            "score_drift": drift})
    out["lanes_checked_on_cpu"] = picks
    out["best_fit_flat"] = round(float(res.policy_score[1]), 8)

    if exact_best_fit is not None:
        ex = exact.make_param_run_fn(wl, parametric.score, SimConfig())
        got = float(jax.jit(ex)(params[1], exact.initial_state(
            wl, SimConfig())).policy_score)
        out["best_fit_exact"] = round(got, 8)
        for name, v in (("flat", float(res.policy_score[1])),
                        ("exact", got)):
            if abs(v - exact_best_fit) > 1e-7:
                bad.append({"best_fit": name, "got": v,
                            "want": exact_best_fit})

    if mesh is not None:
        padded, real = pad_population(params, mesh)
        scores = make_sharded_eval(wl, mesh, cfg=cfg, engine="flat")(
            padded, real)[0]
        from fks_tpu.parallel import lanes_per_device
        out["lanes_per_device"] = _require_every_device(
            lanes_per_device(scores), mesh)
        drift = float(np.max(np.abs(np.asarray(scores)[:real]
                                    - np.asarray(res.policy_score))))
        if drift > 1e-6:
            bad.append({"sharded_vs_vmap_drift": drift})
    out["ok"] = not bad
    out["mismatch"] = bad
    return out


def step_evaluate_code(wl, checked: dict, mesh=None) -> dict:
    """One generation through ``CodeEvaluator(wl, engine="flat")`` with the
    tier and segment length the platform selects. ``checked``: name ->
    (source, recorded flat fitness, or None to require only a clean
    evaluation). The batched tier must have served every unique
    candidate (a batch failure otherwise falls back per candidate and
    still returns scores) and the recorded fitnesses must reproduce to
    1e-5. Fingerprint dedup is off: the audited champions
    are coefficient variants of one another, which it collapses — by
    design — onto one representative's score."""
    from fks_tpu.funsearch.backend import CodeEvaluator

    ev = CodeEvaluator(wl, engine="flat", mesh=mesh, fp_dedup=False)
    names = list(checked)
    records = ev.evaluate([checked[n][0] for n in names])
    stats = ev.last_eval_stats
    out = {"vm_batch": bool(ev.vm_batch), "vm_seg_steps": ev.vm_seg_steps,
           "candidates": len(names), "unique": stats["unique"],
           "vm_batch_lanes": stats["vm_batch_lanes"],
           "fallback_lanes": stats["fallback_lanes"],
           "segments": stats["segments"], "scores": {}}
    bad = []
    if ev.vm_batch and (stats["vm_batch_lanes"] != stats["unique"]
                        or stats["fallback_lanes"]):
        bad.append("batched tier did not serve the whole generation")
    for name, rec in zip(names, records):
        want = checked[name][1]
        out["scores"][name] = round(float(rec.score), 6)
        if rec.error or (want is not None
                         and abs(rec.score - want) > 1e-5):
            bad.append({"policy": name, "got": rec.score, "want": want,
                        "error": rec.error})
    if mesh is not None and ev.vm_batch:
        out["lanes_per_device"] = _require_every_device(
            ev.last_lanes_per_device, mesh)
    out["ok"] = not bad
    out["mismatch"] = bad
    return out


def carry_leaves_differing(wl, cfg, placed_by, loaded) -> list:
    """Leaves of ``loaded`` (the carry ``flat.initial_state`` builds on
    the host from the workload's snapshot) that are not, bit for bit,
    what the flat engine itself reaches ON THIS DEVICE after the
    snapshot's ``E0`` steps from the empty cluster under ``placed_by``,
    the policy that placed the residents. The float leaf is
    ``snap_sums``: the host rounds the utilization sums as the compiled
    step does (a division by a constant total becomes a product with its
    reciprocal), and only a run on the device says that it still does."""
    import dataclasses

    import jax
    import numpy as np

    from fks_tpu.sim import flat
    from fks_tpu.sim.engine import loop_tables

    empty = dataclasses.replace(wl, snapshot=None)
    ktable, max_steps = loop_tables(empty, cfg)
    step = flat.build_step(empty, placed_by, cfg, ktable, max_steps)
    e0 = wl.snapshot.e0
    stepped = jax.jit(lambda s: jax.lax.while_loop(
        lambda s: flat.lane_active(s, max_steps) & (s.steps < e0),
        step, s))(flat.initial_state(empty, cfg))
    return [field for field, a, b in zip(stepped._fields, stepped, loaded)
            if (a is None) != (b is None) or (a is not None and not (
                np.asarray(a).dtype == np.asarray(b).dtype
                and np.array_equal(np.asarray(a), np.asarray(b))))]


def step_evaluate_forked(wl, window: int, expect: dict, mesh=None,
                         placed_by=None) -> dict:
    """One generation of two lanes (the seed policies) forked from the
    workload's snapshot (``fks_tpu.data.snapshot``), ``window`` events
    past the fork, through ``CodeEvaluator(wl, engine="flat")`` as the
    platform builds it. Every lane must stop at event ``E0 + window`` of
    the WHOLE run with the residents where the snapshot put them, and
    ``expect`` (name -> (scheduled pods, failed placements), the plain
    reference's ``simulate_from``; None to skip) must reproduce. With
    ``placed_by`` (the policy that placed the residents) the evaluator's
    forked carry must also be the engine's own after those placements,
    leaf by leaf (``carry_leaves_differing``)."""
    import numpy as np

    from fks_tpu.funsearch import template
    from fks_tpu.funsearch.backend import CodeEvaluator
    from fks_tpu.sim.engine import SimConfig

    snap = wl.snapshot
    stop = snap.e0 + window
    ev = CodeEvaluator(wl, SimConfig(max_steps=stop), engine="flat",
                       mesh=mesh, fp_dedup=False)
    sources = template.seed_policies()
    records = ev.evaluate(list(sources.values()))
    stats = ev.last_eval_stats
    out = {"start_event": stats["start_event"], "window": window,
           "residents": snap.e0, "vm_batch": bool(ev.vm_batch),
           "vm_batch_lanes": stats["vm_batch_lanes"],
           "frag_events": stats["frag_events"], "lanes": {}}
    bad = []
    if stats["start_event"] != snap.e0:
        bad.append("the evaluator did not start at the fork")
    if ev.vm_batch and stats["vm_batch_lanes"] != len(sources):
        bad.append("batched tier did not serve the whole generation")
    if placed_by is not None:
        out["carry_leaves_differing"] = carry_leaves_differing(
            wl, ev.cfg, placed_by, ev.state0)
        if out["carry_leaves_differing"]:
            bad.append("the forked carry is not the engine's own")
    pod = np.asarray(snap.pod)
    for name, rec in zip(sources, records):
        res = rec.result
        got = (int(res.scheduled_pods), int(res.num_fragmentation_events))
        out["lanes"][name] = {"events": int(res.events_processed),
                              "scheduled": got[0], "frag_events": got[1]}
        stayed = (np.array_equal(np.asarray(res.assigned_node)[pod],
                                 np.asarray(snap.node))
                  and np.array_equal(np.asarray(res.assigned_gpus)[pod],
                                     np.asarray(snap.gpus)))
        want = expect.get(name)
        if (int(res.events_processed) != stop or not stayed
                or bool(res.failed) or (want is not None and got != want)):
            bad.append({"policy": name, "got": got, "want": want,
                        "events": int(res.events_processed),
                        "residents_stayed": bool(stayed)})
    out["ok"] = not bad
    out["mismatch"] = bad
    return out


def step_evolve(out_dir: str, generations: int = 2,
                population_size: int = 0) -> dict:
    """``cli evolve --fake-llm --engine flat``: returns 0, writes a
    champion under ``--out`` (never into ``policies/discovered/``), and
    no exact rescore was swallowed. ``population_size`` (0 = the default
    20) rides in through ``--config``; a generation drafts
    ``min(8, population_size - elite_size)`` candidates."""
    from fks_tpu import cli

    champs = os.path.join(out_dir, "evolve_champions")
    run_dir = os.path.join(out_dir, "evolve_run")
    ledger_before = sorted(os.listdir(LEDGER))
    argv = ["evolve", "--fake-llm", "--engine", "flat", "--generations",
            str(generations), "--out", champs, "--run-dir", run_dir]
    if population_size:
        config = os.path.join(out_dir, "evolve_config.json")
        with open(config, "w") as f:
            json.dump({"funsearch": {"population_size": population_size}},
                      f)
        argv += ["--config", config]
    with contextlib.redirect_stdout(sys.stderr):
        rc = cli.main(argv)
    with open(os.path.join(run_dir, "meta.json")) as f:
        meta = json.load(f)
    best = sorted(glob.glob(os.path.join(champs, "funsearch_*.json")))
    out = {"rc": rc, "champion_files": len(best),
           "best_search": meta.get("best_score"),
           "best_exact": meta.get("best_exact"),
           "generations": meta.get("generations"),
           "rescore_platform": meta.get("rescore_platform"),
           "rescore_fallbacks": meta.get("rescore_fallbacks"),
           "ledger_untouched": sorted(os.listdir(LEDGER)) == ledger_before}
    out["ok"] = (rc == 0 and bool(best) and out["ledger_untouched"]
                 and meta.get("rescore_fallbacks") == 0
                 and meta.get("generations") == generations)
    return out


def _score_agrees(got: float, ref: float) -> bool:
    """Within ``SCORE_RTOL`` of the reference's own magnitude."""
    return abs(got - ref) <= SCORE_RTOL * abs(ref)


def _serve_over_http(engine, queries: list, watcher) -> dict:
    """Stand the engine up behind the HTTP front on an ephemeral port,
    POST every query twice, and hold each first-pass answer to the
    engine's own unbatched exact answer (``serve.selftest``'s
    comparison): placements identical, fitness to ``SCORE_RTOL`` of the
    reference's. The second pass must compile nothing and repeat the first bit for bit."""
    from fks_tpu.serve import ServeService, make_http_server

    service = ServeService(engine, max_wait_s=0.002)
    server = make_http_server(service, 0, deadline_s=600.0)
    port = server.server_address[1]
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    try:
        first = [_post(port, {"id": f"smoke-{i}", "pods": q})
                 for i, q in enumerate(queries)]
        marks = watcher.backend_compile_count
        second = [_post(port, {"id": f"smoke-again-{i}", "pods": q})
                  for i, q in enumerate(queries)]
        recompiles = watcher.backend_compile_count - marks
    finally:
        server.shutdown()
        server.server_close()
        service.close()
        thread.join(timeout=30)
    bad, max_drift = [], 0.0
    for i, (q, a, b) in enumerate(zip(queries, first, second)):
        ref = engine.reference_answer(q)
        drift = abs(a["score"] - ref["score"])
        max_drift = max(max_drift, drift)
        if (not _score_agrees(a["score"], ref["score"])
                or a["placements"] != ref["placements"]
                or a["scheduled"] != ref["scheduled"]
                or b["score"] != a["score"]
                or b["placements"] != a["placements"]):
            bad.append({"query": i, "pods": len(q), "drift": drift,
                        "placements_match":
                            a["placements"] == ref["placements"]})
    return {
        "queries": [len(q) for q in queries],
        "scores": [round(a["score"], 6) for a in first],
        "scheduled": [a["scheduled"] for a in first],
        "max_drift": max_drift, "second_pass_compiles": recompiles,
        "degraded_fallback_armed": service.degrade is not None,
        "engine_kind": engine.engine_kind, "sim_engine": engine.engine_name,
        "program_capacity": engine.program_capacity,
        "lanes_per_device": _require_every_device(
            engine.last_lanes_per_device, engine.mesh),
        "ok": (not bad and recompiles == 0 and service.degrade is None),
        "mismatch": bad,
    }


def step_serve(wl_a, wl_b, champions: list, sizes_a: list, sizes_b: list,
               watcher, mesh=None, portfolio_pods: int = 128) -> dict:
    """The best ledger champion (``champions[0]``) as a VM serve engine
    over A's cluster snapshot behind ``make_http_server``; one more
    engine over B's node list; then ``champions[:2]`` as a two-slot
    ``PortfolioEngine`` through ``portfolio_selftest``. What-if queries
    are windows of the default pod list. ``--degraded-fallback`` is not
    armed: a device fault must fail the step, not move it to the CPU."""
    from fks_tpu.portfolio import PortfolioEngine, portfolio_selftest
    from fks_tpu.serve import ShapeEnvelope, VMServeEngine

    envelope = ShapeEnvelope(max_pods=max(sizes_a + sizes_b
                                          + [portfolio_pods]))
    out = {}
    for tag, wl, sizes in (("A", wl_a, sizes_a), ("B", wl_b, sizes_b)):
        if not sizes:
            continue
        engine = VMServeEngine(champions[0], wl, envelope=envelope,
                               mesh=mesh)
        base = engine.base_pods
        # sliding windows, so no two queries see the same arrivals
        queries = []
        for i, n in enumerate(sizes):
            start = (17 * i) % max(1, len(base) - n + 1)
            queries.append(base[start:start + n])
        out[tag] = _serve_over_http(engine, queries, watcher)
    if len(champions) > 1:
        pf = PortfolioEngine(champions[:2], wl_a, n_slots=2,
                             envelope=envelope, mesh=mesh)
        res = portfolio_selftest(pf, count=2, pods_per_query=portfolio_pods)
        out["portfolio"] = {k: res[k] for k in (
            "ok", "n_slots", "checked", "max_drift", "mixed_max_drift",
            "placements_match", "program_capacity", "failures")}
        # the selftest's own tolerance is 1e-5 and it reports no scores,
        # so the bound is absolute here: fitness is at most 1 (these
        # queries score 0.1-0.5), and every chip run so far read 0.0
        out["portfolio"]["ok"] = bool(
            res["ok"] and res["max_drift"] <= SCORE_RTOL
            and res["mixed_max_drift"] <= SCORE_RTOL)
    out["ok"] = all(v["ok"] for v in out.values())
    return out


def step_fused(wl, lanes: int = 64, seed: int = 0,
               interpret: bool = False) -> dict:
    """``make_fused_population_run(..., interpret=False)``: the Pallas
    kernel compiled by libtpu's Mosaic at ``lanes`` candidates, then
    the fused-vs-flat device gate (8 candidates: scheduled counts
    equal, scores to 2e-5). ``interpret=True`` is for the CPU tests."""
    import jax
    import numpy as np

    from fks_tpu.models import parametric
    from fks_tpu.parallel import make_population_eval
    from fks_tpu.sim import fused
    from fks_tpu.sim.engine import SimConfig

    cfg = SimConfig(max_steps=4 * wl.num_pods, track_ctime=False)
    params = parametric.init_population(jax.random.PRNGKey(seed), lanes,
                                        noise=0.1)
    run = jax.jit(fused.make_fused_population_run(
        wl, cfg, lanes=lanes, interpret=interpret))
    full = jax.device_get(run(params))
    ncheck = min(8, lanes)
    got = jax.device_get(run(params[:ncheck]))
    ref = jax.device_get(make_population_eval(wl, cfg=cfg, engine="flat")(
        params[:ncheck]))
    sched_equal = bool(np.array_equal(got.scheduled_pods,
                                      ref.scheduled_pods))
    drift = float(np.max(np.abs(np.asarray(got.policy_score)
                                - np.asarray(ref.policy_score))))
    return {"ok": sched_equal and drift <= 2e-5, "lanes": lanes,
            "truncated_lanes": int(np.asarray(full.truncated).sum()),
            "best_score": round(float(np.max(full.policy_score)), 6),
            "gate_candidates": ncheck, "scheduled_equal": sched_equal,
            "score_drift": drift}


# ------------------------------------------------------------------ driver


def _audit_flat_scores(trace: str) -> dict:
    """policy -> recorded flat fitness from the newest audit row."""
    row = None
    with open(AUDIT) as f:
        for line in f:
            doc = json.loads(line)
            if doc.get("trace") == trace and "policies" in doc:
                row = doc
    if row is None:
        raise RuntimeError(f"no divergence audit row for {trace}")
    return {k: float(v["flat"]) for k, v in row["policies"].items()}


def _generation_sources(trace: str) -> dict:
    """The smoke's code generation: first_fit, best_fit and the three
    audited champions, each with its recorded flat fitness."""
    from fks_tpu.obs.watchdog import panel_sources

    recorded = _audit_flat_scores(trace)
    return {name: (code, recorded[name])
            for name, code in panel_sources(3).items()}


def run_steps(steps: list, watcher, device: dict, partial: bool = False,
              cache_dir: str | None = None,
              t_start: float | None = None) -> int:
    """Run ``steps`` (name, thunk) in order, stopping at the first whose
    check fails; one JSON row per step on stdout, then the summary row
    (ends with ``"claim": null``), then — last — the verdict the chip check
    reads: ``{"ok": ..., "device": {"platform", "kind", "count"}}`` and
    nothing else. ``t_start``: when the caller's set-up began, so the
    summary's wall includes it. Returns the exit code."""
    if t_start is None:
        t_start = time.perf_counter()
    done = []
    failed = None
    for name, fn in steps:
        t0 = time.perf_counter()
        c0 = (watcher.backend_compile_seconds, watcher.compiled_count,
              watcher.cache_hits)
        try:
            check = fn()
        except Exception as e:  # noqa: BLE001 — the failure IS the result
            import traceback
            traceback.print_exc()
            check = {"ok": False,
                     "error": f"{type(e).__name__}: {str(e)[-2000:]}"}
        if "ok" not in check:  # a step over several deployments
            check["ok"] = all(v["ok"] for v in check.values())
        row = {
            "step": name, "ok": bool(check["ok"]),
            "bringup_wall_s": round(time.perf_counter() - t0, 2),
            "bringup_compile_s": round(
                watcher.backend_compile_seconds - c0[0], 2),
            # programs XLA really compiled (requests minus persistent-
            # cache hits), the summary's definition too
            "backend_compiles": watcher.compiled_count - c0[1],
            "compile_cache_hits": watcher.cache_hits - c0[2],
            "check": check,
        }
        print(json.dumps(row), flush=True)
        done.append({k: row[k] for k in (
            "step", "ok", "bringup_wall_s", "bringup_compile_s",
            "backend_compiles", "compile_cache_hits")})
        if not row["ok"]:
            failed = name
            break
    watcher.uninstall()
    summary = {
        "step": "summary", "ok": failed is None,
        "partial": partial, "failed_step": failed, "steps": done,
        "bringup_wall_s": round(time.perf_counter() - t_start, 2),
        "backend_compiles": watcher.compiled_count,
        "compile_cache_hits": watcher.cache_hits,
        "compile_cache_dir": cache_dir,
        "claim": None,
    }
    print(json.dumps(summary), flush=True)
    print(json.dumps({"ok": failed is None, "device": device}), flush=True)
    return 0 if failed is None else 1


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--steps", default="",
                    help="comma-separated subset of steps (default: all)")
    wanted = [s for s in ap.parse_args(argv).steps.split(",") if s]

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        log(f"chip_smoke: no TPU (jax.devices()[0].platform == "
            f"{dev.platform!r}); nothing is run on another backend")
        return 3
    from fks_tpu import obs
    from fks_tpu.data import TraceParser
    from fks_tpu.funsearch import EvolutionConfig
    from fks_tpu.models import zoo
    from fks_tpu.parallel import population_mesh
    from fks_tpu.serve import load_champion
    from fks_tpu.utils import place_compile_cache

    cache_dir = place_compile_cache()
    devices = jax.devices()
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    import importlib.metadata

    import jaxlib
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "unknown"
    print(json.dumps({"step": "device", **device,
                      "x64": bool(jax.config.jax_enable_x64),
                      "compile_cache_dir": cache_dir,
                      "jax": jax.__version__, "jaxlib": jaxlib.__version__,
                      "libtpu": libtpu}), flush=True)
    mesh = population_mesh(devices) if len(devices) > 1 else None
    watcher = obs.CompileWatcher().install()
    out_dir = tempfile.mkdtemp(prefix="fks_chip_smoke_")
    t_start = time.perf_counter()

    parser = TraceParser()
    wl_a = parser.parse_workload(node_file=NODES_A, pod_file=PODS)
    wl_b = parser.parse_workload(node_file=NODES_B, pod_file=PODS)
    log(f"deployment A: {wl_a.num_nodes} nodes x {wl_a.num_pods} pods; "
        f"B: {wl_b.num_nodes} nodes x {wl_b.num_pods} pods")
    checked = _generation_sources(PODS)
    # POPULATION CUT for the time limit: a full generation is
    # candidates_per_generation (8) lanes, but the FakeLLM candidates that
    # would fill it hit the 65,216-step cap under flat on this trace and
    # hold the lockstep batch ~23k events (~90 s) past the slowest
    # champion. The evolve step runs such candidates anyway.
    full = EvolutionConfig().candidates_per_generation
    log(f"evaluate_code: population cut from {full} to {len(checked)} "
        "(audited policies only; FakeLLM fillers dropped)")
    # and for evolve: FakeLLM drafts runaway candidates into every
    # generation, so each generation is 65,216 lockstep events whatever
    # its width; 4 lanes instead of 8 roughly halve the per-event cost.
    # The same cut on any device count: the evaluator, not the smoke,
    # keeps a mesh launch at two lanes per device (vm.bucket_lanes).
    elite = EvolutionConfig().elite_size
    evolve_pop = elite + min(full, 4)
    log(f"evolve: candidates per generation cut from {full} to "
        f"{evolve_pop - elite} (population_size {evolve_pop})")
    ledger = sorted(glob.glob(os.path.join(LEDGER, "funsearch_*.json")),
                    key=lambda p: -load_champion(p).score)
    champions = [load_champion(p) for p in ledger[:2]]

    steps = [
        ("parity", lambda: step_parity(
            PODS, NODES_A, PARITY_A, wl_a.num_pods, out_dir)),
        ("evaluate_parametric", lambda: {
            "A": step_evaluate_parametric(wl_a, 256, mesh),
            "B": step_evaluate_parametric(wl_b, 8, mesh,
                                          exact_best_fit=BEST_FIT_B)}),
        ("evaluate_code", lambda: {
            **step_evaluate_code(wl_a, checked, mesh),
            "population_cut_from": full}),
        ("evaluate_forked", lambda: step_evaluate_forked(
            parser.parse_workload(node_file=NODES_B, pod_file=PODS_LOADED,
                                  snapshot_file=SNAPSHOT),
            1024, FORKED_B, mesh, placed_by=zoo.best_fit())),
        ("evolve", lambda: {
            **step_evolve(out_dir, population_size=evolve_pop),
            "candidates_per_generation_cut": [full, evolve_pop - elite]}),
        ("serve", lambda: step_serve(
            wl_a, wl_b, champions, [128, 256, 512, 1024], [128],
            watcher, mesh)),
        ("fused", lambda: step_fused(wl_a)),
    ]
    unknown = sorted(set(wanted) - {name for name, _ in steps})
    if unknown:
        log(f"chip_smoke: unknown steps {unknown}; have "
            f"{[name for name, _ in steps]}")
        return 2
    if wanted:
        steps = [(name, fn) for name, fn in steps if name in wanted]
    return run_steps(steps, watcher, device, partial=bool(wanted),
                     cache_dir=cache_dir, t_start=t_start)


if __name__ == "__main__":
    sys.exit(main())
