"""Command-line harness: benchmark table, single runs, evolution.

TPU-native counterpart of the reference's script entry points — the
5-policy benchmark table (reference: tests/test_scheduler.py:223-361
``SchedulerTester`` + ``main``), the integration smoke run
(tests/test_integration.py:110-148), and the evolution CLI
(funsearch/funsearch_integration.py:682-706) — consolidated behind one
``argparse`` interface, which the reference lacks entirely (SURVEY.md §5:
"no argparse/env/CLI flags anywhere").

Usage:
    python -m fks_tpu.cli bench [--policies a,b,...] [--trace F] [--nodes F]
    python -m fks_tpu.cli simulate --policy best_fit [--validate]
    python -m fks_tpu.cli evolve [--config F] [--fake-llm] [--checkpoint F]
    python -m fks_tpu.cli scale [--nodes-count N] [--pods-count P] [--pop C]
    python -m fks_tpu.cli serve [--champion F] [--queries F | --http PORT]
    python -m fks_tpu.cli loadgen [--tenants SPEC] [--duration S] [--http]
    python -m fks_tpu.cli report RUN_DIR
    python -m fks_tpu.cli export-metrics RUN_DIR [--out F]
    python -m fks_tpu.cli watch RUN_DIR [--interval S] [--once]
    python -m fks_tpu.cli compare BASELINE CANDIDATE [--threshold m=rel:X]
    python -m fks_tpu.cli trends ROOT [--metric m,...] [--fail-on-alert]
    python -m fks_tpu.cli trace-diff --engines exact,flat [--policy P | --code F]
    python -m fks_tpu.cli scenarios [--suite NAME [--scenario I]]
    python -m fks_tpu.cli lint [PATHS...] [--write-pins | --no-pins]
    python -m fks_tpu.cli traces
    python -m fks_tpu.cli snapshot

Every subcommand accepts ``--run-dir DIR`` to flight-record the run
(fks_tpu.obs): spans, compile/device telemetry, and per-generation
evolution ledger land in DIR as JSONL; ``report DIR`` renders the summary,
``export-metrics`` emits OpenMetrics text, ``watch`` live-tails with a
heartbeat liveness verdict, and ``compare`` gates a candidate run against
a baseline (nonzero exit on regression).
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import sys


def _apply_platform_flags(args):
    """``--cpu`` forces the CPU backend; ``--devices N`` sizes the device
    set the command meshes over — N virtual CPU devices under ``--cpu``,
    the first N real devices otherwise — and fails when fewer exist
    (silently running on one device is the footgun the flag prevents)."""
    import jax

    n_dev = getattr(args, "devices", 0)
    if getattr(args, "cpu", False):
        jax.config.update("jax_platforms", "cpu")
        if n_dev:
            import os

            # must precede first backend init (same constraint as
            # __graft_entry__.dryrun_multichip)
            jax.config.update("jax_num_cpu_devices", n_dev)
            # n virtual device programs time-slicing few host cores skew
            # their arrival at collectives far past XLA-CPU's default
            # terminate timeout (observed: the 100k-pod mesh run died in
            # rendezvous on a 1-core container until these were raised;
            # README "Synthetic scale"). XLA_FLAGS is read at backend
            # creation, so appending here is still in time.
            tokens = os.environ.get("XLA_FLAGS", "").split()
            names = {t.split("=")[0] for t in tokens}
            for f in ("--xla_cpu_collective_timeout_seconds=7200",
                      "--xla_cpu_collective_call_terminate_timeout_seconds"
                      "=7200"):
                name = f.split("=")[0]
                # token-boundary match, not substring: a user-set value
                # for the SAME flag is honored (warn, since 40 s defaults
                # hang the 100k-pod mesh run), and an unrelated flag
                # sharing a prefix can't mask ours
                if name in names:
                    if f not in tokens:
                        print(f"fks_tpu: honoring existing {name} from "
                              "XLA_FLAGS", file=sys.stderr)
                    continue
                tokens.append(f)
            try:  # private probe; best-effort warning only
                initialized = bool(jax._src.xla_bridge._backends)
            except AttributeError:
                initialized = False
            if initialized:  # appended too late to apply
                print("fks_tpu: JAX backends already initialized; "
                      "XLA_FLAGS collective timeouts will not take "
                      "effect this run", file=sys.stderr)
            os.environ["XLA_FLAGS"] = " ".join(tokens)
    if getattr(args, "f64", False):
        jax.config.update("jax_enable_x64", True)
    if n_dev:
        have = jax.devices()
        if len(have) < n_dev:
            raise SystemExit(
                f"--devices {n_dev}: only {len(have)} "
                f"{have[0].platform} device(s) visible")


def _mesh_devices(args):
    """The devices a command meshes over: the first ``--devices`` of what
    is visible (``_apply_platform_flags`` already checked the count), or
    everything visible when the flag is unset."""
    import jax

    n_dev = getattr(args, "devices", 0)
    return jax.devices()[:n_dev] if n_dev else jax.devices()


def _metrics_writer(args):
    """Context manager: a MetricsWriter when --metrics was given (opened up
    front so bad paths fail fast, closed on every exit path), else a null
    context yielding None."""
    if getattr(args, "metrics", ""):
        from fks_tpu.utils import MetricsWriter

        return MetricsWriter(args.metrics)
    return contextlib.nullcontext(None)


def _flight_recorder(args, command):
    """Context manager installing the process-wide flight recorder when
    ``--run-dir`` was given (fks_tpu.obs.recording), else the shared
    NullRecorder — identical API, zero filesystem writes. Opened up front
    so an unwritable run directory fails before any device work."""
    from fks_tpu import obs

    run_dir = getattr(args, "run_dir", "")
    if not run_dir:
        return obs.recording(obs.NULL)
    return obs.recording(obs.FlightRecorder(
        run_dir, meta={"command": command, "argv": sys.argv[1:]}))


def _parse_workload(args):
    from fks_tpu.data import TraceParser

    parser = TraceParser()
    return parser, parser.parse_workload(
        node_file=args.nodes, pod_file=args.trace,
        snapshot_file=getattr(args, "snapshot", "") or None,
        gpu_spec=getattr(args, "gpu_spec", "ignore"))


def _add_trace_flags(p, snapshot=False):
    """``--trace`` / ``--nodes``; ``snapshot``: the commands that evaluate
    policies (bench, simulate, evolve) also take ``--snapshot`` and
    ``--gpu-spec``."""
    p.add_argument("--trace", default="openb_pod_list_default.csv",
                   help="pod CSV under benchmarks/traces/csv/ (its "
                        "gpu_spec column is read only with --gpu-spec "
                        "honor)")
    p.add_argument("--nodes", default="gpu_models_filtered.csv",
                   help="node CSV under benchmarks/traces/csv/")
    if snapshot:
        p.add_argument("--gpu-spec", choices=("ignore", "honor"),
                       default="ignore",
                       help="what to do with the pod list's gpu_spec "
                            "column (the |-joined GPU models a pod "
                            "accepts; OpenB's gpuspec* lists fill it). "
                            "ignore (default): as upstream does. honor: a "
                            "pod that names models is placed only on a "
                            "node whose model (the node list's column) is "
                            "among them; every other node is to it as a "
                            "cordoned node is. Engines exact and flat")
        p.add_argument("--snapshot", default="",
                       help="snapshot CSV under benchmarks/traces/csv/ "
                            "(name,node_sn,gpus for a cluster loaded by "
                            "arrivals alone; with event,rule for a moment "
                            "of a run with departures and refusals): start "
                            "from the state it pins; needs --engine flat on "
                            "these commands (fks_tpu.data.snapshot; "
                            "serving forks on the exact engine through "
                            "the library, from either kind of file: "
                            "VMServeEngine on a workload parsed with "
                            "snapshot_file=)")


def _result_row(name, res, wall):
    import numpy as np

    return {
        "policy": name,
        "score": round(float(res.policy_score), 4),
        "scheduled": f"{int(res.scheduled_pods)}",
        "cpu%": round(100 * float(res.avg_cpu_utilization), 1),
        "mem%": round(100 * float(res.avg_memory_utilization), 1),
        "gpu%": round(100 * float(res.avg_gpu_count_utilization), 1),
        "milli%": round(100 * float(res.avg_gpu_memory_utilization), 1),
        "frag": round(float(res.gpu_fragmentation_score), 3),
        "snaps": int(res.num_snapshots),
        "events": int(res.events_processed),
        "max_nodes": int(res.max_nodes),
        "wall_s": round(wall, 3),
    }


def _print_table(rows):
    if not rows:
        return
    cols = list(rows[0].keys())
    widths = {c: max(len(c), *(len(str(r[c])) for r in rows)) for c in cols}
    line = "  ".join(c.rjust(widths[c]) for c in cols)
    print(line)
    print("-" * len(line))
    for r in rows:
        print("  ".join(str(r[c]).rjust(widths[c]) for c in cols))


def _pick_simulate(args):
    from fks_tpu.sim import get_engine
    return get_engine(getattr(args, "engine", "exact")).simulate


def cmd_bench(args):
    """The reference benchmark table (test_scheduler.py:287-331): every
    requested policy against the workload, jit-compiled, with wall time."""
    _apply_platform_flags(args)
    import jax.numpy as jnp

    from fks_tpu.models import zoo
    from fks_tpu.sim.engine import SimConfig
    from fks_tpu.utils import result_record

    from fks_tpu import obs

    simulate = _pick_simulate(args)
    _, wl = _parse_workload(args)
    names = (args.policies.split(",") if args.policies else list(zoo.ZOO))
    dtype = jnp.float64 if args.f64 else jnp.float32
    cfg = SimConfig(score_dtype=dtype, validate_invariants=args.validate)
    print(f"workload: {wl.num_nodes} nodes x {wl.num_pods} pods "
          f"({args.nodes} x {args.trace})", file=sys.stderr)
    rows = []
    with _flight_recorder(args, "bench") as rec, \
            obs.watch_compiles(rec), _metrics_writer(args) as metrics:
        if rec.enabled:
            rec.annotate_meta(engine=args.engine, trace=args.trace,
                              workload={"nodes": wl.num_nodes,
                                        "pods": wl.num_pods})
            obs.record_devices(rec)
        for name in names:
            if name not in zoo.ZOO:
                print(f"unknown policy {name!r}; have {list(zoo.ZOO)}",
                      file=sys.stderr)
                return 2
            with obs.span("policy", policy=name) as t:
                res = simulate(wl, zoo.ZOO[name](dtype=dtype), cfg)
                t.sync(res.policy_score)
            wall = t.seconds
            rows.append(_result_row(name, res, wall))
            if metrics:
                metrics.write("bench", result_record(res), policy=name,
                              wall_s=wall, trace=args.trace, nodes=args.nodes)
            rec.metric("bench", result_record(res), policy=name,
                       wall_s=wall, trace=args.trace, nodes=args.nodes)
            if args.validate and int(res.invariant_violations):
                print(f"WARNING: {name}: {int(res.invariant_violations)} "
                      "invariant violations", file=sys.stderr)
    _print_table(rows)
    return 0


def cmd_simulate(args):
    """Single policy, detailed output (reference: tests/test_integration.py
    style summary)."""
    _apply_platform_flags(args)
    import jax.numpy as jnp
    import numpy as np

    from fks_tpu.models import zoo
    from fks_tpu.sim.engine import SimConfig
    from fks_tpu.utils import result_record

    from fks_tpu import obs

    simulate = _pick_simulate(args)
    _, wl = _parse_workload(args)
    dtype = jnp.float64 if args.f64 else jnp.float32
    cfg = SimConfig(score_dtype=dtype, validate_invariants=args.validate)
    with _flight_recorder(args, "simulate") as rec, \
            obs.watch_compiles(rec), \
            _metrics_writer(args) as metrics:  # up front: bad paths fail fast
        with obs.span("simulate", policy=args.policy) as t:
            res = simulate(wl, zoo.ZOO[args.policy](dtype=dtype), cfg)
            t.sync(res.policy_score)
        wall = t.seconds
        n_pods = wl.num_pods
        gpu_pods = int(np.sum(np.asarray(wl.pods.num_gpu)[:n_pods] > 0))
        out = _result_row(args.policy, res, wall)
        out.update({
            "gpu_pods": gpu_pods, "cpu_only_pods": n_pods - gpu_pods,
            "success_rate": round(100 * int(res.scheduled_pods) / max(1, n_pods), 2),
            "failed": bool(res.failed), "truncated": bool(res.truncated),
            "invariant_violations": int(res.invariant_violations),
        })
        if metrics:
            metrics.write("simulate", result_record(res), policy=args.policy,
                          wall_s=wall, trace=args.trace, nodes=args.nodes)
        rec.metric("simulate", result_record(res), policy=args.policy,
                   wall_s=wall, trace=args.trace, nodes=args.nodes)
    print(json.dumps(out, indent=2))
    return 0


def _divergence_bound(trace: str, path: str = ""):
    """Latest measured flat-vs-exact divergence for ``trace`` from the
    divergence audit (tools/divergence_audit.py): ``(drift, cascades)``
    where drift is the arithmetic max|d| with retry-cascade rows excluded
    (falling back to max|d| for pre-cascade-era rows) and cascades counts
    panel policies whose flat run blew the event budget. None when no
    audit row exists."""
    import os

    path = path or os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmarks", "results", "divergence_audit.jsonl")
    found = None
    try:
        with open(path) as f:
            for line in f:
                try:
                    row = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if row.get("trace") == trace and \
                        row.get("max_abs_d") is not None:
                    found = row  # latest row wins
    except OSError:
        return None
    if found is None:
        return None
    drift = found.get("max_drift")
    if drift is None:
        drift = found["max_abs_d"]
    return float(drift), int(found.get("flat_cascades", 0))


def cmd_evolve(args):
    """Evolution loop (reference: funsearch_integration.py:682-706), with a
    hermetic --fake-llm mode and checkpoint/resume the reference lacks."""
    from fks_tpu.funsearch import EvolutionConfig, FakeLLM
    from fks_tpu.funsearch import evolution as evo
    from fks_tpu.sim.engine import SimConfig

    cfg = (EvolutionConfig.from_json(args.config) if args.config
           else EvolutionConfig())
    if args.generations is not None:
        cfg.generations = args.generations
    if args.parametric_rounds is not None:
        cfg.parametric_rounds = args.parametric_rounds
    if args.parity_sample is not None:
        cfg.parity_sample = args.parity_sample
    if args.parity_tol is not None:
        cfg.parity_tol = args.parity_tol
    if args.suite is not None:
        cfg.scenario_suite = args.suite
    if args.robust_agg is not None:
        cfg.robust_aggregation = args.robust_agg
    if args.budget is not None:
        cfg.budget_schedule = args.budget
    if args.budget_eta is not None:
        cfg.budget_eta = args.budget_eta
    if args.probe_suite is not None:
        cfg.probe_suite = args.probe_suite
    if args.probe_steps is not None:
        cfg.probe_steps = args.probe_steps
    if args.wal and not args.checkpoint:
        print("note: --wal without --checkpoint only protects the first "
              "generation; pass --checkpoint so every generation boundary "
              "is durable", file=sys.stderr)
    backend = FakeLLM(seed=cfg.seed) if args.fake_llm else None
    if backend is None and not cfg.llm.api_key:
        print("no API key in config; use --fake-llm for hermetic runs",
              file=sys.stderr)
        return 2
    if args.engine != "exact":
        # search on a fast engine ranks by a fitness that can differ from
        # the exact replica's; surface the bound MEASURED on this trace
        # (round-3 verdict weak #3) instead of a global number
        bound = _divergence_bound(args.trace)
        if bound is not None:
            drift, cascades = bound
            casc = (f"; {cascades} panel polic"
                    f"{'y' if cascades == 1 else 'ies'} hit a retry "
                    "cascade (flat score 0 — culled, never over-promoted)"
                    if cascades else "")
            print(f"note: measured flat-vs-exact drift on {args.trace}: "
                  f"max|d|={drift:.4f}{casc} (panel of seed + champion "
                  "policies; tools/divergence_audit.py). NEW BEST "
                  "admissions are exact-rescored; treat fast-engine "
                  "rankings within the drift bound as ties.",
                  file=sys.stderr)
        else:
            print(f"note: no divergence audit row for {args.trace}; run "
                  "tools/divergence_audit.py --traces "
                  f"{args.trace} for a measured flat-vs-exact bound",
                  file=sys.stderr)
    _apply_platform_flags(args)
    from fks_tpu import obs

    _, wl = _parse_workload(args)
    # a generation shards over every visible device (as ``scale`` does);
    # one device keeps the plain vmap tier
    devices = _mesh_devices(args)
    mesh = None
    if len(devices) > 1:
        from fks_tpu.parallel import population_mesh
        mesh = population_mesh(devices)
    with _flight_recorder(args, "evolve") as rec, \
            obs.watch_compiles(rec), _metrics_writer(args) as metrics:
        if rec.enabled:
            rec.annotate_meta(engine=args.engine, trace=args.trace,
                              nodes=args.nodes,
                              workload={"nodes": wl.num_nodes,
                                        "pods": wl.num_pods})
            obs.record_devices(rec)
        on_gen = None
        if metrics:
            import dataclasses

            def on_gen(st):
                # streamed per generation: an interrupted evolution still
                # leaves a complete metric trail up to the crash point
                metrics.write("generation", dataclasses.asdict(st))
        fs = evo.run(wl, cfg, backend=backend,
                     sim_config=SimConfig(watchdog=args.watchdog),
                     checkpoint_path=args.checkpoint,
                     wal_path=args.wal, out_dir=args.out,
                     engine=args.engine, on_generation=on_gen,
                     profile=args.profile, mesh=mesh)
        if fs.best:
            rec.annotate_meta(best_score=fs.best[1],
                              best_exact=fs.best_exact,
                              generations=fs.generation)
        if fs.sentinel.alerts:
            rec.annotate_meta(parity_alerts=fs.sentinel.alerts)
        if fs.best:
            print(f"best fitness: {fs.best[1]:.4f}")
            # on interrupt evo.run already persisted champions — don't
            # double-save
            if args.out and not getattr(fs, "interrupted", False):
                path = fs.save_top_policies(args.out, k=5)
                print(f"saved top policies to {path}")
                print("saved best policy to "
                      f"{fs.save_best_policy(args.out)}")
        if args.engine != "exact":
            # after the saves: they rescore too. A swallowed rescore
            # failure ranks on search fitness, so say how many there were
            # and where the rescores ran
            print(f"exact rescore: platform={fs.rescore_platform or '-'} "
                  f"fallbacks={fs.rescore_fallbacks}", file=sys.stderr)
            rec.annotate_meta(rescore_platform=fs.rescore_platform,
                              rescore_fallbacks=fs.rescore_fallbacks)
    if fs.sentinel.alerts:
        # the parity sentinel's nonzero-exit policy: drift beyond the
        # tolerance means the fitness selection trusted disagrees with the
        # exact reference evaluator — champions are saved above, but the
        # run must not read as clean to CI/driver scripts
        print(f"PARITY ALERT: {fs.sentinel.alerts} generation(s) exceeded "
              f"drift tolerance {cfg.parity_tol:g} (max drift "
              f"{fs.sentinel.max_drift:.3g}); see the run dir's alert "
              "events", file=sys.stderr)
        return 3
    if getattr(fs, "llm_outage", False):
        # distinct exit code: the run halted on the LLM-outage circuit
        # breaker (llm_outage ledger event + checkpoint written), so a
        # supervisor can tell "endpoint down, retry later" apart from a
        # failed search
        print(f"LLM OUTAGE: halted at generation {fs.generation} after "
              f"{fs.cfg.llm_outage_generations} consecutive generations "
              "with zero drafted candidates; checkpoint saved",
              file=sys.stderr)
        return 4
    return 0


def cmd_scale(args):
    """Synthetic scale run (BASELINE.json config 5 shape): N-node x P-pod
    generated trace, population-parallel evaluation, throughput report.
    Uses the device mesh when more than one device is visible, plain vmap
    otherwise. ``--code-pop N`` additionally measures the VM
    code-candidate tier (FakeLLM candidates lowered to register programs,
    sharded over the same mesh via make_sharded_code_eval)."""
    _apply_platform_flags(args)
    import jax

    from fks_tpu import obs
    from fks_tpu.data.synthetic import synthetic_workload
    from fks_tpu.models import parametric
    from fks_tpu.obs import span
    from fks_tpu.parallel import (
        make_population_eval, make_sharded_eval, pad_population,
        population_mesh,
    )
    from fks_tpu.sim.engine import SimConfig, resolve_auto_prefilter
    from fks_tpu.utils import ThroughputMeter

    with _flight_recorder(args, "scale") as rec, \
            obs.watch_compiles(rec), \
            _metrics_writer(args) as metrics:  # up front: bad paths fail fast
        node_park = None
        if getattr(args, "openb_nodes", False):
            from fks_tpu.data.traces import parse_node_yaml
            # repo-root-relative resolution (default_traces_dir), so the
            # vendored list loads from any cwd
            node_park = parse_node_yaml()
        wl = synthetic_workload(args.nodes_count, args.pods_count,
                                seed=args.seed, nodes=node_park)
        print(f"synthetic workload: {wl.num_nodes} nodes x {wl.num_pods} "
              f"pods, population {args.pop}"
              + (" (OpenB node park)" if node_park else ""),
              file=sys.stderr)
        if rec.enabled:
            rec.annotate_meta(engine=args.engine,
                              workload={"nodes": wl.num_nodes,
                                        "pods": wl.num_pods},
                              population=args.pop)
            obs.record_devices(rec)
        pop = parametric.init_population(
            jax.random.PRNGKey(args.seed), args.pop, noise=0.1)
        pk_override = getattr(args, "prefilter_k", None)
        if args.engine == "fused" and pk_override is None:
            pk = 0  # the fused kernel has no prefilter path; don't probe
        else:
            pk = resolve_auto_prefilter(
                parametric.score, jax.tree_util.tree_map(lambda x: x[0], pop),
                wl.cluster.n_padded, wl.cluster.g_padded,
                override=pk_override, recorder=rec)
        cfg = SimConfig(node_prefilter_k=pk,
                        state_pack=getattr(args, "state_pack", False))
        devices = _mesh_devices(args)
        try:
            if len(devices) > 1:
                mesh = population_mesh(devices)
                padded, real = pad_population(pop, mesh)
                obs.record_mesh(mesh, real_count=args.pop, recorder=rec)
                ev = make_sharded_eval(wl, mesh, cfg=cfg,
                                       elite_k=min(4, args.pop),
                                       engine=args.engine)
                with span("eval", population=args.pop) as t:
                    scores = t.sync(ev(padded, real)[0])[:real]
                mode = f"sharded over {len(devices)} devices"
            else:
                evp = make_population_eval(wl, cfg=cfg, engine=args.engine)
                with span("eval", population=args.pop) as t:
                    res = t.sync(evp(pop))
                scores = res.policy_score
                mode = "vmap on 1 device"
        except ValueError as e:
            if args.engine != "fused" or (
                    "VMEM" not in str(e)
                    and "node_prefilter_k" not in str(e)
                    and "state_pack" not in str(e)):
                raise  # only the fused kernel's guards get guidance
            print(f"error: {e}\n(try smaller --nodes-count/--pods-count, "
                  f"or --engine flat)", file=sys.stderr)
            return 2
        meter = ThroughputMeter()
        meter.add(args.pop, t.seconds)
        out = {
            "mode": mode, "engine": args.engine,
            "nodes": wl.num_nodes, "pods": wl.num_pods,
            "population": args.pop, "wall_s": round(t.seconds, 3),
            "evals_per_sec": round(meter.rate, 3),
            "score_min": round(float(scores.min()), 4),
            "score_max": round(float(scores.max()), 4),
            "node_prefilter_k": cfg.node_prefilter_k,
            "prefilter_auto": pk_override is None,
            "state_pack": cfg.state_pack,
            "openb_nodes": node_park is not None,
        }
        if getattr(args, "code_pop", 0) > 0:
            from fks_tpu.funsearch import vm
            from fks_tpu.parallel import make_sharded_code_eval
            from fks_tpu.sim import get_engine

            # the fused kernel evaluates parametric weights only; the VM
            # tier runs on the interpreter engines
            code_engine = "flat" if args.engine == "fused" else args.engine
            c = wl.cluster
            progs, _ = vm.lower_fake_candidates(
                c.n_padded, c.g_padded, args.code_pop, capacity=256)
            if len(progs) < args.code_pop:
                print(f"error: FakeLLM lowered only {len(progs)} VM "
                      f"candidates; lower --code-pop", file=sys.stderr)
                return 2
            stacked = vm.stack_programs(progs[: args.code_pop])
            # the code tier probes its OWN policy cost: VM register
            # programs are the expensive case the prefilter exists for,
            # so auto may choose k>0 here while the parametric tier above
            # stayed dense
            pk_code = resolve_auto_prefilter(
                vm.score, progs[0], c.n_padded, c.g_padded,
                override=pk_override, recorder=rec)
            ccfg = dataclasses.replace(cfg, node_prefilter_k=pk_code)
            if len(devices) > 1:
                cpadded, creal = pad_population(stacked, mesh)
                cev = make_sharded_code_eval(
                    wl, mesh, cfg=ccfg, elite_k=min(4, args.code_pop),
                    engine=code_engine)
                with span("code_eval", code_population=args.code_pop) as ct:
                    cres = ct.sync(cev(cpadded, creal)[0])
            else:
                mod = get_engine(code_engine)
                crun = mod.make_population_run_fn(wl, vm.score, ccfg)
                with span("code_eval", code_population=args.code_pop) as ct:
                    cres = ct.sync(crun(stacked, mod.initial_state(wl, ccfg)))
            cscores = cres.policy_score[: args.code_pop]
            cmeter = ThroughputMeter()
            cmeter.add(args.code_pop, ct.seconds)
            out.update({
                "code_population": args.code_pop,
                "code_engine": code_engine,
                "code_prefilter_k": pk_code,
                "code_wall_s": round(ct.seconds, 3),
                "code_evals_per_sec": round(cmeter.rate, 3),
                "code_score_max": round(float(cscores.max()), 4),
            })
        if metrics:
            metrics.write("scale", out)
        rec.metric("scale", out)
    print(json.dumps(out, indent=2))
    return 0


def cmd_serve(args):
    """Serve a pinned champion as a warm what-if query engine
    (fks_tpu.serve): build or load an artifact, optionally pre-compile
    every shape bucket, then answer queries over stdin/JSONL, a file, or
    a localhost HTTP listener. ``--selftest N`` instead runs the
    batched-vs-unbatched exact-parity sweep and exits nonzero on any
    drift."""
    _apply_platform_flags(args)
    from fks_tpu import obs
    from fks_tpu.serve import (
        ServeEngine, ServeService, ShapeEnvelope, latest_champion,
        load_champion, selftest,
    )
    from fks_tpu.serve.service import run_http, run_jsonl

    with _flight_recorder(args, "serve") as rec, obs.watch_compiles(rec):
        import os as _os
        from fks_tpu.serve.artifact import CHAMPION_DIR
        mesh = None
        if getattr(args, "devices", 0):
            # mesh-sharded serving: shard the lane axis over the first
            # --devices devices (virtual under --cpu, real otherwise)
            from fks_tpu.parallel import population_mesh
            mesh = population_mesh(_mesh_devices(args))
        ledger_dir = args.ledger_dir or CHAMPION_DIR
        promotion_log = (args.promotion_log
                         or _os.path.join(ledger_dir, "promotion.jsonl"))
        if args.artifact:
            engine = ServeEngine.load(args.artifact, recorder=rec, mesh=mesh)
        else:
            champ_path = args.champion
            if not champ_path and args.follow_ledger:
                # crash recovery: the promotion log outranks raw ledger
                # order — restart with whatever the last surviving
                # promotion shipped, not merely the best-scored file
                from fks_tpu.pipeline import PromotionLog
                active = PromotionLog(promotion_log).active()
                if active and _os.path.exists(active.get("champion", "")):
                    champ_path = active["champion"]
                    print(f"resuming promoted champion: {champ_path}",
                          file=sys.stderr)
            if not champ_path:
                champ_path = latest_champion(ledger_dir, recorder=rec)
            if not champ_path:
                print("error: no champion JSON found — pass --champion or "
                      "evolve one first (policies/discovered/)",
                      file=sys.stderr)
                return 2
            champion = load_champion(champ_path)
            _, wl = _parse_workload(args)
            build_kw = dict(
                envelope=ShapeEnvelope(max_pods=args.max_pods,
                                       max_batch=args.max_batch),
                engine=args.engine,
                prefilter_k=getattr(args, "prefilter_k", None),
                state_pack=getattr(args, "state_pack", False),
                mesh=mesh, recorder=rec)
            engine = None
            if getattr(args, "serve_engine", "aot") == "vm":
                from fks_tpu.funsearch.vm import VMUnsupported
                from fks_tpu.serve import VMServeEngine
                try:
                    engine = VMServeEngine(champion, wl, **build_kw)
                except VMUnsupported as e:
                    # coverage gap, not an error: serve it on the exact
                    # AOT closure engine and say so (the recorded event
                    # is what the vm_serve_gate / tests assert on)
                    rec.event("vm_swap", outcome="fallback",
                              champion=champ_path, detail=str(e))
                    print(f"champion not VM-lowerable ({e}); falling "
                          "back to the AOT closure engine",
                          file=sys.stderr)
            if engine is None:
                engine = ServeEngine(champion, wl, **build_kw)
        if rec.enabled:
            rec.annotate_meta(
                engine=engine.engine_name,
                engine_kind=engine.engine_kind,
                champion={"score": engine.champion.score,
                          "source": engine.champion.source},
                envelope=engine.envelope.to_json(),
                policy_tier=engine.policy_tier,
                prefilter_k=engine.prefilter_k)
        cap = getattr(engine, "program_capacity", None)
        print(f"serving champion score={engine.champion.score:.4f} "
              f"tier={engine.policy_tier} engine={engine.engine_name} "
              f"kind={engine.engine_kind}"
              + (f" capacity={cap}" if cap else "")
              + f" prefilter_k={engine.prefilter_k}", file=sys.stderr)
        if args.save_artifact:
            if args.warmup:
                engine.warmup()
            path = engine.save(args.save_artifact)
            print(f"artifact saved: {path}", file=sys.stderr)
        if args.selftest:
            result = selftest(engine, count=args.selftest,
                              pods_per_query=args.pods_per_query,
                              tol=args.audit_tol)
            if getattr(args, "serve_engine", "aot") == "vm":
                # did the requested VM binding actually engage, or did
                # the champion fall back to the AOT closure engine?
                result["vm_coverage"] = (1.0 if engine.engine_kind == "vm"
                                         else 0.0)
            if rec.enabled and "snapshot_cache" in result:
                rec.metric("snapshot_cache", **result["snapshot_cache"])
            print(json.dumps(result, indent=2))
            return 0 if result["ok"] else 1
        if args.warmup and not args.save_artifact:
            n = engine.warmup()
            print(f"warm: {n} bucket programs compiled", file=sys.stderr)
        if args.save_artifact and not (args.queries or args.http):
            return 0  # artifact-build invocation, nothing to serve
        slo = None
        if args.slo_p99_ms or args.slo_qps:
            from fks_tpu.serve.accounting import SLOConfig
            slo = SLOConfig(p99_ms=args.slo_p99_ms, qps=args.slo_qps,
                            error_budget=args.slo_error_budget)
        service = ServeService(engine, recorder=rec,
                               max_wait_s=args.max_wait_ms / 1e3,
                               audit_every=args.audit_every,
                               audit_tol=args.audit_tol, slo=slo,
                               max_queue=args.max_queue,
                               default_deadline_s=args.request_deadline_s,
                               accounting=args.accounting)
        if args.degraded_fallback:
            from fks_tpu.resilience import exact_fallback_factory

            # fallback + rebuild reuse the engine's own champion/workload;
            # the rebuild recreates the primary configuration warm
            service.enable_degraded_mode(
                exact_fallback_factory(engine.champion, _parse_workload(
                    args)[1], engine.envelope, recorder=rec),
                rebuild_factory=None)
            print("degraded-mode fallback armed (exact engine, batch 1)",
                  file=sys.stderr)
        drainer = None
        if args.drain_state:
            from fks_tpu.resilience import (DrainCoordinator,
                                            load_serve_state)

            if _os.path.exists(args.drain_state):
                try:
                    n = service.preload_replay(
                        load_serve_state(args.drain_state)["replay"])
                    print(f"replay buffer preloaded: {n} queries from "
                          f"{args.drain_state}", file=sys.stderr)
                except ValueError as e:
                    print(f"ignoring stale drain state: {e}",
                          file=sys.stderr)
            drainer = DrainCoordinator(service,
                                       state_path=args.drain_state,
                                       recorder=rec)
            if not drainer.install():
                print("warning: SIGTERM handler unavailable off the main "
                      "thread; drain runs on normal shutdown only",
                      file=sys.stderr)
        stop_follow = None
        if args.follow_ledger:
            from fks_tpu.serve.accounting import SLOConfig as _SLO
            from fks_tpu.pipeline import (
                PromotionConfig, PromotionController, follow_ledger,
            )
            controller = PromotionController(
                service, ledger_dir=ledger_dir, log_path=promotion_log,
                config=PromotionConfig(slo=slo if slo is not None
                                       else _SLO()),
                recorder=rec)
            # one synchronous poll before traffic (a champion newer than
            # the one we loaded promotes up front, deterministically),
            # then the background poll thread takes over
            first = controller.poll_once()
            if first.get("action") != "idle":
                print(f"promotion: {first}", file=sys.stderr)
            stop_follow, _ = follow_ledger(controller,
                                           interval=args.promote_interval)
        try:
            if args.http:
                print(f"listening on http://127.0.0.1:{args.http} "
                      "(POST /query, GET /stats, GET /healthz)",
                      file=sys.stderr)
                run_http(service, args.http,
                         deadline_s=args.request_deadline_s,
                         drain_coordinator=drainer)
                errors = 0
            elif args.queries and args.queries != "-":
                with open(args.queries) as f:
                    errors = run_jsonl(service, f)
            else:
                errors = run_jsonl(service)  # stdin
        finally:
            if stop_follow is not None:
                stop_follow.set()
            if drainer is not None and drainer.report is None:
                # normal shutdown still drains + persists (idempotent
                # with the SIGTERM path)
                drainer.drain()
            service.close()
            summary = service.summary()
            print(json.dumps(summary), file=sys.stderr)
    return 1 if errors else 0


def cmd_loadgen(args):
    """Drive a sustained multi-tenant arrival mix against a warm serve
    service (fks_tpu.obs.workload.run_loadgen) and print the summary —
    the four compare-gated keys ``loadgen_qps`` / ``loadgen_p99_ms`` /
    ``loadgen_shed_rate`` / ``loadgen_fairness_index`` plus per-tenant
    breakdowns. Accounting is always on: the run dir gets
    ``tenant_stats`` / ``workload_mix`` / ``loadgen_summary`` records
    alongside the serve metrics, so ``report`` / ``watch`` /
    ``export-metrics`` render the tenant view afterwards. Default is a
    hermetic template champion over a synthetic workload; ``--http``
    routes through the concurrent localhost HTTP front instead of the
    in-process client."""
    _apply_platform_flags(args)
    from fks_tpu import obs
    from fks_tpu.obs.workload import (
        http_client, parse_tenant_spec, run_loadgen, service_client,
    )
    from fks_tpu.serve import (
        ChampionSpec, ServeEngine, ServeService, ShapeEnvelope,
        load_champion, make_http_server,
    )
    from fks_tpu.serve.accounting import SLOConfig

    try:
        plan = parse_tenant_spec(args.tenants)
    except ValueError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    with _flight_recorder(args, "loadgen") as rec, obs.watch_compiles(rec):
        if args.champion:
            champion = load_champion(args.champion)
            _, wl = _parse_workload(args)
        else:
            # hermetic default: a template champion over a synthetic
            # workload, so loadgen runs before any evolution has
            # produced a ledger (and repeat runs are bit-identical)
            from fks_tpu.data.synthetic import synthetic_workload
            from fks_tpu.funsearch import template

            champion = ChampionSpec(
                code=template.fill_template("score = 1000"),
                source="<loadgen-default>")
            wl = synthetic_workload(16, 32, seed=args.seed)
        engine = ServeEngine(
            champion, wl,
            envelope=ShapeEnvelope(max_pods=args.max_pods,
                                   max_batch=args.max_batch),
            engine=args.engine, recorder=rec)
        engine.warmup()  # measure serving, not first-call compiles
        slo = (SLOConfig(p99_ms=args.slo_p99_ms) if args.slo_p99_ms
               else None)
        service = ServeService(engine, recorder=rec, slo=slo,
                               max_queue=args.max_queue,
                               accounting=True,
                               workload_every=args.workload_every)
        if rec.enabled:
            rec.annotate_meta(tenants=args.tenants,
                              duration_s=args.duration,
                              front="http" if args.http is not None
                              else "in-process")
        server = None
        try:
            if args.http is not None:
                import threading

                server = make_http_server(service, args.http)
                port = server.server_address[1]
                threading.Thread(target=server.serve_forever,
                                 daemon=True).start()
                send = http_client(port)
                print(f"loadgen -> http://127.0.0.1:{port}/query",
                      file=sys.stderr)
            else:
                send = service_client(service)
            summary = run_loadgen(send, plan, duration_s=args.duration,
                                  seed=args.seed, recorder=rec)
        finally:
            if server is not None:
                server.shutdown()
                server.server_close()
            service.close()
            # record the serve-side view: tenant_stats / workload_mix /
            # slo_burn rows land in the run dir even when the request
            # count never crossed a workload_every window
            service.summary()
    print(json.dumps(summary, indent=2))
    return 0


#: deterministic built-in portfolio: four template logics with distinct
#: placement behaviour, so the portfolio gate runs before any evolution
#: has produced a ledger (and repeat runs are bit-identical)
_PORTFOLIO_LOGICS = (
    # raw-milli scores, NOT the normalized "+fit/total" variants: those
    # collapse into all-tie constant policies under the template's
    # int() truncation, and four behaviorally identical slots could not
    # catch a cross-slot routing bug in the parity selftest
    "score = 1000",
    "score = node.cpu_milli_left - pod.cpu_milli",
    "score = node.memory_mib_left - pod.memory_mib",
    "score = pod.cpu_milli - node.cpu_milli_left",
)


def cmd_portfolio(args):
    """Multi-tenant champion-portfolio serving (fks_tpu.portfolio): N
    resident policies in ONE slot-vmapped VM executable, routed per
    request. ``--selftest N`` runs the per-slot parity sweep (every
    resident slot vs a single-champion VM engine, plus a mixed-slot
    batch) and then promotes one slot mid-traffic under a compile
    watcher. ``--http`` serves the routed front instead."""
    _apply_platform_flags(args)
    from fks_tpu import obs
    from fks_tpu.funsearch import template
    from fks_tpu.portfolio import (
        PortfolioEngine, PortfolioService, Router, portfolio_selftest,
        vm_coverage_split,
    )
    from fks_tpu.serve import ChampionSpec, ShapeEnvelope, load_champion
    from fks_tpu.serve.service import run_http

    with _flight_recorder(args, "portfolio") as rec, obs.watch_compiles(rec):
        mesh = None
        if getattr(args, "devices", 0):
            from fks_tpu.parallel import population_mesh
            mesh = population_mesh(_mesh_devices(args))
        if args.champion:
            champs = [load_champion(p) for p in args.champion]
            _, wl = _parse_workload(args)
        else:
            from fks_tpu.data.synthetic import synthetic_workload
            champs = [ChampionSpec(code=template.fill_template(lg),
                                   score=0.5 + 0.1 * i,
                                   source=f"<builtin-{i}>")
                      for i, lg in enumerate(_PORTFOLIO_LOGICS)]
            wl = synthetic_workload(16, 32, seed=args.seed)
        n_pad = wl.cluster.n_padded
        g_pad = wl.cluster.g_padded
        resident, outside = vm_coverage_split(champs, n_pad, g_pad)
        if not resident:
            print("error: no champion is VM-lowerable at this cluster "
                  "shape — a portfolio needs at least one resident slot",
                  file=sys.stderr)
            return 2
        for c in outside:
            print(f"champion {c.source or '<inline>'} outside the VM "
                  "vocabulary; excluded from the slot table (serve it "
                  "via the Router's AOT fallback)", file=sys.stderr)
        n_slots = args.slots or len(resident) + 1  # +1 spare shadow slot
        engine = PortfolioEngine(
            resident, wl, n_slots=n_slots,
            envelope=ShapeEnvelope(max_pods=args.max_pods,
                                   max_batch=args.max_batch),
            engine=args.engine, mesh=mesh, recorder=rec)
        if rec.enabled:
            rec.annotate_meta(
                engine_kind=engine.engine_kind, n_slots=engine.n_slots,
                program_capacity=engine.program_capacity,
                slots=[c.source for c in engine.slot_champions])
        print(f"portfolio: {len(resident)} resident / {len(outside)} "
              f"fallback champions, {engine.n_slots} slots, "
              f"capacity={engine.program_capacity}", file=sys.stderr)
        engine.warmup()
        if args.selftest:
            return _portfolio_selftest_run(args, engine, resident,
                                           portfolio_selftest, rec)
        pins = {}
        for spec in args.pin:
            tenant, _, slot = spec.partition("=")
            pins[tenant] = int(slot)
        ab = {}
        for spec in args.ab:
            slot, _, weight = spec.partition("=")
            ab[int(slot)] = float(weight)
        router = Router(engine.n_slots, pins=pins, ab_split=ab or None)
        service = PortfolioService(engine, router=router, recorder=rec,
                                   max_wait_s=args.max_wait_ms / 1e3,
                                   max_queue=args.max_queue,
                                   accounting=True)
        try:
            if args.http:
                print(f"listening on http://127.0.0.1:{args.http} "
                      "(POST /query, GET /stats, GET /healthz)",
                      file=sys.stderr)
                run_http(service, args.http)
            else:
                from fks_tpu.serve.service import run_jsonl
                run_jsonl(service)
        finally:
            service.close()
            print(json.dumps(service.summary()), file=sys.stderr)
    return 0


def _portfolio_selftest_run(args, engine, resident, portfolio_selftest,
                            rec):
    """The gate body: per-slot + mixed-batch parity, then one slot
    promoted mid-traffic with zero XLA compiles."""
    import threading

    from fks_tpu import obs
    from fks_tpu.funsearch import template
    from fks_tpu.serve import ChampionSpec

    result = portfolio_selftest(engine, count=args.selftest,
                                pods_per_query=args.pods_per_query,
                                tol=args.audit_tol)
    # mid-traffic slot promotion: hammer every resident slot from
    # threads while one slot's tables are swapped out and back — the
    # zero-compile contract under concurrency, on this exact build
    target = min(1, engine.n_slots - 1)
    promoted = ChampionSpec(
        code=template.fill_template(
            "score = 3000 + (node.cpu_milli_left - pod.cpu_milli) "
            "/ max(1, node.cpu_milli_total)"),
        score=9.9, source="<promoted>")
    base = engine.base_pods
    stop = threading.Event()
    errors = []

    def _hammer(slot):
        i = 0
        while not stop.is_set():
            q = [dict(base[(i + j) % len(base)]) for j in range(3)]
            try:
                ans = engine.answer_batch([q], slots=[slot])[0]
                if ans.get("score") is None:
                    errors.append(f"slot {slot}: empty answer")
            except Exception as e:  # noqa: BLE001 — surfaced in result
                errors.append(f"slot {slot}: {type(e).__name__}: {e}")
                return
            i += 1

    watcher = obs.CompileWatcher().install()
    try:
        threads = [threading.Thread(target=_hammer, args=(s,))
                   for s in range(min(len(resident), engine.n_slots))]
        for t in threads:
            t.start()
        old = engine.swap_slot(target, promoted)
        engine.swap_slot(target, old)
        stop.set()
        for t in threads:
            t.join(timeout=60)
        compiles = watcher.backend_compile_count
    finally:
        stop.set()
        watcher.uninstall()
    result["swap"] = {"slot": target, "swaps": 2, "compiles": compiles,
                      "errors": errors[:5],
                      **{k: engine.last_swap_breakdown[k]
                         for k in ("swap_ms", "h2d_ms", "h2d_bytes")}}
    result["ok"] = bool(result["ok"] and compiles == 0 and not errors)
    if rec.enabled:
        rec.metric("portfolio_selftest", **{
            k: v for k, v in result.items() if k != "failures"})
    print(json.dumps(result, indent=2))
    return 0 if result["ok"] else 1


def cmd_pipeline(args):
    """Promotion-pipeline utilities (fks_tpu.pipeline). Default: print
    the promotion.jsonl state-machine status (per-attempt states, the
    active promotion, interrupted attempts, torn lines). ``--drill``
    runs the deterministic fault-injection drill matrix instead and
    exits nonzero on any failed drill."""
    import os

    _apply_platform_flags(args)
    from fks_tpu import obs
    from fks_tpu.serve.artifact import CHAMPION_DIR

    ledger_dir = args.ledger_dir or CHAMPION_DIR
    log_path = args.log or os.path.join(ledger_dir, "promotion.jsonl")
    if args.drill:
        from fks_tpu.pipeline import run_drills

        with _flight_recorder(args, "pipeline") as rec, \
                obs.watch_compiles(rec):
            results = run_drills(log=lambda m: print(m, file=sys.stderr),
                                 only=args.only)
            ok = all(r["ok"] for r in results)
            if rec.enabled:
                rec.annotate_meta(drills=len(results), drills_ok=ok)
        print(json.dumps({"ok": ok, "drills": results}, indent=2))
        return 0 if ok else 1
    from fks_tpu.pipeline import PromotionLog

    print(json.dumps(PromotionLog(log_path).summary(), indent=2))
    return 0


def cmd_report(args):
    """Render a flight-recorder run directory (written by ``--run-dir``)
    back into a human-readable summary — generations table with a fitness
    sparkline, admit/reject breakdown, compile events, span hotspots — from
    the JSONL files alone (no in-process state)."""
    from fks_tpu.obs.report import render_report

    try:
        print(render_report(args.run_dir))
    except FileNotFoundError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    return 0


def cmd_export_metrics(args):
    """Render a flight-recorder run directory as OpenMetrics text
    exposition (``# TYPE``/``# HELP`` blocks, ``# EOF`` terminator) —
    scrape-able by any Prometheus textfile collector, no client library."""
    from fks_tpu.obs.exporter import to_openmetrics

    try:
        text = to_openmetrics(args.run_dir)
    except (FileNotFoundError, ValueError) as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    if args.out:
        # atomic replace: a scraper must never read a half-written file
        import os
        tmp = args.out + ".tmp"
        with open(tmp, "w") as f:
            f.write(text)
        os.replace(tmp, args.out)
        print(f"wrote {args.out}", file=sys.stderr)
    else:
        sys.stdout.write(text)
    return 0


def cmd_watch(args):
    """Live-tail a run directory: new generation/parity/bench records plus
    a heartbeat liveness verdict (HEALTHY / STALE / DEAD — thresholds at
    2x / 10x the run's own metric cadence) every ``--interval`` seconds.
    Exits 0 when the run finishes ok, 1 on error status or a dead run."""
    from fks_tpu.obs.exporter import watch

    try:
        return watch(args.run_dir, interval=args.interval, once=args.once)
    except (FileNotFoundError, ValueError) as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    except KeyboardInterrupt:
        return 0


def cmd_spans(args):
    """Causal-trace viewer over a run directory's ``trace_span`` events
    (fks_tpu.obs.trace_ctx): list traces, render one request's latency
    waterfall (``--trace``), rank the slowest requests (``--slowest``),
    verify every served request reconstructs a complete waterfall
    (``--check-complete``), or print the
    per-generation critical path with the device-idle vs LLM-idle split
    (``--critical-path``, gated by ``--min-fraction``)."""
    from fks_tpu.obs import trace_ctx
    from fks_tpu.obs.report import load_run

    try:
        _meta, events, metrics = load_run(args.run_dir)
    except FileNotFoundError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    spans = trace_ctx.trace_spans(events)
    by = trace_ctx.traces_by_id(spans)

    if args.trace:
        match = by.get(args.trace)
        if match is None:  # allow unambiguous prefixes (ids are long)
            hits = [t for t in by if t.startswith(args.trace)]
            if len(hits) != 1:
                print(f"error: trace {args.trace!r} "
                      f"{'is ambiguous' if hits else 'not found'} "
                      f"({len(by)} traces in run)", file=sys.stderr)
                return 2
            match = by[hits[0]]
        print(trace_ctx.render_waterfall(match))
        return 0

    def _root(tid):
        roots = [s for s in by[tid] if not s.get("parent_id")]
        return roots[0] if len(roots) == 1 else None

    if args.check_complete:
        # every request the service REPORTED serving must reconstruct a
        # complete causally-linked waterfall — the metric stream is the
        # ground truth for what was served, the event stream must match
        served = [m for m in metrics if m.get("kind") == "serve_request"
                  and m.get("trace_id")]
        bad = [m["trace_id"] for m in served
               if not trace_ctx.waterfall_complete(by.get(m["trace_id"], []))]
        print(f"served requests: {len(served)}  "
              f"complete waterfalls: {len(served) - len(bad)}")
        for tid in bad[:10]:
            print(f"  INCOMPLETE {tid}")
        if not served:
            print("error: no traced serve_request metrics in run",
                  file=sys.stderr)
            return 1
        return 1 if bad else 0

    if args.critical_path:
        gens = sorted(t for t in by if _root(t) is not None
                      and _root(t).get("path") == "generation")
        if not gens:
            print("error: no generation traces in run", file=sys.stderr)
            return 1
        failed = 0
        print(f"{'trace':<22} {'wall s':>8} {'attr %':>7} "
              f"{'dev-idle s':>10} {'llm-idle s':>10}  bounding")
        for tid in gens:
            cp = trace_ctx.critical_path(by[tid])
            if not cp.get("ok"):
                failed += 1
                print(f"{tid:<22} (no root span)")
                continue
            frac = cp["attributed_fraction"]
            if frac < args.min_fraction:
                failed += 1
            print(f"{tid:<22} {cp['wall_seconds']:>8.3f} "
                  f"{frac * 100:>6.1f}% {cp['device_idle_seconds']:>10.3f} "
                  f"{cp['llm_idle_seconds']:>10.3f}  "
                  f"{cp['bounding_stage']}"
                  f"{'  << below min-fraction' if frac < args.min_fraction else ''}")
        return 1 if failed else 0

    order = sorted(
        by, key=lambda t: -max(float(s.get("seconds", 0.0))
                               for s in by[t]))
    if args.slowest:
        shown = [t for t in order
                 if _root(t) is not None
                 and _root(t).get("path") == trace_ctx.SERVE_ROOT]
        for tid in shown[: args.slowest]:
            print(trace_ctx.render_waterfall(by[tid]))
            print()
        if not shown:
            print("error: no serve/request traces in run", file=sys.stderr)
            return 1
        return 0

    print(f"{len(by)} traces, {len(spans)} spans")
    for tid in order[:30]:
        root = _root(tid)
        path = root.get("path", "?") if root else "(torn)"
        wall = max(float(s.get("seconds", 0.0)) for s in by[tid])
        print(f"  {tid:<24} {path:<16} {wall * 1e3:>10.3f} ms  "
              f"{len(by[tid])} spans")
    if len(by) > 30:
        print(f"  ... {len(by) - 30} more (use --trace/--slowest)")
    return 0


def cmd_compare(args):
    """Cross-run regression gate: diff two run dirs (or bench JSONL files)
    on the shared metric vocabulary — throughput, compile seconds, fitness
    best/median, parity drift, watchdog violation counts — and exit 1 when
    the candidate regresses past a threshold (fks_tpu.obs.compare).
    ``--baseline auto`` (the literal word as BASELINE) resolves the best
    healthy historical run under ``--history-root`` instead of a
    hand-picked path (fks_tpu.obs.history)."""
    from fks_tpu.obs.compare import (
        compare_runs, format_comparison, has_regression,
        parse_threshold_overrides,
    )

    baseline = args.baseline
    if baseline == "auto":
        from fks_tpu.obs.history import resolve_auto_baseline

        root = args.history_root or _default_history_root()
        baseline = resolve_auto_baseline(root)
        if baseline is None:
            print(f"error: no healthy historical run under {root} to "
                  "auto-select as baseline", file=sys.stderr)
            return 2
        print(f"auto baseline: {baseline}", file=sys.stderr)
    try:
        thresholds = (parse_threshold_overrides(args.threshold)
                      if args.threshold else None)
        rows = compare_runs(baseline, args.candidate,
                            thresholds=thresholds)
    except (FileNotFoundError, ValueError) as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    print(format_comparison(rows, baseline, args.candidate))
    return 1 if has_regression(rows) else 0


def _default_history_root() -> str:
    """benchmarks/results under the repo root."""
    import os

    return os.environ.get("FKS_BENCH_RESULTS_DIR") or os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmarks", "results")


def cmd_trends(args):
    """Cross-run trend report (fks_tpu.obs.history): index every
    flight-recorder run dir and bench evidence file under ROOT, render
    per-metric timelines as sparklines, and flag regressions with the
    robust z-score pass. Exit code contract: 0 = rendered (alerts print
    but don't fail), 1 with ``--fail-on-alert`` when any metric alerted,
    2 = bad/empty root — scriptable like ``compare``."""
    from fks_tpu.obs.history import RunHistory
    from fks_tpu.obs.report import sparkline

    try:
        hist = RunHistory(args.root)
        hist.scan()
    except FileNotFoundError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    if not hist.entries:
        print(f"error: no runs indexed under {args.root}", file=sys.stderr)
        return 2
    if args.write_index:
        path = hist.write_index()
        print(f"indexed {len(hist.entries)} entries -> {path}",
              file=sys.stderr)
    metrics = ([m.strip() for m in args.metric.split(",") if m.strip()]
               if args.metric else None)
    reports = hist.trends(metrics=metrics, window=args.window, z=args.z)
    print(f"trend report: {len(hist.entries)} indexed entries "
          f"under {args.root}")
    total_alerts = 0
    with _flight_recorder(args, "trends") as rec:
        for rep in reports:
            rec.metric("trend_report",
                       {k: rep[k] for k in ("metric", "runs", "alerts",
                                            "higher_is_better", "window",
                                            "z", "values", "labels")})
            arrow = ("higher=better" if rep["higher_is_better"]
                     else "lower=better")
            print(f"\n{rep['metric']}  ({rep['runs']} runs, {arrow})")
            print(f"  {sparkline(rep['values'])}  latest "
                  f"{rep['values'][-1]:g}")
            for a in rep["alerts"]:
                total_alerts += 1
                print(f"  ALERT {a['direction']} at {a['run']}: "
                      f"{a['value']:g} vs prior median {a['median']:g} "
                      f"(robust z {a['z']:+.1f})")
    if not reports:
        print("\nno watched metrics present in the indexed entries")
    print(f"\n{total_alerts} trend alert(s)")
    if total_alerts and args.fail_on_alert:
        return 1
    return 0


def cmd_trace_diff(args):
    """Replay one policy through two engines with the decision trace on and
    report the first divergent scheduling step (fks_tpu.funsearch.tracing).
    Exit code contract: 0 = no divergence, 1 = divergence found, 2 = error
    — scriptable like ``compare``."""
    _apply_platform_flags(args)
    from fks_tpu.funsearch import tracing
    from fks_tpu.sim.engine import SimConfig

    engines = [e.strip() for e in args.engines.split(",") if e.strip()]
    if len(engines) != 2:
        print(f"--engines needs exactly two comma-separated names, got "
              f"{engines}", file=sys.stderr)
        return 2
    bad = [e for e in engines if e not in ("exact", "flat")]
    if bad:
        print(f"unsupported trace engine(s) {bad}: the fused kernel does "
              "not carry the decision trace; use 'exact' and/or 'flat'",
              file=sys.stderr)
        return 2
    _, wl = _parse_workload(args)
    label = args.code or args.policy
    if args.scenario is not None:
        # replay on one suite scenario (fault-injected variants included:
        # both trace engines carry NODE_DOWN/NODE_UP rows) instead of the
        # base workload
        from fks_tpu.scenarios import get_suite

        try:
            suite = get_suite(args.suite, wl)
        except ValueError as e:
            print(f"error: {e}", file=sys.stderr)
            return 2
        if not 0 <= args.scenario < len(suite):
            print(f"error: --scenario {args.scenario} out of range for "
                  f"suite {suite.name!r} ({len(suite)} scenarios)",
                  file=sys.stderr)
            return 2
        wl = suite.workloads[args.scenario]
        label = (f"{label}@{suite.name}"
                 f"[{args.scenario}:{suite.names[args.scenario]}]")
    code = ""
    if args.code:
        try:
            with open(args.code) as f:
                code = f.read()
        except OSError as e:
            print(f"error: {e}", file=sys.stderr)
            return 2
    try:
        param_policy, params = tracing.policy_params(
            wl, policy_name=args.policy, code=code)
    except Exception as e:  # noqa: BLE001 — bad policy/code is a usage error
        print(f"error: {type(e).__name__}: {e}", file=sys.stderr)
        return 2
    cfg_kw = {"cond_policy": True}
    if args.max_steps:
        cfg_kw["max_steps"] = args.max_steps
    # duplicate engine names (exact-vs-exact self-consistency) get #i tags
    # so the record's per-engine keys stay distinct
    names = [f"{e}#{i}" if engines.count(e) > 1 else e
             for i, e in enumerate(engines)]
    specs = [(name, eng, param_policy, params)
             for name, eng in zip(names, engines)]
    with _flight_recorder(args, "trace-diff") as rec:
        record = tracing.trace_diff(
            wl, specs, cfg=SimConfig(**cfg_kw), score_tol=args.tol,
            recorder=rec, label=label)
    print(tracing.format_diff(record))
    return 1 if record["divergent"] else 0


def cmd_lint(args):
    """Repo-wide JAX-invariant lint + jaxpr-pin gate (fks_tpu.analysis.
    lint): AST checks for trace-safety violations over the given paths,
    then the pinned-jaxpr manifest check (key entry points lowered with
    each Python-static SimConfig flag and hashed). Exit code contract:
    0 = clean, 1 = findings or pin drift, 2 = error — scriptable like
    ``compare``.
    ``--write-pins`` re-lowers and rewrites the manifest instead of
    checking it (exit 0)."""
    _apply_platform_flags(args)
    from fks_tpu.analysis import lint

    paths = args.paths or ["fks_tpu"]
    pins_path = args.pins or lint.PIN_MANIFEST
    findings = lint.lint_paths(paths)
    for f in findings:
        print(f)
    pin_msgs = []
    try:
        if args.write_pins:
            man = lint.write_pins(pins_path)
            print(f"wrote {len(man['pins'])} jaxpr pins -> {pins_path}")
        elif not args.no_pins:
            pin_msgs = lint.check_pins(pins_path)
            for m in pin_msgs:
                print(m)
    except Exception as e:  # noqa: BLE001 — broken lowering is an error,
        print(f"error: {type(e).__name__}: {e}", file=sys.stderr)  # not drift
        return 2
    ok = not findings and not pin_msgs
    with _flight_recorder(args, "lint") as rec:
        rec.metric("lint_report", {
            "paths": list(paths),
            "findings": [f.to_json() for f in findings],
            "pin_drift": list(pin_msgs),
            "ok": ok,
        })
    print(f"lint: {len(findings)} finding(s), {len(pin_msgs)} pin "
          f"message(s) -> {'clean' if ok else 'FAIL'}")
    return 0 if ok else 1


def cmd_scenarios(args):
    """Scenario-suite discovery and inspection (fks_tpu.scenarios): with no
    flags, list the registered suites; with ``--suite`` materialize one
    against the workload and print its summary (per-scenario parameters +
    fault-event counts); with ``--scenario I`` zoom into one scenario,
    including its concrete NODE_DOWN/NODE_UP timeline. ``--run-dir``
    additionally lands the suite summary in the flight-recorder trail as a
    ``scenario_suite`` metric, tying an evolve run's robust scores to the
    exact scenario family they were measured on."""
    from fks_tpu.scenarios import list_suites

    if not args.suite:
        print(json.dumps(list_suites(), indent=2))
        return 0
    _apply_platform_flags(args)
    import numpy as np

    from fks_tpu.ops.heap import KIND_NODE_DOWN
    from fks_tpu.scenarios import get_suite

    _, wl = _parse_workload(args)
    with _flight_recorder(args, "scenarios") as rec:
        try:
            suite = get_suite(args.suite, wl)
        except ValueError as e:
            print(f"error: {e}", file=sys.stderr)
            return 2
        desc = suite.describe()
        rec.metric("scenario_suite", desc)
        if args.scenario is None:
            print(json.dumps(desc, indent=2))
            return 0
        if not 0 <= args.scenario < len(suite):
            print(f"error: --scenario {args.scenario} out of range for "
                  f"suite {suite.name!r} ({len(suite)} scenarios)",
                  file=sys.stderr)
            return 2
        fe = suite.workloads[args.scenario].faults
        m = np.asarray(fe.mask)
        row = dict(desc["scenarios"][args.scenario], fault_timeline=[
            {"time": int(t), "node": int(nd),
             "kind": ("NODE_DOWN" if int(k) == KIND_NODE_DOWN
                      else "NODE_UP")}
            for t, nd, k in zip(np.asarray(fe.time)[m],
                                np.asarray(fe.node)[m],
                                np.asarray(fe.kind)[m])])
    print(json.dumps(row, indent=2))
    return 0


def cmd_traces(args):
    """Dataset discovery (reference: parser.py:103-115)."""
    from fks_tpu.data import TraceParser

    parser = TraceParser()
    print("node files:")
    for f in parser.get_available_node_files():
        print(f"  {f}")
    print("pod files:")
    for f in parser.get_available_pod_files():
        print(f"  {f}")
    return 0


#: the committed snapshots: file -> (node list, pod list, placing policy
#: of the zoo, events, node_prefilter_k, what the parse does with
#: gpu_spec), each the flat engine's float32 run of the policy for that
#: many events from the empty cluster
COMMITTED_SNAPSHOTS = {
    # the loaded cluster: the first 5,888 arrivals of the inflated list
    # (70 % of the cluster's GPUs) under the large-cluster rule; nobody
    # has left, nothing was refused
    "openb_snapshot_inflated080_e5888.csv.gz": (
        "openb_node_list_all_node.csv", "openb_pod_list_inflated080.csv",
        "best_fit", 5888, 64, "ignore"),
    # a real trace mid-run: upstream's 16 nodes after 12,288 events of
    # cpu250 (5,618 departures, 1,002 refused placements, a pod waiting)
    "openb_snapshot_cpu250_firstfit_e12288.csv.gz": (
        "gpu_models_filtered.csv", "openb_pod_list_cpu250.csv",
        "first_fit", 12288, 0, "ignore"),
    # the production cluster under GPU-type constraints: the first 4,864
    # events of the inflated gpuspec25 list (58 % of the cluster's GPUs;
    # a quarter of the GPU pods name the models they accept) with the
    # constraints honoured
    "openb_snapshot_gpuspec25_inflated080_e4864.csv.gz": (
        "openb_node_list_all_node.csv",
        "openb_pod_list_gpuspec25_inflated080.csv", "best_fit", 4864, 64,
        "honor"),
    # the same cluster and list as what-if serving takes it: the first
    # 5,888 arrivals (70 % of the GPUs, the loaded cluster's fork) as
    # first_fit places them with the constraints honoured, every one
    # placed (best_fit refuses a pod at event 3,569; when the file was
    # made, PR 49, the exact engine forked from placed CREATEs only:
    # since PR 52 it forks from any valid prefix, and the file stays as
    # pinned); first_fit's score has no arithmetic in it, so the file
    # hangs on no precision
    "openb_snapshot_gpuspec25_inflated080_firstfit_e5888.csv.gz": (
        "openb_node_list_all_node.csv",
        "openb_pod_list_gpuspec25_inflated080.csv", "first_fit", 5888, 64,
        "honor"),
}


def write_snapshot(path=None,
                   name="openb_snapshot_inflated080_e5888.csv.gz"):
    """Rewrite one of ``COMMITTED_SNAPSHOTS``. ``path`` defaults to the
    committed file beside the traces. Returns (path, snapshot)."""
    from pathlib import Path

    from fks_tpu.data import TraceParser
    from fks_tpu.data.snapshot import write_snapshot_csv_gz
    from fks_tpu.models import zoo
    from fks_tpu.sim import flat
    from fks_tpu.sim.engine import SimConfig

    nodes, pods, policy, e0, k, gpu_spec = COMMITTED_SNAPSHOTS[name]
    parser = TraceParser()
    wl = parser.parse_workload(node_file=nodes, pod_file=pods,
                               gpu_spec=gpu_spec)
    snap = flat.make_snapshot(wl, zoo.ZOO[policy](), e0,
                              SimConfig(node_prefilter_k=k))
    if path is None:
        path = Path(parser.csv_dir) / name
    write_snapshot_csv_gz(wl, snap, path)
    return path, snap


def cmd_snapshot(args):
    """Rewrite the committed ``--snapshot`` files byte for byte, as
    ``python -m fks_tpu.data.inflate`` rewrites its pod list."""
    import numpy as np

    for name in COMMITTED_SNAPSHOTS:
        path, snap = write_snapshot(name=name)
        node = np.asarray(snap.node)
        print(f"{path}: {snap.e0} events, {len(node)} CREATE attempts, "
              f"{int((node < 0).sum())} of them refused, on "
              f"{len(np.unique(node[node >= 0]))} nodes")
    return 0


def build_parser() -> argparse.ArgumentParser:
    """The whole command line: one sub-parser per command, each bound to
    its ``cmd_*`` through ``fn``. Builds only; nothing runs."""
    ap = argparse.ArgumentParser(prog="fks_tpu", description=__doc__)
    sub = ap.add_subparsers(dest="cmd", required=True)
    common = argparse.ArgumentParser(add_help=False)
    common.add_argument("--cpu", action="store_true",
                        help="force the CPU backend")
    common.add_argument("--metrics", default="",
                        help="append JSONL metric records to this file")
    common.add_argument("--run-dir", default="",
                        help="flight-recorder run directory (meta.json, "
                             "events.jsonl, metrics.jsonl, heartbeat); "
                             "render afterwards with 'fks_tpu report DIR'")
    common.add_argument("--engine", choices=("exact", "flat", "fused"),
                        default="exact",
                        help="simulation engine: 'exact' replicates the "
                             "reference bit-for-bit; 'flat' is the TPU "
                             "throughput engine (documented retry-rule "
                             "divergence, fks_tpu.sim.flat); 'fused' is the "
                             "Pallas whole-loop-in-VMEM kernel (parametric "
                             "populations — 'scale' command only)")

    b = sub.add_parser("bench", help="policy comparison table", parents=[common])
    _add_trace_flags(b, snapshot=True)
    b.add_argument("--policies", default="",
                   help="comma-separated zoo policy names (default: all)")
    b.add_argument("--f64", action="store_true",
                   help="float64 evaluator arithmetic (exact reference parity)")
    b.add_argument("--validate", action="store_true",
                   help="enable the per-event invariant audit")
    b.set_defaults(fn=cmd_bench)

    s = sub.add_parser("simulate", help="one policy, detailed JSON result", parents=[common])
    _add_trace_flags(s, snapshot=True)
    s.add_argument("--policy", default="best_fit")
    s.add_argument("--f64", action="store_true")
    s.add_argument("--validate", action="store_true")
    s.set_defaults(fn=cmd_simulate)

    e = sub.add_parser("evolve", help="run FunSearch evolution", parents=[common])
    _add_trace_flags(e, snapshot=True)
    e.add_argument("--config", default="", help="reference-format llm_config.json")
    e.add_argument("--fake-llm", action="store_true",
                   help="deterministic offline codegen backend")
    e.add_argument("--checkpoint", default="", help="evolution checkpoint path")
    e.add_argument("--wal", default="",
                   help="generation write-ahead log path "
                        "(fks_tpu.resilience.wal): drafted candidates and "
                        "eval outcomes are fsync'd mid-generation and the "
                        "loop checkpoints every generation — a kill "
                        "mid-generation resumes without re-spending LLM "
                        "calls or device evals (pair with --checkpoint)")
    e.add_argument("--out", default="", help="directory for champion JSONs")
    e.add_argument("--generations", type=int, default=None)
    e.add_argument("--parametric-rounds", type=int, default=None,
                   help="device-resident weight-evolution generations to "
                        "interleave per LLM generation (hybrid mode; the "
                        "champion is rendered to source and competes in "
                        "the code population)")
    e.add_argument("--watchdog", action="store_true",
                   help="enable the in-graph numerics watchdog "
                        "(SimConfig.watchdog): NaN/Inf policy scores are "
                        "masked to 0 and flagged in "
                        "SimResult.numeric_flags; violations land as "
                        "'watchdog' events in the run dir")
    e.add_argument("--parity-sample", type=int, default=None,
                   help="per generation, re-score this many sampled "
                        "population members through the exact reference "
                        "evaluator (JIT tier) and alert on fitness drift "
                        "(0 = off; exit 3 when any generation alerts)")
    e.add_argument("--parity-tol", type=float, default=None,
                   help="parity drift tolerance (default 1e-5; raise "
                        "above the measured divergence bound for "
                        "--engine flat)")
    e.add_argument("--suite", default=None,
                   help="score candidates by composite ROBUST fitness over "
                        "this scenario suite (fks_tpu.scenarios; try "
                        "'default8') instead of single-trace fitness — "
                        "one vmapped evaluation covers every scenario, "
                        "fault-injected variants included")
    e.add_argument("--robust-agg", choices=("mean", "min", "cvar"),
                   default=None,
                   help="how per-scenario scores fold into the robust "
                        "score (default mean; cvar = mean of the worst "
                        "quarter)")
    e.add_argument("--budget", choices=("none", "halving"), default=None,
                   help="eval-budget allocation over the suite "
                        "(fks_tpu.funsearch.budget): 'halving' probes the "
                        "whole generation cheaply, then only the top "
                        "1/eta advance to the full suite (requires "
                        "--suite; champion parity is sentinel-audited)")
    e.add_argument("--budget-eta", type=int, default=None,
                   help="survivor fraction denominator for --budget "
                        "halving (default 2: keep the top half)")
    e.add_argument("--probe-suite", default=None,
                   help="probe-rung suite name (default smoke3)")
    e.add_argument("--probe-steps", type=int, default=None,
                   help="probe-rung event budget (truncated trace "
                        "prefix; 0 = full trace on the probe suite)")
    e.add_argument("--profile", action="store_true",
                   help="attribute wall time per pipeline stage (codegen/"
                        "preflight/transpile/device-eval/rank/ledger) with "
                        "compile-vs-compute split and lane occupancy — "
                        "device_profile records in the run dir, rendered "
                        "by 'report'. Off compiles identical programs "
                        "(jaxpr-pinned)")
    e.set_defaults(fn=cmd_evolve)

    sc = sub.add_parser("scale", help="synthetic scale run + throughput",
                        parents=[common])
    sc.add_argument("--nodes-count", "--nodes", dest="nodes_count",
                    type=int, default=1000)
    sc.add_argument("--pods-count", "--pods", dest="pods_count",
                    type=int, default=100000)
    sc.add_argument("--pop", type=int, default=8)
    sc.add_argument("--seed", type=int, default=0)
    sc.add_argument("--prefilter-k", type=int, default=None,
                    help="SimConfig.node_prefilter_k: score only the "
                         "top-k statically-feasible nodes per event "
                         "(0 = dense scan, bit-identical to the default "
                         "program). Default: auto — a cheap policy-cost "
                         "probe enables the prefilter for expensive "
                         "policies on big node parks and leaves cheap "
                         "parametric scoring dense "
                         "(fks_tpu.sim.engine.resolve_auto_prefilter)")
    sc.add_argument("--state-pack", action="store_true",
                    help="SimConfig.state_pack: narrow flat-engine carry "
                         "columns to 16-bit where the value range "
                         "provably fits (exact integer packing)")
    sc.add_argument("--openb-nodes", action="store_true",
                    help="draw the node park from the vendored OpenB "
                         "node list (benchmarks/traces/node_yaml/, 1213 "
                         "nodes; --nodes-count selects a prefix) instead "
                         "of the synthetic archetype sampler")
    sc.add_argument("--code-pop", type=int, default=0,
                    help="also measure the VM code-candidate tier with N "
                         "FakeLLM-lowered register programs (0 = off); "
                         "sharded over the mesh when >1 device is visible")
    sc.add_argument("--devices", type=int, default=0,
                    help="devices to mesh over: N virtual CPU devices "
                         "with --cpu, the first N real devices otherwise "
                         "(fails when fewer are visible; 0 = every "
                         "visible device)")
    sc.set_defaults(fn=cmd_scale)

    sv = sub.add_parser("serve",
                        help="serve a pinned champion as a warm what-if "
                             "query engine (JSONL/HTTP)", parents=[common])
    _add_trace_flags(sv)
    sv.add_argument("--champion", default="",
                    help="champion JSON from the evolution ledger "
                         "(default: best under policies/discovered/)")
    sv.add_argument("--artifact", default="",
                    help="load a saved serve artifact directory instead of "
                         "building from --champion/--trace")
    sv.add_argument("--serve-engine", choices=("aot", "vm"), default="aot",
                    help="champion binding: 'aot' bakes the policy into "
                         "per-champion closure executables (the exact "
                         "reference); 'vm' serves the champion as data — "
                         "register-program tables passed to champion-"
                         "agnostic executables, so a promotion hot-swap "
                         "is a table upload with zero XLA compiles "
                         "(VM-unlowerable champions fall back to aot)")
    sv.add_argument("--save-artifact", default="",
                    help="persist the built engine (artifact.json) to "
                         "this directory; compiled programs stay in the "
                         "process's persistent compile cache")
    sv.add_argument("--max-pods", type=int, default=1024,
                    help="shape envelope: largest query (pods per what-if)")
    sv.add_argument("--max-batch", type=int, default=8,
                    help="shape envelope: largest coalesced request batch")
    sv.add_argument("--max-wait-ms", type=float, default=5.0,
                    help="flush policy: max ms the oldest pending request "
                         "waits for batch-mates (default 5)")
    sv.add_argument("--request-deadline-s", type=float, default=60.0,
                    help="per-request deadline budget in seconds (default "
                         "60, the old hardcoded HTTP timeout); a request's "
                         "own deadline_ms field wins; shed/expired "
                         "requests answer a structured 503 with "
                         "Retry-After instead of hanging (0 = no "
                         "deadline)")
    sv.add_argument("--max-queue", type=int, default=0,
                    help="bounded request queue: admission control sheds "
                         "submits beyond this depth with a typed 503 "
                         "(0 = unbounded, the historical behaviour)")
    sv.add_argument("--degraded-fallback", action="store_true",
                    help="arm degraded-mode serving: on a classified "
                         "device fault, atomically flip to a reduced-"
                         "batch exact-CPU fallback engine (same champion "
                         "and ladder) and rebuild the primary off the "
                         "request path; recovery is probation-gated")
    sv.add_argument("--drain-state", default="",
                    help="on SIGTERM, drain the batcher and persist the "
                         "replay buffer + summary to this path (loaded "
                         "back on the next start to refill shadow-eval "
                         "replay traffic)")
    sv.add_argument("--prefilter-k", type=int, default=None,
                    help="SimConfig.node_prefilter_k override (default: "
                         "the cluster-shape rule for a VM champion, the "
                         "policy-cost probe for an AOT one)")
    sv.add_argument("--state-pack", action="store_true",
                    help="SimConfig.state_pack for the serving engine; "
                         "also engages the 16-bit packed query-upload "
                         "path (bit-identical answers, ~half the "
                         "H2D bytes per request table)")
    sv.add_argument("--devices", type=int, default=0,
                    help="mesh-sharded serving: shard the coalesced "
                         "batch axis over N devices (virtual CPU devices "
                         "with --cpu, the first N real devices otherwise; "
                         "fails when fewer are visible) — one AOT "
                         "executable per (lane, pod) bucket spans every "
                         "device (0 = single-device engine)")
    sv.add_argument("--warmup", action="store_true",
                    help="pre-compile every (lane, pod) shape bucket "
                         "before answering")
    sv.add_argument("--queries", default="",
                    help="answer request JSONL from this file ('-' or "
                         "empty = stdin), one answer line per request")
    sv.add_argument("--http", type=int, default=0,
                    help="serve a localhost HTTP listener on this port "
                         "instead of JSONL")
    sv.add_argument("--selftest", type=int, default=0,
                    help="run the batched-vs-unbatched exact-parity sweep "
                         "with N queries and exit (nonzero on drift)")
    sv.add_argument("--pods-per-query", type=int, default=4,
                    help="query size for --selftest (default 4)")
    sv.add_argument("--audit-every", type=int, default=0,
                    help="ParitySentinel-audit every Nth served answer "
                         "against the unbatched exact engine (0 = off)")
    sv.add_argument("--audit-tol", type=float, default=1e-5,
                    help="audit/selftest score drift tolerance")
    sv.add_argument("--slo-p99-ms", type=float, default=0.0,
                    help="SLO: target p99 latency in ms (0 = unset); "
                         "burn-rate records land as slo_burn metrics — "
                         "'watch' alerts live, export-metrics publishes "
                         "fks_slo_* gauges")
    sv.add_argument("--slo-qps", type=float, default=0.0,
                    help="SLO: target sustained queries/sec (0 = unset)")
    sv.add_argument("--slo-error-budget", type=float, default=0.01,
                    help="fraction of requests allowed over the p99 "
                         "target (default 0.01; burn_rate = observed "
                         "over-fraction / this budget)")
    sv.add_argument("--follow-ledger", action="store_true",
                    help="run the promotion controller alongside serving: "
                         "tail the champion ledger, shadow-gate each new "
                         "champion, hot-swap on promotion, auto-rollback "
                         "on SLO burn (fks_tpu.pipeline)")
    sv.add_argument("--ledger-dir", default="",
                    help="champion ledger directory to follow (default: "
                         "policies/discovered/)")
    sv.add_argument("--promotion-log", default="",
                    help="promotion.jsonl path (default: "
                         "<ledger-dir>/promotion.jsonl)")
    sv.add_argument("--promote-interval", type=float, default=5.0,
                    help="seconds between ledger polls (default 5)")
    sv.add_argument("--accounting", action="store_true",
                    help="per-tenant accounting + query fingerprinting "
                         "(fks_tpu.serve.accounting): tenant_stats / "
                         "workload_mix records in the run dir, "
                         "fks_tenant_* gauges from export-metrics, a "
                         "tenant table in 'report' (off by default — the "
                         "disabled path costs nothing per request)")
    sv.set_defaults(fn=cmd_serve)

    lg = sub.add_parser(
        "loadgen",
        help="drive a sustained multi-tenant arrival mix against a warm "
             "serve service and print the gated loadgen summary",
        parents=[common])
    _add_trace_flags(lg)
    lg.add_argument("--tenants", default="a:closed:2,b:closed:2,c:open:25",
                    help="arrival plan, comma-separated "
                         "name:mode:amount[:pods] — 'closed' amount = "
                         "worker count (submit-wait-repeat), 'open' "
                         "amount = Poisson qps (arrivals never wait on "
                         "responses); pods = pods per query (default 2)")
    lg.add_argument("--duration", type=float, default=5.0,
                    help="seconds to sustain the arrival plan (default 5)")
    lg.add_argument("--seed", type=int, default=0,
                    help="loadgen RNG seed (open-loop arrival gaps; also "
                         "the synthetic-workload seed)")
    lg.add_argument("--champion", default="",
                    help="champion JSON to serve (default: a hermetic "
                         "built-in template champion over a synthetic "
                         "workload)")
    lg.add_argument("--http", type=int, nargs="?", const=0, default=None,
                    help="route through the concurrent localhost HTTP "
                         "front on this port (bare --http = ephemeral "
                         "port) instead of the in-process client")
    lg.add_argument("--max-pods", type=int, default=64,
                    help="shape envelope: largest query (default 64)")
    lg.add_argument("--max-batch", type=int, default=4,
                    help="shape envelope: largest coalesced batch "
                         "(default 4)")
    lg.add_argument("--max-queue", type=int, default=0,
                    help="bounded queue depth for admission-control "
                         "shedding (0 = unbounded)")
    lg.add_argument("--slo-p99-ms", type=float, default=50.0,
                    help="per-tenant SLO p99 target feeding burn rates "
                         "(default 50; 0 = unset)")
    lg.add_argument("--workload-every", type=int, default=100,
                    help="emit tenant_stats/workload_mix every N served "
                         "requests (default 100)")
    lg.set_defaults(fn=cmd_loadgen)

    pf = sub.add_parser(
        "portfolio",
        help="serve N resident champions from ONE slot-vmapped VM "
             "executable with per-request routing (pin / affinity / "
             "A-B / coverage fallback)",
        parents=[common])
    _add_trace_flags(pf)
    pf.add_argument("--champion", action="append", default=[],
                    help="champion JSON to load into a slot (repeatable; "
                         "default: four deterministic built-in template "
                         "champions over a synthetic workload)")
    pf.add_argument("--slots", type=int, default=0,
                    help="slot-table size (default: resident champions "
                         "+ 1 spare shadow slot)")
    pf.add_argument("--seed", type=int, default=0,
                    help="synthetic-workload seed for the built-in "
                         "champion set (default 0)")
    pf.add_argument("--devices", type=int, default=0,
                    help="mesh-sharded serving: shard the lane axis "
                         "over N devices (virtual CPU devices with --cpu, "
                         "the first N real devices otherwise); the slot "
                         "table is replicated (0 = single-device engine)")
    pf.add_argument("--max-pods", type=int, default=64,
                    help="shape envelope: largest query (default 64)")
    pf.add_argument("--max-batch", type=int, default=4,
                    help="shape envelope: largest coalesced batch "
                         "(default 4)")
    pf.add_argument("--max-wait-ms", type=float, default=5.0,
                    help="flush policy: max ms the oldest pending "
                         "request waits for batch-mates (default 5)")
    pf.add_argument("--max-queue", type=int, default=0,
                    help="bounded queue depth for admission-control "
                         "shedding (0 = unbounded)")
    pf.add_argument("--pin", action="append", default=[],
                    help="tenant pin rule tenant=slot (repeatable)")
    pf.add_argument("--ab", action="append", default=[],
                    help="A/B split rule slot=weight (repeatable; "
                         "weights normalized; assignment keyed by a "
                         "deterministic request-id hash)")
    pf.add_argument("--http", type=int, default=0,
                    help="serve a localhost HTTP listener on this port "
                         "instead of JSONL over stdin")
    pf.add_argument("--selftest", type=int, default=0,
                    help="run the per-slot + mixed-batch parity sweep "
                         "with N queries per slot, then promote one "
                         "slot mid-traffic under a compile watcher, "
                         "and exit (nonzero on drift or any compile)")
    pf.add_argument("--pods-per-query", type=int, default=3,
                    help="query size for --selftest (default 3)")
    pf.add_argument("--audit-tol", type=float, default=1e-5,
                    help="selftest score drift tolerance")
    pf.set_defaults(fn=cmd_portfolio)

    pp = sub.add_parser(
        "pipeline", parents=[common],
        help="promotion-pipeline status / fault-injection drills")
    pp.add_argument("--ledger-dir", default="",
                    help="champion ledger directory (default: "
                         "policies/discovered/)")
    pp.add_argument("--log", default="",
                    help="promotion.jsonl path (default: "
                         "<ledger-dir>/promotion.jsonl)")
    pp.add_argument("--drill", action="store_true",
                    help="run the deterministic fault-injection drill "
                         "matrix (corrupt champion, device-eval error, "
                         "p99 regression, kill -9 at every state, "
                         "rollback-on-burn, zero-recompile swap, LLM "
                         "outage, plus the resilience matrix: deadline "
                         "storm, queue overload, device loss mid-batch, "
                         "degrade-then-recover, SIGTERM drain, WAL "
                         "resume) and exit nonzero on any failure")
    pp.add_argument("--only", default="",
                    help="comma-separated drill-name substrings: run only "
                         "the matching drills (e.g. "
                         "--only deadline_storm,wal_resume)")
    pp.set_defaults(fn=cmd_pipeline)

    r = sub.add_parser("report",
                       help="summarize a flight-recorder run directory")
    r.add_argument("run_dir", help="directory written by --run-dir")
    r.set_defaults(fn=cmd_report)

    x = sub.add_parser("export-metrics",
                       help="render a run directory as OpenMetrics text")
    x.add_argument("run_dir", help="directory written by --run-dir")
    x.add_argument("--out", default="",
                   help="write to this file (atomic replace) instead of "
                        "stdout — point a node_exporter textfile "
                        "collector at it")
    x.set_defaults(fn=cmd_export_metrics)

    w = sub.add_parser("watch",
                       help="live-tail a run directory with a heartbeat "
                            "liveness verdict")
    w.add_argument("run_dir", help="directory written by --run-dir")
    w.add_argument("--interval", type=float, default=5.0,
                   help="seconds between polls (default 5)")
    w.add_argument("--once", action="store_true",
                   help="print one snapshot + verdict and exit")
    w.set_defaults(fn=cmd_watch)

    sp = sub.add_parser("spans",
                        help="causal-trace viewer: per-request latency "
                             "waterfalls and evolve critical paths")
    sp.add_argument("run_dir", help="directory written by --run-dir")
    sp.add_argument("--trace", metavar="ID",
                    help="render the waterfall of one trace "
                         "(unambiguous id prefix accepted)")
    sp.add_argument("--slowest", type=int, metavar="N",
                    help="render the N slowest serve/request waterfalls")
    sp.add_argument("--check-complete", action="store_true",
                    help="exit 1 unless every traced serve_request "
                         "reconstructs a complete waterfall")
    sp.add_argument("--critical-path", action="store_true",
                    help="per-generation critical path with device-idle "
                         "vs LLM-idle seconds")
    sp.add_argument("--min-fraction", type=float, default=0.95,
                    help="with --critical-path: fail if any generation "
                         "attributes less than this fraction of its "
                         "wall (default 0.95)")
    sp.set_defaults(fn=cmd_spans)

    c = sub.add_parser("compare",
                       help="regression-gate a candidate run against a "
                            "baseline (exit 1 on regression)")
    c.add_argument("baseline", help="run dir or bench JSONL file")
    c.add_argument("candidate", help="run dir or bench JSONL file")
    c.add_argument("--threshold", default="",
                   help="comma-separated overrides, e.g. "
                        "'evals_per_sec=rel:0.2,best_score=abs:1e-4'")
    c.add_argument("--history-root", default="",
                   help="with BASELINE 'auto': the history root to select "
                        "the best healthy run from (default: "
                        "benchmarks/results, or $FKS_BENCH_RESULTS_DIR)")
    c.set_defaults(fn=cmd_compare)

    tr = sub.add_parser(
        "trends",
        help="cross-run trend report over a directory of run dirs / bench "
             "evidence (exit 1 with --fail-on-alert on regressions)")
    tr.add_argument("root",
                    help="directory holding flight-recorder run dirs "
                         "and/or bench JSONL evidence files (e.g. "
                         "benchmarks/results)")
    tr.add_argument("--metric", default="",
                    help="comma-separated metrics to watch (default: the "
                         "built-in TREND_METRICS vocabulary)")
    tr.add_argument("--window", type=int, default=5,
                    help="prior-run window the robust median/MAD is "
                         "computed over (default 5)")
    tr.add_argument("--z", type=float, default=3.5,
                    help="robust z-score threshold (MAD units, default "
                         "3.5; the MAD is floored at 2%% of the median so "
                         "flat series don't false-positive)")
    tr.add_argument("--fail-on-alert", action="store_true",
                    help="exit 1 when any watched metric alerts (the CI "
                         "gate mode)")
    tr.add_argument("--write-index", action="store_true",
                    help="persist the scanned entries to ROOT/history.jsonl "
                         "(atomic replace)")
    tr.add_argument("--run-dir", default="",
                    help="flight-recorder run directory for the "
                         "trend_report records")
    tr.set_defaults(fn=cmd_trends)

    td = sub.add_parser(
        "trace-diff",
        help="replay one policy through two engines with decision traces "
             "and report the first divergent step (exit 1 on divergence)")
    _add_trace_flags(td)
    td.add_argument("--engines", default="exact,flat",
                    help="two comma-separated engines from {exact, flat} "
                         "(the fused kernel cannot carry the trace); "
                         "repeat one (exact,exact) for a self-check")
    td.add_argument("--policy", default="best_fit",
                    help="zoo policy to replay (ignored with --code)")
    td.add_argument("--code", default="",
                    help="candidate source file to replay on the "
                         "funsearch VM instead of a zoo policy")
    td.add_argument("--suite", default="default8",
                    help="scenario suite providing --scenario variants "
                         "(default default8)")
    td.add_argument("--scenario", type=int, default=None,
                    help="replay on suite scenario INDEX (0-based) instead "
                         "of the base workload — fault-injected scenarios "
                         "diff NODE_DOWN/NODE_UP rows too")
    td.add_argument("--max-steps", type=int, default=0,
                    help="cap replay steps (0 = engine default)")
    td.add_argument("--tol", type=float, default=1e-5,
                    help="score/margin comparison tolerance (default 1e-5)")
    td.add_argument("--cpu", action="store_true",
                    help="force the CPU backend")
    td.add_argument("--run-dir", default="",
                    help="flight-recorder run directory for the "
                         "decision_trace / trace_diff records")
    td.set_defaults(fn=cmd_trace_diff)

    sn = sub.add_parser("scenarios",
                        help="list scenario suites / describe one suite "
                             "or scenario", parents=[common])
    _add_trace_flags(sn)
    sn.add_argument("--suite", default="",
                    help="materialize this suite against the workload and "
                         "print its summary (omit to list registered "
                         "suites)")
    sn.add_argument("--scenario", type=int, default=None,
                    help="describe one scenario (0-based index) incl. its "
                         "fault timeline")
    sn.set_defaults(fn=cmd_scenarios)

    ln = sub.add_parser(
        "lint",
        help="JAX-invariant AST lints + jaxpr-pin drift gate "
             "(exit 1 on findings or drift)")
    ln.add_argument("paths", nargs="*",
                    help="files/directories to lint (default: fks_tpu)")
    ln.add_argument("--pins", default="",
                    help="pin manifest path (default: "
                         "tests/fixtures/jaxpr_pins.json)")
    ln.add_argument("--write-pins", action="store_true",
                    help="recompute and rewrite the pin manifest instead "
                         "of checking it")
    ln.add_argument("--no-pins", action="store_true",
                    help="AST lints only (skip the jaxpr lowering sweep)")
    ln.add_argument("--cpu", action="store_true",
                    help="force the CPU backend")
    ln.add_argument("--run-dir", default="",
                    help="flight-recorder run directory for the "
                         "lint_report record")
    ln.set_defaults(fn=cmd_lint)

    t = sub.add_parser("traces", help="list available trace files")
    t.set_defaults(fn=cmd_traces)

    ss = sub.add_parser(
        "snapshot",
        help="rewrite the committed snapshots (the --snapshot files of "
             "simulate / bench / evolve): the loaded cluster and the "
             "real trace mid-run")
    ss.set_defaults(fn=cmd_snapshot)
    return ap


def main(argv=None) -> int:
    ap = build_parser()
    args = ap.parse_args(argv)
    from fks_tpu.utils import place_compile_cache
    place_compile_cache()
    if getattr(args, "engine", "exact") == "fused" and args.cmd != "scale":
        ap.error("--engine fused evaluates parametric populations only — "
                 "it applies to the 'scale' command (other commands run "
                 "single policies or arbitrary evolved code; use "
                 "'exact'/'flat' there)")
    if getattr(args, "snapshot", "") and args.engine != "flat":
        ap.error("--snapshot: flat engine only on this command (candidate "
                 "evaluation forks on the flat engine; the exact engine's "
                 "fork is what serve engines use); pass --engine flat")
    if getattr(args, "snapshot", "") and getattr(args, "parity_sample", 0):
        ap.error("--snapshot: no parity sentinel (--parity-sample rescores "
                 "candidates on the exact engine, and candidate evaluation "
                 "forks on the flat engine only)")
    return args.fn(args)


if __name__ == "__main__":
    raise SystemExit(main())
