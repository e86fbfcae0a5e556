"""Candidate-evaluation tier benchmark: VM vs per-candidate jit vs parametric.

VERDICT r1 #4: "measure VM-tier evals/s vs jit-tier compile+run on real
LLM-shaped candidates, and record an end-to-end evolve --fake-llm
generation throughput". This tool measures, on the current device:

  vm-warm      one candidate through the shared VM interpreter program
               (per-candidate cost once the interpreter is compiled)
  jit-compile  transpile + XLA-compile one UNSEEN candidate (the cost the
               VM tier avoids)
  jit-warm     re-run of a compiled candidate (pure device run)
  parametric   evals/s for a vmapped parametric population (the backbone)
  evolve-gen   wall time of one full FakeLLM generation through
               FunSearch.evolve_generation (codegen + eval + admission)

Prints one JSON object; pass --metrics FILE to append a JSONL record.
Usage: python tools/measure_tiers.py [--engine flat] [--cpu] [--pop 64]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--engine", choices=("exact", "flat"), default="flat")
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--pop", type=int, default=64)
    ap.add_argument("--candidates", type=int, default=6)
    ap.add_argument("--metrics", default="")
    args = ap.parse_args()

    import jax
    if args.cpu:
        jax.config.update("jax_platforms", "cpu")

    from fks_tpu.data import TraceParser
    from fks_tpu.funsearch import (
        CodeEvaluator, EvolutionConfig, FakeLLM, FunSearch, template,
    )
    from fks_tpu.models import parametric
    from fks_tpu.parallel import make_population_eval
    from fks_tpu.sim.engine import SimConfig

    dev = jax.devices()[0]
    wl = TraceParser().parse_workload()
    fake = FakeLLM(seed=11, junk_rate=0.0)
    codes = [template.fill_template(fake.complete("x"))
             for _ in range(args.candidates)]
    out = {"device": f"{dev.platform}:{dev.device_kind}",
           "engine": args.engine, "workload": f"{wl.num_nodes}x{wl.num_pods}"}

    # ---- VM tier: warm per-candidate cost (compile interpreter on c0)
    ev = CodeEvaluator(wl, engine=args.engine)
    t0 = time.perf_counter()
    r0 = ev.evaluate_one(codes[0])
    out["vm_first_s"] = round(time.perf_counter() - t0, 3)  # incl. compile
    assert r0.ok, r0.error
    times = []
    skipped = 0
    for c in codes[1:]:
        t0 = time.perf_counter()
        r = ev.evaluate_one(c)
        dt = time.perf_counter() - t0
        # only successful VM-tier evaluations may enter the timing: a
        # validation-error record returns in milliseconds and a
        # VM-unsupported candidate pays a jit compile — both would corrupt
        # vm_warm_s. A degenerate candidate that exhausts the step budget
        # (score 0, truncated) is skipped too: it times max_steps, not a
        # typical eval.
        if r.ok:
            times.append(dt)
        else:
            skipped += 1
    assert ev.compile_count == 0, "a candidate fell to the jit tier"
    assert len(times) >= 2, "too few clean candidates to time"
    out["vm_skipped_candidates"] = skipped
    out["vm_warm_s"] = round(min(times), 3)
    out["vm_tier_hits"] = ev.vm_count
    out["vm_evals_per_sec"] = round(1.0 / min(times), 3)

    # ---- batched VM tier: a GENERATION as one device launch (the
    # population-batched path; round-3 verdict ask #3). Two distinct
    # candidate sets: the first launch pays the population-engine
    # compile, the second is the steady-state per-generation cost.
    evb = CodeEvaluator(wl, engine=args.engine, vm_batch=True)
    gen_a = [template.fill_template(fake.complete("x"))
             for _ in range(args.candidates)]
    gen_b = [template.fill_template(fake.complete("x"))
             for _ in range(args.candidates)]
    t0 = time.perf_counter()
    recs = evb.evaluate(gen_a)
    out["vm_batch_first_s"] = round(time.perf_counter() - t0, 3)
    t0 = time.perf_counter()
    recs = evb.evaluate(gen_b)
    dt = time.perf_counter() - t0
    assert evb.compile_count == 0, "a candidate fell to the jit tier"
    out["vm_batch_pop"] = len(recs)
    out["vm_batch_launches"] = evb.vm_batch_count
    out["vm_batch_warm_s"] = round(dt, 3)
    out["vm_batch_evals_per_sec"] = round(len(recs) / dt, 3)

    # ---- jit tier: per-unseen-candidate compile+run, then warm re-run
    ev2 = CodeEvaluator(wl, engine=args.engine, use_vm=False)
    t0 = time.perf_counter()
    ev2.evaluate_one(codes[0])
    out["jit_compile_run_s"] = round(time.perf_counter() - t0, 3)
    t0 = time.perf_counter()
    ev2.evaluate_one(codes[0])
    out["jit_warm_s"] = round(time.perf_counter() - t0, 3)

    # ---- parametric tier: chunked vmapped population
    params = parametric.init_population(jax.random.PRNGKey(0), args.pop,
                                        noise=0.1)
    pev = make_population_eval(wl, cfg=SimConfig(), engine=args.engine)
    r = pev(params)
    jax.block_until_ready(r.policy_score)  # compile
    t0 = time.perf_counter()
    r = pev(params)
    jax.block_until_ready(r.policy_score)
    dt = time.perf_counter() - t0
    out["parametric_pop"] = args.pop
    out["parametric_sweep_s"] = round(dt, 3)
    out["parametric_evals_per_sec"] = round(args.pop / dt, 2)

    # ---- exact-engine diet (single lane): µs/event on THIS device — the
    # on-chip validation of the round-3 CPU-only instruction-diet claim
    # (117 -> 72.8 µs/event; VERDICT r4 weak #4 / ask #8). Fault-isolated:
    # a failure here records the error and keeps the tier numbers.
    try:
        from fks_tpu.models import zoo
        from fks_tpu.sim import engine as exact_engine
        ecfg = SimConfig()
        runfn = jax.jit(exact_engine.make_run_fn(
            wl, zoo.ZOO["best_fit"](), ecfg))
        es0 = exact_engine.initial_state(wl, ecfg)
        er = runfn(es0)
        jax.block_until_ready(er.policy_score)  # compile
        t0 = time.perf_counter()
        er = runfn(es0)
        jax.block_until_ready(er.policy_score)
        dt = time.perf_counter() - t0
        n_ev = int(er.events_processed)
        out["exact_best_fit_s"] = round(dt, 3)
        out["exact_events"] = n_ev
        out["exact_us_per_event"] = round(dt / max(n_ev, 1) * 1e6, 2)
    except Exception as e:  # noqa: BLE001 — keep the tier numbers
        out["exact_error"] = f"{type(e).__name__}: {e}"

    # ---- end-to-end generation: codegen + eval + admission (reuses the
    # warmed evaluator, as a steady-state generation would)
    cfg = EvolutionConfig(population_size=12, generations=1, elite_size=3,
                          candidates_per_generation=8, max_workers=8, seed=5,
                          early_stop_threshold=1.1)
    fs = FunSearch(ev, cfg, backend=FakeLLM(seed=5), log=lambda *a: None)
    fs.initialize_population()
    compiles_before = ev.compile_count
    t0 = time.perf_counter()
    st = fs.evolve_generation()
    out["evolve_gen_s"] = round(time.perf_counter() - t0, 3)
    out["evolve_gen_candidates"] = st.new_candidates
    out["evolve_cand_per_sec"] = round(st.new_candidates
                                       / max(out["evolve_gen_s"], 1e-9), 3)
    # delta, not cumulative: compiles from earlier sections must not be
    # attributed to the generation
    out["evolve_xla_compiles"] = ev.compile_count - compiles_before

    # compact, single line: callers take the LAST parsable stdout line as
    # the payload — an indented dump would leave them a closing brace
    print(json.dumps(out))
    if args.metrics:
        from fks_tpu.utils import MetricsWriter
        with MetricsWriter(args.metrics) as mw:
            mw.write("tier_benchmark", out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
