"""The benchmark's one command.

    python3 -m chipbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process. It requires a TPU with at least the chips the cell asks for
(anything else: exit 3, no result line), places the one compile cache,
checks the configuration's files against their hashes, builds the cell's
driver and warms the cell's own shapes (all of that is ``setup_s``), makes
whole calls for ``--seconds`` (``chipbench.window``), then compares a
seeded sample of what the window produced with the plain reference and
prints the contract's one JSON line last. ``--trace 0`` reports the cell's
end-to-end metrics; ``--trace 1`` runs the same window with the program's
stage profiler on, then goes on making the same whole calls while a helper
thread traces a slice of them, placed in the call's device stage as the
window's own calls show it (``trace_slice``), and reports the per-layer
metrics, ``busy_s``/``window_s`` and the breakdown. Either list of metrics
is read by the readers of ``chipbench/metrics/`` (``cells.metric_reader``).
"""
from __future__ import annotations

import time

T_START = time.perf_counter()   # process start, as nearly as Python can say

import argparse  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

from chipbench import cells, window  # noqa: E402

#: a process writes its traces under a directory of its own: two runs in
#: one checkout (the tests run several side by side) clear only their own
TRACE_DIR = os.path.join(cells.HERE, ".trace", str(os.getpid()))


def say(**row) -> None:
    print(json.dumps(row), flush=True)


def _devices(chips: int, require_tpu: bool):
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        print(f"chipbench: no TPU (platform {devs[0].platform!r}); nothing "
              "is measured on another backend", file=sys.stderr)
        raise SystemExit(3)
    if len(devs) < chips:
        print(f"chipbench: the cell needs {chips} chips, JAX sees "
              f"{len(devs)}", file=sys.stderr)
        raise SystemExit(3)
    return devs[:chips]


#: what ``jax.profiler.start_trace`` takes on a v5e host while a call runs,
#: per chip of the cell (my chip runs, PR 33: 0.043-0.051 s on one chip in
#: six runs, 0.157-0.181 s on four in three): the profiler is started this
#: much before the slice is due; a retake starts it by what the attempt
#: before it measured
PROFILER_START_S = 0.044
#: slices taken before a traced run gives up (``trace_slice``)
ATTEMPTS = 3


def _sleep_until(t: float) -> None:
    left = t - time.perf_counter()
    if left > 0:
        time.sleep(left)


def trace_slice(driver, calls, for_s: float, phase: float, lead_s: float,
                need_device: bool) -> dict:
    """Trace ``for_s`` seconds of the steady state: the cell's own whole
    calls go on (the window's compiled programs at the window's shapes)
    and a helper thread, born with the stamp of a call's start, waits for
    the offset at which that call's device stage is due, starts the
    profiler, holds the ``bench/trace_window`` span for ``for_s`` seconds
    and stops it. The offset is ``chipbench.reduce.anchor``'s: the
    driver's ``device_stage`` in ``calls`` (the window's whole calls, with
    the program's spans where it has them), the slice's middle at
    ``phase`` of the stage; the profiler is started ``lead_s`` before
    that, what its start is known to take, and the span opens on time or
    as soon as the start returns. A whole call is millions of device events
    (param256: 3.0 million, 141 MB, 85 s to write), so the trace is
    bounded in time and not in calls. Calls stop once the slice is over.

    A slice missed when no device instruction worked in it, or when it
    does not lie inside the device stage that ITS OWN call turned out to
    have (a host slowed by the profiler, a call that changed under it; a
    slice in the host stages can still hold a few stray copies: 6 events,
    3.3 us, in the cluster cell's transpile, my chip runs, PR 33). It is
    then taken again in the next call, ``ATTEMPTS`` times in all, each
    time placed by the calls made HERE so far. After the last miss the run
    fails and names where every slice fell; few device events INSIDE the
    stage are a finding and no miss. Without ``need_device`` (the
    selftest's CPU runs) the first slice stands and ``device`` is None.

    Returns the ``trace`` row: where the slice fell (``call_offset_s``:
    its two ends after the start of its call; ``stage_s``: the stage it
    was aimed at and ``stage_from`` what that was read from;
    ``call_stage_s``: the stage its own call turned out to have;
    ``phase``: its middle in that stage; ``inside``: the innermost program
    span open at its middle; ``in_stage``), ``attempts``,
    ``profiler_start_s`` and, under ``device``, the reduction
    (``chipbench.reduce.xplane``)."""
    import jax
    from chipbench.drivers.common import trace_window
    from chipbench.reduce import anchor, spans, xplane

    # no Python call tracing: it slows host-heavy calls threefold
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    now = time.perf_counter
    made = []                    # (t0, t1) of every call made here
    lead, fell = lead_s, []
    for attempt in range(1, ATTEMPTS + 1):
        stage = anchor.stage_of(calls, driver.device_stage, for_s)
        begin = anchor.place(stage, for_s, phase)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        sliced = threading.Event()
        shot, fault = {}, []

        def tracer(t_call):
            try:
                _sleep_until(t_call + begin - lead)
                t = now()
                jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
                shot["start_s"] = now() - t
                try:
                    _sleep_until(t_call + begin)
                    shot["w0"] = now()
                    with trace_window():
                        time.sleep(for_s)
                    shot["w1"] = now()
                finally:
                    sliced.set()
                    jax.profiler.stop_trace()
            except BaseException as e:  # noqa: BLE001 — handed to the caller
                fault.append(e)
                sliced.set()

        helper = None
        try:
            while not sliced.is_set():
                t0 = now()
                if helper is None:
                    helper = threading.Thread(target=tracer, args=(t0,),
                                              name="chipbench-tracer")
                    helper.start()
                driver.call(-2 - len(made))
                made.append((t0, now()))
        finally:
            if helper is not None:
                helper.join()
        if fault:
            raise fault[0]
        paths = glob.glob(os.path.join(TRACE_DIR, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        red = xplane.reduce_trace(paths[0]) if paths else None
        shutil.rmtree(TRACE_DIR, ignore_errors=True)

        # where it fell: in the call of ``made`` that was running at its
        # middle, against the stage THAT call turned out to have
        mid = (shot["w0"] + shot["w1"]) / 2
        t_call = max((c for c in made if c[0] <= mid), default=made[0])
        own = spans.calls_between([t_call])[0]
        got = driver.device_stage(own, for_s)
        a0, a1 = got if got else (own.t0 + stage.d0, own.t0 + stage.d1)
        where = {
            "call_offset_s": [shot["w0"] - own.t0, shot["w1"] - own.t0],
            "stage_s": [stage.d0, stage.d1], "stage_from": stage.source,
            "call_stage_s": [got[0] - own.t0, got[1] - own.t0] if got
            else None,
            "phase": (mid - a0) / (a1 - a0),
            "inside": anchor.innermost(own.spans, mid)
            or (driver.span if mid < own.t1 else "_between_calls_"),
            "in_stage": bool(a0 <= shot["w0"] and shot["w1"] <= a1),
            "profiler_start_s": shot["start_s"],
            "device_events": red["device_events"] if red else 0}
        fell.append(where)
        hit = red and red["device_events"] and where["in_stage"]
        if hit or not need_device:
            return {**where, "attempts": attempt,
                    "calls_during_trace": len(made), "device": red}
        lead = shot["start_s"]
        calls = spans.calls_between(made)
    raise SystemExit(
        f"chipbench: none of {ATTEMPTS} traced slices lay inside its call's "
        "device stage with a device instruction at work; nothing is "
        "reported from them. They fell at "
        + "; ".join(
            "{:.3f}-{:.3f} s of a call (phase {:.2f} of its device stage; "
            "aimed at {:.3f}-{:.3f} s, from {}) inside {}".format(
                *w["call_offset_s"], w["phase"], *w["stage_s"],
                w["stage_from"], w["inside"])
            + (", no device instruction" if not w["device_events"] else "")
            for w in fell))


def worst_compared(numbers) -> dict:
    """Every kind of number ``check`` compared (``lane3.placements_differ``
    is of the kind ``placements_differ``), each once: its largest value,
    the lane or query that read it, its limit, and how many of the kind
    are over theirs. A value that is no number (NaN) is over and is the
    worst."""
    out = {}
    for n in numbers:
        item, _, kind = n.name.rpartition(".")
        c = out.setdefault(kind, {"value": n.value, "limit": n.limit,
                                  "worst_in": item, "over_limit": 0,
                                  "of": 0})
        c["of"] += 1
        c["over_limit"] += not n.ok
        if not c["value"] != c["value"] and not n.value <= c["value"]:
            c.update(value=n.value, limit=n.limit, worst_in=item)
    for c in out.values():       # the line stays strict JSON
        if not abs(c["value"]) < float("inf"):
            c["value"] = repr(c["value"])
    return out


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True, overrides=None) -> dict:
    """Run one cell and return the result line as a dict. ``require_tpu``
    and ``overrides`` are the selftest's: it drives everything but the
    look for a chip, at tiny sizes, on the CPU."""
    cell = cells.load_cell(name, overrides)
    devs = _devices(cell.chips, require_tpu)
    t_devices = time.perf_counter() - T_START
    from fks_tpu import obs
    from fks_tpu.utils import place_compile_cache

    cache_dir = place_compile_cache()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if require_tpu:
        from chipbench.reduce.peaks import peaks_for
        peaks_for(device["kind"])      # an unknown chip is an error
    say(row="device", **device, compile_cache_dir=cache_dir,
        workload=name, seed=seed, seconds=seconds, trace=int(trace))
    files = cells.verify_files(cell.config)
    mesh = None
    if cell.chips > 1:
        from fks_tpu.parallel import population_mesh
        mesh = population_mesh(devs)

    watcher = obs.CompileWatcher().install()
    driver = cells.load_driver(cell.traffic["driver"]).Driver(
        cell, seed, files, mesh, trace)
    try:
        shapes = driver.setup()
        setup_s = time.perf_counter() - T_START
        setup_programs = watcher.compiled_count
        say(row="setup", setup_s=setup_s, start_to_devices_s=t_devices,
            programs_compiled=setup_programs,
            cache_hits=watcher.cache_hits,
            reduced={k: cell.config[k] for k in cell.config["reduced"]},
            **shapes)

        c0 = watcher.compiled_count
        rows, elapsed = window.run_window(driver.call, seconds)
        window_compiles = watcher.compiled_count - c0
        for i, r in enumerate(rows):
            say(row="call", i=i, **r)
        say(row="window", calls=len(rows), elapsed_s=elapsed,
            compiles_in_window=window_compiles)

        ctx = dict(driver.counters(), setup_programs=setup_programs,
                   setup_s=setup_s, rows=rows, elapsed_s=elapsed)
        breakdown = None
        if trace:
            from chipbench.reduce import anchor, spans
            got = trace_slice(
                driver, spans.window_calls(ctx) or anchor.calls_of_rows(rows),
                float(cell.traffic["trace_for_s"]),
                float(cell.traffic.get("trace_phase", 0.5)),
                PROFILER_START_S * cell.chips, require_tpu)
            red = got.pop("device")
            if red:
                got.update(busy_s=red["busy_s"], window_s=red["window_s"],
                           chips=red["chips"])
                ctx["trace_busy_s"] = device["busy_s"] = red["busy_s"]
                ctx["trace_window_s"] = device["window_s"] = red["window_s"]
                breakdown = {"device_ops": red["device_ops"],
                             "idle_gaps": red["idle_gaps"]}
            say(row="trace", **got)

        attempted, failed = driver.attempted_failed(rows)
        numbers = driver.check()
        for n in numbers:
            say(row=n.row, name=n.name, value=n.value, limit=n.limit,
                ok=n.ok)
        correct = bool(numbers) and all(n.ok for n in numbers) \
            and failed == 0
        compared = worst_compared(numbers)
    finally:
        driver.close()
        watcher.uninstall()

    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = cells.metric_reader(m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        elif not trace:
            raise SystemExit(f"chipbench: the run has no {m['name']}")

    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    device["memory_peak_bytes"] = int(max(peaks))
    result = {"correct": correct, "attempted": int(attempted),
              "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    # last in the line and last on standard error: what a record that
    # keeps only the end of a failed run still holds
    result["compared"] = compared
    for name, c in compared.items():
        print(f"chipbench: compared {name} = {c['value']!r} (limit "
              f"{c['limit']!r}, worst in {c['worst_in']}, "
              f"{c['over_limit']} of {c['of']} over)", file=sys.stderr)
    print(f"chipbench: correct = {correct} (failed operations: "
          f"{int(failed)})", file=sys.stderr, flush=True)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    result = run_cell(a.workload, a.seed, a.seconds, bool(a.trace))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
