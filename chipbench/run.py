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
thread traces a slice of them (``trace_slice``), and reports the per-layer
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

TRACE_DIR = os.path.join(cells.HERE, ".trace")


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


def trace_slice(call, at_s: float, for_s: float, need_device: bool):
    """Trace ``for_s`` seconds of the steady state: the cell's own whole
    calls go on (the window's compiled programs at the window's shapes)
    while a helper thread waits ``at_s`` seconds from the first call's
    start, starts the profiler, holds the ``bench/trace_window`` span for
    ``for_s`` seconds and stops it. A whole call is millions of device
    events (param256: 3.0 million, 141 MB, 85 s to write), so the trace is
    bounded in time and not in calls; ``at_s`` and ``for_s`` are the
    traffic file's. Calls stop once the slice is over. Returns the
    reduction (``chipbench.reduce.xplane``); a trace without device
    events is an error on a chip and None in the selftest's CPU runs."""
    import jax
    from chipbench.drivers.common import trace_window
    from chipbench.reduce.xplane import reduce_trace

    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    # no Python call tracing: it slows host-heavy calls threefold
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    sliced = threading.Event()
    fault = []

    def tracer():
        try:
            time.sleep(at_s)
            jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
            try:
                with trace_window():
                    time.sleep(for_s)
            finally:
                sliced.set()
                jax.profiler.stop_trace()
        except BaseException as e:  # noqa: BLE001 — handed to the caller
            fault.append(e)
            sliced.set()

    helper = threading.Thread(target=tracer, name="chipbench-tracer")
    helper.start()
    try:
        calls = 0
        while not sliced.is_set():
            call(-2 - calls)
            calls += 1
    finally:
        helper.join()
    if fault:
        raise fault[0]
    paths = glob.glob(os.path.join(TRACE_DIR, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    red = reduce_trace(paths[0]) if paths else None
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    if red is None:
        if need_device:
            raise SystemExit("chipbench: the traced slice holds no device "
                             "operation; nothing is reported from it")
        return None
    red["calls_during_trace"] = calls
    return red


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
        red = trace_slice(driver.call, float(cell.traffic["trace_at_s"]),
                          float(cell.traffic["trace_for_s"]),
                          require_tpu) if trace else None
        if red:
            say(row="trace", busy_s=red["busy_s"], window_s=red["window_s"],
                chips=red["chips"], device_events=red["device_events"],
                calls_during_trace=red["calls_during_trace"])
            ctx["trace_busy_s"] = device["busy_s"] = red["busy_s"]
            ctx["trace_window_s"] = device["window_s"] = red["window_s"]
            breakdown = {"device_ops": red["device_ops"],
                         "idle_gaps": red["idle_gaps"]}

        attempted, failed = driver.attempted_failed(rows)
        numbers = driver.check()
        for n in numbers:
            say(row="compared", name=n.name, value=n.value, limit=n.limit,
                ok=n.ok)
        correct = bool(numbers) and all(n.ok for n in numbers) \
            and failed == 0
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
