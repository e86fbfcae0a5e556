"""Find a cell's files by the names in ``BENCHMARK.json``.

A cell is ``workloads[i]``: a configuration under a traffic mix. Its
files are ``chipbench/configs/<config>.json``, ``chipbench/traffic/
<traffic>.json`` (which names its driver, ``chipbench/drivers/<driver>.py``)
and, for every metric that lists the cell, end-to-end or per-layer,
``chipbench/metrics/<metric>.json`` with an optional reader
``chipbench/metrics/<metric>.py``. A later PR adds a cell, a configuration
or a metric by adding such files and entries; nothing here names one.
"""
from __future__ import annotations

import dataclasses
import hashlib
import importlib
import importlib.util
import json
import os
from typing import Any, Callable, Dict, List, Optional

from chipbench import window

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "chipbench")


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[dict]        # BENCHMARK.json entries this cell reports
    per_layer: List[dict]


def load_cell(name: str, overrides: Optional[dict] = None) -> Cell:
    """``overrides``: {"config": {...}, "traffic": {...}} laid over the
    files' values, for the selftest's tiny sizes only."""
    bench = _json(os.path.join(ROOT, "BENCHMARK.json"))
    rows = [w for w in bench["workloads"] if w["name"] == name]
    if not rows:
        raise SystemExit(f"chipbench: no workload {name!r} in BENCHMARK.json; "
                         f"have {[w['name'] for w in bench['workloads']]}")
    w = rows[0]
    cfg_row = next(c for c in bench["configs"] if c["name"] == w["config"])
    config = _json(os.path.join(ROOT, cfg_row["file"]))
    traffic = _json(os.path.join(HERE, "traffic", f"{w['traffic']}.json"))
    overrides = overrides or {}
    config.update(overrides.get("config", {}))
    traffic.update(overrides.get("traffic", {}))

    def mine(m):
        return "workloads" not in m or name in m["workloads"]

    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic,
                end_to_end=[m for m in bench["end_to_end"] if mine(m)],
                per_layer=[m for m in bench["per_layer"] if mine(m)])


def verify_files(config: dict) -> Dict[str, str]:
    """Every ``{"file", "sha256"}`` in the configuration must match the
    bytes on disk: an edited trace is another deployment, so the run
    fails. Returns {key: absolute path}."""
    out = {}
    for key, val in config.items():
        if not (isinstance(val, dict) and "file" in val and "sha256" in val):
            continue
        path = os.path.join(ROOT, val["file"])
        with open(path, "rb") as f:
            got = hashlib.sha256(f.read()).hexdigest()
        if got != val["sha256"]:
            raise SystemExit(
                f"chipbench: {val['file']} has sha256 {got}, the "
                f"configuration pins {val['sha256']}")
        out[key] = path
    return out


def load_driver(name: str):
    return importlib.import_module(f"chipbench.drivers.{name}")


def metric_reader(name: str) -> Callable[[dict], Optional[float]]:
    """The reader of one metric, end-to-end or per-layer, over the run's
    context: the driver's counters, ``setup_s``, ``setup_programs``, the
    window's per-call ``rows`` and ``elapsed_s`` and, in a traced run,
    ``trace_busy_s``/``trace_window_s``. ``<metric>.py`` (``read(ctx)``)
    where present, else the declarative ``reads`` of ``<metric>.json``:
    ``{"counter": k}``, ``{"rate": k}`` (work ``k`` in the whole calls per
    second of them), ``{"ratio": [num, den], "scale": s}`` or
    ``{"one_minus_ratio": [num, den], "scale": s}``. A reader that finds
    nothing to read returns None and the metric is left out."""
    base = os.path.join(HERE, "metrics", name)
    if os.path.exists(base + ".py"):
        spec = importlib.util.spec_from_file_location(
            "chipbench_metric_" + name.replace(".", "_").replace("-", "_"),
            base + ".py")
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read
    reads = _json(base + ".json")["reads"]
    scale = float(reads.get("scale", 1.0))

    def read(ctx: dict) -> Optional[float]:
        if "counter" in reads:
            v = ctx.get(reads["counter"])
            return None if v is None else float(v) * scale
        if "rate" in reads:
            rows = [r for r in ctx.get("rows", ()) if reads["rate"] in r]
            return window.rate(rows, reads["rate"], ctx["elapsed_s"]) \
                * scale if rows else None
        key = "ratio" if "ratio" in reads else "one_minus_ratio"
        num, den = (ctx.get(k) for k in reads[key])
        if num is None or not den:
            return None
        r = float(num) / float(den)
        return (r if key == "ratio" else 1.0 - r) * scale

    return read
