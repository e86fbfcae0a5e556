"""From a profiler trace (``*.xplane.pb``) to device busy time and a
breakdown. Read with ``jax.profiler.ProfileData`` alone.

What a v5e trace holds (looked at by hand, PR 23): one plane per chip,
``/device:TPU:<n>``, whose line ``XLA Ops`` has one event per executed HLO
instruction, named by its HLO text (``%fusion.9 = ... fusion(...)``), and
``/host:CPU`` with one line per host thread, on the same clock; the
benchmark's ``jax.profiler.TraceAnnotation`` spans are events of the
``python3`` line.

``XLA Ops`` events NEST: a ``%while`` event lasts as long as its whole
loop and the body's instructions lie inside it. Taking the union of all
events would call a device busy for as long as any loop is open, so
control-flow containers (the opcodes ``while``, ``conditional`` and
``call``, whatever the instruction is named: XLA names a conditional
``%cond.17.clone.9``) are left out and busy time is the union of the
instructions that do the work. The
gaps that remain inside a loop are the device waiting on itself
(scalar-unit control, launches of many small fusions); gaps between
programs are the host's. The traced window is a slice of the steady state
that starts in the middle of a call, so the host span of that call is not
in the trace and the device trace alone has to tell the two kinds apart:
idle time is reported by the innermost benchmark span open at the gap AND
by the gap's length (``GAP_CLASSES``): gaps of microseconds are between
the instructions of one program, gaps of milliseconds are the host's.
"""
from __future__ import annotations

import re
from typing import Dict, List, Optional, Tuple

from jax.profiler import ProfileData

#: `` = <shape> <opcode>(`` of an instruction that only holds others
CONTAINER = re.compile(r" = .*?[\s)}\]](while|conditional|call)\(")
OPS_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
SPAN_PREFIX = "bench/"
#: the most the chip's clock is taken to lag the host's (measured: 1.1 ms)
SKEW_NS = 5e6
#: (upper edge in ns, label) of the idle gaps' length classes
GAP_CLASSES = ((1e4, "under_10us"), (1e6, "10us_to_1ms"),
               (float("inf"), "over_1ms"))


def short_name(hlo: str) -> str:
    """``%select_reduce_fusion.9 = (s32[256,16]{...}, ...) fusion(...)`` ->
    ``select_reduce_fusion.9 s32[256,16]``: the instruction's name and the
    shape of its (first) result."""
    name = hlo.split(" = ", 1)[0].lstrip("%")
    m = re.search(r"= \(?([a-z0-9]+\[[0-9,]*\])", hlo)
    return f"{name} {m.group(1)}" if m else name


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def load_trace(path: str):
    """(devices, spans): per chip the ``XLA Ops`` events as (start ns, end
    ns, HLO text), and the benchmark's host spans as (start, end, name)."""
    pd = ProfileData.from_file(path)
    spans: List[Tuple[float, float, str]] = []
    devices: Dict[int, list] = {}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[int(m.group(1))] = [
                        (e.start_ns, e.start_ns + e.duration_ns, e.name)
                        for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.start_ns, e.start_ns + e.duration_ns,
                                      e.name))
    return devices, spans


def reduce_trace(path: str, window: str = "bench/trace_window",
                 top: int = 10) -> Optional[dict]:
    """``window``: the name of the host span that bounds the traced
    window (its first occurrence). Returns None when the trace has no
    device plane (a CPU run: nothing to read).

    The chip's clock and the host's are not the same clock: in the
    recorded fixture every device event lies about 1.1 ms BEFORE the host
    call that launched it. Lengths are right on both, so ``window_s`` is
    the host span's length and ``busy_s`` the union of working
    instructions; to say which span a gap falls in, device times are
    shifted forward until the first instruction starts inside the window,
    which is good to a millisecond or so: enough for calls that last
    seconds, not for telling two short spans apart.

    Returns ``busy_s`` (mean over chips), ``window_s``, ``chips``,
    ``device_events`` (working instructions inside the window, all chips),
    ``device_ops`` (top instructions by summed seconds, over all chips) and
    ``idle_gaps`` (idle seconds of the first chip by ``<innermost benchmark
    span open at the gap> <length class>``)."""
    devices, spans = load_trace(path)
    return reduce_events(devices, spans, window, top)


def reduce_events(devices: Dict[int, list],
                  spans: List[Tuple[float, float, str]],
                  window: str = "bench/trace_window",
                  top: int = 10) -> Optional[dict]:
    """``reduce_trace`` on events already loaded (the selftest feeds it a
    hand-made loop)."""
    devices = {k: [e for e in v if not CONTAINER.search(e[2])]
               for k, v in devices.items()}
    devices = {k: v for k, v in devices.items() if v}
    if not devices:
        return None
    spans.sort()
    named = [s for s in spans if s[2] == window]
    if not named:
        raise ValueError(f"trace has no host span {window!r}; spans: "
                         f"{sorted({s[2] for s in spans})}")
    w0, w1 = named[0][:2]
    # the first instruction that can belong to the window: nothing the
    # device ran more than SKEW_NS before the span opened does
    near = [e[0] for ev in devices.values() for e in ev
            if e[1] > w0 - SKEW_NS]
    shift = max(0.0, w0 - min(near)) if near else 0.0

    op_seconds: Dict[str, float] = {}
    n_events = 0
    busy_per_chip = []
    merged0: List[Tuple[float, float]] = []
    for chip in sorted(devices):
        work = []
        for s, e, name in devices[chip]:
            s, e = max(s + shift, w0), min(e + shift, w1)
            if e <= s:
                continue
            work.append((s, e))
            n_events += 1
            key = short_name(name)
            op_seconds[key] = op_seconds.get(key, 0.0) + (e - s) / 1e9
        merged = _union(work)
        busy_per_chip.append(sum(e - s for s, e in merged) / 1e9)
        if chip == min(devices):
            merged0 = merged

    # idle gaps of the first chip, by the innermost span open at each
    gaps: Dict[str, float] = {}
    edges = [w0] + [x for iv in merged0 for x in iv] + [w1]
    for g0, g1 in zip(edges[0::2], edges[1::2]):
        if g1 <= g0:
            continue
        mid = (g0 + g1) / 2
        inner = [s for s in spans if s[0] <= mid < s[1]]
        name = min(inner, key=lambda s: s[1] - s[0])[2] if inner \
            else "_no_span_"
        name += " " + next(c for edge, c in GAP_CLASSES if g1 - g0 < edge)
        gaps[name] = gaps.get(name, 0.0) + (g1 - g0) / 1e9

    def ranked(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])
                [:top]]

    return {"busy_s": sum(busy_per_chip) / len(busy_per_chip),
            "window_s": (w1 - w0) / 1e9, "chips": len(devices),
            "device_events": n_events,
            "device_ops": ranked(op_seconds), "idle_gaps": ranked(gaps)}
