#!/usr/bin/env python3
"""Compile a benchmark cell's device program for a DESCRIBED TPU and print
the kernels of its loop body, with shapes and layouts. No chip is needed:
libtpu is installed, so ``jax.experimental.topologies`` hands the real TPU
compiler a topology to compile for (PERF.md section 6, PRs 35 and 41). The
compiled module carries the instruction names the ledger's ``breakdown``
reports (``select_reduce_fusion.9 s32[256,16]``), so the two are read side
by side. It cannot run anything, and XLA's ``estimated_cycles`` are a cost
model, not a clock: this says WHAT a change does to the program.

    JAX_PLATFORMS=cpu python tools/describe_compile.py population
    JAX_PLATFORMS=cpu python tools/describe_compile.py codegen --cluster 1523
    JAX_PLATFORMS=cpu python tools/describe_compile.py whatif --hlo /tmp/w.hlo

``population``: ``make_population_eval(engine="flat")`` at param256's
shapes. ``codegen``: the batched VM tier's population runner, 8 lanes of
a ledger champion at its own capacity bucket (the generation's register
file). ``whatif``: ``VMServeEngine``'s executable, for that champion, for
2 lanes of the 256-pod bucket on the exact engine (whatif8's largest
chunk). ``--cluster 1523`` is the OpenB cluster (under
the program's own large-cluster rule) with the inflated trace;
``--cluster 1523-gpuspec25`` the same cluster with the inflated gpuspec25
list and its GPU-type constraints honoured (the step's type term).
"""
from __future__ import annotations

import argparse
import collections
import functools
import math
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
# quiet libtpu's search for a metadata server; the topology is described
os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")
os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")

#: instructions that are no kernel of their own
PLUMBING = frozenset((
    "parameter", "get-tuple-element", "tuple", "constant", "bitcast",
    "copy-start", "copy-done", "partition-id", "replica-id", "after-all"))

_INSTR = re.compile(r"^\s*(?:ROOT )?%(\S+) = (.*?) ([a-z][a-z-]*)\(")
_ARRAY = re.compile(r"([a-z]+\d*)\[([\d,]*)\]\{([\d,]*)")
_CYCLES = re.compile(r'"estimated_cycles":"(\d+)","iteration_bounds":\[([^\]]*)\]')


def topology_device(name: str = "v5e:2x2"):
    """The first device of a described topology, or None where this
    installation cannot describe one (no libtpu)."""
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(name, "tpu").devices[0]
    except Exception:  # noqa: BLE001 -- any failure means "cannot describe"
        return None


def compile_for(device, fn, *args, **jit_kw) -> str:
    """Optimized HLO of ``jit(fn)(*args)`` compiled for ``device``; the
    arguments are read for shape and dtype only."""
    import jax
    from jax.sharding import SingleDeviceSharding

    sh = SingleDeviceSharding(device)
    spec = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(jax.numpy.shape(x),
                                       jax.numpy.result_type(x), sharding=sh),
        args)
    return jax.jit(fn, **jit_kw).lower(*spec).compile().as_text()


@functools.lru_cache(maxsize=4)
def computations(hlo: str) -> dict:
    """name -> the lines of each computation of an HLO module's text."""
    out, name = {}, None
    for line in hlo.splitlines():
        if name is None:
            m = re.match(r"^(?:ENTRY )?%(\S+) \(.*\) -> .* \{$", line)
            if m:
                name = m.group(1)
                out[name] = []
        elif line == "}":
            name = None
        else:
            out[name].append(line)
    return out


def _kernels(lines) -> list:
    """One dict an instruction of a computation's ``lines`` that is not
    plumbing (``name``, ``op``, ``result``: the result's text, ``arrays``:
    ``(dtype, dims, minor_to_major)`` of each array in it, ``cycles``:
    ``estimated_cycles`` x ``iteration_bounds`` where the compiler gave
    them, else 0, ``calls``: the computation a fusion runs or a ``while``
    repeats, else None)."""
    rows = []
    for line in lines:
        m = _INSTR.match(line)
        if not m or m.group(3) in PLUMBING:
            continue
        cyc = _CYCLES.search(line)
        cycles = 0
        if cyc:
            cycles = int(cyc.group(1))
            for it in re.findall(r"\d+", cyc.group(2)):
                cycles *= int(it)
        arrays = [(d, tuple(int(x) for x in s.split(",") if x),
                   tuple(int(x) for x in l.split(",") if x))
                  for d, s, l in _ARRAY.findall(m.group(2))]
        calls = re.search(r"(?:calls|body)=%([^,\s}]+)", line)
        rows.append(dict(name=m.group(1), op=m.group(3),
                         result=m.group(2), arrays=arrays, cycles=cycles,
                         calls=calls and calls.group(1)))
    return rows


def fused_ops(hlo: str, row: dict) -> list:
    """The opcodes inside a kernel of `_kernels`: those of the computation
    a fusion calls, or the instruction's own."""
    lines = computations(hlo).get(row["calls"], ())
    return [m.group(3) for m in map(_INSTR.match, lines) if m] or [row["op"]]


@functools.lru_cache(maxsize=4)
def _while_bodies(hlo: str) -> dict:
    """name -> `_kernels` of every ``while`` body of the module."""
    comps = computations(hlo)
    return {b: _kernels(comps.get(b, ()))
            for b in set(re.findall(r"body=%([^,\s}]+)", hlo))}


def loop_body(hlo: str) -> list:
    """The kernels of the event loop of a benchmark cell's program
    (`_kernels`): the largest ``while`` body that itself holds a ``while``
    (the op-slot loop, which a block of slots a turn makes the larger of
    the two), or the largest body of all where none nests (a program that
    interprets nothing)."""
    bodies = list(_while_bodies(hlo).values())
    outer = [b for b in bodies if any(r["op"] == "while" for r in b)]
    return max(outer or bodies, key=len, default=[])


def slot_loops(hlo: str, outer: bool = False) -> list:
    """The kernels of the interpreter's op-slot loops, a list a loop: from
    the ``while`` inside `loop_body` that carries the largest array, which
    is the register file ``[lanes, rows, N, G]``, down to every innermost
    ``while`` that carries an array of that size. One loop where a turn is
    a slot (serving, one program alone) and before PR 47; two where the
    batched VM walks RUNS of turns, the narrow turn's and the wide turn's
    in the order the run loop holds them. The run loop's own body is
    scalar bookkeeping and is listed only with ``outer`` (first). Empty
    where the event loop holds no ``while`` (a program that interprets
    nothing)."""
    def largest(r):
        return max((math.prod(dims) for _, dims, _ in r["arrays"]), default=0)

    inner = [r for r in loop_body(hlo) if r["op"] == "while"]
    if not inner:
        return []
    file_size = max(map(largest, inner))
    holding, innermost, todo = [], [], [max(inner, key=largest)]
    while todo:
        body = _while_bodies(hlo)[todo.pop(0)["calls"]]
        deeper = [r for r in body
                  if r["op"] == "while" and largest(r) == file_size]
        todo = deeper + todo
        (holding if deeper else innermost).append(body)
    return holding * outer + innermost


def slot_loop(hlo: str) -> list:
    """The kernels of every loop of `slot_loops`, joined."""
    return [r for body in slot_loops(hlo) for r in body]


def array_mentions(hlo: str, shape: tuple) -> list:
    """The layout text (``2,3,1,0:T(8,128)S(1)``: minor to major, tiling,
    memory space) of every mention of an array of ``shape`` in the module,
    results and operands and fused parameters alike."""
    dims = ",".join(str(d) for d in shape)
    return re.findall(r"[a-z]+\d*\[" + dims + r"\]\{([^}]*)\}", hlo)


def operand_layouts(hlo: str, shape: tuple) -> collections.Counter:
    """minor_to_major -> how many arrays of ``shape`` the whole module
    mentions with it (results and fused parameters alike)."""
    return collections.Counter(
        m.split(":")[0] for m in array_mentions(hlo, shape))


# ------------------------------------------------------------ executables

def _workload(cluster: str):
    from fks_tpu.data import TraceParser

    if cluster == "16":
        return TraceParser().parse_workload()
    if cluster == "1523-gpuspec25":
        return TraceParser().parse_workload(
            node_file="openb_node_list_all_node.csv",
            pod_file="openb_pod_list_gpuspec25_inflated080.csv",
            gpu_spec="honor")
    return TraceParser().parse_workload(
        node_file="openb_node_list_all_node.csv",
        pod_file="openb_pod_list_inflated080.csv")


def population(device, cluster: str, lanes: int) -> str:
    import jax.numpy as jnp
    from fks_tpu.models import parametric
    from fks_tpu.parallel import make_population_eval
    from fks_tpu.sim.engine import SimConfig

    wl = _workload(cluster)
    ev = make_population_eval(
        wl, cfg=SimConfig(max_steps=4 * wl.num_pods, track_ctime=False),
        engine="flat", jit=False)
    return compile_for(
        device, ev, jnp.zeros((lanes, parametric.NUM_FEATURES), jnp.float32))


def _champion() -> str:
    """The ledger champion the benchmark's VM cells hold (score 0.5365):
    the longest program of every generation and the one serving answers
    with, so its capacity bucket is the register file's."""
    import json

    with open(os.path.join(
            ROOT, "policies", "discovered",
            "funsearch_20260801_045536_score0.5365.json")) as f:
        return json.load(f)["code"]


def codegen(device, cluster: str, lanes: int) -> str:
    from fks_tpu.funsearch import vm
    from fks_tpu.sim import flat
    from fks_tpu.sim.engine import SimConfig, shape_prefilter_k

    wl = _workload(cluster)
    c = wl.cluster
    cfg = SimConfig(max_steps=2048 if cluster == "16" else 1024,
                    node_prefilter_k=shape_prefilter_k(c.n_padded))
    view = cfg.resolve_prefilter_k(c.n_padded) or c.n_padded
    prog = vm.compile_policy(_champion(), view, c.g_padded)
    stacked = vm.stack_programs([prog] * lanes)
    return compile_for(device,
                       flat.make_population_run_fn(wl, vm.score, cfg),
                       stacked, flat.initial_state(wl, cfg))


def whatif(device, cluster: str, lanes: int) -> str:
    from fks_tpu.serve import ChampionSpec, VMServeEngine
    from fks_tpu.sim.engine import shape_prefilter_k

    wl = _workload(cluster)
    eng = VMServeEngine(
        ChampionSpec(code=_champion()), wl,
        engine="exact", prefilter_k=shape_prefilter_k(wl.cluster.n_padded))
    bucket = 256
    example = (eng._prog_dev,) + eng._example_batch(lanes, bucket)
    return compile_for(device, eng._make_serve_fn(bucket), *example,
                       donate_argnums=(1, 3))


EXECUTABLES = {"population": (population, "16", 256),
               "codegen": (codegen, "16", 8),
               "whatif": (whatif, "1523", 2)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("executable", choices=sorted(EXECUTABLES))
    ap.add_argument("--describe", default="v5e:2x2", metavar="TOPOLOGY")
    ap.add_argument("--cluster", choices=("16", "1523", "1523-gpuspec25"))
    ap.add_argument("--lanes", type=int)
    ap.add_argument("--hlo", metavar="FILE",
                    help="also write the compiled module's text here")
    args = ap.parse_args(argv)

    device = topology_device(args.describe)
    if device is None:
        print(f"no TPU compiler here can describe {args.describe}",
              file=sys.stderr)
        return 3
    build, cluster, lanes = EXECUTABLES[args.executable]
    hlo = build(device, args.cluster or cluster, args.lanes or lanes)
    if args.hlo:
        with open(args.hlo, "w") as f:
            f.write(hlo)
    loops = slot_loops(hlo)
    for title, rows in [("event loop", loop_body(hlo))] + [
            ("op-slot loop" + f" {i} of {len(loops)}" * (len(loops) > 1), rows)
            for i, rows in enumerate(loops, 1)]:
        for r in sorted(rows, key=lambda r: -r["cycles"]):
            print(f"{r['cycles']:>9} {r['op']:<12} {r['name']:<34} "
                  f"{r['result'][:150]}")
        ops = collections.Counter(r["op"] for r in rows)
        total = sum(r["cycles"] for r in rows)
        print(f"# {args.executable} on {device.device_kind}: {len(rows)} "
              f"instructions in the {title}'s body ("
              + ", ".join(f"{v} {k}" for k, v in ops.most_common())
              + f"); estimated_cycles x iteration_bounds {total}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
