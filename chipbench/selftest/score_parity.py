"""How closely the plain reference's arithmetic is the program's, read at
the SCORES and not at the placements they decide:

    python3 -m chipbench.selftest.score_parity --workload <code cell> \
        --seeds 1,2,3 [--lanes 2] [--every 16] [--cpu]

A placement moves only where a rounding decides an argmax, once in some
hundreds of lanes (seed 451715641 of ``openb1523-loaded.codegen8`` was
such a lane; PERF.md section 6, PR 33), so a dozen ``correct`` runs say
little of the arithmetic. This reads it directly; what it read on the
v5e is why ``chipbench/reference/nearties.py`` exists. For a seed's sources
the plain reference runs the cell's lane; at every ``--every``-th
decision the state is kept, and the lane's source scores EVERY node of
that state three ways: the reference in float32 (what the drivers
compare with), the reference in float64 (upstream's arithmetic) and the
program's batched-VM program (``vm.compile_policy`` + ``vm.score`` under
``jit``, on the machine's default device: the chip, or the CPU with
``--cpu``). It prints, per seed, the scores compared and how many differ
from the program's under each, and by how much at the most; the last
line sums them. On the CPU backend the float32 count is 0 (NumPy's and
XLA:CPU's float32 are both IEEE) and the float64 count is the control of
the reading: the instrument sees a rounding where there is one. On the
v5e neither is 0 (12 of 1,541,276 and 62 on 1,523 nodes, 12 of 25,312 and
6 on 16; my chip runs, PR 33): the chip's divide is not correctly
rounded. The exit code is 0 while no score is further from the float32
reference than the configuration's ``score_near_tie_units``. The
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys

import numpy as np

from chipbench import cells
from chipbench.drivers import common
from chipbench.reference import policies
from chipbench.reference.plain_sim import simulate


class _Keep(Exception):
    pass


def decisions(cell, files, code: str, every: int, dtype: str, rows):
    """(pod fields, cpu_left, mem_left, gpu_left, gpu_milli_left) at every
    ``every``-th decision the policy is asked for, from the fork on."""
    cluster, pods = common.reference_inputs(cell.config, files)
    inner = policies.source_policy(code, dtype=dtype)
    kept, calls = [], [0]

    def policy(pod, s, cand):
        calls[0] += 1
        if calls[0] % every == 0:
            kept.append(((pod.cpu_milli, pod.memory_mib, pod.num_gpu,
                          pod.gpu_milli, pod.creation_time,
                          pod.duration_time),
                         s.cpu_left.copy(), s.mem_left.copy(),
                         s.gpu_left.copy(), s.gpu_milli_left.copy()))
        return inner(pod, s, cand)

    kw = dict(retry=cell.config["retry_rule"],
              prefilter_k=int(cell.config.get("node_prefilter_k", 0)))
    cap = int(cell.config["code_eval_max_steps"])
    if rows is None:
        simulate(cluster, pods, policy, max_steps=cap, **kw)
    else:
        from chipbench.reference.plain_sim_loaded import simulate_from
        simulate_from(cluster, pods, rows, policy,
                      max_steps=len(rows) + cap, **kw)
    return cluster, kept


def reference_scores(cluster, code: str, dtype: str, kept) -> np.ndarray:
    """The source's score of every real node in each kept state."""
    from chipbench.reference.plain_sim import PodObj, State

    policy = policies.source_policy(code, dtype=dtype)
    s, pod = State(cluster), PodObj()
    every = np.arange(cluster.n)
    out = []
    for fields, cpu, mem, gpu, milli in kept:
        for name, v in zip(PodObj.__slots__, fields):
            setattr(pod, name, int(v))
        for i, nd in enumerate(s.nodes):
            nd.cpu_milli_left, nd.memory_mib_left = int(cpu[i]), int(mem[i])
            nd.gpu_left = int(gpu[i])
            for j, g in enumerate(nd.gpus):
                g.gpu_milli_left = int(milli[i, j])
        out.append(policy(pod, s, every))
    return np.asarray(out, np.int64)


def program_scores(wl, code: str, kept) -> np.ndarray:
    import jax
    import jax.numpy as jnp
    from fks_tpu.funsearch import vm
    from fks_tpu.sim.types import NodeView, PodView

    c = wl.cluster
    n, npad = int(np.asarray(c.node_mask).sum()), int(c.n_padded)
    prog = vm.compile_for_workload(code, wl)
    score = jax.jit(lambda pod, nodes: vm.score(prog, pod, nodes))

    def pad(x):
        x = np.asarray(x, np.int32)
        out = np.zeros((npad,) + x.shape[1:], np.int32)
        out[:n] = x
        return jnp.asarray(out)

    out = []
    for fields, cpu, mem, gpu, milli in kept:
        g = np.zeros((n, int(c.g_padded)), np.int32)
        g[:, :milli.shape[1]] = milli
        nodes = NodeView(
            cpu_milli_left=pad(cpu), cpu_milli_total=c.cpu_total,
            memory_mib_left=pad(mem), memory_mib_total=c.mem_total,
            gpu_left=pad(gpu), num_gpus=c.num_gpus,
            gpu_milli_left=pad(g), gpu_milli_total=c.gpu_milli_total,
            gpu_mem_total=c.gpu_mem_total, gpu_mask=c.gpu_mask,
            node_mask=c.node_mask)
        pod = PodView(*(jnp.int32(int(v)) for v in fields))
        out.append(np.asarray(score(pod, nodes))[:n])
    return np.asarray(out, np.int64)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--lanes", type=int, default=2,
                    help="lanes of a seed's generation that are read")
    ap.add_argument("--every", type=int, default=16)
    ap.add_argument("--cpu", action="store_true")
    a = ap.parse_args(argv)
    import jax
    if not a.cpu and jax.devices()[0].platform != "tpu":
        print("score_parity: no TPU (--cpu reads the CPU backend)",
              file=sys.stderr)
        return 3
    cell = cells.load_cell(a.workload)
    files = cells.verify_files(cell.config)
    total = {"scores": 0, "differ_float32": 0, "differ_float64": 0,
             "worst_float32": 0}
    for seed in (int(s) for s in a.seeds.split(",")):
        d = cells.load_driver(cell.traffic["driver"]).Driver(
            cell, seed, files, None, False)
        rows = None
        if "start_event" in cell.config:
            d.e0 = int(cell.config["start_event"])
            rows = d.rows()
        wl = d._workload() if rows is not None \
            else common.parse_workload(cell.config, files)
        # the champions, where the seed put them: the float-free seed
        # policies have nothing to round
        lanes = [i for i, src in enumerate(d._sources())
                 if "10000" in src][:a.lanes]
        row = {"seed": seed, "lanes": lanes, "scores": 0,
               "differ_float32": 0, "differ_float64": 0, "worst": {}}
        for lane in lanes:
            code = d._sources()[lane]
            cluster, kept = decisions(
                cell, files, code, a.every,
                cell.config["guarantees"]["score_dtype"], rows)
            got = program_scores(wl, code, kept)
            row["scores"] += int(got.size)
            for dtype in ("float32", "float64"):
                ref = reference_scores(cluster, code, dtype, kept)
                bad = ref != got
                row[f"differ_{dtype}"] += int(bad.sum())
                if bad.any():
                    row["worst"][dtype] = max(
                        row["worst"].get(dtype, 0),
                        int(np.abs(ref - got).max()))
        print(json.dumps(row), flush=True)
        for k in ("scores", "differ_float32", "differ_float64"):
            total[k] += row[k]
        total["worst_float32"] = max(total["worst_float32"],
                                     row["worst"].get("float32", 0))
    total["device"] = jax.devices()[0].platform
    print(json.dumps(total), flush=True)
    unit = int(cell.config["guarantees"]["score_near_tie_units"])
    return 0 if total["scores"] and total["worst_float32"] <= unit else 1


if __name__ == "__main__":
    sys.exit(main())
