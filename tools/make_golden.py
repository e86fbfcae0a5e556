#!/usr/bin/env python3
"""Generate ground-truth fixtures into tests/fixtures/*.json.

Two fixture families:

- **Reference fixtures** (default mode): import the reference
  implementation (read-only, at /root/reference) and record its observable
  behavior. These are the parity bar for the TPU-native framework (fitness
  to 1e-5, exact event counts). No reference code is copied; we only
  execute it and record outputs. Reference entry points exercised:
    - benchmarks/parser.py TraceParser.parse_workload
    - simulator/main.py KubernetesSimulator.run_schedule
    - simulator/evaluator.py SchedulingEvaluator.get_policy_score
    - tests/test_scheduler.py policy zoo (imported as module)

- **Scenario-fault fixture** (``--scenario-fault``): the reference has no
  fault vocabulary (NODE_DOWN/NODE_UP cordon events are a fks_tpu.scenarios
  extension), so this fixture is pinned from the repo's OWN exact engine —
  the bit-replica of the reference event loop — on a deterministic
  fault-injected scenario. It is a regression pin, not reference parity:
  tests/test_scenarios.py replays the scenario through the exact AND flat
  engines and holds both to the recorded scores (<= 1e-5), so any future
  change to fault semantics that shifts fitness must come with a
  regenerated fixture.
"""
import argparse
import copy
import json
import os
import sys

REF = "/root/reference"
OUT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                   "..", "tests", "fixtures")

# scenario-fault fixture recipe (everything the test needs to rebuild the
# exact same workload + scenario from seeds alone). The spec is chosen so
# the cordon windows REROUTE ~half the placements (the pinned assignment
# vector is fault-sensitive) without forcing retries — retry semantics are
# the flat engine's one documented divergence, and this fixture gates
# BOTH engines to 1e-5.
FAULT_WORKLOAD = {"num_nodes": 4, "num_pods": 60, "seed": 7}
FAULT_SPEC = {"name": "golden_fault", "seed": 42, "fault_nodes": 3,
              "fault_start_frac": 0.3, "fault_duration_frac": 0.4,
              "demand_scale": 1.2}
FAULT_POLICIES = ("first_fit", "best_fit")


def _load_reference():
    """Import the reference implementation (module-level state: sys.path +
    cwd, as its TraceParser uses relative paths). Lazy so --scenario-fault
    works in containers without /root/reference."""
    sys.path.insert(0, REF)
    sys.path.insert(0, os.path.join(REF, "tests"))
    os.chdir(REF)
    from benchmarks.parser import TraceParser
    from simulator.event_simulator import DiscreteEventSimulator
    from simulator.evaluator import SchedulingEvaluator
    from simulator.main import KubernetesSimulator
    import test_scheduler as zoo
    import test_simulator as micro
    return (TraceParser, DiscreteEventSimulator, SchedulingEvaluator,
            KubernetesSimulator, zoo, micro)


def make_run_policy(DiscreteEventSimulator, SchedulingEvaluator,
                    KubernetesSimulator):
    def run_policy(cluster, pods, policy, with_eval=True):
        cluster = copy.deepcopy(cluster)
        pods = copy.deepcopy(pods)
        node_index = {nid: i for i, nid in enumerate(cluster.nodes_dict)}
        ev = DiscreteEventSimulator(pods)
        evaluator = (SchedulingEvaluator(cluster, enabled=True)
                     if with_eval else None)
        sim = KubernetesSimulator(cluster, pods, ev, policy,
                                  evaluator=evaluator)
        sim.run_schedule()
        out = {
            "scheduled_pods": sum(1 for p in pods if p.assigned_node != ""),
            "max_nodes": sim.max_nodes,
            "assignments": [node_index.get(p.assigned_node, -1) for p in pods],
            "assigned_gpus": [sorted(p.assigned_gpus) for p in pods],
            "final_creation_time": [p.creation_time for p in pods],
            "final_cpu_left": [n.cpu_milli_left
                               for n in cluster.nodes_dict.values()],
            "final_mem_left": [n.memory_mib_left
                               for n in cluster.nodes_dict.values()],
            "final_gpu_left": [n.gpu_left
                               for n in cluster.nodes_dict.values()],
            "final_gpu_milli_left": [[g.gpu_milli_left for g in n.gpus]
                                     for n in cluster.nodes_dict.values()],
        }
        if with_eval:
            res = evaluator.get_evaluation_results()
            out.update({
                "policy_score": evaluator.get_policy_score(pods),
                "avg_cpu_utilization": res.avg_cpu_utilization,
                "avg_memory_utilization": res.avg_memory_utilization,
                "avg_gpu_count_utilization": res.avg_gpu_count_utilization,
                "avg_gpu_memory_utilization": res.avg_gpu_memory_utilization,
                "gpu_fragmentation_score": res.gpu_fragmentation_score,
                "num_snapshots": res.num_snapshots,
                "num_fragmentation_events": res.num_fragmentation_events,
                "events_processed": evaluator.events_processed,
                "snapshots": [
                    [s.cpu_utilization, s.memory_utilization,
                     s.gpu_count_utilization, s.gpu_memory_utilization,
                     s.event_progress]
                    for s in evaluator.utilization_snapshots
                ],
                "fragmentation_events": evaluator.fragmentation_events,
            })
        return out
    return run_policy


def make_reference_fixtures():
    (TraceParser, DiscreteEventSimulator, SchedulingEvaluator,
     KubernetesSimulator, zoo, micro) = _load_reference()
    run_policy = make_run_policy(DiscreteEventSimulator, SchedulingEvaluator,
                                 KubernetesSimulator)
    os.makedirs(OUT, exist_ok=True)
    parser = TraceParser()

    policies = {
        "first_fit": zoo.first_fit_scheduler,
        "best_fit": zoo.best_fit_scheduler,
        "funsearch_4901": zoo.funsearch_4901_scheduler,
        "funsearch_4816": zoo.funsearch_4816_scheduler,
        "funsearch_4800": zoo.funsearch_4800_scheduler,
    }

    # 1. Default workload, all 5 zoo policies.
    cluster, pods = parser.parse_workload()
    golden = {"trace": {"node_file": "gpu_models_filtered.csv",
                        "pod_file": "openb_pod_list_default.csv",
                        "num_nodes": len(cluster.nodes_dict),
                        "num_pods": len(pods)},
              "policies": {}}
    for name, fn in policies.items():
        print(f"running {name}...", flush=True)
        golden["policies"][name] = run_policy(cluster, pods, fn)
        print(f"  score={golden['policies'][name]['policy_score']:.6f} "
              f"snaps={golden['policies'][name]['num_snapshots']}")
    with open(os.path.join(OUT, "golden_default.json"), "w") as f:
        json.dump(golden, f)

    # 2. Alternate traces with best_fit + first_fit (robustness).
    alt = {}
    # NOTE: the multigpu* traces lack the gpu_spec/creation_time columns and
    # the reference parser raises KeyError on them -- excluded (no parity
    # obligation).
    for pod_file in ["openb_pod_list_gpushare40.csv",
                     "openb_pod_list_gpuspec33.csv",
                     "openb_pod_list_cpu250.csv"]:
        cluster2, pods2 = parser.parse_workload(pod_file=pod_file)
        alt[pod_file] = {}
        for name in ["first_fit", "best_fit"]:
            print(f"running {name} on {pod_file}...", flush=True)
            alt[pod_file][name] = run_policy(cluster2, pods2, policies[name])
    with open(os.path.join(OUT, "golden_alt_traces.json"), "w") as f:
        json.dump(alt, f)

    # 3. Micro scenario (test_simulator.py): 2 nodes, 4 pods, no evaluator.
    mc = micro.create_test_cluster()
    mp = micro.create_test_pods()
    m = run_policy(mc, mp, micro.best_fit_scheduler, with_eval=False)
    m["pods"] = [
        {"pod_id": p.pod_id, "cpu_milli": p.cpu_milli,
         "memory_mib": p.memory_mib, "num_gpu": p.num_gpu,
         "gpu_milli": p.gpu_milli, "creation_time": p.creation_time,
         "duration_time": p.duration_time}
        for p in micro.create_test_pods()
    ]
    with open(os.path.join(OUT, "golden_micro.json"), "w") as f:
        json.dump(m, f)

    print("fixtures written to", OUT)


def make_scenario_fault_fixture():
    """Pin the exact engine's behavior on a deterministic fault-injected
    scenario (see module docstring: a regression pin from the repo's own
    reference-replica engine, consumed by tests/test_scenarios.py)."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), ".."))
    import numpy as np

    from fks_tpu.data.synthetic import synthetic_workload
    from fks_tpu.models import zoo
    from fks_tpu.funsearch import tracing
    from fks_tpu.scenarios import ScenarioSpec, perturb_workload
    from fks_tpu.sim.engine import SimConfig

    wl = synthetic_workload(FAULT_WORKLOAD["num_nodes"],
                            FAULT_WORKLOAD["num_pods"],
                            seed=FAULT_WORKLOAD["seed"])
    spec = ScenarioSpec(**FAULT_SPEC)
    swl = perturb_workload(wl, spec)
    fe = swl.faults
    m = np.asarray(fe.mask)
    fixture = {
        "workload": dict(FAULT_WORKLOAD),
        "spec": spec.describe(),
        "fault_timeline": [
            {"time": int(t), "node": int(nd), "kind": int(k)}
            for t, nd, k in zip(np.asarray(fe.time)[m],
                                np.asarray(fe.node)[m],
                                np.asarray(fe.kind)[m])],
        "policies": {},
    }
    cfg = SimConfig()
    for name in FAULT_POLICIES:
        pol = zoo.ZOO[name]()
        res = tracing.replay(swl, "exact",
                             lambda _p, pod, nodes: pol(pod, nodes),
                             None, cfg)
        rows = tracing.extract_trace(res)
        fixture["policies"][name] = {
            "policy_score": float(res.policy_score),
            "scheduled_pods": int(res.scheduled_pods),
            "events_processed": int(res.events_processed),
            "num_snapshots": int(res.num_snapshots),
            "max_nodes": int(res.max_nodes),
            # Placement vector: the aggregate score is invariant to WHICH
            # node hosts a pod, so the per-CREATE [pod, node] sequence is
            # the fixture's actual fault-sensitivity evidence (the cordon
            # reroutes ~half of these relative to a no-fault run).
            "assignments": [[r["pod"], r["node"]] for r in rows
                            if r["kind"] == "CREATE"],
            "fault_rows": sum(1 for r in rows
                              if r["kind"] in ("NODE_DOWN", "NODE_UP")),
        }
        print(f"{name}: score={fixture['policies'][name]['policy_score']:.6f}"
              f" scheduled={fixture['policies'][name]['scheduled_pods']}"
              f" fault_rows={fixture['policies'][name]['fault_rows']}",
              flush=True)
    os.makedirs(OUT, exist_ok=True)
    path = os.path.join(OUT, "golden_scenario_fault.json")
    with open(path, "w") as f:
        json.dump(fixture, f, indent=1)
    print("fixture written to", path)


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--scenario-fault", action="store_true",
                    help="write tests/fixtures/golden_scenario_fault.json "
                         "from the repo's own exact engine (no reference "
                         "checkout needed)")
    args = ap.parse_args()
    if args.scenario_fault:
        make_scenario_fault_fixture()
    else:
        make_reference_fixtures()


if __name__ == "__main__":
    main()
