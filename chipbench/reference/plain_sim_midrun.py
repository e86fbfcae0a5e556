"""Plain reference for a run forked from a snapshot of a moment of a real
run (``openb16-cpu250-midrun``): the first ``E0`` events hold departures,
refused placements and retries, not arrivals alone.

*A snapshot of a workload under a retry rule is E0 and the decision of
every CREATE attempt among the first E0 events of a run, in event order:
the pod, and either its node and GPU set or "no node". Which event comes
when follows from the workload, the rule and those decisions. Evaluating
a policy from the snapshot is the run of the workload in which the first
E0 events are decided by the snapshot and every later one by the policy.*

``plain_sim.simulate`` already runs the whole trace from event 0 and
makes every event of it from its policy's decisions, so no loop is
written again here: ``simulate_from`` hands it a policy that answers the
first ``len(log)`` CREATE attempts from the log (one positive score on
the logged node, none for "no node"; it checks itself that the attempt is
the logged pod's and the placement is feasible on what the earlier events
left) and the free policy from then on. The departures, the retries at
``1 +`` the earliest pending DELETE, the waiting set, the fragmentation
and utilization sums and every counter of the prefix are then whatever
the plain loop makes of those decisions, and all a ``Result`` reports is a
quantity of the whole run. The GPUs of a logged placement are the loop's
own best-fit pick, compared with the log's at the end. ``validate`` holds
the log to its ``E0``: run to ``max_steps = E0`` it is used up exactly.
It runs FREE after the fork: it never sees what the program placed.

The file is read with the reference's own CSV reader, independently of
``fks_tpu.data``: rows ``name,node_sn,gpus,event,rule`` keyed by the pod
list's names and the node list's ``sn``; ``gpus`` the node's GPU slots
joined by ``|``, an empty ``node_sn`` "no node", ``event`` the attempt's
index among the run's events, and a last row with an empty name that
holds ``E0`` under ``event`` and the retry rule under ``rule``.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple

import numpy as np

from chipbench.reference.data import _rows
from chipbench.reference.plain_sim import (
    Cluster, Pods, Result, _feasible, simulate)


@dataclasses.dataclass
class Log:
    """The CREATE attempts in event order: (pod, node or -1, GPU
    bitmask); where the log ends; the rule it was made under."""

    attempts: List[Tuple[int, int, int]]
    e0: int
    rule: str


def load_log(snapshot_csv: str, cluster_csv: str, trace_csv: str) -> Log:
    """The snapshot's rows against the names of the two CSVs it is keyed
    by; an unknown name, or a file without its last row, is an error."""
    pod_of = {r["name"]: i for i, r in enumerate(_rows(trace_csv))}
    node_of = {r["sn"]: i for i, r in enumerate(_rows(cluster_csv))}
    at, end = [], None
    for r in _rows(snapshot_csv):
        if not r["name"]:
            end = (int(r["event"]), r["rule"])
            continue
        bits = 0
        for slot in filter(None, (r.get("gpus") or "").split("|")):
            bits |= 1 << int(slot)
        at.append((int(r["event"]), pod_of[r["name"]],
                   node_of[r["node_sn"]] if r["node_sn"] else -1, bits))
    if end is None:
        raise ValueError("the snapshot has no last row that says where "
                         "its log ends")
    at.sort()
    if len({e for e, *_ in at}) != len(at) or (at and at[-1][0] >= end[0]):
        raise ValueError("the snapshot's attempts are not one an event, "
                         f"below event {end[0]}")
    return Log([a[1:] for a in at], *end)


def _decided_by(log: Log, policy, decide):
    """(policy, decide) for ``plain_sim.simulate``: the log's answers for
    its attempts, then ``policy``; ``decide`` (``nearties.admit``'s) sees
    every decision, the logged ones too, so a program that moved a
    resident parts from the reference there and nothing is admitted."""
    calls = [0]

    def scores(pod, s, cand):
        n = calls[0]
        calls[0] += 1
        if n >= len(log.attempts):
            return policy(pod, s, cand)
        _, node, _ = log.attempts[n]
        out = np.zeros(len(cand), np.int64)
        if node >= 0:
            if not _feasible(pod, s)[node]:
                raise ValueError(f"the snapshot's attempt {n} puts a pod "
                                 f"on node {node}, which cannot hold it")
            out[np.nonzero(cand == node)[0]] = 1
        return out

    def winner(i, cand, sc):
        n = calls[0] - 1
        if n < len(log.attempts) and log.attempts[n][0] != i:
            raise ValueError(
                f"the snapshot's attempt {n} is pod {log.attempts[n][0]}'s "
                f"and the run meets pod {i} there")
        return int(np.argmax(sc)) if decide is None else decide(i, cand, sc)

    return scores, winner, calls


def _check_gpus(log: Log, res: Result) -> None:
    for n, (i, node, bits) in enumerate(log.attempts):
        if node >= 0 and (res.assigned_node[i], res.assigned_gpus[i]) \
                != (node, bits):
            raise ValueError(
                f"the snapshot's attempt {n} gives pod {i} GPUs {bits:#b} "
                f"of node {node}; best-fit picks "
                f"{int(res.assigned_gpus[i]):#b} there")


def validate(cluster: Cluster, pods: Pods, log: Log, retry: str) -> Result:
    """The log is a snapshot of this workload under ``retry``: the run of
    its decisions alone reaches event ``E0`` with every attempt used and
    none missing. Returns that run (the state at the fork)."""
    refused = any(node < 0 for _, node, _ in log.attempts)
    if (log.rule or refused) and log.rule != retry:
        # a log with no refusal names no rule: nothing was re-queued
        raise ValueError(f"the snapshot was made under the retry rule "
                         f"{log.rule!r}, the run is under {retry!r}")

    def none_left(pod, s, cand):
        raise ValueError(f"a CREATE attempt among the first {log.e0} "
                         "events is not in the snapshot's log")

    scores, winner, calls = _decided_by(log, none_left, None)
    res = simulate(cluster, pods, scores, retry=retry, max_steps=log.e0,
                   decide=winner)
    if res.steps != log.e0 or res.failed or calls[0] != len(log.attempts):
        raise ValueError(
            f"the snapshot's log does not end at event {log.e0}: the run "
            f"of its decisions made {res.steps} events and used "
            f"{calls[0]} of {len(log.attempts)} attempts")
    _check_gpus(log, res)
    return res


def simulate_from(cluster: Cluster, pods: Pods, log: Log, policy, *,
                  retry: str = "earliest_delete",
                  max_steps: Optional[int] = None, prefilter_k: int = 0,
                  interval: float = 0.05, acc_dtype=np.float32,
                  decide=None) -> Result:
    """``plain_sim.simulate`` with the log (``validate``d by the caller)
    deciding its attempts and ``policy`` every later one. ``max_steps``
    is absolute: the prefix counts."""
    scores, winner, _ = _decided_by(log, policy, decide)
    res = simulate(cluster, pods, scores, retry=retry, max_steps=max_steps,
                   prefilter_k=prefilter_k, interval=interval,
                   acc_dtype=acc_dtype, decide=winner)
    _check_gpus(log, res)
    return res
