"""Plain reference for a what-if QUERY forked from a moment of a real run
(``openb16-cpu250-midrun-snapshot``): what ``plain_sim_fork.simulate``
needs around it and does not hold.

*A snapshot says what happened: the first E0 events of the run, timed by
the rule the snapshot names. A forked query's run is the run of ``base
pods ++ query pods`` in which those E0 events happen as logged and every
later event is the engine's own: the champion decides each CREATE attempt
and upstream's ``heap_array`` rule re-queues each refusal.* The base is
every pod with an attempt in the log, once, in first-attempt order (after
12,288 events of cpu250: 5,669 pods, 5,618 of them gone); its pods keep
their order among themselves (the rank of their names, made dense); a
query's pods are named by their ordinal, as the service names them, so
they rank after every base pod and among themselves by position. The
heap at the fork is then what CPython's ``heapq`` holds after ``heapify``
of all CREATEs (base and query, in that order) and the E0 logged pops and
pushes: it depends on the query, so the whole run is made anew for each.

``inputs`` makes the pods and the re-keyed log from the benchmark's own
parse (``plain_sim_midrun.load_log`` reads the snapshot with the
reference's own CSV reader); ``simulate_query`` runs them and says which
of the QUERY's pods wait at the end. ``decide`` (``nearties.admit``'s) is
asked about the query's pods alone, by their position in the query: an
answer lists no other pod, so only there is the program's node known.
Nothing of ``fks_tpu`` is imported.
"""
from __future__ import annotations

import dataclasses
from typing import List, Sequence, Tuple

import numpy as np

from chipbench.reference import plain_sim_fork
from chipbench.reference.plain_sim import Cluster, Pods, Result
from chipbench.reference.plain_sim_midrun import Log


def base_of(log: Log) -> List[int]:
    """The pods with an attempt in the log, once, in first-attempt
    order."""
    return list(dict.fromkeys(i for i, _, _ in log.attempts))


def inputs(pods: Pods, log: Log, query: Sequence[int]) -> Tuple[Pods, Log]:
    """(``base ++ query`` as ``Pods``, the log keyed by position in it).
    ``log`` is keyed by index into ``pods``; ``query`` lists the query's
    rows of ``pods`` in the order the service is given them."""
    base = base_of(log)
    at = {i: n for n, i in enumerate(base)}
    taken = pods.take(base + [int(i) for i in query])
    rank = np.empty(taken.p, np.int64)
    rank[np.argsort(taken.rank[:len(base)], kind="stable")] = \
        np.arange(len(base))
    rank[len(base):] = len(base) + np.arange(taken.p - len(base))
    taken.rank = rank
    return taken, Log([(at[i], node, bits) for i, node, bits in log.attempts],
                      log.e0, log.rule)


def simulate_query(cluster: Cluster, pods: Pods, log: Log, policy, *,
                   max_steps: int, prefilter_k: int = 0,
                   retry: str = "heap_array", decide=None, **kw
                   ) -> Tuple[Result, List[int]]:
    """``plain_sim_fork.simulate`` on what ``inputs`` made, and the
    query's pods that wait at the end, by their position in the query.
    ``max_steps`` is absolute (the prefix's events count); ``kw`` goes on
    to ``simulate`` (the control's ``acc_dtype``, a test's ``at_fork``)."""
    base = len(base_of(log))

    def of_query(i, cand, scores):
        if decide is None or i < base:
            return int(np.argmax(scores))
        return decide(i - base, cand, scores)

    ref, waiting = plain_sim_fork.simulate(
        cluster, pods, log, policy, retry=retry, max_steps=max_steps,
        prefilter_k=prefilter_k, decide=of_query, **kw)
    return ref, np.flatnonzero(waiting[base:]).tolist()


def of_query(ref: Result, base: int) -> Result:
    """``ref`` with the per-pod arrays cut to the query's pods (an answer
    lists those only); every count stays the whole run's."""
    return dataclasses.replace(ref, assigned_node=ref.assigned_node[base:],
                               assigned_gpus=ref.assigned_gpus[base:])


def finished(ref: Result) -> bool:
    """Did the run end with an empty heap inside its budget?"""
    return not (ref.truncated or ref.failed)
