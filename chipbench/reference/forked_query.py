"""Plain reference for a what-if QUERY forked from a pinned snapshot
(``openb1523-loaded-snapshot``): what ``plain_sim_loaded.simulate_from``
needs around it and does not hold.

*A forked query's run is the run of ``residents ++ query pods`` in which
the snapshot decides the first E0 events and the policy every later one.*
``simulate_from`` is that run once it is handed the pods in that order and
the snapshot's rows keyed by their new positions; this module makes both
from the benchmark's own parse (``inputs``), and reads off the run what a
query's answer says beyond a ``Result``: which of the QUERY's pods wait
at the end (``simulate_query``). A pod waits when the last decision made
for it placed it nowhere: upstream's waiting set, which a pod joins at a
failed placement and leaves when a retry places it. ``Result`` does not
carry the set and ``plain_sim_loaded`` is not edited, so it is read
through the ``decide`` hook, which sees every decision and its scores.

The residents keep their order among themselves (the rank of their names,
made dense); a query's pods are named by their ordinal, as the service
names them, so they rank after every resident and among themselves by
position. Nothing of ``fks_tpu`` is imported.
"""
from __future__ import annotations

import dataclasses
from typing import List, Sequence, Tuple

import numpy as np

from chipbench.reference.plain_sim import Cluster, Pods, Result
from chipbench.reference.plain_sim_loaded import Rows, simulate_from


def inputs(pods: Pods, rows: Rows, query: Sequence[int]) -> Tuple[Pods, Rows]:
    """(``residents ++ query`` as ``Pods``, the snapshot's rows keyed by
    position in it). ``rows`` is keyed by index into ``pods``; the
    residents go first, in arrival order; ``query`` lists the query's
    rows of ``pods`` in the order the service is given them."""
    residents = sorted(rows, key=lambda i: (int(pods.creation_time[i]),
                                            int(pods.rank[i])))
    e0 = len(residents)
    taken = pods.take(list(residents) + [int(i) for i in query])
    rank = np.empty(taken.p, np.int64)
    rank[np.argsort(taken.rank[:e0], kind="stable")] = np.arange(e0)
    rank[e0:] = e0 + np.arange(taken.p - e0)
    taken.rank = rank
    return taken, {n: rows[i] for n, i in enumerate(residents)}


def simulate_query(cluster: Cluster, pods: Pods, rows: Rows, policy, *,
                   max_steps: int, prefilter_k: int = 0,
                   retry: str = "heap_array", decide=None, **kw
                   ) -> Tuple[Result, List[int]]:
    """``simulate_from`` on what ``inputs`` made, and the query's pods
    that wait at the end, by their position in the query. ``max_steps`` is
    absolute (the residents' events count); ``decide`` as
    ``plain_sim.simulate`` takes it (``nearties.admit`` passes one);
    ``kw`` goes on to ``simulate_from`` (the control's ``acc_dtype``)."""
    e0 = len(rows)
    placed: dict = {}          # pod -> did its last decision place it

    def watch(i, cand, scores):
        k = int(np.argmax(scores)) if decide is None \
            else int(decide(i, cand, scores))
        placed[int(i)] = bool(scores[k] > 0)
        return k

    ref = simulate_from(cluster, pods, rows, policy, retry=retry,
                        max_steps=max_steps, prefilter_k=prefilter_k,
                        decide=watch, **kw)
    waiting = sorted(i - e0 for i, ok in placed.items()
                     if not ok and i >= e0)
    return ref, waiting


def of_query(ref: Result, e0: int) -> Result:
    """``ref`` with the per-pod arrays cut to the query's pods (an answer
    lists those only); every count stays the whole run's."""
    return dataclasses.replace(ref, assigned_node=ref.assigned_node[e0:],
                               assigned_gpus=ref.assigned_gpus[e0:])
