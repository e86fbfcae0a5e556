"""Plain reference for a what-if QUERY whose pods name the GPU models they
accept, forked from a pinned snapshot of a typed cluster
(``openb1523-gpuspec25-loaded-snapshot``).

*A query pod may carry ``gpu_spec``: a string of GPU model names joined by
``|`` (a list of strings is the same set; absent or empty allows every
node; a repeated name means nothing; a name no node of the cluster has
allows nothing). A query pod with a non-empty ``gpu_spec`` may be placed
only on a node whose ``model`` is in the set; every other node is to it
as a cordoned node is. The residents keep the words the trace gave them
and stay where the snapshot put them. A forked query's run is the run of
``residents ++ query pods`` in which the snapshot decides the first E0
events and the policy every later one.*

``forked_query.inputs`` makes that order and keys the snapshot's rows by
it; ``inputs`` here applies the same order to ``allowed``: a resident's
row is ``plain_sim_gpuspec.load_allowed``'s, read from the trace; **a
query pod's row is made from the ``gpu_spec`` the service was SENT**
(``allowed_row``: split on ``|``, matched against the node list's
``model`` column by this module's own read), so a field lost between the
request and the engine shows as a difference. The loop is
``plain_sim_gpuspec.simulate_from``, which takes a log of CREATE attempts
and not the loaded snapshot's ``{pod: (node, GPUs)}``: ``log_of`` writes
the one as the other (the residents are the first E0 positions, in event
order, every one placed) and nothing of that module is edited. The
waiting set is read through ``decide`` as ``forked_query.simulate_query``
reads it. With every ``gpu_spec`` empty the run equals
``forked_query.simulate_query`` field for field
(``tests/test_serve_fork.py``). It runs FREE after the fork: it never
sees what the program placed. Nothing of ``fks_tpu`` is imported.
"""
from __future__ import annotations

from typing import List, Sequence, Tuple

import numpy as np

from chipbench.reference import forked_query
from chipbench.reference.data import _rows
from chipbench.reference.forked_query import of_query  # noqa: F401
from chipbench.reference.plain_sim import Cluster, Pods, Result
from chipbench.reference.plain_sim_gpuspec import simulate_from, validate
from chipbench.reference.plain_sim_loaded import Rows
from chipbench.reference.plain_sim_midrun import Log


def node_models(cluster_csv: str) -> List[str]:
    """The node list's ``model`` column, in row order (empty: none)."""
    return [r.get("model") or "" for r in _rows(cluster_csv)]


def allowed_row(spec, models: Sequence[str]) -> np.ndarray:
    """bool[N]: the nodes a pod that was sent ``spec`` may take."""
    if not isinstance(spec, str):
        spec = "|".join(spec or ())
    names = set(filter(None, spec.split("|")))
    return np.array([not names or (bool(m) and m in names) for m in models],
                    bool)


def inputs(pods: Pods, rows: Rows, allowed: np.ndarray,
           query: Sequence[int], specs: Sequence, models: Sequence[str]
           ) -> Tuple[Pods, Rows, np.ndarray]:
    """``forked_query.inputs`` and ``allowed`` in the same order: the
    residents' rows of ``allowed`` (``load_allowed``, keyed like ``pods``)
    in arrival order, then one row a query pod from ``specs``, what the
    service was sent for it."""
    taken, keyed = forked_query.inputs(pods, rows, query)
    residents = sorted(rows, key=lambda i: (int(pods.creation_time[i]),
                                            int(pods.rank[i])))
    if len(specs) != len(query):
        raise ValueError(f"{len(query)} query pods, {len(specs)} gpu_specs")
    sent = [allowed_row(s, models) for s in specs]
    return taken, keyed, np.array(
        [allowed[i] for i in residents] + sent, bool).reshape(
            taken.p, allowed.shape[1])


def log_of(rows: Rows) -> Log:
    """The loaded snapshot's rows, keyed by position in ``residents ++
    query``, as the log ``plain_sim_gpuspec`` replays: attempt ``n`` is
    resident ``n``'s CREATE, placed; no refusal, so no rule is named."""
    return Log([(n, *rows[n]) for n in range(len(rows))], len(rows), "")


def validate_snapshot(cluster: Cluster, pods: Pods, rows: Rows,
                      allowed: np.ndarray, retry: str) -> Result:
    """``plain_sim_gpuspec.validate`` of the residents alone: the rows are
    the first E0 events of the typed workload's run and put no pod on a
    node it may not take."""
    taken, keyed, ok = inputs(pods, rows, allowed, (), (), ())
    return validate(cluster, taken, ok, log_of(keyed), retry)


def simulate_query(cluster: Cluster, pods: Pods, rows: Rows,
                   allowed: np.ndarray, policy, *, max_steps: int,
                   prefilter_k: int = 0, retry: str = "heap_array",
                   decide=None, **kw) -> Tuple[Result, List[int]]:
    """``forked_query.simulate_query`` under the type rule, on what
    ``inputs`` made: the run and the query's pods that wait at the end,
    by their position in the query."""
    e0 = len(rows)
    placed: dict = {}          # pod -> did its last decision place it

    def watch(i, cand, scores):
        k = int(np.argmax(scores)) if decide is None \
            else int(decide(i, cand, scores))
        placed[int(i)] = bool(scores[k] > 0)
        return k

    ref = simulate_from(cluster, pods, allowed, log_of(rows), policy,
                        retry=retry, max_steps=max_steps,
                        prefilter_k=prefilter_k, decide=watch, **kw)
    waiting = sorted(i - e0 for i, ok in placed.items()
                     if not ok and i >= e0)
    return ref, waiting
