"""A decision that one unit of a score decides, and what the comparison
does with it.

A source policy's score is ``int(10,000 x a weighted sum of ratios)``.
The configurations state float32 scores, and the plain reference rounds
every operation to float32 as NumPy does (IEEE, to nearest). The chip
does not: its float32 divide is not correctly rounded, so one score in
some 130,000 (1,523 nodes) to 2,000 (16 nodes) is the reference's plus
or minus ONE (``python3 -m chipbench.selftest.score_parity`` on the
v5e; PERF.md section 6, PR 33), and no arithmetic written without the
chip is the chip's bit for bit. Where the reference's two best nodes lie
within that one unit, which of them wins is no property of the program:
upstream's float64 parts from float32 there as well (seed 451715641 of
``openb1523-loaded.codegen8``, lane 1: one such decision in 1,024, and
311 placements after it).

So the reference still runs free, and the comparison is still exact, but
the FIRST decision at which the program's run leaves the reference's is
looked at: if the reference itself scores the program's node within
``score_near_tie_units`` of its own best (and above 0), the reference
takes that node there, runs on free from it, and the decision is counted
(``near_ties_admitted``, held to the configuration's
``near_ties_per_run``). Any other difference stays one: a node the
reference scores 2 below its best, a lower precision (bfloat16 parts by
tens of units at the first decision it moves). What is within a unit at
EVERY decision fails the count: another rule for ties among equal nodes
(upstream's is the lowest index), or scores a unit off everywhere, need
an admission every few decisions.
"""
from __future__ import annotations

from typing import Callable, Tuple

import numpy as np

from chipbench.reference.compare import Number


def admit(run: Callable, got_nodes, guarantees: dict, tag: str
          ) -> Tuple[object, Number]:
    """``run(decide)`` is the plain reference's run of one lane or query
    with the ``decide`` hook of ``plain_sim.simulate``; ``got_nodes`` the
    node the program gave each of its pods (-1: none). Returns the
    reference's ``Result``, free but for the admitted decisions, and how
    many there were beside their limit."""
    unit = int(guarantees.get("score_near_tie_units", 0))
    most = int(guarantees.get("near_ties_per_run", 0))
    got = np.asarray(got_nodes, np.int64)
    forced: dict = {}          # the n-th decision of the run -> its node
    while True:
        parted, count = [], [0]

        def decide(i, cand, scores):
            n = count[0]
            count[0] += 1
            if n in forced:
                return int(np.nonzero(cand == forced[n])[0][0])
            k = int(np.argmax(scores))
            if not parted and scores[k] > 0 and i < len(got) \
                    and cand[k] != got[i]:
                at = np.nonzero(cand == got[i])[0]
                parted.append((n, int(got[i]),
                               int(scores[at[0]]) if len(at) else 0,
                               int(scores[k])))
            return k

        ref = run(decide)
        # one past the limit is still taken, so that the count reads over
        # its limit and the rest of the lane is still compared
        if not parted or unit <= 0 or len(forced) > most:
            break
        n, node, score, best = parted[0]
        if score < max(1, best - unit):
            break               # no near tie: the difference stands
        forced[n] = node
    return ref, Number(f"{tag}.near_ties_admitted", float(len(forced)),
                       float(most), row="admitted")
