"""The comparison that decides ``correct``: one program output (a lane, a
query's answer) against the plain reference's run of the same input.

Every number compared is returned beside its limit. The limits are the
configuration's guarantees (``chipbench/configs/<config>.json``,
``guarantees``): trajectories identical to the reference's FREE run (the
same node and the same GPUs for every pod, the same counts and flags:
exact, limit 0), and the fitness of a finished run within
``fitness_rtol``. A tie broken another way than upstream's (lowest node
index), a prefilter that keeps other nodes, a score in a lower precision:
each moves a placement and is a difference.
"""
from __future__ import annotations

import dataclasses
from typing import List

import numpy as np

from chipbench.reference.plain_sim import Result


@dataclasses.dataclass
class Number:
    name: str
    value: float
    limit: float
    #: the kind of row it is printed as; a count of admitted near ties
    #: (``nearties.admit``) is compared like any other, and says "admitted"
    row: str = "compared"

    @property
    def ok(self) -> bool:
        return bool(self.value <= self.limit)   # NaN fails


@dataclasses.dataclass
class Output:
    """What the program produced for one lane or query, real pods only."""

    assigned_node: np.ndarray   # node index, -1 = not placed
    assigned_gpus: np.ndarray   # bitmask of GPU slots
    scheduled: int
    events: int
    score: float
    failed: bool
    truncated: bool

    @classmethod
    def of_lane(cls, res, pods: int, lane=None) -> "Output":
        """From a ``SimResult``-shaped object (the program's, or the plain
        reference's ``Result``): one lane of a batch, or the whole of an
        unbatched one; real pods only."""
        pick = (lambda x: np.asarray(x)) if lane is None \
            else (lambda x: np.asarray(x)[lane])
        return cls(assigned_node=pick(res.assigned_node)[:pods],
                   assigned_gpus=pick(res.assigned_gpus)[:pods],
                   scheduled=int(pick(res.scheduled_pods)),
                   events=int(pick(res.events_processed)),
                   score=float(pick(res.policy_score)),
                   failed=bool(pick(res.failed)),
                   truncated=bool(pick(res.truncated)))


def compare(tag: str, ref: Result, got: Output, guarantees: dict
            ) -> List[Number]:
    nodes = np.asarray(got.assigned_node, np.int64)
    gpus = np.asarray(got.assigned_gpus, np.int64)
    out = [
        Number(f"{tag}.placements_differ",
               float((ref.assigned_node != nodes).sum()), 0.0),
        Number(f"{tag}.gpu_picks_differ",
               float((ref.assigned_gpus != gpus).sum()), 0.0),
        Number(f"{tag}.scheduled_diff",
               float(abs(ref.scheduled_pods - got.scheduled)), 0.0),
        Number(f"{tag}.events_diff",
               float(abs(ref.events_processed - got.events)), 0.0),
        Number(f"{tag}.flags_differ",
               float((ref.failed != got.failed)
                     + (ref.truncated != got.truncated)), 0.0),
    ]
    if ref.policy_score > 0 or got.score > 0:   # a finished, scored run
        rel = abs(got.score - ref.policy_score) / max(ref.policy_score,
                                                      1e-30)
        out.append(Number(f"{tag}.fitness_rel_err", float(rel),
                          float(guarantees["fitness_rtol"])))
    return out
