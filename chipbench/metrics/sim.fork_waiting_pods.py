"""``sim.fork_waiting_pods``: see ``sim.fork_waiting_pods.json``
(``doc``). Read from the ring itself, as ``sim.fork_state_ms`` is. A
program whose ``tier/fork_state`` span has no ``waiting`` field (older
than PR 42) or no span at all gives nothing."""
from chipbench.reduce import spans


def read(ctx: dict):
    got = spans.ring()
    forks = [r for r in (got[0] if got else ())
             if r.name == "tier/fork_state" and "waiting" in r.fields]
    if not forks:
        return None
    return float(forks[-1].fields["waiting"])
