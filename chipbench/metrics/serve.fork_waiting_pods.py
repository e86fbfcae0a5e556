"""``serve.fork_waiting_pods``: see ``serve.fork_waiting_pods.json``
(``doc``). Read from the ring itself, as ``serve.fork_state_ms`` and
``sim.fork_waiting_pods`` are (the span lies in set-up). A program whose
``serve/fork_state`` span has no ``waiting`` field (older than PR 52) or
no span at all gives nothing."""
from chipbench.reduce import spans


def read(ctx: dict):
    got = spans.ring()
    forks = [r for r in (got[0] if got else ())
             if r.name == "serve/fork_state" and "waiting" in (r.fields or {})]
    if not forks:
        return None
    return float(forks[-1].fields["waiting"])
