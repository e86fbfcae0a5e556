"""``sim.fork_replay_us_per_event``: see
``sim.fork_replay_us_per_event.json`` (``doc``). The span lies in set-up,
before the window's calls, so it is read from the ring itself (as
``sim.fork_state_ms`` is). A program whose span has no ``departed`` field
(older than PR 42: no replay) or no span at all gives nothing."""
from chipbench.reduce import spans


def read(ctx: dict):
    got = spans.ring()
    forks = [r for r in (got[0] if got else ())
             if r.name == "tier/fork_state" and "departed" in r.fields
             and r.fields.get("start_event")]
    if not forks:
        return None
    return sum((r.t1 - r.t0) / r.fields["start_event"]
               for r in forks) / len(forks) * 1e6
