"""``sim.fork_state_ms``: see ``sim.fork_state_ms.json`` (``doc``). The
span lies in set-up, before the window's calls, so it is read from the
ring itself and not from the window's selection. A program without the
span (older than PR 31) gives nothing."""
from chipbench.reduce import spans


def read(ctx: dict):
    got = spans.ring()
    forks = [r for r in (got[0] if got else ())
             if r.name == "tier/fork_state"]
    if not forks:
        return None
    return sum(r.t1 - r.t0 for r in forks) / len(forks) * 1e3
