"""``serve.fork_state_ms``: see ``serve.fork_state_ms.json`` (``doc``). The
span lies in set-up, before the window's calls, so it is read from the
ring itself and not from the window's selection (as ``sim.fork_state_ms``
is). A program without the span (older than PR 37) gives nothing."""
from chipbench.reduce import spans


def read(ctx: dict):
    got = spans.ring()
    forks = [r for r in (got[0] if got else ())
             if r.name == "serve/fork_state"]
    if not forks:
        return None
    return sum(r.t1 - r.t0 for r in forks) / len(forks) * 1e3
