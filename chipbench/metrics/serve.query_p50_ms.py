"""``serve.query_p50_ms``: the median of the per-request ``latency_ms`` the
service stamped on the answers of the window (closed loop, so it is about
one coalesced call's length)."""


def read(ctx: dict):
    return ctx.get("latency_p50_ms")
