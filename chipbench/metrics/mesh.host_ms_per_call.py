"""``mesh.host_ms_per_call``: per generation, ``mesh/shard_put`` + the
self time of ``mesh/segment`` (the launch of a segment, without its
``mesh/segment/wait`` child) + ``mesh/finish`` (the launch of the
finalize program)."""
from chipbench.reduce import spans

WHOLE = ("mesh/shard_put", "mesh/finish")
SELF = ("mesh/segment",)


def read(ctx: dict):
    calls = spans.calls_with(ctx, WHOLE + SELF)
    if not calls:
        return None
    return (spans.sum_s(calls, WHOLE)
            + spans.self_s(calls, SELF)) / len(calls) * 1e3
