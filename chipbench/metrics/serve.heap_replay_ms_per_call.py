"""``serve.heap_replay_ms_per_call``: see
``serve.heap_replay_ms_per_call.json`` (``doc``) and
``chipbench/reduce/spans.py``. A program without the span (older than PR
52, or an engine that does not fork) gives nothing."""
from chipbench.reduce import spans


def read(ctx: dict):
    return spans.sum_ms_per_call(ctx, "serve/chunk/stack/heap_replay")
