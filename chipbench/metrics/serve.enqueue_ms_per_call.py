"""``serve.enqueue_ms_per_call``: see ``serve.enqueue_ms_per_call.json`` (``doc``) and
``chipbench/reduce/spans.py``."""
from chipbench.reduce import spans


def read(ctx: dict):
    return spans.sum_ms_per_call(ctx, "serve/chunk/enqueue")
