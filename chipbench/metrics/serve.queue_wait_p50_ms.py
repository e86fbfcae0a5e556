"""``serve.queue_wait_p50_ms``: see ``serve.queue_wait_p50_ms.json`` (``doc``) and
``chipbench/reduce/spans.py``."""
from chipbench.reduce import spans


def read(ctx: dict):
    return spans.request_wait_p50_ms(
        ctx, "serve/request/queue_wait", "serve/request/batch_wait")
