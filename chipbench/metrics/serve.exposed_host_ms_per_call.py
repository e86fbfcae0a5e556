"""``serve.exposed_host_ms_per_call``: see ``serve.exposed_host_ms_per_call.json`` (``doc``) and
``chipbench/reduce/spans.py``."""
from chipbench.reduce import spans


def read(ctx: dict):
    return spans.exposed_ms_per_call(ctx, "serve/chunk/wait_device")
