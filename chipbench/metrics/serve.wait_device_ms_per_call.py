"""``serve.wait_device_ms_per_call``: see ``serve.wait_device_ms_per_call.json`` (``doc``) and
``chipbench/reduce/spans.py``."""
from chipbench.reduce import spans


def read(ctx: dict):
    return spans.union_ms_per_call(ctx, "serve/chunk/wait_device")
