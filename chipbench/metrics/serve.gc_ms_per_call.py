"""``serve.gc_ms_per_call``: see ``serve.gc_ms_per_call.json`` (``doc``) and
``chipbench/reduce/hostspans.py``."""
from chipbench.reduce import hostspans


def read(ctx: dict):
    return hostspans.gc_ms_per_call(ctx, "serve/batch")
