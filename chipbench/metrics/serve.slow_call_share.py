"""``serve.slow_call_share``: see ``serve.slow_call_share.json`` (``doc``) and
``chipbench/reduce/hostspans.py``."""
from chipbench.reduce import hostspans


def read(ctx: dict):
    return hostspans.slow_call_share(ctx, "serve/batch")
