"""``tier.pool_overhead_ms_per_call``: see ``tier.pool_overhead_ms_per_call.json`` (``doc``) and
``chipbench/reduce/hostspans.py``."""
from chipbench.reduce import hostspans


def read(ctx: dict):
    return hostspans.pool_overhead_ms_per_call(ctx)
