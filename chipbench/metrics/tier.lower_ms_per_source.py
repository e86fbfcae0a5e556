"""``tier.lower_ms_per_source``: see ``tier.lower_ms_per_source.json`` (``doc``) and
``chipbench/reduce/hostspans.py``."""
from chipbench.reduce import hostspans


def read(ctx: dict):
    return hostspans.lower_ms_per_source(ctx)
