"""``tier.pack_ms_per_call``: see ``tier.pack_ms_per_call.json`` (``doc``) and
``chipbench/reduce/hostspans.py``."""
from chipbench.reduce import hostspans


def read(ctx: dict):
    return hostspans.pack_ms_per_call(ctx)
