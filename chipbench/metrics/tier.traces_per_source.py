"""``tier.traces_per_source``: see ``tier.traces_per_source.json`` (``doc``) and
``chipbench/reduce/spans.py``. A program whose transpile spans carry no
``sources`` field (older than PR 28) gives nothing."""
from chipbench.reduce import spans

TRANSPILE = ("tier/transpile",)


def read(ctx: dict):
    calls = spans.calls_with(ctx, TRANSPILE)
    sources = calls and spans.field_sum(calls, TRANSPILE, "sources")
    if not sources:
        return None
    return spans.field_sum(calls, TRANSPILE, "traces") / sources
