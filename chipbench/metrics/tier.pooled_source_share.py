"""``tier.pooled_source_share``: see ``tier.pooled_source_share.json``
(``doc``) and ``chipbench/reduce/spans.py``. A program whose transpile
spans carry no ``pooled`` field (older than PR 39) gives nothing: a 0
there is a generation lowered in process, which is a reading."""
from chipbench.reduce import spans

TRANSPILE = ("tier/transpile",)


def read(ctx: dict):
    calls = spans.calls_with(ctx, TRANSPILE)
    sources = calls and spans.field_sum(calls, TRANSPILE, "sources")
    if not sources or not any("pooled" in (r.fields or {})
                              for r in spans.named(calls, TRANSPILE)):
        return None
    return 100.0 * spans.field_sum(calls, TRANSPILE, "pooled") / sources
