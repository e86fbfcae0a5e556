"""``tier.check_ms_per_source``: see ``tier.check_ms_per_source.json``
(``doc``) and ``chipbench/reduce/spans.py``. A program without the span
(older than PR 51) gives nothing."""
from chipbench.reduce import spans

CHECK = ("tier/transpile/check",)


def read(ctx: dict):
    calls = spans.calls_with(ctx, CHECK)
    return calls and spans.sum_s(calls, CHECK) \
        / len(spans.named(calls, CHECK)) * 1e3
