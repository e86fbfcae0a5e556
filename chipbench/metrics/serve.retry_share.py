"""``serve.retry_share``: see ``serve.retry_share.json`` (``doc``) and
``chipbench/reduce/spans.py``. A program whose ``serve/chunk/extract``
spans carry no ``lane_events`` (older than PR 37, or an engine that does
not fork) gives nothing."""
from chipbench.reduce import spans

EXTRACT = ("serve/chunk/extract",)


def read(ctx: dict):
    calls = spans.calls_with(ctx, EXTRACT)
    events = calls and spans.field_sum(calls, EXTRACT, "lane_events")
    if not events:
        return None
    return 100.0 * spans.field_sum(calls, EXTRACT, "frag_events") / events
