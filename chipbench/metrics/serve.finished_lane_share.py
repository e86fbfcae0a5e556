"""``serve.finished_lane_share``: see ``serve.finished_lane_share.json``
(``doc``) and ``chipbench/reduce/spans.py``. A program whose
``serve/batch`` roots carry no ``finished_lanes`` (older than PR 52, or an
engine that does not fork) gives nothing."""
from chipbench.reduce import spans

BATCH = ("serve/batch",)


def read(ctx: dict):
    calls = spans.calls_with(ctx, BATCH)
    roots = [r for r in spans.named(calls or (), BATCH)
             if "finished_lanes" in (r.fields or {})]
    queries = sum(r.fields["queries"] for r in roots)
    if not queries:
        return None
    return 100.0 * sum(r.fields["finished_lanes"] for r in roots) / queries
