"""``serve.typed_pod_share``: see ``serve.typed_pod_share.json`` (``doc``)
and ``chipbench/reduce/spans.py``. A program whose ``serve/chunk/stack``
spans carry no ``typed_pods`` (older than PR 49, or an engine whose
workload is not typed) gives nothing."""
from chipbench.reduce import spans

STACK = ("serve/chunk/stack",)


def read(ctx: dict):
    calls = spans.calls_with(ctx, STACK)
    pods = calls and spans.field_sum(calls, STACK, "pods")
    if not pods:
        return None
    return 100.0 * spans.field_sum(calls, STACK, "typed_pods") / pods
