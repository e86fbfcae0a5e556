"""``vm.live_slot_share.serve``: see ``vm.live_slot_share.serve.json``
(``doc``) and ``chipbench/reduce/spans.py``; the serving twin of
``vm.live_slot_share`` (a per-layer metric moves ONE end-to-end metric, and
the serving cell reports another than the code cells)."""
from chipbench.reduce import spans

ENQUEUE = ("serve/chunk/enqueue",)


def read(ctx: dict):
    calls = spans.calls_with(ctx, ENQUEUE)
    capacity = calls and spans.field_sum(calls, ENQUEUE, "capacity")
    if not capacity:
        return None
    return 100.0 * spans.field_sum(calls, ENQUEUE, "slots") / capacity
