"""``vm.live_slot_share``: see ``vm.live_slot_share.json`` (``doc``) and
``chipbench/reduce/spans.py``. The spans of a program that does not bound
the op-slot loop by the live programs carry no ``capacity`` field, and the
metric is then left out."""
from chipbench.reduce import spans

LAUNCH = ("tier/vm_batch/launch",)


def read(ctx: dict):
    calls = spans.calls_with(ctx, LAUNCH)
    capacity = calls and spans.field_sum(calls, LAUNCH, "capacity")
    if not capacity:
        return None
    return 100.0 * spans.field_sum(calls, LAUNCH, "slots") / capacity
