"""``vm.slots_per_turn``: see ``vm.slots_per_turn.json`` (``doc``) and
``chipbench/reduce/spans.py``. A program whose launch spans carry no
``turns`` field (older than PR 46) gives nothing."""
from chipbench.reduce import spans

LAUNCH = ("tier/vm_batch/launch",)


def read(ctx: dict):
    calls = spans.calls_with(ctx, LAUNCH)
    turns = calls and spans.field_sum(calls, LAUNCH, "turns")
    if not turns:
        return None
    return spans.field_sum(calls, LAUNCH, "slots") / turns
