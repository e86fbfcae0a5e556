"""``vm.register_mb``: see ``vm.register_mb.json`` (``doc``) and
``chipbench/reduce/spans.py``. A program whose launch spans carry no
``register_bytes`` field (older than PR 26) gives nothing."""
from chipbench.reduce import spans

LAUNCH = ("tier/vm_batch/launch",)


def read(ctx: dict):
    calls = spans.calls_with(ctx, LAUNCH)
    total = calls and spans.field_sum(calls, LAUNCH, "register_bytes")
    if not total:
        return None
    return total / len(spans.named(calls, LAUNCH)) / 1e6
