"""``vm.narrow_turn_share``: see ``vm.narrow_turn_share.json`` (``doc``)
and ``chipbench/reduce/spans.py``. A program whose launch spans carry no
``wide_turns`` field (older than PR 47) gives nothing."""
from chipbench.reduce import spans

LAUNCH = ("tier/vm_batch/launch",)


def read(ctx: dict):
    calls = spans.calls_with(ctx, LAUNCH)
    turns = calls and spans.field_sum(calls, LAUNCH, "turns")
    if not turns or not all("wide_turns" in (r.fields or {})
                            for r in spans.named(calls, LAUNCH)):
        return None
    wide = spans.field_sum(calls, LAUNCH, "wide_turns")
    return 100.0 * (turns - wide) / turns
