"""``tier.uploads_per_call``: see ``tier.uploads_per_call.json`` (``doc``)
and ``chipbench/reduce/spans.py``. A program whose spans carry no
``uploads`` field (older than PR 40) gives nothing: a 0 is a reading."""
from chipbench.reduce import spans

UPLOADERS = ("tier/transpile/pack", "tier/vm_batch/stack_programs")


def read(ctx: dict):
    calls = spans.calls_with(ctx, UPLOADERS)
    if not calls or not any("uploads" in (r.fields or {})
                            for r in spans.named(calls, UPLOADERS)):
        return None
    return spans.field_sum(calls, UPLOADERS, "uploads") / len(calls)
