"""``tier.harvest_ms_per_call``: see ``tier.harvest_ms_per_call.json`` (``doc``) and
``chipbench/reduce/spans.py``."""
from chipbench.reduce import spans


def read(ctx: dict):
    return spans.sum_ms_per_call(
        ctx, "tier/vm_batch/stack_programs", "tier/vm_batch/d2h", "tier/record")
