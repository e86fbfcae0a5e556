"""``vm.merged_read_share``: see ``vm.merged_read_share.json`` (``doc``)
and ``chipbench/reduce/spans.py``. A program whose launch spans carry no
``merged_reads`` / ``split_reads`` fields (older than PR 44) gives
nothing."""
from chipbench.reduce import spans

LAUNCH = ("tier/vm_batch/launch",)


def read(ctx: dict):
    calls = spans.calls_with(ctx, LAUNCH)
    if not calls:
        return None
    merged = spans.field_sum(calls, LAUNCH, "merged_reads")
    reads = merged + spans.field_sum(calls, LAUNCH, "split_reads")
    return 100.0 * merged / reads if reads else None
