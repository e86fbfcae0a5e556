"""``vm.scatter_write_share``: see ``vm.scatter_write_share.json`` (``doc``)
and ``chipbench/reduce/spans.py``. A program whose launch spans carry no
``slice_writes`` / ``scatter_writes`` fields (older than PR 35) gives
nothing."""
from chipbench.reduce import spans

LAUNCH = ("tier/vm_batch/launch",)


def read(ctx: dict):
    calls = spans.calls_with(ctx, LAUNCH)
    if not calls:
        return None
    scatters = spans.field_sum(calls, LAUNCH, "scatter_writes")
    writes = scatters + spans.field_sum(calls, LAUNCH, "slice_writes")
    return 100.0 * scatters / writes if writes else None
