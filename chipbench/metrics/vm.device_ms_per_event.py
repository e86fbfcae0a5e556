"""``vm.device_ms_per_event``: the window's ``tier/vm_batch/launch`` +
``tier/vm_batch/wait_device`` seconds over its lockstep events (a
segmented runner waits for its segments inside ``launch``, a single
dispatch in ``wait_device``: the sum is the device's part either way)."""
from chipbench.reduce import spans

DEVICE = ("tier/vm_batch/launch", "tier/vm_batch/wait_device")


def read(ctx: dict):
    calls = spans.calls_with(ctx, DEVICE)
    events = ctx.get("lockstep_events")
    if not calls or not events:
        return None
    return spans.sum_s(calls, DEVICE) / float(events) * 1e3
