"""``vm.us_per_slot``: see ``vm.us_per_slot.json`` (``doc``) and
``chipbench/reduce/spans.py``. Every generation of a window runs the same
number of lockstep events, so the events x slots of the window are its
events times the mean ``slots`` of its launches. A program whose launch
spans carry no ``slots`` field gives nothing."""
from chipbench.reduce import spans

LAUNCH = ("tier/vm_batch/launch",)
DEVICE = LAUNCH + ("tier/vm_batch/wait_device",)


def read(ctx: dict):
    calls = spans.calls_with(ctx, LAUNCH)
    events = ctx.get("lockstep_events")
    slots = calls and spans.field_sum(calls, LAUNCH, "slots")
    if not slots or not events:
        return None
    mean_slots = slots / len(spans.named(calls, LAUNCH))
    return spans.sum_s(calls, DEVICE) / (float(events) * mean_slots) * 1e6
