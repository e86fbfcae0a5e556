"""``sim.typed_pod_share``: see ``sim.typed_pod_share.json`` (``doc``) and
``chipbench/reduce/spans.py``. A program whose ``tier/evaluate`` roots
carry no ``typed_pods`` field (older than PR 45), or a driver that does
not count the workload's pods, gives nothing."""
from chipbench.reduce import spans

ROOT = ("tier/evaluate",)


def read(ctx: dict):
    calls = spans.calls_with(ctx, ROOT)
    pods = ctx.get("workload_pods")
    if not calls or not pods:
        return None
    typed = [r.fields["typed_pods"] for r in spans.named(calls, ROOT)
             if "typed_pods" in (r.fields or {})]
    if not typed:
        return None
    return 100.0 * sum(typed) / len(typed) / float(pods)
