"""``serve.d2h_kb_per_call``: see ``serve.d2h_kb_per_call.json`` (``doc``) and
``chipbench/reduce/spans.py``."""
from chipbench.reduce import spans


def read(ctx: dict):
    return spans.kb_per_call(ctx, "serve/chunk/d2h")
