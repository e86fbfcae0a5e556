"""``serve.h2d_kb_per_call``: see ``serve.h2d_kb_per_call.json`` (``doc``) and
``chipbench/reduce/spans.py``."""
from chipbench.reduce import spans


def read(ctx: dict):
    return spans.kb_per_call(ctx, "serve/chunk/h2d")
