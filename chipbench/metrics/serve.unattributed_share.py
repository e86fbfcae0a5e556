"""``serve.unattributed_share``: see ``serve.unattributed_share.json`` (``doc``) and
``chipbench/reduce/spans.py``."""
from chipbench.reduce import spans


def read(ctx: dict):
    return spans.unattributed_share(ctx, "serve/batch")
