#!/usr/bin/env python
"""Standalone entry for the repo lint gate — ``python tools/fks_lint.py``
is ``python -m fks_tpu.cli lint`` with the same flags and exit codes
(0 clean / 1 findings-or-drift / 2 error), for CI configs that invoke
tools/ scripts directly. ``--cpu`` is NOT implied; pass it to lint on the
CPU backend."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from fks_tpu.cli import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(["lint", *sys.argv[1:]]))
