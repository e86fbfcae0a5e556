"""The benchmark's own self-check, in tier-1.

``python3 -m chipbench.selftest`` guards ``correct`` in every cell: the
plain reference against upstream's goldens, whole calls only, a tie
broken another way is a difference, the bfloat16 control fails, no result
without a TPU. It wants four CPU devices and f32 where this suite's
conftest sets eight and x64, so it runs ONCE in a process of its own and
each of its tests is one case here, passed by its ``PASS`` line.
"""
import os
import pathlib
import re
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
NAMES = re.findall(
    r"^def (test_\w+)\(",
    (REPO / "chipbench" / "selftest" / "tests.py").read_text(), re.M)


@pytest.fixture(scope="module")
def selftest_lines():
    # the selftest picks its own platform and device count; nothing this
    # process was given for its eight devices or x64 may leak into it
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_ENABLE_X64")}
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench.selftest"], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=900)
    lines = proc.stdout.splitlines()
    assert lines, f"the selftest printed nothing:\n{proc.stderr[-2000:]}"
    return lines, proc.stderr


def test_the_selftest_has_tests():
    assert len(NAMES) >= 15


@pytest.mark.parametrize("name", NAMES)
def test_selftest_case_passes(selftest_lines, name):
    lines, stderr = selftest_lines
    assert f"PASS {name}" in lines, (
        f"chipbench.selftest did not pass {name}:\n{stderr[-4000:]}")
