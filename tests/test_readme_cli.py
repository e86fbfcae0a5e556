"""Every ``python -m fks_tpu.cli ...`` example in a fenced block of
README.md parses with the real parser. ``argparse`` only: nothing runs.
An example that stops parsing is fixed in the README, not skipped here.
"""
import pathlib
import shlex

import pytest

from fks_tpu import cli

README = pathlib.Path(__file__).resolve().parent.parent / "README.md"
PREFIX = "python -m fks_tpu.cli"


def _examples():
    """(line number, argv) per fenced line that starts with the command,
    after an optional ``$ `` prompt; lines ending in a backslash are
    joined, and a ``#`` comment, a pipe or a redirection ends the
    command."""
    out, fenced, pending = [], False, None
    for no, line in enumerate(README.read_text().splitlines(), 1):
        if line.lstrip().startswith("```"):
            fenced, pending = not fenced, None
            continue
        if not fenced:
            continue
        text = line.strip()
        if pending is not None:
            start, text = pending[0], pending[1] + " " + text
        elif text.removeprefix("$ ").startswith(PREFIX):
            start, text = no, text.removeprefix("$ ")
        else:
            continue
        if text.endswith("\\"):
            pending = (start, text[:-1].rstrip())
            continue
        pending = None
        argv = shlex.split(text, comments=True)[len(PREFIX.split()):]
        for stop in ("|", ">", ">>", "2>", "&&"):
            if stop in argv:
                argv = argv[:argv.index(stop)]
        out.append((start, argv))
    return out


EXAMPLES = _examples()


def test_the_readme_has_examples():
    assert len(EXAMPLES) >= 25


@pytest.mark.parametrize(
    "argv", [pytest.param(a, id=f"L{no}-{a[0]}") for no, a in EXAMPLES])
def test_readme_example_parses(argv):
    parser = cli.build_parser()
    try:
        args = parser.parse_args(argv)
    except SystemExit as e:  # argparse prints its reason to stderr
        pytest.fail(f"README example does not parse (exit {e.code}): "
                    f"{PREFIX} {' '.join(argv)}")
    assert callable(args.fn)
