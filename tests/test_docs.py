"""The documents and the smoke scripts tell a reader to run only what the
tree has: a `python <path>.py`, a `python -m pytest <path>` or a
`tools/<name>.sh` that names no tracked file is a stale instruction (a
README that still said to run a deleted driver is what this was written
after)."""
import functools
import glob
import os
import re
import subprocess

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOCS = ["README.md", "MIGRATION.md", ".claude/skills/verify/SKILL.md"] + sorted(
    os.path.relpath(p, REPO)
    for p in glob.glob(os.path.join(REPO, "tools", "*.sh")))

_SCRIPT = re.compile(r"\bpython3?\s+(?!-)([\w./-]+\.py)\b")
_PYTEST = re.compile(r"\bpython3?\s+-m\s+pytest((?:\s+(?:\\\n\s*)?[\w./:-]+)+)")
_TOOL = re.compile(r"\b(tools/\w+\.sh)\b")


@functools.lru_cache(maxsize=None)
def _tracked():
    """What git would commit plus what is staged; where the checkout has
    no `.git`, what is on the disk."""
    try:
        out = subprocess.run(
            ["git", "ls-files"], cwd=REPO, capture_output=True, text=True,
            check=True).stdout.split("\n")
        return {p for p in out if p and os.path.exists(os.path.join(REPO, p))}
    except (OSError, subprocess.CalledProcessError):
        return {os.path.relpath(os.path.join(d, f), REPO)
                for d, _, fs in os.walk(REPO) for f in fs}


def commands(text):
    """The repo paths that `text` tells a reader to run."""
    named = set(_SCRIPT.findall(text)) | set(_TOOL.findall(text))
    for args in _PYTEST.findall(text):
        named.update(a.split("::")[0] for a in args.split()
                     if a.startswith(("tests", "benchmarks")))
    # an absolute path is the reader's own file, not the tree's
    return {p for p in named if not p.startswith("/")}


@pytest.mark.parametrize("doc", DOCS)
def test_a_document_names_only_commands_that_exist(doc):
    with open(os.path.join(REPO, doc)) as fh:
        named = commands(fh.read())
    tracked = _tracked()
    dirs = {os.path.dirname(p) for p in tracked}
    missing = sorted(p for p in named
                     if p not in tracked and p.rstrip("/") not in dirs)
    assert not missing, f"{doc} says to run what the tree has not: {missing}"


def test_the_reading_finds_the_three_forms():
    text = ("run `python gone.py --config x`, then `JAX_PLATFORMS=cpu python3\n"
            "  benchmarks/run.py`, `python -m pytest tests/test_a.py::T::t \\\n"
            "    tests/test_b.py -q` and tools/old_smoke.sh; `python -m\n"
            "paddle_tpu.analysis` and `python /root/scratch/mine.py` are not "
            "the tree's")
    assert commands(text) == {
        "gone.py", "benchmarks/run.py", "tests/test_a.py", "tests/test_b.py",
        "tools/old_smoke.sh"}
