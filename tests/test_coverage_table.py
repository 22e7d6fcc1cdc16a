"""COVERAGE.md must stay truthful: every implemented-at path importable,
zero unclassified rows (round-3 next-step #4)."""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REF = "/root/reference/paddle/fluid/operators"


@pytest.mark.skipif(not os.path.isdir(REF),
                    reason="reference tree not present")
def test_gen_coverage_check_passes():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "gen_coverage.py"),
         "--check"],
        env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-1000:]
    assert os.path.exists(os.path.join(REPO, "COVERAGE.md"))
