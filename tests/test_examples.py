"""The examples/ scripts run end to end (user-facing quick starts)."""

import subprocess
import sys
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("script", [
    "examples/iris_logreg.py",
    "examples/staged_workflow.py",
    "examples/streaming_ctr.py",
])
def test_example_runs(script):
    env = dict(os.environ)
    # tests are CPU runs: the harness pins it from outside
    env["PYTHONPATH"] = REPO
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, os.path.join(REPO, script)],
                       capture_output=True, text=True, timeout=600,
                       cwd=REPO, env=env)
    assert r.returncode == 0, (r.stdout or "") + (r.stderr or "")
