"""The single API gate: recorded-spec compatibility + reference-__all__
parity across every public namespace (collapses the per-module parity
assertions formerly scattered over test files)."""
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_api_gate_passes():
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH",
                                                             ""))
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools",
                                      "check_api_compatible.py")],
        capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "reference-__all__ names verified" in r.stdout
