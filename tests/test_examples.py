"""Subprocess smoke tests for the documented entry points.

API redesigns must not silently break the examples: each runs as a real
``python examples/<name>.py`` subprocess (CPU jax, tiny configs) and must
exit 0 with its landmark output present.
"""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXAMPLES = os.path.join(REPO, "examples")


def _run_example(name: str, timeout: int = 300) -> str:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    env.setdefault("JAX_PLATFORMS", "cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(EXAMPLES, name)],
        capture_output=True, text=True, timeout=timeout, env=env, cwd=REPO,
    )
    assert proc.returncode == 0, (
        f"{name} exited {proc.returncode}\n--- stdout ---\n{proc.stdout}"
        f"\n--- stderr ---\n{proc.stderr}"
    )
    return proc.stdout


def test_quickstart_example():
    out = _run_example("quickstart.py")
    assert "Q2  output record 0 derives from" in out
    assert "run_many fused" in out
    assert "session stats" in out


def test_fairness_audit_example():
    out = _run_example("fairness_audit.py")
    assert "all three methods agree" in out
    assert "impact closure matches the composed relation" in out


def test_erasure_audit_example():
    out = _run_example("erasure_audit.py")
    assert "RecomputePlan" in out
    assert "rebuild order:" in out
    assert "stale cached relations dropped:" in out
    assert "what-if: zero ingest row 0's income" in out
    assert "without rerunning the pipeline" in out


@pytest.mark.filterwarnings("ignore::DeprecationWarning")
def test_serve_with_lineage_example():
    out = _run_example("serve_with_lineage.py", timeout=600)
    assert "response row 2 derives from request row" in out
    assert "session stats (shared composed relations)" in out
    assert "federation stats (single-entry catalog)" in out


def test_streaming_lineage_example():
    out = _run_example("streaming_lineage.py")
    assert "after 40 appended ops: extends=" in out
    assert "spilled to disk" in out
    assert "faulted back: rehydrations=" in out
    assert "bounded: composed-relation residency stayed under" in out


@pytest.mark.filterwarnings("ignore::DeprecationWarning")
def test_federated_lineage_example():
    out = _run_example("federated_lineage.py", timeout=600)
    assert "capability: prep index is read-only from the serving tier" in out
    assert "traces to raw user row" in out
    assert "batch trace-to-source:" in out
    assert "federation stats:" in out
