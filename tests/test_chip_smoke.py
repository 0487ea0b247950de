"""chip_smoke.py's contract off the chip: it refuses to run without a TPU
(tier-1), and its CPU rehearsal keeps every phase's wiring from rotting
(slow tier: run it before spending chip time)."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(*args):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), *args],
        capture_output=True, timeout=600, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )


def test_refuses_to_run_without_a_tpu():
    out = _run()
    assert out.returncode != 0
    assert b"needs a TPU" in out.stderr and b"'cpu'" in out.stderr
    assert b'"ok"' not in out.stdout  # no result line


@pytest.mark.slow
def test_cpu_rehearsal_runs_every_phase():
    out = _run("--cpu-rehearsal")
    assert out.returncode == 0, out.stdout.decode(errors="replace")[-3000:]
    text = out.stdout.decode()
    assert "platform=cpu" in text and '"ok"' not in text
    for phase in ("kernels", "static", "serving", "multichip"):
        assert f"phase {phase}: passed" in text
