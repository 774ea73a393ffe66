"""The benchmark's own tests (``benchmarks/tests``, CPU only) as one tier-1
test: they live under ``benchmarks/`` with a ``conftest.py`` of their own, so
tier-1's ``pytest tests/`` does not collect them (ISSUE 23 asked for this;
ISSUE 25 satellite).  A subprocess, so that neither suite's ``conftest.py``
or module names reach the other."""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LIMIT_S = 900  # the suite takes two and a half minutes alone on this box (PR 33)


def test_benchmark_suite_passes():
    # the suite's own conftest.py sets what it needs; what tier-1's sets for
    # its children (no persistent compile cache, 8 CPU devices) would fail
    # the tests of the command's compile-then-rerun
    env = {
        k: v for k, v in os.environ.items()
        if k not in ("JAX_ENABLE_COMPILATION_CACHE", "XLA_FLAGS", "JAX_ENABLE_X64")
    }
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, "-m", "pytest", "benchmarks/tests", "-q",
         "-p", "no:cacheprovider", "-p", "no:randomly"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=LIMIT_S,
    )
    assert p.returncode == 0, (p.stdout[-4000:] + p.stderr[-2000:])
