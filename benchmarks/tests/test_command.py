"""The command as the driver runs it: a parent that never touches jax and a
child that does the run.  On a cold compile cache the first child's set-up
compiles, so it stops before its window (``RERUN``) and the parent runs a
second child, which loads every program and measures; on the cache that
leaves, one child is enough.  ``setup_s`` counts from the parent's start."""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def command(cache_dir):
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(cache_dir),
               JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", "criteo_lr.skew", "--dry-run", "--seconds", "0.5",
         "--seed", "3000000001"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=300,
    )
    assert p.returncode == 0, p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    assert len(lines) == 1  # the first child printed nothing there
    return json.loads(lines[0]), p.stderr


def test_a_set_up_that_compiled_is_run_again_from_the_cache(tmp_path):
    cold, err = command(tmp_path / "cache")
    assert err.count("compiled in set-up") == 1
    assert err.count('"cache_misses": 0}') == 1  # the child that measured
    warm, err = command(tmp_path / "cache")
    assert "compiled in set-up" not in err
    assert cold["correct"] is True and warm["correct"] is True
    # the first command's set-up holds the child that compiled as well
    setup = [r["metrics"]["setup_s"]["value"] for r in (cold, warm)]
    assert setup[0] > setup[1] > 0

