"""Without a TPU the benchmark exits non-zero and prints no result."""
import os
import subprocess
import sys

import tiny

ROOT = os.path.dirname(tiny.BENCH)


def test_run_refuses_the_cpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qwen3-0.6b.lmsys-chat",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs 1 TPU" in p.stderr
