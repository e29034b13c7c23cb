"""``bench/run.py`` refuses to run without a TPU and prints no result."""
import os
import subprocess
import sys

from bench import spec


def test_run_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(spec.BENCH_DIR / "run.py"), "--workload",
         "rfast100m.n2.straggler.tok512", "--seed", str(2 ** 31 + 3),
         "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "correct" not in proc.stdout
    assert "needs 1 TPU" in proc.stderr
