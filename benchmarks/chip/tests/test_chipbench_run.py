"""Without a TPU, run.py exits non-zero and prints no result line."""

import os
import subprocess
import sys

from chipbench_tiny import catalog


def test_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "nemo-12b-s10.chat", "--seed", "2147483659", "--seconds", "1",
         "--trace", "0"],
        cwd=catalog.ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr
