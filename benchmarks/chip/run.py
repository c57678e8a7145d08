"""Compass on-chip benchmark: one run of one cell of BENCHMARK.json.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine with the TPU chips the cell
asks for.  Prints one JSON line (correct, attempted, failed, metrics,
device, [breakdown], checks) as the last line of stdout; exits non-zero
and prints no result when there is no TPU or a step fails.
"""

import time

T_START = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

from chipbench.cli import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_start=T_START))
