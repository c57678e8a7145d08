"""Readings that a cell's logit-gap limit is set from, on the chip, at the
cell's own sizes and load, many seeds in one process.

    python3 benchmarks/chip/tools/readings.py --workload nemo-12b-s10.chat \
        --seeds 1 2 3 --seconds 15

For each seed: weights, cluster and warm-up from that seed, a short window
of the cell's traffic, then over the same sample of served requests that a
run compares: ``served``, the widest gap by which a served token's logit
lies below the float32 reference's best (the program's reading), and
``control``, the same gap for the tokens that the reference computed at
fp8 ranks first (the control's reading).  One JSON line per seed.
"""

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
sys.path.insert(0, str(Path(__file__).resolve().parents[3] / "src"))

from chipbench import cli  # noqa: E402
from chipbench.cell import Cell, Spec, logit_gaps  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", nargs="+", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    args = ap.parse_args()
    cli.configure_jax()
    if cli.accelerator(1) is None:
        return 3
    spec = Spec.from_benchmark(args.workload)
    for seed in args.seeds:
        t0 = time.perf_counter()
        cell = Cell(spec, seed)
        cell.setup()
        win = cell.serve(args.seconds)
        exact = cell.exact_checks(win)
        idx = cell.sample(win)
        seqs = cell.sequences(win, idx)
        cell.free_program()
        gaps = logit_gaps(spec.conf, cell.weights, seqs, control=True)
        print(json.dumps({
            "workload": spec.name, "seed": seed, **gaps, **exact,
            "compared_requests": len(idx),
            "longest_prompt": max((len(f) for f, _ in seqs), default=0),
            "seconds": time.perf_counter() - t0,
        }), flush=True)
        cell.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
