"""A whole run of a cell with a fault planted under it, or the control in
the program's place, on the chip: it has to come out not correct.

    python3 benchmarks/chip/tools/faults.py --workload nemo-12b-s10.chat \
        --fault control --seeds 1 2 3 --seconds 15

For each seed, in one process: ``run.py``'s run with the named fault
(``chipbench/faults.py``) planted after set-up.  One line per seed on
stdout: the workload, fault, seed, ``correct`` and the checks with their
limits.
"""

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
sys.path.insert(0, str(Path(__file__).resolve().parents[3] / "src"))

from chipbench import catalog, cli  # noqa: E402
from chipbench.cell import Cell, Spec  # noqa: E402
from chipbench.faults import FAULTS  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--fault", required=True, choices=sorted(FAULTS))
    ap.add_argument("--seeds", nargs="+", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    args = ap.parse_args()
    cli.configure_jax()
    devices = cli.accelerator(1)
    if devices is None:
        return 3
    bench = catalog.benchmark()
    spec = Spec.from_benchmark(args.workload, bench)
    setup = Cell.setup

    def planted(cell):
        setup(cell)
        FAULTS[args.fault](cell)

    Cell.setup = planted
    for seed in args.seeds:
        out = cli.run(spec, seed, args.seconds, False, devices,
                      catalog.peaks(devices[0].device_kind), bench,
                      time.perf_counter())
        print(json.dumps({"workload": spec.name, "fault": args.fault,
                          "seed": seed, "correct": out["correct"],
                          "attempted": out["attempted"],
                          "checks": out["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
