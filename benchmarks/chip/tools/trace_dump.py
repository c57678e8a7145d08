"""Look at one device trace by hand: set up a cell, trace a short window,
keep the trace under ``chiprun_out/trace_dump/`` and print each plane's
lines with their event counts and most frequent event names.

    python3 benchmarks/chip/tools/trace_dump.py --workload nemo-12b-s10.chat
"""

import argparse
import collections
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
sys.path.insert(0, str(Path(__file__).resolve().parents[3] / "src"))

from chipbench import catalog, cli, trace  # noqa: E402
from chipbench.cell import Cell, Spec  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--seed", type=int, default=5)
    args = ap.parse_args()
    cli.configure_jax()
    if cli.accelerator(1) is None:
        return 3
    import jax
    from jax.profiler import ProfileData

    cell = Cell(Spec.from_benchmark(args.workload), args.seed)
    cell.setup()
    print("step module:", cell.step_module())
    out = catalog.ROOT / "chiprun_out" / "trace_dump"
    jax.profiler.start_trace(str(out))
    cell.serve(args.seconds, annotate=True)
    jax.profiler.stop_trace()
    path = trace.find_xplane(str(out))
    print("trace:", path)
    for plane in ProfileData.from_file(path).planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            names = collections.Counter(e.name for e in line.events)
            evs = list(line.events)
            t0 = evs[0].start_ns if evs else 0
            print(f"  LINE {line.name!r}: {len(evs)} events, first at "
                  f"{t0}; top {names.most_common(6)}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
