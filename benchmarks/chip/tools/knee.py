"""Find a configuration's knee under a traffic mix: the highest arrival
rate at which the backlog does not grow over a window.

    python3 benchmarks/chip/tools/knee.py --config nemo-12b-s10 \
        --traffic chat --rates 0.7 0.9 1.1 --seconds 30 --seed 7

One process, on the chip: for each mix, one set-up, then one window per
rate; the requests due in a window are all served.  Prints one JSON line
per (mix, rate): requests due, mean service seconds (first five apart),
JCT p50/p90, the mean JCT of the first and of the last tenth of the
requests due, and tokens/s.  A rate holds when the last tenth waits no
longer than the first.
"""

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
sys.path.insert(0, str(Path(__file__).resolve().parents[3] / "src"))

from chipbench import catalog, cli  # noqa: E402
from chipbench.cell import Cell, Spec  # noqa: E402
from chipbench.stats import percentile  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", nargs="+", required=True)
    ap.add_argument("--rates", nargs="+", type=float, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args()
    cli.configure_jax()
    if cli.accelerator(1) is None:
        return 3
    bench = catalog.benchmark()
    conf = catalog.config(args.config, bench)
    for name in args.traffic:
        mix = dict(catalog.traffic(name), after_window="drain")
        cell = Cell(Spec(f"{args.config}.{name}", conf, mix), args.seed)
        cell.setup()
        for rate in args.rates:
            win = cell.serve(args.seconds, rate_per_s=rate)
            jct = win.jcts()
            tenth = max(1, len(jct) // 10)
            svc = [d - s for s, d in zip(win.started, win.done)
                   if d is not None]
            print(json.dumps({
                "config": args.config, "traffic": name, "rate_per_s": rate,
                "due": win.attempted,
                "completed": sum(d is not None for d in win.done),
                "service_s": sum(svc) / max(1, len(svc)),
                "first_services_s": svc[:5],
                "jct_p50_s": percentile(jct, 50),
                "jct_p90_s": percentile(jct, 90),
                "first_tenth_jct_s": sum(jct[:tenth]) / tenth,
                "last_tenth_jct_s": sum(jct[-tenth:]) / tenth,
                "tokens_per_s": win.tokens_in_window() / win.seconds,
                "compiles_in_window": win.compiles,
            }), flush=True)
        cell.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
