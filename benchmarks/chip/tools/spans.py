"""Read the served path's own spans (``compass.*``) in one cell on the chip:
the per-layer numbers they give, the checks that tie them together, the
device's idle time inside requests split by program span, and what the
spans cost.

    python3 benchmarks/chip/tools/spans.py --workload nemo-12b-s10.chat \
        --seed 5 [--seconds 6] [--cost-seconds 15] [--keep DIR]

One process, one cell: set-up as ``run.py`` does it, then windows of the
mix's schedule, each serving the same requests in the same order:

1. ``--cost-seconds`` with the profiler off (the spans are annotations
   that record nothing), twice;
2. the same with the flight recorder on, as ``ServingCluster(trace=True)``
   sets it up (wall spans and virtual-clock events);
3. ``--seconds`` traced as ``run.py --trace 1`` traces, read with
   ``chipbench.spans``; ``--keep DIR`` leaves the ``.xplane.pb`` in DIR
   (relative to the checkout's root).

Prints a report on stderr and one JSON line on stdout.
"""

import argparse
import json
import shutil
import statistics
import sys
import tempfile
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
sys.path.insert(0, str(Path(__file__).resolve().parents[3] / "src"))

from chipbench import catalog, cli, spans, trace  # noqa: E402
from chipbench.cell import TRACE_SECONDS, Cell, Spec  # noqa: E402


def service_s(win):
    """Host seconds each request of the window took to serve."""
    return [d - s for s, d in zip(win.started, win.done)
            if s is not None and d is not None]


def paired(base, other):
    """Median and mean of ``other / base - 1`` over requests served in both."""
    r = [b / a - 1 for a, b in zip(base, other)]
    return {"requests": len(r), "median": statistics.median(r) if r else None,
            "mean": sum(r) / len(r) if r else None}


def report(tr, module):
    """Numbers read from one loaded trace."""
    per = spans.phase_executions(tr, module)
    steps = trace.step_durations_ns(tr, module) if module else []
    out = {
        "link": None if not any(tr.module_runs.values())
        else "run_id" if any(r in tr.launches for runs in
                             tr.module_runs.values() for r in runs)
        else "order",
        "tasks": len(tr.program_spans("task")),
        "prefill_ms": spans.prefill_ms(tr, module),
        "decode_token_ms": spans.decode_token_ms(tr, module),
        "engine_idle_ms": spans.engine_idle_ms(tr),
        "host_dispatch_ms": spans.host_dispatch_ms(tr),
        "decode_step_ms": sum(steps) / len(steps) / 1e6 if steps else None,
    }
    if per and module:
        # step-program time of the counted tasks that prefill and decode hold
        tasks = tr.program_spans("task")
        in_tasks = [
            s for runs in tr.module_runs.values() for s in runs.values()
            if trace.module_matches(s.name, module)
            and any(t.start_ns <= s.start_ns <= t.end_ns for t in tasks)]
        held = [s for p in per for ph in ("prefill", "decode") for s in p[ph]
                if trace.module_matches(s.name, module)]
        out["step_time_held_share"] = (
            sum(s.dur_ns for s in held) / sum(s.dur_ns for s in in_tasks)
            if in_tasks else None)
        # decode's executions by program
        by: dict = {}
        for p in per:
            for s in p["decode"]:
                name = s.name.split("(")[0]
                by.setdefault(name, []).append(s.dur_ns / 1e6)
        out["decode_programs"] = {k: [len(v), sum(v) / len(v)]
                                  for k, v in by.items()}
        # how far device starts precede their launches: the clock offset
        runs = {r: s for rs in tr.module_runs.values() for r, s in rs.items()}
        early = [tr.launches[r] - s.start_ns for r, s in runs.items()
                 if r in tr.launches]
        out["device_before_launch_ms_max"] = (
            max(early) / 1e6 if early else None)
    split = spans.idle_split(tr)
    if split:
        total = sum(split.values())
        out["idle_in_requests_ms"] = total / 1e6
        out["idle_split_ms"] = {k: v / 1e6 for k, v in sorted(
            split.items(), key=lambda kv: -kv[1])}
        # idle inside requests that a program span below ``request`` holds
        out["idle_in_spans_share"] = 1 - sum(
            split.get(k, 0.0) for k in ("request", spans.RUNTIME_WAIT)) / total
        window = trace.union(tr.host_spans("window"))
        busy = trace.union(s for ops in tr.ops.values() for s in ops)
        if window:
            idle = trace.length(trace.gaps(busy, window[0]))
            out["idle_outside_requests_ms"] = (idle - total) / 1e6
    for name in ("task_setup", "prefill", "decode", "sync", "readback",
                 "state", "plan", "request"):
        d = [s.dur_ns / 1e6 for s in tr.program_spans(name)]
        if d:
            out.setdefault("host_span_ms_mean", {})[name] = sum(d) / len(d)
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument("--seconds", type=float, default=TRACE_SECONDS)
    ap.add_argument("--cost-seconds", type=float, default=15.0)
    ap.add_argument("--keep", metavar="DIR")
    args = ap.parse_args()
    cli.configure_jax()
    if cli.accelerator(1) is None:
        return 3
    import jax

    from repro.core.telemetry import FlightRecorder

    cell = Cell(Spec.from_benchmark(args.workload), args.seed)
    cell.setup()
    module = cell.step_module()
    out = {"workload": args.workload, "seed": args.seed, "step_module": module}

    cost = {}
    if args.cost_seconds > 0:
        off = [service_s(cell.serve(args.cost_seconds)) for _ in range(2)]
        # what ServingCluster(trace=True) attaches
        sc = cell.sc
        sc.recorder = FlightRecorder(sc.cluster.n_workers)
        sc.scheduler.recorder = sc.recorder
        sc.spans.recorder = sc.recorder
        on = service_s(cell.serve(args.cost_seconds))
        sc.recorder = sc.scheduler.recorder = sc.spans.recorder = None
        cost = {"off_again_vs_off": paired(off[0], off[1]),
                "recorder_vs_off": paired(off[0], on),
                "off_mean_s": sum(off[0]) / max(1, len(off[0]))}

    tdir = tempfile.mkdtemp(prefix="spans-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tdir, profiler_options=opts)
    win = cell.serve(args.seconds, annotate=True)
    jax.profiler.stop_trace()
    if args.cost_seconds > 0:
        base = service_s(cell.serve(args.seconds))
        cost["profiler_vs_off"] = paired(base, service_s(win))
    path = trace.find_xplane(tdir)
    if args.keep:
        dest = (catalog.ROOT / args.keep
                / f"{args.workload}-{args.seed}.xplane.pb")
        dest.parent.mkdir(parents=True, exist_ok=True)
        shutil.copy(path, dest)
        out["kept"] = str(dest)
    tr = spans.load(path)
    shutil.rmtree(tdir, ignore_errors=True)
    out.update(report(tr, module))
    out["cost"] = cost
    for k, v in out.items():
        print(f"spans: {k}: {v}", file=sys.stderr)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
