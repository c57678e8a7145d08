"""``run.py``'s body: one run of one cell, one JSON line on stdout."""

from __future__ import annotations

import argparse
import dataclasses
import json
import shutil
import sys
import tempfile
import time
from typing import Any, Dict, List, Optional


def parse(argv: Optional[List[str]]) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def configure_jax() -> None:
    """Persistent compilation cache at a fixed path inside the checkout,
    every program cached however fast it compiled."""
    import jax

    from chipbench.catalog import ROOT

    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def accelerator(chips: int):
    """The devices to run on, or None (with the reason on stderr) when JAX
    finds no TPU or fewer chips than the cell asks for."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"chipbench: needs {chips} TPU chip(s); JAX found "
              f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        return None
    return devs[:chips]


@dataclasses.dataclass
class Context:
    """What a metric reader may read."""

    spec: Any
    win: Any
    setup_s: float
    peaks: Dict[str, Any]
    trace: Any = None
    step_module: Optional[str] = None


def metrics_for(bench: Dict, workload: str, traced: bool) -> List[Dict]:
    group = bench["per_layer"] if traced else bench["end_to_end"]
    return [m for m in group
            if "workloads" not in m or workload in m["workloads"]]


def run(spec, seed: int, seconds: float, traced: bool, devices, peaks,
        bench: Dict, t_start: float) -> Dict[str, Any]:
    import jax

    from chipbench import catalog, trace
    from chipbench.cell import TRACE_SECONDS, HOST_LABELS, Cell, logit_gaps

    cell = Cell(spec, seed)
    cell.setup()
    module = cell.step_module() if traced else None
    tdir = None
    if traced:
        seconds = min(seconds, TRACE_SECONDS)
        tdir = tempfile.mkdtemp(prefix="chipbench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # the harness's annotations suffice
        jax.profiler.start_trace(tdir, profiler_options=opts)
    setup_s = time.perf_counter() - t_start
    win = cell.serve(seconds, annotate=traced)
    tr = None
    if traced:
        jax.profiler.stop_trace()
        tr = trace.load(trace.find_xplane(tdir))
        shutil.rmtree(tdir, ignore_errors=True)
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)

    ctx = Context(spec, win, setup_s, peaks, tr, module)
    metrics = {}
    for m in metrics_for(bench, spec.name, traced):
        v = catalog.metric_reader(m["name"]).read(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    done = sum(d is not None for d in win.done)
    jcts = sorted(j for j in win.jcts() if j != float("inf"))
    late = win.idle_lateness
    print(f"chipbench: {spec.name} seed {seed}: {win.attempted} due in "
          f"{seconds} s, {done} completed, {win.failed} failed; "
          f"{win.compiles} programs compiled in the window; generator late "
          f"when idle: max {max(late, default=0.0):.6f} s, mean "
          f"{sum(late) / max(1, len(late)):.6f} s over {len(late)}",
          file=sys.stderr)
    if jcts:
        print(f"chipbench: JCT s p50 {jcts[len(jcts) // 2]:.4f} max "
              f"{jcts[-1]:.4f}; tokens in window {win.tokens_in_window()}",
              file=sys.stderr)

    exact = cell.exact_checks(win)
    idx = cell.sample(win)
    seqs = cell.sequences(win, idx)
    cell.free_program()
    limit = spec.conf["check"]["logit_gap_limit"]
    checks: Dict[str, Dict[str, Any]] = {
        k: {"value": v, "limit": 0} for k, v in exact.items()}
    checks["compared_requests"] = {"value": len(idx), "limit": 1}
    if seqs:
        gaps = logit_gaps(spec.conf, cell.weights, seqs)
        checks["logit_gap"] = {"value": gaps["served"], "limit": limit}
        checks["compared_tokens"] = {"value": gaps["tokens"], "limit": 1}
    correct = (
        all(v == 0 for v in exact.values())
        and len(idx) >= 1
        and limit is not None
        and checks["logit_gap"]["value"] <= limit
    )

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": int(peak)}
    out: Dict[str, Any] = {
        "correct": bool(correct), "attempted": win.attempted,
        "failed": win.failed, "metrics": metrics, "device": device,
    }
    if tr is not None:
        span = trace.union(tr.host_spans("window"))
        device["busy_s"] = trace.busy_in(tr, span) / 1e9
        device["window_s"] = trace.length(span) / 1e9
        out["breakdown"] = {
            "device_ops": trace.top_ops(tr),
            "idle_gaps": trace.idle_by_host(tr, span[0], HOST_LABELS)
            if span else [],
        }
    out["checks"] = checks
    for name, c in checks.items():
        kind = "at least" if name.startswith("compared_") else "at most"
        print(f"check {name}: {c['value']} ({kind} {c['limit']})",
              file=sys.stderr)
    return out


def main(argv: Optional[List[str]] = None,
         t_start: Optional[float] = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    args = parse(argv)
    from chipbench import catalog
    from chipbench.cell import Spec

    bench = catalog.benchmark()
    spec = Spec.from_benchmark(args.workload, bench)
    configure_jax()
    devices = accelerator(spec.chips)
    if devices is None:
        return 3
    peaks = catalog.peaks(devices[0].device_kind)
    out = run(spec, args.seed, args.seconds, bool(args.trace), devices, peaks,
              bench, t_start)
    sys.stdout.flush()
    print(json.dumps(out))
    return 0
