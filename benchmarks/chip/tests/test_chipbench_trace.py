"""Trace reduction on a small synthetic trace: busy union, idle share,
step time, roofline share and the idle breakdown."""

from types import SimpleNamespace

import pytest
from chipbench_tiny import catalog  # noqa: F401  (sets sys.path)

from chipbench import trace, work
from chipbench.trace import Span, Trace

DEV = "/device:TPU:0"


def synthetic() -> Trace:
    # Two submits, 0-100 and 200-300 us.  Device ops overlap each other
    # inside the first; the second has a gap while the host plans.
    us = 1000.0
    ops = [Span("fusion.1", 10 * us, 40 * us), Span("fusion.2", 30 * us, 60 * us),
           Span("dot.3", 70 * us, 90 * us), Span("fusion.1", 220 * us, 250 * us),
           Span("fusion.1", 280 * us, 300 * us)]
    modules = [Span("jit__lambda(7)", 10 * us, 60 * us),
               Span("jit__lambda(7)", 220 * us, 250 * us),
               Span("jit_argmax", 70 * us, 90 * us)]
    host = [Span("chipbench.window", 0, 400 * us),
            Span("chipbench.submit", 0, 100 * us),
            Span("chipbench.submit", 200 * us, 300 * us),
            Span("chipbench.plan", 200 * us, 220 * us),
            Span("chipbench.idle", 100 * us, 200 * us)]
    return Trace(ops={DEV: ops}, modules={DEV: modules}, host=host)


def test_union_intersect_gaps():
    spans = [Span("a", 0, 10), Span("b", 5, 20), Span("c", 30, 40)]
    assert trace.union(spans) == [(0, 20), (30, 40)]
    assert trace.length(trace.union(spans)) == 30
    assert trace.intersect([(0, 20), (30, 40)], [(15, 35)]) == [(15, 20), (30, 35)]
    assert trace.gaps([(0, 20), (30, 40)], (0, 50)) == [(20, 30), (40, 50)]
    assert trace.gaps([], (3, 5)) == [(3, 5)]


def test_busy_and_idle_share():
    tr = synthetic()
    serving = trace.union(tr.host_spans("submit"))
    # busy inside submits: 10-60 (50) + 70-90 (20) + 220-250 (30) + 280-300 (20)
    assert trace.busy_in(tr, serving) == pytest.approx(120_000)
    reader = catalog.metric_reader("device_idle_share")
    ctx = SimpleNamespace(trace=tr)
    assert reader.read(ctx) == pytest.approx(100 * (1 - 120 / 200))
    # a suffix reuses the base name's reader
    assert catalog.metric_reader("device_idle_share.over").read(ctx) == \
        reader.read(ctx)


def test_no_device_ops_reads_nothing():
    tr = Trace(ops={}, modules={}, host=synthetic().host)
    ctx = SimpleNamespace(trace=tr, step_module="jit__lambda")
    for name in ("device_idle_share", "decode_step_ms", "step_roofline_mfu"):
        assert catalog.metric_reader(name).read(ctx) is None


def test_step_time_by_module_name():
    tr = synthetic()
    assert trace.module_matches("jit__lambda(7)", "jit__lambda")
    assert not trace.module_matches("jit__lambda_2", "jit__lambda")
    d = trace.step_durations_ns(tr, "jit__lambda")
    assert d == [50_000, 30_000]
    ctx = SimpleNamespace(trace=tr, step_module="jit__lambda")
    assert catalog.metric_reader("decode_step_ms").read(ctx) == \
        pytest.approx(0.04)


def test_roofline_share_from_shapes():
    tr = synthetic()
    conf = catalog.load_json(catalog.BENCH_DIR / "configs" / "nemo-12b-s10.json")
    mix = catalog.traffic("chat")
    spec = SimpleNamespace(conf=conf, mix=mix)
    peaks = catalog.peaks("TPU v5 lite")
    ctx = SimpleNamespace(trace=tr, step_module="jit__lambda", spec=spec,
                          peaks=peaks)
    flops, nbytes = work.decode_step(conf["model"], "dense",
                                     work.mean_context(mix))
    least = max(flops / 197e12, nbytes / 819e9)
    got = catalog.metric_reader("step_roofline_mfu").read(ctx)
    assert got == pytest.approx(100 * least / 40e-6)


def test_idle_breakdown_by_host_span():
    tr = synthetic()
    window = trace.union(tr.host_spans("window"))[0]
    got = dict(trace.idle_by_host(tr, window, ("plan", "idle", "submit")))
    # device gaps: 0-10, 60-70 and 250-280 inside submits; 90-220, whose
    # middle is in the host's idle wait; 300-400 outside every span
    assert got == pytest.approx({"submit": 50e-6, "idle": 130e-6,
                                 "outside harness spans": 100e-6})
    top = trace.top_ops(tr)
    assert top[0][0] == "fusion.1"
    assert top[0][1] == pytest.approx(80e-6)
