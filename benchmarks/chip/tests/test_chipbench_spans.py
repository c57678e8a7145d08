"""The served path's own spans in a trace (``chipbench/spans.py``), on the
synthetic trace of ``test_chipbench_trace`` extended with program spans
and ``run_id`` links, and ``spans.load`` on a profiler trace taken here on
the CPU."""

import dataclasses
from types import SimpleNamespace

import pytest
from chipbench_tiny import catalog  # noqa: F401  (sets sys.path)
from test_chipbench_trace import DEV, synthetic

from chipbench import spans, trace
from chipbench.spans import ProgramSpan, SpanTrace
from chipbench.trace import Span

us = 1000.0
STEP = "jit__lambda"  # the synthetic trace's step program


def _p(name, a, b, **stats):
    return ProgramSpan("compass." + name, a * us, b * us, stats)


def extended(links: bool = True) -> SpanTrace:
    """``synthetic()`` with the program's spans: two requests of one task
    each.  Device executions by run_id (times in us):

    * 6: cache set-up, 5-6, launched at 4.5 in ``task_setup``;
    * 5: a prompt slice, 8-9, launched at 6.5 in task 0's prefill;
    * 11: step, 10-60 (the synthetic ``jit__lambda``), launched at 7;
    * 12: argmax, 70-90, launched at 9 in task 0's decode;
    * 13: step, 220-250, launched at 223 in task 1's prefill;
    * 14: argmax, 290-295, launched at 226 in task 1's decode.

    The runtime held launches back at 7.5-8.5 (across task 0's prefill
    and decode), 230-250 (task 1's decode) and 95-96 (a readback).
    """
    tr = synthetic()
    m = tr.modules[DEV]
    slice_, setup = Span("jit_dynamic_slice(3)", 8 * us, 9 * us), \
        Span("jit_broadcast_in_dim(4)", 5 * us, 6 * us)
    argmax2 = Span("jit__argmax(9)", 290 * us, 295 * us)
    runs = {6: setup, 5: slice_, 11: m[0], 12: m[2], 13: m[1], 14: argmax2}
    launches = {6: 4.5 * us, 5: 6.5 * us, 11: 7 * us, 12: 9 * us,
                13: 223 * us, 14: 226 * us}
    program = [
        _p("request", 0, 100, job=0), _p("plan", 0, 2, job=0),
        _p("state", 2, 4, job=0),
        _p("task", 4, 100, job=0, prompt=1, decode=1),
        _p("task_setup", 4, 6, job=0), _p("prefill", 6, 8, job=0, calls=1),
        _p("decode", 8, 12, job=0, calls=1), _p("sync", 12, 92, job=0),
        _p("readback", 92, 96, job=0), _p("state", 96, 99, job=0),
        _p("request", 200, 300, job=1), _p("plan", 200, 217, job=1),
        _p("state", 217, 219, job=1),
        _p("task", 219, 300, job=1, prompt=2, decode=2),
        _p("task_setup", 219, 222, job=1), _p("prefill", 222, 224, job=1,
                                                calls=2),
        _p("decode", 224, 270, job=1, calls=2), _p("sync", 270, 296, job=1),
        _p("readback", 296, 299, job=1),
    ]
    return SpanTrace(
        ops=tr.ops, modules=tr.modules, host=tr.host, program=sorted(program, key=lambda s: (s.start_ns, -s.end_ns)),
        module_runs={DEV: runs}, launches=launches if links else {},
        launch_waits=[Span("ExecutePrepare", a * us, b * us)
                      for a, b in ((7.5, 8.5), (230, 250), (95, 96))])


def _ctx(tr):
    conf = catalog.load_json(
        catalog.BENCH_DIR / "configs" / "nemo-12b-s10.json")
    spec = SimpleNamespace(conf=conf, mix=catalog.traffic("chat"))
    return SimpleNamespace(trace=tr, step_module=STEP, spec=spec,
                           peaks=catalog.peaks("TPU v5 lite"))


def read(name, tr):
    return catalog.metric_reader(name).read(_ctx(tr))


def new(name, tr):
    """The per-layer number ``name`` that ``chipbench.spans`` reads."""
    fn = getattr(spans, name)
    return fn(tr, STEP) if name in ("prefill_ms", "decode_token_ms") \
        else fn(tr)


NEW = ("prefill_ms", "decode_token_ms", "engine_idle_ms", "host_dispatch_ms")


def test_existing_readers_read_the_same():
    base, ext = synthetic(), extended()
    for name in ("device_idle_share", "decode_step_ms", "step_roofline_mfu"):
        assert read(name, ext) == read(name, base)
    window = trace.union(base.host_spans("window"))[0]
    labels = ("plan", "task", "idle", "submit")
    assert trace.idle_by_host(ext, window, labels) == \
        trace.idle_by_host(base, window, labels)
    assert trace.top_ops(ext) == trace.top_ops(base)
    assert trace.busy_in(ext, [window]) == trace.busy_in(base, [window])


def test_metrics_by_run_id():
    tr = extended()
    # prefill: slice 1 + step 50 in task 0, step 30 in task 1, over their
    # prompts of 1 and 2 positions
    assert new("prefill_ms", tr) == pytest.approx((51 + 30) / 3 / 1e3)
    # decode: argmax 20 and argmax 5 over 1 + 2 calls
    assert new("decode_token_ms", tr) == pytest.approx(25 / 3 / 1e3)
    # host: prefill 2 + 2 us, decode 4 + 46 us, less the waits inside
    # them (1 + 20 us; the readback's does not count), over 1 + 2 + 1 + 2
    # calls
    assert new("host_dispatch_ms", tr) == pytest.approx((54 - 21) / 6 / 1e3)
    assert new("host_dispatch_ms", dataclasses.replace(
        tr, launch_waits=[])) == pytest.approx(54 / 6 / 1e3)
    # idle in request 0: 0-10 (middle in task_setup), 60-70 (sync), 90-100
    # (middle 95 in the runtime's launch wait 95-96, inside readback); in
    # request 1: 200-220 (plan), 250-280 (middle 265 in decode)
    assert spans.idle_split(tr) == pytest.approx(
        {"task_setup": 10 * us, "sync": 10 * us, spans.RUNTIME_WAIT: 10 * us,
         "plan": 20 * us, "decode": 30 * us})
    # every gap inside a request but the launch wait's, over 2 tasks
    assert new("engine_idle_ms", tr) == pytest.approx(70 / 2 / 1e3)
    no_waits = dataclasses.replace(tr, launch_waits=[])
    assert spans.idle_split(no_waits)["readback"] == pytest.approx(10 * us)
    assert new("engine_idle_ms", no_waits) == pytest.approx(80 / 2 / 1e3)
    # linked by run_id, the phases need no step program's name
    for name in ("prefill_ms", "decode_token_ms"):
        assert getattr(spans, name)(tr, None) == new(name, tr)


def test_metrics_by_order_without_links():
    tr = extended(links=False)
    # task 0's executions in order: set-up, slice, step | argmax.  The
    # first ``calls`` (1) step executions, and what precedes them, are the
    # prefill's: set-up 1 + slice 1 + step 50; task 1 (2 calls, one step
    # traced): step 30 | argmax 5
    assert new("prefill_ms", tr) == pytest.approx((52 + 30) / 3 / 1e3)
    assert new("decode_token_ms", tr) == pytest.approx(25 / 3 / 1e3)
    per = spans.phase_executions(tr, STEP)
    assert [[s.name.split("(")[0] for s in p["decode"]] for p in per] == [
        ["jit_argmax"], ["jit__argmax"]]
    # spans alone: the same as with links
    assert new("host_dispatch_ms", tr) == new("host_dispatch_ms", extended())
    assert new("engine_idle_ms", tr) == new("engine_idle_ms", extended())
    # without the step program's name there is no order to go by
    assert spans.prefill_ms(tr, None) is None


def test_new_readers_read_nothing_without_program_spans():
    base = synthetic()
    for tr in (SpanTrace(ops=base.ops, modules=base.modules, host=base.host),
               dataclasses.replace(extended(), program=[])):
        for name in NEW:
            assert new(name, tr) is None
        assert spans.idle_split(tr) is None
    # spans but no device executions (a host-only trace)
    host_only = dataclasses.replace(extended(), ops={}, modules={},
                                    module_runs={}, launches={})
    for name in ("prefill_ms", "decode_token_ms", "engine_idle_ms"):
        assert new(name, host_only) is None


class _Ev:
    def __init__(self, start, dur, **stats):
        self.start_ns, self.duration_ns = start, dur
        self.stats = list(stats.items())


def test_launch_times_follow_flows_back():
    # main thread: launch 100-130 consumes flow (14, 1) from the Python
    # line's 99; inside it the runtime's 120-125 produces (7, 2).  A worker
    # thread later consumes (7, 2) at 400 and, inside, enqueues run 42.
    # Another run, 43, carries its id on the main thread with no flow.
    main = SimpleNamespace(events=[
        _Ev(100, 30, _ct=14, _c=1), _Ev(120, 5, _pt=7, _p=2),
        _Ev(140, 5, run_id=43)])
    py = SimpleNamespace(events=[_Ev(99, 1, _pt=14, _p=1)])
    worker = SimpleNamespace(events=[
        _Ev(400, 50, _ct=7, _c=2), _Ev(410, 5, run_id=42, _pt=12, _p=9)])
    done = SimpleNamespace(events=[_Ev(900, 5, run_id=42, _ct=12, _c=9)])
    plane = SimpleNamespace(lines=[worker, main, py, done])
    assert spans._launch_times(plane) == {42: 99, 43: 140}


def _named(name, start, dur):
    return SimpleNamespace(name=name, start_ns=start, duration_ns=dur,
                           stats=[])


def test_launch_waits_are_the_prepares_own_time():
    # a prepare 100-200 whose children cover 180-185 and 188-200 (one with
    # a child of its own) waited 100-180 and 185-188; a prepare whose child
    # covers it waited nothing; other events are not waits
    prep = "CommonPjRtLoadedExecutable::ExecutePrepare"
    main = SimpleNamespace(events=[
        _named("Execute", 90, 120), _named(prep, 100, 100),
        _named("Acquire semaphore", 180, 5), _named("Allocate", 188, 12),
        _named("MemoryAllocation", 190, 2), _named(prep, 300, 10),
        _named("LoadProgram", 300, 10), _named("Other", 400, 100)])
    got = spans._launch_waits(SimpleNamespace(lines=[main]))
    assert [(w.start_ns, w.end_ns) for w in got] == [(100, 180), (185, 188)]


def test_load_keeps_program_spans_and_launches(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x * 2)
    x = jnp.ones((4,))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("compass.prefill", job=3, calls=2):
        for _ in range(2):
            y = f(x)
        y.block_until_ready()
    jax.profiler.stop_trace()
    tr = spans.load(trace.find_xplane(str(tmp_path)))
    (p,) = tr.program_spans("prefill")
    assert p.stats == {"job": 3, "calls": 2}
    # the CPU's host launches carry their run_id: both lie in the span
    inside = [r for r, t in tr.launches.items()
              if p.start_ns <= t <= p.end_ns]
    assert len(inside) == 2
    assert tr.host == [] and tr.module_runs == {}
