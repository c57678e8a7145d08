"""Wall-clock spans of the served path (``ServedSpans`` in
serving/engine.py, ``FlightRecorder.wall_spans`` in core/telemetry.py):
nesting, stats, first-call marking, no effect on what is served, nothing
recorded with tracing off, and the spans' landing in a profiler trace."""

import glob
import json
import os
import tracemalloc

import jax
import numpy as np
import pytest

from repro.configs import ARCHS
from repro.core import ClusterSpec, GB, validate_schema
from repro.core import telemetry as telemetry_mod
from repro.core.types import DFG, MB, TaskSpec
from repro.models import init_params
from repro.serving import HostedModel, ServingCluster

DECODE = 3
REPO = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")


def load_schema(name):
    with open(os.path.join(REPO, "schemas", name)) as f:
        return json.load(f)


def _chain():
    return DFG(
        "chain2",
        tasks=[
            TaskSpec("a", 0.05, model_id=0, output_bytes=0.01 * MB,
                     input_bytes=0.01 * MB),
            TaskSpec("b", 0.05, model_id=0, output_bytes=0.01 * MB),
        ],
        edges=[("a", "b")],
    )


@pytest.fixture(scope="module")
def hosted():
    cfg = ARCHS["mistral-nemo-12b"].reduced(dtype="float32")
    return HostedModel(0, cfg, init_params(cfg, jax.random.key(0)))


def _cluster(hosted, trace):
    sc = ServingCluster(ClusterSpec(n_workers=2, gpu_capacity_bytes=1 * GB),
                        [hosted], decode_tokens=DECODE, trace=trace)
    sc.register_pipeline(_chain())
    return sc


def _prompt(n, seed=0):
    return np.random.default_rng(seed).integers(0, 100, size=(1, n),
                                                dtype=np.int32)


@pytest.fixture(scope="module")
def traced(hosted):
    """Three requests, prompts 5, 5, 7: the second repeats every shape."""
    sc = _cluster(hosted, trace=True)
    results = [sc.submit(_chain(), {"a": _prompt(n, i)})
               for i, n in enumerate((5, 5, 7))]
    return sc, results


def _children(spans, i):
    return [s for s in spans if s.parent == i]


def test_spans_nest_and_share_the_job_id(traced):
    sc, results = traced
    spans = sc.recorder.wall_spans
    requests = [(i, s) for i, s in enumerate(spans) if s.name == "request"]
    assert [s.stats["job"] for _, s in requests] == [r.job_id for r in results]
    for i, req in requests:
        assert req.parent == -1
        assert req.stats["dfg"] == "chain2" and req.stats["tasks"] == 2
        kids = _children(spans, i)
        assert [k.name for k in kids] == ["plan", "state", "task", "state",
                                          "state", "task", "state"]
        for k in kids:
            assert k.stats["job"] == req.stats["job"]
            assert req.t0 <= k.t0 <= k.t1 <= req.t1
        for ti, task in ((j, s) for j, s in enumerate(spans)
                         if s.parent == i and s.name == "task"):
            phases = _children(spans, ti)
            assert [p.name for p in phases] == [
                "task_setup", "prefill", "decode", "sync", "readback"]
            for p in phases:
                assert p.stats["job"] == req.stats["job"]
                assert p.stats["task"] == task.stats["task"]
                assert p.stats["worker"] == task.stats["worker"]
                assert task.t0 <= p.t0 <= p.t1 <= task.t1
            assert task.stats["worker"] == results[
                req.stats["job"]].assignment[task.stats["task"]]


def test_calls_match_prompt_and_decode_tokens(traced):
    sc, results = traced
    spans = sc.recorder.wall_spans
    for ti, task in enumerate(spans):
        if task.name != "task":
            continue
        by = {s.name: s for s in _children(spans, ti)}
        assert by["prefill"].stats["calls"] == task.stats["prompt"]
        assert by["decode"].stats["calls"] == task.stats["decode"] == DECODE
        assert by["readback"].stats["copies"] == DECODE
        assert by["task_setup"].stats["capacity"] == (
            task.stats["prompt"] + DECODE + 1)
    # chain: stage b is fed stage a's DECODE tokens
    prompts = [s.stats["prompt"] for s in spans if s.name == "task"]
    assert prompts == [5, DECODE, 5, DECODE, 7, DECODE]
    generated = sum(o.size for r in results for o in r.outputs.values())
    assert generated == sum(
        s.stats["calls"] for s in spans if s.name == "decode")


def test_first_call_marks_each_new_shape_once(traced):
    sc, _ = traced
    spans = sc.recorder.wall_spans
    firsts = [(s, spans[s.parent]) for s in spans if s.name == "first_call"]
    # capacities 5+3+1, 3+3+1 in request 0; none new in request 1; 7+3+1
    # in request 2
    assert [(f.stats["job"], f.stats["capacity"]) for f, _ in firsts] == [
        (0, 9), (0, 7), (2, 11)]
    assert all(p.name == "prefill" for _, p in firsts)


def test_wall_clock_reads_are_the_spans(traced):
    sc, results = traced
    reqs = [s for s in sc.recorder.wall_spans if s.name == "request"]
    for r, s in zip(results, reqs):
        assert r.latency_s == s.t1 - s.t0


def test_chrome_trace_has_a_wall_clock_process(traced):
    sc, _ = traced
    chrome = json.loads(json.dumps(sc.recorder.to_chrome_trace()))
    validate_schema(chrome, load_schema("trace.schema.json"))
    pid = sc.recorder.n_workers + 1
    wall = [e for e in chrome["traceEvents"] if e["pid"] == pid]
    assert wall[0]["args"]["name"] == "served path (wall clock)"
    assert sum(e["ph"] == "X" for e in wall) == len(sc.recorder.wall_spans)
    # the virtual-clock stream holds no wall spans
    assert all(json.loads(line)["kind"] not in ("request", "task")
               for line in sc.recorder.to_jsonl().splitlines())


def test_a_span_closes_when_its_body_raises(hosted):
    sc = _cluster(hosted, trace=True)
    with pytest.raises(KeyError):
        with sc.spans("request", dfg="x") as outer:
            with sc.spans("plan"):
                raise KeyError("no plan")
    req, plan = sc.recorder.wall_spans
    assert plan.parent == 0 and req.parent == -1
    assert req.t0 <= plan.t0 <= plan.t1 <= req.t1 == outer[1]
    # the next span opens at the top again
    with sc.spans("request"):
        pass
    assert sc.recorder.wall_spans[-1].parent == -1


def test_tracing_changes_nothing_served(hosted, traced):
    _, on = traced
    sc = _cluster(hosted, trace=False)
    off = [sc.submit(_chain(), {"a": _prompt(n, i)})
           for i, n in enumerate((5, 5, 7))]
    assert sc.recorder is None
    for a, b in zip(on, off):
        assert a.assignment == b.assignment
        assert a.outputs.keys() == b.outputs.keys()
        for k in a.outputs:
            np.testing.assert_array_equal(a.outputs[k], b.outputs[k])


def test_tracing_off_allocates_nothing_in_telemetry(hosted):
    sc = _cluster(hosted, trace=False)
    sc.submit(_chain(), {"a": _prompt(5)})  # compile outside the check
    flt = [tracemalloc.Filter(True, telemetry_mod.__file__)]
    tracemalloc.start(25)
    try:
        before = tracemalloc.take_snapshot()
        for i in range(3):
            sc.submit(_chain(), {"a": _prompt(5, i)})
        after = tracemalloc.take_snapshot()
    finally:
        tracemalloc.stop()
    grown = [s for s in after.filter_traces(flt).compare_to(
        before.filter_traces(flt), "lineno")
        if s.size_diff > 0 or s.count_diff > 0]
    assert not grown, "\n".join(str(s) for s in grown)


def test_spans_and_stats_land_in_the_profiler_trace(hosted, tmp_path):
    from jax.profiler import ProfileData

    sc = _cluster(hosted, trace=False)
    sc.submit(_chain(), {"a": _prompt(4)})
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        r = sc.submit(_chain(), {"a": _prompt(4)})
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                     recursive=True)[0]
    got = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("compass."):
                    got.setdefault(e.name, []).append(dict(e.stats))
    assert sorted(got) == sorted("compass." + n for n in (
        "request", "plan", "state", "task", "task_setup", "prefill",
        "decode", "sync", "readback"))
    assert [s["job"] for s in got["compass.request"]] == [r.job_id]
    assert [s["calls"] for s in got["compass.prefill"]] == [4, DECODE]
    assert [s["calls"] for s in got["compass.decode"]] == [DECODE, DECODE]
    assert {s["task"] for s in got["compass.task"]} == {"a", "b"}
    assert all(s["job"] == r.job_id for v in got.values() for s in v)


def test_step_program_has_a_stable_name(hosted):
    from repro.models import init_cache

    sc = _cluster(hosted, trace=False)
    cache = init_cache(hosted.cfg, 1, capacity=8)
    text = sc.engine.decode_fn(0).lower(
        hosted.params, cache, np.zeros((1,), np.int32)).as_text()
    assert text.split("{", 1)[0].split("@", 1)[1].split()[0] == \
        "jit_served_decode_step"
