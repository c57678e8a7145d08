"""Per-layer numbers read from the served path's own spans (``compass.*``)
in a device trace.

``load`` reads a trace as ``chipbench.trace.load`` does and keeps, besides,
what the program's spans need (``SpanTrace``):

* ``program``: the served path's own spans (``compass.*``), with the
  stats the program gave each;
* ``module_runs[d]``: each execution on device ``d`` by its ``run_id``;
* ``launches``: ``run_id`` -> when the host launched that execution;
* ``launch_waits``: host intervals in which the runtime held a launch
  back until the device queue had room (``LAUNCH_WAIT``).

Each model task is a ``compass.task`` span holding, in order,
``task_setup``, ``prefill``, ``decode``, ``sync`` and ``readback``;
``prefill`` and ``decode`` time the dispatch of their step calls (stat
``calls``), which the device runs later.  A device execution belongs to
the phase whose span was open when the host launched it: the execution's
``run_id`` names its launch (``SpanTrace.launches``).  Where the trace has
no such link, executions go by order inside the task instead: the device
runs one stream in order, the task ends after ``sync``, so every
execution that starts inside the task is the task's, and its first
``calls`` executions of the step program are the prefill's; an execution
of another program goes with the next step execution after it, and those
after the last step execution with the decode.

Host time in the dispatch spans leaves out the runtime's waits for room
in the device queue (``SpanTrace.launch_waits``): while the device binds,
the host runs about 13 calls ahead and then each launch waits for the
device.

Every function returns None on a trace without program spans.
"""

from __future__ import annotations

import bisect
import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

from chipbench import trace
from chipbench.trace import Span, Trace

PROGRAM_PREFIX = "compass."
# The runtime's preparation of a launch (``CommonPjRtLoadedExecutable::
# ExecutePrepare``): with the device queue full it blocks there, before
# its first child event, until the device frees a slot.
LAUNCH_WAIT = "ExecutePrepare"

# The spans inside ``request`` an idle gap may fall in, innermost first.
INNER_SPANS = ("first_call", "task_setup", "prefill", "decode", "sync",
               "readback", "state", "plan", "task")
# An idle gap whose middle finds the host held in the runtime's launch
# wait (``SpanTrace.launch_waits``): the device drained its queue while the
# runtime still counted it full, which is not the program's host work.
RUNTIME_WAIT = "runtime launch wait"


@dataclasses.dataclass(frozen=True)
class ProgramSpan(Span):
    stats: Dict[str, Any] = dataclasses.field(default_factory=dict,
                                              compare=False)


@dataclasses.dataclass
class SpanTrace(Trace):
    program: List[ProgramSpan] = dataclasses.field(default_factory=list)
    module_runs: Dict[str, Dict[int, Span]] = dataclasses.field(
        default_factory=dict)
    launches: Dict[int, float] = dataclasses.field(default_factory=dict)
    launch_waits: List[Span] = dataclasses.field(default_factory=list)

    def program_spans(self, name: str) -> List[ProgramSpan]:
        return [s for s in self.program if s.name == PROGRAM_PREFIX + name]


def load(path: str) -> SpanTrace:
    """``trace.load(path)``, and what the program's spans need besides."""
    from jax.profiler import ProfileData

    base = trace.load(path)
    program: List[ProgramSpan] = []
    module_runs: Dict[str, Dict[int, Span]] = {}
    launches: Dict[int, float] = {}
    launch_waits: List[Span] = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name != "XLA Modules":
                    continue
                runs = module_runs.setdefault(plane.name, {})
                for e in line.events:
                    rid = dict(e.stats).get("run_id")
                    if rid is not None:
                        runs[int(rid)] = Span(
                            e.name, e.start_ns, e.start_ns + e.duration_ns)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                program.extend(
                    ProgramSpan(e.name, e.start_ns, e.start_ns + e.duration_ns,
                                dict(e.stats))
                    for e in line.events if e.name.startswith(PROGRAM_PREFIX)
                )
            for rid, t in _launch_times(plane).items():
                launches[rid] = min(t, launches.get(rid, t))
            launch_waits.extend(_launch_waits(plane))
    program.sort(key=lambda s: (s.start_ns, -s.end_ns))
    return SpanTrace(ops=base.ops, modules=base.modules, host=base.host,
                     program=program, module_runs=module_runs,
                     launches=launches, launch_waits=launch_waits)


def _launch_times(plane) -> Dict[int, float]:
    """``run_id`` -> start of the host event that launched it.

    Host events that carry a ``run_id`` may lie on a runtime thread, after
    the launch.  Flows lead back from them: an event (or the innermost
    event open around it on its line) that consumes a flow (stats ``_ct``,
    ``_c``) was caused by the event that produced it (``_pt``, ``_p``).
    The launch is the earliest event such a chain reaches; where no flow
    leads anywhere, it is the event that carries the ``run_id``."""
    produced: Dict[Tuple[Any, Any], Tuple[float, Any]] = {}
    carriers: List[Tuple[int, float, Any]] = []
    for line in plane.lines:
        open_: List[Tuple[float, Any]] = []  # (end, flow consumed) per level
        evs = sorted(((e.start_ns, e.duration_ns, dict(e.stats))
                      for e in line.events), key=lambda x: (x[0], -x[1]))
        for start, dur, st in evs:
            while open_ and open_[-1][0] <= start:
                open_.pop()
            consumed = (st["_ct"], st["_c"]) if "_c" in st else None
            cause = consumed
            for _, c in reversed(open_):
                if cause is not None:
                    break
                cause = c
            if "_p" in st:
                produced[(st.get("_pt"), st["_p"])] = (start, cause)
            if "run_id" in st:
                carriers.append((int(st["run_id"]), start, cause))
            open_.append((start + dur, consumed))

    def origin(t: float, cause: Any) -> float:
        for _ in range(16):  # a chain is a few hops long
            if cause not in produced:
                break
            t, cause = produced[cause]
        return t

    out: Dict[int, float] = {}
    for rid, start, cause in carriers:
        t = origin(start, cause)
        out[rid] = min(t, out.get(rid, t))
    return out


def _launch_waits(plane) -> List[Span]:
    """The parts of each ``LAUNCH_WAIT`` event on ``plane`` that none of
    its child events on its line cover: its own time, which is the wait
    for room in the device queue (a few microseconds when there is room)."""
    out: List[Span] = []
    for line in plane.lines:
        evs = sorted(((e.name, e.start_ns, e.start_ns + e.duration_ns)
                      for e in line.events), key=lambda x: (x[1], -x[2]))
        for i, (name, a, b) in enumerate(evs):
            if not name.endswith(LAUNCH_WAIT):
                continue
            kids = []
            j = i + 1
            while j < len(evs) and evs[j][1] < b:
                if evs[j][2] <= b:
                    kids.append(Span(*evs[j]))
                j += 1
            out.extend(Span(name, x, y) for x, y in trace.gaps(trace.union(kids), (a, b)))
    return out


def _inside(outer: Span, spans: Sequence[ProgramSpan]) -> List[ProgramSpan]:
    return [s for s in spans
            if s.start_ns >= outer.start_ns and s.end_ns <= outer.end_ns]


def phase_executions(tr: SpanTrace, step_module: Optional[str]
                     ) -> Optional[List[Dict[str, List[Span]]]]:
    """Per ``compass.task`` span: ``{"prefill": [...], "decode": [...]}``,
    the device executions each phase launched; None without tasks or
    without device executions."""
    tasks = tr.program_spans("task")
    if not tasks or not any(tr.module_runs.values()):
        return None
    prefills, decodes = tr.program_spans("prefill"), tr.program_spans("decode")
    runs = {rid: s for r in tr.module_runs.values() for rid, s in r.items()}
    linked = [(t, rid) for rid, t in tr.launches.items() if rid in runs]
    out = []
    if linked:
        linked.sort()
        times = [t for t, _ in linked]

        def launched(span: Span) -> List[Span]:
            lo = bisect.bisect_left(times, span.start_ns)
            hi = bisect.bisect_right(times, span.end_ns)
            return [runs[rid] for _, rid in linked[lo:hi]]

        for task in tasks:
            out.append({
                "prefill": [x for p in _inside(task, prefills)
                            for x in launched(p)],
                "decode": [x for d in _inside(task, decodes)
                           for x in launched(d)],
            })
        return out
    if step_module is None:
        return None
    execs = sorted(runs.values(), key=lambda s: s.start_ns)
    for task in tasks:
        mine = [s for s in execs if task.start_ns <= s.start_ns <= task.end_ns]
        calls = sum(int(p.stats.get("calls", 0))
                    for p in _inside(task, prefills))
        phases: Dict[str, List[Span]] = {"prefill": [], "decode": []}
        pending: List[Span] = []
        steps = 0
        for s in mine:
            pending.append(s)
            if trace.module_matches(s.name, step_module):
                steps += 1
                phases["prefill" if steps <= calls else "decode"] += pending
                pending = []
        phases["decode"] += pending
        out.append(phases)
    return out


def _ms(spans: Sequence[Span]) -> float:
    return sum(s.dur_ns for s in spans) / 1e6


def prefill_ms(tr: SpanTrace, step_module: Optional[str]) -> Optional[float]:
    """Device ms of the executions the prefill phases launched, over the
    prompt positions of their tasks (stat ``prompt``; today one step call
    each)."""
    per = phase_executions(tr, step_module)
    positions = sum(int(t.stats.get("prompt", 0))
                    for t in tr.program_spans("task"))
    if not per or not positions:
        return None
    return sum(_ms(p["prefill"]) for p in per) / positions


def decode_token_ms(tr: SpanTrace, step_module: Optional[str]) -> Optional[float]:
    """Device ms of the executions the decode phases launched (a step and
    an argmax per generated token), over the phases' ``calls``."""
    per = phase_executions(tr, step_module)
    calls = sum(int(s.stats.get("calls", 0))
                for s in tr.program_spans("decode"))
    if not per or not calls:
        return None
    return sum(_ms(p["decode"]) for p in per) / calls


def host_dispatch_ms(tr: SpanTrace) -> Optional[float]:
    """Host ms per step call: the prefill and decode spans' time, less the
    runtime's waits for room in the device queue inside them, over their
    ``calls``."""
    spans = tr.program_spans("prefill") + tr.program_spans("decode")
    calls = sum(int(s.stats.get("calls", 0)) for s in spans)
    if not calls:
        return None
    waited = trace.length(trace.intersect(trace.union(spans),
                                          trace.union(tr.launch_waits)))
    return (sum(s.dur_ns for s in spans) - waited) / 1e6 / calls


def idle_split(tr: SpanTrace) -> Optional[Dict[str, float]]:
    """Device idle ns inside ``compass.request`` spans, by what the host
    was in at the middle of each gap: a runtime launch wait, else the
    innermost program span."""
    requests = trace.union(tr.program_spans("request"))
    if not requests or not tr.ops:
        return None
    busy = trace.union(s for spans in tr.ops.values() for s in spans)
    cover = [(RUNTIME_WAIT, trace.union(tr.launch_waits))] + [
        (name, trace.union(tr.program_spans(name))) for name in INNER_SPANS]
    starts = {name: [a for a, _ in iv] for name, iv in cover}
    tot: Dict[str, float] = {}
    for window in requests:
        for a, b in trace.gaps(busy, window):
            mid = (a + b) / 2
            label = "request"  # in none of the spans inside it
            for name, iv in cover:
                k = bisect.bisect_right(starts[name], mid) - 1
                if k >= 0 and iv[k][1] > mid:
                    label = name
                    break
            tot[label] = tot.get(label, 0.0) + (b - a)
    return tot


def engine_idle_ms(tr: SpanTrace) -> Optional[float]:
    """Device idle ms per task inside ``compass.request`` spans while the
    host did the program's own work (``idle_split`` says which), not the
    runtime's launch waits."""
    split = idle_split(tr)
    tasks = tr.program_spans("task")
    if split is None or not tasks:
        return None
    own = sum(v for k, v in split.items() if k != RUNTIME_WAIT)
    return own / 1e6 / len(tasks)
