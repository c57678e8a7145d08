"""Reduce a profiler trace to intervals the metric readers use.

A trace is read once into plain lists of ``Span(name, start_ns, end_ns)``:

* ``ops[d]``: operations that ran on device ``d`` (the "XLA Ops" line of
  each ``/device:TPU:n`` plane);
* ``modules[d]``: executions of whole compiled programs on device ``d``
  (the "XLA Modules" line);
* ``host``: the harness's own annotations (``chipbench.*``) on the host.

All times are on the profiler's one clock, so host spans and device
intervals can be intersected.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
from typing import Dict, Iterable, List, Sequence, Tuple

HOST_PREFIX = "chipbench."


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start_ns: float
    end_ns: float

    @property
    def dur_ns(self) -> float:
        return self.end_ns - self.start_ns


@dataclasses.dataclass
class Trace:
    ops: Dict[str, List[Span]]
    modules: Dict[str, List[Span]]
    host: List[Span]

    def host_spans(self, name: str) -> List[Span]:
        return [s for s in self.host if s.name == HOST_PREFIX + name]


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops: Dict[str, List[Span]] = {}
    modules: Dict[str, List[Span]] = {}
    host: List[Span] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                dest = {"XLA Ops": ops, "XLA Modules": modules}.get(line.name)
                if dest is None:
                    continue
                dest.setdefault(plane.name, []).extend(
                    Span(e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events
                )
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(
                    Span(e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events if e.name.startswith(HOST_PREFIX)
                )
    return Trace(ops=ops, modules=modules, host=host)


def union(spans: Iterable[Span]) -> List[Tuple[float, float]]:
    """Merged, sorted, disjoint intervals covering ``spans``."""
    out: List[Tuple[float, float]] = []
    for s in sorted(spans, key=lambda s: s.start_ns):
        if out and s.start_ns <= out[-1][1]:
            if s.end_ns > out[-1][1]:
                out[-1] = (out[-1][0], s.end_ns)
        else:
            out.append((s.start_ns, s.end_ns))
    return out


def length(iv: Sequence[Tuple[float, float]]) -> float:
    return sum(b - a for a, b in iv)


def intersect(a: Sequence[Tuple[float, float]],
              b: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Intersection of two merged interval lists."""
    out = []
    i = j = 0
    while i < len(a) and j < len(b):
        lo = max(a[i][0], b[j][0])
        hi = min(a[i][1], b[j][1])
        if lo < hi:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def gaps(iv: Sequence[Tuple[float, float]],
         within: Tuple[float, float]) -> List[Tuple[float, float]]:
    """The parts of ``within`` that ``iv`` leaves uncovered."""
    out = []
    t = within[0]
    for a, b in iv:
        if b <= t:
            continue
        if a > t:
            out.append((t, min(a, within[1])))
        t = max(t, b)
        if t >= within[1]:
            break
    if t < within[1]:
        out.append((t, within[1]))
    return [(a, b) for a, b in out if b > a]


def module_matches(event_name: str, module: str) -> bool:
    """A trace names a program execution ``<module>`` or ``<module>(<id>)``
    or ``<module>.<n>``."""
    return (event_name == module or event_name.startswith(module + "(")
            or event_name.startswith(module + "."))


def step_durations_ns(tr: Trace, module: str) -> List[float]:
    return [s.dur_ns for spans in tr.modules.values() for s in spans
            if module_matches(s.name, module)]


def busy_in(tr: Trace, window: Sequence[Tuple[float, float]]) -> float:
    """Device-busy nanoseconds inside ``window``, averaged over devices."""
    if not tr.ops:
        return 0.0
    per = [length(intersect(union(spans), window)) for spans in tr.ops.values()]
    return sum(per) / len(per)


def top_ops(tr: Trace, n: int = 10) -> List[Tuple[str, float]]:
    tot: Dict[str, float] = {}
    for spans in tr.ops.values():
        for s in spans:
            tot[s.name] = tot.get(s.name, 0.0) + s.dur_ns
    k = max(1, len(tr.ops))
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / k / 1e9] for name, ns in best]


def idle_by_host(tr: Trace, window: Tuple[float, float],
                 labels: Sequence[str], n: int = 10) -> List[Tuple[str, float]]:
    """Idle device time inside ``window``, summed by what the host was
    doing at the middle of each gap: the first of ``labels`` (harness span
    names, innermost first) whose spans cover it."""
    if not tr.ops:
        return []
    busy = union(s for spans in tr.ops.values() for s in spans)
    cover = [(name, union(tr.host_spans(name))) for name in labels]
    starts = {name: [a for a, _ in iv] for name, iv in cover}
    tot: Dict[str, float] = {}
    for a, b in gaps(busy, window):
        mid = (a + b) / 2
        label = "outside harness spans"
        for name, iv in cover:
            k = bisect.bisect_right(starts[name], mid) - 1
            if k >= 0 and iv[k][1] > mid:
                label = name
                break
        tot[label] = tot.get(label, 0.0) + (b - a)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in best]
