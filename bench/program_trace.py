"""The program's own spans in a profiler trace, and the device's idle time
attributed to them.

The step loop opens ``serve.*`` spans (``repro.serve.telemetry``) that
land on the host plane of the same ``.xplane.pb`` ``bench/trace.py``
reduces, with their attributes as event stats.  The traced stretch and
the device's busy time are read as there: the host span
``bench.trace_window``, and the union of the op events on each device
plane's op line.  Each idle interval of the stretch goes to the
innermost ``serve.*`` span covering its midpoint, or to ``BETWEEN`` when
none does (the harness's own work between steps).  A trace with no
``serve.*`` span (a program without them) reduces to nothing.
"""

from __future__ import annotations

import bisect
import dataclasses
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Tuple

from bench.trace import OP_LINES, WINDOW_SPAN, _clip, _union, find_xplane

SPAN_PREFIX = "serve."
BETWEEN = "between steps"
# the address space and transfer plane's share of a step: everything the
# step loop does on the host but the prefill, the decode and retirement
MANAGER_SPANS = ("serve.fence", "serve.compact", "serve.admit",
                 "serve.grow", "serve.transfer", "serve.sync")


@dataclasses.dataclass
class HostSpan:
    name: str
    start: int                        # ns, on the trace's clock
    end: int
    attrs: dict
    self_ns: int = 0                  # less the time of child spans


@dataclasses.dataclass
class ProgramTrace:
    window_s: float
    spans: List[HostSpan]             # started in the stretch, by start
    idle_s: Dict[str, float]          # device idle by owner, mean of devices

    def count(self, name: str) -> int:
        return sum(1 for s in self.spans if s.name == name)

    def self_seconds(self, names: Iterable[str]) -> float:
        names = set(names)
        return sum(s.self_ns for s in self.spans if s.name in names) * 1e-9

    def attr_sum(self, name: str, key: str) -> float:
        return sum(s.attrs.get(key, 0) for s in self.spans if s.name == name)

    def idle_share(self, owner: str) -> float:
        return self.idle_s.get(owner, 0.0) / self.window_s


def _nest(lines: List[List[HostSpan]]) -> None:
    """Self time of each span: spans of one thread's line nest."""
    for spans in lines:
        stack: List[HostSpan] = []
        for s in sorted(spans, key=lambda s: (s.start, -s.end)):
            while stack and stack[-1].end <= s.start:
                stack.pop()
            s.self_ns = s.end - s.start
            if stack and s.end <= stack[-1].end:
                stack[-1].self_ns -= s.end - s.start
            stack.append(s)


def build(window: Tuple[int, int], lines: List[List[HostSpan]],
          device_ops: List[List[Tuple[int, int]]]) -> Optional[ProgramTrace]:
    """The reduction, from the stretch, the ``serve.*`` spans of each
    host line and the op intervals of each device."""
    if not any(lines) or not device_ops:
        return None
    w0, w1 = window
    _nest(lines)
    spans = sorted((s for line in lines for s in line if w0 <= s.start <= w1),
                   key=lambda s: s.start)
    starts = [s.start for s in spans]

    def owner(mid: int) -> str:
        for i in range(bisect.bisect_right(starts, mid) - 1, -1, -1):
            if spans[i].end >= mid:
                return spans[i].name
        return BETWEEN

    idle: Dict[str, float] = defaultdict(float)
    for evs in device_ops:
        merged = _union([c for c in (_clip(s, e, w0, w1) for s, e in evs)
                         if c])
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for i in range(0, len(edges), 2):
            s, e = edges[i], edges[i + 1]
            if e > s:
                idle[owner((s + e) // 2)] += (e - s) * 1e-9 / len(device_ops)
    return ProgramTrace((w1 - w0) * 1e-9, spans, dict(idle))


def read(path: str):
    """(stretch, ``serve.*`` spans of each host line, op intervals of
    each device) of a trace file; the stretch is None without the
    window span."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    window = None
    lines: List[List[HostSpan]] = []
    device_ops: List[List[Tuple[int, int]]] = []
    for plane in data.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            evs = [(ev.start_ns, ev.end_ns) for line in plane.lines
                   if line.name in OP_LINES for ev in line.events]
            if evs:
                device_ops.append(evs)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans = []
                for ev in line.events:
                    if ev.name == WINDOW_SPAN and window is None:
                        window = (ev.start_ns, ev.end_ns)
                    elif ev.name.startswith(SPAN_PREFIX):
                        spans.append(HostSpan(ev.name, ev.start_ns,
                                              ev.end_ns, dict(ev.stats)))
                if spans:
                    lines.append(spans)
    return window, lines, device_ops


def reduce(path: str) -> Optional[ProgramTrace]:
    window, lines, device_ops = read(path)
    return None if window is None else build(window, lines, device_ops)


_CACHE: Dict[str, Optional[ProgramTrace]] = {}


def for_ctx(ctx) -> Optional[ProgramTrace]:
    """The reduction of a traced run's trace (read once per run), or
    nothing for an untraced run."""
    if ctx.trace is None:
        return None
    from bench.harness import TRACE_DIR
    path = find_xplane(str(TRACE_DIR))
    if path not in _CACHE:
        _CACHE[path] = reduce(path)
    return _CACHE[path]
