"""Spans and compile counters for the serving step loop.

Every ``Engine`` owns one ``Telemetry`` (``eng.telemetry``).
``span(name)`` times a region on ``time.perf_counter``; while a profiler
is recording it also opens a ``jax.profiler.TraceAnnotation`` of the
same name, so the span lands on the host plane of the device trace, on
the clock of the device's op events, with its attributes as the event's
metadata.  With no profiler recording, the annotation is skipped.

Per span name the recorder keeps running totals, always on
(``SpanTotals``): count, seconds and the compiles charged to the span;
a span opened with ``lead=True`` also sums its lead, the time from its
parent's start to its own.  ``capture(True)`` keeps each span's record
with its attributes until ``capture(False)`` hands them back, so
nothing grows while capture is off.

Compiles: one process-wide ``jax.monitoring`` listener charges each
backend compile (a persistent-cache load records one too) to the
innermost open span of the thread that compiled.  JAX compiles
synchronously in the dispatching thread, so that span is the caller.
``compile_totals()`` reads the process-wide counts, with
persistent-cache hits.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Dict, List, Optional, Tuple

import jax

__all__ = ["SpanTotals", "Telemetry", "compile_totals"]

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

_clock = time.perf_counter
_profiling = jax.profiler.TraceAnnotation.is_enabled  # a profiler records


@dataclasses.dataclass
class SpanTotals:
    count: int = 0
    seconds: float = 0.0
    lead_s: float = 0.0               # spans opened with ``lead=True``
    compiles: int = 0


class _Stack(threading.local):
    def __init__(self):
        self.open: list = []


class _Process:
    """Process-wide compile counts and each thread's open spans."""

    def __init__(self):
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        self.local = _Stack()
        self.installed = False

    def install(self) -> None:
        if not self.installed:
            self.installed = True
            jax.monitoring.register_event_duration_secs_listener(
                self._duration)
            jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_) -> None:
        if event != COMPILE_EVENT:
            return
        self.compiles += 1
        self.compile_s += duration
        st = self.local.open
        if st:
            st[-1].compiles += 1

    def _event(self, event: str, **_) -> None:
        if event == CACHE_HIT_EVENT:
            self.cache_hits += 1


_PROCESS = _Process()
_LOCAL = _PROCESS.local


def compile_totals() -> Tuple[int, float, int]:
    """(compiles, compile seconds, persistent-cache hits) seen by this
    process since the counters were installed (the first ``Telemetry``
    or the first call here)."""
    _PROCESS.install()
    return _PROCESS.compiles, _PROCESS.compile_s, _PROCESS.cache_hits


class Span:
    """One span; ``recording`` says whether anyone reads its attributes
    (a recording profiler or capture), and ``set(**attrs)`` keeps them
    only then."""

    __slots__ = ("tel", "name", "lead_from", "attrs", "t0", "t1",
                 "compiles", "stack", "ann", "recording")

    def __init__(self, tel: "Telemetry", name: str, lead: bool):
        self.tel, self.name, self.lead_from = tel, name, lead
        self.attrs: dict = {}
        self.compiles = 0

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    def set(self, **attrs) -> None:
        if self.recording:
            self.attrs.update(attrs)

    def __enter__(self) -> "Span":
        self.stack = st = _LOCAL.open
        # the parent's start, for the lead; None when no lead is kept
        self.lead_from = st[-1].t0 if self.lead_from else None
        st.append(self)
        if _profiling():
            self.ann = ann = jax.profiler.TraceAnnotation(self.name)
            ann.__enter__()
            self.recording = True
        else:
            self.ann = None
            self.recording = self.tel.records is not None
        self.t0 = _clock()
        return self

    def __exit__(self, *exc) -> None:
        self.t1 = t1 = _clock()
        self.stack.pop()
        tot = self.tel._totals.get(self.name) or self.tel._entry(self.name)
        tot.count += 1
        tot.seconds += t1 - self.t0
        if self.lead_from is not None:
            tot.lead_s += self.t0 - self.lead_from
        if self.compiles:
            tot.compiles += self.compiles
        if self.recording:
            if self.compiles:
                self.attrs["compiles"] = self.compiles
            if self.ann is not None:
                if self.attrs:
                    self.ann.set_metadata(**self.attrs)
                self.ann.__exit__(*exc)
            records = self.tel.records
            if records is not None:
                records.append(dict(self.attrs, name=self.name,
                                    t0=self.t0, t1=t1))


class Telemetry:
    """Span recorder of one engine; see the module docstring."""

    def __init__(self):
        _PROCESS.install()
        self._totals: Dict[str, SpanTotals] = {}
        self.records: Optional[List[dict]] = None

    def span(self, name: str, *, lead: bool = False) -> Span:
        """A span named ``name``; with ``lead`` (only inside another
        span) its totals also sum the time from the parent's start."""
        return Span(self, name, lead)

    def totals(self, name: str) -> SpanTotals:
        """Running totals of the spans named ``name`` (zeros if none
        closed yet)."""
        return self._totals.get(name) or SpanTotals()

    def _entry(self, name: str) -> SpanTotals:
        tot = self._totals[name] = SpanTotals()
        return tot

    def capture(self, on: bool) -> List[dict]:
        """Start keeping span records (``on``), or stop and return the
        records kept since capture was turned on."""
        out = self.records or []
        self.records = [] if on else None
        return out
