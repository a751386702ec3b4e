"""Spans: the program's one timing mechanism, on the profiler's clock.

:func:`span` wraps a host-side stage in a ``jax.profiler.TraceAnnotation``
named ``jag.<name>``. Under ``jax.profiler.trace`` (or ``start_trace``)
the span lands on the profiler's host plane, on the same clock as the
device programs it launches, so a trace opened in Perfetto or TensorBoard
shows each stage beside the device ops it caused and the idle gaps
between them. Outside a trace an annotation costs about a microsecond, so
spans are always emitted, whether or not telemetry is attached.

Spans sit on the host around compiled calls, never inside a traced
function (rule JAG006): a span inside ``jax.jit`` would time tracing, not
execution. Nesting is by time on one thread. The names in use:

=====================  ==================================================
``search_auto``        one request (``request=<n>``: the executor's count)
``plan``               the planner; children ``plan.probe`` (sample ids,
                       estimate launch) and ``plan.band`` (host banding)
``gather:<route>``     one route group's query and filter gather
``execute:<route>``    one route group's launch
``scatter``            the regroup into query order
``delta``, ``merge``   a streaming index's delta scan and its merge
``sync:<site>``        a device->host read (``planner``, ``reorder``,
                       ``finalize``)
``jit:<program>``      the first call of a jitted function: trace,
                       compile (or cache load) and launch
``compact``            a streaming compaction; children
                       ``compact.prepare``, ``compact.insert``,
                       ``compact.finalize``, ``compact.reprune``
=====================  ==================================================

A :class:`SpanRecorder` passed to :func:`span` also keeps the span as a
host-clock :class:`Span` record, which ``Telemetry(spans=True)`` reads.

This module imports only ``jax`` and the standard library, so that every
layer can import it.
"""
from __future__ import annotations

import time
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Dict, List, Optional

from jax.profiler import TraceAnnotation

PREFIX = "jag."


@contextmanager
def span(name: str, recorder: Optional["SpanRecorder"] = None, **args):
    """Time a host-side stage as the profiler span ``jag.<name>``; record
    it in ``recorder`` too when one is given. ``args`` become the trace
    event's arguments."""
    with TraceAnnotation(PREFIX + name, **args):
        if recorder is None:
            yield
        else:
            with recorder.span(name, **args):
                yield


@dataclass(frozen=True)
class Span:
    """One completed pipeline stage."""

    name: str
    t0: float                  # seconds since the recorder's origin
    t1: float
    depth: int                 # nesting depth at entry (0 = top level)
    parent: Optional[str]      # enclosing span's name, if any
    args: Dict[str, object] = field(default_factory=dict)

    @property
    def duration_us(self) -> float:
        return (self.t1 - self.t0) * 1e6


class SpanRecorder:
    """Bounded recorder of nested host-side spans.

    Appends are O(1); once ``capacity`` spans are held the oldest are
    evicted (``dropped`` counts them).  Reentrant nesting is tracked
    with an explicit stack, so recording is single-threaded like the
    rest of the serving loop.
    """

    def __init__(self, capacity: int = 8192):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = int(capacity)
        self.spans: List[Span] = []
        self.dropped = 0
        self._stack: List[str] = []
        self._origin = time.perf_counter()

    @contextmanager
    def span(self, name: str, **args):
        """Time a pipeline stage; nest freely."""
        depth = len(self._stack)
        parent = self._stack[-1] if self._stack else None
        self._stack.append(name)
        t0 = time.perf_counter() - self._origin
        try:
            yield self
        finally:
            t1 = time.perf_counter() - self._origin
            self._stack.pop()
            self.spans.append(Span(name, t0, t1, depth, parent, dict(args)))
            if len(self.spans) > self.capacity:
                drop = len(self.spans) - self.capacity
                del self.spans[:drop]
                self.dropped += drop

    def clear(self) -> None:
        self.spans.clear()
        self.dropped = 0

    def totals_us(self) -> Dict[str, float]:
        """Summed wall time per span name, microseconds."""
        out: Dict[str, float] = {}
        for s in self.spans:
            out[s.name] = out.get(s.name, 0.0) + s.duration_us
        return out


__all__ = ["PREFIX", "Span", "SpanRecorder", "span"]
