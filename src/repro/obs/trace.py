"""Span/event tracer on the injectable-clock convention (DESIGN.md §12).

A ``Trace`` records a forest of nested ``Span``s — wall-clock intervals
with a dotted name and static attributes — plus point-in-time events.
Timestamps come from one injectable ``clock()`` callable exactly like
the streaming service's latency stamps (serve/clock.py): the default is
``time.perf_counter``; tests inject a ``ManualClock`` and assert span
durations against exact values instead of wall-clock noise.

Every span also opens a ``jax.profiler.TraceAnnotation`` of the same
name, with or without a ``Trace`` installed: while the JAX profiler
runs, each span lands in its ``.xplane.pb`` on the calling thread's host
line, on the clock of the device planes, so an idle stretch of the
device can be put down to the span the host was in. The profiler sees
the bare name only: names are a stable schema and carry no per-call
values (those are attributes of the ``Span``), so a trace groups by
name. With the profiler off an annotation costs about a microsecond.

Span naming scheme (the contract DESIGN.md §12 documents):

  session.run / session.prepare / session.chunk — the engine drivers;
      ``session.chunk`` carries ``branch``/``count``/``cap`` attrs
  session.iter — one iteration of the host loop or the sharded Pipe
      (``mode``/``count`` attrs); in the host loop the parent of
      session.resize (the worklist moves to a smaller capacity bucket),
      session.dispatch (the jitted step call) and session.readback (the
      one read-back); session.finalize — after the host loop, the copy
      of the colors to the host and the algorithm's finalize
  batch.run / batch.dispatch — the barrier batch (exec/batch.py)
  stream.pump / stream.dispatch — the continuous-batching service
  tune.sweep / tune.candidate — the tile autotuner (kernels/tune.py)
  obs.profile — launch/gather/exchange profiling (eval_shape, no
      device execution)

``to_chrome()`` exports the Chrome trace-event JSON format (complete
``"X"`` events with microsecond ``ts``/``dur``, instants as ``"i"``),
loadable directly in Perfetto / ``chrome://tracing``.

Deep code attaches spans without threading a trace argument through
every signature via the AMBIENT trace: ``tracing(trace)`` installs a
trace for the dynamic extent of a block, ``maybe_span(name, **attrs)``
opens a span on the innermost installed trace — or only the profiler
annotation when none is installed.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time

from jax.profiler import TraceAnnotation


@dataclasses.dataclass
class Span:
    """One timed interval: name, [start, end), static attrs, children."""

    name: str
    start: float
    end: "float | None" = None
    attrs: dict = dataclasses.field(default_factory=dict)
    children: list = dataclasses.field(default_factory=list)

    @property
    def seconds(self) -> "float | None":
        return None if self.end is None else self.end - self.start

    def walk(self):
        yield self
        for c in self.children:
            yield from c.walk()


@dataclasses.dataclass
class Event:
    """One instantaneous marker."""

    name: str
    ts: float
    attrs: dict = dataclasses.field(default_factory=dict)


class Trace:
    """A span forest + event list with one injectable timestamp source."""

    def __init__(self, clock=None):
        self.clock = clock or time.perf_counter
        self.spans: list[Span] = []     # roots
        self.events: list[Event] = []
        self._stack: list[Span] = []

    @contextlib.contextmanager
    def span(self, name: str, **attrs):
        with TraceAnnotation(name):
            sp = Span(name=name, start=self.clock(), attrs=attrs)
            parent = self._stack[-1] if self._stack else None
            (parent.children if parent else self.spans).append(sp)
            self._stack.append(sp)
            try:
                yield sp
            finally:
                self._stack.pop()
                sp.end = self.clock()

    def event(self, name: str, **attrs) -> Event:
        ev = Event(name=name, ts=self.clock(), attrs=attrs)
        self.events.append(ev)
        return ev

    def walk(self):
        """Depth-first over every span in the forest."""
        for sp in self.spans:
            yield from sp.walk()

    def find(self, name: str) -> list[Span]:
        """Every span with this exact name, depth-first order."""
        return [sp for sp in self.walk() if sp.name == name]

    def to_chrome(self) -> dict:
        """Chrome trace-event JSON (the "trace events" array format).

        Complete spans become ``ph: "X"`` duration events with
        microsecond ``ts``/``dur`` relative to the trace's earliest
        timestamp; events become thread-scoped instants (``ph: "i"``).
        The dict round-trips through ``json.dump`` straight into
        Perfetto / ``chrome://tracing``.
        """
        stamps = [sp.start for sp in self.walk()] + \
            [ev.ts for ev in self.events]
        t0 = min(stamps) if stamps else 0.0
        out = []
        for sp in self.walk():
            dur = 0.0 if sp.end is None else sp.end - sp.start
            out.append({"name": sp.name, "cat": "repro", "ph": "X",
                        "ts": (sp.start - t0) * 1e6, "dur": dur * 1e6,
                        "pid": 0, "tid": 0, "args": dict(sp.attrs)})
        for ev in self.events:
            out.append({"name": ev.name, "cat": "repro", "ph": "i",
                        "ts": (ev.ts - t0) * 1e6, "s": "t",
                        "pid": 0, "tid": 0, "args": dict(ev.attrs)})
        return {"traceEvents": out, "displayTimeUnit": "ms"}


# ---------------------------------------------------------------------------
# ambient trace — instrumentation points without signature threading
# ---------------------------------------------------------------------------

_AMBIENT: list[Trace] = []


def current_trace() -> "Trace | None":
    """The innermost trace installed by ``tracing()``, or None."""
    return _AMBIENT[-1] if _AMBIENT else None


@contextlib.contextmanager
def tracing(trace: Trace):
    """Install ``trace`` as the ambient trace for the block. Nests —
    the innermost installation wins, restored on exit."""
    _AMBIENT.append(trace)
    try:
        yield trace
    finally:
        _AMBIENT.pop()


class _Annotation:
    """The profiler annotation alone, entered as a span that records
    nothing: ``with maybe_span(...) as sp`` binds None, as callers that
    attach attributes only to a real span expect."""

    __slots__ = ("_ann",)

    def __init__(self, name: str):
        self._ann = TraceAnnotation(name)

    def __enter__(self) -> None:
        self._ann.__enter__()

    def __exit__(self, *exc):
        return self._ann.__exit__(*exc)


def maybe_span(name: str, **attrs):
    """A span on the ambient trace, or, when no trace is installed, the
    profiler annotation alone (``attrs`` then go nowhere)."""
    tr = current_trace()
    return _Annotation(name) if tr is None else tr.span(name, **attrs)
