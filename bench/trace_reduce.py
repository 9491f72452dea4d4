"""From a profiler trace (``.xplane.pb``) to the numbers the benchmark
reports: device busy time and idle share, device time per program, the
longest idle stretches labelled by what the host was doing, and the
``breakdown`` of the result line.

What a TPU trace holds, as JAX writes it:

* one plane per chip, ``/device:TPU:<i>``, with a line ``XLA Modules``
  (one event per program execution, named ``jit_<function>(<hash>)``)
  and a line ``XLA Ops`` (one event per operation, named by its HLO
  text, ``%fusion.3 = s32[...] fusion(...)``);
* the plane ``/host:CPU``, whose lines are host threads; the Python
  threads' lines are named ``python`` and carry the benchmark's own
  ``TraceAnnotation`` spans (``bench.window``, ``bench.color``, ...) and
  JAX's host events (``PjitFunction(<name>)``,
  ``np.asarray(jax.Array)``, ...).

Busy time is the union of the ``XLA Ops`` intervals inside the window
``bench.window``; the idle share is 1 minus busy over the window,
averaged over the chips. All times are in the trace's nanoseconds.
"""
from __future__ import annotations

import bisect
import dataclasses
import re
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
WINDOW = "bench.window"
SPAN_PREFIX = "bench."


class NoMatch(LookupError):
    """A per-layer metric found nothing in the trace where the run says
    there is something to read (a program renamed away, say). The run
    fails; the metric never reads 0."""


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float      # ns
    end: float        # ns


@dataclasses.dataclass
class Chip:
    modules: list     # Event per program execution, sorted by start
    ops: list         # Event per operation, sorted by start


@dataclasses.dataclass
class Trace:
    """The parts of a trace the reduction reads, in plain lists."""

    chips: list               # Chip per device plane, in plane order
    host: list                # Event on the Python threads' lines


def load(path) -> Trace:
    """Read one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    chips, host = [], []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {ln.name: ln for ln in plane.lines}
            chips.append(Chip(
                modules=_events(lines.get("XLA Modules")),
                ops=_events(lines.get("XLA Ops"))))
        elif plane.name == "/host:CPU":
            for ln in plane.lines:
                if ln.name == "python":
                    host.extend(_events(ln))
    host.sort(key=lambda e: e.start)
    return Trace(chips=chips, host=host)


def _events(line) -> list:
    if line is None:
        return []
    out = [Event(e.name, e.start_ns, e.start_ns + e.duration_ns)
           for e in line.events]
    out.sort(key=lambda e: e.start)
    return out


def reduce_dir(directory) -> "Reduction":
    """The reduction of the one trace under ``directory``."""
    found = sorted(Path(directory).rglob("*.xplane.pb"))
    if len(found) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {directory}, "
                           f"found {len(found)}")
    return Reduction(load(found[0]))


def program_name(module_event_name: str) -> str:
    """``jit_dense_step_impl(1512...)`` -> ``jit_dense_step_impl``."""
    return module_event_name.split("(", 1)[0]


def op_name(op_event_name: str) -> str:
    """``%fusion.3 = s32[...] fusion(...)`` -> ``fusion.3``."""
    return op_event_name.split(" = ", 1)[0].lstrip("%")


def merge(intervals) -> list:
    """Union of ``(start, end)`` intervals, sorted and disjoint."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo, hi) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


class Reduction:
    def __init__(self, trace: Trace):
        if not trace.chips:
            raise RuntimeError("the trace holds no TPU device plane")
        self.trace = trace
        win = [e for e in trace.host if e.name == WINDOW]
        if win:
            self.lo, self.hi = win[0].start, win[0].end
        else:      # no annotation: the span of the device's operations
            ops = [e for c in trace.chips for e in c.ops]
            self.lo = min(e.start for e in ops)
            self.hi = max(e.end for e in ops)
        self.busy = [merge(clip([(e.start, e.end) for e in c.ops],
                                self.lo, self.hi))
                     for c in trace.chips]
        self._spans = [e for e in trace.host
                       if e.name.startswith(SPAN_PREFIX)]
        self._other = [e for e in trace.host
                       if not e.name.startswith(SPAN_PREFIX)]
        self._starts = [e.start for e in self._other]
        self._reach, top = [], float("-inf")
        for e in self._other:           # latest end among events 0..j
            top = max(top, e.end)
            self._reach.append(top)

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) / 1e9

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        per_chip = [sum(e - s for s, e in b) for b in self.busy]
        return sum(per_chip) / len(per_chip) / 1e9

    def program_time(self, patterns) -> "tuple[float, float] | None":
        """``(executions, seconds)`` of the programs whose names hold one
        of ``patterns`` and that started inside the window, per chip
        (averaged over the chips); None when no program matches."""
        count = secs = 0.0
        for c in self.trace.chips:
            for e in c.modules:
                if self.lo <= e.start < self.hi and any(
                        p in program_name(e.name) for p in patterns):
                    count += 1
                    secs += (e.end - e.start) / 1e9
        if count == 0:
            return None
        n = len(self.trace.chips)
        return count / n, secs / n

    def gaps(self, chip: int = 0) -> list:
        """Idle stretches ``(start, end)`` of one chip inside the window."""
        out, t = [], self.lo
        for s, e in self.busy[chip]:
            if s > t:
                out.append((t, s))
            t = max(t, e)
        if self.hi > t:
            out.append((t, self.hi))
        return out

    def host_label(self, t: float) -> str:
        """What the host was doing at ``t``: the innermost benchmark span
        and the innermost other host event covering it, as
        ``bench.color > np.asarray(jax.Array)``."""
        span = min((e for e in self._spans if e.start <= t < e.end),
                   key=lambda e: e.end - e.start, default=None)
        other = None
        j = bisect.bisect_right(self._starts, t) - 1
        while j >= 0 and self._reach[j] > t:     # some event up to j ends after t
            e = self._other[j]
            if e.end > t and (other is None or
                              e.end - e.start <= other.end - other.start):
                other = e
            j -= 1
        parts = [x.name for x in (span, other) if x is not None]
        return " > ".join(parts) if parts else "host: no event"

    def idle_by_host(self, chip: int = 0, top: int = 10) -> list:
        """Idle seconds of one chip by what the host was doing at the
        middle of each idle stretch, largest first."""
        acc: dict = {}
        for s, e in self.gaps(chip):
            label = self.host_label((s + e) / 2)
            acc[label] = acc.get(label, 0.0) + (e - s) / 1e9
        return sorted(acc.items(), key=lambda kv: -kv[1])[:top]

    def ops_by_time(self, chip: int = 0, top: int = 10) -> list:
        """Device seconds of one chip by ``<program>/<operation>``, largest
        first. An operation is charged its self time: operations nest (a
        ``while`` holds its body's), so each is charged its span less the
        spans of the operations directly inside it. Each is charged to
        the program running it."""
        c = self.trace.chips[chip]
        starts = [m.start for m in c.modules]
        ops = sorted((op for op in c.ops if self.lo <= op.start < self.hi),
                     key=lambda e: (e.start, -e.end))
        self_ns = [op.end - op.start for op in ops]
        stack: list = []
        for i, op in enumerate(ops):
            while stack and ops[stack[-1]].end <= op.start:
                stack.pop()
            if stack:
                self_ns[stack[-1]] -= op.end - op.start
            stack.append(i)
        acc: dict = {}
        for op, ns in zip(ops, self_ns):
            j = bisect.bisect_right(starts, op.start) - 1
            prog = (program_name(c.modules[j].name)
                    if j >= 0 and c.modules[j].end >= op.start else "?")
            key = f"{prog}/{op_name(op.name)}"
            acc[key] = acc.get(key, 0.0) + ns / 1e9
        return sorted(acc.items(), key=lambda kv: -kv[1])[:top]

    def breakdown(self) -> dict:
        return {"device_ops": [[k, v] for k, v in self.ops_by_time()],
                "idle_gaps": [[k, v] for k, v in self.idle_by_host()]}
