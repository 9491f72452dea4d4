"""Shared arithmetic of the step metrics (not a metric itself: the
harness loads only the files that ``BENCHMARK.json`` names)."""


from bench.trace_reduce import NoMatch


def step_ms(run, programs, mode):
    """Device milliseconds per step of ``mode`` (``"D"`` or ``"S"``): the
    device time of the programs whose names hold one of ``programs``,
    over the steps of that mode the window's colorings ran (their mode
    traces). None when no step of that mode ran; ``NoMatch`` when steps
    ran but no program of the trace matches ``programs``."""
    red = run.reduction
    if red is None or run.traffic["kind"] != "solo":
        return None
    steps = sum(r.mode_trace.count(mode) for r in run.results)
    if steps == 0:
        return None
    got = red.program_time(programs)
    if got is None:
        raise NoMatch(f"the window ran {steps} {mode} steps, but no "
                      f"program of the trace matches {programs}")
    _, seconds = got
    return seconds / steps * 1e3
