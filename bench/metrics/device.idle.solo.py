"""device.idle.solo: the share of the traced window in which no operation
ran on the device.

Layer: the device. 1 minus the union of the device's operation
intervals (busy) over the window's length, both from the profiler's
trace (``bench/trace_reduce.py``), averaged over the chips. A window
in which no operation ran fails the run (``NoMatch``). Moves
``color_s``.
"""
from bench.trace_reduce import NoMatch

UNIT = "%"


def read(run):
    red = run.reduction
    if red is None:
        return None
    if red.window_s <= 0 or red.busy_s <= 0:
        raise NoMatch("no device operation ran in the traced window")
    return 100.0 * (1.0 - red.busy_s / red.window_s)
