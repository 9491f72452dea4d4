"""exec.dispatches: device-program launches the host issued per coloring.

Layer: the session (``exec/session.py``). Read from the program's own
counter, ``ColoringResult.host_dispatches``, of each window coloring;
the median. In the host regime it is one launch per iteration, so a
change that keeps iterations on the device shows here first. Moves
``color_s``.
"""
import statistics

UNIT = "dispatches"


def read(run):
    if run.traffic["kind"] != "solo" or not run.results:
        return None
    return statistics.median(r.host_dispatches for r in run.results)
