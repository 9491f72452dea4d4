"""kernel.sparse_roofline: one sparse step's share of its memory roofline.

Layer: the kernels (``kernels/``: the packed csr-segment passes and the
ELL tiles a sparse step runs). The least bytes a sparse step must move,
over the chip's HBM bandwidth (``bench/peaks.json``), divided by
``step.sparse_ms``. The least bytes are those of
``kernel.dense_roofline`` over the step's worklist alone: its live
entries read once as int32 neighbour ids, and each of its rows' color
and window base read and written once (``least_bytes(rows, live)``).
The live entries come from the program's counter
(``ColoringResult.sparse_entries``) and the rows from the worklist
counts, so the share counts the work the algorithm's worklist fixes,
not what the packing gathers, whatever implements the step. None where
no sparse step ran or the program keeps no such counter; ``NoMatch``
exactly where ``step.sparse_ms`` raises it. Moves ``color_s``.
"""
from bench.harness import load_reader
from bench.metrics._steps import step_ms

UNIT = "%"
PROGRAMS = ("sparse_step_impl",)
DENSE = load_reader("kernel.dense_roofline")


def read(run):
    ms = step_ms(run, PROGRAMS, "S")
    if ms is None:
        return None
    need = steps = 0
    for r in run.results:
        live = getattr(r, "sparse_entries", None) or []
        rows = [c for m, c in zip(r.mode_trace, r.counts) if m == "S"]
        if len(live) != len(rows):
            return None          # sparse steps the counter did not see
        need += sum(DENSE.least_bytes(c, e) for c, e in zip(rows, live))
        steps += len(rows)
    per_step = need / steps
    return (100.0 * per_step / DENSE.hbm_bytes_per_s(run.device["kind"])
            / (ms / 1e3))
