"""step.sparse_ms: device time of one sparse (data-driven) step.

Layer: the steps (``core/ipgc.py``). The device time of the sparse-step
programs in the traced window (module names holding one of PROGRAMS:
the two-phase and the fused family), over the sparse steps the window's
colorings ran. Moves ``color_s``.
"""
from bench.metrics._steps import step_ms

UNIT = "ms"
PROGRAMS = ("sparse_step_impl",)   # jit_sparse_step_impl, jit_fused_sparse_...


def read(run):
    return step_ms(run, PROGRAMS, "S")
