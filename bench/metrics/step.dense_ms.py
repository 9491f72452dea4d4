"""step.dense_ms: device time of one dense (topology-driven) step.

Layer: the steps (``core/ipgc.py``). The device time of the dense-step
programs in the traced window (module names holding one of PROGRAMS:
the two-phase and the fused family), over the dense steps the window's
colorings ran. Moves ``color_s``.
"""
from bench.metrics._steps import step_ms

UNIT = "ms"
PROGRAMS = ("dense_step_impl",)   # jit_dense_step_impl, jit_fused_dense_...


def read(run):
    return step_ms(run, PROGRAMS, "D")
