"""Run one cell: its runner (``bench/runners/<kind>.py``, by the
``kind`` of its traffic mix) drives the program and judges the window.

``control`` runs the program with a guarantee broken on purpose (the
``solo`` runner stops each coloring at half the iterations a sound one
takes, through the program's own ``max_iter``). Only ``bench/control.py``
and the tests set it; a benchmark run never does.
"""
from __future__ import annotations

import importlib
import sys

from bench.harness import CompileCounter, Run, device_fields, info


def run_cell(run: Run, *, control: bool = False) -> Run:
    """Set up, measure, read the device, and judge: everything but the
    device check and the result line."""
    import jax

    runner = importlib.import_module(f"bench.runners.{run.traffic['kind']}")
    with CompileCounter() as counter:
        runner.drive(run, counter, control=control)
    run.e2e["setup_s"] = run.setup_s
    run.device = device_fields(jax.devices()[:run.cell["chips"]])
    info(setup_s=run.setup_s, window_s=run.window_s,
         memory_peak_bytes=run.device["memory_peak_bytes"])
    runner.judge(run)
    print(f"bench: {run.cell['name']} seed {run.seed}: judged "
          f"{run.attempted} answers", file=sys.stderr)
    return run
