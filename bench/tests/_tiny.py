"""Cells of the benchmark at a size a CPU test can hold.

The configurations and mixes are the committed ones with the scale cut
and, for the tests only, a cap on iterations so that a broken step ends
quickly; the cell, the harness and the program path are the real ones.
"""
from __future__ import annotations

import copy

from bench import harness, load

SEED = 2 ** 33 + 12345          # above 32 bits, as the driver's are


def tiny_run(cell_name: str, *, seconds: float = 0.3, trace=False,
             seed: int = SEED) -> harness.Run:
    cell = harness.find_cell(harness.load_benchmark(), cell_name)
    mix = copy.deepcopy(load.load_traffic(cell["traffic"]))
    cfg = copy.deepcopy(harness.load_config(cell["config"]))
    if cfg["generator"] == "rmat":
        cfg["scale"] = 9
    else:
        cfg["nodes"] = 3000
    cfg["spec"] = dict(cfg.get("spec", {}), max_iter=60)
    return harness.Run(cell=cell, config=cfg, traffic=mix, seed=seed,
                       seconds=seconds, trace=trace)
