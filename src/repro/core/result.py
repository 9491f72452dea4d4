"""``ColoringResult`` — what every coloring run returns.

It lives in ``core`` so the JPL reference (``core/baselines.py``) and
the dispatch regimes in ``repro.exec`` share one result type without an
upward import.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class ColoringResult:
    colors: np.ndarray          # [N] final colors (>= 0 everywhere)
    n_colors: int
    iterations: int
    mode_trace: str             # 'D'/'S' per iteration
    counts: list[int]           # worklist size per host dispatch: one entry
    #                             per iteration for the host loop, one per
    #                             while_loop chunk for the outlined regime
    tti: list[float]            # wall seconds, same granularity as counts
    total_seconds: float
    host_dispatches: int = 0    # device-program launches the host issued
    # dist regime only (DESIGN.md §13): per-iteration exchange-path trace
    # ('d' dense, 'b' packed-boundary, 'm' mixed within a two-phase
    # iteration) and the modeled bytes each iteration moved per device
    exchange_trace: str = ""
    exchange_bytes: list = dataclasses.field(default_factory=list)
    # host regime only (empty elsewhere): per 'S' of mode_trace, the
    # adjacency entries of the rows the sparse step ran (live) and the
    # entries it gathered to do so, padding included (ipgc.sparse_slots);
    # per 'D' the same of the dense step (ipgc.dense_slots)
    sparse_entries: list = dataclasses.field(default_factory=list)
    sparse_slots: list = dataclasses.field(default_factory=list)
    dense_entries: list = dataclasses.field(default_factory=list)
    dense_slots: list = dataclasses.field(default_factory=list)
