"""kernel.dense_roofline: one dense sweep's share of its memory roofline.

Layer: the kernels (``kernels/``: the ELL and csr-segment passes a dense
step runs). The least bytes any implementation of a dense sweep must
move (``least_bytes``), over the chip's HBM bandwidth (``bench/peaks.json``),
divided by ``step.dense_ms``; with several graphs in a run, each dense
step counts the bytes of its own graph. The sweep is bound by memory: it gathers
colors and does no arithmetic worth counting against an operations
peak. The same count holds whatever layout or kernel implements the
sweep, so the share compares implementations. Moves ``color_s``.
"""
import json
from pathlib import Path

import numpy as np

from bench.metrics._steps import step_ms

UNIT = "%"
PROGRAMS = ("dense_step_impl",)
PEAKS = Path(__file__).resolve().parents[1] / "peaks.json"


def least_bytes(n_nodes: int, n_entries: int) -> int:
    """Every adjacency entry read once as an int32 neighbour id, and every
    node's state (its color and its window base, int32 each) read once
    and written once."""
    return 4 * n_entries + 16 * n_nodes


def directed_entries(src, dst, n: int) -> int:
    """Adjacency entries of the simple graph the edges define: both
    directions, self loops and duplicates dropped."""
    s = np.concatenate([src, dst]).astype(np.int64)
    d = np.concatenate([dst, src]).astype(np.int64)
    keep = s != d
    return int(np.unique(s[keep] * n + d[keep]).size)


def hbm_bytes_per_s(kind: str) -> float:
    peaks = json.loads(PEAKS.read_text())
    if kind not in peaks:
        raise KeyError(f"no peaks for device kind {kind!r} in {PEAKS}")
    return float(peaks[kind]["hbm_bytes_per_s"])


def read(run):
    ms = step_ms(run, PROGRAMS, "D")
    if ms is None:
        return None
    # least bytes of the window's dense steps, each on its own graph
    need = steps = 0
    per_graph = {}
    for r, i in zip(run.results, run.graph_of):
        if i not in per_graph:
            src, dst, n = run.edges[i]
            per_graph[i] = least_bytes(n, directed_entries(src, dst, n))
        d = r.mode_trace.count("D")
        need += d * per_graph[i]
        steps += d
    per_step = need / steps
    return 100.0 * per_step / hbm_bytes_per_s(run.device["kind"]) / (ms / 1e3)
