"""Random geometric graph generator (DIMACS10 rgg_n_2_*_s0 family).

The recipe of the DIMACS10 challenge's ``rgg_n_2_15_s0`` ...
``rgg_n_2_24_s0``: ``n`` points uniform in the unit square, and an edge
between every two points closer than ``r = f * sqrt(ln n / n)``, with
``f = 0.55``. Every edge is found: the square is cut into bins of side at
least ``r``, and each point is compared with every point of its own bin
and of the neighbouring bins, however full they are.

Node ids follow the bins in row-major order (within a bin, the order the
points were drawn), so nearly every neighbour of a node lies within a
few bin rows of it. Each undirected edge appears once. Pure numpy; the
same seed gives the same edges.
"""
from __future__ import annotations

import numpy as np

#: points whose candidate pairs are laid out at once (cache-sized)
_BLOCK = 1 << 13


def radius(n: int, factor: float) -> float:
    """The recipe's connection radius for ``n`` points."""
    return factor * np.sqrt(np.log(n) / n)


def points(n: int, seed: int) -> np.ndarray:
    """``(n, 2)`` float64 points uniform in the unit square."""
    return np.random.default_rng(seed).random((n, 2))


def edges(n: int, factor: float, seed: int):
    """``(src, dst, n)``: every pair of the ``n`` points of ``seed``
    closer than ``radius(n, factor)``, once, in bin order."""
    pts = points(n, seed)
    r = radius(n, factor)
    g = max(int(1.0 / r), 1)                         # bins a side, >= r
    bx = np.minimum((pts[:, 0] * g).astype(np.int64), g - 1)
    by = np.minimum((pts[:, 1] * g).astype(np.int64), g - 1)
    b = by * g + bx
    order = np.argsort(b, kind="stable")
    x, y, bx, by, b = (a[order] for a in (pts[:, 0], pts[:, 1], bx, by, b))
    start = np.searchsorted(b, np.arange(g * g + 1))
    # each point is compared with two runs of ids: the later points of
    # its own bin and those of the next bin in its row, then the three
    # bins of the next row that touch its bin (consecutive in bin order)
    ids = np.arange(n, dtype=np.int64)
    row_end = start[np.minimum(b + 2 - (bx == g - 1), g * g)]
    up = np.minimum(by + 1, g - 1) * g
    up_lo = start[up + np.maximum(bx - 1, 0)]
    up_hi = np.where(by < g - 1, start[up + np.minimum(bx + 2, g)], up_lo)
    srcs, dsts = [], []
    for lo in range(0, n, _BLOCK):
        sl = slice(lo, lo + _BLOCK)
        for first, end in ((ids[sl] + 1, row_end[sl]),
                           (up_lo[sl], up_hi[sl])):
            count = end - first
            # every (point, candidate) pair of these runs, laid out flat
            j = np.arange(count.sum()) + np.repeat(first - np.cumsum(count)
                                                   + count, count)
            near = ((np.repeat(x[sl], count) - x[j]) ** 2
                    + (np.repeat(y[sl], count) - y[j]) ** 2) < r * r
            srcs.append(np.repeat(ids[sl], count)[near])
            dsts.append(j[near])
    return np.concatenate(srcs), np.concatenate(dsts), n


def from_config(cfg: dict, seed: int):
    """Edges of the configuration's graph: ``2**scale`` points."""
    return edges(1 << cfg["scale"], cfg["radius_factor"], seed)
