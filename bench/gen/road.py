"""Road-network edge generator (europe_osm family).

A copy of the repository's ``edges_road`` as it stood when the benchmark
was defined, kept here so that a change to the program cannot change the
benchmark's graphs: a chain backbone with branch edges on about 4% of
the nodes, each to a node at most 49 positions ahead, so the median
degree is 2 and the mean about 2.08. Pure numpy; the same seed gives the
same edges.
"""
from __future__ import annotations

import numpy as np


def edges(n: int, seed: int):
    """``(src, dst, n)`` for ``n`` nodes."""
    rng = np.random.default_rng(seed)
    src = np.arange(n - 1)
    dst = src + 1
    nb = max(n // 25, 1)
    bs = rng.integers(0, n, size=nb)
    bd = np.clip(bs + rng.integers(2, 50, size=nb), 0, n - 1)
    return np.concatenate([src, bs]), np.concatenate([dst, bd]), n


def from_config(cfg: dict, seed: int):
    """Edges of the configuration's graph."""
    return edges(cfg["nodes"], seed)
