"""RMAT / Kronecker edge generator (Graph500 kron_g500 family).

A copy of the repository's ``edges_rmat`` as it stood when the benchmark
was defined, kept here so that a change to the program cannot change the
benchmark's graphs. Pure numpy; the same seed gives the same edges.
"""
from __future__ import annotations

import numpy as np


def edges(scale: int, edge_factor: int, seed: int, a: float = 0.57,
          b: float = 0.19, c: float = 0.19):
    """``(src, dst, n)``: ``n = 2**scale`` nodes, ``n * edge_factor``
    directed draws (duplicates and self loops included, as Graph500
    generates them), node labels permuted so ids carry no degree."""
    rng = np.random.default_rng(seed)
    n = 1 << scale
    e = n * edge_factor
    src = np.zeros(e, dtype=np.int64)
    dst = np.zeros(e, dtype=np.int64)
    r = np.empty(e)
    p_d = np.empty(e)
    bit_s = np.empty(e, dtype=bool)
    bit_d = np.empty(e, dtype=bool)
    for _ in range(scale):
        rng.random(out=r)
        np.greater_equal(r, a + b, out=bit_s)          # lower half of rows
        rng.random(out=r)
        np.copyto(p_d, b / (a + b))
        np.copyto(p_d, 1 - (c / (1 - a - b)), where=bit_s)
        np.less(r, p_d, out=bit_d)                     # right half of cols
        np.left_shift(src, 1, out=src)
        src |= bit_s
        np.left_shift(dst, 1, out=dst)
        dst |= bit_d
    perm = rng.permutation(n)
    return perm[src], perm[dst], n


def from_config(cfg: dict, seed: int):
    """Edges of the configuration's graph."""
    return edges(cfg["scale"], cfg["edge_factor"], seed, cfg["a"], cfg["b"],
                 cfg["c"])
