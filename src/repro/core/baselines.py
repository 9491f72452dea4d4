"""Baselines the paper compares against.

* ``jpl_color`` — Jones–Plassmann–Luby independent-set coloring, the
  algorithm cuSPARSE's ``csrcolor`` implements. One color class per round
  (plus the two-sided trick: local max AND local min get colors 2r / 2r+1),
  very fast per round but uses many more colors — reproducing the paper's
  Table IV gap.

The Kokkos vertex-based baseline (Deveci et al.) is the data-driven
policy with a 32-wide window: ``ExecutionSpec(mode="data", window=32)``.
"""
from __future__ import annotations

import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ipgc
from repro.core.result import ColoringResult
from repro.graphs.csr import Graph, NO_COLOR


def _round_hash(x: jax.Array, r: jax.Array) -> jax.Array:
    """Per-round priority (uint32 splitmix-ish, positive int32)."""
    x = x.astype(jnp.uint32) + jnp.uint32(0x9E3779B9) * (r.astype(jnp.uint32) + 1)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> 16)
    return (x >> 1).astype(jnp.int32)


@partial(jax.jit, static_argnames=())
def _jpl_round(ig: ipgc.IPGCGraph, colors: jax.Array, rnd: jax.Array):
    """One JPL round: independent-set extraction by per-round random
    priority; local max -> color 2r, local min -> color 2r+1."""
    n = ig.n_nodes
    ids = jnp.arange(n, dtype=jnp.int32)
    un = colors[:n] == NO_COLOR
    pr = jnp.where(un, _round_hash(ids, rnd), -1)
    pr_ext = jnp.concatenate([pr, jnp.full((1,), -1, jnp.int32)])

    nbr_pr = pr_ext[ig.ell_idx]                       # (N, K); pad -> -1
    nbr_max = nbr_pr.max(axis=1)
    LARGE = jnp.int32(0x7FFFFFFF)
    nbr_pr_min = jnp.where(nbr_pr >= 0, nbr_pr, LARGE)
    nbr_min = nbr_pr_min.min(axis=1)

    # hub tails: fold COO contributions with segment max/min on node ids
    tpr = pr_ext[ig.tail_dst]
    upd = jnp.where(ig.tail_valid, tpr, -1)
    nbr_max = nbr_max.at[ig.tail_src].max(upd)
    updmin = jnp.where(ig.tail_valid & (tpr >= 0), tpr, LARGE)
    nbr_min = nbr_min.at[ig.tail_src].min(updmin)

    is_max = un & (pr > nbr_max)
    is_min = un & (pr < nbr_min) & ~is_max
    newc = jnp.where(is_max, 2 * rnd,
                     jnp.where(is_min, 2 * rnd + 1, colors[:n]))
    colors = colors.at[:n].set(newc)
    remaining = (newc == NO_COLOR).sum(dtype=jnp.int32)
    return colors, remaining


def jpl_color(g: Graph, *, max_rounds: int = 10_000) -> ColoringResult:
    ig = ipgc.prepare(g)
    colors = ipgc.init_colors(ig.n_nodes)
    t0 = time.perf_counter()
    rounds = 0
    remaining = ig.n_nodes
    counts = []
    while remaining > 0 and rounds < max_rounds:
        counts.append(int(remaining))
        colors, rem = _jpl_round(ig, colors, jnp.int32(rounds))
        remaining = int(rem)
        rounds += 1
    final = np.asarray(colors[: ig.n_nodes])
    # compact the palette (JPL leaves gaps); chromatic count = #distinct
    n_colors = len(np.unique(final[final >= 0]))
    return ColoringResult(colors=final, n_colors=n_colors, iterations=rounds,
                          mode_trace="J" * rounds, counts=counts, tti=[],
                          total_seconds=time.perf_counter() - t0)
