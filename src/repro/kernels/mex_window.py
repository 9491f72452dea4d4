"""Pallas TPU kernel: windowed mex over neighbour colors.

For a tile of rows, computes the first free color index inside the window
``[base, base+W)`` given the row's (ELL-gathered) neighbour colors and an
extra forbidden bitmap (hub/tail side-channel). This is the compute
hot-spot of the IPGC assign step: O(rows * K * W) comparisons, pure VPU
work on (TILE_R, 128) vectors.

Layout reasoning (HBM->VMEM->VREG):
  * W = window is fixed at a multiple of 128 — one or more full lane rows.
  * K (ELL width) is the unrolled reduction dim (static lane slices, see
    rowtile.py); each k contributes one (TILE_R, W) compare+or, so the
    working set is 3 * TILE_R * W * 4 bytes
    (nc tile + forbidden accumulator + iota), far under VMEM for
    TILE_R = 8..64.
  * neighbour colors arrive pre-gathered (the gather is an XLA dynamic-
    gather on the embedding-style ELL table; TPU Pallas has no in-kernel
    HBM gather, unlike CUDA pointer chasing — see DESIGN.md §2).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.rowtile import first_free


def _mex_kernel(nc_ref, base_ref, extra_ref, out_ref, *, window: int):
    out_ref[...] = first_free(nc_ref[...], base_ref[...],
                              extra_ref[...] != 0, window)


def mex_window_pallas(nc: jax.Array, base: jax.Array, extra_forb: jax.Array,
                      window: int, *, tile_rows: int = 32,
                      interpret: bool = False) -> jax.Array:
    """Returns first-free window index per row, -1 if the window is full.

    nc:         (R, K) int32 neighbour colors (pad/uncolored < 0)
    base:       (R,)  int32 window base per row
    extra_forb: (R, W) bool  extra forbidden positions (hub tails)
    """
    r, k = nc.shape
    assert extra_forb.shape == (r, window)
    pad = (-r) % tile_rows
    if pad:
        nc = jnp.pad(nc, ((0, pad), (0, 0)), constant_values=-2)
        base = jnp.pad(base, (0, pad))
        extra_forb = jnp.pad(extra_forb, ((0, pad), (0, 0)))
    rp = r + pad
    grid = (rp // tile_rows,)
    out = pl.pallas_call(
        functools.partial(_mex_kernel, window=window),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_rows, k), lambda i: (i, 0)),
            pl.BlockSpec((tile_rows, 1), lambda i: (i, 0)),
            pl.BlockSpec((tile_rows, window), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((tile_rows, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rp, 1), jnp.int32),
        interpret=interpret,
    )(nc, base[:, None].astype(jnp.int32), extra_forb.astype(jnp.int32))
    return out[:r, 0]
