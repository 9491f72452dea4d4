"""Pallas TPU kernel: stream compaction (the worklist "push").

Turns a dense active mask into the compacted index array — the TPU-native
replacement for IrGL's warp-aggregated atomic worklist pushes
(DESIGN.md §2). Each grid step owns one ``(1, TILE)`` block of the mask
and the same block of the output, and

  1. computes the tile's exclusive prefix sum of the mask (a masked
     reduction over a ``(TILE, TILE)`` iota compare; Mosaic has no
     ``cumsum``),
  2. materialises the tile's compacted global indices in positions
     ``[0, tile_count)`` of its own output block (one-hot position match —
     O(TILE^2) VPU compares, still HBM-bound overall).

The caller stitches the tiles: an exclusive prefix sum over the per-tile
counts gives each tile's global offset, and one scatter moves the valid
prefix of every block there. Mosaic refuses a store into VMEM at an
offset it cannot prove aligned, so the kernel never stores at a running
offset, and it never holds more than one tile of the output.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.csr_segment import prefix_sum


def _compact_kernel(mask_ref, out_ref, *, tile: int):
    m = mask_ref[...]                               # (1, TILE) int32 0/1
    src = jax.lax.broadcasted_iota(jnp.int32, (tile, tile), 0)   # j
    dst = jax.lax.broadcasted_iota(jnp.int32, (tile, tile), 1)   # p / i
    # row j of each (TILE, TILE) matrix below is about mask position j
    m_col = jnp.sum(jnp.where(dst == src, m, 0), axis=1, keepdims=True)
    excl = jnp.sum(jnp.where(dst < src, m, 0), axis=1, keepdims=True)
    hit = (excl == dst) & (m_col != 0)              # (j, p)
    local = jnp.sum(jnp.where(hit, src, 0), axis=0, keepdims=True)
    out_ref[...] = local + pl.program_id(0) * tile  # absolute node ids


def compact_pallas(mask: jax.Array, *, tile: int = 256,
                   interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """mask bool[N] -> (items int32[N] padded with N, count int32[])."""
    n = mask.shape[0]
    pad = (-n) % tile
    m = jnp.pad(mask.astype(jnp.int32), (0, pad))
    n_grid = (n + pad) // tile
    local = pl.pallas_call(
        functools.partial(_compact_kernel, tile=tile),
        grid=(n_grid,),
        in_specs=[pl.BlockSpec((1, tile), lambda i: (0, i))],
        out_specs=pl.BlockSpec((1, tile), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, n + pad), jnp.int32),
        interpret=interpret,
    )(m[None, :])
    counts = m.reshape(n_grid, tile).sum(axis=1)
    offsets = prefix_sum(counts) - counts
    pos = jnp.arange(tile, dtype=jnp.int32)
    # positions past a tile's count hold junk; index n drops them
    dest = jnp.where(pos < counts[:, None], offsets[:, None] + pos, n)
    items = jnp.full((n,), n, jnp.int32).at[dest.reshape(-1)].set(
        local.reshape(-1), mode="drop")
    return items, counts.sum(dtype=jnp.int32)
