"""Pallas TPU kernel: fused assign+resolve over one neighbour-color tile.

The two-phase engine runs two kernels per iteration (conflict + mex), each
re-reading a ``(TILE_R, K)`` neighbour-color tile from HBM. The fused step
(DESIGN.md §5) pipelines resolve-of-last-round with assign-of-this-round,
so ONE tile load feeds both:

  1. conflict: row u loses iff pending and some neighbour holds the same
     color with a higher (priority, id) pair — 5 compares + a K-reduce on
     the resident tile.
  2. windowed mex: forbidden bitmap OR-accumulated from the SAME tile
     (plus the hub side-channel bitmap), then first-free as a min over
     the window iota (rowtile.py).

Outputs are per-row ``lose`` flags and the first free window index
(``-1`` when the window is exhausted); the caller applies the need/pending
masking and the hub-tail lose merge (those are O(N)/O(T) vector ops, not
tile work). Working set is ~4 * TILE_R * max(K, W) * 4 bytes — VMEM-bound
well under budget for TILE_R = 8..64, W a multiple of 128.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.rowtile import first_free, lanes, loses, slab


def _fused_kernel(nc_ref, npr_ref, nid_ref, row_ref, extra_ref, out_ref, *,
                  window: int):
    nc = nc_ref[...]                      # (TR, K) neighbour colors
    row = row_ref[...]                    # (TR, 5) base/cu/pu/uid/pending
    base, cu, pu, uid = (row[:, j:j + 1] for j in range(4))
    lose = loses(nc, npr_ref[...], nid_ref[...], cu, pu, uid) & (
        row[:, 4:5] != 0)
    first = first_free(nc, base, extra_ref[...] != 0, window)
    out_ref[...] = lanes(lose.astype(jnp.int32), first)


def fused_step_pallas(nc: jax.Array, npr: jax.Array, nbr_ids: jax.Array,
                      base: jax.Array, cu: jax.Array, pu: jax.Array,
                      ids: jax.Array, pending: jax.Array,
                      extra_forb: jax.Array, window: int, *,
                      tile_rows: int = 32, interpret: bool = False
                      ) -> tuple[jax.Array, jax.Array]:
    """Returns (lose, first_free) per row; ``first_free`` is -1 when the
    whole window is forbidden.

    nc:        (R, K) int32 neighbour colors (pad/uncolored < 0)
    npr:       (R, K) int32 neighbour priorities (pad = -1)
    nbr_ids:   (R, K) int32 neighbour ids (pad = N)
    base:      (R,)  int32 window base per row
    cu/pu/ids: (R,)  int32 own color / priority / id
    pending:   (R,)  bool  speculated-last-round flag
    extra_forb:(R, W) bool extra forbidden positions (hub tails)
    """
    r, k = nc.shape
    assert extra_forb.shape == (r, window)
    pad = (-r) % tile_rows
    if pad:
        nc = jnp.pad(nc, ((0, pad), (0, 0)), constant_values=-2)
        npr = jnp.pad(npr, ((0, pad), (0, 0)), constant_values=-1)
        nbr_ids = jnp.pad(nbr_ids, ((0, pad), (0, 0)))
        base = jnp.pad(base, (0, pad))
        cu = jnp.pad(cu, (0, pad), constant_values=-2)
        pu = jnp.pad(pu, (0, pad), constant_values=-1)
        ids = jnp.pad(ids, (0, pad))
        pending = jnp.pad(pending, (0, pad))
        extra_forb = jnp.pad(extra_forb, ((0, pad), (0, 0)))
    rp = r + pad
    tile = pl.BlockSpec((tile_rows, k), lambda i: (i, 0))
    out = pl.pallas_call(
        functools.partial(_fused_kernel, window=window),
        grid=(rp // tile_rows,),
        in_specs=[tile, tile, tile,
                  pl.BlockSpec((tile_rows, 5), lambda i: (i, 0)),
                  pl.BlockSpec((tile_rows, window), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((tile_rows, 2), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rp, 2), jnp.int32),
        interpret=interpret,
    )(nc, npr, nbr_ids, slab(base, cu, pu, ids, pending),
      extra_forb.astype(jnp.int32))
    return out[:r, 0] != 0, out[:r, 1]
