"""Shared pieces of the ELL row-tile kernels (mex_window, conflict,
fused_step, fused_compact, jpl_prio).

Every one of these kernels walks the rows in ``(TILE_R, K)`` tiles, with a
handful of per-row scalars beside each tile. Two facts about the TPU shape
what lives here:

  * HBM arrays are tiled ``(8, 128)``, so a ``(R, 1)`` column occupies as
    much memory as a ``(R, 128)`` array (1 GiB of int32 at 2^21 rows).
    Per-row scalars therefore travel packed side by side in ONE
    ``(R, P)`` int32 slab, and each kernel writes ONE ``(R, Q)`` output
    slab, instead of one column per value.
  * Mosaic lowers neither a dynamic slice of a vector value nor a bool
    ``argmax``. ``K`` and the window are static, so the per-column loop
    unrolls over static lane slices, and "first free slot" is a min over
    an iota.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def slab(*cols: jax.Array) -> jax.Array:
    """Pack per-row vectors (bool or int) into one (R, len(cols)) int32."""
    return jnp.stack([c.astype(jnp.int32) for c in cols], axis=1)


def lanes(*cols: jax.Array) -> jax.Array:
    """In-kernel inverse of ``slab``: (TR, 1) int32 columns -> (TR, Q)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (cols[0].shape[0], len(cols)),
                                    1)
    out = cols[-1]
    for j in range(len(cols) - 2, -1, -1):
        out = jnp.where(lane == j, cols[j], out)
    return jnp.broadcast_to(out, lane.shape)


def any_rows(x: jax.Array) -> jax.Array:
    """(TR, K) bool -> (TR, 1) bool row-wise OR."""
    return jnp.max(x.astype(jnp.int32), axis=1, keepdims=True) != 0


def loses(nc, npr, nid, cu, pu, uid) -> jax.Array:
    """(TR, 1) bool: some neighbour holds the row's color with a higher
    (priority, id) pair — the tie-break of ``ipgc._conflict_rows``."""
    same = (nc == cu) & (cu >= 0)
    higher = (npr > pu) | ((npr == pu) & (nid > uid))
    return any_rows(same & higher)


def first_free(nc: jax.Array, base: jax.Array, forb: jax.Array,
               window: int) -> jax.Array:
    """(TR, 1) int32 first window slot no neighbour color occupies, -1 when
    the whole window is forbidden. ``forb`` (TR, W) bool seeds the bitmap
    (the hub side-channel); negative ``nc - base`` (uncolored / pad
    neighbours) and ``>= window`` never match."""
    rel = nc - base
    iota_w = jax.lax.broadcasted_iota(jnp.int32, forb.shape, 1)
    for k in range(nc.shape[1]):
        forb = forb | (rel[:, k:k + 1] == iota_w)
    first = jnp.min(jnp.where(forb, window, iota_w), axis=1, keepdims=True)
    return jnp.where(first < window, first, -1)
