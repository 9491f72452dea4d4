"""Pallas TPU kernel: per-row neighbour-priority extrema for JPL rounds.

The Jones-Plassmann-Luby independent-set test is a pure priority compare:
row u joins the max-set iff its per-round random priority beats every
*active* neighbour's priority, the min-set iff it is strictly below all of
them (the two-sided trick: both sets are independent, so each round
confirms two color classes).

Inactive (already colored / pad) neighbours arrive pre-masked to -1, so the
kernel is a masked row reduction over the ELL axis:

  nbr_max[u] = max_k npr[u, k]                     (-1 if no active nbr)
  nbr_min[u] = min_k (npr[u, k] if npr >= 0 else LARGE)

Layout reasoning (HBM->VMEM->VREG): both extrema are lane reductions of
the resident (TILE_R, K) npr tile, written as one (TILE_R, 2) output block
— pure VPU work, no MXU. Priorities
arrive pre-hashed (the splitmix hash is cheap elementwise jnp; the kernel
covers the O(rows * K) reduction hot-spot).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.rowtile import lanes

LARGE = 0x7FFFFFFF  # int literal: jnp constants would be captured as consts


def _extrema_kernel(npr_ref, out_ref):
    npr = npr_ref[...]                    # (TR, K) int32, inactive = -1
    out_ref[...] = lanes(
        jnp.max(npr, axis=1, keepdims=True),
        jnp.min(jnp.where(npr >= 0, npr, LARGE), axis=1, keepdims=True))


def jpl_extrema_pallas(npr: jax.Array, *, tile_rows: int = 32,
                       interpret: bool = False
                       ) -> tuple[jax.Array, jax.Array]:
    """Row-wise (max, masked min) of active-neighbour priorities.

    npr: (R, K) int32 neighbour priorities; inactive/pad lanes = -1.
    Returns (nbr_max (R,), nbr_min (R,)): max is -1 and min is LARGE for
    rows with no active neighbour.
    """
    r, k = npr.shape
    pad = (-r) % tile_rows
    if pad:
        npr = jnp.pad(npr, ((0, pad), (0, 0)), constant_values=-1)
    rp = r + pad
    out = pl.pallas_call(
        _extrema_kernel,
        grid=(rp // tile_rows,),
        in_specs=[pl.BlockSpec((tile_rows, k), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((tile_rows, 2), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rp, 2), jnp.int32),
        interpret=interpret,
    )(npr)
    return out[:r, 0], out[:r, 1]
