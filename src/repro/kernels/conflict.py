"""Pallas TPU kernel: conflict detection with one-endpoint resolution.

Row u "loses" (gets uncolored, stays in the worklist) iff some neighbour v
has the same color and a higher (priority, id) pair — the paper's
"exactly one node from the conflicting edge is removed from the worklist".

Pure elementwise-compare + reduce over the ELL width: a single
(TILE_R, K) tile per input, one pass, no reduction loop needed since K is
a tile dimension. Memory-bound; the kernel exists to fuse the five
comparisons into one VMEM-resident pass instead of five HBM sweeps.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.rowtile import loses, slab


def _conflict_kernel(nc_ref, npr_ref, nid_ref, row_ref, out_ref):
    row = row_ref[...]        # (TR, 3) own color / priority / id
    out_ref[...] = loses(nc_ref[...], npr_ref[...], nid_ref[...],
                         row[:, 0:1], row[:, 1:2],
                         row[:, 2:3]).astype(jnp.int32)


def conflict_pallas(nc: jax.Array, npr: jax.Array, nbr_ids: jax.Array,
                    cu: jax.Array, pu: jax.Array, ids: jax.Array,
                    *, tile_rows: int = 32, interpret: bool = False
                    ) -> jax.Array:
    r, k = nc.shape
    pad = (-r) % tile_rows
    if pad:
        nc = jnp.pad(nc, ((0, pad), (0, 0)), constant_values=-2)
        npr = jnp.pad(npr, ((0, pad), (0, 0)), constant_values=-1)
        nbr_ids = jnp.pad(nbr_ids, ((0, pad), (0, 0)))
        cu = jnp.pad(cu, (0, pad), constant_values=-2)
        pu = jnp.pad(pu, (0, pad), constant_values=-1)
        ids = jnp.pad(ids, (0, pad))
    rp = r + pad
    tile = pl.BlockSpec((tile_rows, k), lambda i: (i, 0))
    out = pl.pallas_call(
        _conflict_kernel,
        grid=(rp // tile_rows,),
        in_specs=[tile, tile, tile,
                  pl.BlockSpec((tile_rows, 3), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((tile_rows, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rp, 1), jnp.int32),
        interpret=interpret,
    )(nc, npr, nbr_ids, slab(cu, pu, ids))
    return out[:r, 0] != 0
