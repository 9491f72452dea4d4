"""Pallas TPU kernel: one IPGC iteration (assign + resolve + new-color
selection) in a single grid, followed by the worklist emission.

The fused step kernel (``kernels/fused_step.py``) returns only the
per-row verdicts (lose, first free slot); the caller then selected the new
colors and bases. This kernel folds that selection into the same row-tile
grid (DESIGN.md §10), so a dense-mode IPGC iteration launches one Pallas
kernel and then one XLA compaction:

per (TILE_R,)-row grid step —

  1. resolve: row u loses iff pending and some neighbour holds the same
     color with a higher (priority, id) pair (plus the precomputed hub
     COO-tail lose flag), on the resident ``(TILE_R, K)`` tile.
  2. assign: windowed mex over the SAME tile (forbidden bitmap
     OR-accumulated per ELL lane, seeded from the hub side-channel);
     rows that lost or were still uncolored take ``base + first`` or
     advance their base when the window is exhausted.
  3. the tile's new colors, new bases and survivor flags (``still =
     need``) leave as one ``(TILE_R, 3)`` output block.

The compaction of the survivors into the worklist runs after the kernel,
in the caller (``ipgc._fused_compact_rows``), as the same
``worklist.compact_items`` the jnp path uses. An in-kernel
compaction needs each tile to store at a running offset, and Mosaic
refuses a store into VMEM at an offset it cannot prove aligned; holding
the whole items array in VMEM instead does not fit at real graph sizes.
Per-tile compacted blocks would still need an XLA prefix sum and scatter
to stitch, which is the work ``compact_items`` does on the survivor mask.

The caller emits the row's ``ids`` input (not a computed global index),
so ONE kernel serves both worklist forms: the dense step passes ``ids =
iota(N)`` (emission == ``worklist.compact_mask``) and the sparse step
passes its items block (emission == ``worklist.compact_items`` — invalid
rows have ``active = False`` and never survive).

Grid specialisation by layout kind (DESIGN.md §10): pure-ell graphs call
the no-hub variant (hub operands compiled out entirely, mirroring the
static ``_has_hubs`` dispatch); ell-tail / hub-split pass the hub
side-channel bitmap and lose flags as extra operands. csr-segment does
not route here — its one-pass edge-parallel core is jnp segment ops
(``kernels/csr_segment.edge_fused``; see its module docstring for why no
Pallas variant exists).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.rowtile import first_free, lanes, loses, slab


def _fused_compact_kernel(*refs, window: int, no_color: int, with_hub: bool):
    if with_hub:
        nc_ref, npr_ref, nid_ref, row_ref, extra_ref, out_ref = refs
    else:
        nc_ref, npr_ref, nid_ref, row_ref, out_ref = refs
    nc = nc_ref[...]                      # (TR, K) neighbour colors
    # (TR, 6|7) base / own color / priority / id / active / pending
    # [/ hub lose]
    row = row_ref[...]
    base, cu, pu, uid = (row[:, j:j + 1] for j in range(4))
    act = row[:, 4:5] != 0
    pend = row[:, 5:6] != 0

    lose = loses(nc, npr_ref[...], nid_ref[...], cu, pu, uid) & pend
    if with_hub:
        lose = lose | ((row[:, 6:7] != 0) & pend)
        forb = extra_ref[...] != 0
    else:
        forb = jnp.zeros((nc.shape[0], window), bool)
    first = first_free(nc, base, forb, window)
    has = first >= 0

    need = lose | (act & (cu < 0))        # rows to (re)color = survivors
    new_c = jnp.where(need & has, base + first,
                      jnp.where(lose, no_color, cu))
    new_b = jnp.where(need & ~has, base + window, base)
    out_ref[...] = lanes(new_c, new_b, need.astype(jnp.int32))


def fused_compact_pallas(nc: jax.Array, npr: jax.Array, nbr_ids: jax.Array,
                         base: jax.Array, cu: jax.Array, pu: jax.Array,
                         ids: jax.Array, active: jax.Array,
                         pending: jax.Array,
                         extra_forb: jax.Array | None,
                         hub_lose: jax.Array | None, window: int, *,
                         tile_rows: int = 32, no_color: int = -1,
                         interpret: bool = False
                         ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Fused step kernel over R rows.

    nc/npr/nbr_ids: (R, K) int32 neighbour color/priority/id tiles
    base:           (R,)  int32 window base per row
    cu/pu/ids:      (R,)  int32 own color / priority / id
    active:         (R,)  bool  row is in the worklist (valid, for sparse)
    pending:        (R,)  bool  speculated-last-round flag
    extra_forb:     (R, W) bool hub-tail forbidden bitmap, or None (the
                    no-hub kernel variant — hub operands compiled out)
    hub_lose:       (R,)  bool hub-tail conflict flags, or None with
                    ``extra_forb``

    Returns ``(new_colors (R,), new_base (R,), still bool(R,))`` —
    bit-identical to the jnp fused step; the survivors ``still`` are the
    rows the caller compacts into the next worklist.
    """
    r, k = nc.shape
    with_hub = extra_forb is not None
    assert (hub_lose is not None) == with_hub, \
        "extra_forb and hub_lose arrive together (the hub variant)"
    rows = [base, cu, pu, ids, active, pending]
    if with_hub:
        assert extra_forb.shape == (r, window)
        rows.append(hub_lose)
    row = slab(*rows)
    pad = (-r) % tile_rows
    if pad:
        nc = jnp.pad(nc, ((0, pad), (0, 0)), constant_values=-2)
        npr = jnp.pad(npr, ((0, pad), (0, 0)), constant_values=-1)
        nbr_ids = jnp.pad(nbr_ids, ((0, pad), (0, 0)))
        # pad rows are inert (active and pending 0): they never survive
        row = jnp.pad(row, ((0, pad), (0, 0)))
        if with_hub:
            extra_forb = jnp.pad(extra_forb, ((0, pad), (0, 0)))
    rp = r + pad
    tile = pl.BlockSpec((tile_rows, k), lambda i: (i, 0))
    operands = [nc, npr, nbr_ids, row]
    in_specs = [tile, tile, tile,
                pl.BlockSpec((tile_rows, len(rows)), lambda i: (i, 0))]
    if with_hub:
        operands.append(extra_forb.astype(jnp.int32))
        in_specs.append(pl.BlockSpec((tile_rows, window), lambda i: (i, 0)))
    out = pl.pallas_call(
        functools.partial(_fused_compact_kernel, window=window,
                          no_color=no_color, with_hub=with_hub),
        grid=(rp // tile_rows,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((tile_rows, 3), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rp, 3), jnp.int32),
        interpret=interpret,
    )(*operands)
    return out[:r, 0], out[:r, 1], out[:r, 2] != 0
