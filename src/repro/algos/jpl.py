"""``jpl`` — Luby-style random-priority independent-set coloring as a
worklist algorithm (Jones–Plassmann–Luby; what cuSPARSE's ``csrcolor``
implements).

Each round r draws a fresh random priority per *active* node (splitmix
hash of (node id, r)); nodes beating every active neighbour join the
max-independent-set and take color 2r, nodes strictly below every active
neighbour take 2r+1 (the two-sided trick — two color classes per round).
There is NO conflict-resolve phase: independent-set membership is decided
before coloring, so a round's assignments are final. The trade-off is
color quality — many more classes than IPGC's speculative mex
(reproducing the paper's Table IV gap) — against very cheap rounds.

Under the protocol both phases maintain the persistent dual worklist
(active = still uncolored), so the hybrid Pipe drives JPL exactly like
IPGC: topology-driven rounds while the active set is large, data-driven
gathered rounds once it thins, chunked outlining on device. The round
counter is the algorithm's ``aux`` state (a traced int32 scalar — it
rides through ``lax.while_loop`` chunks unchanged).

Per-phase communication profile (asserted in tests/test_algos.py):

  * dense round: ZERO gathers of the mutable colors array — neighbour
    activity is read from the priority vector, which encodes it.
  * sparse round: exactly ONE ELL-shaped colors gather (activity of
    neighbours outside the worklist is only knowable from colors).

``impl="pallas"`` routes the row-wise priority-extrema reduction through
``kernels/jpl_prio.py``; ``impl="jnp"`` is the reference reduction.

The color palette has per-round gaps (a round may confirm only one of its
two classes), so ``finalize`` compacts it to dense 0..k-1 labels and
reports the true distinct count.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.algos.base import Algorithm, _compact_palette
from repro.core import ipgc
from repro.core.worklist import Worklist, compact_items, compact_mask, \
    full_worklist
from repro.graphs.csr import NO_COLOR

LARGE = jnp.int32(0x7FFFFFFF)


def round_hash(x: jax.Array, r: jax.Array) -> jax.Array:
    """Per-round priority (uint32 splitmix-ish, positive int32) — the same
    mixer as ``baselines._round_hash`` so JPL results stay comparable."""
    x = x.astype(jnp.uint32) + jnp.uint32(0x9E3779B9) * (r.astype(jnp.uint32)
                                                         + 1)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> 16)
    return (x >> 1).astype(jnp.int32)


def _extrema(npr: jax.Array, impl: str,
             tile_rows: int | None = None) -> tuple[jax.Array, jax.Array]:
    """Row-wise (max, masked-min) active-neighbour priority reduction."""
    if impl == "pallas":
        from repro.kernels import ops as kops
        return kops.jpl_extrema(npr, tile_rows)
    nbr_max = npr.max(axis=1)
    nbr_min = jnp.where(npr >= 0, npr, LARGE).min(axis=1)
    return nbr_max, nbr_min


def _hub_extrema_raw(nh: int, tail_slot: jax.Array, tpr: jax.Array
                     ) -> tuple[jax.Array, jax.Array]:
    """(n_hub+1,) per-hub-slot tail-priority extrema; row n_hub is the
    neutral row non-hub nodes gather (max -1 / min LARGE)."""
    hmax = jnp.full((nh + 1,), -1, jnp.int32).at[tail_slot].max(tpr)
    hmin = jnp.full((nh + 1,), LARGE).at[tail_slot].min(
        jnp.where(tpr >= 0, tpr, LARGE))
    return hmax, hmin


def _hub_extrema(ig: ipgc.IPGCGraph, tpr: jax.Array
                 ) -> tuple[jax.Array, jax.Array]:
    return _hub_extrema_raw(ig.n_hub, ig.tail_slot, tpr)


def _decide(pend, pr, nbr_max, nbr_min, rnd, cu):
    """Two-sided independent-set membership -> new colors + newly flags."""
    is_max = pend & (pr > nbr_max)
    is_min = pend & (pr < nbr_min) & ~is_max
    newly = is_max | is_min
    new_c = jnp.where(is_max, 2 * rnd,
                      jnp.where(is_min, 2 * rnd + 1, cu))
    return new_c, newly


def jpl_dense_step_impl(ig: ipgc.IPGCGraph, colors: jax.Array,
                        rnd: jax.Array, wl: Worklist, *, window: int = 128,
                        impl: str = "jnp", force_hub: bool | None = None,
                        tile_rows: int | None = None
                        ) -> tuple[jax.Array, jax.Array, Worklist]:
    """One topology-driven JPL round over all N rows (``window`` is part of
    the protocol signature but JPL has no mex window — ignored)."""
    n = ig.n_nodes
    active = wl.mask
    ids = jnp.arange(n, dtype=jnp.int32)
    cu = colors[:n]
    pend = active & (cu == NO_COLOR)
    pr = jnp.where(pend, round_hash(ids, rnd), -1)
    pr_ext = jnp.concatenate([pr, jnp.full((1,), -1, jnp.int32)])

    npr = pr_ext[ig.ell_idx]              # (N, K); pad lanes -> -1
    nbr_max, nbr_min = _extrema(npr, impl, tile_rows)
    if ipgc._has_hubs(ig, force_hub):
        tpr = jnp.where(ig.tail_valid, pr_ext[ig.tail_dst], -1)
        hmax, hmin = _hub_extrema(ig, tpr)
        slot = jnp.minimum(ig.hub_slot, ig.n_hub)
        nbr_max = jnp.maximum(nbr_max, hmax[slot])
        nbr_min = jnp.minimum(nbr_min, hmin[slot])

    new_c, newly = _decide(pend, pr, nbr_max, nbr_min, rnd, cu)
    colors2 = colors.at[:n].set(new_c)

    still = active & ~newly
    items, count = compact_mask(still, wl.items.shape[0], n)
    return colors2, rnd + 1, Worklist(mask=still, items=items, count=count)


def jpl_sparse_step_impl(ig: ipgc.IPGCGraph, colors: jax.Array,
                         rnd: jax.Array, wl: Worklist, *, window: int = 128,
                         impl: str = "jnp", force_hub: bool | None = None,
                         tile_rows: int | None = None
                         ) -> tuple[jax.Array, jax.Array, Worklist]:
    """One data-driven JPL round over the gathered C-item worklist.

    Neighbour activity must be read from the colors vector here (a
    neighbour that left the worklist long ago is invisible to the items
    block) — the ONE colors gather of the sparse round.
    """
    n = ig.n_nodes
    items = wl.items
    valid = items < n
    safe = jnp.where(valid, items, 0)
    ids = jnp.where(valid, items, n)

    cu = colors[ids]                      # pad -> PAD_COLOR
    pend = valid & (cu == NO_COLOR)
    pr = jnp.where(pend, round_hash(items, rnd), -1)

    ell_rows = jnp.where(valid[:, None], ig.ell_idx[safe], n)    # (C, K)
    nc = ipgc._gather_neighbor_colors(colors, ell_rows)
    npr = jnp.where(nc == NO_COLOR, round_hash(ell_rows, rnd), -1)
    nbr_max, nbr_min = _extrema(npr, impl, tile_rows)
    if ipgc._has_hubs(ig, force_hub):
        tc = colors[ig.tail_dst]
        tpr = jnp.where(ig.tail_valid & (tc == NO_COLOR),
                        round_hash(ig.tail_dst, rnd), -1)
        hmax, hmin = _hub_extrema(ig, tpr)
        slot = jnp.minimum(ig.hub_slot[safe], ig.n_hub)
        nbr_max = jnp.maximum(nbr_max, jnp.where(valid, hmax[slot], -1))
        nbr_min = jnp.minimum(nbr_min, jnp.where(valid, hmin[slot], LARGE))

    new_c, newly = _decide(pend, pr, nbr_max, nbr_min, rnd, cu)
    colors2 = colors.at[ids].set(jnp.where(valid, new_c, colors[ids]),
                                 mode="drop")

    still = pend & ~newly
    new_items, count = compact_items(items, still, n)
    mask = wl.mask.at[ids].set(still, mode="drop")
    return colors2, rnd + 1, Worklist(mask=mask, items=new_items, count=count)


# ---------------------------------------------------------------------------
# distributed (shard_map) JPL rounds
# ---------------------------------------------------------------------------
#
# Shard-safety rests on two facts (DESIGN.md §§7+13):
#   * priorities are OWNER-COMPUTABLE: ``round_hash(global id, round)``
#     needs no exchange — any shard derives a ghost's priority locally;
#   * neighbour *activity* is readable from colors: JPL never uncolors,
#     so the persistent-worklist invariant specialises to
#     ``mask ≡ (colors == NO_COLOR)`` for every round, making
#     ``where(colors[nbr] == NO_COLOR, round_hash(nbr, r), -1)`` exactly
#     the host step's ``pr_ext[nbr]`` (the PAD sentinel at slot n is
#     PAD_COLOR != NO_COLOR, so pad lanes read -1 — same as pr_ext[n]).
# A round is single-phase, so each shard_map'd round performs exactly ONE
# color exchange (the same additive psum — or packed boundary publish —
# as the ipgc dist steps), and the ``aux`` round counter stays a
# replicated scalar.


def make_jpl_dist_steps(ig_local: ipgc.IPGCGraph, mesh, node_axes: tuple,
                        *, exchange: str = "dense", boundary=None,
                        thresh: "int | None" = None):
    """(dense_round, sparse_round) shard_map'd JPL steps, bit-identical to
    ``jpl_dense_step_impl``/``jpl_sparse_step_impl`` on the partitioned
    graph."""
    from functools import partial

    from jax.sharding import PartitionSpec as P

    from repro.core.distributed import (_exchange_colors, _publish_packed,
                                        _shard_offset)

    n = ig_local.n_nodes
    nh = ig_local.n_hub
    na = node_axes
    bnd = exchange != "dense"
    isb = jnp.asarray(boundary.is_boundary) if bnd else None
    th = int(thresh) if bnd else 0

    def _nbr_extrema(colors, rnd, ell_rows):
        nc = colors[ell_rows]
        npr = jnp.where(nc == NO_COLOR, round_hash(ell_rows, rnd), -1)
        return _extrema(npr, "jnp")

    def _hub_arrays(colors, rnd, tail_dst, tail_valid, tail_slot):
        tc = colors[tail_dst]
        tpr = jnp.where(tail_valid & (tc == NO_COLOR),
                        round_hash(tail_dst, rnd), -1)
        return _hub_extrema_raw(nh, tail_slot, tpr)

    def dense_local(state, rnd, mask_l, isb_l, ell_l, hubslot_l, tail_dst,
                    tail_valid, tail_slot, *, bcap):
        idx = _shard_offset(mesh, node_axes)
        blk = ell_l.shape[0]
        row_ids = idx * blk + jnp.arange(blk, dtype=jnp.int32)
        colors = state[0] if bnd else state
        cu = colors[row_ids]
        pend = mask_l & (cu == NO_COLOR)
        pr = jnp.where(pend, round_hash(row_ids, rnd), -1)
        nbr_max, nbr_min = _nbr_extrema(colors, rnd, ell_l)
        if nh > 0:
            hmax, hmin = _hub_arrays(colors, rnd, tail_dst, tail_valid,
                                     tail_slot)
            slot = jnp.minimum(hubslot_l, nh)
            nbr_max = jnp.maximum(nbr_max, hmax[slot])
            nbr_min = jnp.minimum(nbr_min, hmin[slot])
        new_c, newly = _decide(pend, pr, nbr_max, nbr_min, rnd, cu)
        if bnd:
            colors_out, npk, mx = _publish_packed(
                colors, row_ids, cu, new_c, isb_l, n=n, node_axes=node_axes,
                idx=idx, blk=blk, bcap=bcap, thresh=th)
        else:
            delta = jnp.zeros((n + 1,), jnp.int32).at[row_ids].set(new_c - cu)
            colors_out = _exchange_colors(colors, delta, node_axes)
        still = mask_l & ~newly
        (items_l,) = jnp.nonzero(still, size=blk, fill_value=blk)
        items_l = jnp.where(items_l < blk, idx * blk + items_l, n)
        count = jax.lax.psum(still.sum(dtype=jnp.int32), node_axes)
        if bnd:
            xstats = jnp.stack([npk, mx]).astype(jnp.int32)
            return (colors_out[None], still, items_l.astype(jnp.int32),
                    count, xstats)
        return colors_out, still, items_l.astype(jnp.int32), count

    def sparse_local(state, rnd, mask_l, items_l, isb_l, ell_l, hubslot_l,
                     tail_dst, tail_valid, tail_slot, *, bcap):
        idx = _shard_offset(mesh, node_axes)
        blk = ell_l.shape[0]
        colors = state[0] if bnd else state
        valid = items_l < n
        local = jnp.clip(jnp.where(valid, items_l - idx * blk, 0), 0, blk - 1)
        ids = jnp.where(valid, items_l, n)
        cu = colors[ids]
        pend = valid & (cu == NO_COLOR)
        pr = jnp.where(pend, round_hash(ids, rnd), -1)
        ell_rows = jnp.where(valid[:, None], ell_l[local], n)
        nbr_max, nbr_min = _nbr_extrema(colors, rnd, ell_rows)
        if nh > 0:
            hmax, hmin = _hub_arrays(colors, rnd, tail_dst, tail_valid,
                                     tail_slot)
            slot = jnp.minimum(jnp.where(valid, hubslot_l[local], nh), nh)
            nbr_max = jnp.maximum(nbr_max, jnp.where(valid, hmax[slot], -1))
            nbr_min = jnp.minimum(nbr_min,
                                  jnp.where(valid, hmin[slot], LARGE))
        new_c, newly = _decide(pend, pr, nbr_max, nbr_min, rnd, cu)
        if bnd:
            isb_items = valid & isb_l[local]
            colors_out, npk, mx = _publish_packed(
                colors, ids, cu, jnp.where(valid, new_c, cu), isb_items,
                n=n, node_axes=node_axes, idx=idx, blk=blk, bcap=bcap,
                thresh=th)
        else:
            delta = jnp.zeros((n + 1,), jnp.int32).at[ids].set(
                jnp.where(valid, new_c - cu, 0))
            colors_out = _exchange_colors(colors, delta, node_axes)
        still = pend & ~newly
        new_items, local_count = compact_items(items_l, still, n)
        mask2 = mask_l.at[jnp.where(valid, local, blk)].set(still,
                                                            mode="drop")
        count = jax.lax.psum(local_count, node_axes)
        if bnd:
            xstats = jnp.stack([npk, mx]).astype(jnp.int32)
            return colors_out[None], mask2, new_items, count, xstats
        return colors_out, mask2, new_items, count

    cspec = P(na, None) if bnd else P()
    dense_in = (cspec, P(), P(na), P(na), P(na, None), P(na),
                P(), P(), P())
    sparse_in = (cspec, P(), P(na), P(na), P(na), P(na, None), P(na),
                 P(), P(), P())
    out = (cspec, P(na), P(na), P())
    if bnd:
        out = out + (P(),)

    def _wrap(local_fn, in_specs, sparse: bool):
        def run(colors, rnd, wl: Worklist, *, bcap: int):
            fn = jax.shard_map(partial(local_fn, bcap=bcap), mesh=mesh,
                               in_specs=in_specs, out_specs=out,
                               check_vma=False)
            args = (colors, rnd, wl.mask) + ((wl.items,) if sparse else ())
            outs = fn(*args, isb if bnd else jnp.zeros((n,), bool),
                      ig_local.ell_idx, ig_local.hub_slot,
                      ig_local.tail_dst, ig_local.tail_valid,
                      ig_local.tail_slot)
            colors2, mask, items, count = outs[:4]
            wl2 = Worklist(mask=mask, items=items, count=count)
            if bnd:
                return colors2, rnd + 1, wl2, outs[4]
            return colors2, rnd + 1, wl2

        if bnd:
            step = jax.jit(run, static_argnames=("bcap",))
        else:
            jitted = jax.jit(lambda c, r, w: run(c, r, w, bcap=0))

            def step(colors, rnd, wl):
                return jitted(colors, rnd, wl)
        step.exchanges_per_iter = 1    # a JPL round is single-phase
        return step

    return (_wrap(dense_local, dense_in, sparse=False),
            _wrap(sparse_local, sparse_in, sparse=True))


@dataclasses.dataclass(frozen=True)
class JPL(Algorithm):
    name: str = "jpl"
    #: batch-axis safe: both rounds are shape-static jnp ops, a round's
    #: priorities hash (node id, round) — invariant under padding — and
    #: JPL is mode-invariant (no speculation), so dense-only lanes match
    #: the host loop's per-iteration mode choice bit-exactly
    batch_safe: bool = True
    #: shard-safe because a round's priorities are owner-computable
    #: (``round_hash(global id, round)``) and neighbour activity is
    #: readable from the exchanged colors vector — see the
    #: ``make_jpl_dist_steps`` header comment for the invariant proof
    shard_safe: bool = True
    uses_window: bool = False

    def init_state(self, ig):
        return (ipgc.init_colors(ig.n_nodes),
                jnp.zeros((), dtype=jnp.int32),   # the round counter
                full_worklist(ig.n_nodes))

    def step_impls(self, fused: bool):
        # a JPL round is already single-phase; fused == two-phase here
        return jpl_dense_step_impl, jpl_sparse_step_impl

    def step_fns(self, fused: bool):
        return (ipgc.tallied(jpl_dense_step_impl, dense=True),
                ipgc.tallied(jpl_sparse_step_impl))

    def dense_slots(self, ig, force_hub):
        # the ELL path under every plan (csr-segment too): all N rows
        return self.sparse_slots(ig, ig.n_nodes, 0, force_hub)

    def sparse_slots(self, ig, capacity, live, force_hub):
        # no packing: the ELL rows of the worklist, and the whole hub tail
        tail = ig.tail_dst.shape[0] if ipgc._has_hubs(ig, force_hub) else 0
        return capacity * ig.ell_width + tail

    def resolve_fused(self, fused, *, default):
        return False                      # single step family

    def make_dist_steps(self, ig_local, mesh, node_axes, *, window: int,
                        fused: bool, exchange: str = "dense", boundary=None,
                        thresh: int | None = None):
        # window/fused are protocol arguments JPL ignores (no mex window,
        # single step family) — same contract as the host steps
        return make_jpl_dist_steps(ig_local, mesh, node_axes,
                                   exchange=exchange, boundary=boundary,
                                   thresh=thresh)

    def finalize(self, colors):
        return _compact_palette(colors)

    def check_invariants(self, result, g=None):
        super().check_invariants(result, g)
        # each round confirms at most two color classes
        assert result.n_colors <= 2 * max(result.iterations, 1), (
            f"jpl: {result.n_colors} colors from {result.iterations} rounds")
