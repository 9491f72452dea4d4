"""Explicitly-distributed hybrid coloring engine (shard_map).

Owner-computes partitioning of the paper's Pipe — BOTH phases, so the
persistent-worklist invariant (DESIGN.md §1) holds across shard
boundaries:

  * each shard owns a contiguous node block (graphs.partition.
    prepare_partition pads to equal, 8-aligned blocks and balances total
    degree across them so no shard owns all hubs — straggler mitigation at
    the data layout level);
  * the ONLY cross-shard value is the color vector, published by the
    additive all-gather trick: each shard psums its disjoint owner-block
    delta (int32[N+1]) — the TPU analogue of the GPU's global color array.
    The fused steps (the driver default) perform exactly ONE such exchange
    per iteration — 4N bytes/device/iter, independent of edge count — and
    the two-phase steps exactly TWO (speculate + undo); the invariant is
    enforced at trace time via ``EXCHANGE_COUNTS`` (tests/
    test_distributed.py);
  * worklist state stays shard-local in both phases: the dense sweep
    reads its block of ``mask`` and re-compacts its block of ``items``;
    the sparse step gathers and O(C)-filters only its own items block,
    sliced down a per-shard capacity ladder (``bucket_capacities(block)``)
    at bucket boundaries. The hybrid switch decision needs one scalar
    psum (= IrGL Pipe's size check), read back by the host driver
    (``color_distributed``) exactly like the host-loop Pipe.

The dense two-phase step is bit-identical to the reference engine on any
shard count; the fused steps are bit-identical to ``ipgc.fused_*_step``
(so ``color_distributed`` reproduces ``engine.color(fused=True)``'s
colors, iteration count and mode trace for fixed-H policies —
DESIGN.md §6).

``exchange="boundary"|"auto"`` (DESIGN.md §13) replaces the full-vector
psum with a packed publish of only *changed boundary* vertices — the
paper's dense/sparse hybridization applied to the communication axis
(Bogle & Slota, arXiv 2107.00075). Color state becomes per-shard views
(correct at owned + ghost ids); ``_publish_packed`` switches on-device
between the packed buffers and a dense owner-block swap, so correctness
never depends on the boundary-buffer capacity guess. Every combination
stays bit-identical to the host engine (tests/test_boundary.py).
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import ipgc
from repro.core.engine import ColoringResult
from repro.core.policy import Policy
from repro.core.worklist import (Worklist, compact_items, compact_mask,
                                 resize_block)
from repro.graphs.csr import Graph, NO_COLOR
from repro.obs.metrics import default_registry

# --- exchange instrumentation (trace-time) ---------------------------------
# Every color-vector exchange goes through ``_exchange_colors`` or
# ``_publish_packed`` so tests can assert the communication volume per
# step: one exchange per fused iteration, two per two-phase iteration.
# Counters increment at trace time (à la ipgc.GATHER_COUNTS) — inspect by
# tracing a step with ``jax.eval_shape`` inside an
# ``EXCHANGE_COUNTS.scope()`` block. Keys: ``color_psum`` (dense additive
# all-gather, the exchange="dense" path), ``boundary_pack`` /
# ``dense_swap`` (the two branches of a packed publish — BOTH trace per
# publish, the runtime picks one on-device). The group is a reset-scoped
# ``CounterGroup`` in the obs default registry (DESIGN.md §12); scopes
# zero on entry and restore outer values on exit.
EXCHANGE_COUNTS = default_registry().group(
    "dist.exchanges", ("color_psum", "boundary_pack", "dense_swap"))


def reset_exchange_counts() -> None:
    """Legacy zeroing hook; prefer ``EXCHANGE_COUNTS.scope()``."""
    EXCHANGE_COUNTS.reset()


def _exchange_colors(colors: jax.Array, delta: jax.Array,
                     node_axes: tuple) -> jax.Array:
    """Additive all-gather: shards hold disjoint owner-block updates as a
    dense delta against the replicated vector, so a psum IS the gather."""
    EXCHANGE_COUNTS["color_psum"] += 1
    return colors + jax.lax.psum(delta, node_axes)


def _publish_packed(view, ids, old, vals, is_bnd, *, n: int, node_axes,
                    idx, blk: int, bcap: int, thresh: int):
    """Publish owned color updates into a per-shard color *view*.

    ``view`` is this shard's int32[n+1] color vector (correct at owned +
    ghost ids, possibly stale elsewhere — DESIGN.md §13); ``ids`` are the
    owned global ids being written (pad lanes carry id >= n), ``old`` the
    colors those ids currently hold in the view, ``vals`` the new colors.

    Owned writes always land locally. Cross-shard publication then picks
    ON-DEVICE between:
      * packed: all-gather only the ``(id, color)`` pairs of *changed
        boundary* vertices, compacted into a static int32[bcap] buffer
        (8·bcap·S bytes) and scatter-unpacked (pad id n+1 is out of
        bounds for int32[n+1] → dropped, protecting the PAD_COLOR
        sentinel at slot n);
      * dense swap: all-gather the full owner blocks (~4n bytes) — the
        correctness fallback when any shard's changed-boundary count
        overflows ``bcap`` OR the global changed-boundary total exceeds
        the policy ``thresh``, so correctness never depends on the
        capacity guess.
    The predicate is replicated (computed from an all-gather of every
    shard's changed count) so every shard takes the same branch —
    collectives under ``lax.cond`` stay in lockstep.

    Returns ``(view', n_packed, max_changed)`` with the two stats
    replicated int32 scalars: how many of this iteration's publishes went
    packed (0/1 here; the driver sums across the step's publishes) and
    the largest per-shard changed-boundary count (feeds the driver's
    next-bucket prediction).
    """
    ids = ids.astype(jnp.int32)
    vals = vals.astype(jnp.int32)
    valid = ids < n
    # own writes are always local (drop pad lanes)
    view = view.at[jnp.where(valid, ids, n + 1)].set(vals, mode="drop")
    changed = valid & is_bnd & (vals != old)
    local_cb = changed.sum(dtype=jnp.int32)
    # one scalar all-gather feeds BOTH gate reductions (max + sum) —
    # on-wire collective COUNT matters as much as payload bytes, so the
    # gate costs one rendezvous, not two
    counts = jax.lax.all_gather(local_cb, node_axes)
    biggest = jnp.max(counts)
    total = jnp.sum(counts, dtype=jnp.int32)
    use_packed = (biggest <= bcap) & (total <= thresh)
    m = ids.shape[0]

    def packed(v):
        EXCHANGE_COUNTS["boundary_pack"] += 1
        (pos,) = jnp.nonzero(changed, size=bcap, fill_value=m)
        ids_ext = jnp.concatenate(
            [ids, jnp.full((1,), n + 1, jnp.int32)])
        vals_ext = jnp.concatenate([vals, jnp.zeros((1,), jnp.int32)])
        # ids and colors ride ONE all-gather as a fused (2*bcap,) buffer:
        # same 8*bcap bytes per shard, half the collectives
        payload = jnp.concatenate([ids_ext[pos], vals_ext[pos]])
        allp = jax.lax.all_gather(payload, node_axes)
        allp = allp.reshape(-1, 2 * bcap)
        pids = allp[:, :bcap].reshape(-1)
        pvals = allp[:, bcap:].reshape(-1)
        return v.at[pids].set(pvals, mode="drop")

    def dense_swap(v):
        EXCHANGE_COUNTS["dense_swap"] += 1
        own = jax.lax.dynamic_slice(v, (idx * blk,), (blk,))
        return v.at[:n].set(jax.lax.all_gather(own, node_axes, tiled=True))

    view = jax.lax.cond(use_packed, packed, dense_swap, view)
    return view, use_packed.astype(jnp.int32), biggest


def views_to_colors(views, n_shards: int, n: int):
    """Host-side finalize for the boundary-exchange paths: per-shard views
    only agree at owned + ghost ids, so the true int32[n] color vector is
    the concatenation of each shard's OWN block of its OWN view."""
    v = np.asarray(views)
    block = n // n_shards
    return np.concatenate(
        [v[s, s * block:(s + 1) * block] for s in range(n_shards)])


def _shard_offset(mesh, node_axes: tuple):
    """Linear shard index over the flattened node axes (static shapes)."""
    idx = 0
    mult = 1
    for ax in reversed(node_axes):
        idx = idx + jax.lax.axis_index(ax) * mult
        mult = mult * mesh.shape[ax]  # static (lax.axis_size: jax>=0.6)
    return idx


# ---------------------------------------------------------------------------
# the graph operands of the sharded steps: ELL blocks + shard-local tails
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ShardGraph:
    """A prepared graph laid out for the sharded steps, placed on the mesh.

    ELL rows are row-sharded and priorities replicated. The hub tail is
    split by owner (owner computes): a hub's COO-tail entries live on the
    shard that owns the hub row, so a step sweeps or packs only its own
    entries, never the whole tail (50M entries at kron_g500-logn21
    scale). Every shard's slice is padded to one length (pad entries
    point at the sentinel ``n``, hold the inert slot ``n_hub`` and are
    invalid) and to ``n_hub`` hub slots, the most any shard owns (spare
    slots own empty ranges at the end). Hub slots are shard-local:
    ``hub_slot[u]`` is u's slot on its owner shard (``n_hub`` for
    non-hubs), replicated.

    The arrays are jit *arguments* of the steps, placed once. Closed over
    instead, jit would embed them in every step program as constants
    (about a gigabyte at that scale) and copy them to the devices again
    on every call.
    """

    n_hub: int
    seg_bound: tuple      # ipgc.IPGCGraph.seg_bound over every shard
    # (ell_idx, hub_slot, priority, (tail_src, tail_dst, tail_valid,
    #  tail_slot, tail_start, hub_ids)); tail arrays shard-major
    arrays: tuple
    specs: tuple          # shard_map specs of ``arrays``


def shard_graph(ig: ipgc.IPGCGraph, mesh, node_axes: tuple) -> ShardGraph:
    """Lay a prepared graph out over the mesh's equal row blocks (as
    ``prepare_partition`` numbers them)."""
    n_hub, seg_bound, hub_slot, tails = split_tail(
        ig, math.prod(mesh.shape[a] for a in node_axes))
    na = node_axes
    specs = (P(na, None), P(), P(), P(na))

    def placed(spec, x):
        return jax.device_put(x, NamedSharding(mesh, spec))
    return ShardGraph(
        n_hub=n_hub, seg_bound=seg_bound,
        arrays=(placed(specs[0], ig.ell_idx), placed(P(), hub_slot),
                placed(P(), ig.priority),
                tuple(placed(specs[3], a) for a in tails)),
        specs=specs)


def split_tail(ig: ipgc.IPGCGraph, n_shards: int):
    """The host-side split behind ``shard_graph``: ``(n_hub, seg_bound,
    hub_slot, (tail_src, tail_dst, tail_valid, tail_slot, tail_start,
    hub_ids))``, the tail arrays shard-major."""
    n = ig.n_nodes
    blk = n // n_shards
    valid = np.asarray(ig.tail_valid)
    src = np.asarray(ig.tail_src)[valid]          # ascending source rows
    dst = np.asarray(ig.tail_dst)[valid]
    hubs = np.asarray(ig.hub_ids)[:ig.n_hub]      # ascending hub rows
    hub_shard = hubs // blk
    per_shard = np.bincount(hub_shard, minlength=n_shards)
    nh = int(per_shard.max()) if hubs.size else 0
    first = np.concatenate([[0], np.cumsum(per_shard)[:-1]])
    local = (np.arange(hubs.size) - first[hub_shard]).astype(np.int32)
    hub_slot = np.full(n, nh, np.int32)
    hub_slot[hubs] = local
    hub_ids = np.repeat(np.arange(n_shards, dtype=np.int32) * blk,
                        max(nh, 1))               # spare slots: any own row
    hub_ids[hub_shard * max(nh, 1) + local] = hubs

    e_shard = src // blk
    e_count = np.bincount(e_shard, minlength=n_shards)
    t = max(-(-int(e_count.max(initial=0)) // 8) * 8, 8)
    e_first = np.concatenate([[0], np.cumsum(e_count)[:-1]])
    pos = np.arange(src.size) - e_first[e_shard] + e_shard * t
    slot = hub_slot[src]
    tail_src = np.repeat(np.arange(n_shards, dtype=np.int32) * blk, t)
    tail_dst = np.full(n_shards * t, n, np.int32)
    tail_valid = np.zeros(n_shards * t, bool)
    tail_slot = np.full(n_shards * t, nh, np.int32)
    tail_src[pos], tail_dst[pos] = src, dst
    tail_valid[pos], tail_slot[pos] = True, slot
    bounds = np.concatenate([[0], np.cumsum(e_count)])
    tail_start = np.concatenate([
        np.searchsorted(slot[bounds[s]:bounds[s + 1]], np.arange(nh + 1))
        for s in range(n_shards)]).astype(np.int32)

    owned = np.bincount(src, minlength=n).reshape(n_shards, blk)
    per = [ipgc._top_sums(o) for o in owned]
    return (nh, tuple(max(b) for b in zip(*per)), hub_slot,
            (tail_src, tail_dst, tail_valid, tail_slot, tail_start, hub_ids))


def _bind(local_step, mesh, na: tuple, sg: ShardGraph, isb, *,
          views: bool, sparse: bool):
    """The jitted step around the shard_map'd ``local_step``; the graph
    operands and boundary flags ride as arguments (see ``ShardGraph``)."""
    cspec = P(na, None) if views else P()
    in_specs = ((cspec, P(na), P(na)) + ((P(na),) if sparse else ())
                + (P(na), sg.specs))
    out_specs = (cspec, P(na), P(na), P(na), P()) + ((P(),) if views else ())
    graph = sg.arrays
    isb = jax.device_put(isb, NamedSharding(mesh, P(na)))

    @partial(jax.jit, static_argnames=("bcap",))
    def run(graph, isb, colors, base, wl: Worklist, bcap):
        fn = jax.shard_map(partial(local_step, bcap=bcap), mesh=mesh,
                           in_specs=in_specs, out_specs=out_specs,
                           check_vma=False)
        items = (wl.items,) if sparse else ()
        outs = fn(colors, base, wl.mask, *items, isb, graph)
        colors2, base2, mask, items, count = outs[:5]
        return (colors2, base2,
                Worklist(mask=mask, items=items, count=count)) + outs[5:]

    if views:
        def step(views_, base, wl: Worklist, *, bcap: int):
            return run(graph, isb, views_, base, wl, bcap=bcap)
    else:
        def step(colors, base, wl: Worklist, *, bcap=None):
            return run(graph, isb, colors, base, wl, bcap=None)
    # the program for given state shapes, without running it
    step.lower = lambda colors, base, wl, *, bcap=None: run.lower(
        graph, isb, colors, base, wl, bcap=bcap)
    return step


def _local_graph_view(ig_local: ipgc.IPGCGraph, sg: ShardGraph, n: int,
                      graph) -> ipgc.IPGCGraph:
    """IPGCGraph over this shard's row block and its own hub tail."""
    ell_l, hub_slot, prio, (tail_src, tail_dst, tail_valid, tail_slot,
                            tail_start, hub_ids) = graph
    return ipgc.IPGCGraph(
        n_nodes=n, ell_width=ig_local.ell_width, n_hub=sg.n_hub,
        ell_idx=ell_l, degrees=jnp.zeros((0,), jnp.int32), priority=prio,
        tail_src=tail_src, tail_dst=tail_dst, tail_valid=tail_valid,
        tail_slot=tail_slot, hub_slot=hub_slot, hub_ids=hub_ids,
        tail_start=tail_start, seg_bound=sg.seg_bound)


def _own_block(full: jax.Array, block: jax.Array, row_ids: jax.Array
               ) -> jax.Array:
    """``full`` with this shard's row block (``row_ids``, contiguous)
    overwritten by ``block``: one slice update, not a scatter (an
    unsorted scatter into a vector of millions takes the TPU compiler
    seconds)."""
    return jax.lax.dynamic_update_slice(full, block.astype(full.dtype),
                                        (row_ids[0],))


def _dense_hub_forbidden(ig: ipgc.IPGCGraph, colors, base_l, row_ids,
                         window: int):
    """(blk, W) tail forbidden bitmap of every owned row: a sweep of this
    shard's tail."""
    base_pad = _own_block(jnp.zeros((ig.n_nodes,), jnp.int32), base_l,
                          row_ids)
    return ipgc._hub_forbidden(ig, colors, base_pad, window)[
        ig.hub_slot[row_ids]]


def _dense_hub_lose(ig: ipgc.IPGCGraph, colors, flags, row_ids):
    """(blk,) tail conflict flags of the owned rows flagged in ``flags``."""
    full = _own_block(jnp.zeros((ig.n_nodes + 1,), bool), flags, row_ids)
    return ipgc._hub_lose(ig, colors, full)[ig.hub_slot[row_ids]]


def _sparse_hub(ig: ipgc.IPGCGraph, items_l, valid, base_rows, base_l,
                row_ids, window: int):
    """Hub-tail terms of a sparse step's rows, as ``(forbidden(colors),
    lose(colors, flags, c_rows))``: packed over the tail entries of the
    step's own hub rows when the bound allows (``ipgc._hub_packed``, as
    the single-device sparse steps do), else a sweep of this shard's
    tail. ``flags`` marks the rows that may lose, ``c_rows`` holds their
    colors (negative elsewhere); both forms give the same bits."""
    n, nh = ig.n_nodes, ig.n_hub
    pk = ipgc._hub_packed(ig, items_l)
    if pk is not None:
        def forbidden(colors):
            return ipgc._packed_forbidden(pk, colors[pk.dst], base_rows,
                                          window)

        def lose(colors, flags, c_rows):
            return ipgc._packed_lose(pk, colors[pk.dst], c_rows, base_rows,
                                     window) & valid
        return forbidden, lose
    slot_c = jnp.where(valid, ig.hub_slot[jnp.minimum(items_l, n - 1)], nh)
    base_pad = _own_block(jnp.zeros((n,), jnp.int32), base_l, row_ids)

    def forbidden(colors):
        return ipgc._hub_forbidden(ig, colors, base_pad, window)[slot_c]

    def lose(colors, flags, c_rows):
        full = jnp.zeros((n + 1,), bool).at[
            jnp.where(flags, items_l, n)].set(flags, mode="drop")
        return ipgc._hub_lose(ig, colors, full)[slot_c] & valid
    return forbidden, lose


# ---------------------------------------------------------------------------
# dense (topology-driven) distributed step
# ---------------------------------------------------------------------------

def make_dist_dense_step(ig_local: ipgc.IPGCGraph, mesh, node_axes: tuple,
                         *, window: int = 128, n_global: int | None = None,
                         fused: bool = False, exchange: str = "dense",
                         boundary=None, thresh: int | None = None,
                      sg: "ShardGraph | None" = None):
    """Build a shard_map'd dense step.

    ig_local: the IPGCGraph whose per-shard row blocks will be fed in
    (laid out over the mesh by ``shard_graph``; ``sg`` passes a layout
    the caller already made, so a step pair shares one).
    Returns step(colors_global, base, wl) -> (colors_global, base, wl)
    where colors_global is the replicated int32[N+1] vector and
    base/mask/items are node-sharded.

    ``fused=False`` is the two-phase step (bit-identical to
    ``ipgc.dense_step``, two color exchanges per iteration);
    ``fused=True`` pipelines resolve-of-last-round with assign
    (bit-identical to ``ipgc.fused_dense_step``, ONE exchange).

    ``exchange != "dense"`` switches the color state from one replicated
    int32[N+1] vector to per-shard *views* of shape (S, N+1) — sharded
    ``P(node_axes, None)`` — published through ``_publish_packed``
    instead of the additive psum. The returned step then has signature
    ``step(views, base, wl, *, bcap)`` (``bcap`` static, retraced per
    boundary-buffer rung) and returns an extra replicated int32[2]
    ``xstats = [n_packed_publishes, max_changed_boundary]`` for the
    driver's byte ledger and bucket prediction. ``boundary`` is the
    partition-time ``BoundaryInfo``; ``thresh`` the static changed-count
    threshold from ``policy.exchange_threshold``.
    """
    n = n_global or ig_local.n_nodes
    if sg is None:
        sg = shard_graph(ig_local, mesh, node_axes)
    na = node_axes
    views = exchange != "dense"
    if views:
        isb = boundary.is_boundary
        th = int(thresh)
    else:
        isb = np.zeros((ig_local.n_nodes,), bool)     # unread

    def local_step(colors, base_l, mask_l, isb_l, graph, *, bcap=None):
        idx = _shard_offset(mesh, node_axes)
        ell_l = graph[0]
        blk = ell_l.shape[0]
        row_ids = idx * blk + jnp.arange(blk, dtype=jnp.int32)
        if views:
            colors = colors[0]              # this shard's (n+1,) view
            pub = partial(_publish_packed, n=n, node_axes=node_axes,
                          idx=idx, blk=blk, bcap=bcap, thresh=th)
        ig = _local_graph_view(ig_local, sg, n, graph)
        hubs = sg.n_hub > 0
        active = mask_l
        nc = colors[ell_l]                              # local gather
        prio = ig.priority

        if fused:
            cu = colors[row_ids]
            pu = prio[row_ids]
            pending = active & (cu >= 0)
            npr = prio[ell_l]
            extra = (_dense_hub_forbidden(ig, colors, base_l, row_ids,
                                          window) if hubs else None)
            lose, first, has = ipgc._fused_rows(
                ig, nc, npr, ell_l, base_l, cu, pu, row_ids, pending, extra,
                window, "jnp")
            if hubs:
                # owned hub slots read owned tail_src rows only — a local
                # scatter of pending suffices (no psum)
                lose = lose | (_dense_hub_lose(ig, colors, pending, row_ids)
                               & pending)
            need = lose | (active & (cu < 0))
            new_c = jnp.where(need & has, base_l + first,
                              jnp.where(lose, NO_COLOR, cu))
            new_base = jnp.where(need & ~has, base_l + window, base_l)
            # ONE exchange publishes speculated colors AND uncolorings
            if views:
                colors_out, npk, mx = pub(colors, row_ids, cu, new_c, isb_l)
            else:
                delta = _own_block(jnp.zeros((n + 1,), jnp.int32),
                                   new_c - cu, row_ids)
                colors_out = _exchange_colors(colors, delta, node_axes)
            still = need
        else:
            # --- assign ---
            cu0 = colors[row_ids]
            extra = (_dense_hub_forbidden(ig, colors, base_l, row_ids,
                                          window) if hubs else None)
            new_c, new_base, newly = ipgc._mex_rows(
                ig, nc, base_l, active, cu0, extra, window, "jnp")
            # exchange 1: publish the speculative colors of owned rows
            spec_c = jnp.where(active, new_c, cu0)
            if views:
                colors2, npk1, b1 = pub(colors, row_ids, cu0, spec_c, isb_l)
            else:
                delta = _own_block(jnp.zeros((n + 1,), jnp.int32),
                                   spec_c - cu0, row_ids)
                colors2 = _exchange_colors(colors, delta, node_axes)
            # --- resolve ---
            lose = ipgc._lose_rows(ig, ell_l, row_ids, colors2, newly, "jnp")
            if hubs:
                lose = lose | _dense_hub_lose(ig, colors2, newly, row_ids)
            # exchange 2: uncolor losers (their writes were in colors2)
            c2r = colors2[row_ids]
            if views:
                colors_out, npk2, b2 = pub(colors2, row_ids, c2r,
                                           jnp.where(lose, NO_COLOR, c2r),
                                           isb_l)
                npk, mx = npk1 + npk2, jnp.maximum(b1, b2)
            else:
                undo = _own_block(jnp.zeros((n + 1,), jnp.int32),
                                  jnp.where(lose, NO_COLOR - c2r, 0), row_ids)
                colors_out = _exchange_colors(colors2, undo, node_axes)
            still = lose | (active & ~newly)

        items_l, _ = compact_mask(still, blk, blk)
        items_l = jnp.where(items_l < blk, idx * blk + items_l, n)
        count = jax.lax.psum(still.sum(dtype=jnp.int32), node_axes)
        out = (colors_out, new_base, still, items_l.astype(jnp.int32), count)
        if views:
            return (colors_out[None],) + out[1:] + (
                jnp.stack([npk, mx]).astype(jnp.int32),)
        return out

    step = _bind(local_step, mesh, na, sg, isb, views=views, sparse=False)
    step.exchanges_per_iter = 1 if fused else 2
    return step


# ---------------------------------------------------------------------------
# sparse (data-driven) distributed step — shard-local items/count
# ---------------------------------------------------------------------------

def make_dist_sparse_step(ig_local: ipgc.IPGCGraph, mesh, node_axes: tuple,
                          *, window: int = 128, n_global: int | None = None,
                          fused: bool = False, exchange: str = "dense",
                          boundary=None, thresh: int | None = None,
                       sg: "ShardGraph | None" = None):
    """Build a shard_map'd data-driven step over shard-local worklists.

    Each shard gathers only its own compacted items block (global node ids
    it owns, padded with N), so per-iteration cost tracks the shard's
    active-set slice, not its block size; its hub rows read only their
    own tail entries (packed, ``_sparse_hub``). The color exchange is the
    same additive all-gather as the dense step; the worklist filter
    (``compact_items``) and the ``mask`` write-back stay O(C) and
    shard-local. The returned ``step(colors, base, wl)`` expects
    ``wl.items`` of global shape ``n_shards * C`` (per-shard blocks) and
    retraces per capacity bucket, exactly like the host engine.

    ``exchange != "dense"``: view-state + packed-publish variant, same
    contract as ``make_dist_dense_step`` (extra static ``bcap`` kwarg,
    extra ``xstats`` output).
    """
    n = n_global or ig_local.n_nodes
    if sg is None:
        sg = shard_graph(ig_local, mesh, node_axes)
    na = node_axes
    views = exchange != "dense"
    if views:
        isb = boundary.is_boundary
        th = int(thresh)
    else:
        isb = np.zeros((ig_local.n_nodes,), bool)     # unread

    def local_step(colors, base_l, mask_l, items_l, isb_l, graph, *,
                   bcap=None):
        idx = _shard_offset(mesh, node_axes)
        ell_l = graph[0]
        blk = ell_l.shape[0]
        row_ids = idx * blk + jnp.arange(blk, dtype=jnp.int32)
        if views:
            colors = colors[0]
            pub = partial(_publish_packed, n=n, node_axes=node_axes,
                          idx=idx, blk=blk, bcap=bcap, thresh=th)
        ig = _local_graph_view(ig_local, sg, n, graph)
        hubs = sg.n_hub > 0
        prio = ig.priority
        valid = items_l < n
        # local row index of each owned item (this shard only ever holds
        # ids from its own block; clip guards the pad lanes)
        local = jnp.clip(jnp.where(valid, items_l - idx * blk, 0), 0, blk - 1)
        ids = jnp.where(valid, items_l, n)              # global ids, pad n
        if views:
            isb_items = valid & isb_l[local]
        ell_rows = jnp.where(valid[:, None], ell_l[local], n)    # (C, K)
        nc = colors[ell_rows]
        base_rows = base_l[local]
        cu = colors[ids]                                # pad -> PAD_COLOR
        if hubs:
            hub_forbidden, hub_lose = _sparse_hub(
                ig, items_l, valid, base_rows, base_l, row_ids, window)

        if fused:
            pu = prio[ids]
            npr = prio[ell_rows]
            pending = valid & (cu >= 0)
            extra = hub_forbidden(colors) if hubs else None
            lose, first, has = ipgc._fused_rows(
                ig, nc, npr, ell_rows, base_rows, cu, pu, ids, pending,
                extra, window, "jnp")
            if hubs:
                lose = lose | (hub_lose(colors, pending,
                                        jnp.where(pending, cu, NO_COLOR))
                               & pending)
            need = lose | (valid & (cu < 0))
            new_c = jnp.where(need & has, base_rows + first,
                              jnp.where(lose, NO_COLOR, cu))
            new_base_rows = jnp.where(need & ~has, base_rows + window,
                                      base_rows)
            # ONE exchange (pad lanes contribute delta 0 at the sentinel)
            if views:
                colors_out, npk, mx = pub(colors, ids, cu,
                                          jnp.where(valid, new_c, cu),
                                          isb_items)
            else:
                delta = jnp.zeros((n + 1,), jnp.int32).at[ids].set(
                    new_c - cu)
                colors_out = _exchange_colors(colors, delta, node_axes)
            still = need
        else:
            # --- assign ---
            extra = hub_forbidden(colors) if hubs else None
            new_c, new_base_rows, newly = ipgc._mex_rows(
                ig, nc, base_rows, valid, cu, extra, window, "jnp")
            if views:
                colors2, npk1, b1 = pub(colors, ids, cu,
                                        jnp.where(valid, new_c, cu),
                                        isb_items)
            else:
                delta = jnp.zeros((n + 1,), jnp.int32).at[ids].set(
                    jnp.where(valid, new_c - cu, 0))
                colors2 = _exchange_colors(colors, delta, node_axes)
            # --- resolve ---
            lose = ipgc._lose_rows(ig, ell_rows, ids, colors2, newly, "jnp")
            if hubs:
                lose = lose | hub_lose(colors2, newly,
                                       jnp.where(newly, new_c, NO_COLOR))
            c2 = colors2[ids]
            if views:
                colors_out, npk2, b2 = pub(colors2, ids, c2,
                                           jnp.where(lose, NO_COLOR, c2),
                                           isb_items)
                npk, mx = npk1 + npk2, jnp.maximum(b1, b2)
            else:
                undo = jnp.zeros((n + 1,), jnp.int32).at[ids].set(
                    jnp.where(lose, NO_COLOR - c2, 0))
                colors_out = _exchange_colors(colors2, undo, node_axes)
            still = lose | (valid & ~newly)

        # --- maintain the worklist in O(C), shard-local ---
        new_items, local_count = compact_items(items_l, still, n)
        mask2 = mask_l.at[jnp.where(valid, local, blk)].set(still,
                                                            mode="drop")
        base2 = base_l.at[jnp.where(valid, local, blk)].set(new_base_rows,
                                                            mode="drop")
        count = jax.lax.psum(local_count, node_axes)
        if views:
            xstats = jnp.stack([npk, mx]).astype(jnp.int32)
            return colors_out[None], base2, mask2, new_items, count, xstats
        return colors_out, base2, mask2, new_items, count

    step = _bind(local_step, mesh, na, sg, isb, views=views, sparse=True)
    step.exchanges_per_iter = 1 if fused else 2
    return step


def make_dist_resize(mesh, node_axes: tuple, n_global: int):
    """Shard-local bucket change: every shard slices (or pads) its own
    already-compacted items block — the distributed form of
    ``worklist.resize_items``. Valid whenever the new per-shard capacity
    bounds every shard's live count; the driver guarantees it by picking
    ``pick_bucket(caps_block, min(global_count, block))``."""
    na = node_axes

    @partial(jax.jit, static_argnames=("capacity",))
    def resize(wl: Worklist, capacity: int) -> Worklist:
        fn = jax.shard_map(lambda il: resize_block(il, capacity, n_global),
                           mesh=mesh, in_specs=P(na), out_specs=P(na),
                           check_vma=False)
        return Worklist(mask=wl.mask, items=fn(wl.items), count=wl.count)

    return resize


# ---------------------------------------------------------------------------
# the distributed hybrid Pipe driver
# ---------------------------------------------------------------------------

def color_distributed(
    g: Graph,
    *,
    n_shards: int | None = None,
    mesh=None,
    node_axes: tuple = ("data",),
    mode: str = "hybrid",
    algo: str | object = "ipgc",
    h: float = 0.6,
    window: int | str = "auto",
    bucket_ratio: int = 2,
    max_iter: int = 10_000,
    priority: str = "hash",
    policy: Policy | None = None,
    collect_tti: bool = False,
    fused: bool | None = True,    # fused = ONE color exchange per iteration
    balance: bool = True,
    steps_cache: dict | None = None,
    layout: "str | object | None" = None,
    exchange: str = "dense",
) -> ColoringResult:
    """Sharded hybrid Pipe: the host-loop driver over the shard_map steps.

    The graph is padded + degree-balanced into equal owner blocks
    (``prepare_partition``); the driver then runs the exact host-Pipe
    control flow — policy on the psum'd global count, per-shard capacity
    ladder with slices at bucket boundaries — over the distributed steps.
    With the default ``fused=True`` the steps are bit-identical to
    ``ipgc.fused_*_step`` on the repartitioned graph, so for fixed-H
    policies the result matches ``engine.color(g2, fused=True)`` exactly
    (colors, iteration count, mode trace) on ANY shard count
    (tests/test_distributed.py). Colors are returned in ``g``'s original
    node labeling.

    ``fused=None`` resolves to the distributed default (True).
    ``algo`` must name a shard-safe algorithm (the declaration contract,
    DESIGN.md §7); its ``make_dist_steps`` supplies the shard_map'd step
    pair and its ``init_state``/``finalize`` bracket the run.
    ``steps_cache``: legacy compile-cache argument, still accepted — the
    dict becomes the backing store of the ``Session`` the call runs on,
    so passing the same dict across calls reuses the partitioned graph
    and the jitted shard_map steps exactly as before. ``None`` runs on
    the process-default session (DESIGN.md §9), which amortizes the same
    artifacts across ALL entry points instead of per caller-dict.
    ``layout``: engine-level plan override (``engine.resolve_plan``);
    the sharded steps are the ELL-family tile steps, so ``csr-segment``
    execution is rejected — pass ``layout="ell-tail"`` to run a
    csr-segment-planned graph here (its ELL+tail arrays are complete).
    ``exchange``: cross-shard color publication path (DESIGN.md §13) —
    ``"dense"`` (additive psum of int32[N+1], the historical path),
    ``"boundary"`` (packed changed-boundary buffers whenever they fit),
    or ``"auto"`` (packed only below the byte break-even threshold).
    Static knob: it rides the compile-cache key. All three are
    bit-identical (tests/test_boundary.py).
    """
    # thin dispatcher over the unified session (driver loop + cache live
    # in repro.exec.session; lazy import — repro.exec imports this module)
    from repro.exec import ExecutionSpec, Session, default_session
    spec = ExecutionSpec(
        regime="dist", mode=mode, algo=algo, layout=layout, h=h,
        window=window, bucket_ratio=bucket_ratio, max_iter=max_iter,
        priority=priority, fused=fused, n_shards=n_shards, balance=balance,
        exchange=exchange)
    session = (default_session() if steps_cache is None
               else Session(cache=steps_cache))
    return session.run(spec, g, policy=policy, collect_tti=collect_tti,
                       mesh=mesh, node_axes=node_axes)
