"""Pure-jnp oracles for every Pallas kernel (the correctness references)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def mex_window_ref(nc: jax.Array, base: jax.Array, extra_forb: jax.Array,
                   window: int) -> jax.Array:
    """First free window index per row; -1 if the whole window is forbidden."""
    rel = nc - base[:, None]
    ok = (nc >= 0) & (rel >= 0) & (rel < window)
    iota = jnp.arange(window, dtype=jnp.int32)
    forb = (ok[:, :, None] & (rel[:, :, None] == iota)).any(axis=1)
    forb = forb | extra_forb
    free = ~forb
    has = free.any(axis=1)
    first = jnp.argmax(free, axis=1).astype(jnp.int32)
    return jnp.where(has, first, -1)


def conflict_ref(nc: jax.Array, npr: jax.Array, nbr_ids: jax.Array,
                 cu: jax.Array, pu: jax.Array, ids: jax.Array) -> jax.Array:
    same = (nc == cu[:, None]) & (cu >= 0)[:, None]
    higher = (npr > pu[:, None]) | ((npr == pu[:, None]) &
                                    (nbr_ids > ids[:, None]))
    return (same & higher).any(axis=1)


def fused_step_ref(nc: jax.Array, npr: jax.Array, nbr_ids: jax.Array,
                   base: jax.Array, cu: jax.Array, pu: jax.Array,
                   ids: jax.Array, pending: jax.Array,
                   extra_forb: jax.Array, window: int
                   ) -> tuple[jax.Array, jax.Array]:
    """Fused resolve+assign oracle: conflict check on the pre-snapshot tile
    plus windowed mex over the same tile."""
    lose = conflict_ref(nc, npr, nbr_ids, cu, pu, ids) & pending
    first = mex_window_ref(nc, base, extra_forb, window)
    return lose, first


def fused_compact_ref(nc: jax.Array, npr: jax.Array, nbr_ids: jax.Array,
                      base: jax.Array, cu: jax.Array, pu: jax.Array,
                      ids: jax.Array, active: jax.Array, pending: jax.Array,
                      extra_forb: jax.Array | None,
                      hub_lose: jax.Array | None, window: int, *,
                      capacity: int, n_sentinel: int, no_color: int = -1
                      ) -> tuple[jax.Array, jax.Array, jax.Array,
                                 jax.Array, jax.Array]:
    """Fused step + compaction oracle (kernels/fused_compact.py):
    resolve + windowed mex + new-color/base selection + emission of the
    surviving rows' ``ids`` in ascending row order with a sentinel tail —
    the exact semantics of the jnp fused step followed by
    ``worklist.compact_mask``/``compact_items``."""
    r = nc.shape[0]
    if extra_forb is None:
        extra_forb = jnp.zeros((r, window), bool)
    lose, first = fused_step_ref(nc, npr, nbr_ids, base, cu, pu, ids,
                                 pending, extra_forb, window)
    if hub_lose is not None:
        lose = lose | (hub_lose & pending)
    has = first >= 0
    need = lose | (active & (cu < 0))
    new_c = jnp.where(need & has, base + first,
                      jnp.where(lose, no_color, cu))
    new_base = jnp.where(need & ~has, base + window, base)
    (pos,) = jnp.nonzero(need, size=capacity, fill_value=r)
    ids_ext = jnp.concatenate(
        [ids.astype(jnp.int32), jnp.full((1,), n_sentinel, jnp.int32)])
    return (new_c, new_base, need, ids_ext[pos],
            need.sum(dtype=jnp.int32))


def edge_forbidden_ref(es: jax.Array, ec: jax.Array, base_src: jax.Array,
                       n_rows: int, window: int) -> jax.Array:
    """(N, W) forbidden-bitmap oracle for ``csr_segment.edge_forbidden``:
    materialises the dense (E, W) one-hot and segment-ORs it per row —
    O(N*E) memory, test scale only."""
    rel = ec - base_src
    ok = (ec >= 0) & (rel >= 0) & (rel < window)
    iota = jnp.arange(window, dtype=jnp.int32)
    hot = ok[:, None] & (rel[:, None] == iota)              # (E, W)
    rows = jnp.arange(n_rows, dtype=jnp.int32)
    seg = es[None, :] == rows[:, None]                      # (N, E)
    return (seg[:, :, None] & hot[None, :, :]).any(axis=1)


def edge_conflict_ref(es: jax.Array, ed: jax.Array, cu_e: jax.Array,
                      cv_e: jax.Array, pu_e: jax.Array, pv_e: jax.Array,
                      n_rows: int) -> jax.Array:
    """bool[N] per-row conflict oracle for ``csr_segment.edge_conflict``
    (dense segment-any instead of a scatter)."""
    lose_e = ((cu_e >= 0) & (cu_e == cv_e)
              & ((pv_e > pu_e) | ((pv_e == pu_e) & (ed > es))))
    rows = jnp.arange(n_rows, dtype=jnp.int32)
    return ((es[None, :] == rows[:, None]) & lose_e[None, :]).any(axis=1)


def edge_fused_ref(es: jax.Array, ed: jax.Array, cu_e: jax.Array,
                   cv_e: jax.Array, pu_e: jax.Array, pv_e: jax.Array,
                   base_src: jax.Array, n_rows: int, window: int
                   ) -> tuple[jax.Array, jax.Array]:
    """Oracle for the one-pass csr-segment core
    (``csr_segment.edge_fused``): conflict flags + forbidden bitmap from
    one shared edge sweep."""
    return (edge_conflict_ref(es, ed, cu_e, cv_e, pu_e, pv_e, n_rows),
            edge_forbidden_ref(es, cv_e, base_src, n_rows, window))


def jpl_extrema_ref(npr: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Row-wise (max, masked min) of active-neighbour priorities; inactive
    lanes are -1 on input, LARGE on the min side."""
    large = jnp.int32(0x7FFFFFFF)
    nbr_max = npr.max(axis=1)
    nbr_min = jnp.where(npr >= 0, npr, large).min(axis=1)
    return nbr_max, nbr_min


def compact_ref(mask: jax.Array) -> tuple[jax.Array, jax.Array]:
    n = mask.shape[0]
    (idx,) = jnp.nonzero(mask, size=n, fill_value=n)
    return idx.astype(jnp.int32), mask.sum(dtype=jnp.int32)


def frontier_probe_ref(nbr_in_frontier: jax.Array,
                       unvisited: jax.Array) -> jax.Array:
    return nbr_in_frontier.any(axis=1) & unvisited
