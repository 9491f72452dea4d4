"""Persistent worklist state — the paper's central data structure.

The paper's contribution: the worklist is maintained through *all*
iterations, in both topology-driven and data-driven phases, so mode
switches are free. On TPU the "push with atomics" idiom becomes parallel
stream compaction (see DESIGN.md §2); the dual representation is:

  mask  : bool[N]   dense active flags   (what topology-driven sweeps read)
  items : int32[C]  compacted active ids (what data-driven gathers read)
  count : int32[]   number of valid entries in ``items``

Both step kernels emit *both* representations. Capacity ``C`` is bucketed
(static shapes under jit); the active set of IPGC shrinks monotonically, so
buckets only ever step down.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels.csr_segment import prefix_sum


class Worklist(NamedTuple):
    mask: jax.Array    # bool[N]
    items: jax.Array   # int32[C], padded with N
    count: jax.Array   # int32[]

    @property
    def capacity(self) -> int:
        return self.items.shape[0]


def full_worklist(n_nodes: int) -> Worklist:
    """All nodes active (IPGC initial state: everything uncolored)."""
    return Worklist(
        mask=jnp.ones((n_nodes,), dtype=bool),
        items=jnp.arange(n_nodes, dtype=jnp.int32),
        count=jnp.asarray(n_nodes, dtype=jnp.int32),
    )


def stacked_worklist(real_ns: "list[int]", n_pad: int) -> Worklist:
    """Lane-stacked worklists for batched execution (DESIGN.md §9).

    Lane ``i`` starts with graph ``i``'s full worklist (its first
    ``real_ns[i]`` nodes active) embedded in the shared ``n_pad`` shape
    class: pad rows are inactive in ``mask`` and hold the ``n_pad``
    sentinel in ``items``, so a ``vmap``-ed step sees, per lane, exactly
    the state ``full_worklist(real_n)`` would produce after a resize to
    capacity ``n_pad``. ``count`` is per-lane — the batched Pipe runs
    until every lane's count drains.
    """
    lanes = jnp.arange(n_pad, dtype=jnp.int32)
    ns = jnp.asarray(real_ns, dtype=jnp.int32)[:, None]    # (B, 1)
    mask = lanes[None, :] < ns
    items = jnp.where(mask, lanes[None, :], n_pad).astype(jnp.int32)
    return Worklist(mask=mask, items=items,
                    count=jnp.asarray(real_ns, dtype=jnp.int32))


def compact_mask(mask: jax.Array, capacity: int, n_nodes: int) -> tuple[jax.Array, jax.Array]:
    """Dense mask -> compacted items (the atomic-push replacement).

    ``capacity`` is static, so this compact also works *inside*
    ``lax.while_loop`` bodies — the outlined engine relies on both step
    kernels re-emitting the dual representation every trip without leaving
    the device. jnp reference implementation; ``kernels/compact.py`` is the
    Pallas version with a sequential-grid carry.
    """
    ids = jnp.arange(mask.shape[0], dtype=jnp.int32)
    return _compact(ids, mask, capacity, n_nodes), mask.sum(dtype=jnp.int32)


def compact_items(items: jax.Array, keep: jax.Array, n_nodes: int,
                  capacity: int | None = None) -> tuple[jax.Array, jax.Array]:
    """Filter the existing worklist in O(C) — the data-driven phase never
    touches O(N) state to rebuild its own worklist. The result holds
    ``capacity`` slots (default: as many as ``items``)."""
    cap = items.shape[0] if capacity is None else capacity
    return (_compact(items.astype(jnp.int32), keep, cap, n_nodes),
            keep.sum(dtype=jnp.int32))


def _compact(values: jax.Array, keep: jax.Array, capacity: int,
             fill: int) -> jax.Array:
    """The kept ``values`` in order, in ``capacity`` slots padded with
    ``fill`` (kept values past the capacity are dropped) — what
    ``jnp.nonzero(size=)`` gives, built from a doubling prefix sum and
    one scatter: XLA's ``cumsum``, which ``nonzero`` lowers to, takes
    about 10 s to compile for a TPU at a million rows."""
    slot = prefix_sum(keep.astype(jnp.int32)) - 1
    out = jnp.full((capacity,), fill, jnp.int32)
    return out.at[jnp.where(keep, slot, capacity)].set(values, mode="drop")


def bucket_capacities(n_nodes: int, *, ratio: int = 4, floor: int = 1024) -> list[int]:
    """Geometric capacity ladder N, N/r, N/r^2, ... (static-shape buckets)."""
    caps = []
    c = n_nodes
    while c > floor:
        caps.append(int(-(-c // 8) * 8))
        c //= ratio
    caps.append(min(int(-(-floor // 8) * 8), int(-(-n_nodes // 8) * 8)))
    # dedupe, descending
    out: list[int] = []
    for x in caps:
        if not out or x < out[-1]:
            out.append(x)
    return out


def pick_bucket(caps: list[int], count: int) -> int:
    """Smallest capacity >= count (host-side Pipe decision)."""
    best = caps[0]
    for c in caps:
        if c >= count:
            best = c
    return best


def chunk_lower_bounds(caps: list[int]) -> list[int]:
    """Exit thresholds for chunked outlining: the device loop running at
    ``caps[i]`` keeps iterating while ``count > caps[i+1]`` (0 for the last
    bucket), so the host re-enters only at bucket boundaries."""
    return [*caps[1:], 0]


def resize_block(items: jax.Array, capacity: int, n_nodes: int) -> jax.Array:
    """Resize one compacted items block to a new static capacity.

    Shrinking is a pure slice (valid only when the block's live count is
    <= ``capacity`` — the ladder guarantees it); growing pads with the
    ``n_nodes`` sentinel. Pure and shape-static, so it works both on the
    host (``resize_items``) and inside a ``shard_map`` region, where each
    shard resizes its own worklist block (distributed.make_dist_resize)."""
    c = items.shape[0]
    if capacity == c:
        return items
    if capacity < c:
        return items[:capacity]
    pad = jnp.full((capacity - c,), n_nodes, items.dtype)
    return jnp.concatenate([items, pad])


def resize_items(wl: Worklist, capacity: int, n_nodes: int) -> Worklist:
    """Host-side bucket change. The active set shrinks monotonically, so a
    smaller bucket is a pure slice of the already-compacted items; growing
    (only needed to round the initial full worklist up to ``caps[0]``) pads
    with the ``n_nodes`` sentinel."""
    return Worklist(mask=wl.mask,
                    items=resize_block(wl.items, capacity, n_nodes),
                    count=wl.count)
