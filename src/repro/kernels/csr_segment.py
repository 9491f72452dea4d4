"""Sorted-segment primitives: the edge-wise passes of the ``csr-segment``
layout and of the hub side-channel (DESIGN.md §8).

Both walk a list of *entries* (directed edges) grouped by owner row: the
CSR edge list (``edge_src``/``edge_dst``, owners = all rows) and the COO
tail (``tail_src``/``tail_dst``, owners = hub rows). Entries of one owner
are contiguous and owners appear in ascending order, so every per-row
reduction is a *segmented* reduction over contiguous ranges:

  * ``prefix_sum`` / ``spread`` copy per-owner values onto their entries
    (one scatter of the owners, one prefix sum of the entries) — where a
    gather ``v[src]`` would pay a random access per entry;
  * ``segment_or`` ORs each range into its first entry by log-step
    doubling, and the caller reads one entry per owner.

All three are shifted elementwise passes: no scatter over the entries and
no ``cumsum``. On a TPU a scatter of E updates runs serially (about 9 ns
per update on a v5e at E = 63.5M) and XLA's ``cumsum`` and bool
scatter-max take 10-25 s each to compile at that size; the doubling passes
stream at memory bandwidth and compile in about a second.

``packed_ranges`` lays the entries of a *subset* of owners (a sparse
step's worklist) out contiguously in a static-capacity buffer, so a
data-driven step touches only the entries of its active rows.

Padding contract: pad entries point at the sentinel slot ``N`` (``dst ==
N``), whose color is ``PAD_COLOR`` (-2): it never lands in a window and
never equals a real color, so pad entries are inert without a valid mask.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _shift(x: jax.Array, s, fill=0) -> jax.Array:
    """``x`` moved ``s`` places toward higher indices (``s`` may be
    traced): ``out[i] = x[i - s]``, ``fill`` before the start."""
    n = x.shape[0]
    pad = jnp.full((n,), fill, x.dtype)
    return jax.lax.dynamic_slice(jnp.concatenate([pad, x]), (n - s,), (n,))


def _unshift(x: jax.Array, s, fill=0) -> jax.Array:
    """``x`` moved ``s`` places toward lower indices (``s`` may be
    traced): ``out[i] = x[i + s]``, ``fill`` past the end."""
    n = x.shape[0]
    pad = jnp.full((n,), fill, x.dtype)
    return jax.lax.dynamic_slice(jnp.concatenate([x, pad]), (s,), (n,))


def _doubling(body, init, length: int):
    """Run ``body(shift, carry)`` for shifts 1, 2, 4, ... < ``length``
    as ONE loop: the TPU compiler takes about a second per unrolled pass
    over an array of millions, and a step holds a dozen such loops."""
    steps = max(length - 1, 0).bit_length()
    return jax.lax.fori_loop(
        0, steps, lambda i, c: body(jnp.left_shift(1, i), c), init)


def prefix_sum(x: jax.Array) -> jax.Array:
    """Inclusive prefix sum of a 1-D int32 array (exact modulo 2^32)."""
    return _doubling(lambda s, y: y + _shift(y, s), x, x.shape[0])


def spread(v: jax.Array, starts: jax.Array, m: int) -> jax.Array:
    """Per-entry copy of per-owner values over ``m`` entries.

    Owner ``i`` holds entries ``[starts[i], starts[i+1])`` (``starts`` is
    ascending from 0: an exclusive prefix sum of the owners' entry
    counts; empty owners are allowed). Entry ``j`` gets the value of the
    last owner starting at or before ``j``; entries past the last owner's
    range keep its value. Each owner adds the difference to the previous
    owner's value at its start (a sorted scatter: XLA compiles an
    unsorted one into an array this large in 13 s for a TPU), and a
    prefix sum adds them up: exact for int32 (differences wrap)."""
    v = v.astype(jnp.int32)
    d = v - jnp.concatenate([jnp.zeros((1,), v.dtype), v[:-1]])
    marks = jnp.zeros((m,), jnp.int32).at[starts].add(
        d, mode="drop", indices_are_sorted=True)
    return prefix_sum(marks)


def segment_or(vals: tuple, seg: jax.Array, max_run: int = 0) -> tuple:
    """OR every run of equal ``seg`` (contiguous) into its first entry.

    After the loop, ``out[k][i]`` is the OR of ``vals[k][j]`` over the
    entries ``j >= i`` of ``i``'s run, so the run's first entry holds the
    whole run. ``max_run`` bounds the run length (0: the array length);
    the loop takes ``ceil(log2(max_run))`` doubling passes."""
    def body(s, vs):
        same = _unshift(seg, s, -1) == seg
        return tuple(v | jnp.where(same, _unshift(v, s), jnp.zeros_like(v))
                     for v in vs)
    return _doubling(body, tuple(vals),
                     min(max_run or seg.shape[0], seg.shape[0]))


def read_first(v: jax.Array, starts: jax.Array,
               nonempty: jax.Array) -> jax.Array:
    """Per-owner value at its first entry (zero for empty owners)."""
    got = v[jnp.minimum(starts, v.shape[0] - 1)]
    return jnp.where(nonempty, got, jnp.zeros_like(got))


def window_words(ec: jax.Array, base_e: jax.Array, window: int) -> tuple:
    """Per-entry forbidden bit ``ec - base_e`` of the owner's color
    window, as ``ceil(window / 32)`` uint32 words (zero where the
    neighbour is uncolored or outside the window)."""
    rel = ec - base_e
    ok = (ec >= 0) & (rel >= 0) & (rel < window)
    bit = jnp.left_shift(jnp.uint32(1), (rel & 31).astype(jnp.uint32))
    word = rel >> 5
    return tuple(jnp.where(ok & (word == k), bit, jnp.uint32(0))
                 for k in range(-(-window // 32)))


def words_bitmap(words: tuple, window: int) -> jax.Array:
    """(R, window) bool bitmap from per-row uint32 words."""
    lanes = jnp.arange(window, dtype=jnp.uint32) & 31
    w = jnp.concatenate([jnp.broadcast_to(x[:, None], (x.shape[0], 32))
                         for x in words], axis=1)[:, :window]
    return ((w >> lanes) & 1).astype(bool)


def lose_flags(cu_e: jax.Array, cv_e: jax.Array, pu_e: jax.Array,
               pv_e: jax.Array, src_e: jax.Array,
               dst_e: jax.Array) -> jax.Array:
    """Entry (u, v) makes u lose iff ``c_v == c_u >= 0`` and v wins the
    (priority, id) tie-break — per entry, what ``ipgc._won_rows`` does
    per row."""
    return (cu_e >= 0) & (cu_e == cv_e) & wins(pu_e, pv_e, src_e, dst_e)


def wins(pu_e: jax.Array, pv_e: jax.Array, src_e: jax.Array,
         dst_e: jax.Array) -> jax.Array:
    """The destination wins the (priority, id) tie-break: THE predicate.
    Static, so ``ipgc.prepare`` stores it per entry (``WINS_BIT`` on
    csr-segment, ``IPGCGraph.ell_wins`` on the ELL kinds)."""
    return (pv_e > pu_e) | ((pv_e == pu_e) & (dst_e > src_e))


WINS_BIT = -2 ** 31     # int32 sign bit of a keyed edge destination


def split_key(key: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(destination, destination-wins) of keyed edge destinations: the
    csr-segment layout stores the static tie-break in the sign bit."""
    return key & 0x7FFFFFFF, key < 0


def row_windows(words_e: tuple, seg: jax.Array, row_start: jax.Array,
                nonempty: jax.Array, max_run: int = 0) -> tuple:
    """Per-row words: the OR of each row's entry words."""
    return tuple(read_first(w, row_start, nonempty)
                 for w in segment_or(words_e, seg, max_run))


def window_bit(words: tuple, rel: jax.Array) -> jax.Array:
    """bool[R]: bit ``rel`` of each row's words (False outside them)."""
    w = words[0]
    for k in range(1, len(words)):
        w = jnp.where((rel >> 5) == k, words[k], w)
    ok = (rel >= 0) & (rel < 32 * len(words))
    bit = (w >> (rel & 31).astype(jnp.uint32)) & 1
    return ok & (bit == 1)


def edge_forbidden(es: jax.Array, ec: jax.Array, base_src: jax.Array,
                   row_start: jax.Array, row_deg: jax.Array,
                   window: int, max_run: int = 0) -> jax.Array:
    """(N, W) forbidden bitmap of a CSR edge list sorted by source row.

    ``es``: i32[E] source rows (ascending); ``ec``: i32[E] dst colors
    (PAD_COLOR on padded lanes); ``base_src``: i32[E] window base of the
    source row; ``row_start`` / ``row_deg``: i32[N] first edge and edge
    count of each row; ``max_run`` bounds the degree (see
    ``segment_or``)."""
    return words_bitmap(row_windows(window_words(ec, base_src, window), es,
                                    row_start, row_deg > 0, max_run),
                        window)


def edge_conflict(es: jax.Array, cv_e: jax.Array, wins_e: jax.Array,
                  base_src: jax.Array, c_rows: jax.Array,
                  base_rows: jax.Array, row_start: jax.Array,
                  row_deg: jax.Array, window: int,
                  max_run: int = 0) -> jax.Array:
    """bool[N]: row u holds color ``c_rows[u]`` and some neighbour that
    wins the tie-break (``wins_e``) holds it too — ``lose_flags``
    segment-ORed per row, evaluated as one bit of the window bitmap of
    the winners' colors. Valid for rows whose color lies in their window
    ``[base, base + window)`` (or is negative: they cannot lose).

    Invariant the steps keep: every colored row's color lies in its
    window, because base advances only for rows left uncolored. A step
    that moved a colored row's base would make this test miss conflicts
    (tests/test_kernels.py::test_colored_rows_stay_in_window)."""
    win_c = jnp.where(wins_e, cv_e, -1)
    words = row_windows(window_words(win_c, base_src, window), es,
                        row_start, row_deg > 0, max_run)
    return (c_rows >= 0) & window_bit(words, c_rows - base_rows)


def edge_fused(es: jax.Array, ec: jax.Array, wins_e: jax.Array,
               base_src: jax.Array, c_rows: jax.Array,
               base_rows: jax.Array, row_start: jax.Array,
               row_deg: jax.Array, window: int, max_run: int = 0
               ) -> tuple[jax.Array, jax.Array]:
    """``edge_conflict`` AND ``edge_forbidden`` over the same colors from
    ONE doubling sweep (the csr analogue of the fused_compact kernel,
    DESIGN.md §10)."""
    nw = -(-window // 32)
    words = row_windows(
        window_words(ec, base_src, window)
        + window_words(jnp.where(wins_e, ec, -1), base_src, window),
        es, row_start, row_deg > 0, max_run)
    lose = (c_rows >= 0) & window_bit(words[nw:], c_rows - base_rows)
    return lose, words_bitmap(words[:nw], window)


def packed_ranges(starts: jax.Array, counts: jax.Array, cap: int
                  ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Entries of a list of owners, packed into ``cap`` slots.

    Owner ``i`` contributes ``counts[i]`` entries starting at entry
    ``starts[i]``; they fill slots ``[off[i], off[i] + counts[i])`` in
    owner order. Returns ``(pos, off, live)``: the entry index of every
    slot, each owner's first slot, and which slots hold an entry. The
    caller guarantees ``sum(counts) <= cap``."""
    off = prefix_sum(counts) - counts
    total = off[-1] + counts[-1]
    slot = jnp.arange(cap, dtype=jnp.int32)
    pos = slot + spread(starts - off, off, cap)
    return pos, off, slot < total
