"""IPGC — Iterative Parallel Graph Coloring (Deveci et al. 2016), the
algorithm the paper hybridizes.

Two speculative steps per iteration (paper §II-C):
  1. assign: every *active* (uncolored) node takes the mex of its
     neighbours' colors — computed over a sliding color window
     ``[base, base+W)`` so memory stays O(W) per node even for power-law
     hubs (exact mex; a node whose window is exhausted stays active with
     an advanced base).
  2. resolve: if an edge's endpoints were assigned the same color,
     exactly one endpoint (the one losing a static random-hash priority
     tie-break) is uncolored and stays in the worklist.

Every function exists in two phases:
  *dense*  (topology-driven): operates on all N rows, reads the active mask.
  *sparse* (data-driven): operates on a gathered worklist of capacity C.

Both phases maintain the full worklist state — the paper's contribution.

``impl="pallas"`` routes the per-row window/mex and conflict computations
through the Pallas TPU kernels (validated in interpret mode on CPU);
``impl="jnp"`` is the pure-jnp reference path used for CPU benchmarks.

Hub (degree > ELL width) bookkeeping: ELL rows cover the first K
neighbours; the COO tail covers the rest. Tail contributions are folded in
through a compact per-hub forbidden/conflict side-channel so the sparse
phase stays O(C·K + T + C·W) — see DESIGN.md §2.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.graphs.csr import Graph, NO_COLOR, PAD_COLOR
from repro.core.worklist import Worklist, compact_items, compact_mask
from repro.kernels import csr_segment as kcsr
from repro.obs.metrics import default_registry


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class IPGCGraph:
    """Device-side graph prepared for the coloring engine.

    ``layout_kind`` is the static execution-layout dispatch axis (the
    ``LayoutPlan.kind`` the graph was prepared under, DESIGN.md §8): the
    ELL-family kinds (pure-ell / ell-tail / hub-split) run the ELL tile
    steps below, ``csr-segment`` runs the edge-wise segment variants
    (``edge_src``/``edge_dst`` populated, CSR expanded at prepare time).
    Being static, it keys every jit/step cache exactly like ``algo=``.
    """

    # static metadata
    n_nodes: int = dataclasses.field(metadata=dict(static=True))
    ell_width: int = dataclasses.field(metadata=dict(static=True))
    n_hub: int = dataclasses.field(metadata=dict(static=True))
    # arrays
    ell_idx: jax.Array        # i32[N, K], pad = N
    degrees: jax.Array        # i32[N]
    priority: jax.Array       # i32[N+1], pad = -1
    tail_src: jax.Array       # i32[T] clipped to [0, N-1]
    tail_dst: jax.Array       # i32[T], pad = N
    tail_valid: jax.Array     # bool[T]
    tail_slot: jax.Array      # i32[T] hub slot of tail_src
    hub_slot: jax.Array       # i32[N], n_hub for non-hub nodes
    hub_ids: jax.Array        # i32[max(n_hub,1)]
    # segment index of the tail: hub slot h owns tail entries
    # [tail_start[h], tail_start[h+1])
    tail_start: jax.Array     # i32[n_hub+1]
    # layout dispatch (static) + csr-segment edge arrays (None elsewhere)
    layout_kind: str = dataclasses.field(default="ell-tail",
                                         metadata=dict(static=True))
    edge_src: jax.Array | None = None   # i32[Ep] ascending, pad -> N-1
    # i32[Ep] destination (pad = N, the sentinel slot), with the sign bit
    # (csr_segment.WINS_BIT) set where it wins the (priority, id)
    # tie-break against the source — static, so no step gathers priorities
    edge_dst: jax.Array | None = None
    # the same static tie-break for the ELL-family kinds (None on
    # csr-segment): u32[N, ceil(K/32)], bit k % 32 of word k // 32 of row
    # u set where the neighbour in ELL slot k wins against u; pad slots
    # clear. The two-phase ELL resolve reads it in place of priorities
    ell_wins: jax.Array | None = None
    # static bound for the packed sparse passes: seg_bound[k] bounds the
    # entries (csr edges / tail entries) any 2^k rows own, rounded up a
    # coarse ladder (``_top_sums``); () = no bound, sparse steps sweep
    # every entry
    seg_bound: tuple = dataclasses.field(default=(),
                                         metadata=dict(static=True))


def prepare(g: Graph, *, priority: str = "hash", plan=None) -> IPGCGraph:
    """priority="hash" (paper engine) or "id" (Kokkos-VB-style tie-break).

    ``plan`` is the ``LayoutPlan`` to execute under (None reads the plan
    the graph was assembled with; graphs from the legacy builder default
    to ell-tail). Only ``plan.kind`` matters here — the arrays were laid
    out at assembly; prepare picks the execution variant.
    """
    a = g.arrays
    n = g.n_nodes
    if plan is None:
        plan = getattr(g, "layout", None)
    kind = getattr(plan, "kind", None) or "ell-tail"
    deg = np.asarray(a.degrees)
    # hub rows == rows with tail entries: degree above the plan's spill
    # threshold (== ell_width for every kind; hub-split rows spill whole)
    hub_ids = np.nonzero(deg > a.ell_width)[0].astype(np.int32)
    n_hub = len(hub_ids)
    hub_slot = np.full(n, n_hub, dtype=np.int32)
    hub_slot[hub_ids] = np.arange(n_hub, dtype=np.int32)
    tail_src = np.asarray(a.tail_src)
    tail_valid = tail_src < n
    tail_src_safe = np.minimum(tail_src, n - 1)
    pr = np.asarray(a.priority) if priority == "hash" else np.arange(n, dtype=np.int32)
    prio = np.concatenate([pr, np.full(1, -1, np.int32)])
    edge_src = edge_dst = ell_wins = None
    real = tail_src[tail_valid]                     # sorted by source row
    tail_start = np.searchsorted(hub_slot[real],
                                 np.arange(n_hub + 1)).astype(np.int32)
    if kind == "csr-segment":
        e = int(np.asarray(a.row_ptr)[-1])
        ep = max(-(-max(e, 1) // 8) * 8, 8)
        es = np.full(ep, max(n - 1, 0), dtype=np.int32)  # pad lanes inert
        ed = np.full(ep, n, dtype=np.int32)              # (ec < 0)
        es[:e] = np.repeat(np.arange(n, dtype=np.int32), deg)
        ed[:e] = np.asarray(a.col_idx)
        ed[kcsr.wins(prio[es], prio[ed], es, ed)] |= np.int32(kcsr.WINS_BIT)
        edge_src, edge_dst = jnp.asarray(es), jnp.asarray(ed)
        owned = deg
    else:
        ell_wins = jnp.asarray(_ell_wins(np.asarray(a.ell_idx), prio))
        owned = np.bincount(real, minlength=n)
    return IPGCGraph(
        n_nodes=n,
        ell_width=a.ell_width,
        n_hub=n_hub,
        ell_idx=jnp.asarray(a.ell_idx),
        degrees=jnp.asarray(deg),
        priority=jnp.asarray(prio),
        tail_src=jnp.asarray(tail_src_safe),
        tail_dst=jnp.asarray(a.tail_dst),
        tail_valid=jnp.asarray(tail_valid),
        tail_slot=jnp.asarray(hub_slot[tail_src_safe]),
        hub_slot=jnp.asarray(hub_slot),
        hub_ids=jnp.asarray(hub_ids if n_hub else np.zeros(1, np.int32)),
        tail_start=jnp.asarray(tail_start),
        layout_kind=kind,
        edge_src=edge_src,
        edge_dst=edge_dst,
        ell_wins=ell_wins,
        seg_bound=_top_sums(owned),
    )


def wins_words(k: int) -> int:
    """Words of ``IPGCGraph.ell_wins`` a row of ``k`` ELL slots takes."""
    return -(-k // 32)


def _ell_wins(ell: np.ndarray, prio: np.ndarray) -> np.ndarray:
    """``IPGCGraph.ell_wins`` of an ELL tile (pad = N, ``prio[N]`` the
    pad priority): ``kcsr.wins`` per slot, packed 32 slots a word."""
    n, k = ell.shape
    rows = np.arange(n, dtype=np.int32)[:, None]
    wins = kcsr.wins(prio[:n, None], prio[ell], rows, ell) & (ell < n)
    packed = np.zeros((n, 4 * wins_words(k)), np.uint8)
    packed[:, :-(-k // 8)] = np.packbits(wins, axis=1, bitorder="little")
    return packed.view("<u4").astype(np.uint32)


def slot_wins(words: jax.Array, k: int) -> jax.Array:
    """(R, k) bool tie-break bits of ``ell_wins`` rows ``words``: slot j
    is bit j % 32 of word j // 32 (a broadcast, no gather)."""
    r, nw = words.shape
    w = jnp.broadcast_to(words[:, :, None], (r, nw, 32)).reshape(r, 32 * nw)
    shift = jnp.asarray(np.arange(k) % 32, jnp.uint32)
    return ((w[:, :k] >> shift) & 1) == 1


def _top_sums(owned: np.ndarray) -> tuple:
    """``out[k]`` >= the most entries any ``2^k`` rows own, for
    ``2^k < 2 * len(owned)`` (the packed sparse passes' static bound).

    Each bound is rounded up to the ladder ``{8, ..., 15} * 2^j`` (at
    most 12.5% above the exact sum, so a packed pass carries little
    slack): the bound is a static step argument, so graphs whose arrays
    share shapes and whose bounds share rungs share compiled steps;
    exact sums would give nearly every graph its own programs."""
    top = np.cumsum(np.sort(np.asarray(owned, np.int64))[::-1])
    if top.size == 0:
        return ()
    ks = range(max(top.size - 1, 0).bit_length() + 1)
    return tuple(_ladder(int(top[min(2 ** k, top.size) - 1])) for k in ks)


def _ladder(x: int) -> int:
    """Smallest ``{8, ..., 15} * 2^j`` (x itself up to 16) >= ``x``: the
    same power-of-two ceiling, so the same number of doubling passes."""
    if x <= 16:
        return x
    j = x.bit_length() - 4
    return -(-x // 2 ** j) * 2 ** j


def pad_prepared(ig: IPGCGraph, n_pad: int, k_pad: int, t_pad: int,
                 nh_pad: int) -> IPGCGraph:
    """Embed a prepared graph into a larger static shape class — the
    batch-execution contract (DESIGN.md §9).

    Every step impl in this module is *batch-axis safe*: it is built from
    shape-static jnp ops (gather / scatter-with-drop / doubling loops)
    with no host-side data-dependent control flow, so ``jax.vmap`` over a
    lane-stacked ``IPGCGraph`` + state reproduces the unbatched step
    bit-exactly per lane. ``pad_prepared`` makes lanes stackable: padding
    is *inert by construction* —

      * pad nodes (rows ``n..n_pad``) have no ELL entries, degree 0 and
        priority -1; they are nobody's neighbour and never enter the
        worklist, so their colors stay ``PAD_COLOR`` forever;
      * the old gather sentinel ``n`` (whose color slot held
        ``PAD_COLOR``) is remapped to the new sentinel ``n_pad`` in
        ``ell_idx``/``tail_dst``, preserving pad-lane semantics;
      * ``ell_wins`` pads with clear words: no pad row or slot wins;
      * extra tail entries are ``tail_valid=False``; extra hub slots have
        no tail edges, so their forbidden/conflict rows are all-False
        (the same neutral row non-hub nodes already gather);
      * ``hub_slot`` values ``n_hub`` ("not a hub") are remapped to
        ``nh_pad``, the new neutral row.

    Consequently coloring the padded graph (with pad rows initialized to
    ``PAD_COLOR`` and excluded from the worklist) is bit-identical to
    coloring the original — the invariant ``Session.run_batch`` is built
    on (tests/test_exec.py).
    """
    n, k, nh = ig.n_nodes, ig.ell_width, ig.n_hub
    t = ig.tail_src.shape[0]
    assert ig.layout_kind != "csr-segment", \
        "csr-segment graphs have no batch padding (edge arrays)"
    assert n_pad >= n and k_pad >= k and t_pad >= t and nh_pad >= nh
    ell = jnp.where(ig.ell_idx == n, n_pad, ig.ell_idx)
    ell = jnp.pad(ell, ((0, n_pad - n), (0, k_pad - k)),
                  constant_values=n_pad)
    deg = jnp.pad(ig.degrees, (0, n_pad - n))
    prio = jnp.concatenate([ig.priority[:n],
                            jnp.full((n_pad + 1 - n,), -1, jnp.int32)])
    wins = jnp.pad(ig.ell_wins, ((0, n_pad - n),
                                 (0, wins_words(k_pad) - wins_words(k))))
    tail_src = jnp.pad(ig.tail_src, (0, t_pad - t))        # clipped rows
    tail_dst = jnp.pad(jnp.where(ig.tail_dst == n, n_pad, ig.tail_dst),
                       (0, t_pad - t), constant_values=n_pad)
    tail_valid = jnp.pad(ig.tail_valid, (0, t_pad - t))
    tail_slot = jnp.pad(jnp.where(ig.tail_slot == nh, nh_pad, ig.tail_slot),
                        (0, t_pad - t), constant_values=nh_pad)
    hub_slot = jnp.pad(jnp.where(ig.hub_slot == nh, nh_pad, ig.hub_slot),
                       (0, n_pad - n), constant_values=nh_pad)
    hub_ids = jnp.pad(ig.hub_ids,
                      (0, max(nh_pad, 1) - ig.hub_ids.shape[0]))
    # extra hub slots own empty ranges at the end of the real entries
    tail_start = jnp.pad(ig.tail_start, (0, nh_pad - nh), mode="edge")
    return IPGCGraph(
        n_nodes=n_pad, ell_width=k_pad, n_hub=nh_pad, ell_idx=ell,
        degrees=deg, priority=prio, tail_src=tail_src, tail_dst=tail_dst,
        tail_valid=tail_valid, tail_slot=tail_slot, hub_slot=hub_slot,
        hub_ids=hub_ids, tail_start=tail_start, layout_kind=ig.layout_kind,
        ell_wins=wins)


# Read the env var ONCE at import (it used to be re-read on every trace);
# benchmarks that A/B the hub side-channel use set_force_hub() instead of
# mutating os.environ, which also keeps the jit cache honest: the engine
# passes the resolved value down as a *static* step argument.
_FORCE_HUB_ENV = os.environ.get("REPRO_IPGC_FORCE_HUB", "0") == "1"
_force_hub_override: bool | None = None


def set_force_hub(value: bool | None) -> None:
    """Override (or with ``None`` reset) the hub side-channel forcing."""
    global _force_hub_override
    _force_hub_override = value


def force_hub_enabled() -> bool:
    return _FORCE_HUB_ENV if _force_hub_override is None else _force_hub_override


@contextlib.contextmanager
def forced_hub(value: bool | None):
    """Scoped hub-side-channel forcing — the context-manager form of
    ``set_force_hub`` (restores the *previous* override on exit,
    including the no-override ``None`` state), so A/B tests and
    benchmarks never leak the toggle::

        with ipgc.forced_hub(True):
            r = color(g)          # hub side-channel unconditionally on
    """
    global _force_hub_override
    prev = _force_hub_override
    set_force_hub(value)
    try:
        yield
    finally:
        _force_hub_override = prev


def _force_hub() -> bool:  # kept for back-compat with direct callers
    return force_hub_enabled()


def _has_hubs(ig: IPGCGraph, force_hub: bool | None) -> bool:
    if force_hub is None:
        force_hub = force_hub_enabled()
    return ig.n_hub > 0 or force_hub


# --- gather instrumentation (trace-time) -----------------------------------
# Every ELL-shaped gather of the *mutable* colors array goes through
# ``_gather_neighbor_colors`` so tests can assert how many such gathers a
# step performs (the fused step's contract is exactly one; the two-phase
# steps perform two). Counters increment at trace time — inspect them by
# tracing the raw ``*_impl`` functions with ``jax.eval_shape`` inside a
# ``GATHER_COUNTS.scope()`` block (DESIGN.md §12).
GATHER_COUNTS = default_registry().group("ipgc.gathers",
                                         ("neighbor_colors",))

# Kernel-launch accounting (trace-time, like GATHER_COUNTS): every
# logical device pass a step emits bumps one bucket, so "one iteration is
# one kernel launch" (DESIGN.md §10) is asserted in tests, not eyeballed.
#   mex/conflict/compact — the three separate passes of a two-phase step
#   fused               — a fused step: assign + resolve in one pass
#                         over the neighbour tile (the fused_compact
#                         Pallas kernel on the ELL paths, followed by
#                         XLA's compaction of its survivor mask; the
#                         one-sweep segment core on csr-segment)
# Inspect by tracing the raw ``*_impl`` functions with ``jax.eval_shape``
# under ``LAUNCH_COUNTS.scope()`` (see ``core/policy.measure_launches``).
# Both groups are reset-scoped ``CounterGroup``s registered in the obs
# default registry — the scope zeroes on entry and RESTORES outer values
# on exit, so measurements cannot pollute each other across tests.
LAUNCH_COUNTS = default_registry().group(
    "ipgc.launches", ("mex", "conflict", "compact", "fused"))


def reset_gather_counts() -> None:
    """Legacy zeroing hook; prefer ``GATHER_COUNTS.scope()``."""
    GATHER_COUNTS.reset()


def reset_launch_counts() -> None:
    """Legacy zeroing hook; prefer ``LAUNCH_COUNTS.scope()``."""
    LAUNCH_COUNTS.reset()


def _gather_neighbor_colors(colors: jax.Array, rows: jax.Array) -> jax.Array:
    GATHER_COUNTS["neighbor_colors"] += 1
    return colors[rows]


def init_colors(n_nodes: int) -> jax.Array:
    """int32[N+1]; slot N is the gather sentinel (PAD_COLOR)."""
    c = jnp.full((n_nodes + 1,), NO_COLOR, dtype=jnp.int32)
    return c.at[n_nodes].set(PAD_COLOR)


# ---------------------------------------------------------------------------
# forbidden-window helpers
# ---------------------------------------------------------------------------

def _ell_forbidden(nc: jax.Array, base_rows: jax.Array, window: int) -> jax.Array:
    """(R, W) forbidden bitmap of an ELL tile: each lane's window bit,
    OR-reduced across the row (no scatter: a bool scatter of R*K updates
    takes the TPU compiler ~20 s at a million rows)."""
    words = kcsr.window_words(nc, base_rows[:, None], window)
    return kcsr.words_bitmap(
        tuple(jax.lax.reduce(w, jnp.uint32(0), jax.lax.bitwise_or, (1,))
              for w in words), window)


def _hub_forbidden(ig: IPGCGraph, colors: jax.Array, base: jax.Array,
                   window: int) -> jax.Array:
    """(n_hub+1, W) forbidden bitmap from COO-tail edges; row n_hub is a
    guaranteed-False row that non-hub nodes gather. One sorted-segment
    sweep over the tail (kernels/csr_segment.py)."""
    nh = ig.n_hub
    if nh == 0:
        return jnp.zeros((1, window), bool)
    start, nonempty = _tail_segments(ig)
    tc = jnp.where(ig.tail_valid, colors[ig.tail_dst], PAD_COLOR)
    base_e = kcsr.spread(base[ig.hub_ids[:nh]], start, tc.shape[0])
    words = kcsr.segment_or(kcsr.window_words(tc, base_e, window),
                            ig.tail_slot, _max_run(ig))
    forb = kcsr.words_bitmap(
        tuple(kcsr.read_first(w, start, nonempty) for w in words), window)
    return jnp.concatenate([forb, jnp.zeros((1, window), bool)])


def _max_run(ig: IPGCGraph) -> int:
    """Most entries one row owns (0: unknown, see ``segment_or``)."""
    return ig.seg_bound[0] if ig.seg_bound else 0


def _tail_segments(ig: IPGCGraph) -> tuple[jax.Array, jax.Array]:
    """(first tail entry, owns-an-entry) per hub slot ``0..n_hub-1``."""
    ts = ig.tail_start
    return ts[:-1], ts[1:] > ts[:-1]


def _mex_from_forbidden(forb: jax.Array, active: jax.Array,
                        base_rows: jax.Array, colors_rows: jax.Array,
                        window: int) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Pick first free color in the window; advance base when exhausted."""
    free = (~forb) & active[:, None]
    has = free.any(axis=1)
    first = jnp.argmax(free, axis=1).astype(jnp.int32)
    new_colors = jnp.where(active & has, base_rows + first, colors_rows)
    new_base = jnp.where(active & ~has, base_rows + window, base_rows)
    newly = active & has
    return new_colors, new_base, newly


def _mex_rows(ig: IPGCGraph, nc: jax.Array, base_rows: jax.Array,
              active: jax.Array, colors_rows: jax.Array, extra_forb: jax.Array,
              window: int, impl: str, tile_rows: int | None = None):
    """Row-wise windowed mex; ``impl`` picks jnp or the Pallas kernel."""
    LAUNCH_COUNTS["mex"] += 1
    if impl == "pallas":
        from repro.kernels import ops as kops
        if extra_forb is None:
            extra_forb = jnp.zeros((nc.shape[0], window), bool)
        first, has = kops.mex_window(nc, base_rows, extra_forb, window,
                                     tile_rows)
        new_colors = jnp.where(active & has, base_rows + first, colors_rows)
        new_base = jnp.where(active & ~has, base_rows + window, base_rows)
        return new_colors, new_base, active & has
    forb = _ell_forbidden(nc, base_rows, window)
    if extra_forb is not None:
        forb = forb | extra_forb
    return _mex_from_forbidden(forb, active, base_rows, colors_rows, window)


# ---------------------------------------------------------------------------
# conflict helpers
# ---------------------------------------------------------------------------

def _won_rows(nc: jax.Array, wins: jax.Array, cu: jax.Array) -> jax.Array:
    """Row u conflicts iff some neighbour v that wins the (priority, id)
    tie-break (``kcsr.wins``, per slot in ``wins``) has u's color."""
    same = (nc == cu[:, None]) & (cu >= 0)[:, None]
    return (same & wins).any(axis=1)


def _conflict_rows(nc: jax.Array, npr: jax.Array, nbr_ids: jax.Array,
                   cu: jax.Array, pu: jax.Array, ids: jax.Array) -> jax.Array:
    """``_won_rows`` with the tie-break evaluated from the priorities (the
    fused steps; the Pallas kernels and kernels/ref.py mirror it)."""
    return _won_rows(nc, kcsr.wins(pu[:, None], npr, ids[:, None], nbr_ids),
                     cu)


def _lose_rows(ig: IPGCGraph, ell_rows: jax.Array, row_ids: jax.Array,
               wins: jax.Array, cu: jax.Array, colors: jax.Array,
               newly: jax.Array, impl: str,
               tile_rows: int | None = None) -> jax.Array:
    """Row u (ELL row ``ell_rows``, id ``row_ids``, ``ell_wins`` words
    ``wins``, color ``cu`` in ``colors``) loses iff it conflicts. Only
    newly-colored rows can conflict (mex excluded all surviving older
    colors). The jnp path reads the static tie-break bits; the Pallas
    kernel takes the priorities."""
    LAUNCH_COUNTS["conflict"] += 1
    nc = _gather_neighbor_colors(colors, ell_rows)
    if impl == "pallas":
        from repro.kernels import ops as kops
        return kops.conflict(nc, ig.priority[ell_rows], ell_rows, cu,
                             ig.priority[row_ids], row_ids,
                             tile_rows) & newly
    return _won_rows(nc, slot_wins(wins, nc.shape[1]), cu) & newly


def _hub_lose(ig: IPGCGraph, colors: jax.Array, newly_full: jax.Array) -> jax.Array:
    """(n_hub+1,) conflict flags for hub rows from COO-tail edges (only
    rows flagged in ``newly_full`` can lose)."""
    nh = ig.n_hub
    if nh == 0:
        return jnp.zeros((1,), bool)
    start, nonempty = _tail_segments(ig)
    t = ig.tail_dst.shape[0]
    hubs = ig.hub_ids[:nh]
    cu = jnp.where(newly_full[hubs], colors[hubs], NO_COLOR)
    cv = jnp.where(ig.tail_valid, colors[ig.tail_dst], PAD_COLOR)
    lose = kcsr.lose_flags(kcsr.spread(cu, start, t), cv,
                           kcsr.spread(ig.priority[hubs], start, t),
                           ig.priority[ig.tail_dst], ig.tail_src,
                           ig.tail_dst)
    (lose,) = kcsr.segment_or((lose,), ig.tail_slot, _max_run(ig))
    out = kcsr.read_first(lose, start, nonempty)
    return jnp.concatenate([out, jnp.zeros((1,), bool)])


# ---------------------------------------------------------------------------
# csr-segment step variants — edge-wise segment ops over the full edge set
# ---------------------------------------------------------------------------
# Active when the graph was prepared under a ``csr-segment`` LayoutPlan
# (DESIGN.md §8): no ELL tiles are gathered; both phases run one
# sorted-segment reduction over (edge_src, edge_dst) via
# ``kernels/csr_segment.py``. The hub side-channel is unnecessary — the
# edge set already covers every entry. The mex/conflict semantics are the
# exact predicates of the ELL path evaluated over the same neighbour
# sets, so csr-segment colorings are bit-identical to ell-tail ones.
#
# Phase split: the dense form sweeps every edge (row-complete flags) and
# re-compacts from the mask; the data-driven form packs the edges of its
# worklist rows (``_packed_csr_step``) when the graph's ``seg_bound``
# allows, else sweeps every edge and filters its items block in O(C).

def _row_start(degrees: jax.Array) -> jax.Array:
    """First CSR edge of every row (exclusive prefix sum of degrees)."""
    return kcsr.prefix_sum(degrees) - degrees


def _csr_two_phase_core(ig: IPGCGraph, colors: jax.Array, base: jax.Array,
                        active: jax.Array, *, window: int):
    n = ig.n_nodes
    es = ig.edge_src
    ed, wins = kcsr.split_key(ig.edge_dst)
    deg = ig.degrees
    start = _row_start(deg)
    base_e = kcsr.spread(base, start, es.shape[0])
    # --- assign (speculative windowed mex over the edge segments) ---
    ec = _gather_neighbor_colors(colors, ed)             # E-shaped gather 1
    forb = kcsr.edge_forbidden(es, ec, base_e, start, deg, window,
                               _max_run(ig))
    new_c, new_base, newly = _mex_from_forbidden(
        forb, active, base, colors[:n], window)
    colors2 = colors.at[:n].set(new_c)
    # --- resolve (a newly colored row loses to a winning neighbour) ---
    cv = _gather_neighbor_colors(colors2, ed)            # E-shaped gather 2
    lose = kcsr.edge_conflict(es, cv, wins, base_e,
                              jnp.where(newly, new_c, NO_COLOR), base,
                              start, deg, window, _max_run(ig))
    colors3 = colors2.at[:n].set(jnp.where(lose, NO_COLOR, colors2[:n]))
    still = lose | (active & ~newly)
    return colors3, new_base, still


def _csr_fused_core(ig: IPGCGraph, colors: jax.Array, base: jax.Array,
                    active: jax.Array, *, window: int):
    n = ig.n_nodes
    es = ig.edge_src
    ed, wins = kcsr.split_key(ig.edge_dst)
    deg = ig.degrees
    start = _row_start(deg)
    cu = colors[:n]
    pending = active & (cu >= 0)
    ec = _gather_neighbor_colors(colors, ed)             # the ONE gather
    lose, forb = kcsr.edge_fused(
        es, ec, wins, kcsr.spread(base, start, es.shape[0]),
        jnp.where(pending, cu, NO_COLOR), base, start, deg, window,
        _max_run(ig))
    free = ~forb
    has = free.any(axis=1)
    first = jnp.argmax(free, axis=1).astype(jnp.int32)
    need = lose | (active & (cu < 0))
    new_c = jnp.where(need & has, base + first,
                      jnp.where(lose, NO_COLOR, cu))
    new_base = jnp.where(need & ~has, base + window, base)
    colors2 = colors.at[:n].set(new_c)
    return colors2, new_base, need


def _csr_emit_dense(wl: Worklist, still: jax.Array, n: int) -> Worklist:
    items, count = compact_mask(still, wl.items.shape[0], n)
    return Worklist(mask=still, items=items, count=count)


def _csr_emit_sparse(wl: Worklist, still: jax.Array, n: int) -> Worklist:
    """O(C) data-driven worklist maintenance: filter the items block
    against the row-complete ``still`` flags (mask and items describe the
    same set — the §2 dual-representation invariant)."""
    items = wl.items
    valid = items < n
    keep = jnp.where(valid, still[jnp.minimum(items, n - 1)], False)
    new_items, count = compact_items(items, keep, n)
    return Worklist(mask=still, items=new_items, count=count)


def _csr_step(ig: IPGCGraph, colors: jax.Array, base: jax.Array,
              wl: Worklist, *, window: int, fused: bool, sparse: bool
              ) -> tuple[jax.Array, jax.Array, Worklist]:
    if fused:
        # ONE edge-parallel pass: conflict + forbidden come out of a
        # single sweep over the shared edge gather (kcsr.edge_fused) and
        # the O(C)/O(N) worklist emission fuses into its epilogue — the
        # csr analogue of the fused_compact kernel.
        LAUNCH_COUNTS["fused"] += 1
    else:
        LAUNCH_COUNTS["mex"] += 1
        LAUNCH_COUNTS["conflict"] += 1
        LAUNCH_COUNTS["compact"] += 1
    if sparse:
        cap = packed_cap(ig, wl.capacity, ig.edge_dst.shape[0])
        if cap is not None:
            return _packed_csr_step(ig, colors, base, wl, window=window,
                                    fused=fused, chunk=packed_chunk(cap))
    core = _csr_fused_core if fused else _csr_two_phase_core
    colors2, base2, still = core(ig, colors, base, wl.mask, window=window)
    emit = _csr_emit_sparse if sparse else _csr_emit_dense
    return colors2, base2, emit(wl, still, ig.n_nodes)


# ---------------------------------------------------------------------------
# packed sparse passes — only the entries of the worklist's rows
# ---------------------------------------------------------------------------
# A data-driven step at capacity C needs the edge entries (csr-segment) or
# tail entries (hub side-channel) of its C rows only. The graph's
# ``seg_bound`` (the most entries any C rows own) decides whether the step
# packs them (``packed_cap``); when that bound is over half of all entries
# the step sweeps them all instead. The csr-segment step reads its rows'
# edges in chunks of ``PACKED_CHUNK`` slots, as many as their live entries
# fill (``kcsr.packed_row_words``); the hub side-channel lays its rows'
# tail entries out in one static buffer of ``packed_cap`` slots
# (``_hub_packed``). Per row the result is the same segment reduction over
# the same entries, so every form is bit-identical — only the amount of
# gathered data differs.

# Slots a packed csr-segment pass reads per trip of its chunk loop. On a
# v5e a trip costs about 45 us besides 22 ns a slot, and a step wastes
# half a chunk past its live entries on average: 2^15-2^17 colored the
# kron cell within 3% of each other, 2^16 fastest (PERF.md section 6).
PACKED_CHUNK = 2 ** 16

@dataclasses.dataclass(frozen=True)
class _Packed:
    rows: jax.Array       # i32[C] row of each owner (0 for pad owners)
    off: jax.Array        # i32[C] first slot of each owner
    nonempty: jax.Array   # bool[C] owner has an entry
    dst: jax.Array        # i32[cap] entry destination (N = no entry)
    wins: jax.Array       # bool[cap] destination wins the tie-break
    src: jax.Array        # i32[cap] owner row of each slot (run id)
    max_run: int          # most entries one owner has

    def spread(self, v: jax.Array) -> jax.Array:
        return kcsr.spread(v, self.off, self.dst.shape[0])

    def rows_words(self, words: tuple) -> tuple:
        return kcsr.row_windows(words, self.src, self.off, self.nonempty,
                                self.max_run)


def packed_cap(ig: IPGCGraph, capacity: int, m: int) -> "int | None":
    """Static bound on the entries the rows of a packed pass at worklist
    capacity ``capacity`` own, out of ``m`` entries: the size of the hub
    side-channel's buffer, and the most slots the csr-segment chunk loop
    reads. None where the graph has no bound or the bound would pass half
    of the entries: the pass then sweeps all ``m``."""
    if not ig.seg_bound:
        return None
    k = min(max(capacity - 1, 0).bit_length(), len(ig.seg_bound) - 1)
    cap = max(-(-ig.seg_bound[k] // 1024) * 1024, 1024)
    return None if 2 * cap > m else cap


def packed_chunk(cap: int) -> int:
    """Slots a trip of the csr-segment chunk loop reads, for a step whose
    rows own at most ``cap`` entries: no more than ``cap`` itself."""
    return min(PACKED_CHUNK, cap)


def _packed_forbidden(pk: _Packed, ec: jax.Array, base_rows: jax.Array,
                      window: int) -> jax.Array:
    """(C, W) forbidden bitmap of the packed rows."""
    words = kcsr.window_words(ec, pk.spread(base_rows), window)
    return kcsr.words_bitmap(pk.rows_words(words), window)


def _packed_lose(pk: _Packed, cv: jax.Array, c_rows: jax.Array,
                 base_rows: jax.Array, window: int) -> jax.Array:
    """(C,) conflict flags of the packed rows whose color is ``c_rows``
    (negative for rows that cannot lose; see ``kcsr.edge_conflict``)."""
    win_c = jnp.where(pk.wins, cv, -1)
    words = pk.rows_words(kcsr.window_words(win_c, pk.spread(base_rows),
                                            window))
    return (c_rows >= 0) & kcsr.window_bit(words, c_rows - base_rows)


def _hub_packed(ig: IPGCGraph, items: jax.Array) -> "_Packed | None":
    """Pack the tail entries of the hub rows in ``items`` into a static
    buffer of ``packed_cap`` slots (``None``: no static bound small
    enough — sweep the whole tail). The tie-break comes from the
    priorities."""
    m = ig.tail_dst.shape[0]
    cap = packed_cap(ig, items.shape[0], m)
    if cap is None:
        return None
    n, ts, nh = ig.n_nodes, ig.tail_start, ig.n_hub
    valid = items < n
    rows = jnp.where(valid, items, 0)
    slot = jnp.minimum(ig.hub_slot[rows], nh)
    counts = jnp.where(valid, ts[jnp.minimum(slot + 1, nh)] - ts[slot], 0)
    pos, off, live = kcsr.packed_ranges(ts[slot], counts, cap)
    src = kcsr.spread(rows, off, cap)
    dst = ig.tail_dst[jnp.clip(pos, 0, m - 1)]
    wins = kcsr.wins(kcsr.spread(ig.priority[rows], off, cap),
                     ig.priority[dst], src, dst)
    return _Packed(rows=rows, off=off, nonempty=counts > 0,
                   dst=jnp.where(live, dst, n), wins=wins, src=src,
                   max_run=_max_run(ig))


def _packed_csr_step(ig: IPGCGraph, colors: jax.Array, base: jax.Array,
                     wl: Worklist, *, window: int, fused: bool, chunk: int
                     ) -> tuple[jax.Array, jax.Array, Worklist]:
    """The csr-segment sparse step over the edges of its rows only, read
    in chunks of ``chunk`` slots of their packed order: one chunk loop
    for the forbidden words, which keeps the edges it gathered, and one,
    after the new colors land, for the winners' words over the kept
    edges (one loop with both where ``fused``)."""
    n = ig.n_nodes
    m = ig.edge_dst.shape[0]
    items = wl.items
    valid = items < n
    rows = jnp.where(valid, items, 0)
    ids = jnp.where(valid, items, n)
    counts = jnp.where(valid, ig.degrees[rows], 0)
    starts = _row_start(ig.degrees)[rows]
    base_rows = base[rows]
    cu = jnp.where(valid, colors[rows], PAD_COLOR)
    nw = -(-window // 32)

    def edge_of(pos, live):
        return jnp.where(live, ig.edge_dst[jnp.clip(pos, 0, m - 1)], n)

    def words_of(colors_now, forbidden: bool, winners: bool):
        def words(key, base_e):
            dst, wins = kcsr.split_key(key)
            c = _gather_neighbor_colors(colors_now, dst)
            return ((kcsr.window_words(c, base_e, window) if forbidden
                     else ())
                    + (kcsr.window_words(jnp.where(wins, c, -1), base_e,
                                         window) if winners else ()))
        return words

    def lose_of(words, c_rows):
        return (c_rows >= 0) & kcsr.window_bit(words, c_rows - base_rows)

    def packed(colors_now, forbidden: bool, winners: bool, keep: int = 0):
        return kcsr.packed_row_words(
            starts, counts, (base_rows,), edge_of,
            words_of(colors_now, forbidden, winners),
            nw * (forbidden + winners), chunk, _max_run(ig), keep)

    if fused:
        pending = valid & (cu >= 0)
        words = packed(colors, True, True)
        lose = lose_of(words[nw:], jnp.where(pending, cu, NO_COLOR))
        free = ~kcsr.words_bitmap(words[:nw], window)
        has = free.any(axis=1)
        first = jnp.argmax(free, axis=1).astype(jnp.int32)
        still = lose | (valid & (cu < 0))
        new_c = jnp.where(still & has, base_rows + first,
                          jnp.where(lose, NO_COLOR, cu))
        new_base = jnp.where(still & ~has, base_rows + window, base_rows)
    else:
        # the rows own at most packed_cap entries: keep that many slots
        keep = -(-packed_cap(ig, wl.capacity, m) // chunk) * chunk
        forb, kept = packed(colors, True, False, keep)
        new_c, new_base, newly = _mex_from_forbidden(
            kcsr.words_bitmap(forb, window), valid, base_rows, cu, window)
        colors2 = colors.at[ids].set(jnp.where(valid, new_c, PAD_COLOR))
        lose = lose_of(kcsr.kept_row_words(
            counts, kept, words_of(colors2, False, True), nw, chunk,
            _max_run(ig)), jnp.where(newly, new_c, NO_COLOR))
        new_c = jnp.where(lose, NO_COLOR, new_c)
        still = lose | (valid & ~newly)
    colors2 = colors.at[ids].set(jnp.where(valid, new_c, PAD_COLOR))
    colors2 = colors2.at[n].set(PAD_COLOR)
    base2 = base.at[ids].set(new_base, mode="drop")
    new_items, count = compact_items(items, still, n)
    mask = wl.mask.at[ids].set(still, mode="drop")
    return colors2, base2, Worklist(mask=mask, items=new_items, count=count)


# ---------------------------------------------------------------------------
# dense (topology-driven) step — sweeps all N rows, maintains the worklist
# ---------------------------------------------------------------------------

def dense_step_impl(ig: IPGCGraph, colors: jax.Array, base: jax.Array,
                    wl: Worklist, *, window: int = 128, impl: str = "jnp",
                    force_hub: bool | None = None,
                    tile_rows: int | None = None
                    ) -> tuple[jax.Array, jax.Array, Worklist]:
    if ig.layout_kind == "csr-segment":
        return _csr_step(ig, colors, base, wl, window=window,
                         fused=False, sparse=False)
    n = ig.n_nodes
    active = wl.mask
    row_ids = jnp.arange(n, dtype=jnp.int32)
    # static: hub side-channel compiled out entirely for regular graphs
    # (force_hub restores the unconditional path for A/B runs)
    has_hubs = _has_hubs(ig, force_hub)

    # --- assign (speculative windowed mex) ---
    nc = _gather_neighbor_colors(colors, ig.ell_idx)
    if has_hubs:
        hub_forb = _hub_forbidden(ig, colors, base, window)      # (nh+1, W)
        extra = hub_forb[jnp.minimum(ig.hub_slot, ig.n_hub)]     # (N, W)
    else:
        extra = None
    new_c, new_base, newly = _mex_rows(
        ig, nc, base, active, colors[:n], extra, window, impl, tile_rows)
    colors2 = colors.at[:n].set(new_c)

    # --- resolve (uncolor exactly one endpoint per conflict edge) ---
    lose = _lose_rows(ig, ig.ell_idx, row_ids, ig.ell_wins, new_c, colors2,
                      newly, impl, tile_rows)
    if has_hubs:
        newly_full = jnp.concatenate([newly, jnp.zeros((1,), bool)])
        hub_l = _hub_lose(ig, colors2, newly_full)
        lose = lose | hub_l[jnp.minimum(ig.hub_slot, ig.n_hub)]
    colors3 = colors2.at[:n].set(jnp.where(lose, NO_COLOR, colors2[:n]))

    # --- maintain the worklist (the paper's contribution: also in dense mode)
    still = lose | (active & ~newly)
    LAUNCH_COUNTS["compact"] += 1
    items, count = compact_mask(still, wl.items.shape[0], n)
    return colors3, new_base, Worklist(mask=still, items=items, count=count)


# ---------------------------------------------------------------------------
# sparse (data-driven) step — gathers C worklist rows, O(C*K + T + C*W)
# ---------------------------------------------------------------------------

def sparse_step_impl(ig: IPGCGraph, colors: jax.Array, base: jax.Array,
                     wl: Worklist, *, window: int = 128, impl: str = "jnp",
                     force_hub: bool | None = None,
                     tile_rows: int | None = None
                     ) -> tuple[jax.Array, jax.Array, Worklist]:
    if ig.layout_kind == "csr-segment":
        return _csr_step(ig, colors, base, wl, window=window,
                         fused=False, sparse=True)
    n = ig.n_nodes
    items = wl.items
    valid = items < n
    safe = jnp.where(valid, items, 0)

    # --- assign ---
    has_hubs = _has_hubs(ig, force_hub)
    ell_rows = jnp.where(valid[:, None], ig.ell_idx[safe], n)    # (C, K)
    nc = _gather_neighbor_colors(colors, ell_rows)
    base_rows = base[safe]
    pk = _hub_packed(ig, items) if has_hubs else None
    if pk is not None:
        extra = _packed_forbidden(pk, colors[pk.dst], base_rows, window)
    elif has_hubs:
        hub_forb = _hub_forbidden(ig, colors, base, window)
        extra = hub_forb[jnp.minimum(ig.hub_slot[safe], ig.n_hub)]
    else:
        extra = None
    new_c, new_base_rows, newly = _mex_rows(
        ig, nc, base_rows, valid, colors[safe], extra, window, impl,
        tile_rows)
    colors2 = colors.at[jnp.where(valid, items, n)].set(
        jnp.where(valid, new_c, PAD_COLOR))
    colors2 = colors2.at[n].set(PAD_COLOR)
    # padding rows scatter to the dropped index n — routing them to row 0
    # would let their stale value clobber node 0's real update
    base2 = base.at[jnp.where(valid, items, n)].set(new_base_rows,
                                                    mode="drop")

    # --- resolve ---
    lose = _lose_rows(ig, ell_rows, jnp.where(valid, items, n),
                      ig.ell_wins[safe], new_c, colors2, newly, impl,
                      tile_rows)
    if pk is not None:
        lose = lose | (_packed_lose(pk, colors2[pk.dst],
                                    jnp.where(newly, new_c, NO_COLOR),
                                    base_rows, window) & valid)
    elif has_hubs:
        newly_full = jnp.zeros((n + 1,), bool).at[
            jnp.where(newly, items, n)].set(newly, mode="drop")[: n + 1]
        hub_l = _hub_lose(ig, colors2, newly_full)
        lose = lose | (hub_l[jnp.minimum(ig.hub_slot[safe], ig.n_hub)] & valid)
    colors3 = colors2.at[jnp.where(lose, items, n)].set(
        jnp.where(lose, NO_COLOR, colors2[jnp.minimum(items, n)]), mode="drop")
    colors3 = colors3.at[n].set(PAD_COLOR)

    # --- maintain the worklist in O(C) ---
    still = lose | (valid & ~newly)
    LAUNCH_COUNTS["compact"] += 1
    new_items, count = compact_items(items, still, n)
    mask = wl.mask.at[jnp.where(valid, items, n)].set(still, mode="drop")
    return colors3, base2, Worklist(mask=mask, items=new_items, count=count)


# ---------------------------------------------------------------------------
# fused assign+resolve steps — ONE neighbour-color gather per iteration
# ---------------------------------------------------------------------------
# The two-phase steps above gather ``colors[ell_idx]`` twice per iteration
# (once pre-assign for the mex bitmap, once post-assign for the conflict
# check). The fused steps pipeline the phases instead (DESIGN.md §5): the
# resolve of the assignments speculated in iteration t-1 and the assign of
# iteration t share a single snapshot gather.
#
# Per active row u (active = in the worklist = not yet *confirmed*):
#   pending(u)  := active(u) and colors[u] >= 0   (speculated last step)
#   1. resolve: u loses iff pending and some neighbour holds the same color
#      with a higher (priority, id) — by construction a same-color
#      neighbour can only be same-round pending, so the snapshot is exact.
#   2. assign: rows that lost or were still uncolored re-run the windowed
#      mex over the SAME gathered tile. A neighbour that lost *this* step
#      keeps its doomed color forbidden in the snapshot — a safe
#      over-approximation (validity is never violated; at worst a color
#      index is skipped).
#   3. worklist: confirmed rows (pending and did not lose) leave; newly
#      speculated and window-exhausted rows stay.
#
# Both fused phases maintain the full dual worklist state, so the hybrid
# engine can still switch dense<->sparse for free mid-run.

def _fused_rows(ig: IPGCGraph, nc: jax.Array, npr: jax.Array,
                nbr_ids: jax.Array, base_rows: jax.Array, cu: jax.Array,
                pu: jax.Array, ids: jax.Array, pending: jax.Array,
                extra_forb: jax.Array | None, window: int, impl: str,
                tile_rows: int | None = None
                ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Shared row-wise core: (lose_ell, first, has) from one gathered tile.

    Kept for the distributed steps (exec/dist.py), whose worklist
    emission happens after a cross-shard exchange and so cannot fold into
    the kernel; the single-device fused steps route through
    ``_fused_compact_rows`` below instead.
    """
    LAUNCH_COUNTS["fused"] += 1
    if impl == "pallas":
        from repro.kernels import ops as kops
        if extra_forb is None:
            extra_forb = jnp.zeros((nc.shape[0], window), bool)
        lose, first = kops.fused_step(nc, npr, nbr_ids, base_rows, cu, pu,
                                      ids, pending, extra_forb, window,
                                      tile_rows)
        return lose, first, first >= 0
    lose = _conflict_rows(nc, npr, nbr_ids, cu, pu, ids) & pending
    forb = _ell_forbidden(nc, base_rows, window)
    if extra_forb is not None:
        forb = forb | extra_forb
    free = ~forb
    has = free.any(axis=1)
    first = jnp.argmax(free, axis=1).astype(jnp.int32)
    return lose, first, has


def _fused_compact_rows(ig: IPGCGraph, nc: jax.Array, npr: jax.Array,
                        nbr_ids: jax.Array, base_rows: jax.Array,
                        cu: jax.Array, pu: jax.Array, ids: jax.Array,
                        active: jax.Array, pending: jax.Array,
                        extra_forb: jax.Array | None,
                        hub_lose: jax.Array | None, window: int, impl: str,
                        tile_rows: int | None, capacity: int
                        ) -> tuple[jax.Array, jax.Array, jax.Array,
                                   jax.Array, jax.Array]:
    """ONE-pass row-wise core (DESIGN.md §10): resolve + windowed mex +
    new-color/base selection in a single kernel, then the compacted
    worklist emission (XLA's, after the kernel, for both impls). ``ids`` is the emitted value, so the dense caller
    passes row iota (emission == ``compact_mask``) and the sparse caller
    its items block (emission == ``compact_items``). Returns
    ``(new_colors, new_base, still, items, count)``.
    """
    LAUNCH_COUNTS["fused"] += 1
    if impl == "pallas":
        from repro.kernels import ops as kops
        new_c, new_base, need = kops.fused_compact(
            nc, npr, nbr_ids, base_rows, cu, pu, ids, active, pending,
            extra_forb, hub_lose, window, tile_rows=tile_rows)
    else:
        lose = _conflict_rows(nc, npr, nbr_ids, cu, pu, ids) & pending
        if hub_lose is not None:
            lose = lose | (hub_lose & pending)
        forb = _ell_forbidden(nc, base_rows, window)
        if extra_forb is not None:
            forb = forb | extra_forb
        free = ~forb
        has = free.any(axis=1)
        first = jnp.argmax(free, axis=1).astype(jnp.int32)
        need = lose | (active & (cu < 0))
        new_c = jnp.where(need & has, base_rows + first,
                          jnp.where(lose, NO_COLOR, cu))
        new_base = jnp.where(need & ~has, base_rows + window, base_rows)
    # emission — bit-identical to compact_mask/compact_items over
    # ``need``: surviving ids ascending, sentinel-n tail, count = popcount
    items, count = compact_items(ids.astype(jnp.int32), need, ig.n_nodes,
                                 capacity)
    return new_c, new_base, need, items, count


def fused_dense_step_impl(ig: IPGCGraph, colors: jax.Array, base: jax.Array,
                          wl: Worklist, *, window: int = 128,
                          impl: str = "jnp", force_hub: bool | None = None,
                          tile_rows: int | None = None
                          ) -> tuple[jax.Array, jax.Array, Worklist]:
    if ig.layout_kind == "csr-segment":
        return _csr_step(ig, colors, base, wl, window=window,
                         fused=True, sparse=False)
    n = ig.n_nodes
    active = wl.mask
    row_ids = jnp.arange(n, dtype=jnp.int32)
    has_hubs = _has_hubs(ig, force_hub)

    cu = colors[:n]
    pu = ig.priority[:n]
    pending = active & (cu >= 0)
    nc = _gather_neighbor_colors(colors, ig.ell_idx)   # the ONE gather
    npr = ig.priority[ig.ell_idx]

    if has_hubs:
        hub_slot = jnp.minimum(ig.hub_slot, ig.n_hub)
        extra = _hub_forbidden(ig, colors, base, window)[hub_slot]
        pending_full = jnp.concatenate([pending, jnp.zeros((1,), bool)])
        hub_lose = _hub_lose(ig, colors, pending_full)[hub_slot]
    else:
        extra = None
        hub_lose = None

    # ONE kernel pass: resolve + assign, then worklist emission (emitted
    # value = row iota, so the compacted items == compact_mask of ``still``)
    new_c, new_base, still, items, count = _fused_compact_rows(
        ig, nc, npr, ig.ell_idx, base, cu, pu, row_ids, active, pending,
        extra, hub_lose, window, impl, tile_rows, wl.items.shape[0])
    colors2 = colors.at[:n].set(new_c)
    return colors2, new_base, Worklist(mask=still, items=items, count=count)


def fused_sparse_step_impl(ig: IPGCGraph, colors: jax.Array, base: jax.Array,
                           wl: Worklist, *, window: int = 128,
                           impl: str = "jnp", force_hub: bool | None = None,
                           tile_rows: int | None = None
                           ) -> tuple[jax.Array, jax.Array, Worklist]:
    if ig.layout_kind == "csr-segment":
        return _csr_step(ig, colors, base, wl, window=window,
                         fused=True, sparse=True)
    n = ig.n_nodes
    items = wl.items
    valid = items < n
    safe = jnp.where(valid, items, 0)
    ids = jnp.where(valid, items, n)
    has_hubs = _has_hubs(ig, force_hub)

    ell_rows = jnp.where(valid[:, None], ig.ell_idx[safe], n)    # (C, K)
    nc = _gather_neighbor_colors(colors, ell_rows)     # the ONE gather
    npr = ig.priority[ell_rows]
    cu = jnp.where(valid, colors[safe], PAD_COLOR)
    pu = ig.priority[ids]
    base_rows = base[safe]
    pending = valid & (cu >= 0)

    pk = _hub_packed(ig, items) if has_hubs else None
    if pk is not None:
        tc = colors[pk.dst]
        extra = _packed_forbidden(pk, tc, base_rows, window)
        hub_lose = _packed_lose(pk, tc, jnp.where(pending, cu, NO_COLOR),
                                base_rows, window) & valid
    elif has_hubs:
        hub_slot = jnp.minimum(ig.hub_slot[safe], ig.n_hub)
        extra = _hub_forbidden(ig, colors, base, window)[hub_slot]
        pending_full = jnp.zeros((n + 1,), bool).at[
            jnp.where(pending, items, n)].set(pending, mode="drop")[: n + 1]
        hub_lose = _hub_lose(ig, colors, pending_full)[hub_slot] & valid
    else:
        extra = None
        hub_lose = None

    # ONE kernel pass: emitted value = the items block (invalid rows carry the
    # sentinel n and are inactive), so the compacted items ==
    # compact_items of ``still`` over the old block
    new_c, new_base_rows, still, new_items, count = _fused_compact_rows(
        ig, nc, npr, ell_rows, base_rows, cu, pu, ids, valid, pending,
        extra, hub_lose, window, impl, tile_rows, items.shape[0])

    colors2 = colors.at[jnp.where(valid, items, n)].set(
        jnp.where(valid, new_c, PAD_COLOR))
    colors2 = colors2.at[n].set(PAD_COLOR)
    # padding rows scatter to the dropped index n (see sparse_step_impl)
    base2 = base.at[jnp.where(valid, items, n)].set(new_base_rows,
                                                    mode="drop")
    mask = wl.mask.at[jnp.where(valid, items, n)].set(still, mode="drop")
    return colors2, base2, Worklist(mask=mask, items=new_items, count=count)


# jitted public entry points (``*_impl`` stay traceable for instrumentation)
_STEP_STATICS = ("window", "impl", "force_hub", "tile_rows")
dense_step = jax.jit(dense_step_impl, static_argnames=_STEP_STATICS)
sparse_step = jax.jit(sparse_step_impl, static_argnames=_STEP_STATICS)
fused_dense_step = jax.jit(fused_dense_step_impl, static_argnames=_STEP_STATICS)
fused_sparse_step = jax.jit(fused_sparse_step_impl, static_argnames=_STEP_STATICS)


def step_fns(fused: bool):
    """(dense, sparse) jitted step pair for the requested semantics."""
    return ((fused_dense_step, fused_sparse_step) if fused
            else (dense_step, sparse_step))


# ---------------------------------------------------------------------------
# what a step gathers: the live entries of its rows, and its slots
# ---------------------------------------------------------------------------

def live_entries(ig: IPGCGraph, items: jax.Array) -> jax.Array:
    """int32[] adjacency entries of the rows in a worklist items block
    (the sum of their degrees): what a sparse step over it has to read."""
    n = ig.n_nodes
    valid = items < n
    return jnp.sum(jnp.where(valid, ig.degrees[jnp.where(valid, items, 0)],
                             0), dtype=jnp.int32)


def mask_entries(ig: IPGCGraph, mask: jax.Array) -> jax.Array:
    """int32[] adjacency entries of the rows set in a worklist mask: what
    a dense step, which runs exactly those rows, has to read."""
    return jnp.sum(jnp.where(mask, ig.degrees, 0), dtype=jnp.int32)


def sparse_slots(ig: IPGCGraph, capacity: int, live: int,
                 force_hub: bool | None = None) -> int:
    """Adjacency entries a sparse step at worklist capacity ``capacity``
    whose rows own ``live`` entries gathers, live or not: on csr-segment
    the chunks its live entries fill (``ceil(live / chunk) * chunk``), or
    every edge entry where the step sweeps them; on the ELL path
    ``capacity`` rows of the ELL width, plus the hub tail's packed
    buffer, or the whole tail where the step sweeps it."""
    if ig.layout_kind == "csr-segment":
        m = ig.edge_dst.shape[0]
        cap = packed_cap(ig, capacity, m)
        if cap is None:
            return m
        chunk = packed_chunk(cap)
        return -(-live // chunk) * chunk
    slots = capacity * ig.ell_width
    if _has_hubs(ig, force_hub):
        t = ig.tail_dst.shape[0]
        slots += packed_cap(ig, capacity, t) or t
    return slots


def dense_slots(ig: IPGCGraph, force_hub: bool | None = None) -> int:
    """Adjacency entries a dense step gathers, live or not (static): on
    csr-segment the padded edge array; on the ELL path every row's ELL
    slots, plus the whole hub tail where the step reads it."""
    if ig.layout_kind == "csr-segment":
        return ig.edge_dst.shape[0]
    tail = ig.tail_dst.shape[0] if _has_hubs(ig, force_hub) else 0
    return ig.n_nodes * ig.ell_width + tail


@functools.cache
def tallied(step_impl, *, dense: bool = False):
    """The host loop's jitted form of a step impl: the same step, which
    also returns ``int32[2]`` = (the worklist count after the step, the
    live entries of the rows it ran: those of ``wl.mask`` for a
    ``dense`` step, of ``wl.items`` for a sparse one), read back in one
    transfer. The program keeps the impl's name."""
    @functools.wraps(step_impl)
    def step(ig, colors, aux, wl, **statics):
        live = (mask_entries(ig, wl.mask) if dense
                else live_entries(ig, wl.items))
        colors, aux, wl = step_impl(ig, colors, aux, wl, **statics)
        return colors, aux, wl, jnp.stack([wl.count, live])
    return jax.jit(step, static_argnames=_STEP_STATICS)
