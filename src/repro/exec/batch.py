"""Batched multi-graph coloring — many graphs, one device dispatch.

The serving-scale workload the unified session cache exists for
(DESIGN.md §9): a request stream of mixed-size graphs is colored at high
throughput by padding graphs into *shape-class buckets* and running the
per-iteration step ``vmap``-ed over lanes inside a single
``lax.while_loop`` that trips until every lane's worklist drains.

Shape-class bucketing rules:

  * The node ladder reuses ``worklist.bucket_capacities(max_n,
    ratio=spec.bucket_ratio)``: each graph lands in the smallest rung
    that holds it (``pick_bucket``), so padding waste per lane is bounded
    by the ladder ratio.
  * Within a rung, lanes must agree on every static step argument:
    graphs are sub-grouped by (resolved window, layout kind), and the
    bucket's ELL width / tail length / hub count are the member maxima
    rounded up (multiples of 8 for the ELL width, powers of two for tail
    and hub slots) — ``ipgc.pad_prepared`` guarantees the padding is
    inert. Lane count is rounded up to a power of two with empty lanes
    so the compiled program is reused across batch sizes.

Bit-identity contract (tests/test_exec.py): every lane's colors,
iteration count and reconstructed mode trace are identical to running
``Session.run`` on that graph alone with the same spec in the host
regime. Three ingredients make this exact: padding is inert
(``pad_prepared``), the dense-form and sparse-form steps of a
batch-safe algorithm produce identical state for the same active set
(the dual-worklist invariant — the batched Pipe always executes the
dense form and *reconstructs* the D/S trace from per-lane counts against
the per-lane policy threshold, exact for monotone policies), and drained
lanes are no-ops (an all-False active mask changes nothing).

Restrictions (validated loudly): ``impl="jnp"`` only (the Pallas kernels
are not audited under vmap), monotone policy modes only (an adaptive
host-side policy cannot be replayed per lane), ELL-family layouts only
(csr-segment edge arrays are not lane-stacked), and the algorithm must
declare ``batch_safe=True`` (algos/base.py).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ipgc
from repro.core.policy import Timer, device_threshold, make_policy
from repro.core.result import ColoringResult
from repro.core.worklist import (bucket_capacities, pick_bucket,
                                 stacked_worklist)
from repro.exec.spec import ExecutionSpec
from repro.graphs.csr import NO_COLOR, PAD_COLOR, Graph
from repro.obs import trace as obs_trace


@dataclasses.dataclass(frozen=True)
class ShapeClass:
    """Static signature of one batch bucket — the compile key axis."""

    n_pad: int
    k_pad: int
    t_pad: int
    nh_pad: int
    window: int
    kind: str


def _pow2(x: int, floor: int = 1) -> int:
    p = floor
    while p < x:
        p *= 2
    return p


def _round8(x: int) -> int:
    return max(-(-x // 8) * 8, 8)


def shape_class_for(igs, n_cap: int, window: int, kind: str) -> ShapeClass:
    """The ShapeClass covering every member of one bucket rung: ELL width /
    tail length / hub count are the member maxima rounded up (x8 for the
    ELL width, powers of two for tail and hub slots) so near-miss batches
    reuse one compiled program; ``ipgc.pad_prepared`` guarantees the
    padding is inert."""
    return ShapeClass(
        n_pad=n_cap,
        k_pad=_round8(max(ig.ell_width for ig in igs)),
        t_pad=_pow2(max(ig.tail_src.shape[0] for ig in igs), floor=8),
        nh_pad=(0 if all(ig.n_hub == 0 for ig in igs)
                else _pow2(max(ig.n_hub for ig in igs))),
        window=window, kind=kind)


def grow_shape_class(sc: ShapeClass, ig) -> ShapeClass:
    """Sticky growth for streamed lane groups (serve/stream.py): widen the
    pads to also cover ``ig``, never shrink — resident lanes' carried
    state (colors/aux/worklist) depends only on ``n_pad``, so growth
    re-pads the lane-stacked *graph* arrays without touching state."""
    assert ig.n_nodes <= sc.n_pad, "graph exceeds the group's node rung"
    return ShapeClass(
        n_pad=sc.n_pad,
        k_pad=max(sc.k_pad, _round8(ig.ell_width)),
        t_pad=max(sc.t_pad, _pow2(ig.tail_src.shape[0], floor=8)),
        nh_pad=(sc.nh_pad if ig.n_hub == 0
                else max(sc.nh_pad, _pow2(ig.n_hub))),
        window=sc.window, kind=sc.kind)


def lane_colors(real_n: int, n_pad: int) -> jax.Array:
    """Per-lane initial colors: real slots uncolored, pad slots (and the
    sentinel) PAD_COLOR — so old sentinel gathers stay PAD and pad nodes
    can never look active or conflicting."""
    ar = jnp.arange(n_pad + 1)
    return jnp.where(ar < real_n, NO_COLOR, PAD_COLOR).astype(jnp.int32)


def empty_lane(sc: ShapeClass) -> ipgc.IPGCGraph:
    """An all-padding member of the shape class (fills power-of-two lane
    slots; its count is 0, so every step is a no-op on it)."""
    return ipgc.IPGCGraph(
        n_nodes=sc.n_pad, ell_width=sc.k_pad, n_hub=sc.nh_pad,
        ell_idx=jnp.full((sc.n_pad, sc.k_pad), sc.n_pad, jnp.int32),
        degrees=jnp.zeros((sc.n_pad,), jnp.int32),
        priority=jnp.full((sc.n_pad + 1,), -1, jnp.int32),
        tail_src=jnp.zeros((sc.t_pad,), jnp.int32),
        tail_dst=jnp.full((sc.t_pad,), sc.n_pad, jnp.int32),
        tail_valid=jnp.zeros((sc.t_pad,), bool),
        tail_slot=jnp.full((sc.t_pad,), sc.nh_pad, jnp.int32),
        hub_slot=jnp.full((sc.n_pad,), sc.nh_pad, jnp.int32),
        hub_ids=jnp.zeros((max(sc.nh_pad, 1),), jnp.int32),
        tail_start=jnp.zeros((sc.nh_pad + 1,), jnp.int32),
        layout_kind=sc.kind,
        ell_wins=jnp.zeros((sc.n_pad, ipgc.wins_words(sc.k_pad)),
                           jnp.uint32))


# ---------------------------------------------------------------------------
# lane-axis state bundle (adaptive lane groups, serve/stream.py)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LaneState:
    """One streamed lane group's carried state, bundled with its
    lane-stacked graph so the lane axis (axis 0 of every array leaf) can
    be widened or compacted in one structural map.

    Per-lane semantics are lane-count-independent: the vmapped step
    treats lanes independently, so appending inert filler lanes
    (``widen_lanes``) or dropping inert lanes (``take_lanes``) never
    changes a resident lane's colors/aux/worklist/counters — the stream
    bit-identity contract survives adaptive growth and shrink
    (DESIGN.md §14). What DOES change with the lane count is the
    compiled program (b is a shape), which is why growth is by powers of
    two: the b-ladder is small and each width compiles once.
    """

    stacked: object      # lane-stacked IPGCGraph, (b, ...) leaves
    colors: jax.Array    # (b, n_pad + 1)
    aux: object          # algorithm aux state, lane-stacked
    wl: object           # stacked Worklist: mask/items (b, n_pad), count (b,)
    thresh: jax.Array    # (b,) per-lane policy thresholds
    iters: jax.Array     # (b,) per-lane iteration counters
    nd: jax.Array        # (b,) dense-iteration counters
    ns: jax.Array        # (b,) sparse-iteration counters

    @property
    def b(self) -> int:
        return int(self.thresh.shape[0])

    def _fields(self) -> tuple:
        return (self.stacked, self.colors, self.aux, self.wl,
                self.thresh, self.iters, self.nd, self.ns)


def fresh_lane_state(sc: ShapeClass, alg, b: int = 1) -> LaneState:
    """``b`` inert lanes of shape class ``sc``: every lane is an
    ``empty_lane`` with PAD-only colors, a drained worklist and zeroed
    counters — the template a stream group populates on admission."""
    lane = empty_lane(sc)
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), lane)
    aux = jax.tree.map(lambda *xs: jnp.stack(xs), alg.init_state(lane)[1])
    z = jnp.zeros((1,), jnp.int32)
    st = LaneState(stacked=stacked,
                   colors=lane_colors(0, sc.n_pad)[None],
                   aux=aux, wl=stacked_worklist([0], sc.n_pad),
                   thresh=z, iters=z, nd=z, ns=z)
    return widen_lanes(st, st, b) if b > 1 else st


def widen_lanes(st: LaneState, filler: LaneState, b_new: int) -> LaneState:
    """Grow the lane axis to ``b_new`` by appending broadcast copies of
    ``filler``'s lane 0 (which must be inert); resident lanes' values
    are bit-untouched."""
    extra = b_new - st.b
    if extra < 0:
        raise ValueError(f"widen_lanes cannot shrink {st.b} -> {b_new}")
    if extra == 0:
        return st

    def cat(x, f):
        pad = jnp.broadcast_to(f[:1], (extra,) + x.shape[1:])
        return jnp.concatenate([x, pad], axis=0)

    return LaneState(*jax.tree.map(cat, st._fields(), filler._fields()))


def take_lanes(st: LaneState, idx) -> LaneState:
    """Compact (or reorder) the lane axis to ``idx`` — shrink-on-idle
    retires inert lanes by selecting only the resident ones; each kept
    lane's values are carried verbatim."""
    idx = np.asarray(idx, np.int32)
    return LaneState(*jax.tree.map(lambda x: x[idx], st._fields()))


# ---------------------------------------------------------------------------
# the batched device program
# ---------------------------------------------------------------------------

def _freeze_inert(alive, new, old):
    """Per-lane select: lanes that are not alive keep their old state.

    For a *drained* lane this is a no-op (an all-False active mask makes
    the step itself inert) — it exists so a lane that hit its per-lane
    ``max_iter`` cap stops evolving, exactly like the solo host loop
    stops dispatching at ``max_iter``. The chunked streaming driver
    relies on this: lanes admitted in different rounds carry different
    iteration counts through one shared program.
    """
    def sel(n, o):
        mask = alive.reshape(alive.shape + (1,) * (n.ndim - 1))
        return jnp.where(mask, n, o)

    return jax.tree.map(sel, new, old)


def _batched_chunk_impl(ig, colors, aux, wl, thresh, iters0, nd0, ns0,
                        max_iter, chunk, *,
                        algo, window: int, impl: str, fused: bool,
                        force_hub: bool, tile_rows: "int | None" = None):
    """ONE device program for a whole bucket: the dense-form step vmapped
    over lanes inside a lax.while_loop that runs until every lane drains
    (or ``chunk`` trips elapse — the streaming refill boundary; run_batch
    passes ``chunk = max_iter`` so the loop is the full barrier batch).

    Per-lane bookkeeping mirrors the outlined chunk's D/S counters: a
    lane's iteration counts only while its count is > 0 and below the
    per-lane ``max_iter`` cap, and the D/S split is decided from the
    pre-step count against the lane's policy threshold — the same
    comparison the host loop makes, so the reconstructed trace is exact
    for monotone policies. ``iters0``/``nd0``/``ns0`` carry per-lane
    counters across chunk dispatches: streamed lanes admitted in
    different rounds resume mid-flight through the same compiled program.
    """
    if algo is None:
        dense_fn = (ipgc.fused_dense_step_impl if fused
                    else ipgc.dense_step_impl)
    else:
        dense_fn = algo.step_impls(fused)[0]
    step = jax.vmap(lambda g_, c, a, w: dense_fn(
        g_, c, a, w, window=window, impl=impl, force_hub=force_hub,
        tile_rows=tile_rows))

    def cond(state):
        _, _, wl, trip, iters, _, _ = state
        alive = (wl.count > 0) & (iters < max_iter)
        return alive.any() & (trip < chunk)

    def body(state):
        colors, aux, wl, trip, iters, nd, ns = state
        alive = (wl.count > 0) & (iters < max_iter)
        dense = alive & (wl.count > thresh)      # pre-step count, per lane
        stepped = step(ig, colors, aux, wl)
        colors, aux, wl = _freeze_inert(alive, stepped, (colors, aux, wl))
        return (colors, aux, wl, trip + 1,
                iters + alive.astype(jnp.int32),
                nd + dense.astype(jnp.int32),
                ns + (alive & ~dense).astype(jnp.int32))

    return jax.lax.while_loop(
        cond, body,
        (colors, aux, wl, jnp.zeros((), jnp.int32), iters0, nd0, ns0))


_batched_chunk = jax.jit(
    _batched_chunk_impl,
    static_argnames=("algo", "window", "impl", "fused", "force_hub",
                     "tile_rows"))


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def _validate(spec: ExecutionSpec, graphs):
    alg = spec.validate_batchable()
    for g in graphs:
        if not isinstance(g, Graph):
            raise TypeError(
                "run_batch needs host Graph objects (it pads and stacks "
                f"prepared arrays); got {type(g).__name__}")
    return alg


def run_batch(session, spec: ExecutionSpec, graphs,
              *, map_to_original: bool = False) -> list[ColoringResult]:
    """Color ``graphs`` under ``spec``; results in input order.

    ``map_to_original=True`` maps each lane's colors back through its
    graph's ``Permutation`` (no-op for identity/unreordered graphs), so
    a mixed-reorder batch reports colors in original node ids.
    """
    graphs = list(graphs)
    alg = _validate(spec, graphs)
    if not graphs:
        return []
    with session.pin():
        return _run_batch_pinned(session, spec, alg, graphs,
                                 map_to_original=map_to_original)


def _run_batch_pinned(session, spec, alg, graphs, *, map_to_original):
    from repro.algos.ipgc_algo import IPGC
    algo_static = None if alg == IPGC() else alg
    fused = alg.resolve_fused(spec.fused, default=False)  # host-loop default
    force_hub = False   # as the host regime: hub paths only on hub graphs
    # run_batch is jnp-only, so "auto" resolves to None (no tile grid);
    # an explicit int still rides the static key like every other regime
    tile_rows = spec.tile_rows if isinstance(spec.tile_rows, int) else None
    pol = make_policy(spec.mode, spec.h)

    prepared = [session._prepare(spec, g, alg) for g in graphs]
    for _, ig, _ in prepared:
        if ig.layout_kind == "csr-segment":
            raise NotImplementedError(
                "run_batch has no csr-segment lanes (per-graph edge "
                "arrays are not lane-stacked); pass layout='ell-tail' to "
                "batch this graph's ELL+tail arrays")

    # ---- shape-class bucketing (node ladder = worklist.bucket_capacities)
    caps = bucket_capacities(max(ig.n_nodes for _, ig, _ in prepared),
                             ratio=spec.bucket_ratio)
    groups: dict[tuple, list[int]] = {}
    for i, (_, ig, window) in enumerate(prepared):
        gk = (pick_bucket(caps, ig.n_nodes), window, ig.layout_kind)
        groups.setdefault(gk, []).append(i)

    results: list[ColoringResult | None] = [None] * len(graphs)
    for (n_cap, window, kind), idxs in sorted(groups.items(),
                                              key=lambda kv: kv[1][0]):
        igs = [prepared[i][1] for i in idxs]
        sc = shape_class_for(igs, n_cap, window, kind)
        b_pad = _pow2(len(idxs))

        # ---- lane-stacked graph (cached: identical batches re-dispatch)
        lane_ids = tuple(id(prepared[i][0]) for i in idxs)
        stack_key = ("stack", sc, alg, spec.priority, spec.layout,
                     spec.window, lane_ids, b_pad)

        def build_stack():
            lanes = []
            for i in idxs:
                g, ig, _ = prepared[i]
                pad_key = ("pad", id(g), sc, alg, spec.priority,
                           spec.layout, spec.window)
                lanes.append(session.cached(
                    pad_key,
                    lambda ig=ig, g=g: (g, ipgc.pad_prepared(
                        ig, sc.n_pad, sc.k_pad, sc.t_pad, sc.nh_pad)))[1])
            lanes.extend(empty_lane(sc) for _ in range(b_pad - len(idxs)))
            stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *lanes)
            aux0 = jax.tree.map(lambda *xs: jnp.stack(xs),
                                *[alg.init_state(lane)[1] for lane in lanes])
            return [prepared[i][0] for i in idxs], stacked, aux0

        _, stacked, aux0 = session.cached(stack_key, build_stack)

        # ---- per-lane state + policy thresholds
        real_ns = [prepared[i][1].n_nodes for i in idxs]
        real_ns += [0] * (b_pad - len(idxs))
        colors0 = jnp.stack([lane_colors(rn, sc.n_pad) for rn in real_ns])
        wl0 = stacked_worklist(real_ns, sc.n_pad)
        thresh = jnp.asarray(
            [device_threshold(pol, rn) if rn else 0 for rn in real_ns],
            jnp.int32)

        # program-cache bookkeeping: a first-seen (shape class, lane
        # count, statics) combination is a compile; repeats are hits
        session.cached(("batch-program", sc, b_pad, algo_static, fused,
                        force_hub, spec.impl, tile_rows), lambda: True)

        z = jnp.zeros((b_pad,), jnp.int32)
        with obs_trace.maybe_span("batch.dispatch", lanes=len(idxs),
                                  b_pad=b_pad, n_pad=sc.n_pad,
                                  window=window, kind=kind), Timer() as t:
            colors, aux, wl, _, iters, nd, ns = _batched_chunk(
                stacked, colors0, aux0, wl0, thresh, z, z, z,
                jnp.asarray(spec.max_iter, jnp.int32),
                jnp.asarray(spec.max_iter, jnp.int32),
                algo=algo_static, window=window, impl=spec.impl,
                fused=fused, force_hub=force_hub, tile_rows=tile_rows)
            counts_left = np.asarray(wl.count)   # device sync
        colors_np = np.asarray(colors)
        iters_np, nd_np, ns_np = (np.asarray(iters), np.asarray(nd),
                                  np.asarray(ns))
        if int(counts_left[:len(idxs)].sum()) != 0:
            raise RuntimeError(
                f"batch bucket {sc} hit max_iter={spec.max_iter} with "
                f"undrained lanes (counts {counts_left[:len(idxs)]})")

        for lane, i in enumerate(idxs):
            g, ig, _ = prepared[i]
            rn = ig.n_nodes
            final, n_colors = alg.finalize(colors_np[lane, :rn].copy())
            if map_to_original and getattr(g, "perm", None) is not None:
                final = g.perm.colors_to_original(final)
            results[i] = ColoringResult(
                colors=final, n_colors=n_colors,
                iterations=int(iters_np[lane]),
                mode_trace="D" * int(nd_np[lane]) + "S" * int(ns_np[lane]),
                counts=[rn], tti=[t.seconds], total_seconds=t.seconds,
                host_dispatches=1)
    return results
