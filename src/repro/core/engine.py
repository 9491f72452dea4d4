"""Hybrid coloring engine — the host-side analogue of IrGL's ``Pipe``.

The engine is algorithm-generic (DESIGN.md §7): every entry point takes
``algo=`` (a registry name or ``Algorithm`` instance; default ``"ipgc"``,
bit-identical to the pre-subsystem engine) and threads the algorithm's
steps and opaque ``aux`` state through the same Pipe machinery.

Two dispatch regimes (DESIGN.md §4):

* ``color`` — the host-loop Pipe: the device never sees dynamic shapes; the
  host reads back one scalar (``count``) per iteration — exactly the
  information IrGL's Pipe uses for its worklist-size check — picks dense vs
  sparse (the paper's H policy) and a capacity bucket, and dispatches the
  jitted step.
* ``color_outlined_hybrid`` — the device-resident Pipe: iterations run as
  chunks of ``lax.while_loop`` trips in which each trip picks dense vs
  sparse on-device (``lax.cond`` on ``count`` against the policy's traced
  threshold) at the current static capacity bucket. The host re-enters only
  when the count crosses a bucket boundary or the loop drains, collapsing
  ~O(iterations) host round-trips to ~O(#buckets).

The worklist state is maintained by *both* steps (the paper's
contribution), so there is no rebuild cost at a switch: we only ever
*slice* the already-compacted items array down to a smaller bucket.

Since the unified-session refactor (DESIGN.md §9) both entry points —
plus ``color_distributed`` — are thin dispatchers over
``repro.exec.Session``: they translate their keyword surface into an
``ExecutionSpec`` and run it on the process-default session, which owns
the one keyed compile cache all three regimes share. Results are
bit-identical to the pre-session drivers (tests/test_exec.py).
"""
from __future__ import annotations

import contextlib
import dataclasses
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ipgc
from repro.core.policy import Policy
from repro.core.worklist import full_worklist
from repro.graphs.csr import Graph

# Outlining as the default fast path is gated behind this env flag (read
# once at import): with REPRO_OUTLINE_HYBRID=1, ``color`` transparently
# routes through ``color_outlined_hybrid``. Programmatic callers toggle it
# after import via ``set_outline_default`` (mirrors ``ipgc.set_force_hub``)
# instead of mutating os.environ.
_OUTLINE_ENV = os.environ.get("REPRO_OUTLINE_HYBRID", "0") == "1"
_outline_override: bool | None = None


def set_outline_default(value: bool | None) -> None:
    """Override (or with ``None`` reset) the outline-by-default routing."""
    global _outline_override
    _outline_override = value


def outline_default() -> bool:
    return _OUTLINE_ENV if _outline_override is None else _outline_override


@contextlib.contextmanager
def outlined(value: bool | None):
    """Scoped outline-by-default override — the context-manager form of
    ``set_outline_default`` (restores the *previous* override on exit,
    including the no-override ``None`` state), so callers never leak the
    toggle across tests or benchmark cells::

        with engine.outlined(True):
            r = color(g)          # routes through the outlined Pipe
    """
    global _outline_override
    prev = _outline_override
    set_outline_default(value)
    try:
        yield
    finally:
        _outline_override = prev


@dataclasses.dataclass
class ColoringResult:
    colors: np.ndarray          # [N] final colors (>= 0 everywhere)
    n_colors: int
    iterations: int
    mode_trace: str             # 'D'/'S' per iteration
    counts: list[int]           # worklist size per host dispatch: one entry
    #                             per iteration for the host loop, one per
    #                             while_loop chunk for the outlined engine
    tti: list[float]            # wall seconds, same granularity as counts
    total_seconds: float
    host_dispatches: int = 0    # device-program launches the host issued
    # dist regime only (DESIGN.md §13): per-iteration exchange-path trace
    # ('d' dense, 'b' packed-boundary, 'm' mixed within a two-phase
    # iteration) and the modeled bytes each iteration moved per device
    exchange_trace: str = ""
    exchange_bytes: list = dataclasses.field(default_factory=list)
    # host regime only (empty elsewhere): per 'S' of mode_trace, the
    # adjacency entries of the rows the sparse step ran (live) and the
    # entries it gathered to do so, padding included (ipgc.sparse_slots);
    # per 'D' the same of the dense step (ipgc.dense_slots)
    sparse_entries: list = dataclasses.field(default_factory=list)
    sparse_slots: list = dataclasses.field(default_factory=list)
    dense_entries: list = dataclasses.field(default_factory=list)
    dense_slots: list = dataclasses.field(default_factory=list)


def resolve_plan(g, layout):
    """Resolve an engine-level ``layout=`` argument to a static
    ``LayoutPlan`` (DESIGN.md §8).

    ``None`` -> the plan the graph was assembled under. A kind string
    re-dispatches *execution* on the same arrays (every assembly keeps
    CSR complete and ELL+tail complete, so flipping e.g. an ell-tail
    graph to ``"csr-segment"`` execution — or back — is always sound);
    an explicit ``LayoutPlan`` is passed through. This is the layout
    analogue of ``algo=``: the resolved plan rides the prepared graph's
    static fields, so every step cache keys on it for free.
    """
    from repro.graphs.layout import LAYOUT_KINDS, LayoutPlan
    plan = getattr(g, "layout", None)
    if layout is None:
        return plan
    if isinstance(layout, LayoutPlan):
        return layout
    if layout not in LAYOUT_KINDS:
        raise ValueError(f"unknown layout {layout!r}; valid: "
                         f"{LAYOUT_KINDS} (or a LayoutPlan)")
    return dataclasses.replace(plan or LayoutPlan(), kind=layout)


def adaptive_window(g: Graph, *, lo: int = 32, hi: int = 128) -> int:
    """Color-window heuristic (beyond-paper optimisation, EXPERIMENTS.md
    §Perf): mex(v) <= deg(v), and IPGC's chromatic number tracks the
    *typical* degree, so a window ~2x the median degree covers almost all
    assignments in one pass while hub nodes advance their base. Cuts the
    O(C*W) per-iteration mex term up to 4x on low-degree graphs.

    Degenerate histograms clamp cleanly (tests/test_policy.py): a graph
    with no nodes has no median — return ``lo``; an all-hub graph's
    median blows past the window budget — clamp to ``hi``.
    """
    deg = np.asarray(g.arrays.degrees)
    if deg.size == 0:
        return lo
    med = int(np.median(deg))
    return int(min(max(-(-2 * (med + 1) // 32) * 32, lo), hi))


def color(
    g: Graph | ipgc.IPGCGraph,
    *,
    mode: str = "hybrid",
    algo: str | object = "ipgc",  # registry name or Algorithm instance
    h: float = 0.6,
    window: int | str = "auto",   # paper-faithful: 128 (EXPERIMENTS §Perf A)
    impl: str = "jnp",
    bucket_ratio: int = 2,        # paper-faithful: 4

    max_iter: int = 10_000,
    priority: str = "hash",
    policy: Policy | None = None,
    collect_tti: bool = False,
    fused: bool | None = None,    # one-gather fused steps; None = the
    #                               dispatched engine's default (host loop
    #                               False, outlined per backend, dist True)
    outline: bool | None = None,  # None -> set_outline_default()/env default
    n_shards: int | None = None,  # dist-* modes: shard count (None = all)
    exchange: str = "dense",      # dist-* modes: color publication path —
    #                               "dense" | "boundary" | "auto" (§13)
    layout: "str | object | None" = None,  # LayoutPlan / kind; None = g's plan
    tile_rows: "int | str | None" = "auto",  # Pallas row-tile height; "auto"
    #                               consults the persistent tuner
    #                               (kernels/tune.py) per layout kind
    trace=None,                   # True / obs.Trace: return a RunReport
    #                               (telemetry; DESIGN.md §12) instead of
    #                               the bare ColoringResult
) -> ColoringResult:
    # thin dispatcher: translate the legacy keyword surface into an
    # ExecutionSpec and run it on the process-default session (the one
    # keyed compile cache shared by all three regimes — DESIGN.md §9).
    # lazy import: repro.exec imports this module at import time
    from repro.exec import default_session, spec_for
    spec = spec_for(mode=mode, algo=algo, h=h, window=window, impl=impl,
                    bucket_ratio=bucket_ratio, max_iter=max_iter,
                    priority=priority, fused=fused, outline=outline,
                    n_shards=n_shards, layout=layout, tile_rows=tile_rows,
                    exchange=exchange)
    return default_session().run(spec, g, policy=policy,
                                 collect_tti=collect_tti, trace=trace)


# ---------------------------------------------------------------------------
# device-resident hybrid Pipe (iteration outlining with bucket exits)
# ---------------------------------------------------------------------------


def color_outlined_hybrid(
    g: Graph | ipgc.IPGCGraph,
    *,
    mode: str = "hybrid",
    algo: str | object = "ipgc",
    h: float = 0.6,
    window: int | str = "auto",
    impl: str = "jnp",
    bucket_ratio: int = 2,
    max_iter: int = 10_000,
    priority: str = "hash",
    policy: Policy | None = None,
    collect_tti: bool = False,
    fused: bool | None = None,
    layout: "str | object | None" = None,
    tile_rows: "int | str | None" = "auto",
    trace=None,
) -> ColoringResult:
    """Device-resident hybrid Pipe: ~O(#buckets) host dispatches total.

    Iteration-for-iteration equivalent to the host-loop ``color`` with the
    same ``fused`` setting and a fixed-H policy: within a chunk at bucket
    ``caps[i]`` the count stays in ``(caps[i+1], caps[i]]``, so the host
    loop would have picked the same bucket, and the on-device
    ``count > threshold`` cond is the same comparison the host policy makes.
    The H flip therefore happens *on-device* mid-chunk; the host re-enters
    only to re-dispatch at the next static capacity (``tti``/``counts`` are
    recorded per chunk, and ``mode_trace`` is reconstructed per chunk from
    the on-device D/S trip counters — exact for monotone policies).

    AutoTuned policies are supported via their chunked observe hook: the
    threshold is refreshed between chunks, not between iterations.

    ``fused=None`` resolves per backend: the one-gather fused steps win
    where neighbour-gather bandwidth dominates (TPU), while their deferred
    resolve costs a few extra iterations — a bad trade on the CPU jnp path,
    where the forbidden-bitmap scatter dominates (DESIGN.md §5).

    Thin dispatcher over the unified session (DESIGN.md §9); the chunk
    program lives in ``repro.exec.session`` (jaxpr-identical move).
    """
    from repro.exec import ExecutionSpec, default_session
    spec = ExecutionSpec(
        regime="outlined", mode=mode, algo=algo, layout=layout, h=h,
        window=window, impl=impl, bucket_ratio=bucket_ratio,
        max_iter=max_iter, priority=priority, fused=fused,
        tile_rows=tile_rows)
    return default_session().run(spec, g, policy=policy,
                                 collect_tti=collect_tti, trace=trace)


def color_outlined(
    g: Graph,
    *,
    window: int | str = "auto",
    impl: str = "jnp",
    max_iter: int = 10_000,
    priority: str = "hash",
) -> ColoringResult:
    """IrGL "iteration outlining", dense-only degenerate form: the whole
    Pipe runs as ONE device program (``lax.while_loop`` over dense steps) —
    zero intermediate host round-trips, no capacity bucketing, no H policy.

    Kept as the minimal reference for the outlining idiom; the general
    engine is ``color_outlined_hybrid``, which adds the on-device H policy
    and exits to the host only at capacity-bucket boundaries.
    """
    if window == "auto":
        window = adaptive_window(g)
    ig = ipgc.prepare(g, priority=priority)
    n = ig.n_nodes
    t0 = time.perf_counter()

    def cond(state):
        _, _, wl, it = state
        return (wl.count > 0) & (it < max_iter)

    def body(state):
        colors, base, wl, it = state
        colors, base, wl = ipgc.dense_step(ig, colors, base, wl,
                                           window=window, impl=impl)
        return colors, base, wl, it + 1

    state = (ipgc.init_colors(n), jnp.zeros((n,), jnp.int32),
             full_worklist(n), jnp.zeros((), jnp.int32))
    colors, _, wl, it = jax.lax.while_loop(cond, body, state)
    colors = np.asarray(colors[:n])
    total = time.perf_counter() - t0
    iters = int(it)
    return ColoringResult(colors=colors, n_colors=int(colors.max()) + 1,
                          iterations=iters, mode_trace="O" * iters,
                          counts=[], tti=[], total_seconds=total,
                          host_dispatches=1)
