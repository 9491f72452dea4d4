"""``ExecutionSpec`` — the frozen description of HOW a coloring runs.

The paper's persistent-worklist Pipe runs in three dispatch regimes
(DESIGN.md §9): the host loop, the device-resident outlined chunks, and
the sharded ``shard_map`` driver. An ``ExecutionSpec`` freezes the full
static configuration of a run once, so it can be reused across requests:

  regime x mode x algo x layout x policy knobs x fused knob

Every field is hashable (``algo`` may be an ``Algorithm`` instance and
``layout`` a ``LayoutPlan`` — both frozen dataclasses), so a spec rides
jit static arguments and dict keys directly. ``Session`` (session.py)
keys its unified compile cache on ``spec.static_key() x`` the graph's
static fields. ``Session.run(spec, g)`` is the one way to color a graph,
and ``regime`` is the one place a dispatch regime is chosen.

Runtime-only inputs — a caller-supplied ``Policy`` instance (stateful,
e.g. ``AutoTuned``), ``collect_tti``, a custom mesh — are deliberately
NOT part of the spec: they never key a compiled artifact.
"""
from __future__ import annotations

import dataclasses

REGIMES = ("host", "outlined", "dist")


@dataclasses.dataclass(frozen=True)
class ExecutionSpec:
    """Static execution configuration shared by every dispatch regime."""

    #: dispatch regime: "host" (per-iteration host loop), "outlined"
    #: (device-resident lax.while_loop chunks), "dist" (sharded Pipe)
    regime: str = "host"
    #: policy mode ("hybrid" / "topology" / "data" / "hybrid-auto"); the
    #: sharded Pipe is selected by ``regime="dist"``, not by the mode
    mode: str = "hybrid"
    #: registry name or frozen Algorithm instance
    algo: "str | object" = "ipgc"
    #: engine-level LayoutPlan override (kind string / LayoutPlan / None)
    layout: "str | object | None" = None
    h: float = 0.6
    window: "int | str" = "auto"
    impl: str = "jnp"
    bucket_ratio: int = 2
    max_iter: int = 10_000
    priority: str = "hash"
    #: step family; None resolves per regime via Algorithm.resolve_fused
    fused: "bool | None" = None
    #: dist regime only: shard count (None = all local devices)
    n_shards: "int | None" = None
    #: dist regime only: degree-balance the partition
    balance: bool = True
    #: Pallas row-tile height; "auto" consults kernels/tune.py per
    #: (backend, layout kind, dtype), an int pins it, None = kernel default
    tile_rows: "int | str | None" = "auto"
    #: dist regime only: cross-shard color publication path —
    #: "dense" (full-vector psum), "boundary" (packed changed-boundary
    #: buffers whenever they fit), "auto" (packed below the byte
    #: break-even threshold; policy.exchange_threshold). Static: it keys
    #: the compiled shard_map steps (DESIGN.md §13).
    exchange: str = "dense"

    def __post_init__(self):
        if self.regime not in REGIMES:
            raise ValueError(
                f"unknown regime {self.regime!r}; valid: {REGIMES}")
        if self.mode.startswith("dist-"):
            raise ValueError(
                f"mode {self.mode!r}: the sharded Pipe is a regime, not a "
                f"mode — pass regime='dist', mode={self.mode[5:]!r}")
        if self.exchange not in ("dense", "boundary", "auto"):
            raise ValueError(
                f"unknown exchange {self.exchange!r}; valid: "
                "('dense', 'boundary', 'auto')")

    # -- resolution helpers --------------------------------------------------

    def resolved_algo(self):
        from repro.algos import get_algorithm
        return get_algorithm(self.algo)

    def validate_batchable(self):
        """Check this spec can run lane-batched — the shared admission
        contract of ``Session.run_batch`` and the streaming service
        (exec/batch.py, serve/stream.py; DESIGN.md §§9+11). Returns the
        resolved Algorithm so callers don't resolve twice.

        Lane batching replays host-regime semantics per lane with the
        D/S trace reconstructed from per-lane counts against a monotone
        policy threshold, via vmapped jnp step impls — every knob that
        breaks one of those legs fails loudly here.
        """
        alg = self.resolved_algo()
        if self.regime != "host":
            raise ValueError(
                f"lane-batched execution replays host-regime semantics "
                f"(fused default, window/policy resolution) and would "
                f"silently ignore the {self.regime!r} regime's knobs; "
                "pass a spec with regime='host'")
        if not alg.batch_safe:
            raise ValueError(
                f"algorithm {alg.name!r} is not batch-safe: "
                f"{alg.batch_unsafe_reason or 'no declared batch contract'}")
        if self.impl != "jnp":
            raise ValueError(
                "lane-batched execution requires impl='jnp' (the Pallas "
                "kernels are not audited under vmap)")
        if self.mode == "hybrid-auto":
            raise ValueError(
                f"lane-batched execution cannot replay mode {self.mode!r} "
                "per lane: the batched Pipe needs a monotone per-lane "
                "count threshold (hybrid / topology / data)")
        return alg

    def static_key(self) -> tuple:
        """The spec half of the unified Session cache key (DESIGN.md §9).

        The algorithm joins as its resolved *instance* (frozen dataclass
        equality — a re-registered variant under the same name must not
        share cached artifacts) and ``layout`` as given (kind string or
        frozen ``LayoutPlan``, both hashable).
        """
        return (self.regime, self.mode, self.resolved_algo(), self.layout,
                self.h, self.window, self.impl, self.bucket_ratio,
                self.max_iter, self.priority, self.fused, self.n_shards,
                self.balance, self.tile_rows, self.exchange)
