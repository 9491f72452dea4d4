"""The ``Algorithm`` protocol + registry — pluggable coloring engines.

The paper's hybrid persistent-worklist technique is a claim about the
*execution strategy* (topology-driven vs data-driven dispatch over a
persistent worklist), not about IPGC specifically. This module factors the
algorithm out of the engine so the same Pipe machinery — host loop,
chunked outlining, capacity-bucket ladder, ``Policy`` switching, sharded
``shard_map`` dispatch — drives any colorer that speaks the step contract.

The step contract (shared with the original IPGC steps, so the ``ipgc``
algorithm is bit-identical to the pre-subsystem engine):

    step(ig, colors, aux, wl, *, window, impl, force_hub, tile_rows)
        -> (colors, aux, wl)

  * ``ig``     — the prepared device graph (``ipgc.IPGCGraph``; every
                 registered algorithm reuses the ELL+COO-tail layout).
  * ``colors`` — int32[N+1] replicated color vector (slot N = PAD sentinel).
  * ``aux``    — algorithm-owned pytree threaded opaquely by the engine
                 (IPGC: int32[N] window bases; JPL: the int32[] round
                 counter). The engine never inspects it.
  * ``wl``     — the dual-representation persistent ``Worklist``. Every
                 step (dense AND sparse) must re-emit both representations
                 so mode switches stay free — the paper's invariant.

Dense steps sweep all N rows reading ``wl.mask``; sparse steps gather the
C-capacity ``wl.items``. Both must be shape-static and traceable inside
``lax.while_loop`` (the outlined engine runs them as chunk bodies).
``tile_rows`` is the static Pallas row-tile height resolved by the
Session from ``ExecutionSpec.tile_rows`` (kernels/tune.py); algorithms
without a Pallas tile grid accept and ignore it, exactly like JPL
ignores ``window``.

Shard-safety declaration contract (DESIGN.md §7): an algorithm that sets
``shard_safe=True`` promises its ``make_dist_steps`` returns shard_map'd
steps whose worklist state stays shard-local and whose only cross-shard
value is the color vector — the invariants the dist regime is built on.
Algorithms that cannot (yet) honor that declare ``shard_safe=False``
with a human-readable ``shard_unsafe_reason``; ``Session.run(
ExecutionSpec(regime="dist", algo=...), g)`` fails fast with that reason
rather than silently producing wrong colorings.

Registry semantics: algorithms register under a unique name at import time
(``repro.algos`` registers the three built-ins); ``get_algorithm`` accepts
a name or an ``Algorithm`` instance (passthrough), so
``ExecutionSpec.algo`` takes ``"ipgc" | "jpl" | "spec-greedy" | <instance>``.
Instances are frozen dataclasses — hashable, so they ride through ``jit``
static args (the outlined chunk is specialised per algorithm).
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

from repro.core import ipgc
from repro.core.worklist import full_worklist
from repro.graphs.csr import Graph


@dataclasses.dataclass(frozen=True)
class Algorithm:
    """Base protocol; concrete algorithms subclass and override."""

    name: str = "abstract"
    #: may this algorithm run under ``regime="dist"``?
    shard_safe: bool = False
    #: surfaced by the Session when the dist regime is requested anyway
    shard_unsafe_reason: str = ""
    #: may this algorithm run under ``Session.run_batch``? ``True``
    #: promises the step impls are batch-axis safe — shape-static jnp
    #: ops only, no host-side data-dependent control flow — AND that the
    #: dense-form step applied to an arbitrary active set reproduces the
    #: sparse-form step's state exactly (the dual-worklist invariant),
    #: so a vmapped dense-only lane is bit-identical to the host loop's
    #: per-iteration mode choice (DESIGN.md §9). Declared False by
    #: default with a reason, mirroring ``shard_safe``.
    batch_safe: bool = False
    #: surfaced by ``Session.run_batch`` when batching is requested anyway
    batch_unsafe_reason: str = ""
    #: tie-break priority fed to ``prepare`` when the caller passes None
    default_priority: str = "hash"
    #: does the ``window``/``base`` mex machinery apply? (JPL: no)
    uses_window: bool = True

    # --- graph preparation / state -----------------------------------------
    def prepare(self, g: Graph, *, priority: str | None = None, plan=None
                ) -> ipgc.IPGCGraph:
        """``plan`` is the static ``LayoutPlan`` to execute under
        (DESIGN.md §8); ``None`` uses the plan the graph was assembled
        with. The IPGC-family steps dispatch on ``plan.kind`` (the
        csr-segment edge-wise variants vs the ELL tile path); algorithms
        whose steps read the ELL arrays directly (JPL) run the ELL path
        under any plan — the assembly contract keeps ELL+tail complete
        for every kind, so that is always correct."""
        return ipgc.prepare(g, priority=priority or self.default_priority,
                            plan=plan)

    def init_state(self, ig: ipgc.IPGCGraph):
        """(colors, aux, wl) initial engine state."""
        raise NotImplementedError

    # --- steps -------------------------------------------------------------
    def step_impls(self, fused: bool):
        """(dense_impl, sparse_impl) — unjitted, traceable inside
        ``lax.while_loop`` (the outlined chunk body)."""
        raise NotImplementedError

    def step_fns(self, fused: bool):
        """(dense, sparse) jitted step pair for the host-loop Pipe: the
        ``ipgc.tallied`` forms of the step impls. Each also returns
        ``int32[2]``, the worklist count after it and the live entries
        of its rows, read back in one transfer."""
        raise NotImplementedError

    def dense_slots(self, ig: ipgc.IPGCGraph,
                    force_hub: bool | None) -> int:
        """Adjacency entries the dense step gathers, live or not
        (static). The default is the IPGC steps' rule
        (``ipgc.dense_slots``)."""
        return ipgc.dense_slots(ig, force_hub)

    def sparse_slots(self, ig: ipgc.IPGCGraph, capacity: int, live: int,
                     force_hub: bool | None) -> int:
        """Adjacency entries the sparse step gathers at worklist capacity
        ``capacity`` over rows that own ``live`` entries, live or not.
        The default is the IPGC steps' rule, packing included
        (``ipgc.sparse_slots``)."""
        return ipgc.sparse_slots(ig, capacity, live, force_hub)

    def resolve_fused(self, fused: bool | None, *, default: bool) -> bool:
        """Map the caller's ``fused`` request (None = engine default) to
        the semantics this algorithm actually runs. Algorithms with a
        single step family (JPL; spec-greedy is fused-only) pin it."""
        return default if fused is None else fused

    # --- distributed -------------------------------------------------------
    def make_dist_steps(self, ig_local: ipgc.IPGCGraph, mesh,
                        node_axes: tuple, *, window: int, fused: bool,
                        exchange: str = "dense", boundary=None,
                        thresh: int | None = None):
        """(dense_step, sparse_step) shard_map'd closures for the dist
        regime; only called when ``shard_safe``.
        ``exchange``/``boundary``/``thresh`` select the cross-shard color
        publication path (DESIGN.md §13): with ``exchange != "dense"``
        the returned steps take per-shard color *views* plus a static
        ``bcap`` kwarg and return an extra ``xstats`` output."""
        raise NotImplementedError(
            f"algorithm {self.name!r} is not shard-safe: "
            f"{self.shard_unsafe_reason or 'no distributed steps'}")

    # --- result post-processing -------------------------------------------
    def finalize(self, colors: np.ndarray) -> tuple[np.ndarray, int]:
        """(final colors, n_colors). The default is the IPGC contract —
        colors are already a dense-enough palette, report max+1 — kept
        bit-identical for ``ipgc``; palette-gapped algorithms (JPL's 2r /
        2r+1 classes) override with a compaction."""
        n_colors = int(colors.max()) + 1 if colors.size else 0
        return colors, n_colors

    def check_invariants(self, result, g: Graph | None = None) -> None:
        """Per-algorithm result invariants beyond plain validity; raises
        AssertionError. Shared baseline: the persistent active set never
        grows between host observations."""
        assert all(b <= a for a, b in zip(result.counts, result.counts[1:])), \
            f"{self.name}: worklist grew: {result.counts}"


def _compact_palette(colors: np.ndarray) -> tuple[np.ndarray, int]:
    """Remap the used colors to a dense 0..k-1 palette (validity-preserving
    relabeling; uncolored slots, if any, stay negative)."""
    used = np.unique(colors[colors >= 0])
    out = colors.copy()
    if used.size:
        out[colors >= 0] = np.searchsorted(used, colors[colors >= 0])
    return out, int(used.size)


def init_ipgc_state(ig: ipgc.IPGCGraph):
    """The IPGC-family state triple: sentinel-slot colors, per-node window
    bases, full worklist (shared by ``ipgc`` and ``spec-greedy``)."""
    n = ig.n_nodes
    return (ipgc.init_colors(n), jnp.zeros((n,), dtype=jnp.int32),
            full_worklist(n))


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

_REGISTRY: dict[str, Algorithm] = {}


def register(algo: Algorithm) -> Algorithm:
    """Register (or re-register, e.g. a tuned variant under a new name)."""
    if not algo.name or algo.name == "abstract":
        raise ValueError("algorithm must carry a concrete name")
    _REGISTRY[algo.name] = algo
    return algo


def algorithm_names() -> list[str]:
    return list(_REGISTRY)


def get_algorithm(algo: str | Algorithm) -> Algorithm:
    if isinstance(algo, Algorithm):
        return algo
    try:
        return _REGISTRY[algo]
    except KeyError:
        raise ValueError(
            f"unknown algorithm {algo!r}; registered: "
            f"{sorted(_REGISTRY)}") from None
