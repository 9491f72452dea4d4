"""``RunReport`` — the structured record of ONE coloring run
(DESIGN.md §12).

The paper's hybridization argument is an accounting argument: worklist
size, dense-vs-sparse switches, and per-iteration work decide which
regime wins. The quantities backing that argument historically lived in
scattered places — the result's mode-trace string, the trace-time
counter groups (``ipgc.LAUNCH_COUNTS``, ``ipgc.GATHER_COUNTS``,
``distributed.EXCHANGE_COUNTS``), ``Session.stats``, and per-dispatch
``Timer`` readings inside the drivers. A ``RunReport`` unifies them:

  * identity: regime / algorithm / graph / node count;
  * the full ``ColoringResult`` (colors, iterations, D/S mode trace,
    per-iteration live counts, host dispatches) with passthrough
    properties so a report quacks like the result it wraps;
  * per-iteration device-work profiles measured the same way the test
    suites assert them — ``jax.eval_shape`` of the unjitted step impls
    under counter scopes, so the numbers match ``measure_launches``
    bit-for-bit and no device code runs;
  * for the distributed regime: exchanges per iteration AND **bytes
    exchanged per iteration** — each ``color_psum`` moves one
    ``int32[N+1]`` delta per device, so ``bytes/iter = exchanges/iter
    x 4(N+1)``;
  * a time split: ``dispatch_seconds`` sums the per-dispatch timers,
    against the run's ``total_seconds``;
  * a cache snapshot (``CacheStats.as_dict()`` of the owning session at
    report time, plus this run's delta).

``to_json()`` emits the JSON-safe schema ``examples/color_suite.py
--json`` prints (colors array and live trace excluded; pass
``include_chrome=True`` to embed ``trace.to_chrome()``).

This module is pure data assembly — it imports nothing from the engine
at module scope, so the counter-owning modules can import ``repro.obs``
freely.
"""
from __future__ import annotations

import dataclasses
import json


def totals_from_trace(mode_trace: str, per_iter: dict) -> dict:
    """Whole-run totals from the D/S trace x per-iteration profiles.

    ``per_iter`` maps ``"dense"``/``"sparse"`` -> {kind: count per
    iteration}; the result sums each kind over the actual iteration mix.
    """
    nd = mode_trace.count("D")
    ns = mode_trace.count("S")
    dense = per_iter.get("dense", {}) or {}
    sparse = per_iter.get("sparse", {}) or {}
    keys = sorted(set(dense) | set(sparse))
    return {k: nd * dense.get(k, 0) + ns * sparse.get(k, 0) for k in keys}


def dense_exchange_bytes(n_global: int) -> int:
    """Per-device bytes of ONE ``color_psum``: the psum'd delta is an
    ``int32[n_global + 1]`` (the +1 is the gather-sentinel slot) —
    edge-count independent, the property Bogle & Slota's
    bytes-per-iteration accounting makes auditable."""
    return 4 * (n_global + 1)


def dense_swap_bytes(n_global: int) -> int:
    """Per-device bytes of ONE ``dense_swap`` fallback: the tiled
    all-gather of the disjoint owned ``int32`` blocks reassembles
    exactly ``n_global`` slots (no sentinel — slot n stays local)."""
    return 4 * n_global


def packed_exchange_bytes(bcap: int, n_shards: int) -> int:
    """Per-device bytes of ONE ``boundary_pack`` exchange at capacity
    ``bcap``: two ``int32[bcap]`` all-gathers ((id, color) planes), each
    landing ``bcap`` slots per shard on every device."""
    return 8 * bcap * n_shards


def exchange_section(per_iter: dict, n_global: int, mode_trace: str, *,
                     exchange: str = "dense", n_shards: int = 1,
                     exchange_trace: str = "",
                     exchange_bytes=()) -> dict:
    """The distributed regime's communication accounting, path-aware
    (DESIGN.md §13).

    ``per_iter`` maps ``"dense"``/``"sparse"`` -> the full trace-time
    exchange-kind counts of one step (``color_psum`` on the dense
    exchange path; ``boundary_pack`` AND ``dense_swap`` on the boundary
    paths — both ``lax.cond`` branches trace, so both appear; which one
    RAN each iteration is the runtime ``exchange_trace``/``bytes``
    ledger the driver recorded).
    """
    bytes_per_iter = [int(b) for b in exchange_bytes]
    if exchange == "dense" and not bytes_per_iter:
        payload = dense_exchange_bytes(n_global)
        bytes_per_iter = [per_iter.get(
            "dense" if m == "D" else "sparse", {}).get("color_psum", 0)
            * payload for m in mode_trace]
    # executed exchanges: each publish runs exactly ONE of its traced
    # branches, so count publishes (color_psum on the dense path,
    # boundary_pack == dense_swap == publish sites on the boundary paths)
    def _epi(m):
        d = per_iter.get("dense" if m == "D" else "sparse", {})
        return d.get("color_psum", 0) or d.get("boundary_pack", 0)

    total = sum(_epi(m) for m in mode_trace)
    return {
        "exchange": exchange,
        "per_iter": per_iter,
        "payload_bytes": {
            "color_psum": dense_exchange_bytes(n_global),
            "dense_swap": dense_swap_bytes(n_global),
            "packed_per_slot": 8 * n_shards,   # x bcap = boundary_pack
        },
        "trace": exchange_trace,
        "bytes_per_iter": bytes_per_iter,
        "total_bytes": sum(bytes_per_iter),
        "total": total,
    }


@dataclasses.dataclass
class RunReport:
    """Everything one run did, in one place. See module docstring."""

    #: dispatch regime ("host" / "outlined" / "dist" / "batch" /
    #: "stream" — the latter two are service-level aggregates)
    regime: str = ""
    algo: str = ""
    graph: str = ""
    n_nodes: int = 0
    n_colors: int = 0
    iterations: int = 0
    mode_trace: str = ""
    host_dispatches: int = 0
    #: live worklist size entering each host dispatch
    counts: list = dataclasses.field(default_factory=list)
    #: total seconds / dispatch seconds / dispatches
    timing: dict = dataclasses.field(default_factory=dict)
    #: {"per_iter": {"dense": {...}, "sparse": {...}}, "total": {...}}
    launches: dict = dataclasses.field(default_factory=dict)
    #: same shape, counting mutable-color ELL gathers
    gathers: dict = dataclasses.field(default_factory=dict)
    #: dist only (None elsewhere): see ``exchange_section``
    exchanges: "dict | None" = None
    #: owning session's CacheStats snapshot + this run's delta
    cache: dict = dataclasses.field(default_factory=dict)
    #: the wrapped ColoringResult (None for service-level reports)
    result: object = None
    #: the live Trace, when the run was traced
    trace: object = None
    #: regime-specific additions (stream counters, batch lane stats...)
    extra: dict = dataclasses.field(default_factory=dict)

    # -- ColoringResult passthroughs -----------------------------------------

    @property
    def colors(self):
        return getattr(self.result, "colors", None)

    @property
    def tti(self):
        return getattr(self.result, "tti", [])

    @property
    def total_seconds(self) -> float:
        return self.timing.get("total_seconds", 0.0)

    # -- export --------------------------------------------------------------

    def to_json(self, *, include_chrome: bool = False) -> dict:
        """The JSON-safe report schema (DESIGN.md §12). Excludes the
        colors array and the live trace object; ``include_chrome``
        embeds the Chrome-trace export under ``"chrome_trace"``."""
        out = {
            "regime": self.regime, "algo": self.algo, "graph": self.graph,
            "n_nodes": int(self.n_nodes), "n_colors": int(self.n_colors),
            "iterations": int(self.iterations),
            "mode_trace": self.mode_trace,
            "host_dispatches": int(self.host_dispatches),
            "counts": [int(c) for c in self.counts],
            "timing": dict(self.timing),
            "launches": self.launches, "gathers": self.gathers,
            "exchanges": self.exchanges, "cache": dict(self.cache),
            "extra": self.extra,
        }
        if include_chrome and self.trace is not None:
            out["chrome_trace"] = self.trace.to_chrome()
        json.dumps(out)   # loud schema guarantee: always serialisable
        return out
