"""Persistent tile-size autotuner for the Pallas kernel paths
(DESIGN.md §10).

``tile_rows`` (the row-tile height of every ELL-path kernel grid) is a
pure performance knob: any value yields bit-identical results (the
parity suites sweep it), but the right value depends on the backend, the
execution layout kind, and the dtype — pure-ell graphs amortise fewer,
taller tiles; hub-split rows carry the extra (TILE_R, W) hub bitmap
through VMEM and prefer shorter ones. Rather than hard-coding the 32-row
default everywhere, the engine asks this module at Session prepare time:

  * first use of a ``(backend, layout kind, dtype)`` triple sweeps the
    candidate tile heights over a small synthetic workload shaped like
    that kind (hub operands on for the hub kinds) and records the winner;
  * winners persist in an on-disk JSON cache keyed like the Session
    compile cache (one entry per triple, schema below; default
    ``<checkout>/.cache/tune.json``, see repro/caches.py), so later
    processes skip the sweep;
  * the chosen ``tile_rows`` rides ``ExecutionSpec.static_key()`` — it is
    a static jit argument all the way down, so two runs tuned to
    different tiles can never collide in a compile cache.

Cache file format (DESIGN.md §10): ``{"version": 1, "entries":
{"<backend>/<kind>/<dtype>": {"tile_rows": int, "micros": {"<cand>":
float}}}}``. Corrupt or version-mismatched files are discarded and
re-swept, never trusted.

``csr-segment`` has no Pallas fused kernel (the edge-parallel core is
jnp segment ops — see kernels/csr_segment.py), so its entry records
``tile_rows: None`` and resolution falls through to the default.
"""
from __future__ import annotations

import dataclasses
import json
import os
import time

import numpy as np

from repro.caches import CACHE_ROOT

CACHE_ENV = "REPRO_TUNE_CACHE"
CACHE_VERSION = 1
CANDIDATES = (8, 32, 128)
DEFAULT_TILE_ROWS = 32
# sweep workload shape: small enough to tune in well under a second per
# kind, tall enough that the grid actually iterates for every candidate
_SWEEP_ROWS = 256
_SWEEP_K = 16
_SWEEP_WINDOW = 128
_SWEEP_REPS = 3

ELL_KINDS = ("pure-ell", "ell-tail", "hub-split")

_MEMO: dict[str, "TileConfig"] = {}


@dataclasses.dataclass(frozen=True)
class TileConfig:
    """One tuned entry: the winning tile height plus the sweep timings
    (microseconds per candidate) that justified it."""
    tile_rows: int | None
    micros: dict[str, float] = dataclasses.field(default_factory=dict)


def cache_path() -> str:
    override = os.environ.get(CACHE_ENV)
    if override:
        return override
    return str(CACHE_ROOT / "tune.json")


def tune_key(backend: str, kind: str, dtype: str = "int32") -> str:
    return f"{backend}/{kind}/{dtype}"


def _load() -> dict:
    try:
        with open(cache_path()) as f:
            data = json.load(f)
        if not isinstance(data, dict) or data.get("version") != CACHE_VERSION:
            return {}
        entries = data.get("entries")
        return entries if isinstance(entries, dict) else {}
    except (OSError, ValueError):
        return {}


def _store(entries: dict) -> None:
    path = cache_path()
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            json.dump({"version": CACHE_VERSION, "entries": entries}, f,
                      indent=1, sort_keys=True)
    except OSError:
        pass  # cache is an optimisation; a read-only home just re-sweeps


def _sweep_case(kind: str, rng: np.random.Generator):
    import jax.numpy as jnp
    r, k, w = _SWEEP_ROWS, _SWEEP_K, _SWEEP_WINDOW
    nc = jnp.asarray(rng.integers(-2, 60, size=(r, k)).astype(np.int32))
    npr = jnp.asarray(rng.integers(-1, 100, size=(r, k)).astype(np.int32))
    nid = jnp.asarray(rng.integers(0, r + 1, size=(r, k)).astype(np.int32))
    base = jnp.zeros((r,), jnp.int32)
    cu = jnp.asarray(rng.integers(-2, 60, size=(r,)).astype(np.int32))
    pu = jnp.asarray(rng.integers(0, 100, size=(r,)).astype(np.int32))
    ids = jnp.arange(r, dtype=jnp.int32)
    active = jnp.asarray(rng.random(r) < 0.8)
    pending = active & (cu >= 0)
    if kind in ("ell-tail", "hub-split"):
        extra = jnp.asarray(rng.random((r, w)) < 0.1)
        hub_lose = jnp.asarray(rng.random(r) < 0.05)
    else:
        extra = hub_lose = None
    return nc, npr, nid, base, cu, pu, ids, active, pending, extra, hub_lose


def _time_candidate(case, tile_rows: int) -> float:
    """Median warm wall-micros of the fused step kernel at this tile
    height, measured through ``jit`` so tracing cost (identical for every
    candidate, and amortised by the step jits in real runs) stays out of
    the timed region — un-jitted timings are all trace overhead and rank
    the candidates by noise."""
    import jax
    from repro.kernels.fused_compact import fused_compact_pallas
    from repro.kernels.ops import interpret

    with_hub = case[-1] is not None
    operands = [a for a in case if a is not None]

    @jax.jit
    def call(*arrs):
        if with_hub:
            extra, hub_lose = arrs[-2:]
            arrs = arrs[:-2]
        else:
            extra = hub_lose = None
        return fused_compact_pallas(*arrs, extra, hub_lose, _SWEEP_WINDOW,
                                    tile_rows=tile_rows,
                                    interpret=interpret())

    jax.block_until_ready(call(*operands))   # compile
    jax.block_until_ready(call(*operands))   # warm
    times = []
    for _ in range(_SWEEP_REPS):
        t0 = time.perf_counter()
        jax.block_until_ready(call(*operands))
        times.append((time.perf_counter() - t0) * 1e6)
    return float(np.median(times))


def sweep(kind: str, *, candidates: "tuple[int, ...]" = CANDIDATES
          ) -> TileConfig:
    """Time every candidate tile height on a ``kind``-shaped workload.

    Traced runs see the sweep: with an ambient ``obs.Trace`` installed
    (obs/trace.py), the sweep records a ``tune.sweep`` span with one
    ``tune.candidate`` child per tile height carrying its measured
    microseconds — so a cold first run's tuning cost is attributable in
    the Chrome-trace export instead of vanishing into "prepare time".
    """
    from repro.obs import trace as obs_trace

    if kind not in ELL_KINDS:
        return TileConfig(tile_rows=None)
    rng = np.random.default_rng(0)
    case = _sweep_case(kind, rng)
    micros = {}
    with obs_trace.maybe_span("tune.sweep", kind=kind,
                              candidates=list(candidates)):
        for c in candidates:
            with obs_trace.maybe_span("tune.candidate", kind=kind,
                                      tile_rows=c) as sp:
                micros[str(c)] = _time_candidate(case, c)
                if sp is not None:
                    sp.attrs["micros"] = micros[str(c)]
    best = min(micros, key=micros.get)
    return TileConfig(tile_rows=int(best), micros=micros)


def get_tile_config(kind: str, *, dtype: str = "int32") -> TileConfig:
    """Tuned config for (current backend, layout kind, dtype) — memoised
    in-process, persisted on disk, swept on first miss."""
    import jax
    key = tune_key(jax.default_backend(), kind, dtype)
    if key in _MEMO:
        return _MEMO[key]
    entries = _load()
    hit = entries.get(key)
    if isinstance(hit, dict) and "tile_rows" in hit:
        tr = hit["tile_rows"]
        if tr is None or isinstance(tr, int):
            cfg = TileConfig(tile_rows=tr, micros=dict(hit.get("micros", {})))
            _MEMO[key] = cfg
            return cfg
    cfg = sweep(kind)
    _MEMO[key] = cfg
    entries[key] = dataclasses.asdict(cfg)
    _store(entries)
    return cfg


def resolve_tile_rows(spec_tile: "int | str | None", kind: str,
                      impl: str) -> int | None:
    """Resolve ``ExecutionSpec.tile_rows`` to the static step argument.

    An explicit int is always honored (and always in the jit key). The
    ``"auto"``/None policy consults the tuner only on the Pallas impl for
    an ELL-family kind — the jnp path has no tile grid, so auto resolves
    to None there and cannot fragment its jit caches.
    """
    if isinstance(spec_tile, int):
        return spec_tile
    if impl != "pallas" or kind not in ELL_KINDS:
        return None
    return get_tile_config(kind).tile_rows


def clear_memo() -> None:
    """Drop the in-process memo (tests re-point the cache file)."""
    _MEMO.clear()
