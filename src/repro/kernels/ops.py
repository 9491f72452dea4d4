"""Jit'd public wrappers around the Pallas kernels.

On a TPU backend the kernels compile natively; on any other backend they
execute in interpret mode (``interpret()``) so the engine's
``impl="pallas"`` path stays testable end-to-end. CPU *benchmarks* use the jnp reference path (``impl="jnp"``)
— interpret mode measures Python, not the kernel.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.compact import compact_pallas
from repro.kernels.conflict import conflict_pallas
from repro.kernels.frontier import frontier_probe_pallas
from repro.kernels.fused_compact import fused_compact_pallas
from repro.kernels.fused_step import fused_step_pallas
from repro.kernels.jpl_prio import jpl_extrema_pallas
from repro.kernels.mex_window import mex_window_pallas

DEFAULT_TILE_ROWS = 32


def _tile(tile_rows: "int | None") -> int:
    return DEFAULT_TILE_ROWS if tile_rows is None else tile_rows


def interpret() -> bool:
    """Whether the Pallas kernels run in interpret mode: everywhere but
    on a TPU backend, where they always compile natively. The one place
    this is decided (the autotuner and the kernel bench ask here)."""
    return jax.default_backend() != "tpu"


@partial(jax.jit, static_argnames=("window", "tile_rows"))
def mex_window(nc: jax.Array, base: jax.Array, extra_forb: jax.Array,
               window: int, tile_rows: "int | None" = None
               ) -> tuple[jax.Array, jax.Array]:
    first = mex_window_pallas(nc, base, extra_forb, window,
                              tile_rows=_tile(tile_rows),
                              interpret=interpret())
    return first, first >= 0


@partial(jax.jit, static_argnames=("tile_rows",))
def conflict(nc: jax.Array, npr: jax.Array, nbr_ids: jax.Array,
             cu: jax.Array, pu: jax.Array, ids: jax.Array,
             tile_rows: "int | None" = None) -> jax.Array:
    return conflict_pallas(nc, npr, nbr_ids, cu, pu, ids,
                           tile_rows=_tile(tile_rows),
                           interpret=interpret())


@partial(jax.jit, static_argnames=("window", "tile_rows"))
def fused_step(nc: jax.Array, npr: jax.Array, nbr_ids: jax.Array,
               base: jax.Array, cu: jax.Array, pu: jax.Array,
               ids: jax.Array, pending: jax.Array, extra_forb: jax.Array,
               window: int, tile_rows: "int | None" = None
               ) -> tuple[jax.Array, jax.Array]:
    """Fused resolve+assign: one neighbour-color tile feeds both the
    conflict check and the windowed mex (see kernels/fused_step.py)."""
    return fused_step_pallas(nc, npr, nbr_ids, base, cu, pu, ids, pending,
                             extra_forb, window, tile_rows=_tile(tile_rows),
                             interpret=interpret())


def fused_compact(nc: jax.Array, npr: jax.Array, nbr_ids: jax.Array,
                  base: jax.Array, cu: jax.Array, pu: jax.Array,
                  ids: jax.Array, active: jax.Array, pending: jax.Array,
                  extra_forb: "jax.Array | None",
                  hub_lose: "jax.Array | None", window: int, *,
                  tile_rows: "int | None" = None
                  ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Fused step kernel with new-color/base selection (DESIGN.md §10);
    the caller compacts the survivors.

    Not independently jitted: the optional hub operands change the
    traced signature, and every caller (the ipgc step impls) already
    sits under its own jit with ``window``/``tile_rows`` static.
    """
    return fused_compact_pallas(nc, npr, nbr_ids, base, cu, pu, ids,
                                active, pending, extra_forb, hub_lose,
                                window, tile_rows=_tile(tile_rows),
                                interpret=interpret())


@partial(jax.jit, static_argnames=("tile_rows",))
def jpl_extrema(npr: jax.Array, tile_rows: "int | None" = None
                ) -> tuple[jax.Array, jax.Array]:
    """Per-row (max, masked min) of active-neighbour JPL priorities (the
    independent-set membership compare; see kernels/jpl_prio.py)."""
    return jpl_extrema_pallas(npr, tile_rows=_tile(tile_rows),
                              interpret=interpret())


@jax.jit
def compact(mask: jax.Array) -> tuple[jax.Array, jax.Array]:
    return compact_pallas(mask, interpret=interpret())


@jax.jit
def frontier_probe(nbr_in_frontier: jax.Array,
                   unvisited: jax.Array) -> jax.Array:
    return frontier_probe_pallas(nbr_in_frontier, unvisited,
                                 interpret=interpret())
