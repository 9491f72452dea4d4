"""``correct`` on the CPU at a small size: sound runs pass, and the
control and each fault a cell can have fail. The faults break the timed
path underneath the harness: a step that returns its state unchanged,
half the answers left out, and an answer altered where it is produced
(the top color class recolored 0: a node took its color because a
neighbour held 0)."""
import jax
import numpy as np
import pytest

from bench.cells import run_cell
from bench.tests._tiny import tiny_run
from repro.algos import base
from repro.core import ipgc

CELLS = ["kron_g500.solo", "europe_osm.solo"]


@pytest.mark.parametrize("cell", CELLS)
def test_sound(cell):
    run = run_cell(tiny_run(cell))
    assert run.correct, run.checks
    assert run.attempted >= 1 and run.failed == 0


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails(cell):
    run = run_cell(tiny_run(cell), control=True)
    assert not run.correct
    assert run.checks["uncolored_nodes"][0] > 0


@pytest.fixture
def unchanged_step(monkeypatch):
    def same(ig, colors, aux, wl, **kw):
        return colors, aux, wl

    jax.clear_caches()
    for name in ("dense_step_impl", "sparse_step_impl",
                 "fused_dense_step_impl", "fused_sparse_step_impl"):
        monkeypatch.setattr(ipgc, name, same)
    fn = jax.jit(same, static_argnames=("window", "impl", "force_hub",
                                        "tile_rows"))
    monkeypatch.setattr(ipgc, "step_fns", lambda fused: (fn, fn))
    yield
    jax.clear_caches()


@pytest.mark.parametrize("cell", CELLS)
def test_unchanged_state_fails(cell, unchanged_step):
    run = run_cell(tiny_run(cell))
    assert not run.correct


@pytest.mark.parametrize("cell", CELLS)
def test_half_left_out_fails(cell, monkeypatch):
    real = base.Algorithm.finalize

    def finalize(self, colors):
        out, k = real(self, colors)
        out = out.copy()
        out[len(out) // 2:] = -1
        return out, k

    monkeypatch.setattr(base.Algorithm, "finalize", finalize)
    run = run_cell(tiny_run(cell))
    assert not run.correct


@pytest.mark.parametrize("cell", CELLS)
def test_altered_answer_fails(cell, monkeypatch):
    real = base.Algorithm.finalize

    def finalize(self, colors):
        out, k = real(self, colors)
        out = np.where(out == out.max(), 0, out)
        return out, k

    monkeypatch.setattr(base.Algorithm, "finalize", finalize)
    run = run_cell(tiny_run(cell))
    assert not run.correct
    assert run.checks["conflict_edges"][0] > 0
