"""The two readers of the sparse steps' counter, ``step.sparse_fill`` and
``kernel.sparse_roofline``, on hand-built runs, and the counter on a
small run of each cell through the real runner."""
from types import SimpleNamespace as NS

import pytest

from bench import harness, trace_reduce as tr
from bench.cells import run_cell
from bench.tests._tiny import tiny_run

CELLS = ["kron_g500.solo", "europe_osm.solo"]
V5E = {"kind": "TPU v5 lite"}


def fill():
    return harness.load_reader("step.sparse_fill")


def roofline():
    return harness.load_reader("kernel.sparse_roofline")


def timed(seconds):
    """A reduction whose sparse-step programs ran ``seconds`` in all."""
    return NS(program_time=lambda pats: (1.0, seconds)
              if "sparse_step_impl" in pats else None)


def test_fill_sums_live_over_slots():
    run = NS(traffic={"kind": "solo"},
             results=[NS(sparse_entries=[30, 10], sparse_slots=[100, 100]),
                      NS(sparse_entries=[20], sparse_slots=[200])])
    assert fill().read(run) == pytest.approx(100 * 60 / 400)


@pytest.mark.parametrize("results", [
    [NS(mode_trace="DD", sparse_entries=[], sparse_slots=[])],
    [NS(mode_trace="DSS")],          # a program without the counter
], ids=["no-sparse-step", "no-counter"])
def test_fill_reads_nothing(results):
    run = NS(traffic={"kind": "solo"}, results=results)
    assert fill().read(run) is None


def test_roofline_by_hand():
    # D then two sparse steps over 5 and 2 rows owning 12 and 4 entries,
    # and a second coloring with one step over 3 rows owning 6: least
    # bytes 4 * live + 16 * rows each, in 3 ms of sparse steps in all
    results = [NS(mode_trace="DSS", counts=[9, 5, 2],
                  sparse_entries=[12, 4]),
               NS(mode_trace="S", counts=[3], sparse_entries=[6])]
    run = NS(reduction=timed(0.003), traffic={"kind": "solo"},
             results=results, device=V5E)
    need = (4 * 12 + 16 * 5) + (4 * 4 + 16 * 2) + (4 * 6 + 16 * 3)
    per_step = need / 3
    want = 100 * per_step / 819e9 / 1e-3
    assert roofline().read(run) == pytest.approx(want, rel=1e-12)


@pytest.mark.parametrize("results", [
    [NS(mode_trace="DD", counts=[9, 4], sparse_entries=[])],
    [NS(mode_trace="DS", counts=[9, 4])],      # no counter
], ids=["no-sparse-step", "no-counter"])
def test_roofline_reads_nothing(results):
    run = NS(reduction=timed(0.001), traffic={"kind": "solo"},
             results=results, device=V5E)
    assert roofline().read(run) is None


def test_roofline_fails_where_step_time_fails():
    # sparse steps ran, but no program of the trace is a sparse step
    run = NS(reduction=NS(program_time=lambda pats: None),
             traffic={"kind": "solo"},
             results=[NS(mode_trace="DS", counts=[9, 4],
                         sparse_entries=[8])], device=V5E)
    for name in ("step.sparse_ms", "kernel.sparse_roofline"):
        with pytest.raises(tr.NoMatch):
            harness.load_reader(name).read(run)


def test_roofline_without_a_trace_reads_nothing():
    run = NS(reduction=None, traffic={"kind": "solo"},
             results=[NS(mode_trace="S", counts=[4], sparse_entries=[8])],
             device=V5E)
    assert roofline().read(run) is None


@pytest.mark.parametrize("cell", CELLS)
def test_counter_reaches_the_runner(cell):
    run = run_cell(tiny_run(cell))
    assert run.correct
    for r in run.results:
        assert len(r.sparse_entries) == len(r.sparse_slots) \
            == r.mode_trace.count("S")
        assert all(0 <= e <= s for e, s in zip(r.sparse_entries,
                                                r.sparse_slots))
    assert 0 < fill().read(run) <= 100
