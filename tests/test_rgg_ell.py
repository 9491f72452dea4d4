"""A random geometric graph of the DIMACS10 recipe (``bench/gen/rgg.py``)
on the wide pure-ELL path: rows of more than 32 slots, so two
``ell_wins`` words a row, colored through ``Session.run`` with the
default spec, bit-identical to csr-segment in the host and outlined
regimes; and the dense steps' counter (``ColoringResult.dense_entries``
/ ``dense_slots``) recomputed by stepping the same coloring by hand."""
import numpy as np
import pytest

from bench import reference
from bench.gen import rgg
from repro.algos import get_algorithm
from repro.core import ipgc
from repro.core.worklist import bucket_capacities, pick_bucket, resize_items
from repro.exec import ExecutionSpec, Session
from repro.graphs import build_graph
from repro.graphs.layout import resolve_plan

N, FACTOR, SEED = 2048, 1.0, 1       # max degree 33-40: pure-ell width 40
W = 32


@pytest.fixture(scope="module")
def edges():
    return rgg.edges(N, FACTOR, SEED)


@pytest.fixture(scope="module")
def graph(edges):
    src, dst, n = edges
    return build_graph(src, dst, n, name="rgg-wide", layout="auto",
                       ell_cap=None)


@pytest.fixture(scope="module")
def plain(graph):
    return Session().run(ExecutionSpec(), graph)


def test_plans_two_wins_words(graph):
    ig = ipgc.prepare(graph)
    assert (graph.layout.kind, ig.layout_kind) == ("pure-ell", "pure-ell")
    assert ig.ell_width > 32 and ipgc.wins_words(ig.ell_width) == 2
    assert ig.ell_wins.shape == (N, 2) and ig.n_hub == 0


def test_default_spec_colors_validly(edges, plain):
    src, dst, n = edges
    assert reference.check(src, dst, n, plain.colors, plain.n_colors) == {
        "uncolored_nodes": 0, "conflict_edges": 0, "color_count_gap": 0}
    assert "D" in plain.mode_trace and "S" in plain.mode_trace


@pytest.mark.parametrize("regime", ["host", "outlined"])
def test_colors_as_csr_segment(graph, regime):
    s = Session()
    spec = ExecutionSpec(regime=regime, fused=False)
    ref = s.run(ExecutionSpec(regime="host", fused=False,
                              layout="csr-segment"), graph)
    got = s.run(spec, graph)
    np.testing.assert_array_equal(got.colors, ref.colors)
    assert (got.iterations, got.mode_trace) == (ref.iterations,
                                                ref.mode_trace)
    assert "D" in got.mode_trace and "S" in got.mode_trace


@pytest.mark.parametrize("layout", ["pure-ell", "csr-segment"])
def test_dense_counter_matches_hand_stepping(graph, edges, layout):
    alg = get_algorithm("ipgc")
    ig = alg.prepare(graph, plan=resolve_plan(graph, layout))
    spec = ExecutionSpec(regime="host", fused=False, window=W)
    got = Session().run(spec, ig)
    n = ig.n_nodes
    slots = (n * ig.ell_width if layout == "pure-ell"
             else ig.edge_dst.shape[0])
    assert got.dense_slots == [slots] * got.mode_trace.count("D")
    assert got.dense_entries[0] == 2 * edges[0].size       # 2E

    deg = np.asarray(ig.degrees)
    dense, sparse = ipgc.step_fns(False)
    caps = bucket_capacities(n, ratio=spec.bucket_ratio)
    colors, base, wl = alg.init_state(ig)
    live = []
    for mode, count in zip(got.mode_trace, got.counts):
        assert int(wl.count) == count
        if mode == "D":
            live.append(int(deg[np.asarray(wl.mask)].sum()))
            colors, base, wl = dense(ig, colors, base, wl, window=W)
            continue
        cap = pick_bucket(caps, count)
        if wl.capacity > cap:
            wl = resize_items(wl, cap, n)
        colors, base, wl = sparse(ig, colors, base, wl, window=W)
    assert int(wl.count) == 0
    np.testing.assert_array_equal(got.colors, np.asarray(colors[:n]))
    assert got.dense_entries == live
