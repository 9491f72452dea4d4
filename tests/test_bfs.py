"""Hybrid direction-optimizing BFS (the paper's future work) vs oracle.
Property tests skip individually when hypothesis is absent (see _hyp)."""
import numpy as np
import pytest

from _hyp import given, settings, st

from repro.core.bfs import bfs, bfs_reference
from repro.graphs import build_graph, make_graph

GRAPHS = ["europe_osm_s", "kron_g500-logn21_s", "hollywood-2009_s"]


@pytest.mark.parametrize("mode", ["topdown", "bottomup", "hybrid"])
@pytest.mark.parametrize("name", GRAPHS)
def test_bfs_matches_reference(name, mode):
    g = make_graph(name, scale=0.02)
    want = bfs_reference(g, 0)
    got = bfs(g, 0, mode=mode)
    np.testing.assert_array_equal(got.dist, want)


def test_hybrid_uses_both_directions():
    # hollywood-like social graph: frontier blows up -> bottom-up middle
    g = make_graph("hollywood-2009_s", scale=0.05)
    r = bfs(g, 0, mode="hybrid", h=0.05)
    assert "T" in r.mode_trace and "B" in r.mode_trace, r.mode_trace
    np.testing.assert_array_equal(r.dist, bfs_reference(g, 0))


@settings(max_examples=12, deadline=None)
@given(st.integers(5, 80), st.integers(1, 4), st.data())
def test_bfs_property_random_graphs(n, density, data):
    seed = data.draw(st.integers(0, 2**31 - 1))
    rng = np.random.default_rng(seed)
    e = density * n
    g = build_graph(rng.integers(0, n, e), rng.integers(0, n, e), n,
                    name="h", ell_cap=16)
    src = data.draw(st.integers(0, n - 1))
    mode = data.draw(st.sampled_from(["topdown", "bottomup", "hybrid"]))
    got = bfs(g, src, mode=mode)
    np.testing.assert_array_equal(got.dist, bfs_reference(g, src))


def test_outlined_engine_matches_hybrid():
    from repro.exec import ExecutionSpec, default_session
    g = make_graph("kron_g500-logn21_s", scale=0.02)
    r_o = default_session().run(
        ExecutionSpec(regime="outlined", mode="topology", window=64), g)
    r_h = default_session().run(
        ExecutionSpec(mode="topology", window=64), g)
    np.testing.assert_array_equal(r_o.colors, r_h.colors)
    assert r_o.iterations == r_h.iterations
    assert r_o.mode_trace == r_h.mode_trace == "D" * r_h.iterations


def test_bfs_pallas_impl_parity():
    g = make_graph("hollywood-2009_s", scale=0.02)
    r_j = bfs(g, 0, mode="bottomup", impl="jnp")
    r_p = bfs(g, 0, mode="bottomup", impl="pallas")
    np.testing.assert_array_equal(r_j.dist, r_p.dist)
