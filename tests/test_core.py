"""System behaviour tests for the hybrid coloring engine (the paper core)."""
import numpy as np
import pytest

from repro.core import jpl_color, bucket_capacities, verify_coloring
from repro.core.policy import make_policy, AutoTuned
from repro.core.worklist import pick_bucket
from repro.exec import ExecutionSpec, default_session
from repro.graphs import make_graph, validate_coloring, build_graph

GRAPHS = ["europe_osm_s", "kron_g500-logn21_s", "Audikw_1_s", "circuit5M_s"]


@pytest.fixture(scope="module")
def graphs():
    return {n: make_graph(n, scale=0.02) for n in GRAPHS}


@pytest.mark.parametrize("mode", ["topology", "data", "hybrid", "hybrid-auto"])
@pytest.mark.parametrize("name", GRAPHS)
def test_engine_valid_coloring(graphs, name, mode):
    r = default_session().run(ExecutionSpec(mode=mode), graphs[name])
    verify_coloring(graphs[name], r.colors, context=f"{name}/{mode}")
    assert r.n_colors >= 1


@pytest.mark.parametrize("name", GRAPHS)
def test_baselines_valid(graphs, name):
    # JPL, and the Kokkos vertex-based baseline: data-driven, 32-wide
    # forbidden window, hash tie-break
    vb = ExecutionSpec(mode="data", window=32, priority="hash")
    for r in (jpl_color(graphs[name]),
              default_session().run(vb, graphs[name])):
        verify_coloring(graphs[name], r.colors, context=name)


def test_hybrid_switches_at_h(graphs):
    g = graphs["kron_g500-logn21_s"]
    r = default_session().run(ExecutionSpec(mode="hybrid", h=0.6), g)
    # trace must be a (possibly empty) run of D followed by only S —
    # the active set shrinks monotonically so the policy flips once
    t = r.mode_trace
    assert "SD" not in t, t
    assert t.endswith("S") or t == "D" * len(t)


def test_worklist_monotone_shrink(graphs):
    g = graphs["kron_g500-logn21_s"]
    r = default_session().run(ExecutionSpec(mode="hybrid"), g)
    assert all(b <= a for a, b in zip(r.counts, r.counts[1:])), r.counts


def test_ipgc_fewer_colors_than_jpl(graphs):
    """Table IV qualitative claim: IPGC-family colorings use far fewer
    colors than independent-set (cuSPARSE-style) coloring."""
    worse = 0
    for name, g in graphs.items():
        c_h = default_session().run(ExecutionSpec(mode="hybrid"), g).n_colors
        c_j = jpl_color(g).n_colors
        if c_j < c_h:
            worse += 1
    assert worse == 0


def test_same_colors_across_modes(graphs):
    """Plain/Hybrid/topology implement the *same algorithm* (paper:
    'they all implement exactly the same algorithm for assigning colors,
    just with different optimizations') — identical colorings."""
    g = graphs["Audikw_1_s"]
    r_t = default_session().run(ExecutionSpec(mode="topology"), g)
    r_d = default_session().run(ExecutionSpec(mode="data"), g)
    r_h = default_session().run(ExecutionSpec(mode="hybrid"), g)
    np.testing.assert_array_equal(r_t.colors, r_d.colors)
    np.testing.assert_array_equal(r_t.colors, r_h.colors)


def test_impl_parity_jnp_pallas(graphs):
    g = graphs["circuit5M_s"]
    r_j = default_session().run(ExecutionSpec(mode="hybrid", impl="jnp"), g)
    r_p = default_session().run(ExecutionSpec(mode="hybrid", impl="pallas"), g)
    np.testing.assert_array_equal(r_j.colors, r_p.colors)


def test_triangle_and_star():
    # triangle needs exactly 3 colors, star needs 2
    tri = build_graph(np.array([0, 1, 2]), np.array([1, 2, 0]), 3, name="tri")
    r = default_session().run(ExecutionSpec(mode="hybrid"), tri)
    assert r.n_colors == 3
    assert validate_coloring(tri, r.colors)["conflicts"] == 0
    star = build_graph(np.zeros(10, int), np.arange(1, 11), 11, name="star")
    r = default_session().run(ExecutionSpec(mode="hybrid"), star)
    assert r.n_colors == 2


def test_mex_optimality_on_isolated_nodes():
    # nodes with no neighbours all take color 0
    g = build_graph(np.array([0]), np.array([1]), 8, name="pair")
    r = default_session().run(ExecutionSpec(mode="data"), g)
    assert set(np.asarray(r.colors)[2:].tolist()) == {0}


def test_bucket_ladder():
    caps = bucket_capacities(100_000, ratio=4, floor=1024)
    assert caps[0] >= 100_000
    assert all(a > b for a, b in zip(caps, caps[1:]))
    assert pick_bucket(caps, 100_000) == caps[0]
    assert pick_bucket(caps, 1) == caps[-1]
    for c in range(1, 100_000, 9973):
        assert pick_bucket(caps, c) >= c


def test_policies():
    pol = make_policy("hybrid", 0.6)
    assert pol(61, 100) and not pol(59, 100)
    assert make_policy("topology")(1, 100)
    assert not make_policy("data")(99, 100)
    auto = make_policy("hybrid-auto")
    assert isinstance(auto, AutoTuned)
    assert auto(90, 100)          # prior: dense above H
    auto.observe(True, 90, 100, 1e-3)
    auto.observe(False, 50, 100, 1e-4)
    assert not auto(10, 100)      # sparse clearly cheaper at tiny counts


def test_window_exhaustion_hub():
    """A clique bigger than the window forces base advancement: K_200 with
    window 128 needs 200 colors, exercising multi-window mex."""
    n = 200
    s, d = np.meshgrid(np.arange(n), np.arange(n))
    g = build_graph(s.ravel(), d.ravel(), n, name="K200", ell_cap=64)
    r = default_session().run(ExecutionSpec(mode="hybrid", window=128), g)
    verify_coloring(g, r.colors)
    assert r.n_colors == n
