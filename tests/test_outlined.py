"""Device-resident hybrid Pipe: outlined-engine equivalence + fused-step
contracts (single neighbour-color gather, bounded host dispatches)."""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ipgc, verify_coloring
from repro.core.worklist import bucket_capacities, full_worklist
from repro.exec import ExecutionSpec, default_session
from repro.graphs import build_graph, make_graph, validate_coloring

# power-law (kron), regular mesh (europe_osm), hub-heavy (hollywood)
GRAPHS = ["europe_osm_s", "kron_g500-logn21_s", "hollywood-2009_s"]


@pytest.fixture(scope="module")
def graphs():
    return {n: make_graph(n, scale=0.02) for n in GRAPHS}


def _assert_equivalent(g, r_host, r_out):
    verify_coloring(g, r_out.colors)
    np.testing.assert_array_equal(r_out.colors, r_host.colors)
    assert r_out.iterations == r_host.iterations
    assert r_out.n_colors == r_host.n_colors
    assert r_out.mode_trace == r_host.mode_trace


@pytest.mark.parametrize("fused", [False, True])
@pytest.mark.parametrize("name", GRAPHS)
def test_outlined_matches_host_loop_jnp(graphs, name, fused):
    g = graphs[name]
    r_host = default_session().run(ExecutionSpec(fused=fused), g)
    r_out = default_session().run(
        ExecutionSpec(regime="outlined", fused=fused), g)
    _assert_equivalent(g, r_host, r_out)


@pytest.mark.parametrize("fused", [False, True])
def test_outlined_matches_host_loop_pallas(graphs, fused):
    g = graphs["kron_g500-logn21_s"]
    r_host = default_session().run(
        ExecutionSpec(impl="pallas", fused=fused), g)
    r_out = default_session().run(
        ExecutionSpec(regime="outlined", impl="pallas", fused=fused), g)
    _assert_equivalent(g, r_host, r_out)


def test_outlined_pallas_matches_jnp(graphs):
    g = graphs["europe_osm_s"]
    r_j = default_session().run(
        ExecutionSpec(regime="outlined", impl="jnp"), g)
    r_p = default_session().run(
        ExecutionSpec(regime="outlined", impl="pallas"), g)
    np.testing.assert_array_equal(r_j.colors, r_p.colors)
    assert r_j.iterations == r_p.iterations


def test_outlined_edge_cases():
    # 1-node graph (the only edge is a removed self loop)
    outlined = ExecutionSpec(regime="outlined")
    one = build_graph(np.array([0]), np.array([0]), 1, name="one")
    r = default_session().run(outlined, one)
    assert validate_coloring(one, r.colors) == {
        "conflicts": 0, "uncolored": 0, "n_colors": 1}
    # graph whose edge list is empty after preprocessing
    empty = build_graph(np.array([3]), np.array([3]), 8, name="empty")
    r = default_session().run(outlined, empty)
    v = validate_coloring(empty, r.colors)
    assert v["conflicts"] == 0 and v["uncolored"] == 0 and v["n_colors"] == 1
    # the host loop agrees on the degenerate graphs too
    for g in (one, empty):
        np.testing.assert_array_equal(
            default_session().run(outlined, g).colors,
            default_session().run(ExecutionSpec(fused=True), g).colors)


@pytest.mark.parametrize("ratio", [2, 4])
def test_outlined_dispatch_bound(graphs, ratio):
    """Acceptance: at most len(bucket_capacities(n)) + O(1) host dispatches
    per coloring, vs one dispatch per iteration for the host loop."""
    g = graphs["kron_g500-logn21_s"]
    r = default_session().run(
        ExecutionSpec(regime="outlined", bucket_ratio=ratio), g)
    caps = bucket_capacities(g.n_nodes, ratio=ratio)
    assert r.host_dispatches <= len(caps) + 1
    r_host = default_session().run(ExecutionSpec(fused=True), g)
    assert r_host.host_dispatches == r_host.iterations
    assert r.host_dispatches < r_host.host_dispatches


def test_outlined_hybrid_auto_policy(graphs):
    g = graphs["europe_osm_s"]
    r = default_session().run(
        ExecutionSpec(regime="outlined", mode="hybrid-auto"), g)
    verify_coloring(g, r.colors)


# ---------------------------------------------------------------------------
# fused-step contracts
# ---------------------------------------------------------------------------

def _trace_state(g):
    ig = ipgc.prepare(g)
    n = ig.n_nodes
    return ig, ipgc.init_colors(n), jnp.zeros((n,), jnp.int32), \
        full_worklist(n)


@pytest.mark.parametrize("name", ["europe_osm_s", "hollywood-2009_s"])
def test_fused_step_single_color_gather(graphs, name):
    """Acceptance: the fused steps perform exactly ONE ELL-shaped gather of
    the colors array per iteration; the two-phase steps perform two
    (pre-assign mex + post-assign conflict check)."""
    ig, colors, base, wl = _trace_state(graphs[name])
    cases = [(ipgc.dense_step_impl, 2), (ipgc.sparse_step_impl, 2),
             (ipgc.fused_dense_step_impl, 1), (ipgc.fused_sparse_step_impl, 1)]
    for fn, want in cases:
        ipgc.reset_gather_counts()
        jax.eval_shape(partial(fn, ig, window=32, impl="jnp",
                               force_hub=False), colors, base, wl)
        assert ipgc.GATHER_COUNTS["neighbor_colors"] == want, fn.__name__


def test_fused_host_loop_valid_and_comparable_quality(graphs):
    """Fused (deferred-resolve) semantics stay valid and do not blow up the
    chromatic quality vs the two-phase steps."""
    for name, g in graphs.items():
        r2 = default_session().run(ExecutionSpec(fused=False), g)
        rf = default_session().run(ExecutionSpec(fused=True), g)
        verify_coloring(g, rf.colors, context=name)
        assert rf.n_colors <= 2 * r2.n_colors + 2, (name, rf.n_colors,
                                                    r2.n_colors)


def test_sparse_scatter_padding_does_not_clobber_node0():
    """Regression: worklist padding rows used to scatter their stale
    base/mask values to row 0, silently discarding node 0's window advance
    (and worklist-exit bit) whenever node 0 sat in a padded worklist."""
    from repro.core.worklist import Worklist
    g = build_graph(np.array([0]), np.array([1]), 2, name="pair")
    ig = ipgc.prepare(g)
    n = 2
    colors = ipgc.init_colors(n).at[1].set(0)   # neighbour holds color 0
    base = jnp.zeros((n,), jnp.int32)
    wl = Worklist(mask=jnp.asarray([True, False]),
                  items=jnp.asarray([0, n, n, n], jnp.int32),
                  count=jnp.asarray(1, jnp.int32))
    for fn in (ipgc.sparse_step, ipgc.fused_sparse_step):
        # window=1 is fully forbidden for node 0 -> its base must advance
        _, b2, _ = fn(ig, colors, base, wl, window=1, impl="jnp",
                      force_hub=False)
        assert int(b2[0]) == 1, fn
        assert int(b2[1]) == 0, fn


def test_fused_kernel_matches_ref():
    from repro.kernels import ref
    from repro.kernels.fused_step import fused_step_pallas
    rng = np.random.default_rng(7)
    for r, k, w in [(1, 1, 128), (7, 9, 128), (64, 16, 256), (100, 3, 128)]:
        nc = jnp.asarray(rng.integers(-2, 300, size=(r, k)).astype(np.int32))
        npr = jnp.asarray(rng.integers(-1, 999, size=(r, k)).astype(np.int32))
        nid = jnp.asarray(rng.integers(0, r + 1, size=(r, k)).astype(np.int32))
        base = jnp.asarray((rng.integers(0, 2, size=(r,)) * w).astype(np.int32))
        cu = jnp.asarray(rng.integers(-2, 300, size=(r,)).astype(np.int32))
        pu = jnp.asarray(rng.integers(0, 999, size=(r,)).astype(np.int32))
        ids = jnp.asarray(np.arange(r, dtype=np.int32))
        pend = jnp.asarray(rng.random(r) < 0.5)
        extra = jnp.asarray(rng.random((r, w)) < 0.2)
        lose_p, first_p = fused_step_pallas(nc, npr, nid, base, cu, pu, ids,
                                            pend, extra, w, interpret=True)
        lose_r, first_r = ref.fused_step_ref(nc, npr, nid, base, cu, pu, ids,
                                             pend, extra, w)
        np.testing.assert_array_equal(np.asarray(lose_p), np.asarray(lose_r))
        np.testing.assert_array_equal(np.asarray(first_p),
                                      np.asarray(first_r))
