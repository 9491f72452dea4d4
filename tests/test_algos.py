"""Pluggable coloring-algorithm subsystem (DESIGN.md §7): registry
semantics, per-algorithm validity in every declared execution mode, IPGC
bit-identity with the pre-subsystem engine, and per-algorithm contracts
(JPL gather profile, spec-greedy fused pinning, shard-safety declaration).
"""
import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.algos import Algorithm, algorithm_names, get_algorithm
from repro.algos.jpl import JPL, jpl_dense_step_impl, jpl_sparse_step_impl
from repro.core import ipgc, verify_coloring
from repro.core.worklist import full_worklist
from repro.exec import ExecutionSpec, default_session
from repro.graphs import build_graph, make_graph

# power-law (kron), regular mesh (europe_osm), hub-heavy (hollywood)
GRAPHS = ["europe_osm_s", "kron_g500-logn21_s", "hollywood-2009_s"]
ALGOS = ["ipgc", "jpl", "spec-greedy"]


@pytest.fixture(scope="module")
def graphs():
    return {n: make_graph(n, scale=0.02) for n in GRAPHS}


# ---------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------

def test_registry_contents():
    names = algorithm_names()
    for name in ALGOS:
        assert name in names
        alg = get_algorithm(name)
        assert alg.name == name
        assert get_algorithm(alg) is alg          # instance passthrough


def test_registry_unknown_name():
    with pytest.raises(ValueError, match="unknown algorithm"):
        get_algorithm("nope")


def test_shard_safety_declarations():
    # all three built-ins are shard-safe since the boundary-exchange PR
    # made jpl's rounds owner-computable (DESIGN.md §13)
    assert get_algorithm("ipgc").shard_safe
    assert get_algorithm("spec-greedy").shard_safe
    assert get_algorithm("jpl").shard_safe


def test_abstract_algorithm_rejected():
    with pytest.raises(ValueError):
        from repro.algos import register
        register(Algorithm())


# ---------------------------------------------------------------------------
# validity in every declared execution mode (acceptance criterion)
# ---------------------------------------------------------------------------

def _exec_modes(alg):
    modes = [dict(regime="host"), dict(regime="outlined")]
    if alg.shard_safe:
        modes.append(dict(regime="dist", n_shards=1))
    return modes


@pytest.mark.parametrize("algo", ALGOS)
@pytest.mark.parametrize("name", GRAPHS)
def test_valid_coloring_all_declared_modes(graphs, name, algo):
    g = graphs[name]
    alg = get_algorithm(algo)
    for kw in _exec_modes(alg):
        r = default_session().run(ExecutionSpec(algo=algo, **kw), g)
        verify_coloring(g, r.colors, context=f"{algo} {kw}")
        alg.check_invariants(r, g)
        assert r.n_colors >= 1


@pytest.mark.parametrize("algo", ALGOS)
def test_policy_degenerate_modes(graphs, algo):
    g = graphs["kron_g500-logn21_s"]
    for mode in ("topology", "data"):
        r = default_session().run(ExecutionSpec(algo=algo, mode=mode), g)
        verify_coloring(g, r.colors, context=f"{algo} {mode}")


def test_jpl_edge_cases():
    jpl = ExecutionSpec(algo="jpl")
    one = build_graph(np.array([0]), np.array([0]), 1, name="one")
    r = default_session().run(jpl, one)
    assert r.n_colors == 1
    tri = build_graph(np.array([0, 1, 2]), np.array([1, 2, 0]), 3,
                      name="tri")
    r = default_session().run(jpl, tri)
    verify_coloring(tri, r.colors)
    assert r.n_colors == 3                      # triangle floor holds


# ---------------------------------------------------------------------------
# IPGC bit-identity with the pre-subsystem engine (acceptance criterion)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", GRAPHS)
def test_ipgc_algo_bit_identical_host_loop(graphs, name):
    g = graphs[name]
    r0 = default_session().run(ExecutionSpec(), g)       # default path
    r1 = default_session().run(ExecutionSpec(algo="ipgc"), g)
    np.testing.assert_array_equal(r0.colors, r1.colors)
    assert r0.iterations == r1.iterations
    assert r0.mode_trace == r1.mode_trace
    assert r0.n_colors == r1.n_colors


def test_ipgc_algo_bit_identical_outlined_and_dist(graphs):
    g = graphs["kron_g500-logn21_s"]
    ro0 = default_session().run(ExecutionSpec(regime="outlined"), g)
    ro1 = default_session().run(
        ExecutionSpec(regime="outlined", algo="ipgc"), g)
    np.testing.assert_array_equal(ro0.colors, ro1.colors)
    assert (ro0.iterations, ro0.mode_trace) == (ro1.iterations,
                                                ro1.mode_trace)
    rd0 = default_session().run(ExecutionSpec(regime="dist", n_shards=1), g)
    rd1 = default_session().run(
        ExecutionSpec(regime="dist", algo="ipgc", n_shards=1), g)
    np.testing.assert_array_equal(rd0.colors, rd1.colors)
    assert (rd0.iterations, rd0.mode_trace) == (rd1.iterations,
                                                rd1.mode_trace)


# ---------------------------------------------------------------------------
# per-algorithm contracts
# ---------------------------------------------------------------------------

def test_jpl_colors_invariant_across_modes(graphs):
    """JPL has no speculation: every active node is decided by the same
    priority draw each round, so host/outlined/policy-mode colorings are
    IDENTICAL (stronger than IPGC's cross-mode equality)."""
    g = graphs["europe_osm_s"]
    r_h = default_session().run(ExecutionSpec(algo="jpl"), g)
    r_t = default_session().run(ExecutionSpec(algo="jpl", mode="topology"), g)
    r_d = default_session().run(ExecutionSpec(algo="jpl", mode="data"), g)
    r_o = default_session().run(
        ExecutionSpec(regime="outlined", algo="jpl"), g)
    for r in (r_t, r_d, r_o):
        np.testing.assert_array_equal(r_h.colors, r.colors)
        assert r.iterations == r_h.iterations


def test_jpl_impl_parity(graphs):
    g = graphs["hollywood-2009_s"]       # hub-heavy: exercises tail extrema
    r_j = default_session().run(ExecutionSpec(algo="jpl", impl="jnp"), g)
    r_p = default_session().run(ExecutionSpec(algo="jpl", impl="pallas"), g)
    np.testing.assert_array_equal(r_j.colors, r_p.colors)


def test_jpl_gather_profile(graphs):
    """JPL communication contract: a dense round never gathers the mutable
    colors array (activity rides the priority vector); a sparse round
    performs exactly ONE ELL-shaped colors gather."""
    g = graphs["europe_osm_s"]
    ig = get_algorithm("jpl").prepare(g)
    n = ig.n_nodes
    colors = ipgc.init_colors(n)
    rnd = jnp.zeros((), jnp.int32)
    wl = full_worklist(n)
    for fn, want in [(jpl_dense_step_impl, 0), (jpl_sparse_step_impl, 1)]:
        ipgc.reset_gather_counts()
        jax.eval_shape(partial(fn, ig, window=32, impl="jnp",
                               force_hub=False), colors, rnd, wl)
        assert ipgc.GATHER_COUNTS["neighbor_colors"] == want, fn.__name__


def test_jpl_quality_gap_vs_ipgc(graphs):
    """Table IV qualitative claim, now at the subsystem level: the
    independent-set colorer trades color quality for round speed."""
    worse = 0
    for name, g in graphs.items():
        n_jpl = default_session().run(ExecutionSpec(algo="jpl"), g).n_colors
        n_ipgc = default_session().run(ExecutionSpec(algo="ipgc"),
                                       g).n_colors
        if n_jpl < n_ipgc:
            worse += 1
    assert worse == 0


def test_jpl_palette_is_compact(graphs):
    r = default_session().run(ExecutionSpec(algo="jpl"),
                              graphs["kron_g500-logn21_s"])
    used = np.unique(r.colors[r.colors >= 0])
    np.testing.assert_array_equal(used, np.arange(len(used)))
    assert r.n_colors == len(used)


def test_spec_greedy_pins_fused_family(graphs):
    """spec-greedy IS deferred detect-and-repair: the caller's ``fused``
    request cannot reintroduce a same-iteration resolve phase."""
    g = graphs["europe_osm_s"]
    r_def = default_session().run(ExecutionSpec(algo="spec-greedy"), g)
    r_f0 = default_session().run(
        ExecutionSpec(algo="spec-greedy", fused=False), g)
    np.testing.assert_array_equal(r_def.colors, r_f0.colors)
    assert r_def.iterations == r_f0.iterations
    # same trajectory as the fused IPGC steps it reuses (palette aside)
    r_ipgc = default_session().run(ExecutionSpec(algo="ipgc", fused=True), g)
    assert r_def.iterations == r_ipgc.iterations
    assert r_def.mode_trace == r_ipgc.mode_trace


def test_spec_greedy_dist_matches_quality(graphs):
    g = graphs["kron_g500-logn21_s"]
    r = default_session().run(
        ExecutionSpec(regime="dist", algo="spec-greedy", n_shards=1), g)
    verify_coloring(g, r.colors, context="spec-greedy dist")
    r_host = default_session().run(ExecutionSpec(algo="spec-greedy"), g)
    # dist repartitions (relabels) the graph, so exact colors differ; the
    # class count must stay in the same band
    assert abs(r.n_colors - r_host.n_colors) <= max(4, r_host.n_colors // 2)


def test_dist_rejects_non_shard_safe():
    # the declaration contract still fails fast — exercised via a stub
    # algorithm now that every built-in ships distributed steps
    stub = dataclasses.replace(
        get_algorithm("ipgc"), name="ipgc-noshard", shard_safe=False,
        shard_unsafe_reason="stub: declaration-contract test")
    g = make_graph("europe_osm_s", scale=0.01)
    with pytest.raises(ValueError, match="not shard-safe"):
        default_session().run(
            ExecutionSpec(regime="dist", algo=stub, n_shards=1), g)


def test_custom_algorithm_instance_accepted(graphs):
    """The registry is open: an unregistered instance rides through
    ``algo=`` directly (tuned variants need no global name)."""
    tuned = JPL(name="jpl-tuned")
    r = default_session().run(ExecutionSpec(algo=tuned),
                              graphs["europe_osm_s"])
    verify_coloring(graphs["europe_osm_s"], r.colors)


def test_outlined_specialisation_not_keyed_on_name(graphs):
    """Regression: the outlined engine's IPGC fast-path substitution must
    key on the algorithm *instance* (dataclass equality), not the name —
    a different algorithm carrying the name "ipgc" keeps its own steps."""
    g = graphs["europe_osm_s"]
    rogue = JPL(name="ipgc")
    r = default_session().run(ExecutionSpec(regime="outlined", algo=rogue),
                              g)
    r_jpl = default_session().run(
        ExecutionSpec(regime="outlined", algo="jpl"), g)
    np.testing.assert_array_equal(r.colors, r_jpl.colors)
    assert r.iterations == r_jpl.iterations


def test_check_invariants_flags_growth():
    alg = get_algorithm("ipgc")

    class FakeResult:
        counts = [5, 9]
        iterations = 2
        n_colors = 3

    with pytest.raises(AssertionError, match="grew"):
        alg.check_invariants(FakeResult())


def test_jpl_round_counter_rides_outlining(graphs):
    """The JPL aux state (round counter) must survive chunked outlining:
    color classes 2r/2r+1 only line up if every on-device trip advanced
    the same counter the host loop would have."""
    g = graphs["kron_g500-logn21_s"]
    r_host = default_session().run(ExecutionSpec(algo="jpl"), g)
    r_out = default_session().run(
        ExecutionSpec(regime="outlined", algo="jpl"), g)
    np.testing.assert_array_equal(r_host.colors, r_out.colors)
    assert r_host.iterations == r_out.iterations
    assert r_out.host_dispatches <= r_host.host_dispatches


def test_jpl_extrema_kernel_matches_ref():
    from repro.kernels import ref
    from repro.kernels.jpl_prio import jpl_extrema_pallas
    rng = np.random.default_rng(11)
    for r, k in [(1, 1), (7, 9), (64, 16), (100, 3), (257, 40)]:
        npr = jnp.asarray(rng.integers(-1, 10_000, size=(r, k))
                          .astype(np.int32))
        gm, gn = jpl_extrema_pallas(npr, interpret=True)
        wm, wn = ref.jpl_extrema_ref(npr)
        np.testing.assert_array_equal(np.asarray(gm), np.asarray(wm))
        np.testing.assert_array_equal(np.asarray(gn), np.asarray(wn))
    # all-inactive row: max stays -1, min stays LARGE
    npr = jnp.full((3, 4), -1, jnp.int32)
    gm, gn = jpl_extrema_pallas(npr, interpret=True)
    assert (np.asarray(gm) == -1).all()
    assert (np.asarray(gn) == 0x7FFFFFFF).all()


def test_jpl_hub_side_channel(graphs):
    """Hub COO-tail priorities must reach the extrema fold: force the hub
    side-channel on a hubless mesh graph and require identical output,
    step for step, from the dense and the sparse round."""
    g = graphs["europe_osm_s"]
    alg = get_algorithm("jpl")
    ig = alg.prepare(g)
    assert ig.n_hub == 0          # only force_hub turns the fold on
    dense, sparse = alg.step_fns(False)
    state = {fh: alg.init_state(ig) for fh in (False, True)}
    for it in range(4 * ig.n_nodes):
        step = dense if it % 2 == 0 else sparse
        for fh in (False, True):
            state[fh] = step(ig, *state[fh], window=128, impl="jnp",
                             force_hub=fh, tile_rows=None)[:3]
        np.testing.assert_array_equal(state[True][0], state[False][0])
        if int(state[False][2].count) == 0:
            break
    assert int(state[True][2].count) == 0
