"""Unified execution sessions (DESIGN.md §9): spec/session contracts,
warm-vs-cold bit-identity per regime, the unified compile cache, and
batched multi-graph bit-identity."""
import dataclasses

import jax
import numpy as np
import pytest

from repro.core import ipgc, verify_coloring
from repro.core.worklist import stacked_worklist
from repro.exec import ExecutionSpec, Session, default_session
from repro.graphs import get_dataset, get_dataset_batch, make_graph

GRAPHS = ["europe_osm_s", "kron_g500-logn21_s", "hollywood-2009_s"]


@pytest.fixture(scope="module")
def graphs():
    return {n: make_graph(n, scale=0.02) for n in GRAPHS}


def _same_result(a, b, *, dispatches=True):
    np.testing.assert_array_equal(a.colors, b.colors)
    assert a.iterations == b.iterations
    assert a.n_colors == b.n_colors
    assert a.mode_trace == b.mode_trace
    assert a.counts == b.counts
    if dispatches:
        assert a.host_dispatches == b.host_dispatches


# ---------------------------------------------------------------------------
# ExecutionSpec
# ---------------------------------------------------------------------------

def test_spec_validates_regime_and_is_hashable():
    with pytest.raises(ValueError, match="regime"):
        ExecutionSpec(regime="warp")
    # the sharded Pipe is chosen by the regime alone, never by the mode
    for mode in ("dist-hybrid", "dist-topology"):
        with pytest.raises(ValueError, match="regime='dist'"):
            ExecutionSpec(mode=mode)
    s = ExecutionSpec(regime="host", window=64)
    assert hash(s.static_key())          # usable as a cache key
    assert s.static_key() != ExecutionSpec(regime="outlined",
                                           window=64).static_key()


# ---------------------------------------------------------------------------
# Session.run — a warm run on the same session is bit-identical to its
# cold first run (the benchmark warms up, then times a window of runs)
# ---------------------------------------------------------------------------

def _cold_then_warm(spec, g):
    s = Session()
    cold = s.run(spec, g)
    warm = s.run(spec, g)
    _same_result(cold, warm)
    return warm


@pytest.mark.parametrize("fused", [False, True])
@pytest.mark.parametrize("name", GRAPHS)
def test_session_run_matches_host_entry_point(graphs, name, fused):
    g = graphs[name]
    r = _cold_then_warm(ExecutionSpec(regime="host", fused=fused), g)
    verify_coloring(g, r.colors, context=name)
    assert r.host_dispatches == r.iterations   # host-loop contract


def test_session_run_matches_outlined_entry_point(graphs):
    g = graphs["kron_g500-logn21_s"]
    r = _cold_then_warm(ExecutionSpec(regime="outlined", fused=False), g)
    verify_coloring(g, r.colors)
    assert r.host_dispatches < r.iterations    # chunked contract


def test_session_run_matches_dist_entry_point(graphs):
    g = graphs["europe_osm_s"]
    r = _cold_then_warm(ExecutionSpec(regime="dist", n_shards=1), g)
    verify_coloring(g, r.colors)
    assert r.host_dispatches == r.iterations   # one dispatch an iteration


def test_legacy_steps_cache_still_accepted_and_reused(graphs):
    # a caller-supplied dict is the session's store: a dist run fills it,
    # and a warm run on a new session over the same dict adds nothing
    g = graphs["europe_osm_s"]
    cache: dict = {}
    spec = ExecutionSpec(regime="dist", n_shards=1)
    a = Session(cache=cache).run(spec, g)
    assert len(cache) > 0
    n_entries = len(cache)
    b = Session(cache=cache).run(spec, g)
    assert len(cache) == n_entries        # warm: no new artifacts
    _same_result(a, b)


def test_prepare_cache_is_shared_across_host_and_outlined(graphs):
    g = graphs["europe_osm_s"]
    s = Session()
    s.run(ExecutionSpec(regime="host"), g)
    misses = s.stats.misses
    s.run(ExecutionSpec(regime="outlined"), g)   # same prepared graph
    assert s.stats.misses == misses
    assert s.stats.hits >= 1


def test_warm_session_hits_and_stats(graphs):
    g = graphs["kron_g500-logn21_s"]
    s = Session()
    spec = ExecutionSpec(regime="host")
    s.run(spec, g)
    assert s.stats.misses >= 1 and s.stats.hits == 0
    s.run(spec, g)
    assert s.stats.hits >= 1
    assert 0.0 < s.stats.hit_rate <= 1.0
    d = s.stats.as_dict()
    assert set(d) == {"hits", "misses", "evictions", "hit_rate"}


def test_default_session_backs_the_legacy_entry_points(graphs):
    from repro.exec import reset_default_session
    reset_default_session()
    try:
        g = graphs["hollywood-2009_s"]
        spec = ExecutionSpec(mode="hybrid")
        default_session().run(spec, g)
        stats = default_session().stats
        assert stats.misses >= 1 and stats.hits == 0
        default_session().run(spec, g)
        assert stats.hits >= 1
    finally:
        reset_default_session()


def test_session_bounded_cache_evicts_fifo():
    s = Session(max_entries=2)
    for i in range(4):
        s.cached(("k", i), lambda i=i: i)
    assert len(s.cache) == 2
    assert list(s.cache) == [("k", 2), ("k", 3)]     # oldest evicted
    s.cached(("k", 3), lambda: 99)                    # still a hit
    assert s.stats.hits == 1 and s.stats.misses == 4
    assert s.stats.evictions == 2
    # the process-default session is bounded; explicit sessions are not
    from repro.exec import reset_default_session
    reset_default_session()
    try:
        assert default_session().max_entries is not None
        assert Session().max_entries is None
    finally:
        reset_default_session()


def test_session_pin_protects_live_entries_from_eviction():
    s = Session(max_entries=2)
    with s.pin():
        for i in range(5):
            s.cached(("k", i), lambda i=i: i)
        # every entry was touched under the pin: the bound is exceeded
        # rather than evicting a live run's own artifacts
        assert len(s.cache) == 5 and s.stats.evictions == 0
    # outermost exit restores the bound against the then-oldest entries
    assert len(s.cache) == 2 and s.stats.evictions == 3
    assert list(s.cache) == [("k", 3), ("k", 4)]


def test_session_pin_marks_hits_and_nests():
    s = Session(max_entries=2)
    s.cached(("old",), lambda: 0)
    with s.pin():
        s.cached(("old",), lambda: 0)     # a pinned HIT is protected too
        with s.pin():                     # inner pin extends the outer scope
            s.cached(("a",), lambda: 1)
        s.cached(("b",), lambda: 2)       # over bound: evicts nothing pinned
        assert set(s.cache) == {("old",), ("a",), ("b",)}
    assert len(s.cache) == 2 and ("old",) not in s.cache


def test_dist_cache_keys_by_content_like_legacy_steps_cache():
    # a caller that REBUILDS an equal graph per request still reuses the
    # partitioned graph + jitted shard_map steps
    a = make_graph("europe_osm_s", scale=0.01)
    b = dataclasses.replace(a)            # equal content, distinct object
    cache: dict = {}
    spec = ExecutionSpec(regime="dist", n_shards=1)
    r_a = Session(cache=cache).run(spec, a)
    n_entries = len(cache)
    r_b = Session(cache=cache).run(spec, b)
    assert len(cache) == n_entries        # content key -> warm hit
    _same_result(r_a, r_b)


def test_session_respects_graph_identity_not_name():
    # two DIFFERENT graphs sharing name/size must not share artifacts
    a = make_graph("europe_osm_s", scale=0.01)
    b = dataclasses.replace(a)            # equal content, distinct object
    s = Session()
    spec = ExecutionSpec(regime="host")
    s.run(spec, a)
    misses = s.stats.misses
    s.run(spec, b)
    assert s.stats.misses > misses        # keyed by identity


# ---------------------------------------------------------------------------
# Session.run_batch — many graphs, one dispatch, bit-identical per lane
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("algo,fused", [("ipgc", False), ("ipgc", True),
                                        ("jpl", None),
                                        ("spec-greedy", None)])
def test_run_batch_bit_identical_to_individual(graphs, algo, fused):
    batch = [graphs[n] for n in GRAPHS] + [make_graph("europe_osm_s",
                                                      scale=0.005)]
    s = Session()
    spec = ExecutionSpec(regime="host", algo=algo, fused=fused)
    results = s.run_batch(spec, batch)
    assert len(results) == len(batch)
    for g, rb in zip(batch, results):
        ri = s.run(spec, g)
        np.testing.assert_array_equal(rb.colors, ri.colors)
        assert rb.iterations == ri.iterations
        assert rb.n_colors == ri.n_colors
        assert rb.mode_trace == ri.mode_trace
        assert rb.host_dispatches == 1            # the batched contract
        verify_coloring(g, rb.colors, context=g.name)


@pytest.mark.parametrize("mode", ["topology", "data"])
def test_run_batch_degenerate_policies(graphs, mode):
    batch = [graphs["europe_osm_s"], graphs["kron_g500-logn21_s"]]
    s = Session()
    spec = ExecutionSpec(regime="host", mode=mode)
    for g, rb in zip(batch, s.run_batch(spec, batch)):
        ri = s.run(spec, g)
        np.testing.assert_array_equal(rb.colors, ri.colors)
        assert rb.mode_trace == ri.mode_trace
        want = "D" if mode == "topology" else "S"
        assert set(rb.mode_trace) == {want}


def test_run_batch_duplicate_and_single_lanes(graphs):
    g = graphs["kron_g500-logn21_s"]
    s = Session()
    spec = ExecutionSpec(regime="host")
    one = s.run_batch(spec, [g])
    dup = s.run_batch(spec, [g, g, g])
    for r in (*one, *dup):
        np.testing.assert_array_equal(r.colors, one[0].colors)
    assert s.run_batch(spec, []) == []


def test_run_batch_warm_reuses_stack_and_program(graphs):
    batch = [graphs[n] for n in GRAPHS]
    s = Session()
    spec = ExecutionSpec(regime="host")
    s.run_batch(spec, batch)
    misses = s.stats.misses
    s.run_batch(spec, batch)              # identical batch: all hits
    assert s.stats.misses == misses


def test_run_batch_maps_back_through_permutations():
    base = get_dataset("kron_g500-logn21_s", scale=0.02, layout="ell-tail")
    shuffled = get_dataset("kron_g500-logn21_s", scale=0.02,
                           layout="ell-tail", reorder="shuffle")
    assert shuffled.perm is not None
    s = Session()
    spec = ExecutionSpec(regime="host")
    r_plain, r_shuf = s.run_batch(spec, [base, shuffled],
                                  map_to_original=True)
    # both lanes now report colors in ORIGINAL node ids: verifiable on
    # the unreordered graph
    verify_coloring(base, r_plain.colors)
    verify_coloring(base, r_shuf.colors)


def test_run_batch_validation_failures(graphs):
    g = graphs["europe_osm_s"]
    s = Session()
    with pytest.raises(ValueError, match="regime"):
        s.run_batch(ExecutionSpec(regime="dist", n_shards=2), [g])
    with pytest.raises(ValueError, match="regime"):
        s.run_batch(ExecutionSpec(regime="outlined"), [g])
    with pytest.raises(ValueError, match="monotone"):
        s.run_batch(ExecutionSpec(regime="host", mode="hybrid-auto"), [g])
    with pytest.raises(ValueError, match="impl"):
        s.run_batch(ExecutionSpec(regime="host", impl="pallas"), [g])
    with pytest.raises(TypeError, match="host Graph"):
        s.run_batch(ExecutionSpec(regime="host"), [ipgc.prepare(g)])
    from repro.algos.base import Algorithm
    shy = dataclasses.replace(Algorithm(name="shy"),
                              batch_unsafe_reason="not audited")
    with pytest.raises(ValueError, match="not audited"):
        s.run_batch(ExecutionSpec(regime="host", algo=shy), [g])
    with pytest.raises(NotImplementedError, match="csr-segment"):
        s.run_batch(ExecutionSpec(regime="host", layout="csr-segment"),
                    [g])


def test_run_batch_mixed_hub_and_hubless_lanes(graphs):
    """A bucket mixing hub-bearing and hubless graphs pads the hubless
    lane's hub side-channel — which must stay inert (all-False rows)."""
    hubby = make_graph("hollywood-2009_s", scale=0.01)   # hubs
    mesh = make_graph("europe_osm_s", scale=0.005)       # hubless
    ig_h, ig_m = ipgc.prepare(hubby), ipgc.prepare(mesh)
    assert ig_h.n_hub > 0 and ig_m.n_hub == 0
    s = Session()
    spec = ExecutionSpec(regime="host", window=64)       # same shape rung
    for g, rb in zip([hubby, mesh], s.run_batch(spec, [hubby, mesh])):
        ri = s.run(spec, g)
        np.testing.assert_array_equal(rb.colors, ri.colors)
        assert rb.iterations == ri.iterations


# ---------------------------------------------------------------------------
# batch plumbing: pad_prepared + stacked_worklist
# ---------------------------------------------------------------------------

def test_pad_prepared_is_inert(graphs):
    """One unbatched step on the padded graph == the same step on the
    original, on the original's slots; pad slots never change."""
    import jax.numpy as jnp
    from repro.core.worklist import full_worklist
    g = graphs["hollywood-2009_s"]
    ig = ipgc.prepare(g)
    n = ig.n_nodes
    pad = ipgc.pad_prepared(ig, n + 64, ig.ell_width + 8,
                            ig.tail_src.shape[0] + 16, ig.n_hub + 4)
    colors0 = ipgc.init_colors(n)
    colors0_p = jnp.concatenate([
        colors0[:n], jnp.full((65,), int(colors0[n]), jnp.int32)])
    wl = full_worklist(n)
    wl_p = stacked_worklist([n], n + 64)
    wl_p = type(wl)(mask=wl_p.mask[0], items=wl_p.items[0],
                    count=wl_p.count[0])
    base = jnp.zeros((n,), jnp.int32)
    base_p = jnp.zeros((n + 64,), jnp.int32)
    c1, b1, w1 = ipgc.dense_step(ig, colors0, base, wl,
                                 window=64, impl="jnp", force_hub=False)
    c2, b2, w2 = ipgc.dense_step(pad, colors0_p, base_p, wl_p,
                                 window=64, impl="jnp", force_hub=False)
    np.testing.assert_array_equal(np.asarray(c1[:n]), np.asarray(c2[:n]))
    np.testing.assert_array_equal(np.asarray(b1), np.asarray(b2[:n]))
    assert int(w1.count) == int(w2.count)
    np.testing.assert_array_equal(np.asarray(w1.mask), np.asarray(w2.mask[:n]))
    # pad slots: colors stayed PAD, never active
    assert (np.asarray(c2[n:]) == -2).all()
    assert not np.asarray(w2.mask[n:]).any()


def test_pad_prepared_rejects_csr_segment():
    g = get_dataset("kron_g500-logn21_s", scale=0.01, layout="csr-segment")
    ig = ipgc.prepare(g, plan=g.layout)
    with pytest.raises(AssertionError, match="csr-segment"):
        ipgc.pad_prepared(ig, ig.n_nodes + 8, ig.ell_width,
                          ig.tail_src.shape[0], ig.n_hub)


def test_stacked_worklist_shapes_and_sentinels():
    wl = stacked_worklist([3, 0, 5], 8)
    assert wl.mask.shape == (3, 8) and wl.items.shape == (3, 8)
    np.testing.assert_array_equal(np.asarray(wl.count), [3, 0, 5])
    np.testing.assert_array_equal(np.asarray(wl.items[0]),
                                  [0, 1, 2, 8, 8, 8, 8, 8])
    assert not np.asarray(wl.mask[1]).any()


def test_get_dataset_batch_builds_and_shares():
    gs = get_dataset_batch(
        ["europe_osm_s", ("europe_osm_s", {"seed": 3}), "europe_osm_s"],
        scale=0.01)
    assert len(gs) == 3
    assert gs[0] is gs[2]                 # same cell -> same cached Graph
    assert gs[0] is not gs[1]             # override produced a new cell


# ---------------------------------------------------------------------------
# tile_rows: static key membership + engine-level parity (PR-6 satellites)
# ---------------------------------------------------------------------------

def test_tile_rows_rides_static_key():
    """Two runs tuned (or pinned) to different tiles must never collide in
    the session compile cache — tile_rows is part of every jit key."""
    keys = {ExecutionSpec(regime="host", tile_rows=t).static_key()
            for t in (8, 32, 128, "auto", None)}
    assert len(keys) == 5


def test_tile_rows_specializes_session_cache(graphs):
    g = graphs["europe_osm_s"]
    s = Session()
    a = s.run(ExecutionSpec(regime="host", fused=True, tile_rows=8), g)
    b = s.run(ExecutionSpec(regime="host", fused=True, tile_rows=128), g)
    _same_result(a, b)                    # perf knob only: same trajectory


@pytest.mark.parametrize("regime", ["host", "outlined"])
def test_tile_rows_pallas_bit_identical_to_jnp(graphs, regime):
    """The tile height is a pure performance knob: every (impl, tile_rows)
    combination inside the fused family produces the SAME coloring."""
    g = graphs["hollywood-2009_s"]        # hub-heavy: hub variant on
    kw = dict(regime=regime, fused=True)
    base = default_session().run(ExecutionSpec(impl="jnp", **kw), g)
    for tr in (8, 128, "auto"):
        got = default_session().run(
            ExecutionSpec(impl="pallas", tile_rows=tr, **kw), g)
        np.testing.assert_array_equal(base.colors, got.colors)
        assert got.iterations == base.iterations
        assert got.mode_trace == base.mode_trace
    verify_coloring(g, base.colors)
