"""Per-kernel validation: Pallas (interpret on CPU) vs pure-jnp oracle,
swept over shapes/dtypes, plus hypothesis property tests (property tests
skip individually, with a reason, when hypothesis is absent — see _hyp)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hyp import given, settings, st

from repro.kernels import ops, ref
from repro.kernels.compact import compact_pallas
from repro.kernels.conflict import conflict_pallas
from repro.kernels.mex_window import mex_window_pallas


def _rand_case(rng, r, k, w, cmax=300):
    nc = rng.integers(-2, cmax, size=(r, k)).astype(np.int32)
    base = (rng.integers(0, max(cmax // w, 1), size=(r,)) * w).astype(np.int32)
    extra = rng.random((r, w)) < 0.25
    return jnp.asarray(nc), jnp.asarray(base), jnp.asarray(extra)


@pytest.mark.parametrize("r", [1, 7, 32, 100, 257])
@pytest.mark.parametrize("k", [1, 8, 40, 128])
@pytest.mark.parametrize("w", [128, 256])
def test_mex_window_matches_ref(r, k, w):
    rng = np.random.default_rng(r * 1000 + k * 10 + w)
    nc, base, extra = _rand_case(rng, r, k, w)
    got = mex_window_pallas(nc, base, extra, w, interpret=True)
    want = ref.mex_window_ref(nc, base, extra, w)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("tile_rows", [8, 16, 64])
def test_mex_window_tile_sweep(tile_rows):
    rng = np.random.default_rng(tile_rows)
    nc, base, extra = _rand_case(rng, 130, 24, 128)
    got = mex_window_pallas(nc, base, extra, 128, tile_rows=tile_rows,
                            interpret=True)
    want = ref.mex_window_ref(nc, base, extra, 128)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_mex_result_is_free_and_minimal():
    """mex property: the returned color slot is not forbidden, and every
    smaller slot IS forbidden."""
    rng = np.random.default_rng(3)
    nc, base, extra = _rand_case(rng, 200, 16, 128)
    first = np.asarray(ref.mex_window_ref(nc, base, extra, 128))
    ncn, basen, extran = map(np.asarray, (nc, base, extra))
    for i in range(200):
        rel = ncn[i] - basen[i]
        forb = set(rel[(ncn[i] >= 0) & (rel >= 0) & (rel < 128)].tolist())
        forb |= set(np.nonzero(extran[i])[0].tolist())
        if first[i] < 0:
            assert len(forb) == 128
        else:
            assert first[i] not in forb
            assert all(s in forb for s in range(first[i]))


@pytest.mark.parametrize("r,k", [(1, 1), (16, 8), (100, 33), (300, 128)])
def test_conflict_matches_ref(r, k):
    rng = np.random.default_rng(r + k)
    nc = rng.integers(-2, 30, size=(r, k)).astype(np.int32)
    npr = rng.integers(-1, 100, size=(r, k)).astype(np.int32)
    nid = rng.integers(0, r + 1, size=(r, k)).astype(np.int32)
    cu = rng.integers(-2, 30, size=(r,)).astype(np.int32)
    pu = rng.integers(0, 100, size=(r,)).astype(np.int32)
    ids = np.arange(r, dtype=np.int32)
    args = tuple(map(jnp.asarray, (nc, npr, nid, cu, pu, ids)))
    got = conflict_pallas(*args, interpret=True)
    want = ref.conflict_ref(*args)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("n", [1, 5, 256, 1000, 4096])
@pytest.mark.parametrize("density", [0.0, 0.05, 0.5, 1.0])
def test_compact_matches_ref(n, density):
    rng = np.random.default_rng(n)
    mask = jnp.asarray(rng.random(n) < density)
    got_i, got_c = compact_pallas(mask, interpret=True)
    want_i, want_c = ref.compact_ref(mask)
    assert int(got_c) == int(want_c)
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))


@pytest.mark.parametrize("tile", [128, 256, 512])
def test_compact_tile_sweep(tile):
    rng = np.random.default_rng(tile)
    mask = jnp.asarray(rng.random(3000) < 0.3)
    got_i, got_c = compact_pallas(mask, tile=tile, interpret=True)
    want_i, want_c = ref.compact_ref(mask)
    assert int(got_c) == int(want_c)
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))


@settings(max_examples=30, deadline=None)
@given(st.lists(st.booleans(), min_size=1, max_size=600))
def test_compact_property(bits):
    """Compaction invariants: sorted valid prefix = indices of set bits,
    sentinel tail, count = popcount."""
    mask = jnp.asarray(np.array(bits, dtype=bool))
    items, count = compact_pallas(mask, interpret=True)
    items = np.asarray(items)
    c = int(count)
    assert c == sum(bits)
    np.testing.assert_array_equal(items[:c], np.nonzero(bits)[0])
    assert (items[c:] == len(bits)).all()


@settings(max_examples=25, deadline=None)
@given(st.integers(1, 80), st.integers(1, 20), st.data())
def test_mex_property_hypothesis(r, k, data):
    seed = data.draw(st.integers(0, 2**31 - 1))
    rng = np.random.default_rng(seed)
    nc, base, extra = _rand_case(rng, r, k, 128)
    got = mex_window_pallas(nc, base, extra, 128, interpret=True)
    want = ref.mex_window_ref(nc, base, extra, 128)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_ops_jit_wrappers():
    rng = np.random.default_rng(0)
    nc, base, extra = _rand_case(rng, 64, 8, 128)
    first, has = ops.mex_window(nc, base, extra, 128)
    assert bool(jnp.all((first >= 0) == has))
    mask = jnp.asarray(rng.random(512) < 0.4)
    items, count = ops.compact(mask)
    want_i, want_c = ref.compact_ref(mask)
    np.testing.assert_array_equal(np.asarray(items), np.asarray(want_i))


@pytest.mark.parametrize("r,k", [(1, 1), (17, 8), (100, 40), (256, 128)])
def test_frontier_probe_matches_ref(r, k):
    from repro.kernels.frontier import frontier_probe_pallas
    rng = np.random.default_rng(r * 7 + k)
    nbr = jnp.asarray(rng.random((r, k)) < 0.15)
    unv = jnp.asarray(rng.random(r) < 0.5)
    got = frontier_probe_pallas(nbr, unv, interpret=True)
    want = ref.frontier_probe_ref(nbr, unv)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 60), st.integers(1, 16), st.data())
def test_frontier_probe_property(r, k, data):
    from repro.kernels.frontier import frontier_probe_pallas
    seed = data.draw(st.integers(0, 2**31 - 1))
    rng = np.random.default_rng(seed)
    nbr = jnp.asarray(rng.random((r, k)) < 0.3)
    unv = jnp.asarray(rng.random(r) < 0.5)
    got = np.asarray(frontier_probe_pallas(nbr, unv, interpret=True))
    want = np.asarray(nbr).any(1) & np.asarray(unv)
    np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# compact boundary conditions (PR-6 satellite): the carry machinery at its
# edges — nothing to emit, everything emitted, and ragged tile tails
# ---------------------------------------------------------------------------

def test_compact_empty_mask():
    items, count = compact_pallas(jnp.zeros((257,), bool), interpret=True)
    assert int(count) == 0
    assert (np.asarray(items) == 257).all()          # all-sentinel tail


def test_compact_all_true_mask():
    """count == capacity: every slot of the items array is a real index —
    the wrapper's sentinel masking must leave none standing."""
    n = 300                                          # not a tile multiple
    items, count = compact_pallas(jnp.ones((n,), bool), interpret=True)
    assert int(count) == n
    np.testing.assert_array_equal(np.asarray(items), np.arange(n))


@pytest.mark.parametrize("n", [1, 255, 257, 300])
@pytest.mark.parametrize("tile", [128, 256])
def test_compact_ragged_lengths(n, tile):
    """Lengths not a multiple of ``tile``: the zero-padded tail tiles must
    contribute nothing (padded indices can never appear in the output)."""
    rng = np.random.default_rng(n * tile)
    mask = jnp.asarray(rng.random(n) < 0.5)
    got_i, got_c = compact_pallas(mask, tile=tile, interpret=True)
    want_i, want_c = ref.compact_ref(mask)
    assert int(got_c) == int(want_c)
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))
    assert (np.asarray(got_i)[int(got_c):] == n).all()


# ---------------------------------------------------------------------------
# fused resolve+assign kernel vs oracle
# ---------------------------------------------------------------------------

def _fused_case(rng, r, k, w, *, hub=False, sparse=False):
    """Random operand tuple in the shape the step impls feed the fused
    kernels: dense style (ids = iota, all rows real) or sparse style
    (sentinel ids on invalid rows, active = valid)."""
    n = r
    nc = rng.integers(-2, 40, size=(r, k)).astype(np.int32)
    npr = rng.integers(-1, 100, size=(r, k)).astype(np.int32)
    nid = rng.integers(0, n + 1, size=(r, k)).astype(np.int32)
    base = (rng.integers(0, 3, size=(r,)) * w).astype(np.int32)
    cu = rng.integers(-2, 40, size=(r,)).astype(np.int32)
    pu = rng.integers(0, 100, size=(r,)).astype(np.int32)
    if sparse:
        valid = rng.random(r) < 0.7
        ids = np.where(valid, rng.integers(0, n, size=(r,)), n)
        active = valid
    else:
        ids = np.arange(r)
        active = rng.random(r) < 0.85
    ids = ids.astype(np.int32)
    pending = active & (cu >= 0)
    extra = (rng.random((r, w)) < 0.2) if hub else None
    hl = ((rng.random(r) < 0.15) & active) if hub else None
    out = (nc, npr, nid, base, cu, pu, ids, active, pending, extra, hl)
    return tuple(None if a is None else jnp.asarray(a) for a in out), n


@pytest.mark.parametrize("r,k", [(1, 1), (33, 8), (100, 24)])
def test_fused_step_matches_ref(r, k):
    from repro.kernels.fused_step import fused_step_pallas
    rng = np.random.default_rng(r * 13 + k)
    (nc, npr, nid, base, cu, pu, ids, _a, pending, _e, _h), _n = \
        _fused_case(rng, r, k, 64, hub=True)
    extra = jnp.asarray(rng.random((r, 64)) < 0.2)
    got_l, got_f = fused_step_pallas(nc, npr, nid, base, cu, pu, ids,
                                     pending, extra, 64, interpret=True)
    want_l, want_f = ref.fused_step_ref(nc, npr, nid, base, cu, pu, ids,
                                        pending, extra, 64)
    np.testing.assert_array_equal(np.asarray(got_l), np.asarray(want_l))
    np.testing.assert_array_equal(np.asarray(got_f), np.asarray(want_f))


def _assert_fused_compact_parity(case, n, *, capacity, tile_rows=32, w=64):
    """The kernel, then the caller's emission (``compact_items`` of the
    survivors), against the reference step + compaction."""
    from repro.core.worklist import compact_items
    from repro.kernels.fused_compact import fused_compact_pallas
    new_c, new_b, still = fused_compact_pallas(*case, w, tile_rows=tile_rows,
                                               interpret=True)
    ids = case[6]
    got = (new_c, new_b, still) + compact_items(ids, still, n, capacity)
    want = ref.fused_compact_ref(*case, w, capacity=capacity, n_sentinel=n)
    for g, x, name in zip(got, want,
                          ("new_c", "new_base", "still", "items", "count")):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(x),
                                      err_msg=name)


@pytest.mark.parametrize("hub", [False, True])
@pytest.mark.parametrize("r,k", [(1, 1), (33, 8), (100, 24)])
def test_fused_compact_matches_ref_dense(r, k, hub):
    rng = np.random.default_rng(r * 31 + k + hub)
    case, n = _fused_case(rng, r, k, 64, hub=hub)
    _assert_fused_compact_parity(case, n, capacity=r)


@pytest.mark.parametrize("hub", [False, True])
def test_fused_compact_matches_ref_sparse(hub):
    """Sparse-style operands: sentinel ids on invalid rows never emit, and
    the compacted block matches ``compact_items`` semantics."""
    rng = np.random.default_rng(77 + hub)
    case, n = _fused_case(rng, 90, 16, 64, hub=hub, sparse=True)
    _assert_fused_compact_parity(case, n, capacity=90)


@pytest.mark.parametrize("tile_rows", [8, 16, 64])
def test_fused_compact_tile_sweep(tile_rows):
    rng = np.random.default_rng(tile_rows)
    case, n = _fused_case(rng, 130, 12, 64, hub=True)
    _assert_fused_compact_parity(case, n, capacity=130, tile_rows=tile_rows)


def test_fused_compact_truncating_capacity():
    """count may exceed capacity (compact_mask reports the full popcount
    while the items block truncates) — the kernel must store the FIRST
    ``capacity`` survivors in ascending order and still report the total."""
    rng = np.random.default_rng(5)
    case, n = _fused_case(rng, 96, 8, 64)
    _assert_fused_compact_parity(case, n, capacity=40)


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 70), st.integers(1, 12), st.booleans(), st.data())
def test_fused_compact_property(r, k, hub, data):
    seed = data.draw(st.integers(0, 2**31 - 1))
    rng = np.random.default_rng(seed)
    case, n = _fused_case(rng, r, k, 64, hub=hub,
                          sparse=data.draw(st.booleans()))
    _assert_fused_compact_parity(case, n, capacity=r)


# ---------------------------------------------------------------------------
# csr-segment edge cores vs dense oracles
# ---------------------------------------------------------------------------

def _edge_case(rng, e, n, w):
    """A CSR edge list (sources ascending, the segment layout's contract)
    with per-row window bases and colors: a row's color lies in its
    window or is negative, as every row a step may uncolor."""
    es = np.sort(rng.integers(0, n, size=(e,))).astype(np.int32)
    ed = rng.integers(0, n, size=(e,)).astype(np.int32)
    base = (rng.integers(0, 3, size=(n,)) * w).astype(np.int32)
    c_rows = np.where(rng.random(n) < 0.3, -1,
                      base + rng.integers(0, w, size=(n,))).astype(np.int32)
    cv_e = rng.integers(-2, 3 * w, size=(e,)).astype(np.int32)
    prio = rng.integers(0, 50, size=(n,)).astype(np.int32)
    return tuple(map(jnp.asarray, (es, ed, c_rows, cv_e, prio, base)))


@pytest.mark.parametrize("e,n", [(1, 1), (40, 10), (500, 64)])
def test_edge_cores_match_ref(e, n):
    from repro.kernels import csr_segment as kcsr
    rng = np.random.default_rng(e + n)
    es, ed, c_rows, cv_e, prio, base = _edge_case(rng, e, n, 32)
    deg = np.bincount(np.asarray(es), minlength=n).astype(np.int32)
    start = jnp.asarray(np.cumsum(deg) - deg, jnp.int32)
    deg = jnp.asarray(deg)
    cu_e, pu_e, pv_e, base_e = c_rows[es], prio[es], prio[ed], base[es]
    wins_e = kcsr.wins(pu_e, pv_e, es, ed)
    got_c = kcsr.edge_conflict(es, cv_e, wins_e, base_e, c_rows, base,
                               start, deg, 32)
    want_c = ref.edge_conflict_ref(es, ed, cu_e, cv_e, pu_e, pv_e, n)
    np.testing.assert_array_equal(np.asarray(got_c), np.asarray(want_c))
    got_f = kcsr.edge_forbidden(es, cv_e, base_e, start, deg, 32)
    want_f = ref.edge_forbidden_ref(es, cv_e, base_e, n, 32)
    np.testing.assert_array_equal(np.asarray(got_f), np.asarray(want_f))
    # the one-pass core is exactly the pair from one shared sweep
    fc, ff = kcsr.edge_fused(es, cv_e, wins_e, base_e, c_rows, base, start,
                             deg, 32)
    np.testing.assert_array_equal(np.asarray(fc), np.asarray(want_c))
    np.testing.assert_array_equal(np.asarray(ff), np.asarray(want_f))


@pytest.mark.parametrize("n", [1, 2, 7, 1000])
def test_segment_primitives_match_numpy(n):
    """prefix_sum / spread / segment_or against their numpy meaning."""
    from repro.kernels import csr_segment as kcsr
    rng = np.random.default_rng(n)
    x = rng.integers(-50, 50, size=n).astype(np.int32)
    np.testing.assert_array_equal(
        np.asarray(kcsr.prefix_sum(jnp.asarray(x))), np.cumsum(x))
    counts = rng.integers(0, 4, size=n).astype(np.int32)   # empty owners too
    starts = (np.cumsum(counts) - counts).astype(np.int32)
    m = int(counts.sum()) + 3                              # + a pad tail
    vals = rng.integers(-9, 9, size=n).astype(np.int32)
    owner = np.repeat(np.arange(n), counts)
    # entries past the last range take the last owner's value
    want = np.concatenate([vals[owner], np.full(3, vals[-1])])
    got = kcsr.spread(jnp.asarray(vals), jnp.asarray(starts), m)
    np.testing.assert_array_equal(np.asarray(got), want)
    bits = rng.integers(0, 2 ** 31, size=owner.size).astype(np.uint32)
    (acc,) = kcsr.segment_or((jnp.asarray(bits),), jnp.asarray(owner),
                             int(counts.max()))
    got = kcsr.read_first(acc, jnp.asarray(starts), jnp.asarray(counts > 0))
    want = [np.bitwise_or.reduce(bits[owner == i]) if counts[i] else 0
            for i in range(n)]
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want,
                                                              np.uint32))


@pytest.mark.parametrize("fused", [False, True])
@pytest.mark.parametrize("layout,ell_cap", [("csr-segment", None),
                                            ("ell-tail", 8)])
def test_packed_sparse_steps_match_full_sweep(layout, ell_cap, fused):
    """Sparse steps that read only their rows' entries (the packed form)
    color exactly like sweeps over every edge / tail entry. A geometric
    graph: its top 1024 rows own few enough entries to pack."""
    import dataclasses

    from repro.algos import get_algorithm
    from repro.core import ipgc
    from repro.exec import ExecutionSpec, Session
    from repro.graphs import generators, ingest
    from repro.graphs.layout import run_pipeline

    src, dst, n = generators.edges_rgg(8192, 16, 0)
    g = run_pipeline(ingest.from_arrays(src, dst, n, name="rgg"),
                     layout=layout, ell_cap=ell_cap)
    ig = get_algorithm("ipgc").prepare(g)
    if layout == "csr-segment":
        packed = ipgc.packed_cap(ig, 1024, ig.edge_dst.shape[0])
    else:
        packed = ipgc._hub_packed(ig, jnp.zeros((1024,), jnp.int32))
    assert packed is not None          # the floor bucket does pack
    spec = ExecutionSpec(regime="host", fused=fused, window=32)
    sess = Session()
    got = sess.run(spec, ig)
    want = sess.run(spec, dataclasses.replace(ig, seg_bound=()))
    np.testing.assert_array_equal(got.colors, want.colors)
    assert (got.iterations, got.mode_trace) == (want.iterations,
                                                want.mode_trace)


@pytest.mark.parametrize("fused", [False, True])
@pytest.mark.parametrize("regime", ["host", "outlined"])
def test_chunked_csr_sparse_steps_match_full_sweep(packed_chunk, regime,
                                                   fused):
    """The csr-segment packed sparse step, reading its rows' edges in
    chunks of 16 slots (rows of up to 45 edges span up to four chunks),
    colors exactly like sweeps over every edge, in the host loop and in
    the outlined regime's device loop."""
    import dataclasses

    from repro.algos import get_algorithm
    from repro.core import ipgc
    from repro.exec import ExecutionSpec, Session

    packed_chunk(16)
    ig = get_algorithm("ipgc").prepare(_rgg(8192, "csr-segment", None))
    assert int(np.asarray(ig.degrees).max()) > 2 * 16
    assert ipgc.packed_cap(ig, 1024, ig.edge_dst.shape[0]) is not None
    spec = ExecutionSpec(regime=regime, fused=fused, window=32)
    sess = Session()
    got = sess.run(spec, ig)
    want = sess.run(spec, dataclasses.replace(ig, seg_bound=()))
    assert "S" in got.mode_trace
    np.testing.assert_array_equal(got.colors, want.colors)
    assert (got.iterations, got.mode_trace) == (want.iterations,
                                                want.mode_trace)


def _hub_rgg():
    """A geometric graph with a 400-edge hub (node 0) and 64 isolated
    nodes (the last ids), on the csr-segment layout."""
    from repro.graphs import generators
    src, dst, n = generators.edges_rgg(8192, 16, 0)
    star = np.arange(1, 401)
    return _rgg(None, "csr-segment", None, edges=(
        np.concatenate([src, np.zeros_like(star)]),
        np.concatenate([dst, star]), n + 64))


@pytest.mark.parametrize("fused", [False, True])
@pytest.mark.parametrize("case", ["spans", "live0"])
def test_chunk_loop_cases_match_full_sweep(case, fused):
    """One csr-segment sparse step on a hand-made worklist, read in
    chunks, against the same step sweeping every edge: colors, bases and
    worklist agree. ``spans``: the hub spans four chunks, a chunk
    boundary falls on the next row's start, an owner has no entries and
    the worklist ends in pad rows; ``live0``: its rows own no entries."""
    import dataclasses

    from repro.algos import get_algorithm
    from repro.core import ipgc
    from repro.core.worklist import Worklist

    alg = get_algorithm("ipgc")
    ig = alg.prepare(_hub_rgg())
    n = ig.n_nodes
    deg = np.asarray(ig.degrees)
    assert deg[0] == 400 and (deg[-64:] == 0).all()
    colors, base, wl = alg.init_state(ig)
    dense, _ = ipgc.step_fns(fused)
    colors, base, _ = dense(ig, colors, base, wl, window=32)
    if case == "spans":
        # hub, a row of degree 16, an isolated row, rows of degree 12+
        rest = [r for r in range(1, n - 64) if deg[r] >= 12][:300]
        items = [0, int(np.flatnonzero(deg == 16)[0]), n - 1] + rest
        chunk = 100                     # off[1] = 400 = 4 chunks
    else:
        items = list(range(n - 64, n))
        chunk = 64
    items = np.asarray(items + [n] * (1024 - len(items)), np.int32)
    live = items[items < n]
    counts = np.where(items < n, deg[np.minimum(items, n - 1)], 0)
    off = np.cumsum(counts) - counts
    if case == "spans":
        assert counts[0] > 2 * chunk and off[1] % chunk == 0 < counts[1]
        assert (counts[:live.size] == 0).any() and live.size < items.size
        assert counts.sum() > 3 * chunk
    else:
        assert counts.sum() == 0
    mask = np.zeros(n, bool)
    mask[live] = True
    wl = Worklist(mask=jnp.asarray(mask), items=jnp.asarray(items),
                  count=jnp.int32(live.size))
    got = ipgc._packed_csr_step(ig, colors, base, wl, window=32,
                                fused=fused, chunk=chunk)
    want = ipgc._csr_step(dataclasses.replace(ig, seg_bound=()), colors,
                          base, wl, window=32, fused=fused, sparse=True)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _rgg(n, layout, ell_cap, edges=None):
    from repro.graphs import generators, ingest
    from repro.graphs.layout import run_pipeline
    src, dst, n = edges if edges else generators.edges_rgg(n, 16, 0)
    return run_pipeline(ingest.from_arrays(src, dst, n, name="rgg"),
                        layout=layout, ell_cap=ell_cap)


@pytest.mark.parametrize("fused", [False, True])
@pytest.mark.parametrize("layout,ell_cap", [("csr-segment", None),
                                            ("ell-tail", 8)])
def test_colored_rows_stay_in_window(layout, ell_cap, fused):
    """The invariant the window-bit conflict tests rest on
    (``csr_segment.edge_conflict``, ``ipgc._packed_lose``): after every
    step a colored row's color lies in ``[base, base + W)``, because base
    only advances for rows left uncolored. Dense, full-sweep sparse and
    packed sparse steps over a whole run, with a window small enough
    that bases advance."""
    from repro.algos import get_algorithm
    from repro.core import ipgc
    from repro.core.worklist import resize_items

    w = 8
    ig = get_algorithm("ipgc").prepare(_rgg(8192, layout, ell_cap))
    n = ig.n_nodes
    dense, sparse = ipgc.step_fns(fused)
    colors, base, wl = get_algorithm("ipgc").init_state(ig)
    steps = advanced = 0
    while int(wl.count) > 0:
        if steps < 2:
            step = dense
        else:
            step = sparse
            if int(wl.count) <= 1024 < wl.capacity:
                wl = resize_items(wl, 1024, n)      # the packed bucket
        colors, base, wl = step(ig, colors, base, wl, window=w)
        c, b = np.asarray(colors[:n]), np.asarray(base)
        on = c >= 0
        assert ((b[on] <= c[on]) & (c[on] < b[on] + w)).all(), steps
        advanced = max(advanced, int(b.max()))
        steps += 1
    assert wl.capacity == 1024 and advanced >= 2 * w


def test_same_shape_graphs_share_compiled_steps():
    """``seg_bound`` is a static step argument: rounded up its ladder, two
    graphs with the same shapes but different degrees trace each step
    once; with exact bounds the second graph would retrace."""
    import dataclasses

    from repro.algos import get_algorithm
    from repro.core import ipgc

    g_a = _rgg(4096, "csr-segment", None)
    # move one edge (u, v) to (u, w), w the top-degree node: same node
    # and edge counts, another degree sequence
    deg = np.asarray(g_a.arrays.degrees)
    src = np.repeat(np.arange(g_a.n_nodes), deg)
    dst = np.asarray(g_a.arrays.col_idx)
    w = int(np.argmax(deg))
    near = set(dst[src == w].tolist()) | {w}
    i = next(i for i in range(src.size) if src[i] < dst[i]
             and src[i] not in near and dst[i] != w)
    keep = ~(((src == src[i]) & (dst == dst[i]))
             | ((src == dst[i]) & (dst == src[i])))
    s2 = np.concatenate([src[keep & (src < dst)], [src[i]]])
    d2 = np.concatenate([dst[keep & (src < dst)], [w]])
    g_b = _rgg(None, "csr-segment", None, edges=(s2, d2, g_a.n_nodes))
    assert (g_b.n_nodes, g_b.n_edges) == (g_a.n_nodes, g_a.n_edges)
    assert int(np.asarray(g_b.arrays.degrees).max()) == int(deg.max()) + 1

    alg = get_algorithm("ipgc")
    ig_a, ig_b = alg.prepare(g_a), alg.prepare(g_b)
    assert ig_a.seg_bound == ig_b.seg_bound

    def trace_all(ig):
        colors, base, wl = alg.init_state(ig)
        small = wl._replace(items=wl.items[:1024])
        for fused in (False, True):
            dense, sparse = ipgc.step_fns(fused)
            dense(ig, colors, base, wl, window=32)
            sparse(ig, colors, base, small, window=32)

    def exact(g):
        top = np.cumsum(np.sort(np.asarray(g.arrays.degrees))[::-1])
        return tuple(int(top[min(2 ** k, top.size) - 1])
                     for k in range(len(ig_a.seg_bound)))

    assert exact(g_a) != exact(g_b)
    trace_all(ig_a)
    trace_all(dataclasses.replace(ig_a, seg_bound=exact(g_a)))
    with ipgc.LAUNCH_COUNTS.scope() as traced:
        trace_all(ig_b)
        assert traced.total() == 0
        trace_all(dataclasses.replace(ig_b, seg_bound=exact(g_b)))
        assert traced.total() > 0


@pytest.mark.parametrize("r,k", [(1, 1), (33, 8), (200, 40)])
def test_jpl_extrema_matches_ref(r, k):
    from repro.kernels.jpl_prio import jpl_extrema_pallas
    rng = np.random.default_rng(r + k)
    npr = jnp.asarray(rng.integers(-1, 1000, size=(r, k)).astype(np.int32))
    got_mx, got_mn = jpl_extrema_pallas(npr, interpret=True)
    want_mx, want_mn = ref.jpl_extrema_ref(npr)
    np.testing.assert_array_equal(np.asarray(got_mx), np.asarray(want_mx))
    np.testing.assert_array_equal(np.asarray(got_mn), np.asarray(want_mn))
