"""Boundary-exchange contract (DESIGN.md §13): partition-time ghost/
boundary sets, the capacity ladder, packed-vs-dense publication
bit-identity across algorithms and exchange knobs, overflow fallback
determinism, and the path-aware byte accounting."""
import numpy as np
import pytest

from tests._hyp import HAVE_HYPOTHESIS, HYPOTHESIS_SKIP_REASON, given, \
    settings, st
from tests.test_distributed import _run_forced_devices

from repro.core import verify_coloring
from repro.exec import ExecutionSpec, default_session
from repro.graphs import build_graph, make_graph
from repro.graphs.partition import (boundary_capacities, boundary_info,
                                    exchange_break_even, ghost_ids,
                                    prepare_partition)


def _random_graph(seed: int, n: int, m: int):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n, m)
    dst = rng.integers(0, n, m)
    return build_graph(src, dst, n, name=f"rb{seed}", ell_cap=8)


def _check_ghost_contract(g, n_shards: int):
    """Symmetry + completeness of the ghost/boundary sets against a
    direct numpy recount of the cross edges."""
    n = g.n_nodes
    assert n % n_shards == 0
    blk = n // n_shards
    info = boundary_info(g, n_shards)
    deg = np.asarray(g.arrays.degrees)
    src = np.repeat(np.arange(n, dtype=np.int64), deg)
    dst = np.asarray(g.arrays.col_idx)[:src.size]
    cross = (src // blk) != (dst // blk)
    ghosts = [ghost_ids(g, n_shards, s) for s in range(n_shards)]

    # completeness: every cross edge's endpoint is a ghost of the shard
    # that owns the other endpoint, and both endpoints are boundary
    for u, v in zip(src[cross], dst[cross]):
        assert v in set(ghosts[u // blk])
        assert info.is_boundary[u] and info.is_boundary[v]
    # boundary <-> member of some other shard's ghost set
    all_ghosts = set()
    for gs in ghosts:
        all_ghosts.update(gs.tolist())
    assert all_ghosts == set(np.nonzero(info.is_boundary)[0].tolist())
    # symmetry (undirected adjacency): v ghost-of-s implies some owned
    # node of s is a ghost of v's owner
    for s, gs in enumerate(ghosts):
        for v in gs.tolist():
            assert v // blk != s
            assert any(u // blk == s
                       for u in ghost_ids(g, n_shards, v // blk).tolist())
    # counts are the per-shard boundary populations
    owner = np.arange(n) // blk
    for s in range(n_shards):
        assert info.counts[s] == int(
            np.count_nonzero(info.is_boundary & (owner == s)))


@pytest.mark.parametrize("seed,n_shards", [(0, 2), (1, 4), (2, 8)])
def test_ghost_sets_fixed_draw(seed, n_shards):
    g = _random_graph(seed, 64 * n_shards, 600)
    _check_ghost_contract(g, n_shards)


def test_ghost_sets_after_uneven_partition():
    """n % shards != 0 flows through prepare_partition's padding; the
    padded isolates join no edges, so they are never boundary."""
    g0 = _random_graph(3, 203, 900)                 # 203 % 4 != 0
    g, _ = prepare_partition(g0, 4)
    assert g.n_nodes % 4 == 0 and g.n_nodes >= 203
    _check_ghost_contract(g, 4)
    info = boundary_info(g, 4)
    # the padding isolates join no edges, so they are never boundary
    # (prepare_partition relabels, so find them by their zero degree)
    assert not info.is_boundary[np.asarray(g.arrays.degrees) == 0].any()


def test_boundary_info_rejects_undivisible():
    g = _random_graph(4, 10, 40)
    with pytest.raises(ValueError):
        boundary_info(g, 4)


@pytest.mark.skipif(not HAVE_HYPOTHESIS, reason=HYPOTHESIS_SKIP_REASON)
@given(seed=st.integers(0, 2**16), n_shards=st.sampled_from([2, 4, 8]),
       nodes_per_shard=st.integers(4, 32))
@settings(max_examples=25, deadline=None)
def test_ghost_sets_property(seed, n_shards, nodes_per_shard):
    n = n_shards * nodes_per_shard
    g = _random_graph(seed, n, 4 * n)
    _check_ghost_contract(g, n_shards)


def test_capacity_ladder_properties():
    for n_shards in (2, 4, 8):
        g = _random_graph(5, 64 * n_shards, 2000)
        info = boundary_info(g, n_shards)
        caps = info.capacities
        blk = g.n_nodes // n_shards
        assert caps == tuple(sorted(set(caps), reverse=True))
        assert caps[-1] >= 1
        # the top rung fits the worst shard... or is clamped by the
        # break-even point past which packing cannot beat a dense swap
        be = exchange_break_even(g.n_nodes, n_shards)
        assert caps[0] <= blk
        assert caps[0] <= max(-(-info.max_boundary // 8) * 8, 8) \
            or caps[0] <= max(-(-be // 8) * 8, 8)
        # explicit ladder: halving, 8-aligned, deduped
        ladder = boundary_capacities(256, 100, 10_000, 2)
        assert ladder[0] == 104 and ladder[-1] == 8
        assert all(c % 8 == 0 for c in ladder)


def test_break_even_scales_inverse_with_shards():
    assert exchange_break_even(10_000, 2) > exchange_break_even(10_000, 8)
    assert exchange_break_even(16, 8) == 8          # floor


# ---------------------------------------------------------------------------
# exchange-mode bit-identity (the tentpole acceptance)
# ---------------------------------------------------------------------------

def test_exchange_modes_bit_identical_single_shard():
    g = make_graph("kron_g500-logn21_s", scale=0.01)
    for algo in ("ipgc", "spec-greedy", "jpl"):
        g2, relabel = prepare_partition(g, 1)
        fused = None if algo == "jpl" else True
        r_h = default_session().run(ExecutionSpec(algo=algo, fused=fused),
                                    g2)
        ref = r_h.colors[relabel[:g.n_nodes]]
        for ex in ("dense", "boundary", "auto"):
            r = default_session().run(
                ExecutionSpec(regime="dist", algo=algo, n_shards=1,
                              exchange=ex), g)
            verify_coloring(g, r.colors, context=f"{algo}/{ex}")
            np.testing.assert_array_equal(r.colors, ref)
            assert r.iterations == r_h.iterations, (algo, ex)
            assert r.mode_trace == r_h.mode_trace, (algo, ex)
            assert len(r.exchange_trace) == r.iterations
            assert len(r.exchange_bytes) == r.iterations


def test_exchange_modes_bit_identical_multishard_subprocess():
    """Every algorithm x exchange knob on 1/2/8 simulated devices is
    bit-identical to the host engine AND to the dense-exchange path."""
    code = """
import numpy as np
from repro.core import verify_coloring
from repro.exec import ExecutionSpec, default_session
from repro.graphs import make_graph
from repro.graphs.partition import prepare_partition
g = make_graph("europe_osm_s", scale=0.01)
for algo in ("ipgc", "spec-greedy", "jpl"):
    for s in (1, 2, 8):
        g2, relabel = prepare_partition(g, s)
        fused = None if algo == "jpl" else True
        r_h = default_session().run(ExecutionSpec(algo=algo, fused=fused),
                                    g2)
        ref = r_h.colors[relabel[:g.n_nodes]]
        for ex in ("dense", "boundary", "auto"):
            r = default_session().run(
                ExecutionSpec(regime="dist", algo=algo, n_shards=s,
                              exchange=ex), g)
            verify_coloring(g, r.colors, context=f"{algo}/{ex}/{s}")
            np.testing.assert_array_equal(r.colors, ref)
            assert r.iterations == r_h.iterations, (algo, ex, s)
            assert r.mode_trace == r_h.mode_trace, (algo, ex, s)
print("EXCHANGE_MODES_OK")
"""
    assert "EXCHANGE_MODES_OK" in _run_forced_devices(code)


def test_overflow_falls_back_dense_deterministically():
    """A capacity the boundary population overflows must not corrupt the
    run: the step publishes via the dense swap instead, bit-identically,
    every time (correctness never depends on the capacity guess)."""
    import jax
    import jax.numpy as jnp
    from repro.core import ipgc
    from repro.core.distributed import (make_dist_dense_step,
                                        views_to_colors)
    from repro.core.worklist import full_worklist
    g0 = _random_graph(6, 300, 2400)
    g, _ = prepare_partition(g0, 1)
    ig = ipgc.prepare(g)
    n = ig.n_nodes
    info = boundary_info(g, 1)
    mesh = jax.make_mesh((1,), ("data",))
    # thresh lets everything through; bcap=8 is guaranteed too small for
    # the first dense sweep of a 300-node random graph
    step = make_dist_dense_step(ig, mesh, ("data",), window=64, fused=True,
                                exchange="boundary", boundary=info,
                                thresh=n + 1)
    ref_step = ipgc.step_fns(True)[0]
    outs = []
    for _ in range(2):                               # determinism
        views = jnp.broadcast_to(ipgc.init_colors(n), (1, n + 1))
        cr = ipgc.init_colors(n)
        bd = br = jnp.zeros((n,), jnp.int32)
        wd, wr = full_worklist(n), full_worklist(n)
        for _i in range(3):
            views, bd, wd, xs = step(views, bd, wd, bcap=8)
            cr, br, wr = ref_step(ig, cr, br, wr, window=64, impl="jnp")
            np.testing.assert_array_equal(views_to_colors(views, 1, n),
                                          np.asarray(cr[:n]))
            assert int(wd.count) == int(wr.count)
        assert int(xs[1]) >= 0
        outs.append(views_to_colors(views, 1, n))
    np.testing.assert_array_equal(outs[0], outs[1])


def test_boundary_step_byte_formulas_match_eval_shape():
    """The report's byte formulas price exactly the collectives the
    traced step contains (EXCHANGE_COUNTS eval_shape invariant)."""
    import jax
    import jax.numpy as jnp
    from repro.core import ipgc
    from repro.core.distributed import (EXCHANGE_COUNTS,
                                        make_dist_dense_step,
                                        make_dist_sparse_step)
    from repro.core.worklist import full_worklist
    from repro.obs.report import (dense_exchange_bytes, dense_swap_bytes,
                                  packed_exchange_bytes)
    g0 = _random_graph(7, 200, 1200)
    g, _ = prepare_partition(g0, 1)
    ig = ipgc.prepare(g)
    n = ig.n_nodes
    info = boundary_info(g, 1)
    mesh = jax.make_mesh((1,), ("data",))
    views = jnp.broadcast_to(ipgc.init_colors(n), (1, n + 1))
    base = jnp.zeros((n,), jnp.int32)
    wl = full_worklist(n)
    bcap = info.capacities[0]
    for fused, publishes in ((True, 1), (False, 2)):
        dstep = make_dist_dense_step(ig, mesh, ("data",), window=64,
                                     fused=fused, exchange="boundary",
                                     boundary=info, thresh=n + 1)
        with EXCHANGE_COUNTS.scope() as ec:
            jax.eval_shape(lambda c, b, w: dstep(c, b, w, bcap=bcap),
                           views, base, wl)
            # both lax.cond branches trace: a pack AND a swap per publish
            assert ec["boundary_pack"] == publishes
            assert ec["dense_swap"] == publishes
            assert ec["color_psum"] == 0
        sstep = make_dist_sparse_step(ig, mesh, ("data",), window=64,
                                      fused=fused, exchange="boundary",
                                      boundary=info, thresh=n + 1)
        with EXCHANGE_COUNTS.scope() as ec:
            jax.eval_shape(lambda c, b, w: sstep(c, b, w, bcap=bcap),
                           views, base, wl)
            assert ec["boundary_pack"] == publishes
            assert ec["dense_swap"] == publishes
    # the formulas themselves
    assert dense_exchange_bytes(n) == 4 * (n + 1)
    assert dense_swap_bytes(n) == 4 * n
    assert packed_exchange_bytes(bcap, 8) == 8 * bcap * 8


def test_report_traffic_win_visible():
    """RunReport surfaces the exchanged-bytes ledger; on a
    partition-friendly graph the auto path must move fewer bytes than
    the dense path once the worklist thins (the PR's point)."""
    g = make_graph("europe_osm_s", scale=0.02)
    r_dense = default_session().run(
        ExecutionSpec(regime="dist", n_shards=1, exchange="dense"), g,
        trace=True)
    r_auto = default_session().run(
        ExecutionSpec(regime="dist", n_shards=1, exchange="auto"), g,
        trace=True)
    np.testing.assert_array_equal(r_dense.colors, r_auto.colors)
    xd = r_dense.exchanges
    xa = r_auto.exchanges
    assert xd["exchange"] == "dense" and xa["exchange"] == "auto"
    assert sum(xa["bytes_per_iter"]) < sum(xd["bytes_per_iter"])
    assert "b" in xa["trace"]
