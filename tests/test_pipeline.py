"""Staged graph pipeline tests (DESIGN.md §8): ingest normalization,
reorder permutations + inverse-map convention, layout planning/assembly,
the dataset registry, and layout-aware engine dispatch.

The two regression guards of the refactor live here:

  * ``layout="ell-tail"`` + ``reorder="identity"`` reproduces the
    historical builder arrays bit-identically, and the engines reproduce
    identical colors/iterations/mode-trace across execution layouts;
  * every non-identity reorder's colors, mapped back through the inverse
    permutation, verify on the ORIGINAL node ids.
"""
import jax
import numpy as np
import pytest

from repro.core import verify_coloring
from repro.core.verify import coloring_stats
from repro.exec import ExecutionSpec, default_session
from repro.graphs import (LAYOUT_KINDS, LayoutPlan, REORDERINGS, build_graph,
                          get_dataset, make_graph, plan_layout)
from repro.graphs import ingest, transform
from repro.graphs.layout import assemble
from repro.graphs.registry import (clear_dataset_cache, dataset_names,
                                   register_dataset)


# ---------------------------------------------------------------------------
# ingest
# ---------------------------------------------------------------------------

def test_normalize_dedups_and_sorts():
    e = ingest.from_arrays([2, 0, 0, 1, 2, 2], [2, 1, 1, 0, 1, 1], 3)
    ne = ingest.normalize(e)
    # self loop (2,2) dropped; dups collapsed; symmetrized; (s,d)-sorted
    np.testing.assert_array_equal(ne.src, [0, 1, 1, 2])
    np.testing.assert_array_equal(ne.dst, [1, 0, 2, 1])


def test_normalize_no_symmetrize_keeps_direction():
    e = ingest.from_arrays([0, 0], [1, 1], 3)
    ne = ingest.normalize(e, symmetrize=False)
    np.testing.assert_array_equal(ne.src, [0])
    np.testing.assert_array_equal(ne.dst, [1])


def test_normalize_dedup_no_int64_overflow():
    """The old ``s * n_nodes + d`` dedup key overflowed int64 once
    n_nodes**2 did; the lexsort dedup must survive huge node counts."""
    n = 2 ** 33                        # n*n overflows int64
    src = np.array([n - 1, n - 1, 0, n - 1], dtype=np.int64)
    dst = np.array([n - 2, n - 2, 1, n - 2], dtype=np.int64)
    ne = ingest.normalize(ingest.from_arrays(src, dst, n), symmetrize=False)
    assert ne.n_entries == 2
    np.testing.assert_array_equal(ne.src, [0, n - 1])
    np.testing.assert_array_equal(ne.dst, [1, n - 2])


def test_normalize_matches_naive_dedup():
    rng = np.random.default_rng(3)
    src = rng.integers(0, 40, 500)
    dst = rng.integers(0, 40, 500)
    ne = ingest.normalize(ingest.from_arrays(src, dst, 40))
    want = sorted({(s, d) for s, d in zip(src, dst) if s != d}
                  | {(d, s) for s, d in zip(src, dst) if s != d})
    got = sorted(zip(ne.src.tolist(), ne.dst.tolist()))
    assert got == want


# ---------------------------------------------------------------------------
# load_mtx / snap ingestion
# ---------------------------------------------------------------------------

MTX = ("%%MatrixMarket matrix coordinate pattern symmetric\n"
       "% a comment\n"
       "5 5 5\n1 2\n2 3\n3 4\n4 5\n5 1\n")


def test_load_mtx_equals_build_graph_on_same_edges(tmp_path):
    from repro.graphs.generators import load_mtx
    p = tmp_path / "ring5.mtx"
    p.write_text(MTX)
    g_mtx = load_mtx(str(p), name="ring5")
    g_ref = build_graph(np.array([0, 1, 2, 3, 4]),
                        np.array([1, 2, 3, 4, 0]), 5, name="ring5")
    assert g_mtx.n_nodes == g_ref.n_nodes
    assert g_mtx.n_edges == g_ref.n_edges
    for f in ("row_ptr", "col_idx", "degrees", "ell_idx", "tail_src",
              "tail_dst", "priority"):
        np.testing.assert_array_equal(
            getattr(g_mtx.arrays, f), getattr(g_ref.arrays, f), err_msg=f)


def test_load_mtx_malformed_header_raises(tmp_path):
    p = tmp_path / "bad.mtx"
    p.write_text("not a matrixmarket file\n3 3 1\n1 2\n")
    with pytest.raises(ValueError, match="malformed MatrixMarket header"):
        ingest.from_mtx(str(p))
    p2 = tmp_path / "bad2.mtx"
    p2.write_text("%%MatrixMarket matrix coordinate pattern symmetric\n"
                  "oops\n")
    with pytest.raises(ValueError, match="malformed size line"):
        ingest.from_mtx(str(p2))


def test_from_snap(tmp_path):
    p = tmp_path / "g.snap"
    p.write_text("# SNAP-style comment\n0 1\n1 2\n2 0\n")
    e = ingest.from_snap(str(p))
    assert e.n_nodes == 3 and e.n_entries == 3


# ---------------------------------------------------------------------------
# transform: permutations + the inverse-map convention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("how", sorted(REORDERINGS))
def test_reorderings_are_permutations(how):
    e = ingest.normalize(ingest.from_generator(
        "soc-LiveJournal1_s", scale=0.01))
    _, perm = transform.reorder(e, how)
    assert sorted(perm.new_of_old.tolist()) == list(range(e.n_nodes))
    # inverse really inverts
    np.testing.assert_array_equal(
        perm.new_of_old[perm.old_of_new], np.arange(e.n_nodes))


def test_degree_sort_puts_hubs_first():
    g = get_dataset("circuit5M_s", scale=0.01, reorder="degree-sort",
                    layout="ell-tail")
    deg = np.asarray(g.arrays.degrees)
    assert deg[0] == deg.max()
    assert np.all(np.diff(deg) <= 0)   # non-increasing after the relabel


def test_bfs_rcm_reduces_bandwidth_on_shuffled_chain():
    n = 256
    shuf = np.random.default_rng(0).permutation(n)
    src, dst = shuf[np.arange(n - 1)], shuf[np.arange(1, n)]
    e = ingest.normalize(ingest.from_arrays(src, dst, n))
    re_edges, _ = transform.reorder(e, "bfs-rcm")
    bw = int(np.abs(re_edges.src - re_edges.dst).max())
    assert bw <= 2                      # a chain relabels to bandwidth ~1


@pytest.mark.parametrize("how", [k for k in sorted(REORDERINGS)
                                 if k != "identity"])
def test_reordered_colors_map_back_to_original_ids(how):
    """Acceptance: every non-identity reorder's output, mapped through
    the inverse permutation, verifies on the original node ids."""
    g_orig = make_graph("kron_g500-logn21_s", scale=0.02)
    g_re = get_dataset("kron_g500-logn21_s", scale=0.02, reorder=how,
                       layout="ell-tail", ell_cap=128)
    assert not g_re.perm.is_identity
    r = default_session().run(ExecutionSpec(), g_re)
    verify_coloring(g_re, r.colors, context=f"{how}/internal")
    back = g_re.perm.colors_to_original(r.colors)
    verify_coloring(g_orig, back, context=f"{how}/original-ids")


def test_reordered_colors_map_back_outlined_and_dist():
    g_orig = make_graph("europe_osm_s", scale=0.02)
    g_re = get_dataset("europe_osm_s", scale=0.02, reorder="shuffle",
                       layout="ell-tail", ell_cap=128)
    r_out = default_session().run(ExecutionSpec(regime="outlined"), g_re)
    verify_coloring(g_orig, g_re.perm.colors_to_original(r_out.colors),
                    context="shuffle/outlined")
    r_dist = default_session().run(
        ExecutionSpec(regime="dist", n_shards=min(2, jax.device_count())),
        g_re)
    verify_coloring(g_orig, g_re.perm.colors_to_original(r_dist.colors),
                    context="shuffle/dist")


# ---------------------------------------------------------------------------
# layout: planning + assembly invariants
# ---------------------------------------------------------------------------

def test_plan_layout_validation():
    with pytest.raises(ValueError, match="unknown layout"):
        plan_layout(np.array([2, 2]), layout="nope")
    with pytest.raises(ValueError, match="multiple of 8"):
        LayoutPlan(kind="ell-tail", ell_width=13, hub_threshold=13)
    with pytest.raises(ValueError, match="unknown layout kind"):
        LayoutPlan(kind="nope", ell_width=8, hub_threshold=8)
    # explicit plan passes through untouched
    p = LayoutPlan(kind="hub-split", ell_width=16, hub_threshold=16)
    assert plan_layout(np.array([1, 50]), layout=p) is p


def test_auto_planner_respects_ell_cap():
    """auto must not pick pure-ell when the caller's ell_cap cannot hold
    the max degree — it falls through to a capped ell-tail instead of
    raising (regression: build_graph(layout="auto") with the default
    ell_cap=128 crashed on near-regular graphs of degree 129..512)."""
    deg = np.full(512, 200)            # near-regular, max degree 200
    p = plan_layout(deg, layout="auto", ell_cap=128)
    assert p.kind == "ell-tail" and p.ell_width == 128
    p2 = plan_layout(deg, layout="auto")         # uncapped: regular win
    assert p2.kind == "pure-ell" and p2.ell_width == 200 + (-200 % 8)
    ring = build_graph(np.repeat(np.arange(64), 63),
                       np.concatenate([np.delete(np.arange(64), i)
                                       for i in range(64)]), 64,
                       layout="auto")            # K63 clique, cap 128
    assert ring.layout.kind == "pure-ell"


def test_auto_planner_matches_families():
    """The degree-histogram planner lands each Table-I family on the
    intended layout (at the test scale)."""
    expect = {"Queen_4147_s": "pure-ell",       # regular FEM mesh
              "europe_osm_s": "pure-ell",       # tiny max degree
              "circuit5M_s": "csr-segment",     # low-degree + mega hubs
              "hollywood-2009_s": "hub-split"}  # heavy-tailed social
    for name, kind in expect.items():
        g = get_dataset(name, scale=0.02, layout="auto")
        assert g.layout.kind == kind, (name, g.layout)


def test_ell_tail_with_default_cap_is_bit_identical_to_legacy_builder():
    g1 = make_graph("kron_g500-logn21_s", scale=0.02)     # legacy facade
    g2 = get_dataset("kron_g500-logn21_s", scale=0.02, layout="ell-tail",
                     ell_cap=128)
    assert g1.ell_width == g2.ell_width
    for f in ("row_ptr", "col_idx", "degrees", "ell_idx", "tail_src",
              "tail_dst", "priority"):
        np.testing.assert_array_equal(
            getattr(g1.arrays, f), getattr(g2.arrays, f), err_msg=f)


@pytest.mark.parametrize("kind", LAYOUT_KINDS)
def test_assembly_covers_all_edges(kind):
    """Per-row invariant for every layout: CSR row == ELL row ∪ tail."""
    rng = np.random.default_rng(1)
    e = ingest.normalize(ingest.from_arrays(
        rng.integers(0, 60, 400), rng.integers(0, 60, 400), 60))
    cap = None if kind == "pure-ell" else 16
    plan = plan_layout(e.degrees(), layout=kind, ell_cap=cap)
    g = assemble(e, plan)
    a = g.arrays
    tails: dict[int, set] = {}
    for s, d in zip(np.asarray(a.tail_src), np.asarray(a.tail_dst)):
        if s < g.n_nodes:
            tails.setdefault(int(s), set()).add(int(d))
    for u in range(g.n_nodes):
        csr = set(a.col_idx[a.row_ptr[u]:a.row_ptr[u + 1]].tolist())
        ell = set(x for x in a.ell_idx[u].tolist() if x < g.n_nodes)
        assert ell | tails.get(u, set()) == csr, (kind, u)
        if kind == "pure-ell":
            assert not tails.get(u)
        if kind == "hub-split" and len(csr) > plan.hub_threshold:
            assert not ell                 # hub rows keep nothing in ELL


def test_pure_ell_has_no_tail_and_no_hubs():
    g = get_dataset("Queen_4147_s", scale=0.02, layout="pure-ell")
    assert (np.asarray(g.arrays.tail_src) == g.n_nodes).all()
    from repro.core import ipgc
    ig = ipgc.prepare(g)
    assert ig.n_hub == 0 and ig.layout_kind == "pure-ell"


# ---------------------------------------------------------------------------
# layout-aware engine dispatch
# ---------------------------------------------------------------------------

GRAPH = "kron_g500-logn21_s"


@pytest.fixture(scope="module")
def kron_ref():
    g = make_graph(GRAPH, scale=0.02)
    return g, default_session().run(ExecutionSpec(), g)


@pytest.mark.parametrize("kind", LAYOUT_KINDS)
def test_layout_execution_variants_agree_bit_exactly(kron_ref, kind):
    """Layouts are execution variants of the same math: identical
    forbidden sets, identical tie-breaks — so for a fixed graph and
    priority, every layout build produces the SAME colors, iterations
    and mode trace as the historical ell-tail run."""
    g_ref, r_ref = kron_ref
    if kind == "pure-ell":
        g = get_dataset(GRAPH, scale=0.02, layout=kind)
    else:
        g = get_dataset(GRAPH, scale=0.02, layout=kind, ell_cap=32)
    r = default_session().run(ExecutionSpec(), g)
    np.testing.assert_array_equal(r.colors, r_ref.colors)
    assert r.iterations == r_ref.iterations
    assert r.mode_trace == r_ref.mode_trace


@pytest.mark.parametrize("fused", [False, True])
def test_csr_segment_outlined_matches_host(fused):
    g = get_dataset(GRAPH, scale=0.02, layout="csr-segment")
    r_host = default_session().run(ExecutionSpec(fused=fused), g)
    r_out = default_session().run(
        ExecutionSpec(regime="outlined", fused=fused), g)
    np.testing.assert_array_equal(r_out.colors, r_host.colors)
    assert r_out.mode_trace == r_host.mode_trace
    assert r_out.host_dispatches < r_host.host_dispatches


def test_engine_layout_override_redispatches_execution(kron_ref):
    """``ExecutionSpec(layout=...)`` flips the execution variant on the
    same arrays (the plan rides the prepared graph's static fields)."""
    g, r_ref = kron_ref
    from repro.core import ipgc
    from repro.graphs.layout import resolve_plan
    plan = resolve_plan(g, "csr-segment")
    assert plan.kind == "csr-segment"
    assert plan.ell_width == g.layout.ell_width
    ig = ipgc.prepare(g, plan=plan)
    assert ig.layout_kind == "csr-segment" and ig.edge_src is not None
    r = default_session().run(ExecutionSpec(layout="csr-segment"), g)
    np.testing.assert_array_equal(r.colors, r_ref.colors)
    with pytest.raises(ValueError, match="unknown layout"):
        default_session().run(ExecutionSpec(layout="typo"), g)


def test_csr_segment_gather_contract():
    """csr-segment steps gather the mutable colors edge-wise: twice per
    two-phase iteration, ONCE per fused iteration (§5's contract carried
    to the segment variant)."""
    import jax.numpy as jnp
    from functools import partial
    from repro.core import ipgc
    from repro.core.worklist import full_worklist
    g = get_dataset("circuit5M_s", scale=0.01, layout="csr-segment")
    ig = ipgc.prepare(g)
    n = ig.n_nodes
    colors, base, wl = (ipgc.init_colors(n), jnp.zeros((n,), jnp.int32),
                        full_worklist(n))
    cases = [(ipgc.dense_step_impl, 2), (ipgc.sparse_step_impl, 2),
             (ipgc.fused_dense_step_impl, 1),
             (ipgc.fused_sparse_step_impl, 1)]
    for fn, want in cases:
        ipgc.reset_gather_counts()
        jax.eval_shape(partial(fn, ig, window=32, impl="jnp",
                               force_hub=False), colors, base, wl)
        assert ipgc.GATHER_COUNTS["neighbor_colors"] == want, fn.__name__


def test_dist_rejects_csr_segment_with_clear_message():
    g = get_dataset("europe_osm_s", scale=0.01, layout="csr-segment")
    with pytest.raises(NotImplementedError, match="ell-tail"):
        default_session().run(ExecutionSpec(regime="dist", n_shards=1), g)
    # the documented escape hatch: ELL-family execution of the same graph
    r = default_session().run(
        ExecutionSpec(regime="dist", n_shards=1, layout="ell-tail"), g)
    verify_coloring(g, r.colors, context="dist/ell-override")


@pytest.mark.parametrize("kind", ["pure-ell", "hub-split"])
def test_dist_matches_host_on_ell_family_layouts(kind):
    g = get_dataset("hollywood-2009_s", scale=0.02, layout=kind)
    shards = min(2, jax.device_count())
    r_dist = default_session().run(
        ExecutionSpec(regime="dist", n_shards=shards), g)
    verify_coloring(g, r_dist.colors, context=f"dist/{kind}")
    r_host = default_session().run(ExecutionSpec(fused=True), g)
    assert r_dist.n_colors == r_host.n_colors


def test_jpl_runs_under_every_layout():
    """JPL's rounds read the ELL arrays directly; the assembly contract
    (ELL+tail complete under every plan) keeps it correct regardless of
    the plan kind, and its colorings are layout-invariant."""
    ref = None
    for kind in LAYOUT_KINDS:
        g = get_dataset("europe_osm_s", scale=0.02, layout=kind)
        r = default_session().run(ExecutionSpec(algo="jpl"), g)
        verify_coloring(g, r.colors, context=f"jpl/{kind}")
        if ref is None:
            ref = r.colors
        else:
            np.testing.assert_array_equal(r.colors, ref)


# ---------------------------------------------------------------------------
# dataset registry
# ---------------------------------------------------------------------------

def test_get_dataset_caches():
    clear_dataset_cache()
    g1 = get_dataset("europe_osm_s", scale=0.01)
    g2 = get_dataset("europe_osm_s", scale=0.01)
    assert g1 is g2
    g3 = get_dataset("europe_osm_s", scale=0.01, reorder="shuffle")
    assert g3 is not g1


def test_get_dataset_unknown_name():
    with pytest.raises(ValueError, match="unknown dataset"):
        get_dataset("no-such-graph")


def test_get_dataset_suite_names_registered():
    from repro.graphs import SUITE_SPECS
    assert set(SUITE_SPECS) <= set(dataset_names())


def test_register_ad_hoc_dataset():
    def two_cliques(scale, seed):
        k = max(int(8 * scale), 2)
        s, d = np.meshgrid(np.arange(k), np.arange(k))
        src = np.concatenate([s.ravel(), s.ravel() + k])
        dst = np.concatenate([d.ravel(), d.ravel() + k])
        return ingest.from_arrays(src, dst, 2 * k, name="two-cliques")
    register_dataset("two-cliques", two_cliques)
    g = get_dataset("two-cliques", scale=1.0)
    assert g.n_nodes == 16
    r = default_session().run(ExecutionSpec(), g)
    assert r.n_colors == 8             # each K8 clique needs 8 colors


def test_get_dataset_mtx_and_snap_paths(tmp_path):
    p = tmp_path / "ring5.mtx"
    p.write_text(MTX)
    g = get_dataset(f"mtx:{p}", layout="ell-tail")
    assert g.n_nodes == 5 and g.n_edges == 5
    p2 = tmp_path / "tri.snap"
    p2.write_text("0 1\n1 2\n2 0\n")
    g2 = get_dataset(f"snap:{p2}")
    assert g2.n_nodes == 3 and g2.n_edges == 3
    # file-backed datasets cannot scale — loud error, not a silent
    # full-size graph under a scaled cache key
    with pytest.raises(ValueError, match="cannot be applied"):
        get_dataset(f"mtx:{p}", scale=0.5)


# ---------------------------------------------------------------------------
# validator consolidation
# ---------------------------------------------------------------------------

def test_validate_coloring_wraps_canonical_stats():
    from repro.graphs import validate_coloring
    g = build_graph(np.array([0, 1, 2]), np.array([1, 2, 0]), 3)
    bad = np.array([0, 0, 1])
    assert validate_coloring(g, bad) == coloring_stats(g, bad)
    assert validate_coloring(g, bad)["conflicts"] == 1
    from repro.core.verify import InvalidColoringError
    with pytest.raises(InvalidColoringError):
        verify_coloring(g, bad)
