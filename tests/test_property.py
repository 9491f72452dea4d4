"""Hypothesis property tests on system invariants (each test skips with a
reason when hypothesis is absent — see _hyp; this module is all-property,
so without hypothesis every test here reports skipped, not hidden)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hyp import given, settings, st

from repro.core import jpl_color
from repro.core.worklist import bucket_capacities, pick_bucket
from repro.exec import ExecutionSpec, default_session
from repro.graphs import build_graph, validate_coloring
from repro.graphs.partition import (balance_permutation, prepare_partition,
                                    repartition, shard_bounds)
from repro.graphs.sampler import sample_blocks


@settings(max_examples=15, deadline=None)
@given(st.integers(8, 120), st.integers(0, 300), st.data())
def test_coloring_always_valid_on_random_graphs(n, e, data):
    """Any random multigraph (self loops included — removed by the
    builder) gets a valid complete coloring from every engine mode."""
    seed = data.draw(st.integers(0, 2**31 - 1))
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n, size=max(e, 1))
    dst = rng.integers(0, n, size=max(e, 1))
    g = build_graph(src, dst, n, name="h", ell_cap=32)
    mode = data.draw(st.sampled_from(["hybrid", "data", "topology"]))
    window = data.draw(st.sampled_from([32, "auto"]))
    r = default_session().run(ExecutionSpec(mode=mode, window=window), g)
    v = validate_coloring(g, r.colors)
    assert v["conflicts"] == 0
    assert v["uncolored"] == 0
    # greedy bound: colors <= max_degree + 1
    deg = np.asarray(g.arrays.degrees)
    assert r.n_colors <= (deg.max() if len(deg) else 0) + 1


@settings(max_examples=10, deadline=None)
@given(st.integers(8, 60), st.data())
def test_jpl_valid_on_random_graphs(n, data):
    seed = data.draw(st.integers(0, 2**31 - 1))
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n, size=3 * n)
    dst = rng.integers(0, n, size=3 * n)
    g = build_graph(src, dst, n, name="h")
    r = jpl_color(g)
    v = validate_coloring(g, r.colors)
    assert v["conflicts"] == 0 and v["uncolored"] == 0


@settings(max_examples=20, deadline=None)
@given(st.integers(16, 200_000), st.integers(2, 6))
def test_bucket_ladder_properties(n, ratio):
    caps = bucket_capacities(n, ratio=ratio)
    assert caps[0] >= n
    assert all(a > b for a, b in zip(caps, caps[1:]))
    for c in (1, n // 3 + 1, n):
        assert pick_bucket(caps, c) >= c


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 30), st.integers(1, 8), st.integers(0, 500), st.data())
def test_balance_permutation_is_balanced_permutation(blocks, n_shards, e,
                                                     data):
    """balance_permutation returns a true permutation whose per-shard
    degree load is bounded by mean_load + max_degree (LPT snake deal;
    blocks aligned because n is a multiple of n_shards — the layout
    prepare_partition guarantees the engine)."""
    n = blocks * n_shards
    seed = data.draw(st.integers(0, 2**31 - 1))
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n, size=max(e, 1))
    dst = rng.integers(0, n, size=max(e, 1))
    g = build_graph(src, dst, n, name="h", ell_cap=16)
    perm = balance_permutation(g, n_shards)
    assert sorted(perm.tolist()) == list(range(n))   # a true permutation
    deg = np.asarray(g.arrays.degrees)
    bounds = shard_bounds(n, n_shards)
    loads = [deg[perm[bounds[s]:bounds[s + 1]]].sum()
             for s in range(n_shards)]
    bound = deg.sum() / n_shards + (deg.max() if n else 0)
    assert max(loads) <= bound, (loads, bound)


@settings(max_examples=15, deadline=None)
@given(st.integers(8, 100), st.integers(1, 8), st.data())
def test_repartition_relabel_preserves_coloring_validity(n, n_shards, data):
    """A valid coloring of the original graph, pushed through the
    repartition relabeling, is a valid coloring of the relabeled graph
    (and vice versa) — the invariant the distributed engine's map-back
    relies on."""
    seed = data.draw(st.integers(0, 2**31 - 1))
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n, size=3 * n)
    dst = rng.integers(0, n, size=3 * n)
    g = build_graph(src, dst, n, name="h", ell_cap=16)
    r = default_session().run(ExecutionSpec(mode="hybrid", window=32), g)
    assert validate_coloring(g, r.colors)["conflicts"] == 0
    g2, new_of_old = repartition(g, n_shards,
                                 balance=data.draw(st.booleans()))
    relabeled = np.empty(n, dtype=r.colors.dtype)
    relabeled[new_of_old] = r.colors                 # color moves with node
    v2 = validate_coloring(g2, relabeled)
    assert v2["conflicts"] == 0 and v2["uncolored"] == 0
    assert v2["n_colors"] == r.n_colors
    # and back: coloring the relabeled graph maps to a valid original one
    r2 = default_session().run(ExecutionSpec(mode="hybrid", window=32), g2)
    v_back = validate_coloring(g, r2.colors[new_of_old])
    assert v_back["conflicts"] == 0 and v_back["uncolored"] == 0


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 80), st.integers(1, 8), st.data())
def test_prepare_partition_block_contract(n, n_shards, data):
    """prepare_partition pads to equal 8-aligned shard blocks and its
    relabeling embeds the original graph exactly (the shard_map shape
    contract of the distributed engine)."""
    seed = data.draw(st.integers(0, 2**31 - 1))
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n, size=2 * n)
    dst = rng.integers(0, n, size=2 * n)
    g = build_graph(src, dst, n, name="h", ell_cap=16)
    g2, new_of_old = prepare_partition(g, n_shards)
    assert g2.n_nodes % (8 * n_shards) == 0
    assert g2.n_nodes >= n
    assert g2.n_edges == g.n_edges                   # padding adds no edges
    deg = np.asarray(g.arrays.degrees)
    deg2 = np.asarray(g2.arrays.degrees)
    np.testing.assert_array_equal(deg2[new_of_old[:n]], deg)
    # pad nodes are isolated
    assert deg2.sum() == deg.sum()


@settings(max_examples=10, deadline=None)
@given(st.integers(4, 50), st.integers(1, 6), st.integers(1, 5), st.data())
def test_sampler_returns_real_neighbours(n, f1, f2, data):
    seed = data.draw(st.integers(0, 2**31 - 1))
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n, size=4 * n)
    dst = rng.integers(0, n, size=4 * n)
    g = build_graph(src, dst, n, name="h")
    row_ptr = jnp.asarray(g.arrays.row_ptr)
    col_idx = jnp.asarray(g.arrays.col_idx)
    seeds = jnp.asarray(rng.integers(0, n, size=8), jnp.int32)
    blocks = sample_blocks(jax.random.PRNGKey(seed), row_ptr, col_idx,
                           seeds, (f1, f2))
    rp, ci = np.asarray(row_ptr), np.asarray(col_idx)
    hop1 = np.asarray(blocks.hops[0])
    m1 = np.asarray(blocks.masks[0])
    for i, s in enumerate(np.asarray(seeds)):
        nbrs = set(ci[rp[s]:rp[s + 1]].tolist())
        for j in range(f1):
            if m1[i, j]:
                assert int(hop1[i, j]) in nbrs
            else:
                assert int(hop1[i, j]) == int(s)   # isolated: self-fill


@settings(max_examples=15, deadline=None)
@given(st.lists(st.integers(0, 1000), min_size=1, max_size=200),
       st.integers(1, 8))
def test_pipeline_host_slices_partition(batch_seed, n_hosts):
    from repro.data.pipelines import TokenPipeline
    gb = n_hosts * 4
    p = TokenPipeline(vocab=97, seq_len=8, global_batch=gb,
                      seed=batch_seed[0])
    full = p.batch_at(3)
    parts = [p.host_slice(3, h, n_hosts) for h in range(n_hosts)]
    glued = np.concatenate([np.asarray(x["tokens"]) for x in parts])
    np.testing.assert_array_equal(glued, np.asarray(full["tokens"]))
