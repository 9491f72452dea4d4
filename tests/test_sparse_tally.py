"""The host loop's counter of what each sparse step gathers
(``ColoringResult.sparse_entries`` / ``sparse_slots``; the dense steps'
``dense_entries`` / ``dense_slots`` where JPL's rule differs), recomputed by
stepping the same coloring by hand: the live entries are the degrees of
the worklist's rows before the step, the slots the size of the buffers
the step builds (``ipgc._packed``), or every entry where it sweeps."""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.algos import get_algorithm
from repro.core import ipgc
from repro.core.worklist import bucket_capacities, pick_bucket, resize_items
from repro.exec import ExecutionSpec, Session
from repro.graphs import generators, ingest
from repro.graphs.layout import run_pipeline

W = 32


def _rgg(layout, ell_cap):
    # a geometric graph: its top 1024 rows own few enough entries to pack
    src, dst, n = generators.edges_rgg(8192, 16, 0)
    return run_pipeline(ingest.from_arrays(src, dst, n, name="rgg"),
                        layout=layout, ell_cap=ell_cap)


def _gathered(ig, items) -> tuple[int, int]:
    """(entries the sparse step over ``items`` gathers, read off the
    buffers it builds; entries it would gather sweeping)."""
    if ig.layout_kind == "csr-segment":
        pk = ipgc._packed(ig, items, ig.edge_dst, lambda r: r, lambda r: r,
                          keyed=True)
        m = ig.edge_dst.shape[0]
        return (m if pk is None else pk.dst.shape[0]), m
    assert ig.n_hub > 0
    pk = ipgc._hub_packed(ig, items)
    rows = items.shape[0] * ig.ell_width
    t = ig.tail_dst.shape[0]
    return rows + (t if pk is None else pk.dst.shape[0]), rows + t


@pytest.mark.parametrize("bound", [True, False], ids=["packed", "swept"])
@pytest.mark.parametrize("fused", [False, True])
@pytest.mark.parametrize("layout,ell_cap", [("csr-segment", None),
                                            ("ell-tail", 8)])
def test_counter_matches_hand_stepping(layout, ell_cap, fused, bound):
    alg = get_algorithm("ipgc")
    ig = alg.prepare(_rgg(layout, ell_cap))
    if not bound:                       # no packing bound: every step sweeps
        ig = dataclasses.replace(ig, seg_bound=())
    spec = ExecutionSpec(regime="host", fused=fused, window=W)
    got = Session().run(spec, ig)
    assert len(got.sparse_entries) == len(got.sparse_slots) \
        == got.mode_trace.count("S") > 0

    n = ig.n_nodes
    deg = np.asarray(ig.degrees)
    dense, sparse = ipgc.step_fns(fused)
    caps = bucket_capacities(n, ratio=spec.bucket_ratio)
    colors, base, wl = alg.init_state(ig)
    live, slots, sweeps = [], [], []
    for mode, count in zip(got.mode_trace, got.counts):
        assert int(wl.count) == count
        if mode == "D":
            colors, base, wl = dense(ig, colors, base, wl, window=W)
            continue
        cap = pick_bucket(caps, count)
        if wl.capacity > cap:
            wl = resize_items(wl, cap, n)
        items = np.asarray(wl.items)
        live.append(int(deg[items[items < n]].sum()))
        packed, swept = _gathered(ig, wl.items)
        slots.append(packed)
        sweeps.append(swept)
        colors, base, wl = sparse(ig, colors, base, wl, window=W)
    assert int(wl.count) == 0
    np.testing.assert_array_equal(got.colors, np.asarray(colors[:n]))
    assert got.sparse_entries == live
    assert got.sparse_slots == slots
    # with the bound, the small buckets pack: fewer slots than a sweep
    assert any(s < w for s, w in zip(slots, sweeps)) == bound


@pytest.mark.parametrize("fused", [False, True])
def test_tallied_step_is_the_step(fused):
    """The host loop's sparse program returns the plain step's state bit
    for bit, and (count after, live entries before) in one array."""
    alg = get_algorithm("ipgc")
    ig = alg.prepare(_rgg("csr-segment", None))
    n = ig.n_nodes
    colors, base, wl = alg.init_state(ig)
    dense, sparse = ipgc.step_fns(fused)
    colors, base, wl = dense(ig, colors, base, wl, window=W)
    wl = resize_items(wl, 4096, n)
    want = sparse(ig, colors, base, wl, window=W)
    *state, tally = alg.step_fns(fused)[1](ig, colors, base, wl, window=W)
    np.testing.assert_array_equal(np.asarray(state[0]), np.asarray(want[0]))
    np.testing.assert_array_equal(np.asarray(state[1]), np.asarray(want[1]))
    for a, b in zip(state[2], want[2]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    items = np.asarray(wl.items)
    deg = np.asarray(ig.degrees)
    assert tally.dtype == jnp.int32 and tally.shape == (2,)
    assert [int(v) for v in tally] == [int(want[2].count),
                                       int(deg[items[items < n]].sum())]


def test_jpl_counts_the_whole_tail():
    """JPL packs nothing: each sparse step gathers its rows' ELL slots
    and the whole hub tail."""
    alg = get_algorithm("jpl")
    ig = alg.prepare(_rgg("ell-tail", 8))
    assert ig.n_hub > 0
    spec = ExecutionSpec(regime="host", algo="jpl")
    got = Session().run(spec, ig)
    n = ig.n_nodes
    caps = bucket_capacities(n, ratio=spec.bucket_ratio)
    capacity, want = n, []
    for mode, count in zip(got.mode_trace, got.counts):
        if mode == "S":
            capacity = min(capacity, pick_bucket(caps, count))
            want.append(capacity * ig.ell_width + ig.tail_dst.shape[0])
    assert want and got.sparse_slots == want
    assert all(0 < e <= s for e, s in zip(got.sparse_entries, want))


@pytest.mark.parametrize("layout,ell_cap", [("csr-segment", None),
                                            ("ell-tail", 8)])
def test_jpl_dense_steps_read_the_ell_path(layout, ell_cap):
    """JPL's dense round reads every row's ELL slots and the whole hub
    tail under any plan, csr-segment included."""
    alg = get_algorithm("jpl")
    ig = alg.prepare(_rgg(layout, ell_cap))
    got = Session().run(ExecutionSpec(regime="host", algo="jpl"), ig)
    tail = ig.tail_dst.shape[0] if ig.n_hub else 0
    want = ig.n_nodes * ig.ell_width + tail
    assert got.dense_slots == [want] * got.mode_trace.count("D") != []
    assert got.dense_entries[0] == int(np.asarray(ig.degrees).sum())
    assert all(0 < e <= want for e in got.dense_entries)


def test_other_regimes_leave_the_counter_empty():
    g = _rgg("ell-tail", 8)
    got = Session().run(ExecutionSpec(regime="outlined", window=W), g)
    assert "S" in got.mode_trace and "D" in got.mode_trace
    assert got.sparse_entries == [] and got.sparse_slots == []
    assert got.dense_entries == [] and got.dense_slots == []
