"""The ELL layouts' static tie-break (``IPGCGraph.ell_wins``).

The two-phase ELL resolve reads a per-slot "the neighbour wins" bit built
at prepare time, as csr-segment reads the sign bit of ``edge_dst``, in
place of gathering priorities every step. These tests hold the bit to
the predicate (``kcsr.wins``) on graphs with tied priorities, before and
after ``pad_prepared``; hold the steps to gathering no priorities; and
hold every ELL kind's colorings, in every regime, to the csr-segment
path, which evaluates the same predicate from its own keyed edges.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend.core import Jaxpr, Literal

from repro.core import ipgc, verify_coloring
from repro.core.worklist import full_worklist
from repro.exec import ExecutionSpec, Session
from repro.graphs import build_graph, get_dataset
from repro.graphs.partition import prepare_partition
from repro.kernels import csr_segment as kcsr

ELL_KINDS = ["pure-ell", "ell-tail", "hub-split"]
GRAPH = "kron_g500-logn21_s"


def _bits(words: np.ndarray, k: int) -> np.ndarray:
    """(N, k) bool slot bits of ``ell_wins`` words, decoded on the host."""
    b = np.unpackbits(np.ascontiguousarray(words).astype("<u4")
                      .view(np.uint8), axis=1, bitorder="little")
    return b[:, :k].astype(bool)


def _tied_graph(kind: str, seed: int):
    """A small random graph under ``kind`` whose hash priorities take
    four values, so most neighbours tie and the id decides."""
    rng = np.random.default_rng(seed)
    n = 200
    src, dst = rng.integers(0, n, 900), rng.integers(0, n, 900)
    hub = rng.integers(0, n, 60)            # one high-degree row for the tails
    src, dst = np.concatenate([src, np.zeros(60, int)]), np.concatenate(
        [dst, hub])
    g = build_graph(src, dst, n, name=f"tied{seed}", layout=kind,
                    ell_cap=None if kind == "pure-ell" else 8)
    prio = rng.integers(0, 4, g.n_nodes).astype(np.int32)
    return dataclasses.replace(g, arrays=g.arrays._replace(priority=prio))


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("priority", ["hash", "id"])
@pytest.mark.parametrize("kind", ELL_KINDS)
def test_ell_wins_bit_is_the_tie_break(kind, priority, seed):
    g = _tied_graph(kind, seed)
    ig = ipgc.prepare(g, priority=priority)
    n, k = ig.n_nodes, ig.ell_width
    ell = np.asarray(ig.ell_idx)
    p = np.asarray(ig.priority)
    if priority == "hash":
        assert len(np.unique(p[:n])) <= 4      # the ties are there
    u = np.broadcast_to(np.arange(n)[:, None], ell.shape)
    real = ell < n
    want = np.zeros(ell.shape, bool)
    want[real] = kcsr.wins(p[u[real]], p[ell[real]], u[real], ell[real])
    got = _bits(np.asarray(ig.ell_wins), k)
    np.testing.assert_array_equal(got, want)
    # the device-side decoder reads the same bits
    np.testing.assert_array_equal(
        np.asarray(ipgc.slot_wins(ig.ell_wins, k)), want)

    pad = ipgc.pad_prepared(ig, n + 24, k + 40, ig.tail_src.shape[0] + 8,
                            ig.n_hub + 2)
    got_p = _bits(np.asarray(pad.ell_wins), k + 40)
    np.testing.assert_array_equal(got_p[:n, :k], want)
    assert not got_p[n:].any() and not got_p[:, k:].any()
    np.testing.assert_array_equal(
        np.asarray(ipgc.slot_wins(pad.ell_wins, k + 40)), got_p)


def test_csr_segment_carries_no_ell_wins():
    g = get_dataset(GRAPH, scale=0.01, layout="csr-segment")
    ig = ipgc.prepare(g, plan=g.layout)
    assert ig.ell_wins is None and ig.edge_dst is not None


# ---------------------------------------------------------------------------
# the gain's guard: what the two-phase ELL steps gather
# ---------------------------------------------------------------------------

def _subjaxprs(eqn):
    for v in eqn.params.values():
        for sub in (v if isinstance(v, (tuple, list)) else (v,)):
            inner = getattr(sub, "jaxpr", sub)
            if isinstance(inner, Jaxpr):
                yield inner


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs nested in it."""
    for e in jaxpr.eqns:
        yield e
        for inner in _subjaxprs(e):
            yield from _eqns(inner)


def _iota_gathers(jaxpr, fed_in=()) -> list:
    """The gathers of ``jaxpr`` (nested ones too) whose indices are
    computed from an ``iota`` (``fed_in``: which inputs already are)."""
    fed = {v for v, f in zip(jaxpr.invars, fed_in) if f}
    found = []
    for e in jaxpr.eqns:
        ins = [not isinstance(v, Literal) and v in fed for v in e.invars]
        if e.primitive.name == "gather" and ins[1]:
            found.append(e)
        inner_iota = False
        for inner in _subjaxprs(e):
            found += _iota_gathers(inner, ins if len(ins) == len(
                inner.invars) else [any(ins)] * len(inner.invars))
            inner_iota |= any(x.primitive.name == "iota"
                              for x in _eqns(inner))
        if e.primitive.name == "iota" or any(ins) or inner_iota:
            fed.update(e.outvars)
    return found


@pytest.mark.parametrize("step", ["dense", "sparse"])
def test_ell_resolve_gathers_no_priorities(step):
    """The two-phase pure-ELL step reads no priority, and gathers two
    neighbour-color tiles (assign, resolve) and no identity gather:
    three tile gathers and two identity gathers in the dense step before
    the tie-break was static."""
    g = get_dataset("europe_osm_s", scale=0.02, layout="pure-ell")
    ig = ipgc.prepare(g)
    n, k = ig.n_nodes, ig.ell_width
    assert ig.layout_kind == "pure-ell" and ig.n_hub == 0
    fn = ipgc.dense_step_impl if step == "dense" else ipgc.sparse_step_impl
    wl = full_worklist(n)
    if step == "sparse":
        wl = type(wl)(mask=wl.mask, items=wl.items[:n // 2],
                      count=wl.count)
    closed = jax.make_jaxpr(functools.partial(fn, window=32, impl="jnp"))(
        ig, ipgc.init_colors(n), jnp.zeros((n,), jnp.int32), wl)
    jaxpr = closed.jaxpr
    leaves = jax.tree_util.tree_leaves_with_path(ig)
    prio_var = jaxpr.invars[[jax.tree_util.keystr(p) for p, _ in leaves]
                            .index(".priority")]
    used = {v for e in _eqns(jaxpr) for v in e.invars
            if not isinstance(v, Literal)}
    assert prio_var not in used, "the step reads ig.priority"

    rows = n if step == "dense" else n // 2
    gathers = [e for e in _eqns(jaxpr) if e.primitive.name == "gather"]
    tiles = [e for e in gathers if e.outvars[0].aval.shape == (rows, k)]
    # the sparse step also gathers its rows of ell_idx
    assert len(tiles) == (2 if step == "dense" else 3), \
        [e.outvars[0].aval for e in gathers]
    assert not _iota_gathers(jaxpr), "a gather indexed by an iota"


# ---------------------------------------------------------------------------
# every ELL kind and regime colors as the csr-segment path does
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def kinds():
    return {kind: get_dataset(GRAPH, scale=0.02, layout=kind,
                              **({} if kind == "pure-ell" else
                                 {"ell_cap": 32}))
            for kind in ELL_KINDS}


def _same(a, b, context):
    np.testing.assert_array_equal(a.colors, b.colors, err_msg=context)
    assert (a.iterations, a.mode_trace) == (b.iterations, b.mode_trace), \
        context


@pytest.mark.parametrize("regime", ["host", "batch", "dist"])
@pytest.mark.parametrize("priority", ["hash", "id"])
@pytest.mark.parametrize("kind", ELL_KINDS)
def test_ell_kinds_color_as_csr_segment(kinds, kind, priority, regime):
    """Host two-phase runs, ``run_batch`` lanes (through
    ``pad_prepared``) and the two-phase dist steps on one shard give the
    colors, iterations and mode trace of the csr-segment host run."""
    g = kinds[kind]
    s = Session()
    host = ExecutionSpec(regime="host", priority=priority, fused=False)
    if kind != "pure-ell":
        assert ipgc.prepare(g).n_hub > 0           # the tails are there
    if regime == "dist":
        g2, relabel = prepare_partition(g, 1)
        ref = s.run(dataclasses.replace(host, layout="csr-segment"), g2)
        r = s.run(dataclasses.replace(host, regime="dist", n_shards=1), g)
        np.testing.assert_array_equal(r.colors, ref.colors[relabel[:g.n_nodes]])
        assert (r.iterations, r.mode_trace) == (ref.iterations,
                                                ref.mode_trace)
        return
    ref = s.run(dataclasses.replace(host, layout="csr-segment"), g)
    verify_coloring(g, ref.colors)
    if regime == "host":
        _same(s.run(host, g), ref, kind)
    else:
        small = get_dataset("europe_osm_s", scale=0.005, layout=kind)
        lanes = s.run_batch(host, [g, small])
        _same(lanes[0], ref, kind)
        _same(lanes[1], s.run(dataclasses.replace(host, layout="csr-segment"),
                              small), f"{kind}/small")
