"""The prepared graph's shape class (``ipgc.prepare``, DESIGN.md §8).

Graphs of one size drawn from different seeds share one signature, and
in one ``Session`` every step program is traced once for both; the
padding that puts a graph in its class is inert, so colorings,
iteration counts and mode traces are bit-identical to those of the
graph prepared without it (``ipgc._prepare_exact``)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.gen import rgg, rmat
from repro.core import ipgc
from repro.core.policy import measure_launches
from repro.core.worklist import bucket_capacities, pick_bucket
from repro.exec import ExecutionSpec, Session
from repro.graphs import build_graph
from repro.graphs.layout import LayoutPlan
from repro.obs import Trace

W = 32


def _kron(seed: int):
    """A small Graph500 kron graph on csr-segment (2^11 nodes)."""
    return build_graph(*rmat.edges(11, 12, seed), name=f"kron.{seed}",
                       layout="csr-segment", ell_cap=None)


def _rgg(seed: int):
    """A random geometric graph under an ELL width of 16, below its
    maximum degree, so the overflow rides the hub tail."""
    return build_graph(*rgg.edges(1536, 1.0, seed), name=f"rgg.{seed}",
                       layout=LayoutPlan(kind="ell-tail", ell_width=16,
                                         hub_threshold=16))


# per kind, two seeds whose sizes differ but fall on one rung of every
# ladder (a seed across a rung gets the next class, by design)
PAIRS = {"csr-segment": (_kron, (9, 11)), "ell-tail": (_rgg, (1, 3))}


def _exact(g):
    """``g`` prepared without its shape class, on the device."""
    return jax.tree.map(jnp.asarray, ipgc._prepare_exact(g, "hash", None))


def _pair(kind):
    make, seeds = PAIRS[kind]
    return [make(s) for s in seeds]


@pytest.mark.parametrize("kind", PAIRS)
def test_two_seeds_share_one_signature(kind):
    ga, gb = _pair(kind)
    assert ga.layout.kind == gb.layout.kind == kind
    assert int(np.asarray(ga.arrays.degrees).max()) > ga.arrays.ell_width
    raw = [ipgc.exact_sizes(g, kind) for g in (ga, gb)]
    assert raw[0]["tail"] != raw[1]["tail"]
    assert (raw[0]["edges"], raw[0]["hubs"]) != (raw[1]["edges"],
                                                 raw[1]["hubs"])
    assert ipgc.signature(_exact(ga)) != ipgc.signature(_exact(gb))
    ia, ib = ipgc.prepare(ga), ipgc.prepare(gb)
    assert ipgc.signature(ia) == ipgc.signature(ib)
    assert ipgc.class_sizes(ia) == ipgc.class_sizes(ib)


def _programs(r, n: int) -> set:
    """The step programs a host-loop coloring ran: the dense step, and
    the sparse step at each worklist capacity it moved to."""
    caps = bucket_capacities(n, ratio=ExecutionSpec().bucket_ratio)
    out, capacity = set(), n
    for mode, count in zip(r.mode_trace, r.counts):
        if mode == "S":
            capacity = min(capacity, pick_bucket(caps, count))
        out.add((mode, capacity if mode == "S" else n))
    return out


@pytest.mark.parametrize("kind", PAIRS)
def test_one_session_traces_each_step_once(kind):
    ga, gb = _pair(kind)
    sess = Session()
    spec = ExecutionSpec(regime="host", fused=False, window=W)
    with ipgc.LAUNCH_COUNTS.scope() as traced:
        runs = [sess.run(spec, g) for g in (ga, gb)]
        total = traced.total()
    assert sess.stats.shape_classes == 1
    ig = ipgc.prepare(ga)
    colors, aux, wl = spec.resolved_algo().init_state(ig)
    per = {mode: sum(measure_launches(impl, ig, colors, aux, wl, window=W,
                                      impl="jnp", force_hub=False,
                                      tile_rows=None).values())
           for mode, impl in (("D", ipgc.dense_step_impl),
                              ("S", ipgc.sparse_step_impl))}
    programs = _programs(runs[0], ga.n_nodes) | _programs(runs[1],
                                                          gb.n_nodes)
    assert {m for m, _ in programs} == {"D", "S"}
    # each program was traced for the first graph only
    assert total == sum(per[m] for m, _ in programs)


def _by_kind(kind: str):
    """A small kron graph assembled under ``kind``: every kind but
    pure-ell has a hub tail (ELL width 16), and csr-segment its edge
    arrays; each of their sizes lies off its class's rungs."""
    return build_graph(*rmat.edges(10, 12, 7), name=f"kron-{kind}",
                       layout=kind,
                       ell_cap=None if kind == "pure-ell" else 16)


@pytest.mark.parametrize("regime", ["host", "outlined"])
@pytest.mark.parametrize("kind", ["pure-ell", "ell-tail", "hub-split",
                                  "csr-segment"])
def test_class_padding_is_inert(kind, regime):
    g = _by_kind(kind)
    assert g.layout.kind == kind
    exact, padded = _exact(g), ipgc.prepare(g)
    sizes, before = ipgc.class_sizes(padded), ipgc.class_sizes(exact)
    if kind != "pure-ell":                              # padding engaged
        assert sizes["tail"] > before["tail"]
        assert sizes["hubs"] > before["hubs"]
    assert (sizes["edges"] > before["edges"]) == (kind == "csr-segment")
    spec = ExecutionSpec(regime=regime, fused=False, window=W)
    want, got = Session().run(spec, exact), Session().run(spec, padded)
    np.testing.assert_array_equal(got.colors, want.colors)
    assert (got.iterations, got.mode_trace, list(got.counts)) == (
        want.iterations, want.mode_trace, list(want.counts))


def test_class_size_ladder():
    xs = np.arange(1, 200_000)
    got = np.array([ipgc.class_size(int(x)) for x in xs])
    assert (got[:64] == xs[:64]).all()          # 64 and below stay exact
    assert (got >= xs).all() and (got <= xs * 33 // 32).all()
    assert (got[xs % 8 == 0] % 8 == 0).all()
    # the ladder's rungs: {32, ..., 63} * 2^j
    big = got[got > 64]
    lead = big >> (np.log2(big).astype(int) - 5)
    assert ((lead >= 32) & (lead <= 63)).all()
    assert [ipgc.class_size(x) for x in (3_727_952, 3_729_880)] == [
        3_735_552, 3_735_552]


@pytest.mark.parametrize("chunked", [False, True])
def test_bound_read_keeps_what_the_steps_read(chunked):
    """``_bound_read`` changes no step: rung 0 keeps its doubling passes,
    the others ``packed_cap``'s slots and, on csr-segment, the chunk
    loop's chunk and kept slots."""
    def passes(b):
        return max(b - 1, 0).bit_length()

    def cap(b):
        return max(-(-b // 1024) * 1024, 1024)

    def chunk_keep(c):
        chunk = ipgc.packed_chunk(c)
        return chunk, -(-c // chunk) * chunk

    for b in [0, 1, 2, 13, 16, 17, 700, 1024, 1025, 9000, 2 ** 16,
              2 ** 16 + 1, 73_728, 122_880, 3_932_160]:
        r0 = ipgc._bound_read(b, 0, chunked)
        assert r0 >= b and passes(r0) == passes(b)
        r = ipgc._bound_read(b, 3, chunked)
        assert r >= b and cap(r) == r
        if chunked:
            assert chunk_keep(r) == chunk_keep(cap(b))
        else:
            assert r == cap(b)


def test_session_counts_classes_and_spans_carry_them():
    ga, gb = _pair("csr-segment")
    g3 = _rgg(5)
    sess = Session()
    spec = ExecutionSpec(regime="host", window=W)
    tr = Trace()
    for g in (ga, gb, g3):
        sess.run(spec, g, trace=tr)
    assert sess.stats.shape_classes == 2
    spans = tr.find("session.prepare")
    assert len(spans) == 3
    for sp, g in zip(spans, (ga, gb, g3)):
        kind = g.layout.kind
        exact = ipgc.exact_sizes(g, kind)
        sizes = sp.attrs["shape_class"]
        assert sizes == ipgc.class_sizes(ipgc.prepare(g))
        assert sp.attrs["pad_share"] == pytest.approx(
            sum(sizes.values()) / sum(exact.values()) - 1)
        assert 0 <= sp.attrs["pad_share"] <= 1 / 32
    assert spans[0].attrs["shape_class"] == spans[1].attrs["shape_class"]
