"""Telemetry subsystem contracts (DESIGN.md §12).

Four guarantee families:

  * **Instruments** — histogram bucket-edge semantics (an observation on
    an edge lands IN that bucket; one past it in the next; overflow
    tracked), counter-group scoping (zero on entry, restore on exit),
    registry get-or-create discipline.
  * **Exactness** — span timings and stream latency histograms measured
    against a ``ManualClock`` are exact values, not wall-clock
    approximations; the ticket identity queue+service == total carries
    into the histograms.
  * **Unification** — a traced ``Session.run`` returns a ``RunReport``
    whose counters match the scattered sources bit-for-bit: launches ==
    ``measure_launches``, exchanges == the eval_shape invariant of
    test_distributed.py, mode trace/colors == the untraced run, cache
    == ``CacheStats.as_dict()``.
  * **Non-interference** — telemetry never changes jaxprs: step jaxprs
    with tracing+scopes active are string-identical to clean ones, and
    a traced run's colors are bit-identical to an untraced run's.
"""
import functools
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ipgc
from repro.core.policy import measure_launches
from repro.core.worklist import bucket_capacities, full_worklist, pick_bucket
from repro.exec import ExecutionSpec, Session, default_session
from repro.graphs import make_graph
from repro.obs import (CounterGroup, Histogram, MetricsRegistry, RunReport,
                       Trace, current_trace, maybe_span, tracing)
from repro.serve import ManualClock, StreamConfig
from repro.serve.clock import ManualClock as _MC  # noqa: F401 (re-export)


@pytest.fixture(scope="module")
def g():
    return make_graph("kron_g500-logn21_s", scale=0.01)


@pytest.fixture(scope="module")
def g2():
    return make_graph("rgg_n_2_24_s0_s", scale=0.01)


# ---------------------------------------------------------------------------
# histograms: bucket edges, percentiles without stored samples
# ---------------------------------------------------------------------------

def test_histogram_bucket_edges_are_inclusive_upper_bounds():
    h = Histogram("t", edges=(1.0, 2.0, 4.0))
    # on-edge lands IN the bucket; epsilon past it in the next
    assert h.bucket_index(1.0) == 0
    assert h.bucket_index(1.0000001) == 1
    assert h.bucket_index(0.0) == 0
    assert h.bucket_index(4.0) == 2
    assert h.bucket_index(4.1) == 3          # overflow bucket
    for v in (0.5, 1.0, 1.5, 4.0, 100.0):
        h.observe(v)
    assert h.counts == [2, 1, 1, 1]
    assert h.count == 5
    assert h.sum == pytest.approx(107.0)
    assert (h.min, h.max) == (0.5, 100.0)


def test_histogram_percentiles_are_bucket_upper_edges():
    h = Histogram("t", edges=(1.0, 2.0, 4.0))
    for v in [0.5] * 50 + [1.5] * 40 + [3.0] * 9 + [50.0]:
        h.observe(v)
    assert h.percentile(50) == 1.0    # rank 50 falls in bucket <=1.0
    assert h.percentile(90) == 2.0
    assert h.percentile(99) == 4.0
    assert h.percentile(100) == 50.0  # overflow reports the exact max
    s = h.summary()
    assert s["count"] == 100 and s["p50"] == 1.0 and s["p99"] == 4.0


def test_histogram_empty_and_validation():
    h = Histogram("t", edges=(1.0, 2.0))
    assert h.percentile(50) is None
    assert h.summary() == {"count": 0}
    with pytest.raises(ValueError, match="increasing"):
        Histogram("t", edges=(2.0, 1.0))
    with pytest.raises(ValueError, match="increasing"):
        Histogram("t", edges=(1.0, 1.0))


# ---------------------------------------------------------------------------
# counter groups: legacy dict surface + reset-scoping
# ---------------------------------------------------------------------------

def test_counter_group_dict_surface_and_schema():
    grp = CounterGroup("t.g", ("a", "b"))
    grp["a"] += 2
    assert dict(grp) == {"a": 2, "b": 0}
    assert "a" in grp and grp.total() == 2
    with pytest.raises(KeyError, match="schema"):
        grp["nope"] = 1


def test_counter_group_scopes_nest_and_restore():
    grp = CounterGroup("t.g", ("a",))
    grp["a"] = 3
    with grp.scope() as inner:
        assert inner["a"] == 0           # zeroed on entry
        inner["a"] += 10
        with grp.scope():
            assert grp["a"] == 0
            grp["a"] += 99
        assert grp["a"] == 10            # inner-inner restored
    assert grp["a"] == 3                 # outer restored: no leakage


def test_registry_get_or_create_and_kind_conflicts():
    reg = MetricsRegistry()
    c = reg.counter("x")
    assert reg.counter("x") is c
    with pytest.raises(TypeError, match="registered"):
        reg.gauge("x")
    reg.histogram("h", edges=(1.0,)).observe(0.5)
    reg.group("g", ("k",))["k"] += 1
    d = reg.as_dict()
    assert d["h"]["count"] == 1 and d["g"] == {"k": 1}
    reg.reset()
    assert reg.get("h").count == 0 and reg.get("g")["k"] == 0


def test_engine_counter_groups_live_in_default_registry():
    from repro.obs import default_registry
    reg = default_registry()
    assert reg.get("ipgc.launches") is ipgc.LAUNCH_COUNTS
    assert reg.get("ipgc.gathers") is ipgc.GATHER_COUNTS
    from repro.core import distributed
    assert reg.get("dist.exchanges") is distributed.EXCHANGE_COUNTS


# ---------------------------------------------------------------------------
# tracer: exact-value span timing, ambient installation, Chrome export
# ---------------------------------------------------------------------------

def test_span_timing_is_exact_under_manual_clock():
    clk = ManualClock(start=100.0, tick=0.0)
    tr = Trace(clock=clk)
    with tr.span("outer", graph="k") as outer:
        clk.advance(1.0)
        with tr.span("inner") as inner:
            clk.advance(0.25)
        clk.advance(0.5)
    assert outer.seconds == pytest.approx(1.75)
    assert inner.seconds == pytest.approx(0.25)
    assert tr.spans == [outer] and outer.children == [inner]
    assert outer.attrs == {"graph": "k"}
    # the nesting identity: children partition part of the parent
    assert inner.start >= outer.start and inner.end <= outer.end


def test_ambient_trace_install_and_noop():
    assert current_trace() is None
    with maybe_span("nothing") as sp:    # no ambient trace: records nothing
        assert sp is None
    tr = Trace(clock=ManualClock(tick=1.0))
    with tracing(tr):
        assert current_trace() is tr
        with maybe_span("work", k=1):
            pass
    assert current_trace() is None
    assert [sp.name for sp in tr.walk()] == ["work"]
    assert tr.find("work")[0].attrs == {"k": 1}


def _validate_chrome(doc):
    """Chrome trace-event schema: the keys Perfetto's importer needs."""
    assert set(doc) == {"traceEvents", "displayTimeUnit"}
    assert isinstance(doc["traceEvents"], list)
    for ev in doc["traceEvents"]:
        assert isinstance(ev["name"], str) and ev["name"]
        assert ev["ph"] in ("X", "i")
        assert isinstance(ev["ts"], (int, float)) and ev["ts"] >= 0
        assert isinstance(ev["pid"], int) and isinstance(ev["tid"], int)
        assert isinstance(ev["args"], dict)
        if ev["ph"] == "X":
            assert isinstance(ev["dur"], (int, float)) and ev["dur"] >= 0
        else:
            assert ev["s"] in ("g", "p", "t")
    json.dumps(doc)   # must round-trip


def test_chrome_export_schema_and_values():
    clk = ManualClock(start=5.0, tick=0.0)
    tr = Trace(clock=clk)
    with tr.span("a"):
        clk.advance(0.002)
        tr.event("mark", note="x")
        with tr.span("b"):
            clk.advance(0.001)
    doc = tr.to_chrome()
    _validate_chrome(doc)
    by_name = {ev["name"]: ev for ev in doc["traceEvents"]}
    assert by_name["a"]["ts"] == 0.0          # normalised to trace start
    assert by_name["a"]["dur"] == pytest.approx(3000.0)   # µs
    assert by_name["b"]["dur"] == pytest.approx(1000.0)
    assert by_name["mark"]["ph"] == "i"


# ---------------------------------------------------------------------------
# RunReport: counters match the scattered sources bit-for-bit
# ---------------------------------------------------------------------------

def test_host_report_matches_scattered_sources(g):
    s = Session()
    spec = ExecutionSpec(regime="host", window=64)
    plain = s.run(spec, g)
    rep = s.run(spec, g, trace=True)
    assert isinstance(rep, RunReport)
    # result passthrough: bit-identical to the untraced run
    np.testing.assert_array_equal(rep.colors, plain.colors)
    assert rep.mode_trace == plain.mode_trace
    assert rep.iterations == plain.iterations
    assert rep.counts == plain.counts
    assert rep.host_dispatches == plain.host_dispatches
    # launches: bit-for-bit the measure_launches numbers
    ig = ipgc.prepare(g)
    st = (ipgc.init_colors(ig.n_nodes),
          jnp.zeros((ig.n_nodes,), jnp.int32), full_worklist(ig.n_nodes))
    for mode, impl_fn in (("dense", ipgc.dense_step_impl),
                          ("sparse", ipgc.sparse_step_impl)):
        want = measure_launches(impl_fn, ig, *st, window=64,
                                impl="jnp", force_hub=None, tile_rows=None)
        assert rep.launches["per_iter"][mode] == want
    # totals = per-iter x the actual D/S mix
    nd = plain.mode_trace.count("D")
    ns = plain.mode_trace.count("S")
    assert rep.launches["total"]["mex"] == nd + ns
    assert rep.gathers["total"]["neighbor_colors"] == 2 * (nd + ns)
    # cache section IS the session's CacheStats snapshot
    assert {k: rep.cache[k] for k in ("hits", "misses", "evictions",
                                      "hit_rate", "shape_classes")
            } == s.stats.as_dict()
    # timing split invariants
    t = rep.timing
    assert t["dispatches"] == plain.host_dispatches
    assert t["dispatch_seconds"] <= t["total_seconds"] + 1e-9
    assert set(t) == {"total_seconds", "dispatch_seconds", "dispatches"}
    json.dumps(rep.to_json())


def test_dist_report_exchange_accounting(g):
    from repro.core import distributed
    from repro.core.distributed import make_dist_dense_step
    from repro.graphs.partition import prepare_partition
    s = Session()
    spec = ExecutionSpec(regime="dist", mode="hybrid", window=32,
                         n_shards=1)
    rep = s.run(spec, g, trace=True)
    # fused dist steps (the driver default): ONE exchange per iteration
    assert rep.exchanges["exchange"] == "dense"
    assert rep.exchanges["per_iter"] == {"dense": {"color_psum": 1},
                                         "sparse": {"color_psum": 1}}
    # ...matching the eval_shape invariant measured directly
    g2, _ = prepare_partition(g, 1)
    ig = ipgc.prepare(g2)
    n = ig.n_nodes
    mesh = jax.make_mesh((1,), ("data",))
    step = make_dist_dense_step(ig, mesh, ("data",), window=32, fused=True)
    with distributed.EXCHANGE_COUNTS.scope() as ec:
        jax.eval_shape(step, ipgc.init_colors(n),
                       jnp.zeros((n,), jnp.int32), full_worklist(n))
        assert (rep.exchanges["per_iter"]["dense"]["color_psum"]
                == ec["color_psum"])
    # bytes/iter: one int32[n+1] delta per device per exchange; the
    # dense path is 'd' every iteration at that flat payload
    assert rep.exchanges["payload_bytes"]["color_psum"] == 4 * (n + 1)
    assert rep.exchanges["trace"] == "d" * rep.iterations
    assert rep.exchanges["bytes_per_iter"] == \
        [4 * (n + 1)] * rep.iterations
    assert rep.exchanges["total"] == rep.iterations
    assert rep.exchanges["total_bytes"] == rep.iterations * 4 * (n + 1)


def test_dist_report_boundary_exchange_accounting(g):
    """Boundary path: the report's runtime ledger prices each iteration
    by the path it actually took — packed all-gathers when 'b', the
    owned-block swap when 'd' (obs/report.py formulas)."""
    from repro.obs.report import dense_swap_bytes, packed_exchange_bytes
    s = Session()
    spec = ExecutionSpec(regime="dist", mode="hybrid", window=32,
                         n_shards=1, exchange="auto")
    rep = s.run(spec, g, trace=True)
    n = rep.exchanges["payload_bytes"]["dense_swap"] // 4
    assert rep.exchanges["exchange"] == "auto"
    # both cond branches trace: the per-step profile counts both kinds
    assert rep.exchanges["per_iter"]["dense"] == {"boundary_pack": 1,
                                                  "dense_swap": 1}
    assert rep.exchanges["per_iter"]["sparse"] == {"boundary_pack": 1,
                                                   "dense_swap": 1}
    trace = rep.exchanges["trace"]
    assert len(trace) == rep.iterations and set(trace) <= {"d", "b"}
    for mark, got in zip(trace, rep.exchanges["bytes_per_iter"]):
        if mark == "d":
            assert got == dense_swap_bytes(n)
        else:   # packed: 8 bytes x bcap x n_shards, bcap ladder-valued
            assert got % packed_exchange_bytes(1, 1) == 0 and got > 0
    assert rep.exchanges["total_bytes"] == \
        sum(rep.exchanges["bytes_per_iter"])
    # same run, same colors as the dense-exchange report
    rep0 = s.run(ExecutionSpec(regime="dist", mode="hybrid",
                               window=32, n_shards=1), g)
    np.testing.assert_array_equal(rep.colors, rep0.colors)


def test_outlined_report_and_engine_entry_point(g):
    outlined = ExecutionSpec(regime="outlined", window=64)
    rep = default_session().run(outlined, g, trace=True)
    assert rep.regime == "outlined"
    assert rep.host_dispatches == rep.timing["dispatches"]
    assert len(rep.trace.find("session.chunk")) == rep.host_dispatches
    plain = default_session().run(outlined, g)
    np.testing.assert_array_equal(rep.colors, plain.colors)
    assert rep.mode_trace == plain.mode_trace


def test_batch_report_lanes_match_solo(g, g2):
    s = Session()
    spec = ExecutionSpec(regime="host", window=64)
    rep = s.run_batch(spec, [g, g2], trace=True)
    assert rep.regime == "batch"
    solo = [s.run(spec, x) for x in (g, g2)]
    for lane, r in zip(rep.extra["lanes"], solo):
        assert lane["n_colors"] == r.n_colors
        assert lane["iterations"] == r.iterations
        assert lane["mode_trace"] == r.mode_trace
    for got, want in zip(rep.result, solo):
        np.testing.assert_array_equal(got.colors, want.colors)
    assert rep.host_dispatches == len(rep.trace.find("batch.dispatch"))
    json.dumps(rep.to_json())


# ---------------------------------------------------------------------------
# telemetry never changes jaxprs (the non-interference guarantee)
# ---------------------------------------------------------------------------

def test_traced_and_untraced_step_jaxprs_are_identical(g):
    ig = ipgc.prepare(g)
    st = (ipgc.init_colors(ig.n_nodes),
          jnp.zeros((ig.n_nodes,), jnp.int32), full_worklist(ig.n_nodes))
    step = functools.partial(ipgc.fused_dense_step_impl, ig, window=64,
                             impl="jnp", force_hub=None, tile_rows=None)
    clean = str(jax.make_jaxpr(step)(*st))
    with tracing(Trace()), ipgc.LAUNCH_COUNTS.scope(), \
            ipgc.GATHER_COUNTS.scope(), maybe_span("session.iter"):
        instrumented = str(jax.make_jaxpr(step)(*st))
    assert clean == instrumented


def test_traced_run_colors_bit_identical(g):
    s = Session()
    for spec in (ExecutionSpec(regime="host", window=64),
                 ExecutionSpec(regime="outlined", window=64)):
        plain = s.run(spec, g)
        rep = s.run(spec, g, trace=True)
        np.testing.assert_array_equal(plain.colors, rep.colors)
        assert plain.mode_trace == rep.mode_trace


# ---------------------------------------------------------------------------
# spans in the profiler's trace: the same names, on the caller's host line
# ---------------------------------------------------------------------------

def _profiled(logdir, fn):
    """``fn()`` under the JAX profiler, inside a caller's annotation;
    returns (its result, the events of the host line that holds the
    annotation as ``(name, start_ns, end_ns)``, the other host lines)."""
    from jax.profiler import ProfileData, TraceAnnotation

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(logdir), profiler_options=opts)
    try:
        with TraceAnnotation("caller"):
            out = fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = logdir.rglob("*.xplane.pb")
    lines = [[(e.name, e.start_ns, e.start_ns + e.duration_ns)
              for e in ln.events]
             for pl in ProfileData.from_file(str(path)).planes
             if pl.name == "/host:CPU" for ln in pl.lines]
    (mine,) = [ln for ln in lines if any(e[0] == "caller" for e in ln)]
    return out, mine, [ln for ln in lines if ln is not mine]


def _inside(e, outer):
    return outer[1] <= e[1] and e[2] <= outer[2]


def test_host_loop_spans_reach_the_profiler(g2, tmp_path):
    s = Session()
    spec = ExecutionSpec(regime="host", window=64)
    s.run(spec, g2)                                 # compile outside
    r, line, others = _profiled(tmp_path, lambda: s.run(spec, g2))
    named = lambda name: [e for e in line if e[0] == name]   # noqa: E731
    (caller,) = named("caller")
    iters = named("session.iter")
    assert len(iters) == r.iterations == len(r.mode_trace)
    assert all(_inside(it, caller) for it in iters)
    for name in ("session.dispatch", "session.readback"):
        spans = named(name)
        assert len(spans) == r.iterations
        # one in each iteration, and the read-back after the dispatch
        assert all(_inside(sp, it) for sp, it in zip(spans, iters))
    for d, b in zip(named("session.dispatch"), named("session.readback")):
        assert d[2] <= b[1]
    # a resize where a sparse iteration moves to a smaller bucket
    caps = bucket_capacities(g2.n_nodes, ratio=spec.bucket_ratio)
    moves, capacity = [], g2.n_nodes
    for i, (mode, count) in enumerate(zip(r.mode_trace, r.counts)):
        if mode == "S" and capacity > pick_bucket(caps, count):
            capacity = pick_bucket(caps, count)
            moves.append(i)
    resizes = named("session.resize")
    assert moves and len(resizes) == len(moves)
    assert all(_inside(rs, iters[i]) for rs, i in zip(resizes, moves))
    (fin,) = named("session.finalize")
    assert iters[-1][2] <= fin[1] and _inside(fin, caller)
    assert len(named("session.prepare")) == 1
    # every program span is on the caller's line, none on another
    assert not [e for ln in others for e in ln
                if e[0].startswith("session.")]


def test_profiler_span_names_carry_no_metadata(g, tmp_path):
    # a run with a Trace installed: its spans, with their attributes,
    # reach the profiler under their bare names, in the same order
    s = Session()
    spec = ExecutionSpec(regime="host", window=64)
    s.run(spec, g)
    rep, line, _ = _profiled(tmp_path, lambda: s.run(spec, g, trace=True))
    assert rep.trace.find("session.iter")[0].attrs.keys() == {"mode",
                                                               "count"}
    ours = [e[0] for e in sorted(line, key=lambda e: e[1])
            if e[0].startswith(("session.", "obs."))]
    assert ours == [sp.name for sp in sorted(rep.trace.walk(),
                                             key=lambda sp: sp.start)]
    assert not [name for name in ours if "#" in name or "=" in name]


def test_profiler_leaves_colors_bit_identical(g, tmp_path):
    s = Session()
    spec = ExecutionSpec(regime="host", window=64)
    plain = s.run(spec, g)
    got, _, _ = _profiled(tmp_path, lambda: s.run(spec, g))
    np.testing.assert_array_equal(plain.colors, got.colors)
    assert (plain.mode_trace, plain.counts, plain.sparse_entries,
            plain.sparse_slots) == (got.mode_trace, got.counts,
                                    got.sparse_entries, got.sparse_slots)


# ---------------------------------------------------------------------------
# stream metrics: exact histograms under ManualClock
# ---------------------------------------------------------------------------

def test_stream_histograms_exact_under_manual_clock(g2):
    clk = ManualClock(start=0.0, tick=0.25)
    tr = Trace(clock=clk)
    s = Session()
    stream = s.stream(
        ExecutionSpec(regime="host", window=64),
        StreamConfig(lanes=2, chunk=4, clock=clk, trace=tr))
    graphs = [make_graph("rgg_n_2_24_s0_s", scale=0.005, seed=i)
              for i in range(4)]
    tickets = [stream.submit(x) for x in graphs]
    stream.drain()
    m = stream.metrics
    hq, hs, ht = (m.get("stream.queue_seconds"),
                  m.get("stream.service_seconds"),
                  m.get("stream.total_seconds"))
    done = [tk for tk in tickets if tk.status == "done"]
    assert hq.count == hs.count == ht.count == len(done) == 4
    # queue + service == total, carried into the histogram sums exactly
    assert ht.sum == pytest.approx(hq.sum + hs.sum)
    assert ht.sum == pytest.approx(sum(tk.total_seconds for tk in done))
    assert ht.min == pytest.approx(min(tk.total_seconds for tk in done))
    assert ht.max == pytest.approx(max(tk.total_seconds for tk in done))
    # queue-depth histogram: one observation per pump round
    hd = m.get("stream.queue_depth")
    assert hd.count == stream.round
    # trace spans: one stream.pump per round, dispatches counted
    assert len(tr.find("stream.pump")) == stream.round
    assert len(tr.find("stream.dispatch")) == stream.dispatches
    rep = stream.report()
    assert rep.regime == "stream"
    assert rep.extra["stream"]["done"] == 4
    assert rep.extra["metrics"]["stream.total_seconds"]["count"] == 4
    json.dumps(rep.to_json())
    _validate_chrome(tr.to_chrome())


def test_stream_queue_depth_values_are_exact(g2):
    # lanes=1, full-drain chunks: depths entering each pump are known
    s = Session()
    stream = s.stream(
        ExecutionSpec(regime="host", window=64),
        StreamConfig(lanes=1, chunk=10_000, clock=ManualClock(tick=1.0)))
    graphs = [make_graph("rgg_n_2_24_s0_s", scale=0.005, seed=i)
              for i in range(3)]
    for x in graphs:
        stream.submit(x)
    stream.drain()
    hd = stream.metrics.get("stream.queue_depth")
    # pump 1 sees 3 queued, pump 2 sees 2, pump 3 sees 1 (each round
    # admits one into the single lane and fully drains it)
    assert hd.count == 3
    # DEPTH_EDGES = (0, 1, 2, 4, ...): inclusive upper edges, so depth 3
    # lands in the <=4 bucket
    assert [hd.bucket_index(v) for v in (1, 2, 3)] == [1, 2, 3]
    assert hd.counts[1] == 1 and hd.counts[2] == 1 and hd.counts[3] == 1
    assert (hd.min, hd.max) == (1.0, 3.0)


# ---------------------------------------------------------------------------
# cache stats under pin() with tracing on
# ---------------------------------------------------------------------------

def test_evictions_under_pin_with_tracing(g):
    graphs = [make_graph("rgg_n_2_24_s0_s", scale=0.005, seed=i)
              for i in range(4)]
    s = Session(max_entries=2)
    spec = ExecutionSpec(regime="host", window=64)
    with s.pin():
        reports = [s.run(spec, x, trace=True) for x in graphs]
        # pinned: entries touched in this block are exempt, the bound
        # may be exceeded mid-flight
        assert len(s.cache) > 2
        assert s.stats.evictions == 0
    # outermost exit re-applies the bound against unpinned entries
    assert len(s.cache) <= 2
    assert s.stats.evictions > 0
    # the report's cache section snapshots the same CacheStats object
    rep = s.run(spec, graphs[0], trace=True)
    assert {k: rep.cache[k] for k in ("hits", "misses", "evictions",
                                      "hit_rate", "shape_classes")
            } == s.stats.as_dict()
    assert rep.cache["run_delta"]["evictions"] >= 0
    for r in reports:
        assert isinstance(r, RunReport) and r.n_colors > 0


# ---------------------------------------------------------------------------
# tuner sweep spans
# ---------------------------------------------------------------------------

def test_tune_sweep_records_spans(tmp_path, monkeypatch):
    from repro.kernels import tune
    monkeypatch.setenv(tune.CACHE_ENV, str(tmp_path / "tune.json"))
    tune.clear_memo()
    tr = Trace()
    with tracing(tr):
        cfg = tune.sweep("pure-ell", candidates=(8, 32))
    tune.clear_memo()
    assert cfg.tile_rows in (8, 32)
    sweeps = tr.find("tune.sweep")
    assert len(sweeps) == 1 and sweeps[0].attrs["kind"] == "pure-ell"
    cands = tr.find("tune.candidate")
    assert [sp.attrs["tile_rows"] for sp in cands] == [8, 32]
    assert all(sp.attrs["micros"] > 0 for sp in cands)
    assert all(sp in sweeps[0].children for sp in cands)
