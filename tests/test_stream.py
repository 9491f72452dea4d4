"""Continuous-batching streaming service (DESIGN.md §11): bit-identity
to solo runs under adversarial arrival orders, scheduler invariants
(property-based tests skip individually with a reason when hypothesis is
absent — see _hyp), fake-clock latency accounting, backpressure, and the
bounded-cache-under-streaming regression."""
import random

import numpy as np
import pytest

from _hyp import given, settings, st
from repro.core.policy import AdaptiveChunk, FixedChunk, make_chunk_policy
from repro.core.worklist import bucket_capacities, pick_bucket
from repro.exec import ExecutionSpec, Session
from repro.graphs import make_graph
from repro.graphs.registry import get_dataset_batch, heavy_tail_requests
from repro.serve import ManualClock, StreamConfig, StreamSession

# one small mixed-family pool, built once; sizes straddle several
# hundred..several thousand nodes so arrival order matters (iteration
# counts differ) while everything shares one node rung (fast compiles)
_POOL_SPECS = [("europe_osm_s", 0.001), ("hollywood-2009_s", 0.005),
               ("soc-LiveJournal1_s", 0.01), ("europe_osm_s", 0.004),
               ("kron_g500-logn21_s", 0.003), ("hollywood-2009_s", 0.02)]


_POOL: list = []


def _pool():
    # lazy module-level pool (not a fixture: the hypothesis tests need
    # it too, and mixing pytest fixtures into @given is fragile)
    if not _POOL:
        _POOL.extend(make_graph(n, scale=s, seed=i)
                     for i, (n, s) in enumerate(_POOL_SPECS))
        _POOL.append(_POOL[0])   # a duplicate request (same Graph object)
    return _POOL


@pytest.fixture(scope="module")
def pool():
    return _pool()


_SOLO_CACHE: dict = {}


def _solo(spec, g):
    key = (spec.static_key(), id(g))
    if key not in _SOLO_CACHE:
        _SOLO_CACHE[key] = Session().run(spec, g)
    return _SOLO_CACHE[key]


def _assert_matches_solo(spec, tickets):
    for tk in tickets:
        assert tk.status == "done", (tk.status, tk.reason)
        ref = _solo(spec, tk.graph)
        np.testing.assert_array_equal(tk.result.colors, ref.colors)
        assert tk.result.n_colors == ref.n_colors
        assert tk.result.iterations == ref.iterations
        assert tk.result.mode_trace == ref.mode_trace


def _order(graphs, how, seed=0):
    idx = list(range(len(graphs)))
    if how == "asc":
        idx.sort(key=lambda i: graphs[i].n_nodes)
    elif how == "desc" or how == "big-first":
        idx.sort(key=lambda i: -graphs[i].n_nodes)
    elif how == "shuffled":
        random.Random(seed).shuffle(idx)
    return idx


# ---------------------------------------------------------------------------
# bit-identity: streamed == solo, per request, for any arrival order
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arrival", ["asc", "desc", "shuffled", "big-first"])
@pytest.mark.parametrize("algo,fused", [("ipgc", False), ("ipgc", True),
                                        ("jpl", None),
                                        ("spec-greedy", None)])
def test_stream_bit_identical_to_solo(pool, algo, fused, arrival):
    spec = ExecutionSpec(regime="host", algo=algo, fused=fused, window=64)
    stream = Session().stream(spec, StreamConfig(lanes=2, chunk=3))
    tickets = [stream.submit(pool[i]) for i in _order(pool, arrival)]
    stream.drain()
    _assert_matches_solo(spec, tickets)


def test_stream_chunk_cadence_never_changes_results(pool):
    spec = ExecutionSpec(regime="host", window=64)
    base = None
    for chunk in (1, 7, "auto", AdaptiveChunk(min_iters=1, max_iters=4)):
        s = Session()
        res = s.stream(spec, StreamConfig(lanes=2, chunk=chunk)).run(pool)
        if base is None:
            base = res
        else:
            for r, b in zip(res, base):
                np.testing.assert_array_equal(r.colors, b.colors)
                assert (r.iterations, r.mode_trace) == \
                    (b.iterations, b.mode_trace)


def test_stream_mixed_layouts_and_auto_window(pool):
    # hub-split and ell-tail members land in different lane groups but
    # one stream schedules both; window="auto" also varies per graph
    gs = [make_graph("europe_osm_s", scale=0.002, layout="ell-tail"),
          make_graph("hollywood-2009_s", scale=0.01, layout="hub-split"),
          make_graph("europe_osm_s", scale=0.004, layout="ell-tail")]
    spec = ExecutionSpec(regime="host")
    stream = Session().stream(spec, StreamConfig(lanes=2, chunk=2))
    tickets = [stream.submit(g) for g in gs]
    stream.drain()
    assert len(stream._groups) >= 2
    _assert_matches_solo(spec, tickets)


def test_stream_run_matches_run_batch(pool):
    spec = ExecutionSpec(regime="host", window=64)
    s = Session()
    streamed = s.stream(spec, StreamConfig(lanes=4)).run(pool)
    batched = s.run_batch(spec, pool)
    for r, b in zip(streamed, batched):
        np.testing.assert_array_equal(r.colors, b.colors)
        assert (r.iterations, r.mode_trace) == (b.iterations, b.mode_trace)


def test_stream_rejects_unbatchable_specs_loudly(pool):
    with pytest.raises(ValueError, match="regime"):
        Session().stream(ExecutionSpec(regime="outlined"))
    with pytest.raises(ValueError, match="impl"):
        Session().stream(ExecutionSpec(regime="host", impl="pallas"))
    with pytest.raises(ValueError, match="monotone"):
        Session().stream(ExecutionSpec(regime="host", mode="hybrid-auto"))
    stream = Session().stream(ExecutionSpec(regime="host"))
    with pytest.raises(TypeError, match="host Graph"):
        stream.submit(np.arange(3))
    g = make_graph("kron_g500-logn21_s", scale=0.01, layout="csr-segment")
    with pytest.raises(NotImplementedError, match="csr-segment"):
        stream.submit(g)


# ---------------------------------------------------------------------------
# scheduler invariants
# ---------------------------------------------------------------------------

@settings(max_examples=8, deadline=None)
@given(st.integers(0, 2**30), st.integers(1, 2), st.integers(1, 4),
       st.integers(1, 3))
def test_stream_scheduler_invariants(seed, lanes, chunk, dups):
    spec = ExecutionSpec(regime="host", window=64)
    stream = Session().stream(spec, StreamConfig(lanes=lanes, chunk=chunk,
                                                 max_queue=256))
    rng = random.Random(seed)
    reqs = [g for g in _pool() for _ in range(dups)]
    rng.shuffle(reqs)
    tickets = [stream.submit(g) for g in reqs]
    stream.drain()
    # no request lost or duplicated: every ticket terminal, exactly one
    # result per submission, seqs unique
    assert len({tk.seq for tk in tickets}) == len(reqs)
    assert all(tk.status == "done" for tk in tickets)
    assert stream.counters["done"] == len(reqs)
    assert stream.idle
    # refill only at chunk boundaries: admissions happen in pump rounds,
    # and a request is resident from its admit round to its drain round
    for tk in tickets:
        assert 1 <= tk.admit_round <= tk.drain_round <= stream.round
        # no starvation: a resident lane advances >= 1 iteration per
        # dispatch, so residency is bounded by the solo iteration count
        assert 1 <= tk.chunks <= tk.result.iterations
    _assert_matches_solo(spec, tickets)


@settings(max_examples=8, deadline=None)
@given(st.integers(1, 4), st.integers(0, 2**30))
def test_stream_queue_never_exceeds_bound(bound, seed):
    spec = ExecutionSpec(regime="host", window=64)
    stream = Session().stream(spec, StreamConfig(lanes=1, chunk=1,
                                                 max_queue=bound))
    rng = random.Random(seed)
    tickets = []
    for _ in range(3 * bound + 4):
        tickets.append(stream.submit(rng.choice(_pool())))
        assert stream.queue_len <= bound
        if rng.random() < 0.3:
            stream.pump()
            assert stream.queue_len <= bound
    stream.drain()
    assert stream.queue_len == 0
    done = [tk for tk in tickets if tk.status == "done"]
    rejected = [tk for tk in tickets if tk.status == "rejected"]
    assert len(done) + len(rejected) == len(tickets)
    assert all(tk.reason for tk in rejected)
    _assert_matches_solo(spec, done)


# ---------------------------------------------------------------------------
# latency accounting (fake clock)
# ---------------------------------------------------------------------------

def test_stream_latency_stamps_monotone_and_additive(pool):
    clk = ManualClock(start=10.0, tick=0.25)
    spec = ExecutionSpec(regime="host", window=64)
    stream = Session().stream(
        spec, StreamConfig(lanes=2, chunk=2, clock=clk))
    tickets = [stream.submit(g) for g in pool]
    stream.drain()
    for tk in tickets:
        assert tk.enqueue_s <= tk.admit_s <= tk.drain_s
        assert tk.queue_seconds >= 0 and tk.service_seconds >= 0
        # enqueue->admit and admit->drain partition the total latency
        assert tk.queue_seconds + tk.service_seconds == \
            pytest.approx(tk.total_seconds)
        assert tk.result.host_dispatches == tk.chunks


def test_stream_overload_rejects_immediately_instead_of_hanging(pool):
    clk = ManualClock(tick=1.0)
    stream = Session().stream(
        ExecutionSpec(regime="host", window=64),
        StreamConfig(lanes=1, max_queue=1, clock=clk))
    first = stream.submit(pool[0])
    second = stream.submit(pool[1])      # queue full: bounced, no pump
    assert first.status == "queued" and second.status == "rejected"
    assert "queue full" in second.reason
    assert second.admit_s is None and second.drain_s is None
    stream.drain()
    assert first.status == "done"


def test_manual_clock_is_monotone():
    clk = ManualClock(start=1.0, tick=0.5)
    assert (clk(), clk()) == (1.0, 1.5)
    clk.advance(2.0)
    assert clk() == 4.0
    with pytest.raises(ValueError, match="monotone"):
        clk.advance(-1.0)
    with pytest.raises(ValueError, match="tick"):
        ManualClock(tick=-0.1)


# ---------------------------------------------------------------------------
# backpressure / admission control
# ---------------------------------------------------------------------------

def test_stream_shed_oldest_bounces_the_queue_head(pool):
    stream = Session().stream(
        ExecutionSpec(regime="host", window=64),
        StreamConfig(lanes=1, max_queue=2, shed="shed-oldest"))
    a, b, c = (stream.submit(pool[0]), stream.submit(pool[1]),
               stream.submit(pool[2]))
    assert a.status == "rejected" and "shed" in a.reason
    assert (b.status, c.status) == ("queued", "queued")
    stream.drain()
    assert b.status == "done" and c.status == "done"


def test_stream_shed_policy_hook(pool):
    def keep_smallest(queued, incoming):
        return max((*queued, incoming), key=lambda tk: tk.n_nodes)

    stream = Session().stream(
        ExecutionSpec(regime="host", window=64),
        StreamConfig(lanes=1, max_queue=1, shed=keep_smallest))
    big = max(pool, key=lambda g: g.n_nodes)
    small = min(pool, key=lambda g: g.n_nodes)
    t_big = stream.submit(big)
    t_small = stream.submit(small)       # displaces the bigger request
    assert t_big.status == "rejected" and t_small.status == "queued"

    bad = Session().stream(
        ExecutionSpec(regime="host", window=64),
        StreamConfig(lanes=1, max_queue=1,
                     shed=lambda queued, incoming: object()))
    bad.submit(pool[0])
    with pytest.raises(ValueError, match="shed policy"):
        bad.submit(pool[1])


def test_stream_rejects_oversized_requests(pool):
    g = max(pool, key=lambda g: g.n_nodes)
    stream = Session().stream(
        ExecutionSpec(regime="host", window=64),
        StreamConfig(max_nodes=g.n_nodes - 1))
    tk = stream.submit(g)
    assert tk.status == "rejected" and "max_nodes" in tk.reason


def test_stream_max_iter_exhaustion_fails_the_ticket_not_the_service(pool):
    host = ExecutionSpec(regime="host", window=64)
    solo_iters = {id(g): _solo(host, g).iterations for g in pool}
    g_bad = max(pool, key=lambda g: solo_iters[id(g)])
    g_good = min(pool, key=lambda g: solo_iters[id(g)])
    cap = solo_iters[id(g_bad)] - 1
    assert solo_iters[id(g_good)] <= cap     # the cap only bites g_bad
    spec = ExecutionSpec(regime="host", window=64, max_iter=cap)
    stream = Session().stream(spec, StreamConfig(lanes=2, chunk=2))
    bad = stream.submit(g_bad)
    good = stream.submit(g_good)         # unaffected neighbour lane
    stream.drain()
    assert bad.status == "failed" and "max_iter" in bad.reason
    assert bad.result is None
    assert good.status == "done"
    with pytest.raises(RuntimeError, match="failed"):
        stream.run([g_bad])              # run() surfaces the failure


def test_chunk_policy_knob_resolution():
    assert isinstance(make_chunk_policy(4), FixedChunk)
    assert make_chunk_policy(4)() == 4
    assert isinstance(make_chunk_policy("auto"), AdaptiveChunk)
    pol = AdaptiveChunk(min_iters=2, max_iters=16, iters=4)
    assert make_chunk_policy(pol) is pol
    pol.observe_round(0, 3, 4)           # nobody drained: cadence doubles
    assert pol() == 8
    pol.observe_round(2, 3, 8)           # half drained: cadence halves
    assert pol() == 4
    with pytest.raises(ValueError, match=">= 1"):
        make_chunk_policy(0)
    with pytest.raises(TypeError, match="chunk"):
        make_chunk_policy(True)
    with pytest.raises(TypeError, match="chunk"):
        make_chunk_policy("fast")


# ---------------------------------------------------------------------------
# bounded default-session cache under streaming (regression)
# ---------------------------------------------------------------------------

def test_bounded_session_streams_without_evicting_live_entries(pool):
    # a tiny bound forces evictions mid-stream; results must still be
    # bit-identical because a pump round pins its own entries and all
    # device state is owned by the lane groups, not the cache
    spec = ExecutionSpec(regime="host", window=64)
    s = Session(max_entries=6)
    stream = s.stream(spec, StreamConfig(lanes=2, chunk=2))
    tickets = [stream.submit(g) for g in pool]
    stream.drain()
    _assert_matches_solo(spec, tickets)
    assert s.stats.evictions > 0          # the bound really was exercised
    assert len(s.cache) <= 6              # and re-established after


def test_default_session_stream_entry_point(pool):
    from repro.exec import default_session, reset_default_session
    reset_default_session()
    try:
        spec = ExecutionSpec(regime="host", window=64)
        stream = default_session().stream(spec)
        assert isinstance(stream, StreamSession)
        res = stream.run(pool[:2])
        for r, g in zip(res, pool[:2]):
            ref = _solo(spec, g)
            np.testing.assert_array_equal(r.colors, ref.colors)
    finally:
        reset_default_session()


# ---------------------------------------------------------------------------
# heavy-tailed request mixes (graphs/registry)
# ---------------------------------------------------------------------------

def test_heavy_tail_requests_deterministic_under_seed():
    a = heavy_tail_requests(32, seed=7)
    b = heavy_tail_requests(32, seed=7)
    c = heavy_tail_requests(32, seed=8)
    assert a == b and a != c and len(a) == 32


def test_heavy_tail_batch_covers_multiple_rungs():
    gs = get_dataset_batch(heavy_tail=16, seed=7)
    assert len(gs) == 16
    caps = bucket_capacities(1 << 20, ratio=2)
    rungs = {pick_bucket(caps, g.n_nodes) for g in gs}
    assert len(rungs) >= 2
    # popular repeated cells collapse onto shared Graph objects
    assert len({id(g) for g in gs}) < len(gs)
    again = get_dataset_batch(heavy_tail=16, seed=7)
    assert [g.n_nodes for g in gs] == [g.n_nodes for g in again]


def test_heavy_tail_knob_validation():
    with pytest.raises(ValueError, match="exactly one"):
        get_dataset_batch(["europe_osm_s"], heavy_tail=4)
    with pytest.raises(ValueError, match="exactly one"):
        get_dataset_batch()
    with pytest.raises(ValueError, match="node-parameterized"):
        heavy_tail_requests(4, names=("Audikw_1_s",))
    with pytest.raises(ValueError, match="min_nodes"):
        heavy_tail_requests(4, min_nodes=0)

# ---------------------------------------------------------------------------
# adaptive lane width (DESIGN.md §14): demand growth, shrink-on-idle
# ---------------------------------------------------------------------------

def test_two_resident_rung_runs_at_b2_not_configured_width(pool):
    # the acceptance property: a rung with two resident members pays for
    # a b=2 program, not the configured 8-lane width
    spec = ExecutionSpec(regime="host", window=64)
    stream = Session().stream(spec, StreamConfig(lanes=8, chunk=1))
    # pool[0]/pool[1] share a node rung, so they contend for one group
    a, b = stream.submit(pool[0]), stream.submit(pool[1])
    stream.pump()
    (grp,) = stream._groups.values()
    assert (grp.b, grp.b_max, grp.resident) == (2, 8, 2)
    stream.drain()
    _assert_matches_solo(spec, [a, b])


def test_adaptive_group_grows_and_shrinks_with_demand(pool):
    spec = ExecutionSpec(regime="host", window=64)
    # contention needs one rung: pick the most-populated rung and cycle
    # its members (duplicate requests are the realistic case anyway)
    caps = bucket_capacities(1 << 20)
    by_rung: dict = {}
    for g in pool:
        by_rung.setdefault(pick_bucket(caps, g.n_nodes), []).append(g)
    rung_pool = max(by_rung.values(), key=len)
    host_iters = {id(g): _solo(spec, g).iterations for g in rung_pool}
    slow = max(rung_pool, key=lambda g: host_iters[id(g)])
    rest = [g for g in rung_pool if g is not slow] or [slow]
    others = [rest[i % len(rest)] for i in range(4)]
    stream = Session().stream(
        spec, StreamConfig(lanes=8, chunk=1, shrink_after=1))
    t_slow = stream.submit(slow)
    stream.pump()                       # slow resident alone at b=1
    t_others = [stream.submit(g) for g in others]
    stream.pump()                       # queue pressure: grow mid-flight
    (grp,) = stream._groups.values()
    assert grp.grows >= 1 and grp.b >= 2
    stream.drain()                      # tail rounds under-occupy: shrink
    assert grp.shrinks >= 1
    assert grp.max_b >= 2 and grp.b <= grp.max_b
    # the resident request rode through every width change bit-identically
    _assert_matches_solo(spec, [t_slow] + t_others)


def test_fixed_mode_keeps_configured_width(pool):
    spec = ExecutionSpec(regime="host", window=64)
    stream = Session().stream(
        spec, StreamConfig(lanes=4, adaptive_lanes=False))
    tk = stream.submit(pool[0])
    stream.drain()
    (grp,) = stream._groups.values()
    assert (grp.b, grp.grows, grp.shrinks) == (4, 0, 0)
    _assert_matches_solo(spec, [tk])


@settings(max_examples=6, deadline=None)
@given(st.integers(0, 2**30), st.integers(2, 8), st.integers(1, 3))
def test_stream_invariants_across_grow_shrink(seed, lanes, chunk):
    # the no-lost/no-duplicated/no-starved invariants must survive lane
    # grow/shrink transitions: interleave submissions with pumps so
    # residency rises and falls mid-flight
    spec = ExecutionSpec(regime="host", window=64)
    stream = Session().stream(spec, StreamConfig(
        lanes=lanes, chunk=chunk, shrink_after=1, max_queue=256))
    rng = random.Random(seed)
    reqs = [g for g in _pool() for _ in range(2)]
    rng.shuffle(reqs)
    tickets = []
    for g in reqs:
        tickets.append(stream.submit(g))
        if rng.random() < 0.5:
            stream.pump()
    stream.drain()
    assert len({tk.seq for tk in tickets}) == len(reqs)
    assert all(tk.status == "done" for tk in tickets)
    assert stream.idle
    grown = sum(grp.grows for grp in stream._groups.values())
    shrunk = sum(grp.shrinks for grp in stream._groups.values())
    assert grown >= 1 and shrunk >= 1   # the transitions really happened
    for tk in tickets:
        assert 1 <= tk.admit_round <= tk.drain_round <= stream.round
        assert 1 <= tk.chunks <= tk.result.iterations
    _assert_matches_solo(spec, tickets)


def test_stream_lanes_validated_and_surfaced():
    for bad in (0, -1, True, 2.5, "8"):
        with pytest.raises(ValueError, match="lanes"):
            StreamConfig(lanes=bad)
    with pytest.raises(ValueError, match="shrink_after"):
        StreamConfig(shrink_after=0)
    cfg = StreamConfig(lanes=3)
    assert cfg.lanes_resolved == 4      # no longer silently hidden
    stream = Session().stream(ExecutionSpec(regime="host", window=64), cfg)
    assert stream.stats()["lanes_resolved"] == 4
    assert stream.report().extra["stream"]["lanes_resolved"] == 4


# ---------------------------------------------------------------------------
# admission policies: priority classes, EDF + shed-on-hopeless
# ---------------------------------------------------------------------------

def test_stream_priority_admission_orders_by_class(pool):
    spec = ExecutionSpec(regime="host", window=64)
    stream = Session().stream(
        spec, StreamConfig(lanes=1, chunk=1, admission="priority"))
    lo = stream.submit(pool[0], priority=0)
    hi = stream.submit(pool[1], priority=5)   # same rung: shared lane
    stream.pump()
    assert hi.admit_round == 1          # jumped the FIFO order
    assert lo.status == "queued"
    stream.drain()
    assert lo.admit_round > hi.admit_round
    _assert_matches_solo(spec, [lo, hi])


def test_stream_edf_orders_by_deadline(pool):
    clk = ManualClock(start=0.0, tick=0.5)
    spec = ExecutionSpec(regime="host", window=64)
    stream = Session().stream(spec, StreamConfig(
        lanes=1, chunk=1, admission="edf", clock=clk))
    # all three share a node rung so the single lane serializes them
    loose = stream.submit(pool[0], deadline_s=1e6)
    tight = stream.submit(pool[1], deadline_s=10.0)
    free = stream.submit(pool[4])       # deadline-less: after EDF ones
    stream.drain()
    assert tight.admit_round < loose.admit_round < free.admit_round
    _assert_matches_solo(spec, [loose, tight, free])


def test_stream_edf_sheds_hopeless_tickets_with_reason(pool):
    clk = ManualClock(start=0.0, tick=1.0)
    spec = ExecutionSpec(regime="host", window=64)
    stream = Session().stream(spec, StreamConfig(
        lanes=1, chunk=64, admission="edf", clock=clk))
    g = pool[0]
    warm = stream.submit(g, deadline_s=1e9)
    stream.drain()                      # observes the rung's service time
    assert warm.status == "done" and warm.deadline_met is True
    hopeless = stream.submit(g, deadline_s=0.0)
    stream.pump()
    assert hopeless.status == "rejected"
    assert "deadline" in hopeless.reason
    assert stream.counters["shed_deadline"] == 1
    assert stream.metrics.get("stream.outcome")["shed_deadline"] == 1
    feasible = stream.submit(g, deadline_s=1e9)
    stream.drain()
    assert feasible.status == "done" and feasible.deadline_met is True
    # slack histogram saw both drained deadline tickets, never the shed
    assert stream.metrics.get("stream.deadline_slack").count == 2


def test_stream_edf_never_sheds_without_observations(pool):
    # no service-time history => no estimate => the policy never guesses
    spec = ExecutionSpec(regime="host", window=64)
    stream = Session().stream(
        spec, StreamConfig(lanes=1, admission="edf"))
    tk = stream.submit(pool[0], deadline_s=0.0)   # unmeetable, but unknown
    stream.drain()
    assert tk.status == "done" and tk.deadline_met is False


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_stream_edf_meets_every_deadline_fifo_no_more(seed):
    """One lane on a manual clock (one tick a pump round, one iteration a
    round). Each deadline is the request's completion round in
    shortest-first order plus the largest single request's iterations,
    so earliest-deadline-first (which then runs shortest first) meets
    every one of them, while FIFO's arrival order can only meet fewer.
    Every finished ticket still colors exactly like its solo run."""
    requests = get_dataset_batch(
        heavy_tail={"count": 8, "names": ("europe_osm_s", "circuit5M_s"),
                    "min_nodes": 600, "max_nodes": 3000, "alpha": 1.5},
        seed=seed, layout="ell-tail")
    spec = ExecutionSpec(regime="host", window=128)
    solo = [_solo(spec, g) for g in requests]
    iters = [r.iterations for r in solo]
    deadlines, acc = {}, 0
    for i in sorted(range(len(requests)), key=lambda i: (iters[i], i)):
        acc += iters[i]
        deadlines[i] = float(acc + max(iters))
    sess = Session()
    met = {}
    for admission in ("fifo", "edf"):
        clk = ManualClock(start=0.0, tick=0.0)
        stream = sess.stream(spec, StreamConfig(
            lanes=1, chunk=1, admission=admission, clock=clk,
            max_queue=len(requests), max_nodes=4096))
        tks = [stream.submit(g, deadline_s=deadlines[i])
               for i, g in enumerate(requests)]
        while not stream.idle:
            stream.pump()
            clk.advance(1.0)
            assert stream.round < 100 * sum(iters) + 1000, "stream hung"
        met[admission] = sum(1 for tk in tks if tk.deadline_met)
        for tk, ref in zip(tks, solo):
            if tk.status == "done":
                np.testing.assert_array_equal(tk.result.colors, ref.colors)
    assert met["edf"] == len(requests), (met, iters)
    assert met["fifo"] <= met["edf"], (met, iters)


def test_admission_policy_order_must_be_permutation(pool):
    class Bad:
        def order(self, queued, clock):
            return list(queued)[:-1]

        def hopeless(self, ticket, clock, estimate):
            return None

    stream = Session().stream(ExecutionSpec(regime="host", window=64),
                              StreamConfig(admission=Bad()))
    stream.submit(pool[0])
    stream.submit(pool[1])
    with pytest.raises(ValueError, match="permutation"):
        stream.pump()


# ---------------------------------------------------------------------------
# shed-callable robustness: a raising callback rejects, never loses
# ---------------------------------------------------------------------------

def test_stream_shed_callable_raising_rejects_with_reason(pool):
    def boom(queued, incoming):
        raise RuntimeError("kaboom")

    stream = Session().stream(
        ExecutionSpec(regime="host", window=64),
        StreamConfig(lanes=1, max_queue=1, shed=boom))
    a = stream.submit(pool[0])
    b = stream.submit(pool[1])          # overload: the callback raises
    assert b.status == "rejected"
    assert "shed policy raised" in b.reason and "kaboom" in b.reason
    assert a.status == "queued"         # queued work survives the fault
    stream.drain()
    assert a.status == "done"
    _assert_matches_solo(ExecutionSpec(regime="host", window=64), [a])


# ---------------------------------------------------------------------------
# async front-end: producer threads overlap the pump loop
# ---------------------------------------------------------------------------

def test_stream_serving_overlaps_producers_with_pump_thread(pool):
    import threading

    spec = ExecutionSpec(regime="host", window=64)
    stream = Session().stream(spec, StreamConfig(lanes=4, max_queue=256))
    tickets: list = []

    def produce():
        for g in pool:
            tickets.append(stream.submit(g))

    with stream.serving():
        threads = [threading.Thread(target=produce) for _ in range(2)]
        for th in threads:
            th.start()
        extra = stream.submit(pool[0])  # the caller is a producer too
        for th in threads:
            th.join()
        assert extra.wait(timeout=300)  # per-ticket completion waiting
    assert stream.idle
    assert len({tk.seq for tk in tickets}) == 2 * len(pool)
    _assert_matches_solo(spec, tickets + [extra])
    with pytest.raises(RuntimeError, match="serving"):
        with stream.serving():
            stream.run(pool[:1])        # sync driver is refused mid-serve


# ---------------------------------------------------------------------------
# open-loop arrival traces (graphs/registry)
# ---------------------------------------------------------------------------

def test_heavy_tail_open_loop_arrivals_deterministic_and_monotone():
    plain = heavy_tail_requests(16, seed=7)
    timed = heavy_tail_requests(16, seed=7, rate=10.0)
    # the request mix is byte-identical with and without timestamps
    assert [t[:2] for t in timed] == plain
    assert timed == heavy_tail_requests(16, seed=7, rate=10.0)
    arrivals = [t[2] for t in timed]
    assert arrivals[0] == 0.0
    assert all(b >= a for a, b in zip(arrivals, arrivals[1:]))
    bursty = heavy_tail_requests(16, seed=7, rate=10.0, burstiness=4.0)
    assert [t[:2] for t in bursty] == plain
    assert bursty != timed
    # the batch builder treats the timestamp as scheduling metadata
    gs = get_dataset_batch(heavy_tail={"count": 6, "rate": 5.0}, seed=7)
    assert len(gs) == 6
    with pytest.raises(ValueError, match="rate"):
        heavy_tail_requests(4, rate=0.0)
    with pytest.raises(ValueError, match="burstiness"):
        heavy_tail_requests(4, rate=1.0, burstiness=0.0)
