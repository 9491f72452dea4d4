"""Policy tuning paths: ``AutoTuned.observe_chunk`` mixed-mode updates and
``policy.adaptive_window`` clamping at degenerate degree histograms."""
import numpy as np
import pytest

from repro.core.policy import (AutoTuned, adaptive_window, device_threshold,
                               make_policy)
from repro.graphs import build_graph


# ---------------------------------------------------------------------------
# AutoTuned.observe_chunk — the outlined engine's coarse observe hook
# ---------------------------------------------------------------------------

def test_observe_chunk_dense_majority_updates_dense_cost():
    pol = AutoTuned(prior_h=0.6)
    pol.observe_chunk(dense_iters=3, sparse_iters=1, mean_count=500.0,
                      seconds=0.8)
    # 4 iterations, dense majority: dense_cost <- per-iteration seconds
    assert pol.dense_cost == pytest.approx(0.2)
    assert pol.sparse_unit is None


def test_observe_chunk_sparse_majority_updates_sparse_unit():
    pol = AutoTuned(prior_h=0.6)
    pol.observe_chunk(dense_iters=1, sparse_iters=3, mean_count=400.0,
                      seconds=0.4)
    # sparse majority: unit cost = per-iteration seconds / mean count
    assert pol.dense_cost is None
    assert pol.sparse_unit == pytest.approx(0.1 / 400.0)


def test_observe_chunk_tie_counts_as_dense():
    pol = AutoTuned()
    pol.observe_chunk(dense_iters=2, sparse_iters=2, mean_count=100.0,
                      seconds=0.4)
    assert pol.dense_cost == pytest.approx(0.1)
    assert pol.sparse_unit is None


def test_observe_chunk_zero_iterations_is_a_noop():
    pol = AutoTuned()
    pol.observe_chunk(dense_iters=0, sparse_iters=0, mean_count=0.0,
                      seconds=0.5)
    assert pol.dense_cost is None and pol.sparse_unit is None


def test_observe_chunk_mixed_sequence_moves_the_threshold():
    """A dense chunk then a sparse chunk arm both cost models; from then
    on the threshold is the fitted crossover, not the prior, and further
    chunks move it with the EWMA (mirrors per-iteration ``observe``)."""
    n = 10_000
    pol = AutoTuned(prior_h=0.6)
    assert pol.threshold(n) == int(0.6 * n)          # prior until armed
    pol.observe_chunk(4, 0, mean_count=8_000, seconds=0.04)  # dense 0.01/it
    assert pol.threshold(n) == int(0.6 * n)          # still one-sided
    pol.observe_chunk(0, 4, mean_count=1_000, seconds=0.04)  # 1e-5/slot
    armed = pol.threshold(n)
    # crossover = dense_cost / sparse_unit ~= 1000 (fp truncation aside)
    assert armed == int(pol.dense_cost / pol.sparse_unit)
    assert armed == pytest.approx(1_000, abs=1)
    assert armed != int(0.6 * n)
    # cheaper sparse evidence pushes the crossover UP (sparse wins longer)
    pol.observe_chunk(0, 4, mean_count=1_000, seconds=0.02)
    assert pol.threshold(n) >= armed
    # the policy decision matches the threshold semantics (count <= n)
    for count in (armed, armed + 1, pol.threshold(n), pol.threshold(n) + 1):
        assert pol(count, n) == (count > pol.threshold(n))


def test_observe_chunk_threshold_feeds_device_form():
    pol = AutoTuned()
    pol.observe_chunk(3, 1, mean_count=5_000, seconds=0.3)
    pol.observe_chunk(1, 3, mean_count=500, seconds=0.01)
    n = 4_000
    assert device_threshold(pol, n) == pol.threshold(n)


# ---------------------------------------------------------------------------
# adaptive_window — degenerate degree histograms
# ---------------------------------------------------------------------------

def test_adaptive_window_empty_graph_clamps_to_lo():
    g = build_graph(np.array([], np.int64), np.array([], np.int64), 0,
                    name="null")
    assert adaptive_window(g) == 32
    assert adaptive_window(g, lo=64, hi=256) == 64


def test_adaptive_window_edgeless_graph_clamps_to_lo():
    # nodes exist but every degree is 0 (self loops are dropped)
    g = build_graph(np.array([3]), np.array([3]), 8, name="loops")
    assert adaptive_window(g) == 32


def test_adaptive_window_all_hub_graph_clamps_to_hi():
    # complete graph: every node is a hub (degree 99), median 99 ->
    # 2*(99+1) = 200 overruns the window budget and clamps to hi
    n = 100
    src = np.repeat(np.arange(n), n - 1)
    dst = np.concatenate([np.delete(np.arange(n), i) for i in range(n)])
    g = build_graph(src, dst, n, name="k100")
    assert adaptive_window(g) == 128
    assert adaptive_window(g, lo=32, hi=64) == 64


def test_adaptive_window_tracks_typical_degree_between_clamps():
    # path graph: median degree 2 -> ceil(2*3/32)*32 = 32; a custom lo
    # below the rounded value leaves the histogram in charge
    n = 64
    src = np.arange(n - 1)
    dst = np.arange(1, n)
    g = build_graph(src, dst, n, name="path")
    w = adaptive_window(g, lo=8, hi=512)
    assert w == 32
    # windows are multiples of 32 between the clamps
    assert w % 32 == 0


def test_make_policy_modes_still_resolve():
    # guard: the tuning tests above rely on these spellings
    assert isinstance(make_policy("hybrid-auto"), AutoTuned)
    assert make_policy("hybrid")(900, 1000) is True
    # the sharded Pipe is ExecutionSpec.regime's business, not a mode
    with pytest.raises(ValueError, match="unknown mode"):
        make_policy("dist-hybrid")


# ---------------------------------------------------------------------------
# admission policies (serve-side priority functions, DESIGN.md §14)
# ---------------------------------------------------------------------------

def _tk(seq, priority=0, deadline_at=None):
    from types import SimpleNamespace
    return SimpleNamespace(seq=seq, priority=priority,
                           deadline_at=deadline_at)


def test_make_admission_policy_resolution():
    from repro.core.policy import (EDFAdmission, FIFOAdmission,
                                   PriorityAdmission,
                                   make_admission_policy)
    assert isinstance(make_admission_policy("fifo"), FIFOAdmission)
    assert isinstance(make_admission_policy("priority"), PriorityAdmission)
    assert isinstance(make_admission_policy("edf"), EDFAdmission)
    pol = EDFAdmission(slack=0.5)
    assert make_admission_policy(pol) is pol
    with pytest.raises(ValueError, match="unknown admission"):
        make_admission_policy("lifo")
    with pytest.raises(TypeError, match="admission"):
        make_admission_policy(42)


def test_fifo_admission_never_reorders_and_never_calls_clock():
    from repro.core.policy import FIFOAdmission

    def forbidden():
        raise AssertionError("FIFO must not read the clock")

    pol = FIFOAdmission()
    q = [_tk(3), _tk(1), _tk(2)]
    assert pol.order(tuple(q), forbidden) == q
    assert pol.hopeless(q[0], forbidden, 1.0) is None


def test_priority_admission_sorts_by_class_then_seq():
    from repro.core.policy import PriorityAdmission
    pol = PriorityAdmission()
    q = [_tk(0, priority=0), _tk(1, priority=5), _tk(2, priority=5)]
    assert [t.seq for t in pol.order(tuple(q), lambda: 0.0)] == [1, 2, 0]
    assert pol.hopeless(q[0], lambda: 0.0, 9.9) is None


def test_edf_admission_orders_deadlines_first_then_fifo():
    from repro.core.policy import EDFAdmission
    pol = EDFAdmission()
    q = [_tk(0), _tk(1, deadline_at=9.0), _tk(2, deadline_at=3.0), _tk(3)]
    assert [t.seq for t in pol.order(tuple(q), lambda: 0.0)] == [2, 1, 0, 3]


def test_edf_hopeless_rule():
    from repro.core.policy import EDFAdmission
    pol = EDFAdmission()
    clock = lambda: 10.0
    # no deadline / no estimate: never shed
    assert pol.hopeless(_tk(0), clock, 5.0) is None
    assert pol.hopeless(_tk(0, deadline_at=11.0), clock, None) is None
    # feasible: now + estimate <= deadline
    assert pol.hopeless(_tk(0, deadline_at=15.0), clock, 5.0) is None
    # hopeless: reason names the numbers
    reason = pol.hopeless(_tk(0, deadline_at=11.0), clock, 5.0)
    assert reason is not None and "deadline" in reason
    # slack tightens the rule; shed_hopeless=False disables it
    assert EDFAdmission(slack=1.0).hopeless(
        _tk(0, deadline_at=15.5), clock, 5.0) is not None
    assert EDFAdmission(shed_hopeless=False).hopeless(
        _tk(0, deadline_at=11.0), clock, 5.0) is None
