"""The trace reduction, on a short trace recorded on one TPU v5e: two
dense and three sparse IPGC steps of a 2^12-node kron graph (trimmed from
a longer recording; the benchmark's spans clipped to the kept stretch)."""
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from bench import harness, trace_reduce as tr

FIXTURE = Path(__file__).with_name("data") / "solo_v5e.xplane.pb"


@pytest.fixture(scope="module")
def trace():
    return tr.load(FIXTURE)


@pytest.fixture(scope="module")
def red(trace):
    return tr.Reduction(trace)


def test_planes_and_lines(trace):
    assert len(trace.chips) == 1
    names = {tr.program_name(m.name) for m in trace.chips[0].modules}
    assert {"jit_dense_step_impl", "jit_sparse_step_impl"} <= names
    assert any(e.name == "bench.window" for e in trace.host)


def test_busy_is_the_union_of_operations(trace, red):
    lo, hi = red.lo, red.hi
    ops = [(max(e.start, lo), min(e.end, hi)) for e in trace.chips[0].ops
           if e.end > lo and e.start < hi]
    # the union by a sweep over sorted edges, written apart from merge()
    edges = sorted([(s, 1) for s, _ in ops] + [(e, -1) for _, e in ops])
    busy, depth, since = 0.0, 0, None
    for t, d in edges:
        if depth == 0 and d == 1:
            since = t
        depth += d
        if depth == 0 and d == -1:
            busy += t - since
    assert red.busy_s == pytest.approx(busy / 1e9, rel=1e-12)
    assert 0 < red.busy_s < red.window_s
    gaps = sum(e - s for s, e in red.gaps())
    assert (gaps + busy) / 1e9 == pytest.approx(red.window_s, rel=1e-9)


def test_idle_share_metric(red):
    idle = harness.load_reader("device.idle.solo")
    run = NS(reduction=red)
    want = 100 * (1 - red.busy_s / red.window_s)
    assert idle.read(run) == pytest.approx(want)
    assert 0 < want < 100


def test_program_time_matches_by_name(trace, red):
    dense = [m for m in trace.chips[0].modules
             if tr.program_name(m.name) == "jit_dense_step_impl"]
    count, secs = red.program_time(("dense_step_impl",))
    assert count == len(dense) == 2
    assert secs == pytest.approx(sum(m.end - m.start for m in dense) / 1e9)
    assert red.program_time(("sparse_step_impl",))[0] == 3


def test_step_metrics_divide_by_steps_run(red):
    run = NS(reduction=red, traffic={"kind": "solo"},
             results=[NS(mode_trace="DDSSS")])
    dense = harness.load_reader("step.dense_ms").read(run)
    _, secs = red.program_time(("dense_step_impl",))
    assert dense == pytest.approx(secs / 2 * 1e3)
    assert harness.load_reader("step.sparse_ms").read(run) > 0


def test_nothing_matched_reads_nothing(red):
    assert red.program_time(("_batched_chunk_impl",)) is None


@pytest.mark.parametrize("name", ["step.dense_ms", "step.sparse_ms",
                                  "kernel.dense_roofline"])
def test_program_renamed_away_fails(name):
    # steps ran, but no program of the trace carries the metric's name
    renamed = NS(reduction=NS(program_time=lambda pats: None),
                 traffic={"kind": "solo"}, results=[NS(mode_trace="DS")],
                 edges=[(None, None, 4)], device={"kind": "TPU v5 lite"})
    with pytest.raises(tr.NoMatch):
        harness.load_reader(name).read(renamed)


def test_mode_not_run_is_left_out(red):
    # a window of sparse steps alone has no dense step to time
    run = NS(reduction=red, traffic={"kind": "solo"},
             results=[NS(mode_trace="SSS")])
    assert harness.load_reader("step.dense_ms").read(run) is None
    assert harness.load_reader("step.sparse_ms").read(run) > 0


def test_idle_without_device_work_fails():
    run = NS(reduction=NS(window_s=1.0, busy_s=0.0))
    with pytest.raises(tr.NoMatch):
        harness.load_reader("device.idle.solo").read(run)


def test_breakdown(red):
    b = red.breakdown()
    assert set(b) == {"device_ops", "idle_gaps"}
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    ops = dict(b["device_ops"])
    assert all(k.startswith(("jit_dense_step_impl/", "jit_sparse_step_impl/",
                             "jit_")) for k in ops)
    # self times: no operation is charged more than the programs ran
    total = sum(m.end - m.start for m in red.trace.chips[0].modules) / 1e9
    assert sum(ops.values()) <= total
    idle = sum(v for _, v in b["idle_gaps"])
    assert idle <= red.window_s - red.busy_s + 1e-12
    assert all(k.startswith("bench.") for k, _ in b["idle_gaps"])


def test_no_device_plane_is_an_error():
    with pytest.raises(RuntimeError):
        tr.Reduction(tr.Trace(chips=[], host=[]))
