"""The result line and the command: a small cell driven end to end on
the CPU (the chip check skipped), and the command refusing to run off a
TPU."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from bench import harness
from bench.cells import run_cell
from bench.tests._tiny import tiny_run
from bench.tests.test_bench_trace import FIXTURE

ROOT = Path(__file__).resolve().parents[2]


@pytest.fixture(scope="module")
def solo():
    return run_cell(tiny_run("kron_g500.solo"))


def test_last_line_schema(solo):
    bench = harness.load_benchmark()
    line = harness.result_line(solo, bench)
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] == len(solo.results) >= 1
    want = {m["name"]: m["unit"] for m in harness.cell_metrics(
        bench, "kron_g500.solo", "end_to_end")}
    assert {k: v["unit"] for k, v in line["metrics"].items()} == want
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert line["checks"] == {
        k: {"value": 0, "limit": 0}
        for k in ("uncolored_nodes", "conflict_edges", "color_count_gap")}
    json.loads(json.dumps(line))          # plain JSON, no NaN or objects


def test_traced_line_schema(solo):
    from bench import trace_reduce as tr

    bench = harness.load_benchmark()
    solo.trace, solo.reduction = True, tr.Reduction(tr.load(FIXTURE))
    cpu = solo.device
    solo.device = dict(cpu, kind="TPU v5 lite")     # the fixture's chip
    try:
        readers = {m["name"]: harness.load_reader(m["name"])
                   for m in harness.cell_metrics(bench, "kron_g500.solo",
                                                 "per_layer")}
        line = harness.result_line(solo, bench, readers)
    finally:
        solo.trace, solo.reduction, solo.device = False, None, cpu
    assert list(line)[-1] == "checks"
    assert {"busy_s", "window_s"} <= set(line["device"])
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    # the fixture's programs are not the run's colorings, so only check
    # that what was read carries the declared units
    units = {m["name"]: m["unit"] for m in bench["per_layer"]}
    assert line["metrics"] and all(
        v["unit"] == units[k] for k, v in line["metrics"].items())


def test_renamed_program_fails_the_run(solo, monkeypatch, capsys):
    from bench import trace_reduce as tr

    bench = harness.load_benchmark()
    readers = {m["name"]: harness.load_reader(m["name"])
               for m in harness.cell_metrics(bench, "kron_g500.solo",
                                             "per_layer")}
    # the program's dense step renamed: the recorded trace no longer
    # holds a program that the metric's file names
    monkeypatch.setattr(readers["step.dense_ms"], "PROGRAMS",
                        ("dense_sweep_renamed",))
    monkeypatch.setattr(solo, "trace", True)
    monkeypatch.setattr(solo, "reduction", tr.Reduction(tr.load(FIXTURE)))
    monkeypatch.setattr(solo, "device", dict(solo.device,
                                             kind="TPU v5 lite"))
    with pytest.raises(SystemExit) as exc:
        harness.result_line(solo, bench, readers)
    assert exc.value.code not in (0, None)
    assert "step.dense_ms" in capsys.readouterr().err


def test_no_tpu_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "kron_g500.solo",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert "correct" not in p.stdout
