"""What every cell shares: finding a cell's files by name, the device,
compile counting, the traced window, and the result line.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own, found by the name
``BENCHMARK.json`` gives it:

* ``bench/configs/<config>.json``: the deployment (sizes, generator,
  pinned program settings, guarantees);
* ``bench/traffic/<traffic>.json``: the mix, read by ``bench/load.py``,
  whose ``kind`` names its runner, ``bench/runners/<kind>.py``;
* ``bench/gen/<generator>.py``: a configuration's graph generator;
* ``bench/metrics/<metric>.py``: one per-layer metric's reader.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
#: where a traced run writes its profile (inside the checkout, ignored
#: by git, removed once read)
TRACE_DIR = ROOT / ".cache" / "bench-trace"


def load_benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def find_cell(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json; "
                     f"known: {[c['name'] for c in bench['workloads']]}")


def load_config(name: str) -> dict:
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def cell_metrics(bench: dict, cell: str, kind: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics a cell reports: those
    whose ``workloads`` list names it, or that list none."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]]


def load_reader(metric: str):
    """``bench/metrics/<metric>.py``: ``read(run) -> float | None``."""
    path = BENCH / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def execution_spec(run):
    """The ``ExecutionSpec`` with the configuration's pinned fields (none:
    the library default)."""
    from repro.exec import ExecutionSpec
    return ExecutionSpec(**run.config.get("spec", {}))


def trace_summary(trace: str) -> str:
    """A mode trace, run-length coded: ``DDSSS`` -> ``D2S3``."""
    out, i = [], 0
    while i < len(trace):
        j = i
        while j < len(trace) and trace[j] == trace[i]:
            j += 1
        out.append(f"{trace[i]}{j - i}")
        i = j
    return "".join(out)


def info(**fields) -> None:
    """An earlier line of standard output: never the result line."""
    print(json.dumps({"info": fields}, default=str), flush=True)


def device_check(chips: int):
    """The devices of the run, or exit non-zero when JAX finds no TPU or
    fewer chips than the cell needs (no result is printed then)."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: JAX found no TPU (platform "
              f"{devices[0].platform!r}); nothing was run", file=sys.stderr)
        raise SystemExit(3)
    if len(devices) < chips:
        print(f"bench: the cell needs {chips} chips, JAX found "
              f"{len(devices)}; nothing was run", file=sys.stderr)
        raise SystemExit(3)
    return devices[:chips]


def device_fields(devices) -> dict:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": max((p for p in peaks if p is not None),
                                     default=None)}


class CompileCounter:
    """Counts JAX traces, backend compiles and persistent-cache hits while
    it is open, so a run can say how many happened inside its window
    (there should be none)."""

    EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "traces",
              "/jax/core/compile/backend_compile_duration": "compiles"}

    def __init__(self):
        self.counts = {"traces": 0, "compiles": 0, "cache_hits": 0}

    def _on_duration(self, event, _secs, **_kw):
        key = self.EVENTS.get(event)
        if key:
            self.counts[key] += 1

    def _on_event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.counts["cache_hits"] += 1

    def __enter__(self):
        import jax.monitoring as mon

        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        import jax.monitoring as mon

        mon.unregister_event_duration_listener(self._on_duration)
        mon.unregister_event_listener(self._on_event)

    def snapshot(self) -> dict:
        return dict(self.counts)

    @staticmethod
    def delta(a: dict, b: dict) -> dict:
        return {k: b[k] - a[k] for k in a}


@contextlib.contextmanager
def annotate(name: str):
    """A host span in the profiler's trace (a no-op when not tracing)."""
    import jax

    with jax.profiler.TraceAnnotation(name):
        yield


@contextlib.contextmanager
def traced(run):
    """The measured window, under the profiler when ``run.trace``; the
    reduction lands in ``run.reduction`` after the trace is read."""
    if not run.trace:
        with annotate("bench.window"):
            yield
        return
    import jax

    from bench import trace_reduce

    out = TRACE_DIR / run.cell["name"]
    shutil.rmtree(out, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0          # host spans only, no py calls
    jax.profiler.start_trace(str(out), profiler_options=opts)
    try:
        with annotate("bench.window"):
            yield
    finally:
        jax.profiler.stop_trace()
    try:
        run.reduction = trace_reduce.reduce_dir(out)
    finally:
        shutil.rmtree(out, ignore_errors=True)


@dataclasses.dataclass
class Run:
    """One run of one cell: its inputs, and what the window produced."""

    cell: dict
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    #: when the process started (set-up is counted from here)
    t_start: float = dataclasses.field(default_factory=time.perf_counter)
    setup_s: "float | None" = None
    window_s: "float | None" = None
    #: end-to-end values by metric name, filled by the traffic's runner
    e2e: dict = dataclasses.field(default_factory=dict)
    #: compared numbers: name -> (value, limit)
    checks: dict = dataclasses.field(default_factory=dict)
    attempted: int = 0
    failed: int = 0
    #: one ColoringResult per coloring of the window
    results: list = dataclasses.field(default_factory=list)
    #: the index into ``edges`` of each result's graph
    graph_of: list = dataclasses.field(default_factory=list)
    #: the benchmark's own edges of each graph the window colored
    edges: list = dataclasses.field(default_factory=list)
    reduction: object = None
    device: dict = dataclasses.field(default_factory=dict)

    @property
    def correct(self) -> bool:
        return all(v <= lim for v, lim in self.checks.values())


def result_line(run: Run, bench: dict, readers: "dict | None" = None
                ) -> dict:
    """The contract's last line. ``--trace 0`` carries the cell's
    end-to-end metrics, ``--trace 1`` its per-layer ones; the compared
    numbers come last, each beside its limit. A per-layer metric whose
    reader finds nothing where there must be something (``NoMatch``)
    ends the run with exit code 4 and no line; one with nothing to read
    (None: the layer did no work in this window) is left out."""
    metrics = {}
    if run.trace:
        from bench.trace_reduce import NoMatch

        for m in cell_metrics(bench, run.cell["name"], "per_layer"):
            try:
                value = (readers or {})[m["name"]].read(run)
            except NoMatch as e:
                print(f"bench: per-layer metric {m['name']}: {e}; the run "
                      "fails", file=sys.stderr, flush=True)
                raise SystemExit(4) from e
            if value is None:
                print(f"bench: per-layer metric {m['name']} found nothing "
                      "to read in this run; left out", file=sys.stderr)
                continue
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    else:
        for m in cell_metrics(bench, run.cell["name"], "end_to_end"):
            metrics[m["name"]] = {"value": float(run.e2e[m["name"]]),
                                  "unit": m["unit"]}
    device = dict(run.device)
    line = {"correct": run.correct, "attempted": run.attempted,
            "failed": run.failed, "metrics": metrics, "device": device}
    if run.trace and run.reduction is not None:
        device["busy_s"] = run.reduction.busy_s
        device["window_s"] = run.reduction.window_s
        line["breakdown"] = run.reduction.breakdown()
    line["checks"] = {k: {"value": v, "limit": lim}
                      for k, (v, lim) in run.checks.items()}
    return line


def print_checks(run: Run) -> None:
    """The compared numbers as the last lines of standard error."""
    for k, (v, lim) in run.checks.items():
        verdict = "ok" if v <= lim else "FAILED"
        print(f"check {k} {v} limit {lim} {verdict}", file=sys.stderr)
    print(f"check correct {run.correct}", file=sys.stderr, flush=True)
