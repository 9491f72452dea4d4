"""Engine-dispatch comparison -> ``BENCH_engine.json`` / ``BENCH_dist.json``.

Times the coloring engines end-to-end (post-compile wall clock) per suite
graph:

  hybrid_host        host-loop Pipe, two-phase steps (the seed engine)
  hybrid_host_fused  host-loop Pipe, fused one-gather steps
  hybrid_outlined    device-resident Pipe (chunked lax.while_loop + fused)
  dense / sparse     the paper's degenerate baselines

The JSON records per-mode total seconds, iteration counts, host-dispatch
counts and the per-dispatch TTI trace, so the perf trajectory of the hot
path is tracked from PR 1 onward.

  PYTHONPATH=src python -m benchmarks.bench_engine_modes --scale 0.05

``--dist`` times the sharded Pipe (core.distributed.color_distributed)
across shard counts and writes ``BENCH_dist.json`` with the
per-shard-count scaling. Under ``JAX_PLATFORMS=cpu`` it re-execs itself
with ``--xla_force_host_platform_device_count`` when the environment
forces too few host devices (XLA fixes the count when the backend starts,
so the flag can't be applied in-process); on an accelerator it runs in
this process and stops when there are too few devices.

  PYTHONPATH=src python -m benchmarks.bench_engine_modes --dist --shards 1,2,8

``--algos`` sweeps the registered coloring algorithms (repro.algos) over
the execution modes each declares — host-loop, outlined, and dist-hybrid
where shard-safe — and writes ``BENCH_algos.json`` with time-to-solution
AND color count per algorithm x mode cell (the speed/quality frontier the
subsystem exists to expose). Undeclared cells carry the algorithm's own
reason string instead of numbers.

  PYTHONPATH=src python -m benchmarks.bench_engine_modes --algos

``--layouts`` sweeps the staged graph pipeline (DESIGN.md §8): every
registered reorder x every layout kind (plus the auto planner's pick) per
graph, and writes ``BENCH_graphs.json`` with build time, the chosen
layout kind, ELL width, coloring time and n_colors per cell. Every cell's
coloring is verified on the ORIGINAL node ids (reorders map back through
the inverse permutation before checking).

  PYTHONPATH=src python -m benchmarks.bench_engine_modes --layouts

``--kernels`` runs the fused-kernel leg (DESIGN.md §10) and writes
``BENCH_kernels.json``: per layout kind the launches/iteration counters,
engine seconds + n_colors, the autotuner's chosen tile config, and the
fused+compact vs separate-compact speedup (geomean is the acceptance
number).

  PYTHONPATH=src python -m benchmarks.bench_engine_modes --kernels

``--stream`` runs the continuous-batching leg (DESIGN.md §11): a
heavy-tailed request mix served by ``Session.stream`` — bounded queue,
resident lanes refilled at chunk boundaries — against one static
``run_batch`` barrier on the same warm session, and writes
``BENCH_stream.json`` with graphs/sec both ways, the stream-vs-static
ratio (acceptance: >= 2x) and per-request latency percentiles. Every
streamed result is verified bit-identical to a solo ``Session.run``.
Three adaptive sub-legs (DESIGN.md §14) ride along: an open-loop bursty
arrival trace comparing adaptive lanes + ``serving()`` against the
fixed-width synchronous front-end (acceptance: >= 1.3x throughput), a
two-resident-rung width check (b=2, not the configured 8), and an
EDF-vs-FIFO deadline replay (EDF must meet strictly more).

  PYTHONPATH=src python -m benchmarks.bench_engine_modes --stream

``--smoke`` is the CI fast path: tiny scale, one run, both engine families
(combine with --algos for the algos matrix leg, or --layouts for the
pipeline sweep).
"""
from __future__ import annotations

import argparse
import json
import os
import re
import subprocess
import sys

import numpy as np

from benchmarks.common import csv_row, geomean
from repro.caches import use_compile_cache
from repro.core import color, color_outlined_hybrid, verify_coloring
from repro.graphs import make_suite

DIST_GRAPHS = ["europe_osm_s", "kron_g500-logn21_s", "hollywood-2009_s"]

MODES = {
    "hybrid_host": lambda g, **kw: color(g, mode="hybrid", outline=False,
                                         collect_tti=True, **kw),
    "hybrid_host_fused": lambda g, **kw: color(g, mode="hybrid", fused=True,
                                               outline=False,
                                               collect_tti=True, **kw),
    # fused=False so outlined-vs-host isolates dispatch outlining; the
    # _fused row isolates step fusion (fused=None would pick per backend)
    "hybrid_outlined": lambda g, **kw: color_outlined_hybrid(
        g, fused=False, collect_tti=True, **kw),
    "hybrid_outlined_fused": lambda g, **kw: color_outlined_hybrid(
        g, fused=True, collect_tti=True, **kw),
    "dense": lambda g, **kw: color(g, mode="topology", outline=False,
                                   collect_tti=True, **kw),
    "sparse": lambda g, **kw: color(g, mode="data", outline=False,
                                    collect_tti=True, **kw),
}


def bench(scale: float = 0.05, runs: int = 3, quiet: bool = False,
          out_path: str | None = "BENCH_engine.json") -> dict:
    suite = make_suite(scale=scale)
    report: dict[str, dict] = {"scale": scale, "runs": runs, "graphs": {}}
    for name, g in suite.items():
        row: dict[str, dict] = {}
        for mode, fn in MODES.items():
            # the warm pass runs traced: the row is assembled FROM the
            # RunReport (DESIGN.md §12) so the JSON carries the unified
            # counters — launches/iter, gathers, timing split — next to
            # the legacy keys older trend tooling reads. Timed repeats
            # stay untraced: `seconds` is the bare engine number.
            warm = fn(g, trace=True)          # compile + TTI capture
            verify_coloring(g, warm.colors, context=f"{name}/{mode}")
            best = min(fn(g).total_seconds for _ in range(runs))
            row[mode] = {
                "seconds": best,
                "iterations": warm.iterations,
                "n_colors": warm.n_colors,
                "host_dispatches": warm.host_dispatches,
                "tti": [round(t, 6) for t in warm.tti],
                "launches_per_iter": warm.launches.get("per_iter", {}),
                "gathers_per_iter": warm.gathers.get("per_iter", {}),
                "timing": {k: round(v, 6) if isinstance(v, float) else v
                           for k, v in warm.timing.items()},
            }
        report["graphs"][name] = row
        if not quiet:
            host = row["hybrid_host"]["seconds"]
            outl = row["hybrid_outlined"]["seconds"]
            print(csv_row(name,
                          *(f"{row[m]['seconds'] * 1e3:.2f}" for m in MODES),
                          f"outlined/host={host / max(outl, 1e-12):.2f}x"))
    speedups = [r["hybrid_host"]["seconds"] / max(r["hybrid_outlined"]["seconds"], 1e-12)
                for r in report["graphs"].values()]
    report["geomean_outlined_vs_host"] = geomean(speedups)
    if not quiet:
        print(csv_row("GEOMEAN outlined vs host-loop",
                      f"{report['geomean_outlined_vs_host']:.2f}x"))
    if out_path:
        with open(out_path, "w") as f:
            json.dump(report, f, indent=1)
        if not quiet:
            print(f"# wrote {out_path}")
    return report


def bench_dist(shards: tuple[int, ...] = (1, 2, 8), scale: float = 0.02,
               runs: int = 2, quiet: bool = False,
               out_path: str | None = "BENCH_dist.json") -> dict:
    """Per-shard-count scaling of the sharded Pipe vs the host engine.

    Requires ``jax.device_count() >= max(shards)`` (the CLI wrapper
    re-execs with forced host-platform devices when needed).
    """
    import jax

    from repro.core.distributed import color_distributed
    from repro.graphs import make_graph

    assert jax.device_count() >= max(shards), (
        f"need {max(shards)} devices, have {jax.device_count()} — "
        "run via --dist so the CLI re-execs with forced host devices")
    report: dict = {"scale": scale, "runs": runs,
                    "device_count": jax.device_count(),
                    "backend": jax.default_backend(), "graphs": {}}
    for name in DIST_GRAPHS:
        g = make_graph(name, scale=scale)
        row: dict[str, dict] = {}
        host = color(g, mode="hybrid", fused=True, outline=False)
        row["host_loop"] = {
            "seconds": min(color(g, mode="hybrid", fused=True,
                                 outline=False).total_seconds
                           for _ in range(runs)),
            "iterations": host.iterations, "n_colors": host.n_colors}
        cache: dict = {}   # reuse jitted steps: time post-compile wall clock
        for s in shards:
            for ex in ("dense", "auto"):
                fn = lambda: color_distributed(               # noqa: E731
                    g, n_shards=s, steps_cache=cache, exchange=ex)
                warm = fn()                                   # compile
                verify_coloring(g, warm.colors,
                                context=f"{name}/shards_{s}/{ex}")
                suffix = "" if ex == "dense" else "_auto"
                row[f"shards_{s}{suffix}"] = {
                    "seconds": min(fn().total_seconds
                                   for _ in range(runs)),
                    "iterations": warm.iterations,
                    "n_colors": warm.n_colors,
                    "mode_trace": warm.mode_trace,
                    "exchange_trace": warm.exchange_trace,
                    "bytes_per_iter": list(warm.exchange_bytes),
                    # iterations whose publication went (at least
                    # partly) through the packed sparse exchange
                    "packed_iterations": sum(
                        c in "bm" for c in warm.exchange_trace),
                }
        report["graphs"][name] = row
        if not quiet:
            print(csv_row(name, *(f"{row[k]['seconds'] * 1e3:.2f}"
                                  for k in row)))
    # headline (regress.py gate): geomean over per-ITERATION ratios of
    # dense-psum bytes vs the auto path's actual ledger, at the largest
    # shard count — the PR's "exchanged bytes/iteration" claim
    smax = max(shards)
    ratios = []
    for name, row in report["graphs"].items():
        dense_b = row[f"shards_{smax}"]["bytes_per_iter"]
        auto_b = row[f"shards_{smax}_auto"]["bytes_per_iter"]
        ratios += [d / a for d, a in zip(dense_b, auto_b) if a > 0]
    report["boundary_vs_dense_bytes"] = round(geomean(ratios), 2)
    if not quiet:
        print(csv_row(f"GEOMEAN bytes/iter dense vs auto @{smax} shards",
                      f"{report['boundary_vs_dense_bytes']:.2f}x"))
    if out_path:
        with open(out_path, "w") as f:
            json.dump(report, f, indent=1)
        if not quiet:
            print(f"# wrote {out_path}")
    return report


def bench_algos(shards: int = 2, scale: float = 0.02, runs: int = 2,
                quiet: bool = False,
                out_path: str | None = "BENCH_algos.json") -> dict:
    """Algorithm x execution-mode matrix: seconds, color count, iterations.

    Every registered algorithm runs under every execution mode it declares
    (DESIGN.md §7): host-loop Pipe, device-resident outlined Pipe, and —
    for shard-safe algorithms — the sharded Pipe on ``shards`` devices.
    Each cell's coloring is verified (verify_coloring raises on an invalid
    or incomplete result — a silent quality regression cannot ship a
    number). Requires ``jax.device_count() >= shards`` for the dist cells
    (the CLI re-execs with forced host devices when short).
    """
    import jax

    from repro.algos import algorithm_names, get_algorithm
    from repro.core.distributed import color_distributed
    from repro.graphs import make_graph

    assert jax.device_count() >= shards, (
        f"need {shards} devices for the dist cells, have "
        f"{jax.device_count()} — run via --algos so the CLI re-execs with "
        "forced host devices")
    report: dict = {"scale": scale, "runs": runs, "shards": shards,
                    "backend": jax.default_backend(), "graphs": {}}
    for name in DIST_GRAPHS:
        g = make_graph(name, scale=scale)
        row: dict[str, dict] = {}
        for algo in algorithm_names():
            alg = get_algorithm(algo)
            cells: dict[str, dict] = {}
            exec_modes = {
                "host": dict(outline=False),
                "outlined": dict(outline=True),
                "dist-hybrid": dict(mode="dist-hybrid", n_shards=shards),
            }
            for emode, kw in exec_modes.items():
                if emode == "dist-hybrid" and not alg.shard_safe:
                    cells[emode] = {"unsupported": alg.shard_unsafe_reason}
                    continue
                if emode == "dist-hybrid":
                    # steps_cache so timed repeats reuse the jitted
                    # shard_map steps (same warm-timing discipline as
                    # bench_dist; without it the cell measures retracing)
                    cache: dict = {}
                    fn = lambda: color_distributed(           # noqa: E731
                        g, n_shards=shards, algo=algo, steps_cache=cache)
                else:
                    fn = lambda: color(g, algo=algo,          # noqa: E731
                                       **({"mode": "hybrid"} | kw))
                warm = fn()                       # compile
                verify_coloring(g, warm.colors, context=f"{algo}/{emode}")
                alg.check_invariants(warm, g)
                cells[emode] = {
                    "seconds": min(fn().total_seconds for _ in range(runs)),
                    "n_colors": warm.n_colors,
                    "iterations": warm.iterations,
                    "host_dispatches": warm.host_dispatches,
                }
            row[algo] = cells
        report["graphs"][name] = row
        if not quiet:
            for algo, cells in row.items():
                print(csv_row(name, algo,
                              *(f"{c['seconds'] * 1e3:.2f}ms/"
                                f"{c['n_colors']}c"
                                if "seconds" in c else "n/a"
                                for c in cells.values())))
    if out_path:
        with open(out_path, "w") as f:
            json.dump(report, f, indent=1)
        if not quiet:
            print(f"# wrote {out_path}")
    return report


def bench_layouts(scale: float = 0.02, runs: int = 2, quiet: bool = False,
                  out_path: str | None = "BENCH_graphs.json") -> dict:
    """Reorder x layout matrix over the graph pipeline (DESIGN.md §8).

    Per cell: pipeline build time, the resolved LayoutPlan (kind + ELL
    width + tail entries), host-Pipe coloring seconds/iterations and
    n_colors. Reordered cells verify their colors on the ORIGINAL node
    ids via the inverse permutation — the pipeline's round-trip contract
    rides every benchmark run, not just the test suite.
    """
    import time

    from repro.graphs import LAYOUT_KINDS, REORDERINGS, get_dataset
    from repro.graphs.registry import clear_dataset_cache

    layouts = list(LAYOUT_KINDS) + ["auto"]
    reorders = sorted(REORDERINGS)
    report: dict = {"scale": scale, "runs": runs, "graphs": {}}
    for name in DIST_GRAPHS:
        g_orig = get_dataset(name, scale=scale, layout="ell-tail")
        row: dict[str, dict] = {}
        for ro in reorders:
            for lay in layouts:
                clear_dataset_cache()        # measure the real build cost
                t0 = time.perf_counter()
                try:
                    g = get_dataset(name, scale=scale, reorder=ro,
                                    layout=lay)
                except ValueError as err:    # e.g. pure-ell cap conflicts
                    row[f"{ro}/{lay}"] = {"unsupported": str(err)}
                    continue
                build_s = time.perf_counter() - t0
                fn = lambda: color(g, mode="hybrid",    # noqa: E731
                                   outline=False)
                warm = fn()
                back = (g.perm.colors_to_original(warm.colors)
                        if g.perm is not None else warm.colors)
                verify_coloring(g_orig, back, context=f"{name}/{ro}/{lay}")
                row[f"{ro}/{lay}"] = {
                    "build_seconds": round(build_s, 4),
                    "layout": g.layout.kind,
                    "ell_width": g.ell_width,
                    "tail_entries": int(
                        (np.asarray(g.arrays.tail_src) != g.n_nodes).sum()),
                    "seconds": min(fn().total_seconds for _ in range(runs)),
                    "iterations": warm.iterations,
                    "n_colors": warm.n_colors,
                }
        report["graphs"][name] = row
        if not quiet:
            for cell, v in row.items():
                print(csv_row(name, cell,
                              (f"{v['seconds'] * 1e3:.2f}ms/"
                               f"{v['n_colors']}c/K{v['ell_width']}"
                               if "seconds" in v else "n/a")))
    if out_path:
        with open(out_path, "w") as f:
            json.dump(report, f, indent=1)
        if not quiet:
            print(f"# wrote {out_path}")
    return report


def bench_serve(scale: float = 0.02, batch_sizes: tuple[int, ...] = (1, 8, 64),
                quiet: bool = False,
                out_path: str | None = "BENCH_serve.json") -> dict:
    """Serving throughput: warm-session batched dispatch vs cold per-call.

    Models the request-serving workload the unified session exists for
    (DESIGN.md §9). Per shape class (a suite graph family at a fixed
    scale) and per batch size B, a catalog of B *distinct* graphs (seed
    variants) is colored three ways:

      cold_per_call   a fresh ``Session``, one ``run`` per graph — every
                      request pays preparation and any compilation
      warm_per_call   the same session, same stream again — per-call
                      dispatch with a hot cache
      warm_batch      ``run_batch`` on a session that has already served
                      the stream once — ONE padded device dispatch

    Records graphs/sec and the session cache hit-rate for each, plus the
    acceptance ratio ``warm_batch / cold_per_call``. Every batch result
    is verified against an individual run before timing is trusted.
    """
    import jax

    from repro.core.policy import Timer
    from repro.exec import ExecutionSpec, Session
    from repro.graphs import get_dataset_batch

    classes = ["europe_osm_s", "kron_g500-logn21_s"]
    spec = ExecutionSpec(regime="host")
    report: dict = {"scale": scale, "batch_sizes": list(batch_sizes),
                    "backend": jax.default_backend(), "classes": {}}
    best_b8 = 0.0
    for name in classes:
        row: dict[str, dict] = {}
        for b in batch_sizes:
            requests = get_dataset_batch(
                [(name, {"seed": s}) for s in range(b)], scale=scale)

            cold = Session()
            with Timer() as t_cold:
                cold_results = [cold.run(spec, g) for g in requests]
            cold_stats = cold.stats.as_dict()
            with Timer() as t_wcall:
                [cold.run(spec, g) for g in requests]

            warm = Session()
            batch_results = warm.run_batch(spec, requests)   # compile pass
            for g, rb, ri in zip(requests, batch_results, cold_results):
                verify_coloring(g, rb.colors, context=f"{name}/b{b}")
                np.testing.assert_array_equal(rb.colors, ri.colors)
            with Timer() as t_batch:
                warm.run_batch(spec, requests)
            warm_stats = warm.stats.as_dict()

            cell = {
                "cold_per_call_gps": round(b / t_cold.seconds, 2),
                "warm_per_call_gps": round(b / t_wcall.seconds, 2),
                "warm_batch_gps": round(b / t_batch.seconds, 2),
                "speedup_warm_batch_vs_cold": round(
                    t_cold.seconds / t_batch.seconds, 2),
                "cold_cache": cold_stats,
                "warm_cache": warm_stats,
            }
            if b >= 8:
                best_b8 = max(best_b8, cell["speedup_warm_batch_vs_cold"])
            row[f"batch_{b}"] = cell
            if not quiet:
                print(csv_row(name, f"B={b}",
                              f"cold {cell['cold_per_call_gps']}/s",
                              f"warm-call {cell['warm_per_call_gps']}/s",
                              f"warm-batch {cell['warm_batch_gps']}/s",
                              f"{cell['speedup_warm_batch_vs_cold']}x"))
        report["classes"][name] = row
    report["best_speedup_batch_ge_8"] = best_b8
    if not quiet:
        print(csv_row("BEST warm-batch vs cold (B>=8)", f"{best_b8:.2f}x"))
    if out_path:
        with open(out_path, "w") as f:
            json.dump(report, f, indent=1)
        if not quiet:
            print(f"# wrote {out_path}")
    return report


# The serving traffic mix: names repeat to weight the draw — road and
# hub graphs (4-7 Pipe iterations at these sizes) are the bulk of the
# traffic, web (~10) is uncommon and rgg (~16-20) rare, so a static
# barrier batch mostly rides lanes that finished long ago.
STREAM_MIX = ("europe_osm_s", "circuit5M_s", "europe_osm_s", "circuit5M_s",
              "europe_osm_s", "circuit5M_s", "indochina-2004_s",
              "rgg_n_2_24_s0_s")


def _replay_open_loop(sess, spec, cfg, requests, arrivals, *,
                      asynchronous):
    """Replay an open-loop arrival trace against one stream config.

    ``asynchronous=False`` is the PR-7-style front-end: one caller
    thread interleaving due submissions with ``pump()`` calls.
    ``asynchronous=True`` submits from the caller while the
    ``serving()`` pump thread owns the device — host admission overlaps
    device execution. Returns ``(tickets, makespan_seconds, stream)``;
    the makespan runs from the first arrival to service idle.
    """
    import time as _time

    stream = sess.stream(spec, cfg)
    tickets = []
    t0 = _time.perf_counter()
    if asynchronous:
        with stream.serving():
            for g, due in zip(requests, arrivals):
                lag = due - (_time.perf_counter() - t0)
                if lag > 0:
                    _time.sleep(lag)
                tickets.append(stream.submit(g))
        # serving() exit blocks until the pump thread drains the service
    else:
        i = 0
        while i < len(requests) or not stream.idle:
            now = _time.perf_counter() - t0
            while i < len(requests) and arrivals[i] <= now:
                tickets.append(stream.submit(requests[i]))
                i += 1
            if stream.idle and i < len(requests):
                lag = arrivals[i] - (_time.perf_counter() - t0)
                if lag > 0:
                    _time.sleep(lag)
            else:
                stream.pump()
    return tickets, _time.perf_counter() - t0, stream


def bench_stream(count: int = 20, max_nodes: int = 4_000, lanes: int = 4,
                 seed: int = 7, ol_lanes: int = 8,
                 ol_rate: float | None = None, ol_burstiness: float = 2.0,
                 quiet: bool = False,
                 out_path: str | None = "BENCH_stream.json") -> dict:
    """Continuous-batching leg (DESIGN.md §11, §14) ->
    ``BENCH_stream.json``.

    A heavy-tailed request mix (bounded Pareto over graph sizes — many
    small graphs, a few huge ones) is colored two ways on one warm
    session:

      static   ``run_batch`` — every shape-class rung is one barrier
               batch padded to a power-of-two lane count, iterating until
               its slowest member drains
      stream   ``Session.stream`` — resident lanes per rung, drained
               lanes refilled from the queue at chunk boundaries, so
               small requests stop paying for the tail

    The mix pins ``layout="ell-tail"`` (the stream contract is
    ELL-family only; the auto planner would hand some draws
    csr-segment). Acceptance is ``stream graphs/sec >= 2x static``, and
    it only counts because every streamed result is verified
    bit-identical (colors, iterations, mode trace) to a solo
    ``Session.run`` of the same request. Latency percentiles come from
    the tickets' enqueue/admit/drain stamps.

    Three adaptive sub-legs (DESIGN.md §14) ride on the same session:

      open_loop     a multi-rung bursty arrival trace
                    (``heavy_tail_requests(rate=...)``) replayed twice —
                    fixed-width synchronous front-end (the PR-7
                    behaviour) vs adaptive lanes under ``serving()``.
                    Acceptance: adaptive/async throughput >= 1.3x fixed.
      two_resident  two same-rung requests against ``lanes=8`` must run
                    at b=2, not the configured width.
      deadlines     one trace, two admission policies on a manual
                    clock: EDF must meet strictly more deadlines than
                    FIFO.
    """
    import jax

    from repro.core.policy import Timer
    from repro.exec import ExecutionSpec, Session
    from repro.graphs import get_dataset_batch
    from repro.graphs.registry import heavy_tail_requests
    from repro.serve import ManualClock, StreamConfig

    # min_nodes sits just above the capacity ladder's second rung
    # (max_nodes/2 under the default bucket_ratio=2), so the whole mix
    # shares ONE shape-class rung with its slowest members — the
    # barrier-vs-refill comparison, not a bucketing comparison.
    requests = get_dataset_batch(
        heavy_tail={"count": count, "names": STREAM_MIX,
                    "min_nodes": max_nodes // 2 + 100,
                    "max_nodes": max_nodes, "alpha": 1.5},
        seed=seed, layout="ell-tail")
    spec = ExecutionSpec(regime="host", window=128)
    sess = Session()

    solo = [sess.run(spec, g) for g in requests]   # reference + warm cache

    sess.run_batch(spec, requests)                 # compile pass
    with Timer() as t_static:
        static_results = sess.run_batch(spec, requests)

    # anchor the stream's capacity ladder at the workload bound so its
    # rungs match run_batch's (which anchors at the batch max) — a
    # 1<<20 ladder would pad the big rung's lanes far past static's
    cfg = StreamConfig(lanes=lanes, chunk="auto", max_queue=count,
                       max_nodes=max_nodes)
    sess.stream(spec, cfg).run(requests)           # compile pass
    stream = sess.stream(spec, cfg)
    with Timer() as t_stream:
        tickets = [stream.submit(g) for g in requests]
        stream.drain()

    for g, tk, ref in zip(requests, tickets, solo):
        r = tk.result
        verify_coloring(g, r.colors, context=f"stream seq {tk.seq}")
        np.testing.assert_array_equal(r.colors, ref.colors)
        assert r.iterations == ref.iterations, (tk.seq, r, ref)
        assert r.mode_trace == ref.mode_trace, (tk.seq, r, ref)
    for rb, ref in zip(static_results, solo):
        np.testing.assert_array_equal(rb.colors, ref.colors)

    ratio = t_static.seconds / t_stream.seconds
    # latency percentiles come from the stream's own fixed-bucket
    # histograms (obs/metrics.py) — the same numbers a live service
    # exports, not a recomputation over retained samples
    h_total = stream.metrics.get("stream.total_seconds")
    h_queue = stream.metrics.get("stream.queue_seconds")

    def pct(p):
        return round(float(h_total.percentile(p)), 4)

    # -- open loop: adaptive+async vs the PR-7 fixed-lane front-end ----
    # a multi-rung mix (min_nodes well below the top rung) under timed
    # arrivals: rungs are sparsely resident most of the time, which is
    # exactly where a fixed width pays for lanes it doesn't use
    if ol_rate is None:
        ol_rate = max(10.0, 2.0 * count / t_stream.seconds)
    ol_entries = heavy_tail_requests(
        count, seed=seed, names=STREAM_MIX, min_nodes=max_nodes // 8,
        max_nodes=max_nodes, alpha=1.5, rate=ol_rate,
        burstiness=ol_burstiness)
    arrivals = [e[2] for e in ol_entries]
    ol_graphs = get_dataset_batch(ol_entries, seed=seed, layout="ell-tail")
    ol_solo = [sess.run(spec, g) for g in ol_graphs]

    def ol_cfg(adaptive, lanes_=None):
        return StreamConfig(lanes=lanes_ or ol_lanes,
                            adaptive_lanes=adaptive, chunk="auto",
                            max_queue=count, max_nodes=max_nodes)

    # compile passes: adaptive growth under real-time arrivals can
    # dispatch at ANY pow2 width <= the cap (growth timing is load-
    # dependent), so compile the whole width ladder for every rung in
    # the mix — a fixed-width closed-loop run dispatches at exactly b
    b = 1
    while b <= ol_cfg(False).lanes_resolved:
        sess.stream(spec, ol_cfg(False, lanes_=b)).run(ol_graphs)
        b *= 2

    def ol_leg(adaptive, asynchronous, runs=2):
        best = None
        for _ in range(runs):
            tks, wall, s = _replay_open_loop(
                sess, spec, ol_cfg(adaptive), ol_graphs, arrivals,
                asynchronous=asynchronous)
            for g, tk, ref in zip(ol_graphs, tks, ol_solo):
                assert tk.status == "done", (tk.seq, tk.status, tk.reason)
                np.testing.assert_array_equal(tk.result.colors, ref.colors)
                assert tk.result.iterations == ref.iterations
            if best is None or wall < best[0]:
                best = (wall, s)
        wall, s = best
        st = s.stats()
        h = s.metrics.get("stream.total_seconds")
        return {
            "makespan_s": round(wall, 4),
            "gps": round(count / wall, 2),
            "p50_s": round(float(h.percentile(50)), 4),
            "p99_s": round(float(h.percentile(99)), 4),
            "lane_occupancy": st["lane_occupancy"],
            "shed_rate": round(st["rejected"] / max(1, st["submitted"]), 4),
            "lane_groups": st["lane_groups"],
        }

    ol_fixed = ol_leg(adaptive=False, asynchronous=False)
    ol_adaptive = ol_leg(adaptive=True, asynchronous=True)
    ol_ratio = ol_fixed["makespan_s"] / ol_adaptive["makespan_s"]
    p99_ratio = (ol_fixed["p99_s"] / ol_adaptive["p99_s"]
                 if ol_adaptive["p99_s"] > 0 else None)
    open_loop = {
        "knobs": {"count": count, "min_nodes": max_nodes // 8,
                  "max_nodes": max_nodes, "lanes": ol_lanes,
                  "rate": round(ol_rate, 2), "burstiness": ol_burstiness},
        "fixed_sync": ol_fixed,
        "adaptive_async": ol_adaptive,
        "adaptive_vs_fixed_gps": round(ol_ratio, 2),
        "fixed_vs_adaptive_p99": (None if p99_ratio is None
                                  else round(p99_ratio, 2)),
        "acceptance_ge_1_3x": ol_ratio >= 1.3,
    }

    # -- two residents pay for b=2, not the configured 8-lane width ----
    # chunk=1 so both stay resident past the first pump: the recorded
    # group state is a mid-flight two-resident rung running a b=2
    # program (same compiled program — chunk is a traced scalar)
    tr_stream = sess.stream(spec, StreamConfig(
        lanes=8, chunk=1, max_queue=4, max_nodes=max_nodes))
    tr_a, tr_b = tr_stream.submit(requests[0]), tr_stream.submit(requests[1])
    tr_stream.pump()
    (tr_grp,) = tr_stream._groups.values()
    two_resident = {"b": tr_grp.b, "b_max": tr_grp.b_max,
                    "resident": tr_grp.resident,
                    "acceptance_b2": tr_grp.b == 2}
    assert tr_grp.b == 2, two_resident
    tr_stream.drain()
    for tk, ref in zip((tr_a, tr_b), (solo[0], solo[1])):
        np.testing.assert_array_equal(tk.result.colors, ref.colors)

    # -- deadlines: EDF meets strictly more than FIFO on one trace -----
    # deadlines are SJF completion rounds + one max-service margin on a
    # manual clock (1 tick per pump round): feasible under EDF order for
    # every request, while FIFO's arrival order blows through the tight
    # ones whenever a long request lands early
    iters = [r.iterations for r in solo]
    sjf = sorted(range(count), key=lambda i: (iters[i], i))
    deadlines, acc = {}, 0
    for i in sjf:
        acc += iters[i]
        deadlines[i] = float(acc + max(iters))
    dl_met, dl_shed = {}, {}
    for admission in ("fifo", "edf"):
        clk = ManualClock(start=0.0, tick=0.0)
        s = sess.stream(spec, StreamConfig(
            lanes=1, chunk=1, admission=admission, clock=clk,
            max_queue=count, max_nodes=max_nodes))
        tks = [s.submit(g, deadline_s=deadlines[i])
               for i, g in enumerate(requests)]
        while not s.idle:
            s.pump()
            clk.advance(1.0)
            assert s.round < 100 * sum(iters) + 1000, "deadline leg hung"
        dl_met[admission] = sum(1 for tk in tks if tk.deadline_met)
        dl_shed[admission] = s.stats()["shed_deadline"]
        for i, tk in enumerate(tks):
            if tk.status == "done":
                np.testing.assert_array_equal(tk.result.colors,
                                              solo[i].colors)
    assert dl_met["edf"] > dl_met["fifo"], (dl_met, dl_shed)
    deadline_leg = {
        "count": count, "fifo_met": dl_met["fifo"],
        "edf_met": dl_met["edf"], "fifo_shed": dl_shed["fifo"],
        "edf_shed": dl_shed["edf"],
        "acceptance_edf_gt_fifo": dl_met["edf"] > dl_met["fifo"],
    }

    report = {
        "backend": jax.default_backend(),
        "knobs": {"count": count, "names": list(STREAM_MIX),
                  "min_nodes": max_nodes // 2 + 100,
                  "max_nodes": max_nodes, "lanes": lanes, "seed": seed,
                  "alpha": 1.5, "window": 128, "layout": "ell-tail"},
        "sizes": sorted(g.n_nodes for g in requests),
        "static_seconds": round(t_static.seconds, 4),
        "stream_seconds": round(t_stream.seconds, 4),
        "static_gps": round(count / t_static.seconds, 2),
        "stream_gps": round(count / t_stream.seconds, 2),
        "stream_vs_static": round(ratio, 2),
        "acceptance_ge_2x": ratio >= 2.0,
        "latency": {"p50_s": pct(50), "p90_s": pct(90), "p99_s": pct(99),
                    "max_s": round(h_total.max, 4),
                    "mean_queue_s": round(h_queue.mean, 4)},
        "chunk_dispatches": sum(tk.chunks for tk in tickets),
        "stream_stats": stream.stats(),
        "metrics": stream.metrics.as_dict(),
        "verified_bit_identical": len(tickets) + 2 * len(ol_graphs) + 2,
        "open_loop": open_loop,
        "two_resident": two_resident,
        "deadlines": deadline_leg,
        "adaptive_vs_fixed_gps": open_loop["adaptive_vs_fixed_gps"],
        "fixed_vs_adaptive_p99": open_loop["fixed_vs_adaptive_p99"],
    }
    if not quiet:
        print(csv_row("stream", f"N={count}",
                      f"static {report['static_gps']}/s",
                      f"stream {report['stream_gps']}/s",
                      f"{report['stream_vs_static']}x",
                      f"p50 {report['latency']['p50_s']}s",
                      f"p99 {report['latency']['p99_s']}s"))
        print(csv_row(
            "stream-ol", f"N={count}", f"rate {open_loop['knobs']['rate']}/s",
            f"fixed {ol_fixed['gps']}/s occ {ol_fixed['lane_occupancy']}",
            f"adaptive {ol_adaptive['gps']}/s occ "
            f"{ol_adaptive['lane_occupancy']}",
            f"{open_loop['adaptive_vs_fixed_gps']}x",
            f"p99 {ol_fixed['p99_s']}s->{ol_adaptive['p99_s']}s"))
        print(csv_row("stream-edf", f"N={count}",
                      f"fifo met {dl_met['fifo']}/{count}",
                      f"edf met {dl_met['edf']}/{count}",
                      f"shed {dl_shed['edf']}",
                      f"two-resident b={two_resident['b']}"))
    if out_path:
        with open(out_path, "w") as f:
            json.dump(report, f, indent=1)
        if not quiet:
            print(f"# wrote {out_path}")
    return report


def bench_kernels(scale: float = 0.02, rows: int = 2048, runs: int = 5,
                  quiet: bool = False,
                  out_path: str | None = "BENCH_kernels.json") -> dict:
    """Fused-kernel leg (DESIGN.md §10) -> ``BENCH_kernels.json``.

    Per layout kind: launches/iteration from the trace-time counters
    (fused vs two-phase), end-to-end engine seconds + n_colors on a
    kind-shaped suite graph, the autotuner's chosen tile config (with the
    sweep micros justifying it), and — for the ELL kinds — the warm jitted
    wall time of ONE fused+compact launch (at the tuned tile) against the
    separate-compact path it replaces (fused_step kernel at the fixed
    32-row default + jnp epilogue + compact launch). The geomean of those
    ratios is the PR-6 acceptance number (>= 1.3x).
    """
    import time

    import jax
    import jax.numpy as jnp

    from repro.core import ipgc
    from repro.core.policy import Timer, measure_launches
    from repro.core.worklist import compact_items, full_worklist
    from repro.kernels import tune
    from repro.kernels.compact import compact_pallas
    from repro.kernels.fused_compact import fused_compact_pallas
    from repro.kernels.fused_step import fused_step_pallas
    from repro.graphs import make_graph
    from repro.kernels.ops import interpret as interpret_mode

    interpret = interpret_mode()
    window = 128

    def synth_case(hub: bool):
        rng = np.random.default_rng(0)
        r, k = rows, 16
        nc = jnp.asarray(rng.integers(-2, 60, (r, k)).astype(np.int32))
        npr = jnp.asarray(rng.integers(-1, 100, (r, k)).astype(np.int32))
        nid = jnp.asarray(rng.integers(0, r + 1, (r, k)).astype(np.int32))
        base = jnp.zeros((r,), jnp.int32)
        cu = jnp.asarray(rng.integers(-2, 60, (r,)).astype(np.int32))
        pu = jnp.asarray(rng.integers(0, 100, (r,)).astype(np.int32))
        ids = jnp.arange(r, dtype=jnp.int32)
        active = jnp.asarray(rng.random(r) < 0.8)
        pending = active & (cu >= 0)
        extra = jnp.asarray(rng.random((r, window)) < 0.1) if hub else None
        hl = jnp.asarray(rng.random(r) < 0.05) if hub else None
        return (nc, npr, nid, base, cu, pu, ids, active, pending, extra, hl)

    def timed(fn):
        jax.block_until_ready(fn())          # compile
        jax.block_until_ready(fn())          # warm
        ts = []
        for _ in range(runs):
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            ts.append(time.perf_counter() - t0)
        return float(np.median(ts))

    def kernel_pair(hub: bool, tuned_tile: int):
        case = synth_case(hub)
        nc, npr, nid, base, cu, pu, ids, active, pending, extra, hl = case

        @jax.jit
        def one_launch():
            # the fused kernel, then the step's XLA compaction
            new_c, new_b, need = fused_compact_pallas(
                *case, window, tile_rows=tuned_tile, interpret=interpret)
            items, count = compact_items(ids, need, rows, rows)
            return new_c, new_b, need, items, count

        @jax.jit
        def separate():
            # the pre-§10 path: fused kernel (dense hub bitmap always
            # threaded) + host-side selection + a second compact launch
            ef = extra if hub else jnp.zeros((rows, window), bool)
            lose, first = fused_step_pallas(
                nc, npr, nid, base, cu, pu, ids, pending, ef, window,
                tile_rows=tune.DEFAULT_TILE_ROWS, interpret=interpret)
            if hub:
                lose = lose | (hl & pending)
            has = first >= 0
            need = lose | (active & (cu < 0))
            new_c = jnp.where(need & has, base + first,
                              jnp.where(lose, -1, cu))
            new_b = jnp.where(need & ~has, base + window, base)
            items, count = compact_pallas(need, interpret=interpret)
            return new_c, new_b, need, items, count

        return timed(one_launch), timed(separate)

    kinds = {
        "pure-ell": ("europe_osm_s", "pallas"),
        "ell-tail": ("hollywood-2009_s", "pallas"),
        "hub-split": ("hollywood-2009_s", "pallas"),
        "csr-segment": ("hollywood-2009_s", "jnp"),
    }
    report: dict = {"scale": scale, "rows": rows,
                    "backend": jax.default_backend(),
                    "interpret": interpret, "kinds": {}}
    ratios, tuned_beats_32 = [], []
    for kind, (gname, impl) in kinds.items():
        g = make_graph(gname, scale=scale, layout=kind)
        ig = ipgc.prepare(g)
        state = (ipgc.init_colors(ig.n_nodes),
                 jnp.zeros((ig.n_nodes,), jnp.int32),
                 full_worklist(ig.n_nodes))
        cell: dict = {
            "launches_fused": measure_launches(
                ipgc.fused_dense_step_impl, ig, *state,
                window=32, impl=impl),
            "launches_two_phase": measure_launches(
                ipgc.dense_step_impl, ig, *state, window=32, impl=impl),
        }

        color(g, impl=impl, fused=True, outline=False)   # compile pass
        with Timer() as t_eng:
            r = color(g, impl=impl, fused=True, outline=False)
        cell["engine_seconds"] = round(t_eng.seconds, 4)
        cell["n_colors"] = r.n_colors
        cell["iterations"] = r.iterations
        verify_coloring(g, r.colors, context=kind)

        cfg = tune.get_tile_config(kind)
        cell["tile_config"] = {"tile_rows": cfg.tile_rows,
                               "micros": cfg.micros}
        if kind in tune.ELL_KINDS:
            chosen = cfg.tile_rows or tune.DEFAULT_TILE_ROWS
            fixed = cfg.micros.get(str(tune.DEFAULT_TILE_ROWS))
            best = cfg.micros.get(str(chosen))
            if fixed and best and best < fixed:
                tuned_beats_32.append(kind)
            hub = kind in ("ell-tail", "hub-split")
            t_fused, t_sep = kernel_pair(hub, chosen)
            ratio = t_sep / t_fused
            ratios.append(ratio)
            cell["fused_compact_ms"] = round(t_fused * 1e3, 3)
            cell["separate_compact_ms"] = round(t_sep * 1e3, 3)
            cell["speedup_vs_separate"] = round(ratio, 2)
        if not quiet:
            print(csv_row(
                kind, f"{cell['launches_fused']['fused']} launch/iter",
                f"tile {cfg.tile_rows}",
                (f"{cell['speedup_vs_separate']}x vs separate"
                 if "speedup_vs_separate" in cell else "jnp core"),
                f"{cell['engine_seconds'] * 1e3:.1f}ms/{r.n_colors}c"))
        report["kinds"][kind] = cell
    report["fused_compact_geomean_speedup"] = round(geomean(ratios), 2)
    report["tuned_beats_32_kinds"] = tuned_beats_32
    if not quiet:
        print(csv_row("GEOMEAN fused+compact vs separate",
                      f"{report['fused_compact_geomean_speedup']:.2f}x"),
              csv_row("tuned tile beats fixed 32 on", *tuned_beats_32))
    if out_path:
        with open(out_path, "w") as f:
            json.dump(report, f, indent=1)
        if not quiet:
            print(f"# wrote {out_path}")
    return report


def bench_obs(scale: float = 0.02, runs: int = 5, quiet: bool = False,
              out_path: str | None = "BENCH_obs.json") -> dict:
    """Telemetry overhead gate (DESIGN.md §12) -> ``BENCH_obs.json``.

    Two acceptance numbers:

      * **overhead** — per graph x regime, best-of-``runs`` wall seconds
        of a traced ``Session.run`` (span recording + dispatch meter +
        RunReport assembly, profile cache warm) over best-of-``runs``
        untraced. Acceptance: geomean ratio <= 1.03 — telemetry must be
        effectively free, or nobody leaves it on.
      * **jaxpr identity** — the step jaxpr built with an ambient Trace
        and live counter scopes is STRING-IDENTICAL to one built clean.
        Telemetry lives at trace time only; a counter that leaked into
        the program would shift every compile cache and potentially the
        schedule. This is the compile-level proof backing the
        bit-identity run checks in tests/test_obs.py.

    A full sample ``RunReport.to_json()`` rides along so the report
    schema itself is under version control and schema drift shows up in
    diffs.
    """
    import functools

    import jax
    import jax.numpy as jnp

    from repro.core import ipgc
    from repro.core.policy import Timer
    from repro.core.worklist import full_worklist
    from repro.exec import ExecutionSpec, Session
    from repro.graphs import make_graph
    from repro.obs import Trace, tracing

    specs = {
        "host": ExecutionSpec(regime="host", window=64),
        "outlined": ExecutionSpec(regime="outlined", window=64),
    }
    sess = Session()
    report: dict = {"scale": scale, "runs": runs,
                    "backend": jax.default_backend(), "graphs": {},
                    "threshold": 1.03}
    ratios = []
    sample = None
    for name in DIST_GRAPHS:
        g = make_graph(name, scale=scale)
        row: dict[str, dict] = {}
        for rname, spec in specs.items():
            plain_ref = sess.run(spec, g)            # compile pass
            rep = sess.run(spec, g, trace=True)      # + profile cache warm
            verify_coloring(g, rep.colors, context=f"{name}/{rname}")
            np.testing.assert_array_equal(rep.colors, plain_ref.colors)
            assert rep.mode_trace == plain_ref.mode_trace

            def best_of(traced: bool) -> float:
                times = []
                for _ in range(runs):
                    with Timer() as t:
                        sess.run(spec, g, trace=True if traced else None)
                    times.append(t.seconds)
                return min(times)

            plain_s, traced_s = best_of(False), best_of(True)
            ratio = traced_s / max(plain_s, 1e-12)
            ratios.append(ratio)
            row[rname] = {
                "untraced_seconds": round(plain_s, 6),
                "traced_seconds": round(traced_s, 6),
                "ratio": round(ratio, 4),
                "iterations": rep.iterations,
                "spans": len(list(rep.trace.walk())),
            }
            if sample is None:
                sample = rep.to_json()
        report["graphs"][name] = row
        if not quiet:
            print(csv_row(name, *(f"{rname} {c['ratio']:.3f}x"
                                  for rname, c in row.items())))

    # jaxpr identity: instrumentation on vs off, same program text
    g = make_graph(DIST_GRAPHS[0], scale=scale)
    ig = ipgc.prepare(g)
    state = (ipgc.init_colors(ig.n_nodes),
             jnp.zeros((ig.n_nodes,), jnp.int32),
             full_worklist(ig.n_nodes))
    identical = True
    for step in (ipgc.fused_dense_step_impl, ipgc.dense_step_impl,
                 ipgc.sparse_step_impl):
        fn = functools.partial(step, ig, window=64, impl="jnp",
                               force_hub=None, tile_rows=None)
        clean = str(jax.make_jaxpr(fn)(*state))
        with tracing(Trace()), ipgc.LAUNCH_COUNTS.scope(), \
                ipgc.GATHER_COUNTS.scope():
            instrumented = str(jax.make_jaxpr(fn)(*state))
        identical = identical and (clean == instrumented)
    report["jaxpr_identical_traced_vs_untraced"] = identical

    gm = geomean(ratios)
    report["geomean_traced_vs_untraced"] = round(gm, 4)
    report["acceptance_overhead_le_3pct"] = gm <= report["threshold"]
    report["sample_report"] = sample
    if not quiet:
        print(csv_row("GEOMEAN traced vs untraced", f"{gm:.4f}x",
                      f"jaxpr identical: {identical}"))
    if out_path:
        with open(out_path, "w") as f:
            json.dump(report, f, indent=1)
        if not quiet:
            print(f"# wrote {out_path}")
    return report


def _forced_cpu_devices() -> int:
    """Host-platform devices ``XLA_FLAGS`` forces (1 when it forces none)."""
    m = re.search(r"--xla_force_host_platform_device_count=(\d+)",
                  os.environ.get("XLA_FLAGS", ""))
    return int(m.group(1)) if m else 1


def _needs_reexec(n_devices: int) -> bool:
    """Decided from the environment alone, before anything touches JAX.

    Forced host devices exist only on the CPU platform, and XLA binds
    their count when the backend starts, so under ``JAX_PLATFORMS=cpu``
    a run that needs more of them re-execs itself with the flag raised.
    On an accelerator the run stays in this process: a child would find
    the chip held by its parent.
    """
    return (os.environ.get("JAX_PLATFORMS") == "cpu"
            and _forced_cpu_devices() < n_devices)


def _reexec_with_devices(argv: list[str], n_devices: int) -> int:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        f" --xla_force_host_platform_device_count={n_devices}"
                        ).strip()
    return subprocess.run(
        [sys.executable, "-m", "benchmarks.bench_engine_modes", *argv],
        env=env).returncode


def _require_devices(n_devices: int) -> None:
    import jax
    if jax.device_count() < n_devices:
        raise SystemExit(
            f"needs {n_devices} devices, found {jax.device_count()} on "
            f"{jax.default_backend()}; run under JAX_PLATFORMS=cpu (forced "
            f"host devices) or pass a smaller --shards list")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=0.05)
    ap.add_argument("--runs", type=int, default=3)
    ap.add_argument("--out", default="BENCH_engine.json")
    ap.add_argument("--dist", action="store_true",
                    help="bench the sharded Pipe across --shards")
    ap.add_argument("--shards", default="1,2,8")
    ap.add_argument("--dist-out", default="BENCH_dist.json")
    ap.add_argument("--layouts", action="store_true",
                    help="reorder x layout pipeline matrix "
                         "-> BENCH_graphs.json")
    ap.add_argument("--layouts-out", default="BENCH_graphs.json")
    ap.add_argument("--algos", action="store_true",
                    help="algorithm x execution-mode matrix "
                         "-> BENCH_algos.json")
    ap.add_argument("--algos-shards", type=int, default=2,
                    help="shard count for the --algos dist-hybrid cells")
    ap.add_argument("--algos-out", default="BENCH_algos.json")
    ap.add_argument("--serve", action="store_true",
                    help="warm-session batched serving throughput "
                         "-> BENCH_serve.json")
    ap.add_argument("--serve-out", default="BENCH_serve.json")
    ap.add_argument("--kernels", action="store_true",
                    help="fused+compact kernel leg "
                         "-> BENCH_kernels.json")
    ap.add_argument("--kernels-out", default="BENCH_kernels.json")
    ap.add_argument("--stream", action="store_true",
                    help="continuous-batching stream-vs-static leg "
                         "-> BENCH_stream.json")
    ap.add_argument("--stream-count", type=int, default=20,
                    help="heavy-tail request count for --stream")
    ap.add_argument("--stream-out", default="BENCH_stream.json")
    ap.add_argument("--obs", action="store_true",
                    help="telemetry overhead + jaxpr-identity gate "
                         "-> BENCH_obs.json")
    ap.add_argument("--obs-out", default="BENCH_obs.json")
    ap.add_argument("--smoke", action="store_true",
                    help="CI fast path: tiny scale, 1 run, no JSON for the "
                         "host bench, dist bench on 1,2,8 shards (or the "
                         "algos matrix when combined with --algos)")
    args = ap.parse_args()
    shards = tuple(int(s) for s in args.shards.split(","))
    use_compile_cache()

    if args.obs:
        o_scale, o_runs = (0.01, 3) if args.smoke else (args.scale,
                                                        args.runs)
        print(csv_row("graph", "host ratio", "outlined ratio"))
        bench_obs(scale=o_scale, runs=o_runs, out_path=args.obs_out)
        return
    if args.stream:
        st_count, st_nodes = ((8, 3_000) if args.smoke
                              else (args.stream_count, 4_000))
        print(csv_row("leg", "N", "static", "stream", "ratio", "p50",
                      "p99"))
        bench_stream(count=st_count, max_nodes=st_nodes,
                     out_path=args.stream_out)
        return
    if args.kernels:
        k_scale, k_rows, k_runs = ((0.01, 2048, 3) if args.smoke
                                   else (args.scale, 2048, args.runs))
        print(csv_row("kind", "launches", "tile", "vs separate",
                      "engine"))
        bench_kernels(scale=k_scale, rows=k_rows, runs=k_runs,
                      out_path=args.kernels_out)
        return
    if args.serve:
        s_scale = 0.005 if args.smoke else args.scale
        print(csv_row("class", "B", "cold", "warm-call", "warm-batch",
                      "speedup"))
        bench_serve(scale=s_scale, out_path=args.serve_out)
        return
    if args.layouts:
        l_scale, l_runs = (0.01, 1) if args.smoke else (args.scale,
                                                        args.runs)
        print(csv_row("graph", "reorder/layout", "ms/colors/width"))
        bench_layouts(scale=l_scale, runs=l_runs, out_path=args.layouts_out)
        return
    if args.algos:
        a_scale, a_runs = (0.01, 1) if args.smoke else (args.scale,
                                                        args.runs)
        if _needs_reexec(args.algos_shards):
            sys.exit(_reexec_with_devices(
                ["--algos", "--scale", str(a_scale), "--runs", str(a_runs),
                 "--algos-shards", str(args.algos_shards),
                 "--algos-out", args.algos_out], args.algos_shards))
        _require_devices(args.algos_shards)
        print(csv_row("graph", "algo", "host", "outlined", "dist-hybrid"))
        bench_algos(shards=args.algos_shards, scale=a_scale, runs=a_runs,
                    out_path=args.algos_out)
        return
    if args.smoke:
        reexec = _needs_reexec(max(shards))
        bench(scale=0.01, runs=1, out_path=None)
        if reexec:
            # only under JAX_PLATFORMS=cpu, where no chip is held
            sys.exit(_reexec_with_devices(
                ["--dist", "--shards", args.shards, "--scale", "0.01",
                 "--runs", "1", "--dist-out", args.dist_out],
                max(shards)))
        _require_devices(max(shards))
        bench_dist(shards, scale=0.01, runs=1, out_path=args.dist_out)
        return
    if args.dist:
        if _needs_reexec(max(shards)):
            sys.exit(_reexec_with_devices(
                ["--dist", "--shards", args.shards, "--scale",
                 str(args.scale), "--runs", str(args.runs),
                 "--dist-out", args.dist_out], max(shards)))
        _require_devices(max(shards))
        print(csv_row("graph", "host_loop",
                      *(f"shards_{s}" for s in shards)))
        bench_dist(shards, scale=args.scale, runs=args.runs,
                   out_path=args.dist_out)
        return
    print(csv_row("graph", *MODES, "speedup"))
    bench(args.scale, args.runs, out_path=args.out)


if __name__ == "__main__":
    main()
