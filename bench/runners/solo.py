"""The ``solo`` runner: ``graphs`` graphs of the configuration's size
(the mix's parameter, 1 when it gives none), built from the seed and
colored in turn, round after round, by one ``Session``.

Preparation and transfer of each graph fall in its warm-up coloring, and
so in ``setup_s``; ``color_s`` times the coloring loop, as the paper
times a coloring. A seed draws other graphs, whose colorings take more
or fewer iterations; coloring several graphs in a run averages that
over them. Every coloring of the window is held to the reference
(``bench/reference.py``).
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

from bench import gen, load, reference
from bench.harness import (CompileCounter, Run, annotate, execution_spec,
                           info, trace_summary, traced)


def drive(run: Run, counter: CompileCounter, *, control: bool = False):
    """The mix's graphs, colored in turn by one ``Session``. The window
    closes at the end of the first round that ends after
    ``run.seconds``, so every graph is colored equally often."""
    from repro.exec import Session
    from repro.graphs.csr import build_graph

    cfg = run.config
    count = int(run.traffic.get("graphs", 1))
    run.edges = [gen.edges(cfg, load.sub_seed(run.seed, 1, i))
                 for i in range(count)]
    graphs = [build_graph(src, dst, n, name=f"{cfg['name']}.{i}",
                          **cfg["solo_graph"])
              for i, (src, dst, n) in enumerate(run.edges)]
    sess = Session()
    spec = execution_spec(run)
    warm = [sess.run(spec, g) for g in graphs]   # prepare, transfer, compile
    info(warmup_iterations=[w.iterations for w in warm],
         warmup_mode_traces=[trace_summary(w.mode_trace) for w in warm],
         layout=getattr(graphs[0].layout, "kind", None),
         nodes=run.edges[0][2], graphs=count)
    if control:
        least = min(w.iterations for w in warm)
        spec = dataclasses.replace(spec, max_iter=max(least // 2, 1))
    c0 = counter.snapshot()
    run.setup_s = time.perf_counter() - run.t_start
    with traced(run):
        t0 = time.perf_counter()
        while True:
            for i, g in enumerate(graphs):
                with annotate("bench.color"):
                    r = sess.run(spec, g)
                run.results.append(r)
                run.graph_of.append(i)
            elapsed = time.perf_counter() - t0
            if elapsed >= run.seconds:
                break
    run.window_s = elapsed
    info(compiles_in_window=CompileCounter.delta(c0, counter.snapshot()))
    info(colorings=len(run.results),
         iterations=sorted({r.iterations for r in run.results}),
         mode_traces=sorted({trace_summary(r.mode_trace)
                             for r in run.results}))
    run.e2e["color_s"] = run.window_s / len(run.results)


def judge(run: Run) -> None:
    """Every coloring against its graph's edges. ``colors`` is the mean
    over the graphs of the most colors a graph's colorings used."""
    worst = {"uncolored_nodes": 0, "conflict_edges": 0,
             "color_count_gap": 0}
    seen = {}          # graph -> colorings already checked (they repeat)
    most = {}          # graph -> most colors of its colorings
    failed = 0
    for r, i in zip(run.results, run.graph_of):
        src, dst, n = run.edges[i]
        same = next((v for c, v in seen.get(i, ())
                     if np.array_equal(c, r.colors)), None)
        if same is None:
            same = reference.check(src, dst, n, r.colors, r.n_colors)
            same["colors"] = reference.color_count(r.colors)
            seen.setdefault(i, []).append((r.colors, same))
        failed += any(same[k] for k in worst)
        for k in worst:
            worst[k] = max(worst[k], same[k])
        most[i] = max(most.get(i, 0), same["colors"])
    run.attempted, run.failed = len(run.results), failed
    run.e2e["colors"] = sum(most.values()) / len(most)
    run.checks = {k: (v, 0) for k, v in worst.items()}
    info(distinct_colorings=sum(len(v) for v in seen.values()),
         colors_by_graph=[most[i] for i in sorted(most)])
