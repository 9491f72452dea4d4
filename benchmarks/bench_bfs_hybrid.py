"""Hybrid BFS (the paper's future work applied): top-down vs bottom-up vs
hybrid with the persistent worklist, on the suite's social/power-law
graphs (where direction-optimizing BFS shines)."""
from __future__ import annotations

import argparse

from benchmarks.common import csv_row
from repro.core.bfs import bfs, bfs_reference
from repro.graphs import make_suite

import numpy as np
from repro.caches import use_compile_cache


def bench(scale: float = 0.15, runs: int = 3, quiet: bool = False):
    # europe_osm is excluded from the default: its ~10^4-level diameter
    # makes per-level host syncs dominate (21 s at scale 0.15) — the
    # outlined-loop engine territory, see EXPERIMENTS.md.
    suite = make_suite(scale=scale, names=[
        "hollywood-2009_s", "kron_g500-logn21_s", "soc-LiveJournal1_s",
        "rgg_n_2_24_s0_s"])
    rows = []
    for name, g in suite.items():
        res = {}
        for mode in ("topdown", "bottomup", "hybrid"):
            bfs(g, 0, mode=mode)    # warmup/compile
            res[mode] = min(bfs(g, 0, mode=mode).total_seconds
                            for _ in range(runs)) * 1e3
        r = bfs(g, 0, mode="hybrid")
        np.testing.assert_array_equal(r.dist, bfs_reference(g, 0))
        sp = min(res["topdown"], res["bottomup"]) / res["hybrid"]
        rows.append((name, res["topdown"], res["bottomup"], res["hybrid"],
                     sp, r.mode_trace))
        if not quiet:
            print(csv_row(name, f"{res['topdown']:.1f}",
                          f"{res['bottomup']:.1f}", f"{res['hybrid']:.1f}",
                          f"{sp:.2f}x", r.mode_trace[:18]))
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=0.15)
    args = ap.parse_args()
    use_compile_cache()
    print("graph,topdown_ms,bottomup_ms,hybrid_ms,hybrid_vs_best_pure,trace")
    bench(args.scale)


if __name__ == "__main__":
    main()
