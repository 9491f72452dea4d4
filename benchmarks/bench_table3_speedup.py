"""Paper Table III — time per engine per graph + Fig. 4 speedups.

Engines: Plain (data-driven IPGC, the paper's baseline), Topology,
Hybrid (the contribution), VB (Kokkos-style), JPL (cuSPARSE-style).
Averaged over 3 runs after a compile warmup, on the synthetic Table I
suite at a CPU-friendly scale.
"""
from __future__ import annotations

import argparse

from benchmarks.common import csv_row, geomean
from repro.core import color, jpl_color, vb_color, verify_coloring
from repro.graphs import make_suite
from repro.caches import use_compile_cache


def bench(scale: float = 0.1, runs: int = 3, names=None, quiet=False):
    suite = make_suite(scale=scale, names=names)
    rows = []
    speedups_hybrid = []
    speedups_vb = []
    for name, g in suite.items():
        results = {}
        for label, fn in [
            ("plain", lambda: color(g, mode="data")),
            ("topology", lambda: color(g, mode="topology")),
            ("hybrid", lambda: color(g, mode="hybrid")),
            ("vb_kokkos", lambda: vb_color(g)),
            ("jpl_cusparse", lambda: jpl_color(g)),
        ]:
            fn()  # warmup/compile
            best = min(fn().total_seconds for _ in range(runs))
            results[label] = best * 1e3
            r = fn()
            verify_coloring(g, r.colors, context=f"{name}/{label}")
        sp_h = results["plain"] / results["hybrid"]
        sp_v = results["vb_kokkos"] / results["hybrid"]
        speedups_hybrid.append(sp_h)
        speedups_vb.append(sp_v)
        rows.append((name, results["plain"], results["topology"],
                     results["hybrid"], results["vb_kokkos"],
                     results["jpl_cusparse"], sp_h))
        if not quiet:
            print(csv_row(name, *(f"{results[k]:.1f}" for k in
                                  ("plain", "topology", "hybrid",
                                   "vb_kokkos", "jpl_cusparse")),
                          f"{sp_h:.2f}x"))
    gm = geomean(speedups_hybrid)
    gmv = geomean(speedups_vb)
    if not quiet:
        print(csv_row("GEOMEAN hybrid/plain", f"{gm:.2f}x",
                      "hybrid/vb", f"{gmv:.2f}x"))
        print("# paper: 2.13x over Plain (data-driven), 1.36x over Kokkos")
    return {"rows": rows, "geomean_vs_plain": gm, "geomean_vs_vb": gmv}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=0.1)
    ap.add_argument("--runs", type=int, default=3)
    args = ap.parse_args()
    use_compile_cache()
    print("graph,plain_ms,topology_ms,hybrid_ms,vb_ms,jpl_ms,speedup")
    bench(args.scale, args.runs)


if __name__ == "__main__":
    main()
