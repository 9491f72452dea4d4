"""Paper Table IV — colors used: Hybrid (IPGC) vs cuSPARSE-style JPL.

Plain/Topology/VB use the same assignment algorithm as Hybrid, so (as in
the paper) only Hybrid's count is shown next to the independent-set
baseline. Averaged over seeds.
"""
from __future__ import annotations

import argparse

import numpy as np

from benchmarks.common import csv_row
from repro.core import color, jpl_color
from repro.graphs import make_graph, SUITE_SPECS
from repro.caches import use_compile_cache


def bench(scale: float = 0.1, seeds=(0, 1, 2), quiet=False):
    rows = []
    for name in SUITE_SPECS:
        h, j = [], []
        for s in seeds:
            g = make_graph(name, scale=scale, seed=s)
            h.append(color(g, mode="hybrid").n_colors)
            j.append(jpl_color(g).n_colors)
        rows.append((name, float(np.mean(h)), float(np.mean(j))))
        if not quiet:
            print(csv_row(name, f"{np.mean(h):.1f}", f"{np.mean(j):.1f}",
                          f"{np.mean(j) / np.mean(h):.2f}x"))
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=0.1)
    args = ap.parse_args()
    use_compile_cache()
    print("graph,hybrid_colors,jpl_cusparse_colors,ratio")
    bench(args.scale)


if __name__ == "__main__":
    main()
