"""§Perf Part A: the paper-engine hillclimb ladder, measured wall-clock.

Variants (cumulative):
  A0  paper-faithful baseline: W=128, bucket ratio 4, hub side-channel
      always on (REPRO_IPGC_FORCE_HUB=1 replicates the pre-optimisation
      engine exactly)
  A1  + compile out the hub side-channel for hub-free graphs
  A2  + adaptive mex window (W ~ 2 x median degree)
  A3  + tighter capacity buckets (ratio 2)
Also reports the H-policy sweep on three representative graphs.

  PYTHONPATH=src python -m benchmarks.bench_perf_engine --scale 0.15
"""
from __future__ import annotations

import argparse

from benchmarks.common import csv_row, geomean
from repro.core import color, ipgc, verify_coloring
from repro.graphs import make_suite
from repro.caches import use_compile_cache


def _time(g, runs=3, **kw):
    color(g, **kw)  # warmup/compile
    return min(color(g, **kw).total_seconds for _ in range(runs)) * 1e3


def bench(scale: float = 0.15, runs: int = 3, quiet=False):
    suite = make_suite(scale=scale)
    variants = [
        ("A0_faithful", dict(window=128, bucket_ratio=4), True),
        ("A1_hubskip", dict(window=128, bucket_ratio=4), False),
        ("A2_autowin", dict(window="auto", bucket_ratio=4), False),
        ("A3_buckets2", dict(window="auto", bucket_ratio=2), False),
    ]
    results: dict[str, dict[str, float]] = {v[0]: {} for v in variants}
    plains: dict[str, float] = {}
    for name, g in suite.items():
        for label, kw, force in variants:
            with ipgc.forced_hub(force):
                results[label][name] = _time(g, runs=runs, mode="hybrid",
                                             **kw)
                r = color(g, mode="hybrid", **kw)
                verify_coloring(g, r.colors, context=f"{name}/{label}")
        # the paper's Plain baseline under the SAME final optimisations
        with ipgc.forced_hub(False):
            plains[name] = _time(g, runs=runs, mode="data", window="auto",
                                 bucket_ratio=2)

    if not quiet:
        print(csv_row("graph", *(v[0] for v in variants), "plain_opt",
                      "hybrid/plain"))
        for name in suite:
            sp = plains[name] / results["A3_buckets2"][name]
            print(csv_row(name, *(f"{results[v[0]][name]:.1f}"
                                  for v in variants),
                          f"{plains[name]:.1f}", f"{sp:.2f}x"))
        base = results["A0_faithful"]
        for label, _, _ in variants[1:]:
            gm = geomean([base[n] / results[label][n] for n in suite])
            print(csv_row(f"GEOMEAN {label} vs A0", f"{gm:.2f}x"))
        gm_sp = geomean([plains[n] / results["A3_buckets2"][n]
                         for n in suite])
        print(csv_row("GEOMEAN hybrid/plain (both optimised)",
                      f"{gm_sp:.2f}x"))
    return results, plains


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=0.15)
    ap.add_argument("--runs", type=int, default=3)
    args = ap.parse_args()
    use_compile_cache()
    bench(args.scale, args.runs)


if __name__ == "__main__":
    main()
