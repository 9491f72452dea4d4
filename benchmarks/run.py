"""Aggregate benchmark runner — one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows. Scale flags keep the full
sweep CPU-friendly; individual benches accept --scale for bigger runs.
"""
from __future__ import annotations

import argparse

from repro.caches import use_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=0.1)
    ap.add_argument("--fast", action="store_true",
                    help="smaller graphs / fewer repeats")
    args = ap.parse_args()
    use_compile_cache()
    scale = 0.03 if args.fast else args.scale

    print("name,us_per_call,derived")

    print("# --- paper Table I: suite statistics ---")
    from benchmarks import bench_table1_stats
    for s in bench_table1_stats.bench(scale=scale, quiet=True):
        print(f"table1/{s['name']},,nodes={s['nodes']} edges={s['edges']} "
              f"dmed={s['d_median']} dmax={s['d_max']}")

    print("# --- paper Fig 1: TTI micro-benchmark ---")
    from benchmarks import bench_fig1_tti
    n = 1 << 17 if args.fast else 1 << 20
    r = bench_fig1_tti.bench(n=n, count=max(n // 256, 1), runs=2, quiet=True)
    print(f"fig1/push_wl_total,{r['total_wl'] * 1e6:.0f},")
    print(f"fig1/push_nowl_total,{r['total_nowl'] * 1e6:.0f},")
    print(f"fig1/ideal_hybrid,{r['ideal'] * 1e6:.0f},"
          f"crossover_iter={r['crossover']}")

    print("# --- paper Table III: engine times + speedup ---")
    from benchmarks import bench_table3_speedup
    t3 = bench_table3_speedup.bench(scale=scale, runs=2, quiet=True)
    for name, plain, topo, hyb, vb, jpl, sp in t3["rows"]:
        print(f"table3/{name},{hyb * 1e3:.0f},plain={plain:.1f}ms "
              f"hybrid={hyb:.1f}ms speedup={sp:.2f}x")
    print(f"table3/geomean_speedup,,hybrid/plain={t3['geomean_vs_plain']:.2f}x"
          f" hybrid/vb={t3['geomean_vs_vb']:.2f}x (paper: 2.13x, 1.36x)")

    print("# --- paper Table IV: chromatic quality ---")
    from benchmarks import bench_table4_colors
    for name, h, j in bench_table4_colors.bench(scale=scale, seeds=(0,),
                                                quiet=True):
        print(f"table4/{name},,hybrid={h:.0f} jpl={j:.0f}")

    print("# --- engine dispatch modes (host-loop vs outlined) ---")
    from benchmarks import bench_engine_modes
    em = bench_engine_modes.bench(scale=scale, runs=2, quiet=True,
                                  out_path="BENCH_engine.json")
    for name, row in em["graphs"].items():
        host = row["hybrid_host"]["seconds"]
        outl = row["hybrid_outlined"]["seconds"]
        print(f"engine/{name},{outl * 1e6:.0f},host={host * 1e3:.1f}ms "
              f"outlined={outl * 1e3:.1f}ms "
              f"dispatches={row['hybrid_outlined']['host_dispatches']}"
              f"/{row['hybrid_host']['host_dispatches']} "
              f"speedup={host / max(outl, 1e-12):.2f}x")
    print(f"engine/geomean_outlined_vs_host,,"
          f"{em['geomean_outlined_vs_host']:.2f}x (BENCH_engine.json)")

    print("# --- paper future-work: hybrid BFS on the same substrate ---")
    from benchmarks import bench_bfs_hybrid
    for name, td, bu, hy, sp, trace in bench_bfs_hybrid.bench(
            scale=scale, runs=2, quiet=True):
        print(f"bfs/{name},{hy * 1e3:.0f},topdown={td:.1f}ms "
              f"bottomup={bu:.1f}ms hybrid={hy:.1f}ms "
              f"vs_best_pure={sp:.2f}x")

    print("# --- kernel micro-benchmarks ---")
    from benchmarks import bench_kernels
    for name, us, derived in bench_kernels.bench(quiet=True):
        print(f"kernels/{name},{us:.0f},{derived}")

    print("# --- roofline (from dry-run artifacts, if present) ---")
    try:
        from repro.launch import roofline
        for line in roofline.summary_lines():
            print(line)
    except Exception as exc:
        print(f"roofline/skipped,,{type(exc).__name__}: run "
              "`python -m repro.launch.dryrun --all` first")


if __name__ == "__main__":
    main()
