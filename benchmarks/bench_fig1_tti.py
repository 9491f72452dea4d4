"""Paper Fig. 1 — Push_WL vs Push_NoWL micro-benchmark.

Both kernels deactivate the first COUNT still-active nodes per iteration
(node ids are deactivated in ascending order, like the paper) and BOTH
maintain the worklist throughout. Push_NoWL sweeps all N nodes
(topology-driven); Push_WL iterates the (bucketed) worklist
(data-driven). We record time-per-iteration (TTI) and report the
crossover iteration — the paper's motivating observation.

Scaled for CPU: europe_osm (50.9M nodes, COUNT=1000, ~51k iters) becomes
an N=2^20 road-like graph with COUNT=4096 (~256 iters).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.core.worklist import (Worklist, bucket_capacities, compact_items,
                                 compact_mask, full_worklist, pick_bucket)
from repro.caches import use_compile_cache


def bench(n: int = 1 << 20, count: int = 4096, runs: int = 3,
          out_csv: str | None = "experiments/fig1_tti.csv",
          quiet: bool = False):
    @jax.jit
    def push_nowl(threshold, wl: Worklist):
        # topology-driven: sweep all nodes, still maintain the worklist
        ids = jnp.arange(n, dtype=jnp.int32)
        mask = wl.mask & (ids >= threshold)
        items, cnt = compact_mask(mask, n, n)
        return Worklist(mask=mask, items=items, count=cnt)

    @jax.jit
    def push_wl(threshold, wl: Worklist):
        # data-driven: iterate only the worklist (capacity-bucketed)
        keep = (wl.items < n) & (wl.items >= threshold)
        items, cnt = compact_items(wl.items, keep, n)
        mask = jnp.zeros((n,), bool).at[jnp.where(keep, wl.items, n)].set(
            keep, mode="drop")
        return Worklist(mask=mask, items=items, count=cnt)

    caps = bucket_capacities(n)
    iters = n // count

    def run(kind: str) -> list[float]:
        wl = full_worklist(n)
        ttis = []
        cnt = n
        it = 0
        while cnt > 0:
            thr = jnp.int32((it + 1) * count)
            t0 = time.perf_counter()
            if kind == "nowl":
                wl = push_nowl(thr, wl)
            else:
                cap = pick_bucket(caps, cnt)
                if wl.capacity > cap:
                    wl = Worklist(wl.mask, wl.items[:cap], wl.count)
                wl = push_wl(thr, wl)
            cnt = int(wl.count)
            ttis.append(time.perf_counter() - t0)
            it += 1
        return ttis

    # warmup (compile all buckets)
    run("wl"), run("nowl")
    tti_wl = None
    tti_nowl = None
    for _ in range(runs):
        w, nw = run("wl"), run("nowl")
        tti_wl = w if tti_wl is None else [a + b for a, b in zip(tti_wl, w)]
        tti_nowl = nw if tti_nowl is None else [a + b for a, b in
                                                zip(tti_nowl, nw)]
    tti_wl = [t / runs for t in tti_wl]
    tti_nowl = [t / runs for t in tti_nowl]

    # crossover: first iteration after which WL is consistently faster
    crossover = next((i for i in range(len(tti_wl))
                      if all(w < nw for w, nw in zip(tti_wl[i:], tti_nowl[i:]))
                      ), len(tti_wl))
    total_wl = sum(tti_wl)
    total_nowl = sum(tti_nowl)
    ideal = sum(min(a, b) for a, b in zip(tti_wl, tti_nowl))
    if out_csv:
        import os
        os.makedirs(os.path.dirname(out_csv), exist_ok=True)
        with open(out_csv, "w") as f:
            f.write("iter,tti_push_wl_us,tti_push_nowl_us\n")
            for i, (a, b) in enumerate(zip(tti_wl, tti_nowl)):
                f.write(f"{i},{a * 1e6:.1f},{b * 1e6:.1f}\n")
    if not quiet:
        print(f"n={n} count={count} iters={iters}")
        print(f"crossover at iteration {crossover}/{len(tti_wl)} "
              f"(active={max(n - crossover * count, 0)} "
              f"= {max(n - crossover * count, 0) / n:.0%} of N)")
        print(f"total: Exp1(Push_WL)={total_wl:.3f}s "
              f"Exp2(Push_NoWL)={total_nowl:.3f}s ideal-hybrid={ideal:.3f}s")
        print(f"ideal hybrid speedup vs WL: {total_wl / ideal:.2f}x, "
              f"vs NoWL: {total_nowl / ideal:.2f}x")
    return {"crossover": crossover, "total_wl": total_wl,
            "total_nowl": total_nowl, "ideal": ideal}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=1 << 20)
    ap.add_argument("--count", type=int, default=4096)
    ap.add_argument("--runs", type=int, default=3)
    args = ap.parse_args()
    use_compile_cache()
    bench(args.n, args.count, args.runs)


if __name__ == "__main__":
    main()
