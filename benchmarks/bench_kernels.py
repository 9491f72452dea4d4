"""Kernel micro-benchmarks: jnp reference path timings on CPU.

Pallas kernels target TPU; on this CPU container interpret-mode timing
measures the Python interpreter, not the kernel, so the jnp oracle is the
meaningful CPU number (it is also what the CPU engines run).
"""
from __future__ import annotations

import argparse

import jax.numpy as jnp
import numpy as np

from benchmarks.common import csv_row, time_fn
import jax

from repro.kernels import ref
from repro.caches import use_compile_cache


def bench(r: int = 65536, k: int = 32, w: int = 128, quiet=False):
    rng = np.random.default_rng(0)
    nc = jnp.asarray(rng.integers(-2, 300, size=(r, k)).astype(np.int32))
    base = jnp.zeros((r,), jnp.int32)
    extra = jnp.asarray(rng.random((r, w)) < 0.2)
    mask = jnp.asarray(rng.random(r * 8) < 0.3)

    mex = jax.jit(lambda a, b, c: ref.mex_window_ref(a, b, c, w))
    t1 = time_fn(mex, nc, base, extra)
    compact = jax.jit(ref.compact_ref)
    t2 = time_fn(compact, mask)
    cu = jnp.asarray(rng.integers(0, 32, size=(r,)).astype(np.int32))
    pu = jnp.asarray(rng.integers(0, 999, size=(r,)).astype(np.int32))
    ids = jnp.arange(r, dtype=jnp.int32)
    npr = jnp.asarray(rng.integers(-1, 999, size=(r, k)).astype(np.int32))
    nid = jnp.asarray(rng.integers(0, r, size=(r, k)).astype(np.int32))
    conf = jax.jit(ref.conflict_ref)
    t3 = time_fn(conf, nc, npr, nid, cu, pu, ids)
    rows = [
        ("mex_window_ref", t1 * 1e6, f"{r * k / t1 / 1e9:.2f} Gedge/s"),
        ("compact_ref", t2 * 1e6, f"{mask.shape[0] / t2 / 1e9:.2f} Gelem/s"),
        ("conflict_ref", t3 * 1e6, f"{r * k / t3 / 1e9:.2f} Gedge/s"),
    ]
    if not quiet:
        for row in rows:
            print(csv_row(row[0], f"{row[1]:.0f}", row[2]))
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=65536)
    args = ap.parse_args()
    use_compile_cache()
    print("kernel,us_per_call,derived")
    bench(args.rows)


if __name__ == "__main__":
    main()
