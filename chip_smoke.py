#!/usr/bin/env python3
"""Smoke run of the coloring service on TPU chips, at published graph size.

    python chip_smoke.py                # one chip: every phase but "dist"
    python chip_smoke.py --pallas-full  # one chip: only "pallas", at 2^21
    python chip_smoke.py --chips 4      # four chips: only the "dist" phase

The graph is kron_g500-logn21 at its published size (2^21 nodes, Graph500
edge factor 16), generated from a seed and built once. Phases, in one
process (a second process could not open a chip this one holds):

  device    JAX must see TPU devices, else exit non-zero before any work
  host      Session.run, host regime, layout "auto", impl "jnp"
  outlined  outlined regime with fused=False: bit-identical to "host"
            (colors, iterations, mode trace); then with its TPU default
            (fused=None), verified
  pallas    layout "ell-tail", impl "pallas" against impl "jnp" on the
            same layout, bit-identical, for the fused and the two-phase
            step families; the lowered steps must hold the native kernel
            (``tpu_custom_call``), so nothing runs interpreted. In the
            default run on the same generator at 2^12 nodes, since four
            more colorings at 2^21 do not fit its 20 minutes beside the
            other phases; ``--pallas-full`` runs it alone at 2^21
  service   8 heavy-tail requests through Session.stream(...).serving(),
            each bit-identical to a solo Session.run
  dist      (--chips 4) the dist regime over 4 devices, exchange "dense"
            and "auto", against the host engine on prepare_partition(g, 4)
            mapped back through the relabeling: colors, iterations, mode
            trace; one sharded step must leave a shard on every device

Every coloring is checked with ``verify_coloring``. A failed phase raises,
and the script exits non-zero. Each phase prints one JSON line (seconds,
iterations, colors, peak device bytes, tile_rows); the last line of
standard output is ``{"ok": true, "device": {...}}`` with the device as JAX
reports it.

``--rehearse`` runs the same phases on a small graph wherever JAX runs,
kernels interpreted off the chip (with ``--chips 4`` under
``JAX_PLATFORMS=cpu`` it forces four host devices). It checks paths and
control flow only, and its last line is never the ``ok`` result.

Compiled programs persist in ``$JAX_COMPILATION_CACHE_DIR`` when that is
set, else in ``<checkout>/.cache/jax`` (repro/caches.py).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

GRAPH = "kron_g500-logn21_s"
FULL_SCALE = 32          # suite entry is 2^16 nodes; x32 -> 2^21
SMALL_SCALE = 1 / 16     # 2^12 nodes: the Pallas phase, and rehearsals
N_REQUESTS = 8


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def peak_bytes(devices) -> "list[int | None]":
    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in devices]


def same_result(got, ref, what: str) -> None:
    import numpy as np
    np.testing.assert_array_equal(got.colors, ref.colors, err_msg=what)
    if got.iterations != ref.iterations or got.mode_trace != ref.mode_trace:
        raise AssertionError(
            f"{what}: iterations/mode trace {got.iterations} "
            f"{got.mode_trace!r} != {ref.iterations} {ref.mode_trace!r}")


def summary(r) -> dict:
    return {"iterations": r.iterations, "colors": r.n_colors,
            "mode_trace": r.mode_trace}


def phase_host_outlined(g, devices) -> None:
    from repro.core import verify_coloring
    from repro.exec import ExecutionSpec, Session

    sess = Session()
    t = time.perf_counter()
    host = sess.run(ExecutionSpec(regime="host"), g)
    verify_coloring(g, host.colors, context="host")
    emit("host", layout=g.layout.kind, ell_width=g.ell_width,
         seconds=time.perf_counter() - t, **summary(host),
         peak_bytes=peak_bytes(devices))

    t = time.perf_counter()
    out = sess.run(ExecutionSpec(regime="outlined", fused=False), g)
    verify_coloring(g, out.colors, context="outlined fused=False")
    same_result(out, host, "outlined fused=False vs host")
    emit("outlined", fused=False, seconds=time.perf_counter() - t,
         **summary(out), peak_bytes=peak_bytes(devices))

    t = time.perf_counter()
    out = sess.run(ExecutionSpec(regime="outlined"), g)
    verify_coloring(g, out.colors, context="outlined fused=None")
    emit("outlined", fused=None, seconds=time.perf_counter() - t,
         **summary(out), host_dispatches=out.host_dispatches,
         peak_bytes=peak_bytes(devices))


def phase_pallas(g, devices, on_chip: bool) -> None:
    from repro.algos import get_algorithm
    from repro.core import verify_coloring
    from repro.core.engine import adaptive_window, resolve_plan
    from repro.exec import ExecutionSpec, Session
    from repro.kernels import ops, tune

    alg = get_algorithm("ipgc")
    plan = resolve_plan(g, "ell-tail")
    # prepared once here and handed to the session, so the same device
    # arrays feed the runs and the lowering checked below
    ig = alg.prepare(g, plan=plan)
    window = adaptive_window(g)
    tile_rows = tune.resolve_tile_rows("auto", plan.kind, "pallas")
    if on_chip and ops.interpret():
        raise AssertionError("Pallas kernels would run interpreted on "
                             f"backend {devices[0].platform!r}")
    sess = Session()
    for fused in (True, False):
        spec = dict(regime="host", window=window, fused=fused)
        t = time.perf_counter()
        ref = sess.run(ExecutionSpec(impl="jnp", **spec), ig)
        t_ref = time.perf_counter() - t
        emit("pallas-reference", fused=fused, impl="jnp", seconds=t_ref,
             **summary(ref))
        t = time.perf_counter()
        got = sess.run(ExecutionSpec(impl="pallas", **spec), ig)
        t_got = time.perf_counter() - t
        verify_coloring(g, got.colors, context=f"pallas fused={fused}")
        same_result(got, ref, f"pallas vs jnp, fused={fused}")
        colors, aux, wl = alg.init_state(ig)
        native = []
        for step in alg.step_fns(fused):
            hlo = step.lower(ig, colors, aux, wl, window=window,
                             impl="pallas", force_hub=False,
                             tile_rows=tile_rows).as_text()
            native.append("tpu_custom_call" in hlo)
        if on_chip and not all(native):
            raise AssertionError(f"fused={fused}: a lowered Pallas step "
                                 "holds no tpu_custom_call")
        emit("pallas", fused=fused, layout=ig.layout_kind,
             ell_width=ig.ell_width, n_hub=ig.n_hub, window=window,
             tile_rows=tile_rows, seconds_jnp=t_ref, seconds_pallas=t_got,
             native_kernel=native, **summary(got),
             peak_bytes=peak_bytes(devices))


def phase_service(devices) -> None:
    from repro.core import verify_coloring
    from repro.exec import ExecutionSpec, Session
    from repro.graphs import get_dataset_batch
    from repro.serve import StreamConfig

    graphs = get_dataset_batch(heavy_tail=N_REQUESTS, seed=7,
                               layout="ell-tail")
    spec = ExecutionSpec(regime="host", window=128)
    sess = Session()
    t = time.perf_counter()
    stream = sess.stream(spec, StreamConfig(lanes=4, max_queue=N_REQUESTS,
                                            max_nodes=max(
                                                g.n_nodes for g in graphs)))
    with stream.serving():
        tickets = [stream.submit(g) for g in graphs]
    seconds = time.perf_counter() - t
    for g, tk in zip(graphs, tickets):
        if tk.status != "done":
            raise AssertionError(f"request {tk.seq} {tk.status}: "
                                 f"{tk.reason}")
        verify_coloring(g, tk.result.colors, context=f"request {tk.seq}")
        same_result(tk.result, sess.run(spec, g), f"request {tk.seq}")
    emit("service", requests=len(graphs),
         nodes=[g.n_nodes for g in graphs], seconds=seconds,
         iterations=[tk.result.iterations for tk in tickets],
         colors=[tk.result.n_colors for tk in tickets],
         peak_bytes=peak_bytes(devices))


def phase_dist(g, devices) -> None:
    import jax
    import numpy as np

    from repro.algos import get_algorithm
    from repro.core import verify_coloring
    from repro.core.engine import adaptive_window, resolve_plan
    from repro.exec import ExecutionSpec, Session
    from repro.graphs.partition import prepare_partition

    n_shards = len(devices)
    alg = get_algorithm("ipgc")
    plan = resolve_plan(g, "ell-tail")
    window = adaptive_window(g)
    sess = Session()
    t = time.perf_counter()
    g2, relabel = prepare_partition(g, n_shards)
    ig2 = alg.prepare(g2, plan=plan)
    ref = sess.run(ExecutionSpec(regime="host", fused=True, window=window),
                   ig2)
    ref_colors = np.asarray(ref.colors)[relabel[:g.n_nodes]]
    emit("dist-reference", shards=n_shards, seconds=time.perf_counter() - t,
         **summary(ref))
    for exchange in ("dense", "auto"):
        t = time.perf_counter()
        r = sess.run(ExecutionSpec(regime="dist", mode="dist-hybrid",
                                   layout="ell-tail", exchange=exchange,
                                   n_shards=n_shards), g)
        verify_coloring(g, r.colors, context=f"dist {exchange}")
        np.testing.assert_array_equal(r.colors, ref_colors,
                                      err_msg=f"dist {exchange}")
        if (r.iterations, r.mode_trace) != (ref.iterations, ref.mode_trace):
            raise AssertionError(f"dist {exchange}: {r.iterations} "
                                 f"{r.mode_trace!r} != reference")
        emit("dist", exchange=exchange, shards=n_shards,
             seconds=time.perf_counter() - t, **summary(r),
             exchange_trace=r.exchange_trace,
             peak_bytes=peak_bytes(devices))

    # one sharded dense step: every device must hold its block of the
    # outputs (nothing quietly computed on device 0 alone)
    mesh = jax.make_mesh((n_shards,), ("data",))
    dense, _ = alg.make_dist_steps(ig2, mesh, ("data",), window=window,
                                   fused=True)
    colors, base, wl = alg.init_state(ig2)
    _, base, wl = dense(colors, base, wl)
    holders = {s.device for s in base.addressable_shards
               if s.data.shape[0] == g2.n_nodes // n_shards}
    if holders != set(devices):
        raise AssertionError(f"sharded step output held by {holders}, "
                             f"expected every one of {devices}")
    emit("dist-placement", devices=sorted(str(d) for d in holders))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--pallas-full", action="store_true",
                    help="only the Pallas phase, on the 2^21-node graph")
    ap.add_argument("--rehearse", action="store_true",
                    help="small graph, any backend; prints no result")
    args = ap.parse_args()
    if args.pallas_full and args.chips != 1:
        ap.error("--pallas-full runs on one chip")
    if (args.rehearse and args.chips > 1
            and os.environ.get("JAX_PLATFORMS") == "cpu"):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.chips}")

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not args.rehearse:
        print(f"chip_smoke: JAX found no TPU (platform {platform!r}); "
              "nothing was run", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX found {len(devices)}", file=sys.stderr)
        return 1
    devices = devices[:args.chips]
    emit("device", platform=platform, kind=devices[0].device_kind,
         count=len(devices))

    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.caches import use_compile_cache
    from repro.graphs import get_dataset

    emit("compile-cache", dir=use_compile_cache())
    def graph(scale):
        t = time.perf_counter()
        g = get_dataset(GRAPH, scale=scale, layout="auto")
        emit("graph", name=GRAPH, scale=scale, nodes=g.n_nodes,
             edges=g.n_edges, layout=g.layout.kind, ell_width=g.ell_width,
             seconds=time.perf_counter() - t)
        return g

    g = graph(SMALL_SCALE if args.rehearse else FULL_SCALE)
    if args.chips == 4:
        phase_dist(g, devices)
    elif args.pallas_full:
        phase_pallas(g, devices, on_chip=not args.rehearse)
    else:
        phase_host_outlined(g, devices)
        phase_pallas(graph(SMALL_SCALE), devices,
                     on_chip=not args.rehearse)
        phase_service(devices)

    if args.rehearse:
        print("chip_smoke: rehearsal passed; it is not a chip result",
              file=sys.stderr)
        return 0
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
