"""End-to-end driver: every registered coloring algorithm (repro.algos)
over the synthetic suite, via the pluggable-algorithm registry.

Each run is VERIFIED — an invalid or incomplete coloring raises
``InvalidColoringError`` and exits non-zero instead of printing a wrong
number. Timings are those of the machine it runs on; the chip
benchmark is ``bench/run.py``.

  PYTHONPATH=src python examples/color_suite.py [--scale 0.1]
  PYTHONPATH=src python examples/color_suite.py --algo jpl --regime outlined
"""
import argparse
import json

from repro.algos import algorithm_names, get_algorithm
from repro.core import verify_coloring
from repro.exec import ExecutionSpec, Session
from repro.graphs import LAYOUT_KINDS, REORDERINGS, SUITE_SPECS, get_dataset

ap = argparse.ArgumentParser()
ap.add_argument("--scale", type=float, default=0.1)
ap.add_argument("--algo", action="append", choices=algorithm_names(),
                help="algorithm(s) to run (default: all registered)")
ap.add_argument("--regime", default="host",
                choices=["host", "outlined", "dist"],
                help="dispatch regime: host loop, device-resident outlined "
                     "chunks, or the sharded Pipe")
ap.add_argument("--mode", default="hybrid",
                help="policy mode (hybrid / topology / data / hybrid-auto)")
ap.add_argument("--shards", type=int, default=None,
                help="dist regime: shard count (default: all devices)")
ap.add_argument("--exchange", default="dense",
                choices=["dense", "boundary", "auto"],
                help="dist regime: cross-shard color publication path "
                     "(DESIGN.md §13)")
ap.add_argument("--layout", default="auto",
                choices=list(LAYOUT_KINDS) + ["auto"],
                help="graph pipeline layout plan (DESIGN.md §8)")
ap.add_argument("--reorder", default="identity",
                choices=sorted(REORDERINGS),
                help="graph pipeline node reordering")
ap.add_argument("--json", action="store_true",
                help="run traced (DESIGN.md §12) and emit one RunReport "
                     "JSON object per (graph, algo) row on stdout instead "
                     "of the CSV table")
args = ap.parse_args()

algos = args.algo or algorithm_names()

# ONE session for the whole sweep (DESIGN.md §9): repeated (algo, graph)
# cells reuse prepared artifacts instead of re-jitting per call — the
# warm-cache behaviour a serving deployment sees
session = Session()

if not args.json:
    print(f"== registry sweep: {', '.join(algos)} "
          f"(regime={args.regime}, mode={args.mode}, "
          f"layout={args.layout}, reorder={args.reorder}) ==")
    print("graph,layout,algo,ms,iterations,colors")
for name in SUITE_SPECS:
    g = get_dataset(name, scale=args.scale, layout=args.layout,
                    reorder=args.reorder)
    g_orig = (g if g.perm is None or g.perm.is_identity
              else get_dataset(name, scale=args.scale, layout=args.layout))
    for algo in algos:
        alg = get_algorithm(algo)
        # --json runs traced: the same run returns a full RunReport
        # (launches/iter, timing split, cache hit-rate) at the cost of
        # span bookkeeping; the CSV path stays untraced
        spec = ExecutionSpec(regime=args.regime, mode=args.mode, algo=alg,
                             n_shards=args.shards, exchange=args.exchange)
        r = session.run(spec, g, trace=True if args.json else None)
        # fail loudly: a conflict or uncolored node raises, the script
        # exits non-zero, and no misleading row is printed; reordered
        # graphs verify on the ORIGINAL ids via the inverse permutation
        colors = (r.colors if g.perm is None
                  else g.perm.colors_to_original(r.colors))
        verify_coloring(g_orig, colors, context=f"{name}/{algo}")
        alg.check_invariants(r, g)
        if args.json:
            doc = r.to_json()
            doc["graph"] = name          # the dataset name, not repr(g)
            print(json.dumps(doc))
        else:
            print(f"{name},{g.layout.kind},{algo},"
                  f"{r.total_seconds * 1e3:.2f},"
                  f"{r.iterations},{r.n_colors}")
            res = getattr(r, "result", None) or r
            if getattr(res, "exchange_trace", ""):
                # dist regime: which publication path each iteration took
                # ('d' dense, 'b' packed boundary, 'm' mixed) + the
                # modeled per-device traffic it moved (DESIGN.md §13)
                kb = sum(res.exchange_bytes) / 1e3
                print(f"#   exchange[{args.exchange}]: "
                      f"{res.exchange_trace} ({kb:.1f}KB/device)")

if not args.json:
    print(f"# session cache after sweep: {session.stats.as_dict()}")
