"""Run one benchmark cell once on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic mix are found by name from
``BENCHMARK.json`` (``bench/harness.py`` says where). The run:

1. exits non-zero, printing no result, unless JAX sees as many TPU chips
   as the cell asks for;
2. turns on JAX's compile cache through the program's
   ``repro.caches.use_compile_cache()``: ``$JAX_COMPILATION_CACHE_DIR``
   where it is set, else ``<checkout>/.cache/jax``;
3. builds the cell's graphs from ``--seed``, warms up every program the
   window uses (counted in ``setup_s``), then measures for ``--seconds``;
4. holds every answer of the window to the reference, and prints one
   JSON object as the last line of standard output. With ``--trace 1``
   the window runs under the profiler and the line carries the cell's
   per-layer metrics instead of its end-to-end ones.

Earlier lines of standard output (``{"info": ...}``) report compiles
inside the window, peak device bytes, and iterations and mode trace per
coloring.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed must be >= 0 and --seconds > 0")
    return args


def main(argv=None) -> int:
    args = parse(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

    from bench import harness, load
    from bench.cells import run_cell

    bench = harness.load_benchmark()
    cell = harness.find_cell(bench, args.workload)
    harness.device_check(cell["chips"])

    from repro.caches import use_compile_cache
    harness.info(compile_cache=use_compile_cache())

    run = harness.Run(cell=cell, config=harness.load_config(cell["config"]),
                      traffic=load.load_traffic(cell["traffic"]),
                      seed=args.seed, seconds=args.seconds,
                      trace=bool(args.trace), t_start=T_START)
    readers = ({m["name"]: harness.load_reader(m["name"])
                for m in harness.cell_metrics(bench, cell["name"],
                                              "per_layer")}
               if run.trace else None)
    run_cell(run)
    line = harness.result_line(run, bench, readers)
    harness.print_checks(run)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
