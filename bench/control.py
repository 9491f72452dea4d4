"""The control of ``correct``: the cell with one guarantee broken on
purpose, which the comparison has to find.

    python3 bench/control.py --workload <cell> --seeds 5,6,7 --seconds <s>

Colors are int32 and no step of a coloring is floating point, so there
is no lower precision to run the program in. The control breaks the
guarantee a later change would be tempted to drop, completeness: each
coloring stops early through the program's own ``ExecutionSpec.max_iter``
(solo: half the iterations a sound coloring of the graph takes; open
loop: 8 iterations a request). Every seed runs the cell at its own size
and load, in one process, and prints its compared numbers; the command
exits 0 only when every control run comes out not correct. Benchmark
runs never run this.
"""
import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, three or more")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

    from bench import harness, load
    from bench.cells import run_cell

    bench = harness.load_benchmark()
    cell = harness.find_cell(bench, args.workload)
    harness.device_check(cell["chips"])
    from repro.caches import use_compile_cache
    use_compile_cache()
    caught = True
    for seed in (int(s) for s in args.seeds.split(",")):
        run = harness.Run(cell=cell,
                          config=harness.load_config(cell["config"]),
                          traffic=load.load_traffic(cell["traffic"]),
                          seed=seed, seconds=args.seconds, trace=False)
        run_cell(run, control=True)
        caught &= not run.correct
        print(json.dumps({"control": cell["name"], "seed": seed,
                          "correct": run.correct,
                          "checks": {k: v for k, (v, _) in
                                     run.checks.items()}}), flush=True)
    return 0 if caught else 1


if __name__ == "__main__":
    sys.exit(main())
