"""Run one cell of the benchmark and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout on a machine with the chips the cell asks
for.  Set-up (weights from the seed, engine, warm-up of the cell's
programs) is timed from process start; then the window serves the
cell's traffic for ``--seconds`` seconds of arrivals, drains, and the
served tokens are checked against the configuration's plain reference.
``--trace 1`` also profiles a steady stretch of the window and reports
the cell's per-layer metrics instead of its end-to-end ones.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown``
with ``--trace 1``), and last ``checks``, each number compared with its
limit.  Where JAX finds no TPU, or fewer chips than the cell asks for,
the run exits with code 2 and prints no result.

Options that serve measurement, not the benchmark's own runs:
``--sweep r1,r2,..`` serves one window at each Poisson rate before the
measured one (to find the knee), and
``--control 1`` runs the correctness control in the program's place;
``--dump DIR`` keeps the profiled stretch's events there as JSON.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)
sys.path.insert(1, os.path.join(os.path.dirname(BENCH), "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--sweep", default=None,
                    help="comma-separated rates, one window each first")
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dump", default=None)
    args = ap.parse_args(argv)

    from benchlib.cell import load_cell
    from benchlib.harness import NoDevice, run_cell
    cell = load_cell(args.workload)
    try:
        result = run_cell(cell, seed=args.seed, seconds=args.seconds,
                          trace=bool(args.trace), t_start=T_START,
                          control=bool(args.control),
                          sweep=[float(r) for r in args.sweep.split(",")]
                          if args.sweep else None,
                          dump=args.dump)
    except NoDevice as e:
        print(f"bench: {e}; nothing was measured", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
