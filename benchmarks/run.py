#!/usr/bin/env python3
"""The benchmark's one command:

    python3 benchmarks/run.py --workload <name> --seed <n>
                              --seconds <s> --trace <0|1>

runs one cell of ``BENCHMARK.json`` once, in this process (the only one
that touches JAX), on the chips the cell asks for. Earlier lines of
standard output say what is worth reading and is not a metric; the last
line is the result, one JSON object. Where JAX finds no TPU, or fewer
chips than the cell needs, it exits non-zero and prints no result.

``--rehearse`` is for the benchmark's own tests (``benchmarks/tests``):
the ``tiny`` sizes of the cell's files, on whatever device is there.
Nothing it prints is a device number.
"""

import argparse
import json
import os
import sys
import time

T_START = time.perf_counter()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def main(argv=None):
    from benchmarks.harness.spec import Cell

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes, any device: the benchmark's own "
                         "CPU tests only")
    args = ap.parse_args(argv)
    cell = Cell(args.workload)
    if args.seconds is None:
        args.seconds = float(cell.bench["run_seconds"])
    result = cell.driver().run(cell, args, T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
