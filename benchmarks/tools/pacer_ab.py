#!/usr/bin/env python3
"""What the feed's pacer costs: windows of a fixed number of chunks in
one process, with the pacer and without it, turn about. Without it
``fit()``'s scan path dispatches every chunk of the window ahead of the
device, each with its stacked inputs already in device memory, so keep
``--chunks`` small enough for the chip to hold them. One JSON line per
window; the memory peak never falls, so the paced windows come first.

    python3 benchmarks/tools/pacer_ab.py --workload resnet50.fit \
        --seed 7 --chunks 4
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(argv=None):
    from benchmarks.drivers.fit import FitRun
    from benchmarks.harness.spec import Cell

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--chunks", type=int, default=4)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    fr = FitRun(Cell(args.workload), args)
    fr.start(args.seed)
    n = args.chunks * fr.chunk
    for pace in (True, True, False, False, True, False):
        seconds, taken, wait_s, paced_s = fr.window(n_batches=n, pace=pace)
        stats = fr.dev.memory_stats() or {}
        print(json.dumps({
            "paced": pace, "chunks": taken // fr.chunk,
            "seconds": seconds,
            "examples_per_s": taken * fr.batch / seconds,
            "paced_s": paced_s, "feed_wait_s": wait_s,
            "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
