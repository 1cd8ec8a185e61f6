#!/usr/bin/env python3
"""Take one short traced window of a ``fit`` cell and write down what
the trace holds, for reading by hand: planes, lines, event counts, the
first events of every line with their stats, and the reduction. Writes
``chiprun_out/trace.<workload>.txt``.

    python3 benchmarks/tools/describe_trace.py --workload resnet50.fit
"""

import argparse
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(argv=None):
    import jax

    from benchmarks.drivers.fit import FitRun
    from benchmarks.harness import trace_reduce
    from benchmarks.harness.spec import REPO, Cell

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    fr = FitRun(Cell(args.workload), args)
    fr.start(args.seed)
    out_dir = os.path.join(REPO, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with tempfile.TemporaryDirectory(prefix="bench_trace_") as tdir:
        with jax.profiler.trace(tdir):
            window_s, *_ = fr.window(args.seconds)
        path = trace_reduce.find_xplane(tdir)
        trace = trace_reduce.reduce_dir(tdir, window_s)
        text = [f"xplane {os.path.getsize(path)} bytes, window "
                f"{window_s:.3f} s",
                json.dumps(trace_reduce.summary(trace), indent=1),
                "by stem, seconds: " + json.dumps(dict(sorted(
                    trace["by_stem_s"].items(),
                    key=lambda kv: -kv[1])), indent=1),
                trace_reduce.describe(path)]
    out = os.path.join(out_dir, f"trace.{args.workload}.txt")
    with open(out, "w") as f:
        f.write("\n".join(text) + "\n")
    print(f"wrote {out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
