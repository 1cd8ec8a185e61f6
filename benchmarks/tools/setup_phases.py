#!/usr/bin/env python3
"""One run of a cell, as ``run.py`` makes it, and then where its
set-up went by the program's compile phase records
(``harness/compile_spans.py``): seconds of each phase (the union of its
records, and their plain sum), the cache's outcomes, and the functions
that took longest in each phase. With ``--tracer memory`` a global
``Tracer()`` records from the start, which is what tracing costs when
it is on.

    python3 benchmarks/tools/setup_phases.py --workload resnet50.fit \\
        --seed 7 --trace 1 [--tracer memory] [--top 3] [--out FILE]

The result line of ``run.py`` comes first; the breakdown is one JSON
line after it (and in ``--out``, appended, where given). The window
starts with its ``fit`` span, which is kept where the run is traced or
``--tracer memory`` records it; otherwise the breakdown is ``null``.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def breakdown(setup, top):
    from benchmarks.harness.compile_spans import PHASES

    out = {}
    for name in PHASES:
        recs = setup.named(name)
        out[name] = {
            "records": len(recs),
            "nested": sum(r["attrs"].get("nested", 0) for r in recs),
            "union_s": setup.seconds(name),
            # a trace's own callees are in its nested_s; a lowering's
            # traces are inside its interval already
            "sum_s": sum(r["end"] - r["start"] + (
                r["attrs"].get("nested_s", 0.0)
                if name == "compile.trace" else 0.0) for r in recs),
            "top": [{"fun": f, "records": n, "s": s}
                    for f, n, s in setup.by_fun(name)[:top]],
        }
    out["compile.backend"]["outcomes"] = {
        o: setup.count("compile.backend", o)
        for o in ("hit", "miss", "uncached")}
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0],
                                 allow_abbrev=False)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--tracer", choices=("default", "memory"),
                    default="default")
    ap.add_argument("--top", type=int, default=3)
    ap.add_argument("--out", default=None)
    args, rest = ap.parse_known_args(argv)

    from benchmarks import run
    from benchmarks.harness import compile_spans

    if args.tracer == "memory":
        from deeplearning4j_tpu.observability.trace import (
            Tracer,
            set_global_tracer,
        )

        set_global_tracer(Tracer())
    rc = run.main(["--workload", args.workload] + rest)
    setup = compile_spans.of_setup()
    if setup is None:
        print(json.dumps({"setup_phases": None}), flush=True)
        return rc
    line = {"workload": args.workload, "tracer": args.tracer,
            "setup_phases": breakdown(setup, args.top)}
    print(json.dumps(line), flush=True)
    if args.out:
        with open(args.out, "a") as f:
            f.write(json.dumps(line) + "\n")
    return rc


if __name__ == "__main__":
    sys.exit(main())
