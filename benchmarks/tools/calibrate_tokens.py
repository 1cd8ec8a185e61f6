#!/usr/bin/env python3
"""``calibrate.py`` for a cell of the ``fit_tokens`` driver: readings
for the cell's limits, at the cell's own size, in one process. For
every seed: the program's first chunk against the reference (the
program's arrays are dropped before the reference runs: the chip does
not hold both); for the first ``--controls`` seeds also the control
(the reference in float8 in the program's place) and the fault (the
last row of every batch left out). One JSON line per seed: the numbers
on standard output, and with every leaf's reading beside them in
``chiprun_out/calibrate.<workload>.jsonl``.

    python3 benchmarks/tools/calibrate_tokens.py \
        --workload glm47flash.fit_4k --seeds 101,102,... --controls 2
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(argv=None):
    from benchmarks.harness import compare
    from benchmarks.harness.spec import REPO, Cell, load_module

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=2)
    ap.add_argument("--bf16", action="store_true",
                    help="also the reference with bfloat16 operands")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    cell = Cell(args.workload)
    args.seed = 0
    fr = load_module("drivers", "fit_tokens").FitRun(cell, args)
    steps = fr.traffic["loss_steps"]
    out_dir = os.path.join(REPO, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"calibrate.{args.workload}.jsonl")
    with open(path, "a") as f:
        for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
            t0 = time.perf_counter()
            fr.start(seed, warmup_chunks=1)
            fr.release_arrays()
            reference = fr.reference_readings()
            row = {"seed": seed,
                   "reference_losses": reference["losses"]}
            variants = [("program", None)]
            if i < args.controls:
                variants += [
                    ("control_float8", {"compute": "float8"}),
                    ("fault_row_left_out", {"fault": "row_left_out"})]
                if args.bf16:
                    variants.append(
                        ("reference_bfloat16", {"compute": "bfloat16"}))
            brief = {"seed": seed}
            for name, kw in variants:
                got = (fr.program if kw is None
                       else fr.reference_readings(**kw))
                per_leaf = {}
                values, where = compare.numbers(
                    got, reference, steps, per_leaf)
                row[name] = values
                row[name + "_worst"] = where
                row[name + "_per_leaf"] = per_leaf
                row[name + "_losses"] = got["losses"]
                brief[name] = {k: float(f"{v:.3g}")
                               for k, v in values.items()}
            row["seconds"] = brief["seconds"] = round(
                time.perf_counter() - t0, 1)
            print(json.dumps(brief), flush=True)
            f.write(json.dumps(row) + "\n")
            f.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
