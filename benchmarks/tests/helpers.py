"""Shared by the tests: one rehearsed run in this process."""

import json

CELLS = ["resnet50.fit", "chartransformer12.fit"]


def rehearse(capsys, workload, trace=0, seed=3, seconds=0.5):
    """Run ``run.py --rehearse`` in-process; the result object and the
    earlier lines."""
    from benchmarks import run

    capsys.readouterr()
    rc = run.main(["--workload", workload, "--seed", str(seed),
                   "--seconds", str(seconds), "--trace", str(trace),
                   "--rehearse"])
    out = capsys.readouterr()
    lines = out.out.strip().splitlines()
    return rc, json.loads(lines[-1]), lines[:-1], out.err
