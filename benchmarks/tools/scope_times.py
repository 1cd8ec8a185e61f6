#!/usr/bin/env python3
"""Put a token cell's device time down to the program's scopes: trace
one short window of the cell, compile its scan program once more for
the attached device (a cache hit) to read each operation's
``op_name`` from the compiled text, and add up every operation's self
time by the innermost ``jax.named_scope`` that made it and by pass
(``bwd`` where the ``op_name`` holds a ``transpose(``: the backward
pass and what it recomputes inside it; ``fwd`` otherwise). The scopes are
the names the package's source hands to ``jax.named_scope``; what
carries none of them is ``other`` (the updater, the residual adds, the
norms outside a scope). Writes ``chiprun_out/scopes.<workload>.json``:
milliseconds a step by scope and pass, by stem within each scope, and
every operation's own line.

    python3 benchmarks/tools/scope_times.py --workload granite40hmicro.fit_4k

``harness/trace_reduce.py`` keeps stems and categories, not scopes; a
per-layer metric by scope is a ``benchmark`` PR's (PERF.md section 7).
"""

import argparse
import functools
import glob
import json
import os
import re
import sys
import tempfile
from collections import defaultdict

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?(%[\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_NAMED_SCOPE = re.compile(r'named_scope\(\s*"([^"]+)"\s*\)')


@functools.cache
def program_scopes():
    """Every name the package hands to ``jax.named_scope``, read from
    its source (each is a literal), longest first: a scope a layer adds
    later is in the table without an edit here."""
    from benchmarks.harness.spec import REPO

    found = set()
    for path in glob.glob(os.path.join(
            REPO, "deeplearning4j_tpu", "**", "*.py"), recursive=True):
        with open(path) as f:
            found.update(_NAMED_SCOPE.findall(f.read()))
    return tuple(sorted(found, key=lambda s: (-len(s), s)))


@functools.cache
def _scope_pattern():
    return re.compile(
        r"(?<![\w.])(" + "|".join(map(re.escape, program_scopes()))
        + r")(?![\w.])")


def scope_of(op_name):
    """``(scope, pass)`` of an operation's ``op_name``: the last of the
    program's scopes on its path, as a path part (``/mlp/``) or inside
    a transform's brackets (``transpose(jvp(mlp))``)."""
    found = _scope_pattern().findall(op_name)
    return (found[-1] if found else "other",
            "bwd" if "transpose(" in op_name else "fwd")


def op_names(compiled_text):
    """{instruction name: op_name} of every instruction of a compiled
    program's text that carries one."""
    out = {}
    for line in compiled_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m:
            found = _OP_NAME.search(line)
            if found:
                out[m.group(1)] = found.group(1)
    return out


def by_scope(events, names, steps):
    """``events`` ``[(name, self_ns, category)]`` added up by scope,
    pass and stem, in milliseconds a step."""
    from benchmarks.harness import trace_reduce

    scopes = defaultdict(lambda: defaultdict(float))
    stems = defaultdict(lambda: defaultdict(float))
    ops = defaultdict(float)
    for name, self_ns, cat in events:
        head = name.split(" = ", 1)[0].split(" ")[0]
        scope, side = scope_of(names.get(head, ""))
        ms = self_ns * 1e-6 / steps
        scopes[scope][side] += ms
        stems[scope][f"{trace_reduce.stem_of(name)} [{cat}]"] += ms
        ops[f"{head} [{cat}] {scope}/{side}"] += ms
    return scopes, stems, ops


def main(argv=None):
    import jax
    from jax.sharding import SingleDeviceSharding

    from benchmarks.harness import trace_reduce
    from benchmarks.harness.spec import REPO, Cell, load_module
    from benchmarks.tools.compile_described_tokens import (
        compile_scan_program,
    )

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    fr = load_module("drivers", "fit_tokens").FitRun(
        Cell(args.workload), args)
    fr.start(args.seed)
    steps0 = fr.net.iteration_count
    with tempfile.TemporaryDirectory(prefix="bench_trace_") as tdir:
        with jax.profiler.trace(tdir):
            fr.window(args.seconds)
        planes = [p for p in trace_reduce.load(
            trace_reduce.find_xplane(tdir)).planes
            if p.name.startswith(trace_reduce.DEVICE_PREFIX)]
        events = [ev for p in planes for ev in trace_reduce.self_times(
            trace_reduce.line_events(p, trace_reduce.OPS_LINE))]
    steps = fr.net.iteration_count - steps0
    fr.free_program()
    compiled, _, _ = compile_scan_program(
        args.workload, SingleDeviceSharding(jax.devices()[0]),
        rehearse=args.rehearse)
    scopes, stems, ops = by_scope(
        events, op_names(compiled.as_text()), max(steps, 1))
    rounded = lambda d: {k: round(v, 3) for k, v in sorted(  # noqa: E731
        d.items(), key=lambda kv: -kv[1])}
    out = {
        "workload": args.workload, "steps": steps,
        "device": jax.devices()[0].device_kind, "events": len(events),
        "ms_per_step": round(sum(sum(v.values()) for v in scopes.values()),
                             3),
        "by_scope": {k: rounded(v) for k, v in sorted(
            scopes.items(), key=lambda kv: -sum(kv[1].values()))},
        "stems_by_scope": {k: rounded(v) for k, v in stems.items()},
        "ops": rounded(ops),
    }
    out_dir = os.path.join(REPO, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"scopes.{args.workload}.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({k: out[k] for k in (
        "workload", "steps", "events", "ms_per_step", "by_scope")}))
    print(f"wrote {path}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
