#!/usr/bin/env python
"""Whole-net transform benchmarks: compile-vs-depth and remat-memory.

Two A/Bs for the ``nn/core.py`` transforms, each measurement in a
FRESH subprocess with the persistent compile cache disabled so every
reported compile is a real XLA compile (a warm cache would make the
scan-over-layers ratio meaningless on the second run):

``compile_vs_depth``
    Wall-clock trace+compile of the jitted train step for a
    homogeneous TransformerBlock stack at depth 4 / 16 / 64, with
    scan-over-layers off vs on. Off: the HLO is O(depth) and XLA's
    optimization passes scale super-linearly with it — this is
    exactly the mode that blew the BENCH r05/r06 budgets. On: the
    block body is traced once under ``lax.scan``, so compile time is
    ~flat in depth. Gate: ``speedup_depth64 >= 2``.

``remat_memory``
    XLA's own memory plan (``compiled.memory_analysis()``: temp
    buffer bytes = the activation working set) for the train step of
    the transformer config, remat off vs on (``full``), plus the
    max batch that fits a fixed activation budget (the remat-off
    working set at the base batch) under each policy — the
    "2x batch at fixed HBM" claim made falsifiable on any backend.
    On backends that report ``memory_stats()`` (TPU) the measured
    peak bytes ride along. Gate: ``batch_ratio >= 1.5`` (or
    equivalently ``temp_bytes_ratio >= 1.5``).

Prints ONE JSON line; runnable standalone or from ``bench.py``'s
``compile_vs_depth`` / ``remat_memory`` sections (PR-5 SIGALRM budget
box + PR-6 compile-stats sidecar ride along in the bench harness).
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# every measurement runs in a child with a FRESH, empty compile cache
# dir (and jax's persistent cache left off) — honest cold compiles
_CHILD_ENV_BASE = {
    "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS", "cpu"),
    "JAX_COMPILATION_CACHE_DIR": "",
    "PYTHONPATH": REPO,
}

_MEASURE_SRC = r"""
import json, sys, time
import numpy as np

spec = json.loads(sys.argv[1])
from deeplearning4j_tpu.zoo.models import transformer_lm
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.nn import core
import jax.numpy as jnp
import jax

conf = transformer_lm(
    vocab=spec["vocab"], d_model=spec["d_model"],
    n_layers=spec["depth"], n_heads=spec["heads"],
    scan_layers=spec["scan"], remat=spec["remat"],
)
net = MultiLayerNetwork(conf).init()
rng = np.random.RandomState(0)
b, t = spec["batch"], spec["seq"]
x = jnp.asarray(rng.randn(b, spec["vocab"], t).astype(np.float32))
y = jnp.asarray(np.eye(spec["vocab"], dtype=np.float32)[
    rng.randint(0, spec["vocab"], (b, t))
].transpose(0, 2, 1))
lrs = {k: jnp.asarray(v, jnp.float32)
       for k, v in net.updater_def.scheduled_lrs(0).items()}
tt = jnp.asarray(1, jnp.float32)
key = jax.random.fold_in(net._base_key, 0)
step = net._build_step()
t0 = time.perf_counter()
lowered = step.lower(net.params, net.updater_state, net.state,
                     x, y, None, None, lrs, tt, key)
t_trace = time.perf_counter() - t0
t0 = time.perf_counter()
compiled = lowered.compile()
t_compile = time.perf_counter() - t0
out = {"trace_s": round(t_trace, 3), "compile_s": round(t_compile, 3),
       "total_s": round(t_trace + t_compile, 3)}
if spec.get("memory"):
    try:
        ma = compiled.memory_analysis()
        out["temp_bytes"] = int(ma.temp_size_in_bytes)
        out["argument_bytes"] = int(ma.argument_size_in_bytes)
    except Exception as e:
        out["memory_analysis_error"] = str(e)[:200]
    stats = {}
    try:
        stats = jax.local_devices()[0].memory_stats() or {}
    except Exception:
        pass
    if "peak_bytes_in_use" in stats:
        out["device_peak_bytes"] = int(stats["peak_bytes_in_use"])
print(json.dumps(out))
"""


def _measure(spec: dict, timeout: float,
             allow_timeout: bool = False) -> dict:
    env = {**os.environ, **_CHILD_ENV_BASE}
    with tempfile.TemporaryDirectory() as d:
        env["XDG_CACHE_HOME"] = d
        try:
            out = subprocess.run(
                [sys.executable, "-c", _MEASURE_SRC,
                 json.dumps(spec)],
                capture_output=True, text=True, timeout=timeout,
                env=env,
            )
        except subprocess.TimeoutExpired:
            if not allow_timeout:
                raise
            # the measurement IS the finding: compile exceeded the
            # box — report the box as a lower bound
            return {"total_s": round(float(timeout), 1),
                    "timed_out": True}
    if out.returncode != 0:
        raise RuntimeError(
            f"transform measurement failed for {spec}: "
            f"{out.stderr[-1500:]}"
        )
    return json.loads(out.stdout.strip().splitlines()[-1])


def _base_spec(**over) -> dict:
    spec = {
        "vocab": 13, "d_model": 32, "heads": 2, "seq": 16,
        "batch": 4, "depth": 4, "scan": False, "remat": "none",
    }
    spec.update(over)
    return spec


def compile_vs_depth(depths=(4, 16, 64), budget_s=None) -> dict:
    """Trace+compile wall clock per (depth, scan) — one cold child
    process each. The deepest scan-OFF measurement gets the lion's
    share of the budget (the O(depth) HLO is exactly what compiles
    slowly); if even that box overruns, the box itself is reported
    as a LOWER BOUND and the speedup becomes '>='."""
    budget = float(budget_s or 540.0)
    t0 = time.monotonic()

    def left():
        return max(40.0, budget - (time.monotonic() - t0))

    per = {}
    shallow_box = max(40.0, budget / 10.0)
    for depth in depths:
        deepest = depth == max(depths)
        row = {}
        # scan-on first: it is cheap at every depth and must land
        row["scan_on"] = _measure(
            _base_spec(depth=depth, scan=True), shallow_box
        )
        off_box = (
            min(left() - shallow_box / 2, 300.0)
            if deepest else shallow_box
        )
        row["scan_off"] = _measure(
            _base_spec(depth=depth, scan=False), off_box,
            allow_timeout=True,
        )
        row["speedup"] = round(
            row["scan_off"]["total_s"]
            / max(row["scan_on"]["total_s"], 1e-9), 2,
        )
        if row["scan_off"].get("timed_out"):
            row["speedup_is_lower_bound"] = True
        per[f"depth_{depth}"] = row
    deepest_key = f"depth_{max(depths)}"
    return {
        "model": "transformer_lm (homogeneous TransformerBlock stack)",
        "measured": "trace+compile wall of the jitted train step, "
                    "cold process, compile cache disabled",
        "depths": list(depths),
        **per,
        "speedup_depth_max": per[deepest_key]["speedup"],
        "gate": "speedup >= 2 at the deepest stack",
    }


def remat_memory(base_batch=16, budget_s=None) -> dict:
    """Activation working set (XLA temp bytes) and max-fitting batch
    at a fixed activation budget, remat off vs full."""
    timeout = 280.0
    if budget_s:
        timeout = max(40.0, budget_s / 12.0)
    spec = dict(d_model=64, seq=32, depth=4, memory=True)
    off = _measure(
        _base_spec(batch=base_batch, **spec), timeout
    )
    on = _measure(
        _base_spec(batch=base_batch, remat="full", **spec), timeout
    )
    out = {
        "model": "transformer_lm d_model=64 depth=4 seq=32",
        "measured": "XLA memory_analysis temp bytes (activation "
                    "working set) of the train step; device peak "
                    "bytes when the backend reports memory_stats()",
        "base_batch": base_batch,
        "remat_off": off,
        "remat_on": on,
    }
    if "temp_bytes" in off and "temp_bytes" in on:
        out["temp_bytes_ratio"] = round(
            off["temp_bytes"] / max(on["temp_bytes"], 1), 2
        )
        # max batch under the remat-off working set at base_batch:
        # double until it no longer fits, for each policy
        budget = off["temp_bytes"]

        def max_batch(remat):
            fit = base_batch
            b = base_batch * 2
            while b <= base_batch * 16:
                m = _measure(
                    _base_spec(batch=b, remat=remat, **spec), timeout
                )
                if m.get("temp_bytes", budget + 1) > budget:
                    break
                fit = b
                b *= 2
            return fit

        out["max_batch_off"] = base_batch  # the budget definition
        out["max_batch_on"] = max_batch("full")
        out["batch_ratio"] = round(
            out["max_batch_on"] / out["max_batch_off"], 2
        )
    out["gate"] = ("batch_ratio >= 1.5 (>= 1.5x larger batch at the "
                   "remat-off activation budget)")
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--section", default="both",
                    choices=("compile_vs_depth", "remat_memory",
                             "both"))
    ap.add_argument("--budget-s", type=float, default=None)
    args = ap.parse_args()
    out = {}
    if args.section in ("compile_vs_depth", "both"):
        out["compile_vs_depth"] = compile_vs_depth(
            budget_s=args.budget_s
        )
    if args.section in ("remat_memory", "both"):
        out["remat_memory"] = remat_memory(budget_s=args.budget_s)
    print(json.dumps(out if args.section == "both"
                     else out[args.section]))


if __name__ == "__main__":
    main()
