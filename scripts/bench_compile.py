#!/usr/bin/env python
"""Cold-start vs warm-start serving boot A/B (CPU, seeded, ~1 min).

The measurement gate for the compile-artifact subsystem
(``deeplearning4j_tpu/compile/``): three child processes boot the
SAME serving tier from the SAME checkpoint and the A/B isolates what
each tier of compile reuse buys —

- ``cold``: empty persistent cache, no AOT — every ladder bucket
  pays a real XLA compile at warmup (the pre-subsystem world);
- ``warm``: the persistent cache the cold boot just populated —
  warmup compiles become disk reads (tier 1);
- ``aot``: the checkpoint's bundled AOT-exported executables —
  warmup *deserializes* the bucket ladder; the child performs ZERO
  XLA backend compiles, counter-asserted from jax's own compile
  instrumentation (tier 2).

Each child reports boot-to-ready seconds (CheckpointManager restore +
``ModelServer.start()`` warmup, python/jax import time excluded and
reported separately) and first-predict latency. Prints ONE JSON
line::

    {"cold": {"boot_to_ready_s": ..., "first_predict_ms": ...,
              "backend_compiles": ..., "compile_seconds": ...},
     "warm": {..., "cache_hits": ...},
     "aot":  {..., "aot_buckets": ...},
     "speedup_boot_warm": ..., "speedup_boot_aot": ...,
     "zero_compile_warm_restart": true}

Acceptance gates: ``zero_compile_warm_restart`` (the aot child's
``backend_compiles == 0``) and ``speedup_boot_aot > 1`` (materially
lower boot-to-ready than cold).

Runnable standalone (``python scripts/bench_compile.py``) or via
``bench.py``'s ``aot_compile`` section.
"""

import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

_T0 = time.perf_counter()  # child mode: process-start reference

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

N_IN = 48
MAX_BATCH = 16  # ladder 1,2,4,8,16 -> 5 bucket executables


def _make_net(seed=0):
    from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    conf = (
        NeuralNetConfiguration.Builder().seed(seed).learning_rate(0.1)
        .list()
        .layer(DenseLayer(n_in=N_IN, n_out=512, activation="tanh"))
        .layer(DenseLayer(n_in=512, n_out=512, activation="tanh"))
        .layer(OutputLayer(n_out=8))
        .build()
    )
    return MultiLayerNetwork(conf).init()


def _prepare(ckpt_dir: str, seed: int) -> None:
    """Save the checkpoint + its AOT bundle. Runs in a child with a
    PRIVATE cache dir so exporting (which compiles) cannot pre-warm
    the shared cache the cold measurement must find empty."""
    from deeplearning4j_tpu.compile.aot import export_serving_bundle
    from deeplearning4j_tpu.resilience.checkpoint import (
        CheckpointManager,
    )
    from deeplearning4j_tpu.serving.batcher import BucketLadder

    net = _make_net(seed)
    buckets = BucketLadder(None, MAX_BATCH).buckets
    bundle = export_serving_bundle(net, buckets)
    CheckpointManager(ckpt_dir).save(net, artifacts=bundle)
    print(json.dumps({"prepared": sorted(len(v) for v in
                                         bundle.values())}))


def _serve(ckpt_dir: str, mode: str, seed: int) -> None:
    """Boot the serving tier once and print the measurements. The
    persistent-cache dir comes from JAX_COMPILATION_CACHE_DIR (set
    by the parent); ``mode`` gates AOT install."""
    import numpy as np

    from deeplearning4j_tpu.compile.persistent import cache_stats
    from deeplearning4j_tpu.resilience.checkpoint import (
        CheckpointManager,
    )
    from deeplearning4j_tpu.serving.server import ModelServer

    import_s = time.perf_counter() - _T0  # python+jax+framework
    mgr = CheckpointManager(ckpt_dir)
    t0 = time.perf_counter()
    srv = ModelServer(
        checkpoint_manager=mgr, max_batch_size=MAX_BATCH,
        aot=(mode == "aot"),
    ).start()
    code, _ = srv.readiness()
    boot_s = time.perf_counter() - t0
    try:
        feats = np.random.RandomState(seed).rand(
            3, N_IN
        ).astype(np.float32)
        t1 = time.perf_counter()
        pcode, _, _ = srv.submit(feats)
        first_ms = (time.perf_counter() - t1) * 1000.0
        snap = srv.metrics_snapshot()
    finally:
        srv.stop(drain_timeout=1)
    stats = cache_stats()
    print(json.dumps({
        "mode": mode,
        "ready_code": code,
        "predict_code": pcode,
        "import_s": round(import_s, 3),
        "boot_to_ready_s": round(boot_s, 3),
        "first_predict_ms": round(first_ms, 3),
        "backend_compiles": stats["backend_compiles"],
        "compile_seconds": stats["compile_seconds"],
        "cache_hits": stats["hits"],
        "cache_misses": stats["misses"],
        "aot_buckets": snap["compile"]["aot_buckets_installed"],
        "xla_compiles_total": snap["xla_compiles_total"],
        "post_warmup_compiles_total":
            snap["post_warmup_compiles_total"],
    }), flush=True)


def _spawn(argv, cache_dir: str, timeout: float) -> dict:
    env = dict(os.environ)
    env["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    env.setdefault("JAX_PLATFORMS", "cpu")
    env["PYTHONPATH"] = os.pathsep.join(
        [REPO] + env.get("PYTHONPATH", "").split(os.pathsep)
    )
    out = subprocess.run(
        [sys.executable, os.path.abspath(__file__)] + argv,
        capture_output=True, text=True, env=env, timeout=timeout,
    )
    if out.returncode != 0:
        raise RuntimeError(
            f"child {argv} failed: {out.stderr[-2000:]}"
        )
    return json.loads(out.stdout.strip().splitlines()[-1])


def run(seed=0, child_timeout=120, keep_workdir=False) -> dict:
    work = tempfile.mkdtemp(prefix="dl4j_bench_compile_")
    ckpt = os.path.join(work, "ckpt")
    # the caches sit at fixed paths inside the checkout (the path is
    # part of a cache entry's key) and are emptied first: "cold" is
    # made by deleting, not by inventing a new directory
    caches = os.path.join(REPO, ".jax_cache", "bench_compile")
    shutil.rmtree(caches, ignore_errors=True)
    shared = os.path.join(caches, "shared")
    prep = os.path.join(caches, "prepare")
    try:
        _spawn(["--prepare", "--ckpt", ckpt, "--seed", str(seed)],
               prep, child_timeout)
        fields = ("boot_to_ready_s", "first_predict_ms", "import_s",
                  "backend_compiles", "compile_seconds", "cache_hits",
                  "cache_misses", "aot_buckets",
                  "post_warmup_compiles_total")
        out = {}
        # run order IS the experiment: cold populates the shared
        # cache, warm re-reads it, aot skips the compiler entirely
        for name, mode in (("cold", "jit"), ("warm", "jit"),
                           ("aot", "aot")):
            r = _spawn(
                ["--serve", "--ckpt", ckpt, "--mode", mode,
                 "--seed", str(seed)],
                shared, child_timeout,
            )
            if r.get("ready_code") != 200 or r.get(
                    "predict_code") != 200:
                raise RuntimeError(f"{name} boot unhealthy: {r}")
            out[name] = {k: r[k] for k in fields}
        out["speedup_boot_warm"] = round(
            out["cold"]["boot_to_ready_s"]
            / max(out["warm"]["boot_to_ready_s"], 1e-9), 2
        )
        out["speedup_boot_aot"] = round(
            out["cold"]["boot_to_ready_s"]
            / max(out["aot"]["boot_to_ready_s"], 1e-9), 2
        )
        out["zero_compile_warm_restart"] = (
            out["aot"]["backend_compiles"] == 0
        )
        out["gates"] = ("zero_compile_warm_restart and "
                        "speedup_boot_aot > 1")
        return out
    finally:
        if not keep_workdir:
            shutil.rmtree(work, ignore_errors=True)


def main():
    ap = argparse.ArgumentParser(
        description=__doc__.splitlines()[0]
    )
    ap.add_argument("--prepare", action="store_true")
    ap.add_argument("--serve", action="store_true")
    ap.add_argument("--ckpt")
    ap.add_argument("--mode", choices=("jit", "aot"), default="jit")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--child-timeout", type=float, default=120)
    ap.add_argument("--keep-workdir", action="store_true")
    args = ap.parse_args()
    if args.prepare:
        _prepare(args.ckpt, args.seed)
        return
    if args.serve:
        _serve(args.ckpt, args.mode, args.seed)
        return
    print(json.dumps(run(
        seed=args.seed, child_timeout=args.child_timeout,
        keep_workdir=args.keep_workdir,
    )))


if __name__ == "__main__":
    main()
