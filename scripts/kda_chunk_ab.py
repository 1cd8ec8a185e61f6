#!/usr/bin/env python3
"""The delta rule's chunk kernels alone on the chip, by heads a program.

    python3 scripts/kda_chunk_ab.py [--heads 1,2,4,8] [--out FILE]

At ``ling3flash.fit_4k``'s class (q, k, v ``[2, 4096, 32·128]``
bfloat16, chunks of 64) it times ``kda_chunk_fwd`` (keeping the states,
as under differentiation) and ``kda_chunk_bwd`` with each count of heads
a program in ``--heads``, turn about in this one process: ``--reps``
calls then ``block_until_ready``, per call, the median of ``--rounds``.
Each side's outputs (o, the states, dq, dk, dv, dΓ, dβ) are compared on
the chip with the first side's (one head a program by default): the
heads run the same operations, so anything but equality is printed as
``[fault]`` and the exit code is 2. ``pick`` marks what
``tiling.kda_chunk_heads`` chooses for each kernel. Exits non-zero where
JAX finds no TPU (``--rehearse``: a tiny class on any device, kernels
interpreted; nothing it prints is a device number). The table is printed
as one JSON line, and written to ``--out`` where given.
"""

import argparse
import json
import os
import statistics
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

CHUNK = 64
CLASS = (2, 4096, 32, 128)
REHEARSAL_CLASS = (1, 128, 4, 128)


def inputs(b, t, h, d):
    """q, k, v ``[b, t, h·d]`` bfloat16, Γ ``[b, t, h·d]`` float32 (the
    chunks' cumulative log-decays, each step's in (-5, 0)), β ``[b, t,
    h]`` float32 and a cotangent of the output."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.ops import kda_chunk as kda

    keys = jax.random.split(jax.random.PRNGKey(b * t + h), 6)

    def unit(key, shift=0.0):
        x = jax.random.normal(key, (b, t, h, d)) + shift
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    q = (unit(keys[0]) / d ** 0.5).astype(jnp.bfloat16)
    k = unit(keys[1], 0.4).astype(jnp.bfloat16)
    v = jax.random.normal(keys[2], (b, t, h, d), jnp.bfloat16)
    g = -5.0 * jax.random.uniform(keys[3], (b, t, h, d)) ** 0.25
    gam = kda.chunk_cumsum(g, CHUNK)
    beta = jax.random.uniform(keys[4], (b, t, h))
    do = jax.random.normal(keys[5], (b, t, h * d), jnp.bfloat16)
    flat = lambda x: x.reshape(b, t, h * d)  # noqa: E731
    return flat(q), flat(k), flat(v), flat(gam), beta, do


def timed(fn, args, reps, rounds):
    """ms a call: the median of ``rounds`` runs of ``reps`` calls."""
    import jax

    jax.block_until_ready(fn(*args))
    per = []
    for _ in range(rounds):
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn(*args)
        jax.block_until_ready(out)
        per.append((time.perf_counter() - t0) / reps * 1e3)
    return statistics.median(per)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--heads", default="1,2,4,8")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--rounds", type=int, default=5)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    from deeplearning4j_tpu.ops import kda_chunk as kda
    from deeplearning4j_tpu.ops import tiling

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        print(f"no TPU: JAX sees {dev.platform}", file=sys.stderr)
        return 1
    b, t, h, d = REHEARSAL_CLASS if args.rehearse else CLASS
    interpret = args.rehearse
    q, k, v, gam, beta, do = inputs(b, t, h, d)
    picks = {bwd: tiling.kda_chunk_heads(h, d, CHUNK, 2, bwd)
             for bwd in (False, True)}

    def sides(g):
        fwd = jax.jit(lambda *a: kda.kda_chunk_fwd(
            *a, CHUNK, interpret, keep_states=True, heads=g))
        bwd = jax.jit(lambda *a: kda.kda_chunk_bwd(
            *a, CHUNK, interpret, heads=g))
        return fwd, bwd

    rows, outs, fault = [], {}, False
    groups = [int(g) for g in args.heads.split(",")]
    for g in groups:
        fwd, bwd = sides(g)
        o, states = fwd(q, k, v, gam, beta)
        grads = bwd(q, k, v, gam, beta, states, do)
        outs[g] = [np.asarray(x) for x in (o, states) + tuple(grads)]
        fwd_ms = timed(fwd, (q, k, v, gam, beta), args.reps, args.rounds)
        bwd_ms = timed(bwd, (q, k, v, gam, beta, states, do), args.reps,
                       args.rounds)
        rows.append({"heads": g, "fwd_ms": fwd_ms, "bwd_ms": bwd_ms})
    base = outs[groups[0]]
    for row in rows:
        diff = max(float(np.abs(a.astype(np.float32) - z.astype(np.float32)
                                ).max())
                   for a, z in zip(outs[row["heads"]], base))
        row["max_abs_diff"] = diff
        fault |= diff != 0.0
        mark = " ".join(name for name, bwd in (("pick_fwd", False),
                                              ("pick_bwd", True))
                        if picks[bwd] == row["heads"])
        print(f"heads {row['heads']:2d}  fwd {row['fwd_ms']:8.3f} ms  "
              f"bwd {row['bwd_ms']:8.3f} ms  max|diff| {diff:.3g}"
              f"{'  [fault]' if diff else ''}  {mark}", flush=True)
    report = {"device": dev.device_kind, "class": [b, t, h, d],
              "chunk": CHUNK, "rehearse": args.rehearse, "rows": rows,
              "picks": {"fwd": picks[False], "bwd": picks[True]}}
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(report, f, indent=1)
    print(json.dumps(report))
    return 2 if fault else 0


if __name__ == "__main__":
    sys.exit(main())
