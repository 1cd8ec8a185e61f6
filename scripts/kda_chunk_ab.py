#!/usr/bin/env python3
"""The delta rule's chunk kernels alone on the chip: where the chunks'
cumulative log-decays are summed, and by heads a program.

    python3 scripts/kda_chunk_ab.py [--parent DIR] [--heads 4] [--out FILE]

At ``ling3flash.fit_4k``'s class (q, k, v ``[2, 4096, 32·128]``
bfloat16, chunks of 64) it times ``kda_chunk_fwd`` (keeping the states,
as under differentiation) and ``kda_chunk_bwd``, fed the raw log-decays
``g``, with the kernels' sums of Γ (and of dg) spelled two ways:

- ``a-product``: the package's ``chunk_sums`` / ``chunk_sums_t``, one
  product with a triangle of ones at ``Precision.HIGHEST``;
- ``b-scan``: six shifted adds along sublanes (``pltpu.roll``) with
  masks, exact float32 adds, put in their place for this side only.

``--parent DIR`` times a checkout whose kernels take Γ (a tree from
before the sums moved into them): its kernels alone on Γ made ahead,
and XLA's sums beside them (``chunk_cumsum`` ``[2, 4096, 32, 128]``
float32 and its transpose, the reverse sums of dΓ, each a call of its
own), which is what a step paid for them: 12 forward calls, 6 backward
calls, 12 sums and 6 transposes a step at the cell.

Each count of heads a program in ``--heads`` runs on each side, turn
about in this one process: ``--reps`` calls then ``block_until_ready``,
per call, the median of ``--rounds``. On every side o, dq, dk, dv, dg
and dβ are compared with the XLA form in float32 (``kda_chunked`` at
``default_matmul_precision("highest")``, Γ from ``chunk_cumsum``):
``[fault]`` where a side lies further from it than 1.25 times the
parent's distance + 1e-3 (the interpreter tests' rule in bfloat16), or,
with no parent, than the first side's; and every count of heads has to
give its side's first count's outputs to the bit. A fault makes the exit
code 2. Exits non-zero where JAX finds no TPU (``--rehearse``: a tiny
class on any device, kernels interpreted; nothing it prints is a device
number). The table is printed as one JSON line, and written to
``--out`` where given.
"""

import argparse
import contextlib
import importlib.util
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
# calls a step at the cell: the primal forward and full remat's, the
# backward once, in each of the six delta-rule layers
FWD_CALLS, BWD_CALLS = 12, 6


def scan_sums(g):
    """Spelling (b) of ``chunk_sums``: the cumulative sum down the rows
    of ``g`` ``[C, n]`` as a log-step scan, rows rolled down by 1, 2,
    4, ... and added where they come from inside the chunk."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.pallas import tpu as pltpu

    c = g.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, g.shape, 0)
    s = 1
    while s < c:
        g = g + jnp.where(row >= s, pltpu.roll(g, s, 0), 0.0)
        s *= 2
    return g


def scan_sums_t(dgam):
    """Spelling (b) of ``chunk_sums_t``: the reverse sums, rows rolled
    up by 1, 2, 4, ... (down by ``C`` less that)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.pallas import tpu as pltpu

    c = dgam.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, dgam.shape, 0)
    s = 1
    while s < c:
        dgam = dgam + jnp.where(row < c - s, pltpu.roll(dgam, c - s, 0), 0.0)
        s *= 2
    return dgam


@contextlib.contextmanager
def spelled(kda, spelling):
    """The package's kernels with their sums spelled ``spelling``
    while a side is traced."""
    if spelling == "a-product":
        yield
        return
    saved = kda.chunk_sums, kda.chunk_sums_t
    kda.chunk_sums, kda.chunk_sums_t = scan_sums, scan_sums_t
    try:
        yield
    finally:
        kda.chunk_sums, kda.chunk_sums_t = saved


def load_parent(tree):
    """``deeplearning4j_tpu/ops/kda_chunk.py`` of another checkout, as a
    module of its own beside this tree's package."""
    path = os.path.join(tree, "deeplearning4j_tpu", "ops", "kda_chunk.py")
    spec = importlib.util.spec_from_file_location("kda_chunk_parent", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def inputs(b, t, h, d):
    """q, k, v ``[b, t, h·d]`` bfloat16, the log-decays g ``[b, t, h·d]``
    float32 (each step's in (-5, 0)), β ``[b, t, h]`` float32 and a
    cotangent of the output."""
    import jax
    import jax.numpy as jnp

    keys = jax.random.split(jax.random.PRNGKey(b * t + h), 6)

    def unit(key, shift=0.0):
        x = jax.random.normal(key, (b, t, h, d)) + shift
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    q = (unit(keys[0]) / d ** 0.5).astype(jnp.bfloat16)
    k = unit(keys[1], 0.4).astype(jnp.bfloat16)
    v = jax.random.normal(keys[2], (b, t, h, d), jnp.bfloat16)
    g = -5.0 * jax.random.uniform(keys[3], (b, t, h, d)) ** 0.25
    beta = jax.random.uniform(keys[4], (b, t, h))
    do = jax.random.normal(keys[5], (b, t, h * d), jnp.bfloat16)
    flat = lambda x: x.reshape(b, t, h * d)  # noqa: E731
    return flat(q), flat(k), flat(v), flat(g), beta, do


def reference(kda, q, k, v, g, beta, do, shape):
    """(o, dq, dk, dv, dg, dβ) of the XLA form in float32, every product
    at ``HIGHEST``, Γ from ``chunk_cumsum``."""
    import jax
    import jax.numpy as jnp

    b, t, h, d = shape
    split = lambda x: x.astype(jnp.float32).reshape(b, t, h, d)  # noqa

    def form(q_, k_, v_, g_, beta_):
        return kda.kda_chunked(q_, k_, v_, kda.chunk_cumsum(g_, CHUNK),
                               beta_, CHUNK).reshape(b, t, h * d)

    with jax.default_matmul_precision("highest"):
        o, vjp = jax.vjp(form, *(split(x) for x in (q, k, v, g)), beta)
        grads = vjp(do.astype(jnp.float32))
    return [o] + [x.reshape(b, t, -1) for x in grads[:4]] + [grads[4]]


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
    ap.add_argument("--heads", default="4")
    ap.add_argument("--parent", default=None,
                    help="a checkout whose kernels take Γ")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--rounds", type=int, default=5)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    from deeplearning4j_tpu.ops import kda_chunk as kda

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        print(f"no TPU: JAX sees {dev.platform}", file=sys.stderr)
        return 1
    b, t, h, d = REHEARSAL_CLASS if args.rehearse else CLASS
    interpret = args.rehearse
    q, k, v, g, beta, do = inputs(b, t, h, d)
    ref = [np.asarray(x, np.float32)
           for x in reference(kda, q, k, v, g, beta, do, (b, t, h, d))]
    groups = [int(n) for n in args.heads.split(",")]
    split = lambda x: x.reshape(b, t, h, d)  # noqa: E731
    flat = lambda x: x.reshape(b, t, h * d)  # noqa: E731
    sums = jax.jit(lambda x: flat(kda.chunk_cumsum(split(x), CHUNK)))
    _, sums_vjp = jax.vjp(sums, g)
    sums_t = jax.jit(lambda x: sums_vjp(x)[0])

    def ours(spelling, n):
        """This tree's kernels on g, their sums spelled ``spelling``."""
        with spelled(kda, spelling):
            fwd = jax.jit(lambda *a: kda.kda_chunk_fwd(
                *a, CHUNK, interpret, keep_states=True, heads=n))
            bwd = jax.jit(lambda *a: kda.kda_chunk_bwd(
                *a, CHUNK, interpret, heads=n))
            o, states = fwd(q, k, v, g, beta)
            grads = bwd(q, k, v, g, beta, states, do)
        return fwd, bwd, (g,), [o] + list(grads)

    def parents(mod, n):
        """The parent's kernels alone on Γ made ahead; dΓ summed back
        into dg by XLA for the comparison."""
        gam = sums(g)
        fwd = jax.jit(lambda *a: mod.kda_chunk_fwd(
            *a, CHUNK, interpret, keep_states=True, heads=n))
        bwd = jax.jit(lambda *a: mod.kda_chunk_bwd(
            *a, CHUNK, interpret, heads=n))
        o, states = fwd(q, k, v, gam, beta)
        dq, dk, dv, dgam, dbeta = bwd(q, k, v, gam, beta, states, do)
        return fwd, bwd, (gam,), [o, dq, dk, dv, sums_t(dgam), dbeta]

    sides = [("a-product", lambda n: ours("a-product", n)),
             ("b-scan", lambda n: ours("b-scan", n))]
    if args.parent:
        mod = load_parent(os.path.abspath(args.parent))
        sides.insert(0, ("parent", lambda n: parents(mod, n)))

    rows, firsts, fault = [], {}, False
    names = ("o", "dq", "dk", "dv", "dg", "dbeta")
    for n in groups:
        for side, build in sides:
            fwd, bwd, decays, outs = build(n)
            outs = [np.asarray(x, np.float32) for x in outs]
            err = {name: float(np.abs(x - r).max() / np.abs(r).max())
                   for name, x, r in zip(names, outs, ref)}
            states = fwd(q, k, v, *decays, beta)[1]
            row = {"side": side, "heads": n,
                   "fwd_ms": timed(fwd, (q, k, v, *decays, beta), args.reps,
                                   args.rounds),
                   "bwd_ms": timed(bwd, (q, k, v, *decays, beta, states, do),
                                   args.reps, args.rounds),
                   "err_vs_float32": err}
            first = firsts.setdefault(side, outs)
            row["same_as_first_heads"] = all(
                np.array_equal(x, y) for x, y in zip(outs, first))
            rows.append(row)
    sums_ms = timed(sums, (g,), args.reps, args.rounds)
    sums_t_ms = timed(sums_t, (g,), args.reps, args.rounds)
    yardstick = {}
    for row in rows:
        base = yardstick.setdefault(row["heads"], row["err_vs_float32"])
        bad = [name for name, e in row["err_vs_float32"].items()
               if not e <= 1.25 * base[name] + 1e-3]
        bad += [] if row["same_as_first_heads"] else ["heads"]
        fault |= bool(bad)
        extra = 0.0
        if row["side"] == "parent":
            extra = FWD_CALLS * sums_ms + BWD_CALLS * sums_t_ms
        row["step_ms"] = (FWD_CALLS * row["fwd_ms"] + BWD_CALLS
                          * row["bwd_ms"] + extra)
        worst = max(row["err_vs_float32"].items(), key=lambda kv: kv[1])
        print(f"{row['side']:10s} heads {row['heads']:2d}  fwd "
              f"{row['fwd_ms']:8.3f} ms  bwd {row['bwd_ms']:8.3f} ms  "
              f"a step {row['step_ms']:8.2f} ms  worst vs float32 "
              f"{worst[0]} {worst[1]:.3g}"
              f"{'  [fault: ' + ','.join(bad) + ']' if bad else ''}",
              flush=True)
    print(f"XLA's sums: chunk_cumsum {sums_ms:.3f} ms, its transpose "
          f"{sums_t_ms:.3f} ms a call", flush=True)
    report = {"device": dev.device_kind, "class": [b, t, h, d],
              "chunk": CHUNK, "rehearse": args.rehearse, "rows": rows,
              "xla_sums_ms": sums_ms, "xla_sums_t_ms": sums_t_ms,
              "parent": args.parent}
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(report, f, indent=1)
    print(json.dumps(report))
    return 2 if fault else 0


if __name__ == "__main__":
    sys.exit(main())
