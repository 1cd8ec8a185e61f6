#!/usr/bin/env python3
"""The delta rule's chunk kernels alone on the chip: q and k normalised
by XLA ahead of the kernels, or by the kernels in VMEM.

    python3 scripts/kda_chunk_ab.py [--parent DIR] [--heads 4] [--out FILE]

At ``ling3flash.fit_4k``'s class (q, k, v ``[2, 4096, 32·128]``
bfloat16 as the convolution gives them, raw; chunks of 64) it times, ms
a call:

- ``change``: this tree's ``kda_chunk_fwd`` (keeping the states, as
  under differentiation) and ``kda_chunk_bwd`` on raw q and k, each
  head's rows normalised inside the kernels;
- ``parent`` (with ``--parent DIR``, a checkout whose kernels take q and
  k normalised): XLA's per-head L2 norms of q and k (float32, q also
  over √d, rounded to bfloat16) then that checkout's forward kernel, and
  its backward kernel (on the normalised q and k the forward made) then
  the norms' transpose in XLA (autodiff of the same norms), each pair
  one jitted call, as a step runs them.

A step at the cell makes 12 forward calls (the primal pass and full
remat's) and 6 backward calls. The norms alone, forward and transpose,
are timed beside the parent's side.

Each count of heads a program in ``--heads`` runs on each side, turn
about in this one process: ``--reps`` calls then ``block_until_ready``,
per call, the median of ``--rounds``. On every side o, dq, dk, dv, dg
and dβ (of raw q and k) are compared with the XLA form in float32
(``kda_chunk``'s norms and ``kda_chunked`` at
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


def load_parent(tree):
    """``deeplearning4j_tpu/ops/kda_chunk.py`` of another checkout, as a
    module of its own beside this tree's package."""
    path = os.path.join(tree, "deeplearning4j_tpu", "ops", "kda_chunk.py")
    spec = importlib.util.spec_from_file_location("kda_chunk_parent", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def inputs(b, t, h, d):
    """q, k, v ``[b, t, h·d]`` bfloat16 as SiLU leaves them (q and k
    raw), the log-decays g ``[b, t, h·d]`` float32 (each step's in (-5,
    0)), β ``[b, t, h]`` float32 and a cotangent of the output."""
    import jax
    import jax.numpy as jnp

    keys = jax.random.split(jax.random.PRNGKey(b * t + h), 6)
    act = lambda key, shift=0.0: jax.nn.silu(  # noqa: E731
        jax.random.normal(key, (b, t, h * d)) + shift).astype(jnp.bfloat16)
    q, k, v = act(keys[0]), act(keys[1], 0.4), act(keys[2])
    g = -5.0 * jax.random.uniform(keys[3], (b, t, h * d)) ** 0.25
    beta = jax.random.uniform(keys[4], (b, t, h))
    do = jax.random.normal(keys[5], (b, t, h * d), jnp.bfloat16)
    return q, k, v, g, beta, do


def reference(kda, q, k, v, g, beta, do, shape):
    """(o, dq, dk, dv, dg, dβ) of the XLA form in float32, every product
    at ``HIGHEST``, q and k normalised as ``kda_chunk`` does, Γ from
    ``chunk_cumsum``."""
    import jax
    import jax.numpy as jnp

    b, t, h, d = shape
    split = lambda x: x.astype(jnp.float32).reshape(b, t, h, d)  # noqa

    def form(q_, k_, v_, g_, beta_):
        return kda.kda_chunked(*kda.normed_qk(q_, k_), v_,
                               kda.chunk_cumsum(g_, CHUNK), beta_,
                               CHUNK).reshape(b, t, h * d)

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
                    help="a checkout whose kernels take q and k normalised")
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

    def norms(q_, k_):
        """XLA's norms, as the parent's layer wrote them ahead of the
        kernels."""
        return tuple(flat(x) for x in kda.normed_qk(split(q_), split(k_)))

    norms_fwd = jax.jit(norms)
    _, norms_vjp = jax.vjp(norms, q, k)
    norms_t = jax.jit(lambda dq, dk: norms_vjp((dq, dk)))

    def change(n):
        """This tree's kernels on raw q and k: (forward, its arguments,
        backward, its arguments, the outputs)."""
        fwd = jax.jit(lambda *a: kda.kda_chunk_fwd(
            *a, CHUNK, interpret, keep_states=True, heads=n))
        bwd = jax.jit(lambda *a: kda.kda_chunk_bwd(
            *a, CHUNK, interpret, heads=n))
        o, states = fwd(q, k, v, g, beta)
        bwd_args = (q, k, v, g, beta, states, do)
        return (fwd, (q, k, v, g, beta), bwd, bwd_args,
                [o] + list(bwd(*bwd_args)))

    def parents(mod, n):
        """XLA's norms and the parent's kernels, one call each way; the
        backward reads the normalised q and k the forward made."""
        def fwd(q_, k_, v_, g_, beta_):
            qn, kn = norms(q_, k_)
            return mod.kda_chunk_fwd(qn, kn, v_, g_, beta_, CHUNK, interpret,
                                     keep_states=True, heads=n), qn, kn

        def bwd(q_, k_, qn, kn, v_, g_, beta_, states, do_):
            dqn, dkn, dv, dg, dbeta = mod.kda_chunk_bwd(
                qn, kn, v_, g_, beta_, states, do_, CHUNK, interpret,
                heads=n)
            _, vjp = jax.vjp(norms, q_, k_)
            return vjp((dqn, dkn)) + (dv, dg, dbeta)

        fwd, bwd = jax.jit(fwd), jax.jit(bwd)
        (o, states), qn, kn = fwd(q, k, v, g, beta)
        bwd_args = (q, k, qn, kn, v, g, beta, states, do)
        return (fwd, (q, k, v, g, beta), bwd, bwd_args,
                [o] + list(bwd(*bwd_args)))

    sides = [("change", change)]
    if args.parent:
        mod = load_parent(os.path.abspath(args.parent))
        sides.insert(0, ("parent", lambda n: parents(mod, n)))

    rows, firsts, fault = [], {}, False
    names = ("o", "dq", "dk", "dv", "dg", "dbeta")
    for n in groups:
        for side, build in sides:
            fwd, fwd_args, bwd, bwd_args, outs = build(n)
            outs = [np.asarray(x, np.float32) for x in outs]
            err = {name: float(np.abs(x - r).max() / np.abs(r).max())
                   for name, x, r in zip(names, outs, ref)}
            row = {"side": side, "heads": n,
                   "fwd_ms": timed(fwd, fwd_args, args.reps, args.rounds),
                   "bwd_ms": timed(bwd, bwd_args, args.reps, args.rounds),
                   "err_vs_float32": err}
            first = firsts.setdefault(side, outs)
            row["same_as_first_heads"] = all(
                np.array_equal(x, y) for x, y in zip(outs, first))
            rows.append(row)
    qn, kn = norms_fwd(q, k)
    norms_ms = timed(norms_fwd, (q, k), args.reps, args.rounds)
    norms_t_ms = timed(norms_t, (qn, kn), args.reps, args.rounds)
    yardstick = {}
    for row in rows:
        base = yardstick.setdefault(row["heads"], row["err_vs_float32"])
        bad = [name for name, e in row["err_vs_float32"].items()
               if not e <= 1.25 * base[name] + 1e-3]
        bad += [] if row["same_as_first_heads"] else ["heads"]
        fault |= bool(bad)
        row["step_ms"] = FWD_CALLS * row["fwd_ms"] + BWD_CALLS * row["bwd_ms"]
        worst = max(row["err_vs_float32"].items(), key=lambda kv: kv[1])
        print(f"{row['side']:8s} heads {row['heads']:2d}  fwd "
              f"{row['fwd_ms']:8.3f} ms  bwd {row['bwd_ms']:8.3f} ms  "
              f"a step {row['step_ms']:8.2f} ms  worst vs float32 "
              f"{worst[0]} {worst[1]:.3g}"
              f"{'  [fault: ' + ','.join(bad) + ']' if bad else ''}",
              flush=True)
    print(f"XLA's norms alone: {norms_ms:.3f} ms, their transpose "
          f"{norms_t_ms:.3f} ms a call", flush=True)
    report = {"device": dev.device_kind, "class": [b, t, h, d],
              "chunk": CHUNK, "rehearse": args.rehearse, "rows": rows,
              "xla_norms_ms": norms_ms, "xla_norms_t_ms": norms_t_ms,
              "parent": args.parent}
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(report, f, indent=1)
    print(json.dumps(report))
    return 2 if fault else 0


if __name__ == "__main__":
    sys.exit(main())
