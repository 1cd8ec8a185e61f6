#!/usr/bin/env python3
"""The gated short convolution alone on the chip: three forms of
``C ⊙ conv(B ⊙ x̃)`` over ``[B | C | x̃]`` ``[b, t, 3d]``.

    python3 scripts/short_conv_ab.py [--out FILE]       (on a TPU host)

Per class ``[b, t, 3d]`` (bfloat16, 3 taps; ``lfm2moe.fit_4k``'s ``[2,
4096, 3·2048]`` first) it times one call, forward alone and forward with
its backward (``jax.vjp`` with a random cotangent: output, d[B | C |
x̃], dw), of these sides in this one process, turn about:

- ``X`` — ``ops.short_conv.short_conv_xla`` under autodiff: the path of
  every shape the kernels do not take;
- ``P`` — ``ops.short_conv.short_conv`` as it routes on a TPU: the
  forward and the backward kernel;
- ``B`` — ``X``'s forward beside the backward kernel (a ``custom_vjp``
  here): the form to fall back to if the forward kernel cost the step
  more than it saved. On a v5e the pair won alone and in the cell
  (PERF.md §6), so ``B`` lives here only.

Every side is compared on the chip with the same computation in float32
(``rel_err``: out, d[B | C | x̃], dw), so a kernel that is fast and wrong
shows here and not first in a cell's ``correct`` (a ``P`` or ``B`` past
2% is printed as ``[fault]`` and the exit code is 2). Times are
host-clock, ``--reps`` calls then ``block_until_ready``, per call, the
median of ``--rounds``. Exits non-zero where JAX finds no TPU
(``--rehearse``: tiny shapes on any device, kernels interpreted; nothing
it prints is a device number). The table goes to ``--out`` too, where
one is given.
"""

import argparse
import json
import os
import statistics
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

TAPS = 3
# (b, t, d); the first is lfm2moe.fit_4k's class
CLASSES = [(2, 4096, 2048), (8, 1024, 2048)]
REHEARSAL_CLASSES = [(2, 64, 128)]


def sides():
    """{side: fn(bcx, w) -> y}."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.ops import short_conv as sc
    from deeplearning4j_tpu.ops.dispatch import pallas_interpret

    @jax.custom_vjp
    def backward_kernel(bcx, w):
        return sc.short_conv_xla(bcx, w)

    backward_kernel.defvjp(
        lambda bcx, w: (sc.short_conv_xla(bcx, w), (bcx, w)),
        lambda res, dy: sc.short_conv_bwd(*res, dy, pallas_interpret()))

    return {"X": sc.short_conv_xla, "P": sc.short_conv,
            "B": lambda bcx, w: backward_kernel(bcx, w.astype(jnp.float32))}


def build(fn, shape, dtype, grad):
    """(compiled call, its arguments)."""
    import jax

    b, t, d = shape
    keys = jax.random.split(jax.random.PRNGKey(t + d), 3)
    bcx = jax.random.normal(keys[0], (b, t, 3 * d), dtype)
    w = jax.random.uniform(keys[1], (TAPS, d), dtype, -0.6, 0.6)
    g = jax.random.normal(keys[2], (b, t, d), dtype)

    def call(bcx_, w_, g_):
        out, vjp = jax.vjp(fn, bcx_, w_)
        return (out,) + vjp(g_)

    if grad:
        return jax.jit(call).lower(bcx, w, g).compile(), (bcx, w, g)
    return jax.jit(fn).lower(bcx, w).compile(), (bcx, w)


def errors(built):
    """{label: relative error of each output against the XLA form in
    float32}."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.ops.short_conv import short_conv_xla

    _, args = next(iter(built.values()))   # every side has the same
    f32 = [a.astype(jnp.float32) for a in args]

    def exact(bcx, w, *g):
        out, vjp = jax.vjp(short_conv_xla, bcx, w)
        return (out,) + (vjp(g[0]) if g else ())

    want = jax.jit(exact)(*f32)
    got = {}
    for label, (compiled, a) in built.items():
        outs = compiled(*a)
        outs = outs if isinstance(outs, tuple) else (outs,)
        got[label] = [
            float(jnp.linalg.norm(o.astype(jnp.float32) - w_)
                  / jnp.linalg.norm(w_)) for o, w_ in zip(outs, want)]
    return got


def time_ms(compiled, args, reps):
    import jax

    t0 = time.perf_counter()
    for _ in range(reps):
        out = compiled(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e3


def measure(built, reps, rounds):
    """{label: median ms} of the compiled calls in ``built``, each
    round in the other order."""
    for compiled, args in built.values():   # warm every side
        time_ms(compiled, args, 2)
    got = {label: [] for label in built}
    for r in range(rounds):
        order = list(built.items())
        for label, (compiled, args) in (order if r % 2 == 0
                                        else order[::-1]):
            got[label].append(time_ms(compiled, args, reps))
    return {label: {"ms": statistics.median(v), "range": [min(v), max(v)]}
            for label, v in got.items()}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--rounds", type=int, default=5)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    if args.rehearse:   # off the chip the op takes the kernels only forced
        os.environ["DL4J_TPU_PALLAS"] = "1"

    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        print(f"no TPU here ({dev.platform}): a time from this device "
              "is not a chip number", file=sys.stderr)
        return 1
    classes = CLASSES
    if args.rehearse:
        classes, args.reps, args.rounds = REHEARSAL_CLASSES, 1, 1
    dtype = jnp.bfloat16
    print("[device] " + json.dumps({
        "platform": dev.platform, "kind": dev.device_kind,
        "reps": args.reps, "rounds": args.rounds, "taps": TAPS,
        "dtype": jnp.dtype(dtype).name}), flush=True)
    rows, faults = [], []
    for shape in classes:
        for grad in (False, True):
            built = {label: build(fn, shape, dtype, grad)
                     for label, fn in sides().items()}
            row = {"class": list(shape), "taps": TAPS,
                   "pass": "fwd+bwd" if grad else "fwd",
                   "ms": measure(built, args.reps, args.rounds),
                   "rel_err": errors(built)}
            rows.append(row)
            print("[class] " + json.dumps(row), flush=True)
            # bfloat16 roundings cost under 1% of an output's norm on
            # every side; a kernel past 2% computes something else
            faults += [(shape, row["pass"], label, err)
                       for label, err in row["rel_err"].items()
                       if label in "PB" and max(err) > 0.02]
    if args.out and not args.rehearse:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"device": dev.device_kind, "rows": rows}, f, indent=1)
    for fault in faults:
        print("[fault] " + json.dumps(fault), flush=True)
    return 2 if faults else 0


if __name__ == "__main__":
    sys.exit(main())
