#!/usr/bin/env python3
"""Attention alone on the chip: the program's kernels against XLA's
attention and against JAX's own Pallas flash attention.

    chiprun -- python3 scripts/attention_ab.py [--sweep]

Per shape class ``[b, h, t, d]`` (bfloat16; the benchmark cell's class
``[64, 8, 512, 64]`` causal first) it times one call, forward alone and
forward with its backward (``jax.vjp`` with a random cotangent: output,
dq, dk, dv), of three sides in this one process, turn about. Every
side takes q, k, v and gives its outputs as ``[b, t, h*d]``, what the
projections around an attention layer produce and consume:

- ``P`` — ``ops.flash_attention.mha`` as it routes on a TPU: the
  differentiable kernel path ``_flash_diff`` with the blocks ``mha``
  resolves (forward alone: its primal, the kernel with one output),
  which reads and writes ``[b, t, h*d]`` as it is;
- ``X`` — ``parallel.sequence.attention``, what ``DL4J_TPU_PALLAS=0``
  runs, under ``jax.vjp`` for the backward;
- ``J`` — ``jax.experimental.pallas.ops.tpu.flash_attention`` with its
  default block sizes: a yardstick only, nothing in the program calls
  it.

``X`` and ``J`` are head-major, so their moves to ``[b, h, t, d]`` and
back are inside the timed function: that is what a step pays for them.

``--sweep`` also times ``P`` at every ``(block_q, block_k)`` of
``tiling.attention_candidates``, which is how
``tiling.pick_attention_blocks`` was set (PERF.md §6, PR 32).
``--only B,H,T,D`` keeps the classes of that shape (repeatable).

``--tree DIR`` imports ``deeplearning4j_tpu`` from another checkout
(the parent commit unpacked beside this one), so both trees are timed
by one script in one call; a tree whose ``mha`` is head-major (before
PR 34) gets the moves inside the timed function like ``X`` and ``J``.

Beside the times each side's outputs are compared with attention in
float32 at the highest precision (``rel_err``: out, then dq, dk, dv),
so a kernel that is fast and wrong shows here and not first in a
cell's ``correct`` (a ``P`` past 5% is printed as ``[fault]`` and the
exit code is 2). Times are host-clock, ``--reps`` calls then ``block_until_ready``, per
call, the median of ``--rounds``. Exits non-zero where JAX finds no TPU
(``--rehearse``: tiny shapes on any device, kernels interpreted;
nothing it prints is a device number). The table goes to
``chiprun_out/attention_ab.json`` too.
"""

import argparse
import json
import os
import statistics
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# (b, h, t, d, causal); the first is chartransformer12.fit's class
CLASSES = [
    (64, 8, 512, 64, True),
    (8, 8, 1024, 64, True),
    (4, 8, 4096, 64, True),
    (64, 8, 512, 64, False),
    (64, 4, 512, 128, True),   # one head a program
    (2, 20, 4096, 256, True),  # glm47flash.fit_4k's latent attention
    (2, 32, 4096, 64, True),   # granite40hmicro.fit_4k's attention block
]
REHEARSAL_CLASSES = [(2, 2, 128, 64, True), (2, 2, 128, 64, False)]


def head_major(fn, shape):
    """``fn`` on ``[b, h, t, d]`` as a function of ``[b, t, h*d]``
    arrays: the moves a head-major attention costs its caller."""
    import jax.numpy as jnp

    b, h, t, d = shape

    def moved(*qkv):
        out = fn(*(jnp.transpose(a.reshape(b, t, h, d), (0, 2, 1, 3))
                   for a in qkv))
        return jnp.transpose(out, (0, 2, 1, 3)).reshape(b, t, h * d)

    return moved


def sides(shape, causal, interpret, blocks=None):
    """{side: fn(q, k, v) -> out} for one class, all on ``[b, t, h*d]``.
    ``blocks``: the ``(block_q, block_k)`` ``P`` runs with instead of
    the ones ``mha`` resolves."""
    import importlib
    import inspect

    from jax.experimental.pallas.ops.tpu import flash_attention as jfa

    from deeplearning4j_tpu.parallel.sequence import attention

    # ``ops`` re-exports the function under the module's name
    fa = importlib.import_module("deeplearning4j_tpu.ops.flash_attention")
    h, d = shape[1], shape[3]

    def p(q, k, v):
        if blocks is None:
            return fa.mha(q, k, v, h, causal=causal)
        return fa._flash_diff(q, k, v, h, causal, interpret, *blocks)

    def p_head_major(q, k, v):   # a tree from before PR 34
        if blocks is None:
            return fa.mha(q, k, v, causal=causal)
        return fa._flash_diff(q, k, v, causal, interpret, *blocks)

    if "n_heads" not in inspect.signature(fa.mha).parameters:
        p = head_major(p_head_major, shape)

    def x(q, k, v):
        return attention(q, k, v, causal=causal)

    def j(q, k, v):
        return jfa.flash_attention(q, k, v, causal=causal,
                                   sm_scale=d ** -0.5)

    return {"P": p, "X": head_major(x, shape), "J": head_major(j, shape)}


def build(fn, shape, dtype, grad):
    """(compiled call, its arguments)."""
    import jax

    b, h, t, d = shape
    keys = jax.random.split(jax.random.PRNGKey(t), 4)
    q, k, v, g = (jax.random.normal(key, (b, t, h * d), dtype)
                  for key in keys)

    def call(q_, k_, v_, g_):
        out, vjp = jax.vjp(fn, q_, k_, v_)
        return (out,) + vjp(g_)

    if grad:
        return jax.jit(call).lower(q, k, v, g).compile(), (q, k, v, g)
    return jax.jit(fn).lower(q, k, v).compile(), (q, k, v)


def errors(built, shape, causal):
    """{label: relative error of each output (out, and with a backward
    dq, dk, dv) against attention in float32 at the highest
    precision}: what a side's rounding, or a fault, costs."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.parallel.sequence import attention

    _, args = next(iter(built.values()))   # every side has the same
    f32 = [a.astype(jnp.float32) for a in args]
    reference = head_major(
        lambda *a: attention(*a, causal=causal), shape)

    def exact(q, k, v, *g):
        with jax.default_matmul_precision("highest"):
            out, vjp = jax.vjp(reference, q, k, v)
            return (out,) + (vjp(g[0]) if g else ())

    want = jax.jit(exact)(*f32)
    got = {}
    for label, (compiled, a) in built.items():
        outs = compiled(*a)
        outs = outs if isinstance(outs, tuple) else (outs,)
        got[label] = [
            float(jnp.linalg.norm(o.astype(jnp.float32) - w)
                  / jnp.linalg.norm(w)) for o, w in zip(outs, want)]
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
    ap.add_argument("--sweep", action="store_true")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--only", action="append", default=[],
                    metavar="B,H,T,D",
                    help="time only the classes of this shape")
    ap.add_argument("--tree", default=REPO,
                    help="the checkout deeplearning4j_tpu is imported "
                         "from")
    ap.add_argument("--out", default="chiprun_out/attention_ab.json")
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.abspath(args.tree))
    if args.rehearse:   # off the chip mha takes the kernels only forced
        os.environ["DL4J_TPU_PALLAS"] = "1"

    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.ops import tiling

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        print(f"no TPU here ({dev.platform}): a time from this device "
              "is not a chip number", file=sys.stderr)
        return 1
    classes = CLASSES
    if args.rehearse:
        classes, args.reps, args.rounds = REHEARSAL_CLASSES, 1, 1
    if args.only:
        only = {tuple(int(v) for v in o.split(",")) for o in args.only}
        classes = [c for c in classes if c[:4] in only]
        if not classes:
            print(f"no class of shape {args.only}", file=sys.stderr)
            return 1
    interpret = dev.platform != "tpu"
    dtype = jnp.bfloat16
    print("[device] " + json.dumps({
        "platform": dev.platform, "kind": dev.device_kind,
        "tree": os.path.abspath(args.tree), "reps": args.reps, "rounds": args.rounds,
        "dtype": jnp.dtype(dtype).name}), flush=True)
    rows, faults = [], []
    for b, h, t, d, causal in classes:
        shape = (b, h, t, d)
        configs = [None]
        if args.sweep:
            configs += tiling.attention_candidates(t, d, 2)
        for grad in (False, True):
            built, failed = {}, {}
            for cfg in configs:
                for label, fn in sides(shape, causal, interpret,
                                       cfg).items():
                    if cfg is not None:
                        if label != "P":
                            continue
                        label = "P" + "x".join(str(c) for c in cfg)
                    try:
                        built[label] = build(fn, shape, dtype, grad)
                    except Exception as e:  # a side the compiler refuses
                        if label == "P":    # is reported; P must build
                            raise
                        failed[label] = f"{type(e).__name__}: {e}"[:300]
            row = {"class": list(shape), "causal": causal,
                   "pass": "fwd+bwd" if grad else "fwd",
                   "ms": measure(built, args.reps, args.rounds),
                   "rel_err": errors(built, shape, causal),
                   "failed": failed}
            rows.append(row)
            print("[class] " + json.dumps(row), flush=True)
            # bfloat16 operands cost 0.2-0.6% of a gradient's norm on
            # every side; a kernel past 5% computes something else
            faults += [(shape, causal, row["pass"], label, err)
                       for label, err in row["rel_err"].items()
                       if label.startswith("P") and max(err) > 0.05]
    if not args.rehearse:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"device": dev.device_kind,
                       "tree": os.path.abspath(args.tree), "rows": rows},
                      f, indent=1)
    for fault in faults:
        print("[fault] " + json.dumps(fault), flush=True)
    return 2 if faults else 0


if __name__ == "__main__":
    sys.exit(main())
