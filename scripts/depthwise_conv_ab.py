#!/usr/bin/env python3
"""The Mamba-2 causal depthwise convolution alone on the chip: five
forms of convolution + bias + SiLU + the cast to the compute type.

    chiprun -- python3 scripts/depthwise_conv_ab.py [--layout row_major]

Per class ``[b, t, c]`` (bfloat16, 4 taps; ``granite40hmicro.fit_4k``'s
``[2, 4096, 4352]`` first) it times one call, forward alone and forward
with its backward (``jax.vjp`` with a random cotangent: output, dx, dW,
db), of these sides in this one process, turn about:

- ``X`` — the shifted float32 products of
  ``nn.layers.state_space.causal_depthwise_conv`` with ``jax.nn.silu``
  and the cast behind them, under autodiff: what ran up to PR 37, and
  the path of every shape the kernel does not take;
- ``G`` — ``jax.lax.conv_general_dilated`` with ``feature_group_count
  = c`` on the bfloat16 operand and float32 accumulation, bias and SiLU
  behind it: the same sums in XLA's own code. JAX cannot transpose it
  (the float32 cotangent meets the bfloat16 operand: a ``TypeError``,
  listed under ``failed``), so forward only;
- ``G32`` — the same call on the operand and taps widened to float32 at
  the highest precision, which autodiff can transpose;
- ``P`` — ``state_space.causal_conv_silu`` as it routes on a TPU: XLA's
  forward (``X``'s) and the Pallas backward of ``ops/depthwise_conv.py``;
- ``F`` — a Pallas forward beside that backward, kept here: alone it is
  twice as fast as ``X``'s, in ``granite40hmicro.fit_4k`` the pair let
  XLA put the MLPs' weight-gradient products at the end of the step and
  lost more there than it saved (PERF.md §6, PR 38).

The arrays are handed over as the cell has them, ``[b, c, t]`` with time
minor (the layout XLA gives a state-space mixer's inside; every side
works on the ``[b, t, c]`` view and the transposes fold away);
``--layout row_major`` hands over ``[b, t, c]`` as stored, where XLA
puts a transposing copy on each side of the kernels.

Every side is compared on the chip with the same computation in float32
(``rel_err``: out, dx, dW, db), so a kernel that is fast and wrong shows
here and not first in a cell's ``correct`` (a ``P`` or ``F`` past 2% is
printed as ``[fault]`` and the exit code is 2). Times are host-clock,
``--reps`` calls then ``block_until_ready``, per call, the median of
``--rounds``. Exits non-zero where JAX finds no TPU (``--rehearse``:
tiny shapes on any device, kernels interpreted; nothing it prints is a
device number). The table goes to ``chiprun_out/depthwise_conv_ab.json``
too.
"""

import argparse
import functools
import json
import os
import statistics
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

TAPS = 4
# (b, t, c); the first is granite40hmicro.fit_4k's class
CLASSES = [(2, 4096, 4352), (8, 1024, 4352), (2, 4096, 2304)]
REHEARSAL_CLASSES = [(2, 300, 32)]


def shifted_products(x, w, bias):
    """Side X's convolution: ``K`` shifted float32 products of the
    zero-padded input, summed, plus the bias."""
    import jax.numpy as jnp

    k, t = w.shape[0], x.shape[1]
    xp = jnp.pad(x.astype(jnp.float32), ((0, 0), (k - 1, 0), (0, 0)))
    w = w.astype(jnp.float32)
    return sum(xp[:, i:i + t] * w[i] for i in range(k)) \
        + bias.astype(jnp.float32)


def forward_kernel(x, wb, blocks, interpret):
    """Side F's forward on ``x`` ``[b, c, t]``: the backward kernel's
    tiles, halo and rolled reads, one output."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from deeplearning4j_tpu.ops import depthwise_conv as dc
    from deeplearning4j_tpu.ops import tiling

    b, c, t = x.shape
    taps = wb.shape[1] - 1
    bt, bc = blocks

    def kernel(prev_ref, x_ref, wb_ref, o_ref, xs_ref):
        first = pl.program_id(2) == 0

        def some_rows(i, carry):
            at = pl.ds(pl.multiple_of(i * dc.ROWS, dc.ROWS), dc.ROWS)
            prev = prev_ref[0, at].astype(jnp.float32)
            xs_ref[:, 0:dc.HALO] = jnp.where(first, jnp.zeros_like(prev),
                                             prev)
            xs_ref[:, dc.HALO:] = x_ref[0, at].astype(jnp.float32)
            wb_ = wb_ref[at]
            for l0, n in dc.lane_chunks(bt):
                acc = wb_[:, taps:taps + 1]
                for k in range(taps):
                    acc = acc + wb_[:, k:k + 1] * dc.back(
                        xs_ref, dc.HALO + l0, n, taps - 1 - k)
                o_ref[0, at, l0:l0 + n] = dc.silu_and_slope(acc)[0].astype(
                    o_ref.dtype)
            return carry

        jax.lax.fori_loop(0, bc // dc.ROWS, some_rows, None)

    tile, before, _, per_channel = dc.tile_specs(t, bc, bt, taps)
    return pl.pallas_call(
        kernel, grid=(pl.cdiv(c, bc), b, pl.cdiv(t, bt)),
        in_specs=[before, tile, per_channel], out_specs=tile,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        scratch_shapes=[pltpu.VMEM((dc.ROWS, dc.HALO + bt), jnp.float32)],
        name=tiling.kernel_name("depthwise_conv_fwd", x.dtype, b=b, t=t,
                                c=c, k=taps),
        interpret=interpret)(x, x, wb)


def handed_over(time_minor):
    """The move between the ``[b, t, c]`` view every side works on and
    the array as it is handed over (its own inverse)."""
    import jax.numpy as jnp

    if time_minor:
        return functools.partial(jnp.swapaxes, axis1=1, axis2=2)
    return lambda a: a


def sides(interpret):
    """{side: fn(x, w, bias) -> y} on ``x`` ``[b, t, c]``."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn.layers.state_space import causal_conv_silu
    from deeplearning4j_tpu.ops import depthwise_conv as dc
    from deeplearning4j_tpu.ops import tiling

    def x_side(x, w, bias):
        return jax.nn.silu(shifted_products(x, w, bias)).astype(x.dtype)

    def grouped(x, w, bias, **kw):
        pre = jax.lax.conv_general_dilated(
            x, w[:, None, :], (1,), [(w.shape[0] - 1, 0)],
            dimension_numbers=("NWC", "WIO", "NWC"),
            feature_group_count=x.shape[-1], **kw)
        return jax.nn.silu(pre + bias.astype(jnp.float32))

    def g_side(x, w, bias):
        return grouped(x, w, bias, preferred_element_type=jnp.float32
                       ).astype(x.dtype)

    def g32_side(x, w, bias):
        return grouped(x.astype(jnp.float32), w.astype(jnp.float32), bias,
                       precision=jax.lax.Precision.HIGHEST).astype(x.dtype)

    @jax.custom_vjp
    def both_kernels(x, w, bias):
        blocks = tiling.pick_depthwise_conv_blocks(
            x.shape[1], x.shape[2], x.dtype.itemsize, w.shape[0])
        wb = jnp.concatenate([w.T, bias[:, None]], axis=1)
        return jnp.swapaxes(forward_kernel(
            jnp.swapaxes(x, 1, 2), wb, blocks, interpret), 1, 2)

    both_kernels.defvjp(
        lambda x, w, bias: (both_kernels(x, w, bias), (x, w, bias)),
        lambda res, dy: dc.conv_silu_bwd(*res, dy))

    def f_side(x, w, bias):
        return both_kernels(x, w.astype(jnp.float32),
                            bias.astype(jnp.float32))

    return {"X": x_side, "G": g_side, "G32": g32_side,
            "P": causal_conv_silu, "F": f_side}


def build(fn, shape, dtype, grad, time_minor):
    """(compiled call, its arguments)."""
    import jax

    b, t, c = shape
    keys = jax.random.split(jax.random.PRNGKey(t + c), 4)
    x = jax.random.normal(keys[0], shape, dtype)
    w = jax.random.uniform(keys[1], (TAPS, c), dtype, -0.5, 0.5)
    bias = (0.1 * jax.random.normal(keys[2], (c,))).astype(dtype)
    g = jax.random.normal(keys[3], shape, dtype)
    view = handed_over(time_minor)
    x, g = view(x), view(g)

    def forward(x_, w_, b_):
        return view(fn(view(x_), w_, b_))

    def call(x_, w_, b_, g_):
        out, vjp = jax.vjp(forward, x_, w_, b_)
        return (out,) + vjp(g_)

    if grad:
        return (jax.jit(call).lower(x, w, bias, g).compile(),
                (x, w, bias, g))
    return jax.jit(forward).lower(x, w, bias).compile(), (x, w, bias)


def errors(built, time_minor):
    """{label: relative error of each output (out, and with a backward
    dx, dW, db) against the same computation in float32}."""
    import jax
    import jax.numpy as jnp

    _, args = next(iter(built.values()))   # every side has the same
    f32 = [a.astype(jnp.float32) for a in args]
    view = handed_over(time_minor)

    def exact(x, w, bias, *g):
        out, vjp = jax.vjp(
            lambda x_, w_, b_: view(jax.nn.silu(
                shifted_products(view(x_), w_, b_))), x, w, bias)
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
    ap.add_argument("--layout", choices=("time_minor", "row_major"),
                    default="time_minor")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", default="chiprun_out/depthwise_conv_ab.json")
    args = ap.parse_args(argv)
    if args.rehearse:   # off the chip the op takes the kernel only forced
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
    time_minor = args.layout == "time_minor"
    dtype = jnp.bfloat16
    print("[device] " + json.dumps({
        "platform": dev.platform, "kind": dev.device_kind,
        "reps": args.reps, "rounds": args.rounds, "taps": TAPS,
        "layout": args.layout, "dtype": jnp.dtype(dtype).name}), flush=True)
    rows, faults = [], []
    for shape in classes:
        for grad in (False, True):
            built, failed = {}, {}
            for label, fn in sides(dev.platform != "tpu").items():
                try:
                    built[label] = build(fn, shape, dtype, grad, time_minor)
                except Exception as e:  # a side the compiler refuses is
                    if label in "PF":   # reported; the kernels must build
                        raise
                    failed[label] = f"{type(e).__name__}: {e}"[:300]
            row = {"class": list(shape), "taps": TAPS,
                   "layout": args.layout,
                   "pass": "fwd+bwd" if grad else "fwd",
                   "ms": measure(built, args.reps, args.rounds),
                   "rel_err": errors(built, time_minor), "failed": failed}
            rows.append(row)
            print("[class] " + json.dumps(row), flush=True)
            # bfloat16 roundings cost 0.2% of an output's norm on every
            # side; a kernel past 2% computes something else
            faults += [(shape, row["pass"], label, err)
                       for label, err in row["rel_err"].items()
                       if label in "PF" and max(err) > 0.02]
    if not args.rehearse:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"device": dev.device_kind, "layout": args.layout,
                       "rows": rows}, f, indent=1)
    for fault in faults:
        print("[fault] " + json.dumps(fault), flush=True)
    return 2 if faults else 0


if __name__ == "__main__":
    sys.exit(main())
