#!/usr/bin/env python3
"""Per shape class: ``conv_block`` against XLA's convolution on the chip.

    chiprun -- python3 scripts/conv_class_ab.py [--batch 128]

Walks ``zoo.resnet50``'s training forward once (shapes only), groups
the convolutions the chip's compiler accepts (``conv_block_ok``) by
``conv_shape_class``, and times one ``ConvolutionLayer.apply`` and its
backward (``jax.vjp`` with a random cotangent: output, dL/dx, dL/dW,
dL/db) per class, the kernel (``DL4J_TPU_PALLAS=1``) turn about with
XLA (``=0``) in this one process. A class is a candidate for
``ops.conv_block._FASTER_THAN_XLA`` only if the kernel wins here by
more than the rounds' spread; the whole-cell run decides (PERF.md §6,
PR 29). ``--infer`` times the forward alone with a BN affine and ReLU
in the epilogue, the class ``maybe_fused_conv_bn`` would send.

Times are host-clock, ``--reps`` calls then ``block_until_ready``, per
call. Exits non-zero where JAX finds no TPU (``--rehearse``: batch 2 on
any device, the kernel interpreted; nothing it prints is a device
number). The table goes to ``chiprun_out/conv_class_ab.json`` too.
"""

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def resnet50_conv_calls(batch):
    """[(layer, x shape, dtype name)] of every ``ConvolutionLayer.apply``
    in one training forward of ``zoo.resnet50`` in bfloat16."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn.graph import ComputationGraph
    from deeplearning4j_tpu.nn.layers import ConvolutionLayer
    from deeplearning4j_tpu.zoo import resnet50

    net = ComputationGraph(resnet50(compute_dtype="bfloat16")).init()
    calls = []
    plain_apply = ConvolutionLayer.apply

    def recording_apply(self, params, x, state, **kw):
        calls.append((self, tuple(x.shape), x.dtype.name))
        return plain_apply(self, params, x, state, **kw)

    ConvolutionLayer.apply = recording_apply
    try:
        jax.eval_shape(
            lambda p, s, x: net._forward_values(
                p, s, [x], train=True, rng=jax.random.PRNGKey(0))[0],
            net.params, net.state,
            jax.ShapeDtypeStruct((batch, 3, 224, 224), jnp.float32))
    finally:
        ConvolutionLayer.apply = plain_apply
    return calls


def eligible_classes(calls, bn_fused):
    """{shape class: (layer, x shape, dtype name, count)} of the calls
    the chip's compiler accepts, in forward order."""
    from deeplearning4j_tpu.nn.layers.convolution import _pair
    from deeplearning4j_tpu.ops.conv_block import (
        conv_block_ok,
        conv_shape_class,
    )

    classes = {}
    for layer, xs, dt in calls:
        ws = (layer.n_out, layer.n_in) + _pair(layer.kernel_size)
        if not conv_block_ok(xs, ws, _pair(layer.stride),
                             _pair(layer.padding), dt):
            continue
        key = conv_shape_class(
            xs, ws, dt, bn_fused or layer.activation != "identity")
        _, _, _, n = classes.get(key, (None, None, None, 0))
        classes[key] = (layer, xs, dt, n + 1)
    return classes


def build(layer, xs, dt, mode, infer):
    """The compiled call for one side: ``mode`` "1" the kernel, "0" XLA.
    Routing is decided while tracing, so the variable is set, the cached
    read dropped, and the function compiled here."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn.layers import BatchNormalization
    from deeplearning4j_tpu.nn.layers.convolution import (
        _pair,
        maybe_fused_conv_bn,
    )
    from deeplearning4j_tpu.ops import dispatch

    os.environ["DL4J_TPU_PALLAS"] = mode
    dispatch.reset_for_tests()
    dtype = jnp.dtype(dt)
    ws = (layer.n_out, layer.n_in) + _pair(layer.kernel_size)
    keys = jax.random.split(jax.random.PRNGKey(len(str(xs))), 5)
    # every array crosses the jit boundary as [rows, everything else],
    # and is reshaped inside: a 4-d argument would pin the chip's tiled
    # layout of its last two dims (7 wide padded to 128 lanes) on both
    # sides, which no convolution inside a step program has to read
    flat = lambda a: a.reshape(a.shape[0], -1)
    x = flat(jax.random.normal(keys[0], xs, dtype))
    w = flat((jax.random.normal(keys[1], ws, jnp.float32)
              * (ws[1] * ws[2] * ws[3]) ** -0.5).astype(dtype))
    b = jnp.zeros((ws[0],), dtype)
    as_params = lambda w_, b_: {"W": w_.reshape(ws), "b": b_}
    if infer:
        bn = BatchNormalization(n_out=ws[0], activation="relu")
        bn_params = {"gamma": jnp.ones((ws[0],)), "beta": jnp.zeros((ws[0],))}
        bn_state = {"mean": jax.random.normal(keys[2], (ws[0],)) * 0.1,
                    "var": jnp.ones((ws[0],))}

        def call(w_, b_, a):
            p, a = as_params(w_, b_), a.reshape(xs)
            y = maybe_fused_conv_bn(layer, bn, p, bn_params, bn_state, a)
            if y is None:
                y, _ = layer.apply(p, a, {})
                y, _ = bn.apply(bn_params, y, bn_state)
            return flat(y)

        args = (w, b, x)
    else:
        def forward(w_, b_, a):
            return flat(layer.apply(as_params(w_, b_), a.reshape(xs), {},
                                    train=True)[0])

        def call(w_, b_, a, g):
            y, vjp = jax.vjp(forward, w_, b_, a)
            return (y,) + vjp(g)

        y_shape = jax.eval_shape(forward, w, b, x).shape
        args = (w, b, x, jax.random.normal(keys[3], y_shape, dtype))
    compiled = jax.jit(call).lower(*args).compile()
    has_kernel = "tpu_custom_call" in compiled.as_text()
    return compiled, args, has_kernel


def time_ms(compiled, args, reps):
    import jax

    t0 = time.perf_counter()
    for _ in range(reps):
        out = compiled(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e3


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--reps", type=int, default=30)
    ap.add_argument("--rounds", type=int, default=5)
    ap.add_argument("--infer", action="store_true")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", default="chiprun_out/conv_class_ab.json")
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        print(f"no TPU here ({dev.platform}): a time from this device "
              "is not a chip number", file=sys.stderr)
        return 1
    if args.rehearse:
        args.batch, args.reps, args.rounds = 2, 1, 1
    print("[device] " + json.dumps({
        "platform": dev.platform, "kind": dev.device_kind,
        "batch": args.batch, "reps": args.reps, "rounds": args.rounds,
        "pass": "forward, BN affine + ReLU fused" if args.infer
        else "forward + backward"}), flush=True)
    calls = resnet50_conv_calls(args.batch)
    classes = eligible_classes(calls, args.infer)
    print(f"[model] {len(calls)} convolutions, "
          f"{sum(c[3] for c in classes.values())} accepted by the "
          f"compiler in {len(classes)} classes", flush=True)
    rows = []
    for key, (layer, xs, dt, count) in classes.items():
        t0 = time.perf_counter()
        kernel, k_args, has_kernel = build(layer, xs, dt, "1", args.infer)
        xla, x_args, xla_has_kernel = build(layer, xs, dt, "0", args.infer)
        compile_s = time.perf_counter() - t0
        if (not has_kernel and not args.rehearse) or xla_has_kernel:
            raise AssertionError(
                f"{key}: the kernel side holds a custom call: {has_kernel},"
                f" the XLA side: {xla_has_kernel}")
        for side in ((kernel, k_args), (xla, x_args)):   # warm both
            time_ms(*side, 2)
        k_ms, x_ms = [], []
        for r in range(args.rounds):
            order = [(k_ms, kernel, k_args), (x_ms, xla, x_args)]
            for sink, fn, a in (order if r % 2 == 0 else order[::-1]):
                sink.append(time_ms(fn, a, args.reps))
        row = {
            "class": list(key), "count": count,
            "kernel_ms": statistics.median(k_ms),
            "xla_ms": statistics.median(x_ms),
            "kernel_ms_range": [min(k_ms), max(k_ms)],
            "xla_ms_range": [min(x_ms), max(x_ms)],
            "kernel_over_xla": statistics.median(k_ms)
            / statistics.median(x_ms),
            # the kernel wins only if its slowest round beats XLA's fastest
            "kernel_wins": max(k_ms) < min(x_ms),
            "compile_s": round(compile_s, 1),
        }
        rows.append(row)
        print("[class] " + json.dumps(row), flush=True)
    total = {
        "kernel_ms_per_step": sum(r["kernel_ms"] * r["count"] for r in rows),
        "xla_ms_per_step": sum(r["xla_ms"] * r["count"] for r in rows),
        "classes_kernel_wins": [r["class"] for r in rows
                                if r["kernel_wins"]],
    }
    print("[total] " + json.dumps(total), flush=True)
    if not args.rehearse:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"device": dev.device_kind, "batch": args.batch,
                       "infer": args.infer, "rows": rows, "total": total},
                      f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
