"""Plain reference for the ``resnet50_imagenet`` configuration.

ResNet-50 v1 as He et al. 2015 (arXiv:1512.03385) Table 1, 50-layer
column, lay it out: a 7x7/2 stem, 3x3/2 max pool, bottleneck stages of
(3, 4, 6, 3) blocks at widths 64..512 (x4 out), global average pool, a
1000-way softmax. Straightforward ``jax.numpy`` in float32 with every
convolution and product at ``Precision.HIGHEST``; no kernels, no scan,
no mixed precision. It imports nothing of the program and is handed
nothing the program made: weights come from ``init`` below, data from
the harness.

Departures from the paper, shared with the configuration as the program
builds it (``configs/resnet50_imagenet.json`` lists them): stride 2 sits
on the 3x3 convolution of a stage's first block (v1.5 placement),
convolutions carry a bias, weights are Glorot-normal over the
receptive-field fans, the last batch norm of every bottleneck starts at
a small gain (the configuration's ``init``), and the loss is the mean
cross-entropy over the batch with no weight decay.

Leaves are named and laid out as the configuration's file states
(``<layer>/<param>``; convolution kernels OIHW, the head ``[in, out]``),
so the harness compares leaf against leaf by name.
"""

import math

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST


def conv_table(model):
    """Every convolution in forward order:
    ``(name, c_in, c_out, kernel, stride, pad, in_hw, out_hw)``."""
    rows = []
    hw = model["height"]
    base = model["base_width"]

    def add(name, cin, cout, k, s, p, size):
        out = (size + 2 * p - k) // s + 1
        rows.append((name, cin, cout, k, s, p, size, out))
        return out

    hw = add("stem", model["channels"], base, 7, 2, 3, hw)
    hw = (hw + 2 - 3) // 2 + 1  # 3x3/2 max pool, pad 1
    cin = base
    for stage, depth in enumerate(model["depths"]):
        width = base * 2 ** stage
        for block in range(depth):
            stride = 2 if (block == 0 and stage > 0) else 1
            name = f"s{stage}b{block}"
            if block == 0:
                add(f"{name}_proj", cin, 4 * width, 1, stride, 0, hw)
            add(f"{name}_c1", cin, width, 1, 1, 0, hw)
            mid = add(f"{name}_c2", width, width, 3, stride, 1, hw)
            add(f"{name}_c3", width, 4 * width, 1, 1, 0, mid)
            hw, cin = mid, 4 * width
    return rows


def _bn_names(model):
    names = ["stem_bn"]
    for stage, depth in enumerate(model["depths"]):
        for block in range(depth):
            n = f"s{stage}b{block}"
            names += [f"{n}_bn1", f"{n}_bn2", f"{n}_bn3"]
            if block == 0:
                names.append(f"{n}_projbn")
    return names


def init(cfg, key):
    """Weights and batch-norm running statistics from ``key``, in
    float32. One traceable function: the harness jits it."""
    model = cfg["model"]
    params, state = {}, {}
    for i, (name, cin, cout, k, *_rest) in enumerate(conv_table(model)):
        std = math.sqrt(2.0 / (cin * k * k + cout * k * k))
        w = jax.random.normal(
            jax.random.fold_in(key, i), (cout, cin, k, k), jnp.float32
        ) * std
        params[name] = {"W": w, "b": jnp.zeros((cout,), jnp.float32)}
    channels = {n: c for n, _, c, *_ in conv_table(model)}
    for name in _bn_names(model):
        conv = (name.replace("projbn", "proj").replace("bn", "c")
                if name != "stem_bn" else "stem")
        c = channels[conv]
        gain = (cfg["init"]["residual_last_bn_gain"]
                if name.endswith("_bn3") else 1.0)
        params[name] = {"gamma": jnp.full((c,), gain, jnp.float32),
                        "beta": jnp.zeros((c,), jnp.float32)}
        state[name] = {"mean": jnp.zeros((c,), jnp.float32),
                       "var": jnp.ones((c,), jnp.float32)}
    feat = 4 * model["base_width"] * 2 ** (len(model["depths"]) - 1)
    n_cls = model["n_classes"]
    params["out"] = {
        "W": jax.random.normal(
            jax.random.fold_in(key, 10_000), (feat, n_cls), jnp.float32
        ) * math.sqrt(2.0 / (feat + n_cls)),
        "b": jnp.zeros((n_cls,), jnp.float32),
    }
    return params, state


def _exact(a):
    return a


_exact.grad = _exact


def _conv(p, x, stride, pad, q):
    y = q.grad(lax.conv_general_dilated(
        q(x), q(p["W"]), (stride, stride), ((pad, pad), (pad, pad)),
        dimension_numbers=("NCHW", "OIHW", "NCHW"), precision=HIGHEST,
    ))
    return y + p["b"].reshape(1, -1, 1, 1)


def _bn(cfg, p, st, x):
    """Training-mode batch norm: biased batch variance, running
    statistics moved by ``1 - decay``."""
    decay, eps = cfg["batch_norm"]["decay"], cfg["batch_norm"]["eps"]
    mean = jnp.mean(x, axis=(0, 2, 3))
    var = jnp.mean(jnp.square(x - mean.reshape(1, -1, 1, 1)),
                   axis=(0, 2, 3))
    y = (x - mean.reshape(1, -1, 1, 1)) * lax.rsqrt(
        var + eps).reshape(1, -1, 1, 1)
    y = y * p["gamma"].reshape(1, -1, 1, 1) + p["beta"].reshape(1, -1, 1, 1)
    new = {"mean": decay * st["mean"] + (1 - decay) * mean,
           "var": decay * st["var"] + (1 - decay) * var}
    return y, new


def loss(cfg, params, state, x, y, q=_exact):
    """Mean cross-entropy of one batch and the new batch-norm state.
    ``q`` rounds the operands of every convolution and product, and
    ``q.grad`` the cotangent that comes back to its result: both the
    identity for the reference, a lower precision for the control."""
    model = cfg["model"]
    new_state = {}

    def bn(name, h, relu):
        h, new_state[name] = _bn(cfg, params[name], state[name], h)
        return jax.nn.relu(h) if relu else h

    h = bn("stem_bn", _conv(params["stem"], x, 2, 3, q), True)
    h = lax.reduce_window(
        h, -jnp.inf, lax.max, (1, 1, 3, 3), (1, 1, 2, 2),
        ((0, 0), (0, 0), (1, 1), (1, 1)),
    )
    for stage, depth in enumerate(model["depths"]):
        for block in range(depth):
            n = f"s{stage}b{block}"
            stride = 2 if (block == 0 and stage > 0) else 1
            names = [f"{n}_c1", f"{n}_bn1", f"{n}_c2", f"{n}_bn2",
                     f"{n}_c3", f"{n}_bn3"]
            if block == 0:
                names += [f"{n}_proj", f"{n}_projbn"]

            # one block at a time is kept for the backward pass, the
            # rest recomputed: float32 at the timed batch would not
            # fit the chip otherwise (same arithmetic either way)
            @jax.checkpoint
            def run(ps, sts, h, n=n, stride=stride, first=(block == 0)):
                out = {}

                def nb(name, a, relu):
                    a, out[name] = _bn(cfg, ps[name], sts[name], a)
                    return jax.nn.relu(a) if relu else a

                a = nb(f"{n}_bn1", _conv(ps[f"{n}_c1"], h, 1, 0, q), True)
                a = nb(f"{n}_bn2",
                       _conv(ps[f"{n}_c2"], a, stride, 1, q), True)
                a = nb(f"{n}_bn3", _conv(ps[f"{n}_c3"], a, 1, 0, q), False)
                short = h
                if first:
                    short = nb(f"{n}_projbn",
                               _conv(ps[f"{n}_proj"], h, stride, 0, q),
                               False)
                return jax.nn.relu(a + short), out

            h, out = run(
                {k: params[k] for k in names},
                {k: state[k] for k in names if k in state}, h,
            )
            new_state.update(out)
    h = jnp.mean(h, axis=(2, 3))
    logits = q.grad(jnp.dot(q(h), q(params["out"]["W"]),
                            precision=HIGHEST)) + params["out"]["b"]
    rows = -jnp.sum(y * jax.nn.log_softmax(logits, axis=-1), axis=-1)
    return jnp.mean(rows), new_state
