"""Drives a configuration's plain reference through its first optimizer
steps and takes the readings that ``compare.py`` holds the program to.

The updater is the configuration's, written out plainly here (the
formulas the configuration's file names), in float32. ``compute`` picks
the precision of the reference's convolutions and products:

    float32   the reference: operands as they are, Precision.HIGHEST
    float8    the control: operands rounded to 4 exponent and 3 mantissa
              bits under a per-tensor scale, forward and backward (the
              cotangent of every product is rounded too before the two
              backward products use it), one step below the bfloat16
              the configurations state (what a later PR would be
              tempted by)
    bfloat16  operands rounded to bfloat16: not a control, a way to see
              how much of a gap is rounding

``fault`` breaks the reference in the program's place, for the readings
a limit's upper end is set from: ``half_batch`` (half of the rows left
out, the mean taken over the rest).

The learning rate of each step is the configuration's: one number, or
its ``schedule`` ({iteration: rate}, the newest entry at or before the
iteration, iterations counted from 0), which both cells use to hold the
rate at nought from the fourth step of the first chunk to its end, so
that the state after three steps can be read from a program that hands
its state out only every ``scan_chunk`` steps.
"""

import json

import jax
import jax.numpy as jnp


def _round_to(exponent_bits, mantissa_bits, top=None):
    """Round to a narrower float by ``lax.reduce_precision``, an
    operation of its own that the compiler keeps (a cast down and up
    again it may remove, and on the TPU does). ``top`` scales a tensor so
    that its largest magnitude sits at the format's largest value, as a
    low-precision matrix unit's per-tensor scale would. The result
    rounds an operand on the way forward (straight through for the
    gradient); its ``grad`` leaves a product's result as it is and
    rounds the cotangent that comes back to it, so that the backward
    products take rounded operands as well."""
    def rounded(a32):
        if top is None:
            return jax.lax.reduce_precision(
                a32, exponent_bits, mantissa_bits)
        scale = top / jnp.maximum(jnp.max(jnp.abs(a32)), 1e-30)
        return jax.lax.reduce_precision(
            a32 * scale, exponent_bits, mantissa_bits) / scale

    def q(a):
        a32 = a.astype(jnp.float32)
        return a32 + jax.lax.stop_gradient(rounded(a32) - a32)

    @jax.custom_vjp
    def grad(y):
        return y

    grad.defvjp(lambda y: (y, None),
                lambda _, g: (rounded(g.astype(jnp.float32)),))
    q.grad = grad
    return q


def _exact(a):
    return a


_exact.grad = _exact

QUANTIZERS = {
    "float32": _exact,
    "bfloat16": _round_to(8, 7),
    # 4 exponent and 3 mantissa bits (E4M3), largest value 240
    "float8": _round_to(4, 3, top=240.0),
}


def learning_rates(upd, n_steps):
    """The rate of each of the first ``n_steps`` optimizer steps."""
    schedule = {int(k): float(v)
                for k, v in (upd.get("schedule") or {}).items()}
    out = []
    for it in range(n_steps):
        at = [k for k in schedule if k <= it]
        out.append(schedule[max(at)] if at else upd["learning_rate"])
    return out


def apply_updater(upd, params, grads, moments, t, lr):
    """One step of the configuration's updater on every leaf at the
    rate ``lr``. Returns (params, moments); ``moments`` is a tuple of
    trees."""
    kind = upd["name"].upper()
    tm = jax.tree.map
    if kind == "NESTEROVS":
        mu = upd["momentum"]
        (v,) = moments
        v_new = tm(lambda v, g: mu * v - lr * g, v, grads)
        new = tm(lambda p, v, vn: p - (mu * v - (1.0 + mu) * vn),
                 params, v, v_new)
        return new, (v_new,)
    if kind == "ADAM":
        b1, b2, eps = upd["beta1"], upd["beta2"], upd["epsilon"]
        m, v = moments
        m_new = tm(lambda m, g: b1 * m + (1.0 - b1) * g, m, grads)
        v_new = tm(lambda v, g: b2 * v + (1.0 - b2) * g * g, v, grads)
        new = tm(
            lambda p, m, v: p - lr * (m / (1.0 - b1 ** t))
            / (jnp.sqrt(v / (1.0 - b2 ** t)) + eps),
            params, m_new, v_new,
        )
        return new, (m_new, v_new)
    raise ValueError(f"reference has no updater {upd['name']!r}")


def n_moments(upd):
    return {"NESTEROVS": 1, "ADAM": 2}[upd["name"].upper()]


def leaf_norms(tree):
    """{"layer/param": l2 norm} of every array leaf, in float32."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {
        "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                 for k in path): jnp.sqrt(
                     jnp.sum(jnp.square(leaf.astype(jnp.float32))))
        for path, leaf in flat
    }


@jax.jit
def _norms_of(delta_from, delta_to, moment):
    delta = jax.tree.map(lambda a, b: b.astype(jnp.float32)
                         - a.astype(jnp.float32), delta_from, delta_to)
    return leaf_norms(delta), leaf_norms(moment)


def take_readings(losses, params0, params, first_moment, grad1=None):
    """Host-side readings: per-step losses, per-leaf norm of the
    parameters' change and of the updater's first moment (and, from the
    reference, of its first gradient), and a host copy of the first
    moment itself, ``{"layer/param": array}``."""
    held = lambda tree: {k: v for k, v in tree.items() if v}  # noqa: E731
    delta, moment = _norms_of(held(params0), held(params),
                              held(first_moment))
    flat = jax.tree_util.tree_flatten_with_path(
        jax.device_get(held(first_moment)))[0]
    out = {
        "losses": [float(v) for v in losses],
        "delta": {k: float(v) for k, v in delta.items()},
        "moment": {k: float(v) for k, v in moment.items()},
        "moment_arrays": {"/".join(str(k.key) for k in path): leaf
                          for path, leaf in flat},
    }
    if grad1 is not None:
        out["grad1"] = {k: float(v) for k, v in grad1.items()}
    return out


def run_reference(ref, cfg, key, batches, n_steps, compute="float32",
                  fault=None):
    """The reference's first ``n_steps`` optimizer steps from ``key``
    on ``batches`` (host DataSets, in order), one jitted step at a time
    so that the chip holds one step's activations."""
    step, init = _programs(ref, cfg, compute, fault)
    upd = cfg["updater"]
    params, state = init(key)
    params0, _ = init(key)
    moments = tuple(jax.tree.map(jnp.zeros_like, params)
                    for _ in range(n_moments(upd)))
    losses, grad1 = [], None
    for i, lr in enumerate(learning_rates(upd, n_steps)):
        ds = batches[i % len(batches)]
        params, moments, state, loss, gnorm = step(
            params, moments, state, jnp.asarray(ds.features),
            jnp.asarray(ds.labels), jnp.float32(i + 1), jnp.float32(lr),
        )
        losses.append(loss)
        if i == 0:
            grad1 = gnorm
    return take_readings(losses, params0, params, moments[0], grad1)


_PROGRAMS = {}


def _programs(ref, cfg, compute, fault):
    """The jitted step and weight maker of one (reference, sizes,
    precision, fault), built once in a process."""
    cache_key = (ref.__name__, json.dumps(cfg, sort_keys=True), compute,
                 fault)
    if cache_key in _PROGRAMS:
        return _PROGRAMS[cache_key]
    q = QUANTIZERS[compute]
    upd = cfg["updater"]

    def step(params, moments, state, x, y, t, lr):
        if fault == "half_batch":
            x, y = x[: x.shape[0] // 2], y[: y.shape[0] // 2]
        (loss, new_state), grads = jax.value_and_grad(
            lambda p: ref.loss(cfg, p, state, x, y, q), has_aux=True
        )(params)
        new_params, new_moments = apply_updater(
            upd, params, grads, moments, t, lr)
        return new_params, new_moments, new_state, loss, leaf_norms(grads)

    _PROGRAMS[cache_key] = (
        jax.jit(step, donate_argnums=(0, 1, 2)),
        jax.jit(lambda k: ref.init(cfg, k)),
    )
    return _PROGRAMS[cache_key]
