"""Loss functions (reference: nd4j ``ILossFunction`` impls used through
``LossFunctions.LossFunction`` enum names on output-layer configs).

Semantics mirror the reference: a loss consumes the output layer's
*pre-activation* plus the layer's activation name, so numerically fused
stable paths are used for softmax+MCXENT and sigmoid+XENT (the reference
gets stability from dedicated native ops; we get it from log-space
formulations that XLA fuses).

Shape convention:
- 2-d labels/preout: ``[batch, nOut]`` — one score row per example.
- 3-d (RNN): ``[batch, nOut, time]`` — one score row per (example,
  timestep), with an optional ``[batch, time]`` mask; masked timesteps
  contribute zero score and zero gradient (reference: mask-aware losses
  exercised by ``GradientCheckTestsMasking``).

Gradients are obtained by ``jax.grad`` through these scores — there is
no hand-written ``computeGradient`` twin to keep in sync (the reference
maintains both and gradient-checks them against each other; here they
are one function by construction).
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn import activations

_EPS = 1e-8

# Each row fn: (labels2d, preout2d, activation_name) -> per-row score [rows]


def _activate(preout: jax.Array, activation: str) -> jax.Array:
    if activation == "softmax":
        return jax.nn.softmax(preout, axis=-1)
    return activations.get(activation)(preout)


def _mse(labels, preout, act):
    d = _activate(preout, act) - labels
    return jnp.sum(d * d, axis=-1) / labels.shape[-1]


def _l2(labels, preout, act):
    d = _activate(preout, act) - labels
    return jnp.sum(d * d, axis=-1)


def _l1(labels, preout, act):
    return jnp.sum(jnp.abs(_activate(preout, act) - labels), axis=-1)


def _mae(labels, preout, act):
    return _l1(labels, preout, act) / labels.shape[-1]


def _mape(labels, preout, act):
    out = _activate(preout, act)
    return 100.0 * jnp.sum(
        jnp.abs((labels - out) / (jnp.abs(labels) + _EPS)), axis=-1
    ) / labels.shape[-1]


def _msle(labels, preout, act):
    out = _activate(preout, act)
    d = jnp.log1p(jnp.maximum(out, -1 + _EPS)) - jnp.log1p(
        jnp.maximum(labels, -1 + _EPS)
    )
    return jnp.sum(d * d, axis=-1) / labels.shape[-1]


def _xent(labels, preout, act):
    """Binary cross-entropy; stable-from-logits when act == sigmoid."""
    if act == "sigmoid":
        # log(sigmoid(x)) = -softplus(-x); log(1-sigmoid(x)) = -softplus(x)
        return jnp.sum(
            labels * jax.nn.softplus(-preout)
            + (1.0 - labels) * jax.nn.softplus(preout),
            axis=-1,
        )
    out = jnp.clip(_activate(preout, act), _EPS, 1.0 - _EPS)
    return -jnp.sum(
        labels * jnp.log(out) + (1.0 - labels) * jnp.log(1.0 - out), axis=-1
    )


def _mcxent(labels, preout, act):
    """Multi-class cross-entropy; stable-from-logits when act == softmax."""
    if act == "softmax":
        return -jnp.sum(labels * jax.nn.log_softmax(preout, axis=-1), axis=-1)
    out = jnp.clip(_activate(preout, act), _EPS, 1.0)
    return -jnp.sum(labels * jnp.log(out), axis=-1)


def _kl(labels, preout, act):
    out = jnp.clip(_activate(preout, act), _EPS, 1.0)
    lab = jnp.clip(labels, _EPS, 1.0)
    return jnp.sum(labels * (jnp.log(lab) - jnp.log(out)), axis=-1)


def _cosine(labels, preout, act):
    out = _activate(preout, act)
    num = jnp.sum(labels * out, axis=-1)
    den = jnp.linalg.norm(labels, axis=-1) * jnp.linalg.norm(out, axis=-1)
    return -num / (den + _EPS)


def _hinge(labels, preout, act):
    # labels in {-1, +1}
    return jnp.sum(jnp.maximum(0.0, 1.0 - labels * _activate(preout, act)), axis=-1)


def _squared_hinge(labels, preout, act):
    h = jnp.maximum(0.0, 1.0 - labels * _activate(preout, act))
    return jnp.sum(h * h, axis=-1)


def _poisson(labels, preout, act):
    out = jnp.maximum(_activate(preout, act), _EPS)
    return jnp.sum(out - labels * jnp.log(out), axis=-1)


def _nll(labels, preout, act):
    return _mcxent(labels, preout, act)


def _sparse_mcxent(labels, preout, act):
    """Multi-class cross-entropy against integer labels ``[rows, 1]``
    (class ids in any numeric type): the log-softmax gathered at the
    label, what MCXENT gives for the one-hot of the same id."""
    ids = labels[:, :1].astype(jnp.int32)
    if act == "softmax":
        logp = jax.nn.log_softmax(preout, axis=-1)
    else:
        logp = jnp.log(jnp.clip(_activate(preout, act), _EPS, 1.0))
    return -jnp.take_along_axis(logp, ids, axis=-1)[:, 0]


def sparse_mcxent_sum(h, w, labels, block_rows: int = 0):
    """Sum over rows of the softmax cross-entropy of ``h @ w``
    (``[rows, d] x [d, classes]``, float32 logits) against integer
    ``labels`` ``[rows]``. With ``block_rows`` dividing the rows into
    more than one block, the logits exist a block at a time, forward
    and backward (each block is recomputed for its gradient), and
    ``w``'s gradient adds up over the blocks in float32."""
    def block(hb, lb, wb):
        logits = jnp.dot(hb, wb.astype(hb.dtype),
                         preferred_element_type=jnp.float32)
        picked = jnp.take_along_axis(logits, lb[:, None], axis=-1)[:, 0]
        return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - picked)

    rows = h.shape[0]
    labels = labels.astype(jnp.int32)
    if not block_rows or rows <= block_rows or rows % block_rows:
        return block(h, labels, w)
    n = rows // block_rows
    w32 = w.astype(jnp.float32)

    def step(total, per):
        return total + jax.checkpoint(block)(*per, w32), None

    total, _ = jax.lax.scan(
        step, jnp.zeros((), jnp.float32),
        (h.reshape(n, block_rows, -1), labels.reshape(n, block_rows)))
    return total


_REGISTRY: dict[str, Callable] = {
    "MSE": _mse,
    "SQUARED_LOSS": _l2,
    "L2": _l2,
    "L1": _l1,
    "MEAN_ABSOLUTE_ERROR": _mae,
    "MEAN_ABSOLUTE_PERCENTAGE_ERROR": _mape,
    "MEAN_SQUARED_LOGARITHMIC_ERROR": _msle,
    "XENT": _xent,
    "MCXENT": _mcxent,
    "NEGATIVELOGLIKELIHOOD": _nll,
    "SPARSE_MCXENT": _sparse_mcxent,
    "RECONSTRUCTION_CROSSENTROPY": _xent,
    "KL_DIVERGENCE": _kl,
    "COSINE_PROXIMITY": _cosine,
    "HINGE": _hinge,
    "SQUARED_HINGE": _squared_hinge,
    "POISSON": _poisson,
}


def names() -> list[str]:
    return sorted(_REGISTRY)


def register(name: str, row_fn: Callable) -> None:
    """Register a custom loss (reference analog: custom ILossFunction
    with JSON subtype registration)."""
    _REGISTRY[name.upper()] = row_fn


def _to_rows(a: jax.Array) -> jax.Array:
    """[b, n] -> [b, n]; [b, n, t] -> [b*t, n] (reference reshapes RNN
    output to 2-d before loss, ``RnnOutputLayer``)."""
    if a.ndim == 2:
        return a
    if a.ndim == 3:
        return jnp.transpose(a, (0, 2, 1)).reshape(-1, a.shape[1])
    raise ValueError(f"Loss expects 2-d or 3-d arrays, got shape {a.shape}")


def score(
    loss: str,
    labels: jax.Array,
    preout: jax.Array,
    activation: str,
    mask: jax.Array | None = None,
    average: bool = True,
) -> jax.Array:
    """Scalar loss score (reference ``ILossFunction.computeScore``).

    ``average=True`` divides by the number of unmasked rows (examples,
    or example-timesteps for RNN), matching the reference's
    minibatch-averaged score.
    """
    rows = per_row_scores(loss, labels, preout, activation, mask)
    total = jnp.sum(rows)
    if not average:
        return total
    if mask is not None:
        count = jnp.maximum(jnp.sum(mask), 1.0)
    else:
        count = rows.shape[0]
    return total / count


def per_row_scores(
    loss: str,
    labels: jax.Array,
    preout: jax.Array,
    activation: str,
    mask: jax.Array | None = None,
) -> jax.Array:
    """Per-row (example / example-timestep) scores, mask applied."""
    try:
        fn = _REGISTRY[loss.upper()]
    except KeyError:
        raise ValueError(f"Unknown loss '{loss}'. Known: {names()}") from None
    rows = fn(_to_rows(labels), _to_rows(preout), activation)
    if mask is not None:
        rows = rows * _to_row_mask(mask, labels)
    return rows


def _to_row_mask(mask: jax.Array, labels: jax.Array) -> jax.Array:
    """[b] (2-d case) or [b, t] -> flat row mask aligned with _to_rows."""
    if labels.ndim == 2:
        return mask.reshape(-1)
    return mask.reshape(-1)  # [b, t] row-major matches transpose(0,2,1) flatten
