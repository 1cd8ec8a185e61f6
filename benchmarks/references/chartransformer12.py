"""Plain reference for the ``chartransformer12`` configuration.

The 12-layer character transformer of Al-Rfou et al. 2018
(arXiv:1808.04444), "T12": hidden 512, filter 2048, context 512, byte
vocabulary, causal self-attention. Straightforward ``jax.numpy`` in
float32 with every product at ``Precision.HIGHEST``; attention is the
full masked softmax, no kernel and no cache. It imports nothing of the
program and is handed nothing the program made.

Departures from the paper, shared with the configuration as the program
builds it (``configs/chartransformer12.json`` lists them): pre-norm
blocks, sinusoidal positions added once after the input projection (the
paper learns per-layer position embeddings), GELU (tanh form), no
auxiliary losses, the loss at every position, a dense input projection
of the one-hot bytes, no biases on the query, key and value products.

Inputs and labels are one-hot ``[batch, vocab, time]``; leaves are
named ``<layer index>/<param>`` as the configuration's file states.
"""

import math

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST


def _block_names(model):
    return [str(2 + i) for i in range(model["n_layers"])]


def init(cfg, key):
    """Weights from ``key`` in float32: Glorot-normal matrices (the
    head at the configuration's ``init.head_scale`` of that), zero
    biases, unit layer-norm gains. One traceable function."""
    m = cfg["model"]
    d, ff, v = m["d_model"], m["ffn_hidden"], m["vocab"]
    count = [0]

    def glorot(shape):
        count[0] += 1
        std = math.sqrt(2.0 / (shape[0] + shape[1]))
        return jax.random.normal(
            jax.random.fold_in(key, count[0]), shape, jnp.float32
        ) * std

    zeros = lambda n: jnp.zeros((n,), jnp.float32)  # noqa: E731
    ones = lambda n: jnp.ones((n,), jnp.float32)  # noqa: E731
    params = {"0": {"W": glorot((v, d)), "b": zeros(d)}, "1": {}}
    for name in _block_names(m):
        params[name] = {
            "Wq": glorot((d, d)), "Wk": glorot((d, d)),
            "Wv": glorot((d, d)), "Wo": glorot((d, d)), "bo": zeros(d),
            "ln1_gamma": ones(d), "ln1_beta": zeros(d),
            "ln2_gamma": ones(d), "ln2_beta": zeros(d),
            "w_ff1": glorot((d, ff)), "b_ff1": zeros(ff),
            "w_ff2": glorot((ff, d)), "b_ff2": zeros(d),
        }
    params[str(2 + m["n_layers"])] = {
        "W": glorot((d, v)) * cfg["init"]["head_scale"], "b": zeros(v)}
    state = {name: {} for name in params}
    return params, state


def _layernorm(h, gamma, beta, eps):
    mean = jnp.mean(h, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(h - mean), axis=-1, keepdims=True)
    return (h - mean) / jnp.sqrt(var + eps) * gamma + beta


def _positions(t, d, max_wavelength):
    i = jnp.arange(d)
    freq = max_wavelength ** (-((i // 2) * 2 / d).astype(jnp.float32))
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * freq[None, :]
    return jnp.where((i % 2 == 0)[None, :], jnp.sin(angle), jnp.cos(angle))


def _exact(a):
    return a


_exact.grad = _exact


def loss(cfg, params, state, x, y, q=_exact):
    """Mean cross-entropy over every position of one batch. ``q``
    rounds the operands of every product, and ``q.grad`` the cotangent
    that comes back to its result: both the identity for the reference,
    a lower precision for the control."""
    m = cfg["model"]
    heads, eps = m["n_heads"], m["layer_norm_eps"]
    b, _, t = x.shape
    d = m["d_model"]
    hd = d // heads

    def mm(a, w):
        return q.grad(jnp.matmul(q(a), q(w), precision=HIGHEST))

    h = mm(jnp.transpose(x, (0, 2, 1)), params["0"]["W"]) + params["0"]["b"]
    h = h + _positions(t, d, m["max_wavelength"])[None]
    causal = jnp.tril(jnp.ones((t, t), bool))

    for name in _block_names(m):

        @jax.checkpoint
        def block(p, h):
            a = _layernorm(h, p["ln1_gamma"], p["ln1_beta"], eps)

            def split(w):
                return jnp.transpose(
                    mm(a, w).reshape(b, t, heads, hd), (0, 2, 1, 3))

            qh, kh, vh = split(p["Wq"]), split(p["Wk"]), split(p["Wv"])
            s = q.grad(jnp.einsum("bhqd,bhkd->bhqk", q(qh), q(kh),
                                  precision=HIGHEST)) / math.sqrt(hd)
            s = jnp.where(causal[None, None], s, -jnp.inf)
            w = jax.nn.softmax(s, axis=-1)
            o = q.grad(jnp.einsum("bhqk,bhkd->bhqd", q(w), q(vh),
                                  precision=HIGHEST))
            o = jnp.transpose(o, (0, 2, 1, 3)).reshape(b, t, d)
            h = h + mm(o, p["Wo"]) + p["bo"]
            f = _layernorm(h, p["ln2_gamma"], p["ln2_beta"], eps)
            f = jax.nn.gelu(mm(f, p["w_ff1"]) + p["b_ff1"],
                            approximate=True)
            return h + mm(f, p["w_ff2"]) + p["b_ff2"]

        h = block(params[name], h)
    last = params[str(2 + m["n_layers"])]
    logits = mm(h, last["W"]) + last["b"]                 # [b, t, v]
    labels = jnp.transpose(y, (0, 2, 1))
    rows = -jnp.sum(labels * jax.nn.log_softmax(logits, axis=-1), axis=-1)
    return jnp.mean(rows), state
