"""Kimi Delta Attention (Kimi Linear, arXiv:2510.26692) — a linear-
attention sequence mixer beside ``state_space.py``'s Mamba-2 one and in
the same layout (``[batch, time, features]``): the gated delta rule
with one decay per key channel, whose state is a ``head_dim`` square
per head updated by a rank-one correction.

Per head, with no bias anywhere:

    q, k, v = silu(causal_conv(x·Wq)), silu(causal_conv(x·Wk)), silu(causal_conv(x·Wv))
    q, k    = each head's features over their L2 norm; q also times 1/√head_dim
    β_t     = sigmoid(x_t·Wb)                            one a head
    g_t     = lower · sigmoid(exp(A_log) · (x_t·Wf + dt_bias))   one a key channel
    S_t     = (I - β_t k_t k_tᵀ)·Diag(exp(g_t))·S_{t-1} + β_t k_t v_tᵀ
    o_t     = S_tᵀ q_t
    y_t     = (rms(o_t) ⊙ sigmoid(x_t·Wo_gate))·Wo       rms over a head's features

The gate is the bounded ("safe") form: ``g`` lies in ``(lower, 0)``,
which the chunk form needs (``ops/kda_chunk.py``). The three
projections and their convolutions are one product and one convolution
of ``3·heads·head_dim`` channels (``Wqkv``, ``conv_W``); the recurrence
is ``ops.kda_chunk.kda_chunk``, ``chunk`` positions at a time, which
takes q and k raw and normalises them itself.

A fresh layer starts as Mamba-2's reference implementation starts its
decays: ``A_log = log a`` with ``a`` uniform on [1, 16] per head,
``softplus(dt_bias)`` log-uniform on [0.001, 0.1] per channel, the
convolution uniform on ±1/√``conv_width``, unit gains.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.layers.base import register_layer
from deeplearning4j_tpu.nn.layers.decoder import _TokenMajor, rms_norm
from deeplearning4j_tpu.nn.layers.state_space import causal_conv_silu
from deeplearning4j_tpu.ops.kda_chunk import kda_chunk

_F32 = jnp.float32


@register_layer
@dataclass(frozen=True)
class KimiDeltaAttention(_TokenMajor):
    """The delta-rule mixer above: ``n_heads`` heads of ``head_dim``
    (keys and values alike), a causal convolution of ``conv_width`` taps
    without bias, decays bounded below by ``lower_bound``, RMS norms at
    ``eps``."""

    n_in: int = 0
    n_heads: int = 4
    head_dim: int = 128
    conv_width: int = 4
    chunk: int = 64
    lower_bound: float = -5.0
    eps: float = 1e-6
    activation: str = "identity"

    def regularizable_params(self) -> tuple:
        return ("Wqkv", "Wb", "Wf", "Wo_gate", "Wo")

    def init_params(self, key, dtype=jnp.float32) -> dict:
        ks = jax.random.split(key, 8)
        d, h, k = self.n_in, self.n_heads, self.conv_width
        w = h * self.head_dim
        dt0 = jnp.exp(jax.random.uniform(
            ks[6], (w,), _F32, math.log(1e-3), math.log(1e-1)))
        return {
            "Wqkv": self._weight(ks[0], (d, 3 * w), dtype),
            "conv_W": jax.random.uniform(
                ks[1], (k, 3 * w), _F32, -k ** -0.5, k ** -0.5).astype(dtype),
            "Wb": self._weight(ks[2], (d, h), dtype),
            "Wf": self._weight(ks[3], (d, w), dtype),
            "A_log": jnp.log(jax.random.uniform(
                ks[7], (h,), _F32, 1.0, 16.0)).astype(dtype),
            # softplus(dt_bias) = dt0
            "dt_bias": (dt0 + jnp.log(-jnp.expm1(-dt0))).astype(dtype),
            "Wo_gate": self._weight(ks[4], (d, w), dtype),
            "o_norm": jnp.ones((self.head_dim,), dtype),
            "Wo": self._weight(ks[5], (w, d), dtype),
        }

    def apply(self, params, x, state, *, train=False, rng=None, mask=None):
        b, t, _ = x.shape
        h, dk = self.n_heads, self.head_dim
        w = h * dk
        with jax.named_scope("kda.qkv"):
            qkv = x @ params["Wqkv"]
        with jax.named_scope("kda.conv"):
            qkv = causal_conv_silu(qkv, params["conv_W"])
        q, k, v = (qkv[..., i * w:(i + 1) * w].reshape(b, t, h, dk)
                   for i in range(3))
        with jax.named_scope("kda.gate"):
            beta = jax.nn.sigmoid((x @ params["Wb"]).astype(_F32))
            rate = jnp.repeat(jnp.exp(params["A_log"].astype(_F32)), dk)
            g = self.lower_bound * jax.nn.sigmoid(rate * (
                (x @ params["Wf"]).astype(_F32)
                + params["dt_bias"].astype(_F32)))
        with jax.named_scope("kda.chunk"):
            o = kda_chunk(q, k, v, g.reshape(b, t, h, dk), beta, self.chunk)
        with jax.named_scope("kda.out"):
            o = rms_norm(o, params["o_norm"], self.eps).astype(_F32)
            o = o.reshape(b, t, w) * jax.nn.sigmoid(
                (x @ params["Wo_gate"]).astype(_F32))
            return o.astype(x.dtype) @ params["Wo"], state
