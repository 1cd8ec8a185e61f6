"""The gated short convolution, the sequence mixer of LFM2's convolution
blocks (Liquid AI's LFM2 family; ``Lfm2MoeShortConv`` in
``transformers``) — net-new vs the reference, beside ``decoder.py``'s
attention layers and in their layout (``[batch, time, features]``):

    [B | C | x̃] = x·Win                  Win [d, 3d]
    y = (C ⊙ conv(B ⊙ x̃))·Wout            conv: K causal depthwise taps, no bias, no activation

It gates twice, before and after the convolution; the convolution and
both gates are ``ops.short_conv``'s kernel pair where the shape admits
it and dispatch is on, its XLA form otherwise.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.layers.base import register_layer
from deeplearning4j_tpu.nn.layers.decoder import _TokenMajor
from deeplearning4j_tpu.ops.short_conv import short_conv


@register_layer
@dataclass(frozen=True)
class GatedShortConv(_TokenMajor):
    """``[B | C | x̃] = x·Win``; ``(C ⊙ conv(B ⊙ x̃))·Wout`` with a
    causal depthwise convolution of ``conv_width`` taps (``conv_W``
    ``[conv_width, n_in]``), no bias anywhere. A fresh layer's taps are
    uniform on ±1/√``conv_width`` (a depthwise ``Conv1d``'s default),
    its projections what the configuration's initializer gives."""

    n_in: int = 0
    conv_width: int = 3
    activation: str = "identity"

    def regularizable_params(self) -> tuple:
        return ("Win", "Wout")

    def init_params(self, key, dtype=jnp.float32) -> dict:
        ki, kc, ko = jax.random.split(key, 3)
        d, k = self.n_in, self.conv_width
        return {
            "Win": self._weight(ki, (d, 3 * d), dtype),
            "conv_W": jax.random.uniform(
                kc, (k, d), jnp.float32, -k ** -0.5, k ** -0.5).astype(dtype),
            "Wout": self._weight(ko, (d, d), dtype),
        }

    def apply(self, params, x, state, *, train=False, rng=None, mask=None):
        with jax.named_scope("shortconv.in"):
            bcx = x @ params["Win"]
        with jax.named_scope("shortconv.conv"):
            y = short_conv(bcx, params["conv_W"])
        with jax.named_scope("shortconv.out"):
            return y @ params["Wout"], state
