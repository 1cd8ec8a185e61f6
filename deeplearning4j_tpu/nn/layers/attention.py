"""Attention + layer-norm layers — net-new capability vs the
reference (which predates attention; SURVEY.md §5 "long-context"
names TBPTT/masking as its only sequence tools), added because
long-context is first-class in this framework. Follows the layer
conventions of the recurrent stack: sequence tensors are
[batch, features, time] (DL4J layout), masks [batch, time].

Single-shard attention lowers to two MXU matmuls with the softmax
fused between; for sequences sharded over a ``seq`` mesh axis the same
layer computes via ring attention
(``deeplearning4j_tpu.parallel.sequence.ring_attention``) when given a
``seq_axis``/``seq_axis_size`` — blockwise online softmax with K/V
blocks rotating over ICI."""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.layers.base import (
    LayerSpec,
    register_layer,
)
from deeplearning4j_tpu.nn.weights import init_weights


@register_layer
@dataclass(frozen=True)
class MultiHeadSelfAttention(LayerSpec):
    """Multi-head self-attention over the time axis. ``causal`` masks
    future positions (decoder style); the feature mask argument masks
    padded timesteps (same convention as the recurrent layers)."""

    n_in: int = 0
    n_out: int = 0
    n_heads: int = 4
    causal: bool = False
    activation: str = "identity"
    # when set, q/k/v arrive time-sharded over this mesh axis and the
    # layer computes ring attention instead of local attention
    seq_axis: str = ""
    seq_axis_size: int = 0
    # max total timesteps for incremental decoding (the rnnTimeStep
    # analog): the KV cache is a fixed [b, h, kv_cache, hd] buffer so
    # streaming stays jit-static
    kv_cache: int = 1024

    def input_kind(self) -> str:
        return "recurrent"

    # -- streaming (rnn_time_step) contract -----------------------------

    def streams_state(self) -> bool:
        return True

    def can_stream(self) -> bool:
        # a non-causal layer needs future timesteps — cannot stream
        return self.causal

    def stream_state_keys(self) -> tuple:
        return ("k_cache", "v_cache", "pos")

    def stream_capacity(self):
        return self.kv_cache

    def init_stream_state(self, batch: int, dtype) -> dict:
        hd = self._head_dim()
        shape = (batch, self.n_heads, self.kv_cache, hd)
        return {
            "k_cache": jnp.zeros(shape, dtype),
            "v_cache": jnp.zeros(shape, dtype),
            "pos": jnp.zeros((), jnp.int32),
        }

    def with_input_type(self, it: InputType) -> "MultiHeadSelfAttention":
        changes = {}
        if self.n_in == 0:
            changes["n_in"] = it.size or it.flat_size()
        if self.n_out == 0:
            changes["n_out"] = it.size or it.flat_size()
        return dataclasses.replace(self, **changes) if changes else self

    def output_type(self, it: InputType) -> InputType:
        return InputType.recurrent(self.n_out, it.timeseries_length)

    def regularizable_params(self) -> tuple:
        return ("Wq", "Wk", "Wv", "Wo")

    def _head_dim(self) -> int:
        if self.n_in % self.n_heads != 0:
            raise ValueError(
                f"n_in={self.n_in} not divisible by "
                f"n_heads={self.n_heads}"
            )
        return self.n_in // self.n_heads

    def init_params(self, key, dtype=jnp.float32) -> dict:
        kq, kk, kv, ko = jax.random.split(key, 4)
        d = self.n_in
        mk = lambda k, shp: init_weights(  # noqa: E731
            k, shp, self.weight_init, fan_in=shp[0], fan_out=shp[1],
            distribution=self.dist, dtype=dtype,
        )
        return {
            "Wq": mk(kq, (d, d)),
            "Wk": mk(kk, (d, d)),
            "Wv": mk(kv, (d, d)),
            "Wo": mk(ko, (d, self.n_out)),
            "bo": jnp.full((self.n_out,), self.bias_init, dtype),
        }

    def apply(self, params, x, state, *, train=False, rng=None, mask=None):
        from deeplearning4j_tpu.ops import mha
        from deeplearning4j_tpu.parallel.sequence import (
            merge_heads,
            ring_attention,
            split_heads,
        )

        x = self.maybe_dropout(x, train=train, rng=rng)
        t = x.shape[2]
        h, hd = self.n_heads, self._head_dim()
        xt = jnp.transpose(x, (0, 2, 1))               # [b, t, f]
        # [b, t, h*hd], head i in columns [i*hd, (i+1)*hd): the layout
        # ``mha`` reads and writes, so nothing moves between these
        # products and the attention kernels
        q, k, v = (xt @ params[w] for w in ("Wq", "Wk", "Wv"))
        if "k_cache" in state:
            # incremental decode: append this chunk's K/V to the cache
            # and attend over the filled prefix (fixed cache shape ->
            # jit-static; reference analog: rnnTimeStep's stateMap).
            # The cache is head-major: this branch moves its arrays
            # there and back itself
            from jax import lax as _lax

            q, k, v = (split_heads(a, h) for a in (q, k, v))
            pos = state["pos"]
            kc = _lax.dynamic_update_slice(
                state["k_cache"], k.astype(state["k_cache"].dtype),
                (0, 0, pos, 0),
            )
            vc = _lax.dynamic_update_slice(
                state["v_cache"], v.astype(state["v_cache"].dtype),
                (0, 0, pos, 0),
            )
            scale = 1.0 / jnp.sqrt(jnp.asarray(hd, q.dtype))
            s = jnp.einsum("bhqd,bhkd->bhqk", q, kc) * scale
            key_idx = jnp.arange(self.kv_cache)[None, None, None, :]
            q_idx = (pos + jnp.arange(t))[None, None, :, None]
            s = jnp.where(key_idx <= q_idx, s, -1e9)
            p = jax.nn.softmax(s, axis=-1)
            o = jnp.einsum("bhqk,bhkd->bhqd", p, vc)
            new_state = {
                **state, "k_cache": kc, "v_cache": vc,
                "pos": pos + t,
            }
            y = merge_heads(o) @ params["Wo"] + params["bo"]
            y = self.activate_fn()(y)
            return jnp.transpose(y, (0, 2, 1)), new_state
        if self.seq_axis and self.seq_axis_size > 1:
            # the ring rotates head-major blocks: moved here, as above
            o = merge_heads(ring_attention(
                *(split_heads(a, h) for a in (q, k, v)),
                axis_name=self.seq_axis, axis_size=self.seq_axis_size,
                causal=self.causal, mask=mask,
            ))
        else:
            # mha dispatches to the Pallas flash kernels on TPU
            o = mha(q, k, v, h, causal=self.causal, mask=mask)
        y = o @ params["Wo"] + params["bo"]             # [b, t, n_out]
        if mask is not None:
            y = y * mask[:, :, None]
        y = self.activate_fn()(y)
        return jnp.transpose(y, (0, 2, 1)), state       # [b, n_out, t]


@register_layer
@dataclass(frozen=True)
class TransformerBlock(LayerSpec):
    """Pre-norm transformer block: LN -> multi-head self-attention ->
    residual, LN -> FFN (or Switch-MoE) -> residual. Net-new vs the
    reference, composing the attention/norm/MoE layers into the
    standard long-context building block. Sequence layout follows the
    recurrent stack: [batch, features, time], mask [batch, time].

    ``n_experts > 0`` swaps the dense FFN for a Switch
    mixture-of-experts (top-1, capacity-dropped tokens ride the
    residual)."""

    n_in: int = 0
    n_out: int = 0
    n_heads: int = 4
    ffn_hidden: int = 0   # 0 -> 4 * n_in
    causal: bool = True
    n_experts: int = 0    # 0 -> dense FFN; >0 -> Switch MoE
    capacity_factor: float = 1.25
    activation: str = "identity"
    seq_axis: str = ""
    seq_axis_size: int = 0
    kv_cache: int = 1024  # incremental-decode cache (see MHSA)

    def input_kind(self) -> str:
        return "recurrent"

    def with_input_type(self, it: InputType) -> "TransformerBlock":
        changes = {}
        if self.n_in == 0:
            changes["n_in"] = it.size or it.flat_size()
        width = changes.get("n_in", self.n_in)
        if self.n_out == 0:
            changes["n_out"] = width
        if (changes.get("n_out", self.n_out)) != width:
            from deeplearning4j_tpu.exceptions import (
                DL4JInvalidConfigException,
            )

            raise DL4JInvalidConfigException(
                "TransformerBlock is residual: n_out must equal n_in"
            )
        return dataclasses.replace(self, **changes) if changes else self

    def output_type(self, it: InputType) -> InputType:
        return InputType.recurrent(self.n_out, it.timeseries_length)

    def regularizable_params(self) -> tuple:
        return ("Wq", "Wk", "Wv", "Wo", "w_ff1", "w_ff2", "w1", "w2")

    def _attn(self) -> MultiHeadSelfAttention:
        return MultiHeadSelfAttention(
            n_in=self.n_in, n_out=self.n_in, n_heads=self.n_heads,
            causal=self.causal, seq_axis=self.seq_axis,
            seq_axis_size=self.seq_axis_size, kv_cache=self.kv_cache,
            weight_init=self.weight_init, dist=self.dist,
        )

    # -- streaming (rnn_time_step) contract: delegate to the attention
    # sublayer (LN/FFN are per-position and carry nothing)

    def streams_state(self) -> bool:
        return True

    def can_stream(self) -> bool:
        return self.causal

    def stream_state_keys(self) -> tuple:
        return ("k_cache", "v_cache", "pos")

    def stream_capacity(self):
        return self.kv_cache

    def init_stream_state(self, batch: int, dtype) -> dict:
        return self._attn().init_stream_state(batch, dtype)

    def _ln(self) -> "LayerNormalization":
        return LayerNormalization(n_out=self.n_in)

    def _moe(self):
        from deeplearning4j_tpu.nn.layers.moe import MixtureOfExperts

        return MixtureOfExperts(
            n_in=self.n_in, n_out=self.n_in,
            n_experts=self.n_experts,
            hidden_size=self.ffn_hidden or 4 * self.n_in,
            capacity_factor=self.capacity_factor,
            activation="identity",
        )

    def init_params(self, key, dtype=jnp.float32) -> dict:
        k_attn, k_ff1, k_ff2, k_moe = jax.random.split(key, 4)
        d = self.n_in
        h = self.ffn_hidden or 4 * d
        p = {}
        p.update(self._attn().init_params(k_attn, dtype))
        p["ln1_gamma"] = jnp.ones((d,), dtype)
        p["ln1_beta"] = jnp.zeros((d,), dtype)
        p["ln2_gamma"] = jnp.ones((d,), dtype)
        p["ln2_beta"] = jnp.zeros((d,), dtype)
        if self.n_experts > 0:
            p.update(self._moe().init_params(k_moe, dtype))
        else:
            p["w_ff1"] = init_weights(
                k_ff1, (d, h), self.weight_init, fan_in=d, fan_out=h,
                distribution=self.dist, dtype=dtype,
            )
            p["b_ff1"] = jnp.zeros((h,), dtype)
            p["w_ff2"] = init_weights(
                k_ff2, (h, d), self.weight_init, fan_in=h, fan_out=d,
                distribution=self.dist, dtype=dtype,
            )
            p["b_ff2"] = jnp.zeros((d,), dtype)
        return p

    def _layernorm(self, x, gamma, beta, eps=1e-5):
        mean = jnp.mean(x, axis=1, keepdims=True)
        var = jnp.var(x, axis=1, keepdims=True)
        return (x - mean) / jnp.sqrt(var + eps) * gamma[:, None] \
            + beta[:, None]

    def apply(self, params, x, state, *, train=False, rng=None,
              mask=None):
        x = self.maybe_dropout(x, train=train, rng=rng)
        # attention sublayer (pre-norm); streaming KV-cache state (if
        # any) passes through to the attention and back out
        h1 = self._layernorm(x, params["ln1_gamma"], params["ln1_beta"])
        attn_params = {
            k: params[k] for k in ("Wq", "Wk", "Wv", "Wo", "bo")
        }
        a, state = self._attn().apply(
            attn_params, h1, state, train=False, rng=None, mask=mask
        )
        x = x + a
        # FFN / MoE sublayer (pre-norm)
        h2 = self._layernorm(x, params["ln2_gamma"], params["ln2_beta"])
        if self.n_experts > 0:
            from deeplearning4j_tpu.parallel.expert import (
                moe_ffn_reference,
            )

            moe_params = {
                k: params[k] for k in ("router", "w1", "b1", "w2", "b2")
            }
            b, fdim, t = h2.shape
            tokens = h2.transpose(0, 2, 1).reshape(b * t, fdim)
            token_mask = (
                mask.reshape(b * t) if mask is not None else None
            )
            upd = moe_ffn_reference(
                moe_params, tokens, self.capacity_factor, token_mask
            )
            upd = upd.reshape(b, t, fdim).transpose(0, 2, 1)
            x = x + upd
        else:
            ht = jnp.transpose(h2, (0, 2, 1))           # [b, t, f]
            ff = jax.nn.gelu(ht @ params["w_ff1"] + params["b_ff1"])
            ff = ff @ params["w_ff2"] + params["b_ff2"]
            ff = jnp.transpose(ff, (0, 2, 1))           # [b, f, t]
            if mask is not None:
                ff = ff * mask[:, None, :]
            x = x + ff
        return self.activate_fn()(x), state


@register_layer
@dataclass(frozen=True)
class LayerNormalization(LayerSpec):
    """Layer norm over the feature axis for [b, f] or [b, f, t]
    tensors (companion to attention; the reference's only norm is
    BatchNormalization)."""

    n_out: int = 0
    # named `eps` (not `epsilon`) to avoid shadowing the optimizer
    # epsilon inherited from LayerSpec — same as BatchNormalization
    eps: float = 1e-5
    activation: str = "identity"

    def input_kind(self) -> str:
        return "any"

    def with_input_type(self, it: InputType) -> "LayerNormalization":
        if self.n_out == 0:
            return dataclasses.replace(
                self, n_out=it.size or it.flat_size()
            )
        return self

    def regularizable_params(self) -> tuple:
        return ()

    def init_params(self, key, dtype=jnp.float32) -> dict:
        return {
            "gamma": jnp.ones((self.n_out,), dtype),
            "beta": jnp.zeros((self.n_out,), dtype),
        }

    def apply(self, params, x, state, *, train=False, rng=None, mask=None):
        # feature axis is 1 for both [b, f] and [b, f, t]
        mean = jnp.mean(x, axis=1, keepdims=True)
        var = jnp.var(x, axis=1, keepdims=True)
        xn = (x - mean) / jnp.sqrt(var + self.eps)
        g = params["gamma"]
        bta = params["beta"]
        if x.ndim == 3:
            g = g[:, None]
            bta = bta[:, None]
        return self.activate_fn()(xn * g + bta), state


@register_layer
@dataclass(frozen=True)
class PositionalEncoding(LayerSpec):
    """Sinusoidal positional encoding added to [b, n, t] activations
    (Vaswani et al. 2017) — parameter-free, any sequence length, so it
    composes with the jit static-shape contract. Attention is
    permutation-equivariant without it; place after the input
    projection in decoder-only stacks."""

    max_wavelength: float = 10000.0

    def input_kind(self) -> str:
        return "recurrent"

    # -- streaming: carry the absolute position offset ------------------

    def streams_state(self) -> bool:
        return True

    def stream_state_keys(self) -> tuple:
        return ("pos",)

    def init_stream_state(self, batch: int, dtype) -> dict:
        return {"pos": jnp.zeros((), jnp.int32)}

    def apply(self, params, x, state, *, train=False, rng=None, mask=None):
        n, t = x.shape[1], x.shape[2]
        if "pos" in state:
            off = state["pos"]
            pos = (off + jnp.arange(t)).astype(x.dtype)
            state = {**state, "pos": off + t}
        else:
            pos = jnp.arange(t, dtype=x.dtype)
        i = jnp.arange(n)
        freq = jnp.asarray(self.max_wavelength, x.dtype) ** (
            -((i // 2) * 2 / n).astype(x.dtype)
        )
        angle = freq[:, None] * pos[None, :]              # [n, t]
        pe = jnp.where(
            (i % 2 == 0)[:, None], jnp.sin(angle), jnp.cos(angle)
        )
        return x + pe[None].astype(x.dtype), state
