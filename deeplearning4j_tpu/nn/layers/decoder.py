"""Decoder-only language-model parts of the expert era — net-new vs
the reference and beside ``attention.py``'s LayerNorm/GELU block:
RMSNorm, rotary positions on part of a head, the gated SiLU
feed-forward, latent attention (low-rank queries and keys/values with
one rotary key part shared by every head), sigmoid top-k routing over
experts of which this chip holds a stated range (no capacity, no
dropped token) beside a shared expert, a pre-norm block of the two, a
token embedding and an output layer whose loss takes integer labels a
block of rows at a time and may carry a next-next-token prediction
module that shares the embedding and the head.

Layout: these layers keep sequences ``[batch, time, features]`` — the
shape the projections give and ``ops.mha`` takes — not the recurrent
stack's ``[batch, features, time]``; ids and labels are ``[batch,
time]`` whole numbers in any numeric type.
"""

from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.nn import losses as losses_mod
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.layers.base import LayerSpec, register_layer
from deeplearning4j_tpu.nn.weights import init_weights


def rms_norm(x, gamma, eps: float):
    """``x / rms(x) * gamma`` over the last axis, in float32."""
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(
        jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + eps)
    return (y * gamma.astype(jnp.float32)).astype(x.dtype)


def rotary(x, theta: float, width: int):
    """Rotary positions on the last ``width`` features of ``x``
    ``[b, t, ..., f]``, position = index along axis 1. Pairs are
    ``(i, i + width/2)`` of the rotated part (the half-split
    convention); the rest of the features pass through."""
    t, half = x.shape[1], width // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / width)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * freq[None, :]
    shape = (1, t) + (1,) * (x.ndim - 3) + (half,)
    cos, sin = jnp.cos(angle).reshape(shape), jnp.sin(angle).reshape(shape)
    keep, rot = x[..., :x.shape[-1] - width], x[..., x.shape[-1] - width:]
    a = rot[..., :half].astype(jnp.float32)
    b = rot[..., half:].astype(jnp.float32)
    turned = jnp.concatenate(
        [a * cos - b * sin, b * cos + a * sin], axis=-1).astype(x.dtype)
    return jnp.concatenate([keep, turned], axis=-1)


class _TokenMajor(LayerSpec):
    """Shared by the layers here: any input family, no preprocessor."""

    def input_kind(self) -> str:
        return "any"

    def _weight(self, key, shape, dtype):
        return init_weights(
            key, shape, self.weight_init, fan_in=shape[-2],
            fan_out=shape[-1], distribution=self.dist, dtype=dtype)


@register_layer
@dataclass(frozen=True)
class TokenEmbedding(_TokenMajor):
    """ids ``[b, t]`` -> rows of ``W`` ``[b, t, n_out]``, times
    ``multiplier``."""

    n_in: int = 0    # vocabulary rows held
    n_out: int = 0
    multiplier: float = 1.0
    activation: str = "identity"

    def takes_indices(self) -> bool:
        return True

    def output_type(self, it: InputType) -> InputType:
        return InputType.feed_forward(self.n_out)

    def init_params(self, key, dtype=jnp.float32) -> dict:
        return {"W": self._weight(key, (self.n_in, self.n_out), dtype)}

    def apply(self, params, x, state, *, train=False, rng=None, mask=None):
        rows = params["W"][x.astype(jnp.int32)]
        if self.multiplier != 1.0:
            rows = rows * self.multiplier
        return rows, state


@register_layer
@dataclass(frozen=True)
class RMSNorm(_TokenMajor):
    """Root-mean-square norm over the last axis with a learned gain."""

    n_out: int = 0
    eps: float = 1e-5
    activation: str = "identity"

    def with_input_type(self, it: InputType) -> "RMSNorm":
        if self.n_out == 0:
            return dataclasses.replace(
                self, n_out=it.size or it.flat_size())
        return self

    def regularizable_params(self) -> tuple:
        return ()

    def init_params(self, key, dtype=jnp.float32) -> dict:
        return {"gamma": jnp.ones((self.n_out,), dtype)}

    def apply(self, params, x, state, *, train=False, rng=None, mask=None):
        return rms_norm(x, params["gamma"], self.eps), state


@register_layer
@dataclass(frozen=True)
class GatedFeedForward(_TokenMajor):
    """``(silu(x·Wg) ⊙ x·Wu)·Wd``."""

    n_in: int = 0
    hidden_size: int = 0
    activation: str = "identity"

    def regularizable_params(self) -> tuple:
        return ("Wg", "Wu", "Wd")

    def init_params(self, key, dtype=jnp.float32) -> dict:
        kg, ku, kd = jax.random.split(key, 3)
        d, f = self.n_in, self.hidden_size
        return {"Wg": self._weight(kg, (d, f), dtype),
                "Wu": self._weight(ku, (d, f), dtype),
                "Wd": self._weight(kd, (f, d), dtype)}

    def apply(self, params, x, state, *, train=False, rng=None, mask=None):
        with jax.named_scope("mlp"):
            h = jax.nn.silu(x @ params["Wg"]) * (x @ params["Wu"])
            return h @ params["Wd"], state


@register_layer
@dataclass(frozen=True)
class LatentAttention(_TokenMajor):
    """Causal multi-head latent attention, training form (DeepSeek-V2
    §2.1): queries through a ``q_rank`` bottleneck, keys and values
    through a ``kv_rank`` one, both RMS-normed; a head's query and key
    are ``nope_dim`` plain features and ``rope_dim`` rotary ones, the
    rotary key part computed once from ``x`` and shared by every head.
    q, k and v go to ``ops.mha`` as ``[b, t, heads · (nope_dim +
    rope_dim)]`` (``v_dim`` has to be that width too), so the flash
    pair does the attention where it can."""

    n_in: int = 0
    n_heads: int = 4
    q_rank: int = 0
    kv_rank: int = 0
    nope_dim: int = 0
    rope_dim: int = 0
    v_dim: int = 0
    rope_theta: float = 10000.0
    eps: float = 1e-5
    activation: str = "identity"

    def regularizable_params(self) -> tuple:
        return ("Wqa", "Wqb", "Wkva", "Wkvb", "Wo")

    def init_params(self, key, dtype=jnp.float32) -> dict:
        ks = jax.random.split(key, 5)
        d, h = self.n_in, self.n_heads
        qk = self.nope_dim + self.rope_dim
        return {
            "Wqa": self._weight(ks[0], (d, self.q_rank), dtype),
            "q_norm": jnp.ones((self.q_rank,), dtype),
            "Wqb": self._weight(ks[1], (self.q_rank, h * qk), dtype),
            "Wkva": self._weight(
                ks[2], (d, self.kv_rank + self.rope_dim), dtype),
            "kv_norm": jnp.ones((self.kv_rank,), dtype),
            "Wkvb": self._weight(
                ks[3], (self.kv_rank, h * (self.nope_dim + self.v_dim)),
                dtype),
            "Wo": self._weight(ks[4], (h * self.v_dim, d), dtype),
        }

    def apply(self, params, x, state, *, train=False, rng=None, mask=None):
        from deeplearning4j_tpu.ops import mha

        b, t, _ = x.shape
        h, nope, rope, vd = (self.n_heads, self.nope_dim, self.rope_dim,
                             self.v_dim)
        qk = nope + rope
        if vd != qk:
            raise ValueError(
                f"v_dim {vd} is not a head's query width ({qk}): "
                "ops.mha takes q, k and v of one width")
        with jax.named_scope("mla.qkv"):
            cq = rms_norm(x @ params["Wqa"], params["q_norm"], self.eps)
            q = rotary((cq @ params["Wqb"]).reshape(b, t, h, qk),
                       self.rope_theta, rope)
            ckv = x @ params["Wkva"]
            k_pe = rotary(ckv[..., None, self.kv_rank:], self.rope_theta,
                          rope)
            kv = (rms_norm(ckv[..., :self.kv_rank], params["kv_norm"],
                           self.eps) @ params["Wkvb"]
                  ).reshape(b, t, h, nope + vd)
            k = jnp.concatenate(
                [kv[..., :nope],
                 jnp.broadcast_to(k_pe, (b, t, h, rope))], axis=-1)
            q, k, v = (a.reshape(b, t, h * qk)
                       for a in (q, k, kv[..., nope:]))
        with jax.named_scope("mla.attention"):
            o = mha(q, k, v, h, causal=True)
        return o @ params["Wo"], state


@register_layer
@dataclass(frozen=True)
class GroupedQueryAttention(_TokenMajor):
    """Causal attention without positions: ``n_heads`` query heads of
    ``head_dim`` over ``n_kv_heads`` key/value heads, key/value head
    ``j`` serving query heads ``j·r .. j·r + r - 1`` (``r = n_heads /
    n_kv_heads``); scores ``q·kᵀ · scale`` (``1/√head_dim`` where
    ``scale`` is 0); no bias. k and v are repeated ``r`` times ahead of
    ``ops.mha`` and ``scale·√head_dim`` is folded into q, so the flash
    pair does the attention where it can and its kernels stay as they
    are."""

    n_in: int = 0
    n_heads: int = 8
    n_kv_heads: int = 2
    head_dim: int = 64
    scale: float = 0.0
    activation: str = "identity"

    def regularizable_params(self) -> tuple:
        return ("Wq", "Wk", "Wv", "Wo")

    def init_params(self, key, dtype=jnp.float32) -> dict:
        kq, kk, kv, ko = jax.random.split(key, 4)
        d, hd = self.n_in, self.head_dim
        return {"Wq": self._weight(kq, (d, self.n_heads * hd), dtype),
                "Wk": self._weight(kk, (d, self.n_kv_heads * hd), dtype),
                "Wv": self._weight(kv, (d, self.n_kv_heads * hd), dtype),
                "Wo": self._weight(ko, (self.n_heads * hd, d), dtype)}

    def apply(self, params, x, state, *, train=False, rng=None, mask=None):
        from deeplearning4j_tpu.ops import mha

        b, t, _ = x.shape
        h, kv, hd = self.n_heads, self.n_kv_heads, self.head_dim
        if h % kv:
            raise ValueError(
                f"{h} query heads over {kv} key/value heads")
        with jax.named_scope("gqa.qkv"):
            q = x @ params["Wq"]
            fold = (self.scale or hd ** -0.5) * hd ** 0.5
            if fold != 1.0:
                q = q * fold

            def repeated(a):
                return jnp.repeat(a.reshape(b, t, kv, hd), h // kv,
                                  axis=2).reshape(b, t, h * hd)

            k, v = repeated(x @ params["Wk"]), repeated(x @ params["Wv"])
        with jax.named_scope("gqa.attention"):
            o = mha(q, k, v, h, causal=True)
        with jax.named_scope("gqa.out"):
            return o @ params["Wo"], state


# the MXU's rows: the grouped kernel's own row tiles (512 at
# glm47flash.fit_4k's shapes) are multiples of it
_ROW_TILE = 128


def row_ladder(n_slots: int, held: int, n_experts: int) -> tuple:
    """The static row bounds the held experts' data path may run over,
    from shapes alone: twice the even share of ``n_slots`` token-slots
    (``held`` of ``n_experts`` experts are here), rounded up to the row
    tile, then doubling; the last rung is ``n_slots``, so every routing
    fits one. Its length depends on ``(held, n_experts)`` only."""
    rungs, share = [], 2 * held
    while share < n_experts:
        rows = -(-n_slots * share // n_experts)
        rungs.append(min(n_slots, -(-rows // _ROW_TILE) * _ROW_TILE))
        share *= 2
    return tuple(rungs) + (n_slots,)


def _experts_over(bound, k, tokens, w, order, sizes, eg, eu, ed):
    """The held experts' part of the layer over the first ``bound``
    sorted token-slots, which must hold every live one
    (``sum(sizes) <= bound``): ``y[t] = Σ_j w[t, j] · E(tokens[t])`` over
    the slots ``(t, j)`` of held experts; ``w`` is zero at the others."""
    # rows behind the last group belong to no expert held here, and
    # the chip's grouped kernel leaves them as it found them, forward
    # and backward: zero them on both sides of every product (the
    # transpose of a side's ``where`` zeroes the cotangent's rows)
    live = (jnp.arange(bound) < jnp.sum(sizes))[:, None]

    def dot(a, e):
        return jnp.where(live, jax.lax.ragged_dot(
            jnp.where(live, a, 0), e, sizes), 0)

    slot = order[:bound]
    token = slot // k
    rows = tokens[token]
    out = dot(jax.nn.silu(dot(rows, eg)) * dot(rows, eu), ed)
    # by token, not by slot: a sum over the rows of a token, whose
    # transpose is a gather of ``bound`` rows
    weighted = out.astype(jnp.float32) * w.reshape(-1)[slot][:, None]
    return jnp.zeros(tokens.shape, jnp.float32).at[token].add(
        weighted).astype(out.dtype)


def _rung_index(rungs, sizes):
    """The first rung that holds ``sum(sizes)`` rows."""
    return jnp.sum(jnp.sum(sizes) > jnp.asarray(rungs[:-1], jnp.int32))


def _held_experts(rungs, k, *args):
    """``_experts_over`` the first of ``rungs`` (ascending, the last one
    every token-slot) that holds the live rows."""
    rungs = tuple(sorted(set(rungs)))
    if len(rungs) == 1:
        return _experts_over(rungs[0], k, *args)
    return _rung_switch(rungs, k, *args)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _rung_switch(rungs, k, tokens, w, order, sizes, eg, eu, ed):
    return jax.lax.switch(
        _rung_index(rungs, sizes),
        [functools.partial(_experts_over, r, k) for r in rungs],
        tokens, w, order, sizes, eg, eu, ed)


def _rung_switch_fwd(rungs, k, *args):
    # differentiating the switch itself would keep the residuals of
    # every rung, the unused ones written as zeros: keep the inputs and
    # differentiate inside the rung the backward pass takes
    return _rung_switch(rungs, k, *args), args


def _rung_switch_bwd(rungs, k, args, dy):
    tokens, w, order, sizes, eg, eu, ed = args

    def back(bound, tokens, w, eg, eu, ed, dy):
        _, vjp = jax.vjp(
            lambda t, w_, a, b, c: _experts_over(
                bound, k, t, w_, order, sizes, a, b, c),
            tokens, w, eg, eu, ed)
        return vjp(dy)

    d_tokens, d_w, d_eg, d_eu, d_ed = jax.lax.switch(
        _rung_index(rungs, sizes),
        [functools.partial(back, r) for r in rungs],
        tokens, w, eg, eu, ed, dy)
    # keep the casts that follow outside the switch: moved into its
    # branches they write the stacks' gradients out once more, wider
    d_eg, d_eu, d_ed = jax.lax.optimization_barrier((d_eg, d_eu, d_ed))
    return d_tokens, d_w, None, None, d_eg, d_eu, d_ed


_rung_switch.defvjp(_rung_switch_fwd, _rung_switch_bwd)


@register_layer
@dataclass(frozen=True)
class RoutedExperts(_TokenMajor):
    """Sigmoid top-k routing over ``n_experts`` gated feed-forward
    experts of which this chip holds ``held_first..held_last``
    (inclusive; all of them by default), beside ``n_shared`` shared
    experts every chip computes whole (DeepSeek-V3 §2.1.2, ``noaux_tc``
    selection):

        s = sigmoid(x·router)                 over all n_experts
        chosen = top_k(s + route_bias)        route_bias: state, not trained
        w = s[chosen] / sum(s[chosen]) · scaling   (``norm_topk``)
        y = shared(x) + Σ w_e · E_e(x)        over chosen experts held here

    What the absent experts would add is left out: on one chip of an
    expert-parallel group this is the chip's part of the layer, before
    the exchange. No capacity and no dropped token: the token-slots of
    the held experts are sorted by expert and run as grouped products
    (``jax.lax.ragged_dot``) over as many rows as the routing gives.
    The rows moved, masked and gated around the products are those of
    a static bound picked at run time from the live-row count: the
    first rung of ``row_ladder`` that holds them (twice the even share,
    then doubling up to every token-slot; one rung, and no switch in
    the program, where every expert is held).

    State: ``route_bias`` ``[n_experts]``, the selection bias, not
    trained (it starts at zero; no rule here moves it); and the routing
    statistics since they were last published: ``slots``
    ``[n_experts]`` token-slots routed to each expert, ``dropped``
    slots of held experts whose sorted row lies behind the rows the
    grouped products cover (0 as long as the group sizes count every
    held slot), ``rung_calls`` ``[rungs]`` calls that ran at each rung
    of the ladder. ``publish_routing_metrics`` reads them and starts
    them anew: they are int32, so publish before a single expert has
    taken 2**31 slots."""

    n_in: int = 0
    hidden_size: int = 0
    n_experts: int = 8
    held_first: int = 0
    held_last: int = -1          # -1: the last expert
    top_k: int = 2
    n_shared: int = 1
    scaling: float = 1.0
    norm_topk: bool = True
    activation: str = "identity"

    def held(self) -> tuple:
        last = self.n_experts - 1 if self.held_last < 0 else self.held_last
        if not 0 <= self.held_first <= last < self.n_experts:
            raise ValueError(
                f"held experts {self.held_first}..{last} of "
                f"{self.n_experts}")
        return self.held_first, last

    def regularizable_params(self) -> tuple:
        return ("Eg", "Eu", "Ed", "Sg", "Su", "Sd")

    def init_params(self, key, dtype=jnp.float32) -> dict:
        ks = jax.random.split(key, 7)
        d, f = self.n_in, self.hidden_size
        first, last = self.held()
        g, fs = last - first + 1, f * self.n_shared
        p = {"router": self._weight(ks[0], (d, self.n_experts), dtype),
             "Eg": self._weight(ks[1], (g, d, f), dtype),
             "Eu": self._weight(ks[2], (g, d, f), dtype),
             "Ed": self._weight(ks[3], (g, f, d), dtype)}
        if self.n_shared:
            p.update(Sg=self._weight(ks[4], (d, fs), dtype),
                     Su=self._weight(ks[5], (d, fs), dtype),
                     Sd=self._weight(ks[6], (fs, d), dtype))
        return p

    def init_state(self, dtype=jnp.float32) -> dict:
        return {"route_bias": jnp.zeros((self.n_experts,), jnp.float32),
                "slots": jnp.zeros((self.n_experts,), jnp.int32),
                "dropped": jnp.zeros((), jnp.int32),
                "rung_calls": jnp.zeros((len(self.rungs(0)),), jnp.int32)}

    def rungs(self, n_slots: int) -> tuple:
        first, last = self.held()
        return row_ladder(n_slots, last - first + 1, self.n_experts)

    def route(self, params, tokens, route_bias):
        """``(chosen [n, k] expert ids, weights [n, k])``."""
        s = jax.nn.sigmoid(jnp.dot(
            tokens, params["router"].astype(tokens.dtype),
            preferred_element_type=jnp.float32))
        _, chosen = jax.lax.top_k(
            jax.lax.stop_gradient(s) + route_bias, self.top_k)
        w = jnp.take_along_axis(s, chosen, axis=-1)
        if self.norm_topk:
            w = w / jnp.sum(w, axis=-1, keepdims=True)
        return chosen, w * self.scaling

    def apply(self, params, x, state, *, train=False, rng=None, mask=None):
        shape = x.shape
        tokens = x.reshape(-1, shape[-1])
        n, k = tokens.shape[0], self.top_k
        first, last = self.held()
        g = last - first + 1
        with jax.named_scope("moe.route"):
            chosen, w = self.route(params, tokens, state["route_bias"])
            here = (chosen >= first) & (chosen <= last)
            # slots of held experts first, grouped by expert; the rest
            # behind them under the key g
            key = jnp.where(here, chosen - first, g).reshape(-1)
            order = jnp.argsort(key, stable=True)
            sizes = jnp.sum(jax.nn.one_hot(key, g, dtype=jnp.int32), axis=0)
            counts = jnp.sum(
                jax.nn.one_hot(chosen.reshape(-1), self.n_experts,
                               dtype=jnp.int32), axis=0)
        rungs = self.rungs(n * k)
        with jax.named_scope("moe.experts"):
            y = _held_experts(
                rungs, k, tokens, jnp.where(here, w, 0.0), order, sizes,
                params["Eg"], params["Eu"], params["Ed"])
        if self.n_shared:
            with jax.named_scope("moe.shared"):
                y = y + (jax.nn.silu(tokens @ params["Sg"])
                         * (tokens @ params["Su"])) @ params["Sd"]
        new_state = {
            "route_bias": state["route_bias"],
            "slots": state["slots"] + counts,
            # a held slot the sort put behind the live rows was not
            # computed
            "dropped": state["dropped"] + jnp.sum(
                ((key[order] < g)
                 & (jnp.arange(n * k) >= jnp.sum(sizes))).astype(jnp.int32)),
            "rung_calls": state["rung_calls"] + jax.nn.one_hot(
                _rung_index(rungs, sizes), len(rungs), dtype=jnp.int32),
        }
        return y.reshape(shape), new_state

    def routing_report(self, state) -> tuple:
        """``(report, state with the statistics started anew)``."""
        slots, calls, dropped = jax.device_get(
            (state["slots"], state["rung_calls"], state["dropped"]))
        # every call counts top_k slots a token, so the calls since the
        # last report (of one shape: a fit()'s batches) had this many
        n_slots = int(slots.sum()) // max(int(calls.sum()), 1)
        rung_calls = {}
        for rows, c in zip(self.rungs(n_slots), calls.tolist()):
            rung_calls[rows] = rung_calls.get(rows, 0) + c
        report = {"slots": slots, "held": self.held(),
                  "dropped": int(dropped), "rung_calls": rung_calls}
        return report, {**state, **{
            name: jnp.zeros_like(state[name])
            for name in ("slots", "dropped", "rung_calls")}}


@register_layer
@dataclass(frozen=True)
class DecoderBlock(_TokenMajor):
    """Pre-norm residual block of a sequence mixer (``attention``: an
    attention layer of this file or a ``StateSpaceMixer``) and a
    feed-forward layer (``GatedFeedForward`` or ``RoutedExperts``),
    RMS norms: ``h = x + ρ·attention(rms(x))``, ``y = h + ρ·ffn(rms(h))``
    with ``ρ`` = ``residual_multiplier``. Its params are the two
    sub-layers' under their own names plus the two gains; its state is
    the feed-forward layer's."""

    attention: Optional[LayerSpec] = None
    ffn: Optional[LayerSpec] = None
    n_in: int = 0
    eps: float = 1e-5
    residual_multiplier: float = 1.0
    activation: str = "identity"

    def _parts(self):
        keep = dict(weight_init=self.weight_init, dist=self.dist)
        return (dataclasses.replace(self.attention, **keep),
                dataclasses.replace(self.ffn, **keep))

    def regularizable_params(self) -> tuple:
        return (self.attention.regularizable_params()
                + self.ffn.regularizable_params())

    def init_params(self, key, dtype=jnp.float32) -> dict:
        ka, kf = jax.random.split(key)
        attention, ffn = self._parts()
        return {"attn_norm": jnp.ones((self.n_in,), dtype),
                **attention.init_params(ka, dtype),
                "ffn_norm": jnp.ones((self.n_in,), dtype),
                **ffn.init_params(kf, dtype)}

    def init_state(self, dtype=jnp.float32) -> dict:
        return self.ffn.init_state(dtype)

    def apply(self, params, x, state, *, train=False, rng=None, mask=None):
        rho = self.residual_multiplier
        a, _ = self.attention.apply(
            params, rms_norm(x, params["attn_norm"], self.eps), {},
            train=train)
        h = x + (a if rho == 1.0 else a * rho)
        f, state = self.ffn.apply(
            params, rms_norm(h, params["ffn_norm"], self.eps), state,
            train=train)
        return h + (f if rho == 1.0 else f * rho), state

    def routing_report(self, state) -> Optional[tuple]:
        report = getattr(self.ffn, "routing_report", None)
        return report(state) if report and state else None


_MODULE = "mtp_"


@register_layer
@dataclass(frozen=True)
class LMOutputLayer(_TokenMajor):
    """Final RMS norm, the vocabulary head ``W`` ``[n_in, n_out]`` and
    the mean cross-entropy against integer labels, computed from the
    layer's input a block of ``block_rows`` rows at a time so that the
    logits and their cotangent never exist whole
    (``losses.sparse_mcxent_sum``). With ``tie_embeddings`` the head is
    layer ``embedding_layer``'s ``W`` ``[n_out, n_in]`` transposed and
    this layer keeps no ``W`` of its own; the logits are divided by
    ``logits_scaling``.

    ``next_token`` (a block spec) adds one multi-token-prediction
    module (DeepSeek-V3 §2.2): with ``e`` the embedding of the label at
    a position (layer ``embedding_layer``'s ``W``, shared),
    ``h' = [rms(h) ; rms(e)]·proj``, the block, a final RMS norm of its
    own, this layer's head, and cross-entropy against the label one
    further on; the score is ``main + next_token_weight · module's``.
    Labels then carry one position more than the input."""

    n_in: int = 0
    n_out: int = 0
    eps: float = 1e-5
    block_rows: int = 1024
    next_token: Optional[LayerSpec] = None
    next_token_weight: float = 0.3
    embedding_layer: int = 0
    tie_embeddings: bool = False
    logits_scaling: float = 1.0
    loss: str = "SPARSE_MCXENT"
    activation: str = "softmax"

    def has_loss(self) -> bool:
        return True

    def scores_input(self) -> bool:
        return True

    def tied_params(self) -> tuple:
        if self.next_token is None and not self.tie_embeddings:
            return ()
        return (("embed", self.embedding_layer, "W"),)

    def _head(self, params):
        """The head ``[n_in, n_out]``."""
        return params["embed"].T if self.tie_embeddings else params["W"]

    def _scaled(self, h):
        """``h`` over ``logits_scaling``: dividing the head's input
        divides the logits."""
        if self.logits_scaling == 1.0:
            return h
        return h * (1.0 / self.logits_scaling)

    def _module(self):
        return dataclasses.replace(
            self.next_token, weight_init=self.weight_init, dist=self.dist)

    def regularizable_params(self) -> tuple:
        names = () if self.tie_embeddings else ("W",)
        if self.next_token is not None:
            names += (_MODULE + "proj",) + tuple(
                _MODULE + n
                for n in self.next_token.regularizable_params())
        return names

    def init_params(self, key, dtype=jnp.float32) -> dict:
        kw, kp, kb = jax.random.split(key, 3)
        d = self.n_in
        p = {"norm": jnp.ones((d,), dtype)}
        if not self.tie_embeddings:
            p["W"] = self._weight(kw, (d, self.n_out), dtype)
        if self.next_token is not None:
            p[_MODULE + "hnorm"] = jnp.ones((d,), dtype)
            p[_MODULE + "enorm"] = jnp.ones((d,), dtype)
            p[_MODULE + "proj"] = self._weight(kp, (2 * d, d), dtype)
            p[_MODULE + "norm"] = jnp.ones((d,), dtype)
            p.update({_MODULE + n: v for n, v in
                      self._module().init_params(kb, dtype).items()})
        return p

    def init_state(self, dtype=jnp.float32) -> dict:
        if self.next_token is None:
            return {}
        return {_MODULE + n: v
                for n, v in self.next_token.init_state(dtype).items()}

    def apply(self, params, x, state, *, train=False, rng=None, mask=None):
        """Next-token probabilities ``[b, t, n_out]`` of the main head."""
        with jax.named_scope("lm_head"):
            logits = jnp.dot(
                self._scaled(rms_norm(x, params["norm"], self.eps)),
                self._head(params), preferred_element_type=jnp.float32)
        return jax.nn.softmax(logits, axis=-1).astype(x.dtype), state

    def _mean_loss(self, w, h, labels):
        with jax.named_scope("lm_head"):
            return losses_mod.sparse_mcxent_sum(
                self._scaled(h).reshape(-1, h.shape[-1]), w,
                labels.reshape(-1), self.block_rows) / labels.size

    def score_input(self, params, x, labels, state, *, mask=None,
                    train=False, rng=None, remat="none"):
        """``(score, new state)`` from the layer's input ``[b, t, d]``
        and labels ``[b, t]`` (``[b, t + 1]`` with a module)."""
        from deeplearning4j_tpu.nn.core import maybe_remat

        if mask is not None:
            raise NotImplementedError(
                "LMOutputLayer scores every position: no label mask")
        t = x.shape[1]
        labels = labels.astype(jnp.int32)
        w = self._head(params)
        score = self._mean_loss(
            w, rms_norm(x, params["norm"], self.eps), labels[:, :t])
        if self.next_token is None:
            return score, state
        if labels.shape[1] != t + 1:
            raise ValueError(
                f"labels of {labels.shape[1]} positions for {t} inputs: "
                "the prediction module needs one more")
        block = self.next_token
        m = len(_MODULE)

        def module(p, x, st):
            e = p["embed"][labels[:, :t]]
            h = jnp.concatenate(
                [rms_norm(x, p[_MODULE + "hnorm"], self.eps),
                 rms_norm(e, p[_MODULE + "enorm"], self.eps)],
                axis=-1) @ p[_MODULE + "proj"]
            h, st = block.apply(
                {n[m:]: v for n, v in p.items() if n.startswith(_MODULE)},
                h, {n[m:]: v for n, v in st.items()}, train=train)
            return (rms_norm(h, p[_MODULE + "norm"], self.eps),
                    {_MODULE + n: v for n, v in st.items()})

        with jax.named_scope("mtp"):
            h, new_state = maybe_remat(module, remat)(params, x, state)
            extra = self._mean_loss(w, h, labels[:, 1:])
        return score + self.next_token_weight * extra, new_state

    def routing_report(self, state) -> Optional[tuple]:
        if self.next_token is None or not state:
            return None
        found = self.next_token.routing_report(
            {n[len(_MODULE):]: v for n, v in state.items()})
        if found is None:
            return None
        report, state = found
        return report, {_MODULE + n: v for n, v in state.items()}


def publish_routing_metrics(model) -> dict:
    """Read the routing statistics the expert layers of ``model`` keep
    in their state, publish them on the metrics registry and start the
    state's counts anew: ``moe_token_slots_total{layer, held}``,
    ``moe_dropped_tokens_total``, ``moe_rung_calls_total{layer, rows}``
    (calls whose held experts' data path ran over that static bound of
    rows) and the gauge ``moe_expert_load_max_over_mean{layer}`` (the
    busiest expert's slots over the mean, all experts of the layer,
    over every call for this model). One device read per expert layer:
    call it outside a timed window. Returns ``{layer: {"slots": [...],
    "held": (first, last), "dropped": n, "rows_covered": Σ calls × the
    rung's rows}}`` with the totals over every call."""
    from deeplearning4j_tpu.observability.metrics import default_registry

    reg = default_registry()
    slots_total = reg.counter(
        "moe_token_slots_total",
        help="token-slots the router sent to experts held on this chip "
             "(held=true) and to absent ones",
        labels=("layer", "held"))
    dropped_total = reg.counter(
        "moe_dropped_tokens_total",
        help="token-slots of held experts that were not computed")
    rung_calls_total = reg.counter(
        "moe_rung_calls_total",
        help="calls of an expert layer whose held experts' data path "
             "ran over this static bound of rows",
        labels=("layer", "rows"))
    imbalance = reg.gauge(
        "moe_expert_load_max_over_mean",
        help="busiest expert's token-slots over the mean of the "
             "layer's experts",
        labels=("layer",))
    totals = model.__dict__.setdefault("_routing_published", {})
    for name, layer in zip(model.layer_names, model.conf.layers):
        report = getattr(layer, "routing_report", None)
        found = report(model.state.get(name, {})) if report else None
        if found is None:
            continue
        report, model.state[name] = found
        added = report["slots"].astype(np.int64)
        first, last = report["held"]
        held = int(added[first:last + 1].sum())
        slots_total.labels(layer=name, held="true").inc(held)
        slots_total.labels(layer=name, held="false").inc(
            int(added.sum()) - held)
        dropped_total.inc(report["dropped"])
        for rows, calls in report["rung_calls"].items():
            rung_calls_total.labels(layer=name, rows=str(rows)).inc(calls)
        total = totals.setdefault(
            name, {"slots": np.zeros_like(added), "held": (first, last),
                   "dropped": 0, "rows_covered": 0})
        total["slots"] = total["slots"] + added
        total["dropped"] += report["dropped"]
        total["rows_covered"] += sum(
            rows * calls for rows, calls in report["rung_calls"].items())
        if total["slots"].sum():
            imbalance.labels(layer=name).set(
                float(total["slots"].max() / total["slots"].mean()))
    return {name: {**t, "slots": t["slots"].tolist()}
            for name, t in totals.items()}
