"""State-space sequence mixer of the Mamba-2 family (Dao & Gu 2024,
"Transformers are SSMs", arXiv:2405.21060) — net-new vs the reference,
beside ``decoder.py``'s attention layers and in their layout
(``[batch, time, features]``): a fused input projection, a causal
depthwise convolution over time, the selective scan in its chunked
dual form (SSD), a gated RMS norm and an output projection.

Per head, with a state ``S`` of ``[head_dim, state_size]`` from zero:

    S_t = exp(Δ_t·A)·S_{t-1} + Δ_t · x_t B_tᵀ        y_t = S_t C_t + D·x_t

``ssd_chunked`` computes it ``chunk`` positions at a time in plain XLA
(``einsum`` and ``cumsum``; no sequential step over positions, no
kernel), differentiable as it stands: inside a chunk the decay-masked
products ``(L ∘ C Bᵀ)·X``; a chunk's own state ``Σ decay · B ⊗ X``;
the states passed from chunk to chunk; their contribution ``C·S`` to
the next chunk. Every decay is ``exp`` of a difference of cumulative
sums of ``Δ·A`` (never a ratio of exponentials); products take the
compute type with float32 accumulation, the decays, the passing of the
states and the sums of the parts are float32.

The convolution with its bias and SiLU (``causal_conv_silu``) is ``K``
shifted float32 products in XLA going forward, on every platform; its
backward is one Pallas kernel (``ops/depthwise_conv.py``) where the
shape fills the kernel's tiles and dispatch is on, and autodiff of the
forward elsewhere.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.layers.base import register_layer
from deeplearning4j_tpu.nn.layers.decoder import _TokenMajor, rms_norm
from deeplearning4j_tpu.ops import dispatch
from deeplearning4j_tpu.ops.depthwise_conv import (
    conv_silu_bwd,
    depthwise_conv_bwd_ok,
)

_F32 = jnp.float32


def _note_scan_call(chunk: int, chunks: int) -> None:
    """``ssm_scan_calls_total{chunk, chunks}``: one per state-space
    layer traced, as ``pallas_dispatch_total`` counts routing
    decisions (a scanned run of layers is traced once)."""
    from deeplearning4j_tpu.observability.metrics import default_registry

    default_registry().counter(
        "ssm_scan_calls_total",
        help="chunked selective scans traced, by chunk length and "
             "number of chunks",
        labels=("chunk", "chunks"),
    ).labels(chunk=str(chunk), chunks=str(chunks)).inc()


def _decay_below(cs, shifted=None):
    """``exp(shifted[..., t] - cs[..., s])`` where ``s`` comes before
    ``t`` (``s <= t`` with ``shifted`` left out, which then is ``cs``;
    ``s < t`` with it), nought elsewhere: ``[..., l] -> [..., l, l]``.
    The masked entries are made ``-inf`` before ``exp``, so nothing
    overflows and their gradient is nought."""
    n = cs.shape[-1]
    rows = cs if shifted is None else shifted
    keep = jnp.tril(jnp.ones((n, n), bool), 0 if shifted is None else -1)
    return jnp.exp(jnp.where(
        keep, rows[..., :, None] - cs[..., None, :], -jnp.inf))


def ssd_chunked(x, dt, a, b_in, c_in, chunk: int):
    """The selective scan without its ``D·x`` term, from a zero state.

    ``x`` ``[b, t, h, p]``, ``dt`` ``[b, t, h]`` (Δ, after softplus),
    ``a`` ``[h]`` (negative), ``b_in`` and ``c_in`` ``[b, t, g, n]``
    with ``h`` a multiple of ``g``; returns ``y`` ``[b, t, h, p]`` in
    float32. A length that ``chunk`` does not divide is padded behind
    with positions that neither decay nor add (Δ = 0)."""
    bsz, t, h, p = x.shape
    g, n = b_in.shape[2:]
    r = h // g
    cdt = x.dtype
    q = min(chunk, t)
    pad = -t % q
    if pad:
        x, dt, b_in, c_in = (
            jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
            for v in (x, dt, b_in, c_in))
    nc = (t + pad) // q
    _note_scan_call(q, nc)
    x = x.reshape(bsz, nc, q, g, r, p)
    b_in = b_in.reshape(bsz, nc, q, g, n)
    c_in = c_in.reshape(bsz, nc, q, g, n)
    dt = dt.astype(_F32).reshape(bsz, nc, q, g, r)
    # log-decay to each position from its chunk's start: [b, g, r, c, l]
    cs = jnp.cumsum(jnp.transpose(
        dt * a.astype(_F32).reshape(g, r), (0, 3, 4, 1, 2)), axis=-1)
    by_pos = lambda v: jnp.transpose(  # noqa: E731  -> [b, c, l, g, r, 1]
        v, (0, 3, 4, 1, 2))[..., None]
    xdt = x.astype(_F32) * dt[..., None]

    with jax.named_scope("ssm.scan.intra"):
        cb = jnp.einsum("bclgn,bcsgn->bgcls", c_in, b_in,
                        preferred_element_type=_F32)
        m = (_decay_below(cs) * cb[:, :, None]).astype(cdt)
        y = jnp.einsum("bgrcls,bcsgrp->bclgrp", m, xdt.astype(cdt),
                       preferred_element_type=_F32)
    with jax.named_scope("ssm.scan.states"):
        to_end = by_pos(jnp.exp(cs[..., -1:] - cs))
        own = jnp.einsum("bclgn,bclgrp->bcgrpn", b_in,
                         (xdt * to_end).astype(cdt),
                         preferred_element_type=_F32)
    with jax.named_scope("ssm.scan.pass"):
        # the state a chunk starts from: every earlier chunk's own
        # state, decayed over the chunks between
        ends = jnp.cumsum(cs[..., -1], axis=-1)            # [b, g, r, c]
        entering = jnp.einsum(
            "bgrzc,bcgrpn->bzgrpn",
            _decay_below(ends, ends - cs[..., -1]), own,
            precision=jax.lax.Precision.HIGHEST)
    with jax.named_scope("ssm.scan.inter"):
        y = y + by_pos(jnp.exp(cs)) * jnp.einsum(
            "bclgn,bcgrpn->bclgrp", c_in, entering.astype(cdt),
            preferred_element_type=_F32)
    return y.reshape(bsz, nc * q, h, p)[:, :t]


def causal_depthwise_conv(x, w, bias):
    """``y[t, c] = Σ_k w[k, c] · x[t - (K-1) + k, c] + bias[c]`` over
    ``x`` ``[b, t, c]``, positions before the start read as nought;
    float32."""
    k, t = w.shape[0], x.shape[1]
    xp = jnp.pad(x.astype(_F32), ((0, 0), (k - 1, 0), (0, 0)))
    w = w.astype(_F32)
    return sum(xp[:, i:i + t] * w[i] for i in range(k)) \
        + bias.astype(_F32)


def _conv_silu(x, w, bias):
    return jax.nn.silu(causal_depthwise_conv(x, w, bias)).astype(x.dtype)


_conv_silu_pallas_bwd = jax.custom_vjp(_conv_silu)
_conv_silu_pallas_bwd.defvjp(
    lambda x, w, bias: (_conv_silu(x, w, bias), (x, w, bias)),
    lambda res, dy: conv_silu_bwd(*res, dy))


def causal_conv_silu(x, w, bias):
    """``silu(causal_depthwise_conv(x, w, bias))`` in ``x``'s dtype.
    The forward is the shifted float32 products in XLA on every path;
    the backward is the Pallas kernel of ``ops/depthwise_conv.py`` (one
    pass over ``x`` and ``dy``) where ``depthwise_conv_bwd_ok`` admits
    the shape — channels in whole 16-row tiles, at least 128 positions,
    bfloat16 or float32 — and dispatch is on, autodiff of the forward
    otherwise. Decided here from the operand, once, and counted
    (``pallas_dispatch_total{kernel="depthwise_conv_bwd"}``): taps and
    bias are widened ahead of the ``custom_vjp``, so their cotangents
    are rounded where autodiff rounds them."""
    if dispatch.route("depthwise_conv_bwd",
                      depthwise_conv_bwd_ok(x.shape, x.dtype, w.shape[0])):
        return _conv_silu_pallas_bwd(x, w.astype(_F32), bias.astype(_F32))
    return _conv_silu(x, w, bias)


@register_layer
@dataclass(frozen=True)
class StateSpaceMixer(_TokenMajor):
    """Mamba-2 mixer: ``[z | xBC | dt] = u·Win``; ``xBC`` through a
    causal depthwise convolution of ``conv_width`` taps (with bias) and
    SiLU, split into ``x`` (``n_heads`` heads of ``head_dim``), ``B``
    and ``C`` (``n_groups`` groups of ``state_size``); ``Δ =
    softplus(dt + dt_bias)``, ``A = -exp(A_log)`` (one scalar a head);
    the selective scan (``ssd_chunked`` at ``chunk``) plus ``D·x``;
    ``rms(y ⊙ silu(z))`` over all ``n_heads·head_dim`` features (one
    norm group); ``·Wout``. No projection has a bias.

    A fresh layer starts as the Mamba-2 reference implementation does:
    ``A`` uniform on [1, 16], ``Δ`` at a zero input log-uniform on
    [0.001, 0.1], ``D`` = 1, convolution weights uniform on
    ±1/√``conv_width`` with zero bias, unit gains."""

    n_in: int = 0
    n_heads: int = 8
    head_dim: int = 64
    state_size: int = 128
    n_groups: int = 1
    conv_width: int = 4
    chunk: int = 256
    eps: float = 1e-5
    activation: str = "identity"

    def _widths(self):
        inner = self.n_heads * self.head_dim
        return inner, inner + 2 * self.n_groups * self.state_size

    def regularizable_params(self) -> tuple:
        return ("Win", "Wout")

    def init_params(self, key, dtype=jnp.float32) -> dict:
        ki, kc, ka, kd, ko = jax.random.split(key, 5)
        inner, conv = self._widths()
        h, k = self.n_heads, self.conv_width
        dt0 = jnp.exp(jax.random.uniform(
            kd, (h,), _F32, math.log(1e-3), math.log(1e-1)))
        return {
            "Win": self._weight(ki, (self.n_in, inner + conv + h), dtype),
            "conv_W": jax.random.uniform(
                kc, (k, conv), _F32, -k ** -0.5, k ** -0.5).astype(dtype),
            "conv_b": jnp.zeros((conv,), dtype),
            # softplus(dt_bias) = dt0
            "dt_bias": (dt0 + jnp.log(-jnp.expm1(-dt0))).astype(dtype),
            "A_log": jnp.log(jax.random.uniform(
                ka, (h,), _F32, 1.0, 16.0)).astype(dtype),
            "D": jnp.ones((h,), dtype),
            "gate_norm": jnp.ones((inner,), dtype),
            "Wout": self._weight(ko, (inner, self.n_in), dtype),
        }

    def apply(self, params, x, state, *, train=False, rng=None, mask=None):
        b, t, _ = x.shape
        inner, conv = self._widths()
        h, p = self.n_heads, self.head_dim
        g, n = self.n_groups, self.state_size
        with jax.named_scope("ssm.in_proj"):
            zxd = x @ params["Win"]
            z, xbc, dt = (zxd[..., :inner], zxd[..., inner:inner + conv],
                          zxd[..., inner + conv:])
        with jax.named_scope("ssm.conv"):
            xbc = causal_conv_silu(xbc, params["conv_W"], params["conv_b"])
        xs = xbc[..., :inner].reshape(b, t, h, p)
        with jax.named_scope("ssm.scan"):
            delta = jax.nn.softplus(
                dt.astype(_F32) + params["dt_bias"].astype(_F32))
            y = ssd_chunked(
                xs, delta, -jnp.exp(params["A_log"].astype(_F32)),
                xbc[..., inner:inner + g * n].reshape(b, t, g, n),
                xbc[..., inner + g * n:].reshape(b, t, g, n), self.chunk)
            y = y + params["D"].astype(_F32)[:, None] * xs.astype(_F32)
        with jax.named_scope("ssm.gate_norm"):
            y = rms_norm(
                y.reshape(b, t, inner) * jax.nn.silu(z.astype(_F32)),
                params["gate_norm"], self.eps).astype(x.dtype)
        with jax.named_scope("ssm.out_proj"):
            return y @ params["Wout"], state
