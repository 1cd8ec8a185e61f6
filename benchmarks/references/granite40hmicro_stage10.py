"""Plain reference for the ``granite40hmicro_stage10`` configuration.

Granite-4.0-H-Micro (``model_type`` ``granitemoehybrid``, dense:
``num_local_experts`` 0; config.json at
https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/config.json):
Mamba-2 state-space blocks (Dao & Gu 2024, arXiv:2405.21060) around
grouped-query attention blocks without positions, a gated MLP in every
block, RMS norms, four constant multipliers and a head tied to the
embedding. Straightforward ``jax.numpy`` in float32 with every product
at ``Precision.HIGHEST``. It imports nothing of the program and is
handed nothing the program made.

With ``e`` = ``embedding_multiplier``, ``ρ`` = ``residual_multiplier``:

    h = e · E[ids]
    block:  h += ρ · mixer(rms(h; attn_norm));  h += ρ · mlp(rms(h; ffn_norm))
    mlp(u) = (silu(u·Wg) ⊙ u·Wu)·Wd
    logits = rms(h; norm) · Eᵀ / logits_scaling       loss = mean cross-entropy

``mixer`` = attention: q = u·Wq (32 heads of 64), k = u·Wk, v = u·Wv
(8 heads of 64), no bias, no positions; causal softmax of
``q·kᵀ · attention_multiplier``; key/value head j serves query heads
4j .. 4j+3 (each key/value head repeated); ``·Wo``.

``mixer`` = Mamba-2: ``[z | xBC | dt] = u·Win``; ``xBC`` through a
causal depthwise convolution of 4 taps with bias, then SiLU; split into
x (64 heads of 64), B and C (one group of 128); ``Δ = softplus(dt +
dt_bias)``, ``A = -exp(A_log)``; per head, from a zero state,
``S_t = exp(Δ_t A)·S_{t-1} + Δ_t·x_t B_tᵀ``, ``y_t = S_t C_t + D·x_t``;
``rms(y ⊙ silu(z); gate_norm)`` over all 4,096 features; ``·Wout``.
**The recurrence is computed here in its full decay-masked form, not
chunked**: ``y = (L ∘ C Bᵀ)·(Δ x) + D x`` with ``L[t, s] =
exp(Σ_{s<r≤t} Δ_r A)`` for ``s ≤ t``, the whole row of ``t`` keys for a
block of 128 queries at a time (512 in attention); no state is passed
anywhere.

To fit beside its own float32 weights, gradients and Adam's moments
(12.35 GB), ``jax.checkpoint`` is around each block of the stack, each
block of queries and each block of the loss's rows. A block is written
for one row and takes the batch's rows through one copy of its code
(``jax.vmap``): compiled for a described v5e the step's temporaries are
5.1 GiB and its executable 322 MB, 69 MB as the compile cache keeps it;
with the rows unrolled they were 4.2 GiB and 619 MB (122 MB kept, which
beside the program's 68 MB is more than a 192 MiB cache holds, so that
every run compiled both again), as a loop over the rows 7.5 GiB.

Departures from the published description, shared with the program:
- the MLP's fused input matrix (``shared_mlp.input_linear``,
  ``[2048, 16384]``) is kept as its two halves ``Wg`` and ``Wu``: the
  same model under a split of the columns;
- ``head_dim`` is ``hidden_size / num_attention_heads`` = 64;
- the initial values (``assumed`` in the configuration's file) are the
  Mamba-2 reference implementation's, not in config.json;
- one pipeline stage's ten layers (the first period of ``layer_types``)
  between the first stage's embedding and the last stage's head, an
  eighth of the vocabulary's rows (``deployment``).

Ids are ``[batch, time]`` whole numbers, labels the same one position
on. Leaves are ``<layer index>/<param>`` as the configuration's file
states.
"""

import math

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
QUERY_BLOCK = 512          # attention: queries a block
SCAN_QUERY_BLOCK = 128     # state space: [heads, 128, t] float32 a copy
LOSS_BLOCK = 1024


def _sizes(cfg):
    h, p = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    g, n = cfg["mamba_n_groups"], cfg["mamba_d_state"]
    return {
        "d": cfg["hidden_size"], "ff": cfg["shared_intermediate_size"],
        "qh": cfg["num_attention_heads"], "kvh": cfg["num_key_value_heads"],
        "hd": cfg["attention"]["head_dim"],
        "h": h, "p": p, "g": g, "n": n, "inner": h * p,
        "conv": h * p + 2 * g * n, "taps": cfg["mamba_d_conv"],
        "kinds": cfg["layer_types"], "vocab": cfg["vocab_size"],
        "eps": cfg["rms_norm_eps"], "e": cfg["embedding_multiplier"],
        "rho": cfg["residual_multiplier"],
        "att": cfg["attention_multiplier"], "ls": cfg["logits_scaling"],
    }


def init(cfg, key):
    """Weights from ``key`` in float32: every matrix normal(0,
    ``init.std``), unit gains; a state-space layer's convolution
    uniform on ±1/√taps with zero bias, ``D`` = 1, ``A_log = log a``
    with ``a`` uniform on [1, 16], ``dt_bias = softplus⁻¹(Δ₀)`` with
    ``Δ₀`` log-uniform on [0.001, 0.1]. One traceable function."""
    s = _sizes(cfg)
    std = cfg["init"]["std"]
    count = [0]

    def fresh():
        count[0] += 1
        return jax.random.fold_in(key, count[0])

    def normal(shape):
        return jax.random.normal(fresh(), shape, jnp.float32) * std

    def uniform(shape, lo, hi):
        return jax.random.uniform(fresh(), shape, jnp.float32, lo, hi)

    ones = lambda n: jnp.ones((n,), jnp.float32)  # noqa: E731
    d, taps = s["d"], s["taps"]
    params = {"0": {"W": normal((s["vocab"], d))}}
    for i, kind in enumerate(s["kinds"]):
        leaves = {"attn_norm": ones(d)}
        if kind == "mamba":
            dt0 = jnp.exp(uniform((s["h"],), math.log(1e-3), math.log(1e-1)))
            leaves.update(
                Win=normal((d, s["inner"] + s["conv"] + s["h"])),
                conv_W=uniform((taps, s["conv"]), -taps ** -0.5,
                               taps ** -0.5),
                conv_b=jnp.zeros((s["conv"],), jnp.float32),
                dt_bias=dt0 + jnp.log(-jnp.expm1(-dt0)),
                A_log=jnp.log(uniform((s["h"],), 1.0, 16.0)),
                D=ones(s["h"]), gate_norm=ones(s["inner"]),
                Wout=normal((s["inner"], d)))
        else:
            leaves.update(
                Wq=normal((d, s["qh"] * s["hd"])),
                Wk=normal((d, s["kvh"] * s["hd"])),
                Wv=normal((d, s["kvh"] * s["hd"])),
                Wo=normal((s["qh"] * s["hd"], d)))
        leaves.update(ffn_norm=ones(d), Wg=normal((d, s["ff"])),
                      Wu=normal((d, s["ff"])), Wd=normal((s["ff"], d)))
        params[str(1 + i)] = leaves
    params[str(1 + len(s["kinds"]))] = {"norm": ones(d)}
    return params, {name: {} for name in params}


def _exact(a):
    return a


_exact.grad = _exact


def _rms(x, gamma, eps):
    return x * lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * gamma


def _query_blocks(t, rows, block):
    """``rows(start, block)`` for each block of ``block`` queries (all
    ``t`` at once where it does not divide them), recomputed in the
    backward pass; ``[t, ...]``."""
    block = block if t % block == 0 else t
    out = lax.map(jax.checkpoint(lambda start: rows(start, block)),
                  jnp.arange(0, t, block))
    return out.reshape((t,) + out.shape[2:])


def _attention(s, p, u, mm, q):
    """One row ``u`` ``[t, d]``."""
    t = u.shape[0]
    qh, kvh, hd = s["qh"], s["kvh"], s["hd"]
    qs = mm(u, p["Wq"]).reshape(t, qh, hd)
    # each key/value head repeated for the query heads it serves
    ks = jnp.repeat(mm(u, p["Wk"]).reshape(t, kvh, hd), qh // kvh, axis=1)
    vs = jnp.repeat(mm(u, p["Wv"]).reshape(t, kvh, hd), qh // kvh, axis=1)

    def rows(start, block):
        qb = lax.dynamic_slice_in_dim(qs, start, block, axis=0)
        sc = q.grad(jnp.einsum("qhd,khd->hqk", q(qb), q(ks),
                               precision=HIGHEST)) * s["att"]
        seen = (jnp.arange(t)[None, :]
                <= (start + jnp.arange(block))[:, None])
        w = jax.nn.softmax(jnp.where(seen[None], sc, -jnp.inf), axis=-1)
        return q.grad(jnp.einsum("hqk,khd->qhd", q(w), q(vs),
                                 precision=HIGHEST))

    return mm(_query_blocks(t, rows, QUERY_BLOCK).reshape(t, qh * hd),
              p["Wo"])


def _state_space(s, p, u, mm, q):
    """One row ``u`` ``[t, d]``: the recurrence as the full
    decay-masked product over all ``t`` keys."""
    t = u.shape[0]
    h, hp, g, n, inner = s["h"], s["p"], s["g"], s["n"], s["inner"]
    zxd = mm(u, p["Win"])
    z, xbc, dt = (zxd[:, :inner], zxd[:, inner:inner + s["conv"]],
                  zxd[:, inner + s["conv"]:])
    taps = s["taps"]
    padded = jnp.pad(xbc, ((taps - 1, 0), (0, 0)))
    xbc = jax.nn.silu(
        sum(padded[k:k + t] * p["conv_W"][k] for k in range(taps))
        + p["conv_b"])
    x = xbc[:, :inner].reshape(t, h, hp)
    b_in = xbc[:, inner:inner + g * n].reshape(t, g, n)
    c_in = xbc[:, inner + g * n:].reshape(t, g, n)
    delta = jax.nn.softplus(dt + p["dt_bias"])                   # [t, h]
    cum = jnp.cumsum(delta * -jnp.exp(p["A_log"]), axis=0)       # [t, h]
    xdt = (x * delta[..., None]).reshape(t, g, h // g, hp)

    def rows(start, block):
        cb = q.grad(jnp.einsum(
            "qgn,sgn->gqs",
            q(lax.dynamic_slice_in_dim(c_in, start, block, axis=0)),
            q(b_in), precision=HIGHEST))
        mine = lax.dynamic_slice_in_dim(cum, start, block, axis=0)
        seen = (jnp.arange(t)[None, :]
                <= (start + jnp.arange(block))[:, None])
        decay = jnp.exp(jnp.where(
            seen[..., None], mine[:, None, :] - cum[None, :, :],
            -jnp.inf))                                        # [q, s, h]
        m = jnp.transpose(decay, (2, 0, 1)).reshape(
            g, h // g, block, t) * cb[:, None]
        return q.grad(jnp.einsum("grqs,sgrp->qgrp", q(m), q(xdt),
                                 precision=HIGHEST))

    y = (_query_blocks(t, rows, SCAN_QUERY_BLOCK).reshape(t, h, hp)
         + p["D"][:, None] * x)
    y = _rms(y.reshape(t, inner) * jax.nn.silu(z), p["gate_norm"], s["eps"])
    return mm(y, p["Wout"])


def _block(s, kind, p, h, mm, q):
    mixer = _state_space if kind == "mamba" else _attention
    h = h + s["rho"] * mixer(s, p, _rms(h, p["attn_norm"], s["eps"]), mm, q)
    f = _rms(h, p["ffn_norm"], s["eps"])
    return h + s["rho"] * mm(
        jax.nn.silu(mm(f, p["Wg"])) * mm(f, p["Wu"]), p["Wd"])


def _loss_sum(s, h, embed, labels, mm):
    """Cross-entropy summed over the rows ``h`` ``[rows, d]``, a block
    of rows at a time."""
    t = h.shape[0]
    block = LOSS_BLOCK if t % LOSS_BLOCK == 0 else t

    @jax.checkpoint
    def part(hb, lb):
        logp = jax.nn.log_softmax(mm(hb, embed.T) / s["ls"], axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, lb[:, None], axis=-1))

    total, _ = lax.scan(
        lambda acc, per: (acc + part(*per), None),
        jnp.zeros((), jnp.float32),
        (h.reshape(-1, block, h.shape[-1]), labels.reshape(-1, block)))
    return total


def loss(cfg, params, state, x, y, q=_exact):
    """Mean cross-entropy of the next id over every position. ``q``
    rounds the operands of every product, and ``q.grad`` the cotangent
    that comes back to its result: both the identity for the
    reference, a lower precision for the control."""
    s = _sizes(cfg)
    ids, labels = x.astype(jnp.int32), y.astype(jnp.int32)

    def mm(a, w):
        return q.grad(jnp.matmul(q(a), q(w), precision=HIGHEST))

    embed = params["0"]["W"]
    h = s["e"] * embed[ids]
    for i, kind in enumerate(s["kinds"]):
        # every row through one copy of the block's code, recomputed in
        # the backward pass
        block = jax.checkpoint(jax.vmap(
            lambda p, row, kind=kind: _block(s, kind, p, row, mm, q),
            in_axes=(None, 0)))
        h = block(params[str(1 + i)], h)
    last = params[str(1 + len(s["kinds"]))]
    total = _loss_sum(
        s, _rms(h, last["norm"], s["eps"]).reshape(-1, h.shape[-1]),
        embed, labels.reshape(-1), mm)
    return total / labels.size, state
