"""Plain reference for the ``lfm2moe24b_ep8share`` configuration.

LFM2-24B-A2B (``model_type`` ``lfm2_moe``; config.json at
https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json): a
decoder whose mixers are gated short convolutions in three layers of
four and grouped-query attention with q/k norms and rotary positions in
the fourth (``layer_types``), a gated MLP in the two leading layers and
sigmoid top-4 routed experts with no shared expert after them, RMS
norms, a head tied to the embedding. Straightforward ``jax.numpy`` in
float32 with every product at ``Precision.HIGHEST``. It imports nothing
of the program and is handed nothing the program made.

With ``rms`` the RMS norm over the last axis times its gain:

    h = E[ids]
    block:  h += mixer(rms(h; attn_norm));  h += ffn(rms(h; ffn_norm))
    logits = rms(h; norm) · Eᵀ                 loss = mean cross-entropy

``mixer`` = short convolution (``Lfm2MoeShortConv.slow_forward``):
``[B | C | x̃] = u·Win``; ``z_t = Σ_j conv_W[j] ⊙ (B ⊙ x̃)_{t-2+j}``,
**three shifted products**, positions before the start read as nought,
no bias, no activation; ``(C ⊙ z)·Wout``.

``mixer`` = attention: q = u·Wq (32 heads of 64), k = u·Wk, v = u·Wv (8
heads of 64), no bias; each q and k head RMS-normed over its 64 features
(gains ``q_norm``, ``k_norm``), then turned by rotary positions over all
64, pairs ``(i, i + 32)``, θ = ``rope_theta``; causal softmax of
``q·kᵀ/√64`` with each key/value head repeated for the 4 query heads it
serves, a block of queries at a time; ``·Wo``.

``ffn`` = the dense MLP ``(silu(u·Wg) ⊙ u·Wu)·Wd`` in the leading layers;
after them the experts: ``s = sigmoid(u·router)`` over all 64, the top 4
of ``s + route_bias`` chosen, ``w = s / (Σ s + 1e-6) · 1``; each held
expert computed over every token and masked by the routing, one at a
time under ``jax.checkpoint``; no shared expert.

The share (``deployment``): this chip holds experts ``held_experts`` and
``vocab_size`` rows; what the absent experts would add is left out and
the loss is over the held rows. To fit beside its own float32 weights,
gradients and Adam's moments (13.32 GB), every block takes the rows
through one copy of its code (``jax.vmap``) under ``jax.checkpoint``;
queries, the dense MLP's positions, held experts and the loss's rows go
a block at a time under ``jax.checkpoint``.

Departures from the published description, shared with the program: the
``assumed`` readings of the configuration's file (the tied head, a head
of 64, the q/k norms' gains, half-split rotary pairs, the selection bias
at zero, the initial values); one pipeline stage's nine layers.

Ids are ``[batch, time]`` whole numbers, labels the same one position
on. Leaves are ``<layer index>/<param>`` as the configuration's file
states.
"""

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
QUERY_BLOCK = 512
MLP_BLOCK = 1024
LOSS_BLOCK = 1024
ROUTE_EPS = 1e-6


def _sizes(cfg):
    dep = cfg["deployment"]
    h = cfg["num_attention_heads"]
    first = dep["first_layer"]
    layers = range(first, first + cfg["num_hidden_layers"])
    return {
        "d": cfg["hidden_size"], "h": h,
        "kvh": cfg["num_key_value_heads"], "hd": cfg["hidden_size"] // h,
        "taps": cfg["conv_L_cache"],
        "ff": cfg["intermediate_size"], "ef": cfg["moe_intermediate_size"],
        "held": cfg["num_experts"], "first": dep["held_experts"][0],
        "experts": dep["router_experts"], "k": cfg["num_experts_per_tok"],
        "scaling": cfg["routed_scaling_factor"],
        "kinds": [(cfg["layer_types"][i], i < cfg["num_dense_layers"])
                  for i in layers],
        "vocab": cfg["vocab_size"], "eps": cfg["norm_eps"],
        "theta": float(cfg["rope_parameters"]["rope_theta"]),
    }


def init(cfg, key):
    """Weights from ``key`` in float32: every matrix normal(0,
    ``init.std``), unit gains, a short convolution's taps uniform on
    ±1/√taps. One traceable function."""
    s = _sizes(cfg)
    std = cfg["init"]["std"]
    count = [0]

    def fresh():
        count[0] += 1
        return jax.random.fold_in(key, count[0])

    def normal(shape):
        return jax.random.normal(fresh(), shape, jnp.float32) * std

    ones = lambda n: jnp.ones((n,), jnp.float32)  # noqa: E731
    d, hd, taps = s["d"], s["hd"], s["taps"]
    params = {"0": {"W": normal((s["vocab"], d))}}
    state = {"0": {}}
    for i, (kind, dense) in enumerate(s["kinds"]):
        leaves = {"attn_norm": ones(d), "ffn_norm": ones(d)}
        if kind == "conv":
            leaves.update(
                Win=normal((d, 3 * d)),
                conv_W=jax.random.uniform(fresh(), (taps, d), jnp.float32,
                                          -taps ** -0.5, taps ** -0.5),
                Wout=normal((d, d)))
        else:
            leaves.update(
                Wq=normal((d, s["h"] * hd)), Wk=normal((d, s["kvh"] * hd)),
                Wv=normal((d, s["kvh"] * hd)), Wo=normal((s["h"] * hd, d)),
                q_norm=ones(hd), k_norm=ones(hd))
        if dense:
            leaves.update(Wg=normal((d, s["ff"])), Wu=normal((d, s["ff"])),
                          Wd=normal((s["ff"], d)))
        else:
            ef = s["ef"]
            leaves.update(
                router=normal((d, s["experts"])),
                Eg=normal((s["held"], d, ef)), Eu=normal((s["held"], d, ef)),
                Ed=normal((s["held"], ef, d)))
        params[str(1 + i)] = leaves
        state[str(1 + i)] = ({} if dense else {
            "route_bias": jnp.zeros((s["experts"],), jnp.float32)})
    last = str(1 + len(s["kinds"]))
    params[last] = {"norm": ones(d)}
    state[last] = {}
    return params, state


def _exact(a):
    return a


_exact.grad = _exact


def _rms(x, gamma, eps):
    return x * lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * gamma


def _rope(x, theta):
    """Rotary positions over the whole last axis of ``[t, ..., r]``,
    pairs ``(i, i + r/2)``."""
    t, r = x.shape[0], x.shape[-1]
    freq = theta ** (-jnp.arange(r // 2, dtype=jnp.float32) * 2.0 / r)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * freq[None, :]
    shape = (t,) + (1,) * (x.ndim - 2) + (r // 2,)
    cos, sin = jnp.cos(angle).reshape(shape), jnp.sin(angle).reshape(shape)
    a, b = x[..., :r // 2], x[..., r // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _blocks(t, rows, block):
    """``rows(start, block)`` for each block of ``block`` positions (all
    ``t`` at once where it does not divide them), recomputed in the
    backward pass; ``[t, ...]``."""
    block = block if t % block == 0 else t
    out = lax.map(jax.checkpoint(lambda start: rows(start, block)),
                  jnp.arange(0, t, block))
    return out.reshape((t,) + out.shape[2:])


def _short_conv(s, p, u, mm, q):
    """One row ``u`` ``[t, d]``."""
    t, d, taps = u.shape[0], s["d"], s["taps"]
    bcx = mm(u, p["Win"])
    b_, c_, x_ = bcx[:, :d], bcx[:, d:2 * d], bcx[:, 2 * d:]
    padded = jnp.pad(b_ * x_, ((taps - 1, 0), (0, 0)))
    z = sum(padded[j:j + t] * p["conv_W"][j] for j in range(taps))
    return mm(c_ * z, p["Wout"])


def _attention(s, p, u, mm, q):
    """One row ``u`` ``[t, d]``."""
    t = u.shape[0]
    h, kvh, hd = s["h"], s["kvh"], s["hd"]
    qs = _rope(_rms(mm(u, p["Wq"]).reshape(t, h, hd), p["q_norm"], s["eps"]),
               s["theta"])
    ks = _rope(_rms(mm(u, p["Wk"]).reshape(t, kvh, hd), p["k_norm"],
                    s["eps"]), s["theta"])
    # each key/value head repeated for the query heads it serves
    ks = jnp.repeat(ks, h // kvh, axis=1)
    vs = jnp.repeat(mm(u, p["Wv"]).reshape(t, kvh, hd), h // kvh, axis=1)

    def rows(start, block):
        qb = lax.dynamic_slice_in_dim(qs, start, block, axis=0)
        sc = q.grad(jnp.einsum("qhd,khd->hqk", q(qb), q(ks),
                               precision=HIGHEST)) * hd ** -0.5
        seen = (jnp.arange(t)[None, :]
                <= (start + jnp.arange(block))[:, None])
        w = jax.nn.softmax(jnp.where(seen[None], sc, -jnp.inf), axis=-1)
        return q.grad(jnp.einsum("hqk,khd->qhd", q(w), q(vs),
                                 precision=HIGHEST))

    return mm(_blocks(t, rows, QUERY_BLOCK).reshape(t, h * hd), p["Wo"])


def _gated(x, wg, wu, wd, mm):
    return mm(jax.nn.silu(mm(x, wg)) * mm(x, wu), wd)


def _dense(s, p, x, mm):
    """One row ``x`` ``[t, d]``, a block of positions at a time."""
    return _blocks(x.shape[0], lambda start, block: _gated(
        lax.dynamic_slice_in_dim(x, start, block, axis=0),
        p["Wg"], p["Wu"], p["Wd"], mm), MLP_BLOCK)


def _experts(s, p, x, mm, bias):
    """One row ``x`` ``[t, d]``."""
    score = jax.nn.sigmoid(mm(x, p["router"]))
    _, chosen = lax.top_k(lax.stop_gradient(score) + bias, s["k"])
    w = jnp.take_along_axis(score, chosen, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + ROUTE_EPS) * s["scaling"]

    def one(y, per):
        e, wg, wu, wd = per
        mine = jnp.sum(jnp.where(chosen == e + s["first"], w, 0.0), axis=-1)
        return y + mine[:, None] * jax.checkpoint(
            lambda t_, a, b_, c: _gated(t_, a, b_, c, mm))(
                x, wg, wu, wd), None

    y, _ = lax.scan(one, jnp.zeros_like(x),
                    (jnp.arange(s["held"]), p["Eg"], p["Eu"], p["Ed"]))
    return y


def _block(s, kind, dense, p, bias, h, mm, q):
    mixer = _short_conv if kind == "conv" else _attention
    h = h + mixer(s, p, _rms(h, p["attn_norm"], s["eps"]), mm, q)
    f = _rms(h, p["ffn_norm"], s["eps"])
    if dense:
        return h + _dense(s, p, f, mm)
    return h + _experts(s, p, f, mm, bias)


def _loss_sum(h, embed, labels, mm):
    """Cross-entropy summed over the rows ``h`` ``[rows, d]``, a block
    of rows at a time, against the tied head ``embedᵀ``."""
    n = h.shape[0]
    block = LOSS_BLOCK if n % LOSS_BLOCK == 0 else n

    @jax.checkpoint
    def part(hb, lb):
        logp = jax.nn.log_softmax(mm(hb, embed.T), axis=-1)
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
    h = embed[ids]
    for i, (kind, dense) in enumerate(s["kinds"]):
        name = str(1 + i)
        bias = state[name].get("route_bias")
        # every row through one copy of the block's code, recomputed in
        # the backward pass
        block = jax.checkpoint(jax.vmap(
            lambda p, b_, row, kind=kind, dense=dense: _block(
                s, kind, dense, p, b_, row, mm, q),
            in_axes=(None, None, 0)))
        h = block(params[name], bias, h)
    last = params[str(1 + len(s["kinds"]))]
    total = _loss_sum(
        _rms(h, last["norm"], s["eps"]).reshape(-1, h.shape[-1]),
        embed, labels.reshape(-1), mm)
    return total / labels.size, state
