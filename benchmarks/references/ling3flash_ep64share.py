"""Plain reference for the ``ling3flash_ep64share`` configuration.

Ling-3.0-flash (``model_type`` ``bailing_hybrid``; config.json at
https://huggingface.co/inclusionAI/Ling-3.0-flash/blob/main/config.json):
a decoder whose mixers are Kimi Delta Attention (Kimi Linear,
arXiv:2510.26692) in five layers of six and multi-head latent attention
without a query bottleneck in the sixth, a gated MLP in the two leading
layers and sigmoid top-8 routed experts chosen by group beside one
shared expert after them, RMS norms, an untied head. Straightforward
``jax.numpy`` in float32 with every product at ``Precision.HIGHEST``.
It imports nothing of the program and is handed nothing the program
made.

Kimi Delta Attention, per head, **as the recurrence, one position at a
time** (no chunk form):

    q, k, v = silu(conv4(u·Wqkv)) split in three; q, k over their L2 norm, q / √128
    β = sigmoid(u·Wb)       g = lower · sigmoid(exp(A_log) · (u·Wf + dt_bias))
    S_t = S_{t-1} ⊙ exp(g_t) (by key channel);  S_t += β_t k_t (v_t - S_tᵀ k_t)ᵀ
    o_t = S_tᵀ q_t          y = (rms(o) ⊙ sigmoid(u·Wo_gate))·Wo

Latent attention at its own widths (query and key 128 + 64 rotary,
value 128; no padding): q = rms_head(u·Wq) with the rotary turn on its
last 64, ``[c_kv | k_pe] = u·Wkva``, ``kv = rms(c_kv)·Wkvb``, the masked
softmax of ``q·kᵀ/√192`` a block of queries at a time, each head's
output times ``sigmoid(u·Wga)``, then ``·Wo``.

The experts: ``s = sigmoid(u·router)`` over all 512; a group of 64
scores the sum of its two best ``s + route_bias``; among the 4 best
groups the top 8 of ``s + route_bias``; ``w = s / Σ s · 2.5``; the held
experts as a dense loop with a one-hot combine, plus the shared expert.

The share (``deployment``): this chip holds experts ``held_experts``
and ``vocab_size`` rows; what the absent experts would add is left out
and the loss is over the held rows. To fit beside its own float32
weights, gradients and Adam's moments (14.15 GB) every block takes the
rows through one copy of its code (``jax.vmap``) under
``jax.checkpoint``, the recurrence keeps its state every 64 positions
and recomputes between, queries, held experts and the loss's rows go a
block at a time under ``jax.checkpoint``.

Departures from the published description, shared with the program:
the ``assumed`` readings of the configuration's file (the gate's
formula, the initial values, the query norm, the head gate, half-split
rotary pairs, the selection bias at zero); one pipeline stage's seven
layers; no prediction module (its loss weight is 0).

Ids are ``[batch, time]`` whole numbers, labels the same one position
on. Leaves are ``<layer index>/<param>`` as the configuration's file
states.
"""

import math

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
QUERY_BLOCK = 512
STATE_EVERY = 64
LOSS_BLOCK = 1024


def _sizes(cfg):
    dep = cfg["deployment"]
    h, dk = cfg["num_attention_heads"], cfg["head_dim"]
    first = dep["first_layer"]
    layers = range(first, first + cfg["num_hidden_layers"])
    return {
        "d": cfg["hidden_size"], "h": h, "dk": dk, "w": h * dk,
        "taps": cfg["short_conv_kernel_size"],
        "lower": float(cfg["kda_lower_bound"]),
        "kvr": cfg["kv_lora_rank"], "nope": cfg["qk_nope_head_dim"],
        "rope": cfg["qk_rope_head_dim"], "vd": cfg["v_head_dim"],
        "ff": cfg["intermediate_size"], "ef": cfg["moe_intermediate_size"],
        "held": cfg["num_experts"], "first": dep["held_experts"][0],
        "experts": dep["router_experts"], "k": cfg["num_experts_per_tok"],
        "groups": cfg["n_group"], "kept": cfg["topk_group"],
        "shared": cfg["num_shared_experts"],
        "scaling": cfg["routed_scaling_factor"],
        "kinds": [("mla" if (i + 1) % cfg["layer_group_size"] == 0
                   else "kda", i < cfg["first_k_dense_replace"])
                  for i in layers],
        "vocab": cfg["vocab_size"], "eps": cfg["rms_norm_eps"],
        "theta": float(cfg["rope_theta"]),
    }


def init(cfg, key):
    """Weights from ``key`` in float32: every matrix normal(0,
    ``init.std``), unit gains; a delta-rule layer's convolution uniform
    on ±1/√taps, ``A_log = log a`` with ``a`` uniform on [1, 16],
    ``dt_bias = softplus⁻¹(Δ₀)`` with ``Δ₀`` log-uniform on [0.001,
    0.1]. One traceable function."""
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
    d, h, w, taps = s["d"], s["h"], s["w"], s["taps"]
    qk = s["nope"] + s["rope"]
    params = {"0": {"W": normal((s["vocab"], d))}}
    state = {"0": {}}
    for i, (kind, dense) in enumerate(s["kinds"]):
        leaves = {"attn_norm": ones(d), "ffn_norm": ones(d)}
        if kind == "kda":
            dt0 = jnp.exp(uniform((w,), math.log(1e-3), math.log(1e-1)))
            leaves.update(
                Wqkv=normal((d, 3 * w)),
                conv_W=uniform((taps, 3 * w), -taps ** -0.5, taps ** -0.5),
                Wb=normal((d, h)), Wf=normal((d, w)),
                A_log=jnp.log(uniform((h,), 1.0, 16.0)),
                dt_bias=dt0 + jnp.log(-jnp.expm1(-dt0)),
                Wo_gate=normal((d, w)), o_norm=ones(s["dk"]),
                Wo=normal((w, d)))
        else:
            leaves.update(
                Wq=normal((d, h * qk)), q_head_norm=ones(qk),
                Wkva=normal((d, s["kvr"] + s["rope"])),
                kv_norm=ones(s["kvr"]),
                Wkvb=normal((s["kvr"], h * (s["nope"] + s["vd"]))),
                Wo=normal((h * s["vd"], d)), Wga=normal((d, h)))
        if dense:
            leaves.update(Wg=normal((d, s["ff"])), Wu=normal((d, s["ff"])),
                          Wd=normal((s["ff"], d)))
        else:
            ef, fs = s["ef"], s["ef"] * s["shared"]
            leaves.update(
                router=normal((d, s["experts"])),
                Eg=normal((s["held"], d, ef)), Eu=normal((s["held"], d, ef)),
                Ed=normal((s["held"], ef, d)),
                Sg=normal((d, fs)), Su=normal((d, fs)), Sd=normal((fs, d)))
        params[str(1 + i)] = leaves
        state[str(1 + i)] = ({} if dense else {
            "route_bias": jnp.zeros((s["experts"],), jnp.float32)})
    last = str(1 + len(s["kinds"]))
    params[last] = {"norm": ones(d), "W": normal((d, s["vocab"]))}
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


def _unit(x):
    return x * lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True)
                         + 1e-6)


def _delta_rule(q_, k, v, g, beta, q):
    """``o`` ``[t, h, dv]`` of the recurrence from a zero state, one
    position at a time; the state kept every ``STATE_EVERY``
    positions."""
    t, h, dk = k.shape
    every = STATE_EVERY if t % STATE_EVERY == 0 else t

    def step(st, per):
        qt, kt, vt, gt, bt = per
        st = st * jnp.exp(gt)[..., None]
        seen = q.grad(jnp.einsum("hk,hkv->hv", q(kt), q(st),
                                 precision=HIGHEST))
        st = st + bt[:, None, None] * kt[..., None] * (vt - seen)[:, None]
        return st, q.grad(jnp.einsum("hkv,hk->hv", q(st), q(qt),
                                     precision=HIGHEST))

    @jax.checkpoint
    def stretch(st, per):
        return lax.scan(step, st, per)

    per = tuple(a.reshape((t // every, every) + a.shape[1:])
                for a in (q_, k, v, g, beta))
    _, o = lax.scan(stretch, jnp.zeros((h, dk, v.shape[-1]), jnp.float32),
                    per)
    return o.reshape(t, h, v.shape[-1])


def _kda(s, p, u, mm, q):
    """One row ``u`` ``[t, d]``."""
    t = u.shape[0]
    h, dk, w, taps = s["h"], s["dk"], s["w"], s["taps"]
    padded = jnp.pad(mm(u, p["Wqkv"]), ((taps - 1, 0), (0, 0)))
    qkv = jax.nn.silu(sum(padded[i:i + t] * p["conv_W"][i]
                          for i in range(taps)))
    qs, ks, vs = (qkv[:, i * w:(i + 1) * w].reshape(t, h, dk)
                  for i in range(3))
    beta = jax.nn.sigmoid(mm(u, p["Wb"]))
    g = s["lower"] * jax.nn.sigmoid(
        jnp.repeat(jnp.exp(p["A_log"]), dk) * (mm(u, p["Wf"]) + p["dt_bias"]))
    o = _delta_rule(_unit(qs) / math.sqrt(dk), _unit(ks), vs,
                    g.reshape(t, h, dk), beta, q)
    o = _rms(o, p["o_norm"], s["eps"]).reshape(t, w) * jax.nn.sigmoid(
        mm(u, p["Wo_gate"]))
    return mm(o, p["Wo"])


def _mla(s, p, u, mm, q):
    """One row ``u`` ``[t, d]``."""
    t = u.shape[0]
    h, nope, rope, vd = s["h"], s["nope"], s["rope"], s["vd"]
    qh = _rms(mm(u, p["Wq"]).reshape(t, h, nope + rope), p["q_head_norm"],
              s["eps"])
    qh = jnp.concatenate([qh[..., :nope], _rope(qh[..., nope:], s["theta"])],
                         axis=-1)
    ckv = mm(u, p["Wkva"])
    k_pe = _rope(ckv[:, None, s["kvr"]:], s["theta"])          # [t, 1, r]
    kv = mm(_rms(ckv[:, :s["kvr"]], p["kv_norm"], s["eps"]),
            p["Wkvb"]).reshape(t, h, nope + vd)
    kh = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe, (t, h, rope))], axis=-1)
    vh = kv[..., nope:]
    scale = 1.0 / math.sqrt(nope + rope)
    block = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t

    @jax.checkpoint
    def rows(start):
        qb = lax.dynamic_slice_in_dim(qh, start, block, axis=0)
        sc = q.grad(jnp.einsum("qhd,khd->hqk", q(qb), q(kh),
                               precision=HIGHEST)) * scale
        seen = (jnp.arange(t)[None, :]
                <= (start + jnp.arange(block))[:, None])
        wt = jax.nn.softmax(jnp.where(seen[None], sc, -jnp.inf), axis=-1)
        return q.grad(jnp.einsum("hqk,khd->qhd", q(wt), q(vh),
                                 precision=HIGHEST))

    o = lax.map(rows, jnp.arange(0, t, block)).reshape(t, h, vd)
    o = o * jax.nn.sigmoid(mm(u, p["Wga"]))[..., None]
    return mm(o.reshape(t, h * vd), p["Wo"])


def _gated(x, wg, wu, wd, mm):
    return mm(jax.nn.silu(mm(x, wg)) * mm(x, wu), wd)


def _chosen(s, score, bias):
    """The experts each token takes: the top ``k`` of ``score + bias``
    among the ``kept`` groups whose two best are largest."""
    sel = lax.stop_gradient(score) + bias
    n = sel.shape[0]
    grouped = sel.reshape(n, s["groups"], -1)
    two_best = jnp.sort(grouped, axis=-1)[..., -2:].sum(-1)
    kept_rank = jnp.argsort(jnp.argsort(-two_best, axis=-1), axis=-1)
    allowed = (kept_rank < s["kept"])[..., None]
    _, chosen = lax.top_k(
        jnp.where(allowed, grouped, -jnp.inf).reshape(sel.shape), s["k"])
    return chosen


def _experts(s, p, x, mm, bias):
    """One row ``x`` ``[t, d]``."""
    score = jax.nn.sigmoid(mm(x, p["router"]))
    chosen = _chosen(s, score, bias)
    w = jnp.take_along_axis(score, chosen, axis=-1)
    w = w / jnp.sum(w, axis=-1, keepdims=True) * s["scaling"]

    def one(y, per):
        e, wg, wu, wd = per
        mine = jnp.sum(jnp.where(chosen == e + s["first"], w, 0.0), axis=-1)
        return y + mine[:, None] * jax.checkpoint(
            lambda t_, a, b_, c: _gated(t_, a, b_, c, mm))(
                x, wg, wu, wd), None

    y = _gated(x, p["Sg"], p["Su"], p["Sd"], mm)
    y, _ = lax.scan(one, y, (jnp.arange(s["held"]), p["Eg"], p["Eu"],
                             p["Ed"]))
    return y


def _block(s, kind, dense, p, bias, h, mm, q):
    mixer = _kda if kind == "kda" else _mla
    h = h + mixer(s, p, _rms(h, p["attn_norm"], s["eps"]), mm, q)
    f = _rms(h, p["ffn_norm"], s["eps"])
    if dense:
        return h + _gated(f, p["Wg"], p["Wu"], p["Wd"], mm)
    return h + _experts(s, p, f, mm, bias)


def _loss_sum(h, w, labels, mm):
    """Cross-entropy summed over the rows ``h`` ``[rows, d]``, a block
    of rows at a time."""
    n = h.shape[0]
    block = LOSS_BLOCK if n % LOSS_BLOCK == 0 else n

    @jax.checkpoint
    def part(hb, lb):
        logp = jax.nn.log_softmax(mm(hb, w), axis=-1)
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

    h = params["0"]["W"][ids]
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
        last["W"], labels.reshape(-1), mm)
    return total / labels.size, state
