"""Plain reference for the ``glm47flash_ep8share`` configuration.

GLM-4.7-Flash (``model_type`` ``glm4_moe_lite``; config.json at
https://huggingface.co/zai-org/GLM-4.7-Flash/blob/main/config.json), a
decoder of the DeepSeek-V3 family: multi-head latent attention, one
leading dense layer, expert layers with sigmoid top-k routing
(``noaux_tc``, normalised, scaled) beside one shared expert, RMS norms,
rotary positions, an untied head and one multi-token-prediction module
(DeepSeek-V3 report, arXiv:2412.19437, §2.1 and §2.2). Straightforward
``jax.numpy`` in float32 with every product at ``Precision.HIGHEST``:
attention is the masked softmax a block of queries at a time, the
experts a dense loop over the held ones with a one-hot combine (every
held expert computes every token; no sort, no gather of rows), the loss
a block of rows at a time, ``jax.checkpoint`` around each block of
layers so that the step fits the chip. It imports nothing of the
program and is handed nothing the program made.

The share (``deployment`` in the configuration's file): each layer is
divided over 8 chips. This chip holds experts ``held_experts`` of the
``router_experts`` the router scores, and ``vocab_size`` rows of the
vocabulary; attention and the shared expert are whole. An expert layer
gives ``shared(x) + Σ w_e·E_e(x)`` over the chosen experts held here:
what the absent ones would add is left out, and that partial result is
what goes on to the next layer. The loss is over the held rows.

Departures from the published description, shared with the program:
- the prediction module takes the trunk's output before the final
  norm as ``h`` and concatenates ``[rms(h) ; rms(Emb(t_{i+1}))]`` in
  the report's order (its equation 21); released inference code
  concatenates the two the other way round, which is the same module
  under a permutation of ``proj``'s rows;
- rotary pairs are ``(i, i + 32)`` of the 64 rotated features (the
  half-split convention); released checkpoints store the interleaved
  one and permute on load;
- the router's selection bias (state, not trained) starts at zero and
  stays there: its update rule is not in config.json and is left out;
- the loss weight of the module (0.3) is the report's early-training
  value, not in config.json.

Ids are ``[batch, time]`` whole numbers, labels ``[batch, time + 1]``:
the next id and the one after it. Leaves are ``<layer index>/<param>``
as the configuration's file states.
"""

import math

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
QUERY_BLOCK = 512
LOSS_BLOCK = 1024


def _sizes(cfg):
    dep = cfg["deployment"]
    first = dep["held_experts"][0]
    return {
        "d": cfg["hidden_size"], "h": cfg["num_attention_heads"],
        "qr": cfg["q_lora_rank"], "kvr": cfg["kv_lora_rank"],
        "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
        "vd": cfg["v_head_dim"], "ff": cfg["intermediate_size"],
        "ef": cfg["moe_intermediate_size"],
        "held": cfg["n_routed_experts"], "first": first,
        "experts": dep["router_experts"], "k": cfg["num_experts_per_tok"],
        "shared": cfg["n_shared_experts"],
        "scaling": cfg["routed_scaling_factor"],
        "dense": cfg["first_k_dense_replace"],
        "layers": cfg["num_hidden_layers"],
        "modules": cfg["num_nextn_predict_layers"],
        "vocab": cfg["vocab_size"], "eps": cfg["rms_norm_eps"],
        "theta": float(cfg["rope_theta"]),
    }


def _attention_leaves(s, normal, ones):
    qk = s["nope"] + s["rope"]
    return {
        "attn_norm": ones(s["d"]),
        "Wqa": normal((s["d"], s["qr"])), "q_norm": ones(s["qr"]),
        "Wqb": normal((s["qr"], s["h"] * qk)),
        "Wkva": normal((s["d"], s["kvr"] + s["rope"])),
        "kv_norm": ones(s["kvr"]),
        "Wkvb": normal((s["kvr"], s["h"] * (s["nope"] + s["vd"]))),
        "Wo": normal((s["h"] * s["vd"], s["d"])),
        "ffn_norm": ones(s["d"]),
    }


def _expert_leaves(s, normal):
    d, ef, fs = s["d"], s["ef"], s["ef"] * s["shared"]
    return {
        "router": normal((d, s["experts"])),
        "Eg": normal((s["held"], d, ef)), "Eu": normal((s["held"], d, ef)),
        "Ed": normal((s["held"], ef, d)),
        "Sg": normal((d, fs)), "Su": normal((d, fs)), "Sd": normal((fs, d)),
    }


def init(cfg, key):
    """Weights from ``key`` in float32: every matrix normal(0,
    ``init.std``), unit gains. One traceable function."""
    s = _sizes(cfg)
    std = cfg["init"]["std"]
    count = [0]

    def normal(shape):
        count[0] += 1
        return jax.random.normal(
            jax.random.fold_in(key, count[0]), shape, jnp.float32) * std

    ones = lambda n: jnp.ones((n,), jnp.float32)  # noqa: E731
    params = {"0": {"W": normal((s["vocab"], s["d"]))}}
    for i in range(s["layers"]):
        leaves = _attention_leaves(s, normal, ones)
        if i < s["dense"]:
            leaves.update(Wg=normal((s["d"], s["ff"])),
                          Wu=normal((s["d"], s["ff"])),
                          Wd=normal((s["ff"], s["d"])))
        else:
            leaves.update(_expert_leaves(s, normal))
        params[str(1 + i)] = leaves
    last = {"norm": ones(s["d"]), "W": normal((s["d"], s["vocab"]))}
    if s["modules"]:
        module = {**_attention_leaves(s, normal, ones),
                  **_expert_leaves(s, normal)}
        last.update({"mtp_" + k: v for k, v in module.items()})
        last.update(mtp_hnorm=ones(s["d"]), mtp_enorm=ones(s["d"]),
                    mtp_proj=normal((2 * s["d"], s["d"])),
                    mtp_norm=ones(s["d"]))
    params[str(1 + s["layers"])] = last
    bias = lambda: jnp.zeros((s["experts"],), jnp.float32)  # noqa: E731
    state = {name: {} if int(name) <= s["dense"] else {"route_bias": bias()}
             for name in params}
    state[str(1 + s["layers"])] = (
        {"mtp_route_bias": bias()} if s["modules"] else {})
    return params, state


def _exact(a):
    return a


_exact.grad = _exact


def _rms(x, gamma, eps):
    return x * lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * gamma


def _rope(x, theta):
    """Rotary positions over the whole last axis of ``[b, t, ..., r]``,
    pairs ``(i, i + r/2)``."""
    t, r = x.shape[1], x.shape[-1]
    freq = theta ** (-jnp.arange(r // 2, dtype=jnp.float32) * 2.0 / r)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * freq[None, :]
    shape = (1, t) + (1,) * (x.ndim - 3) + (r // 2,)
    cos, sin = jnp.cos(angle).reshape(shape), jnp.sin(angle).reshape(shape)
    a, b = x[..., :r // 2], x[..., r // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _attention(s, p, x, mm, q):
    b, t, _ = x.shape
    h, nope, rope, vd = s["h"], s["nope"], s["rope"], s["vd"]
    cq = _rms(mm(x, p["Wqa"]), p["q_norm"], s["eps"])
    qh = mm(cq, p["Wqb"]).reshape(b, t, h, nope + rope)
    qh = jnp.concatenate(
        [qh[..., :nope], _rope(qh[..., nope:], s["theta"])], axis=-1)
    ckv = mm(x, p["Wkva"])
    k_pe = _rope(ckv[..., None, s["kvr"]:], s["theta"])      # [b, t, 1, r]
    kv = mm(_rms(ckv[..., :s["kvr"]], p["kv_norm"], s["eps"]),
            p["Wkvb"]).reshape(b, t, h, nope + vd)
    kh = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe, (b, t, h, rope))], axis=-1)
    vh = kv[..., nope:]
    scale = 1.0 / math.sqrt(nope + rope)
    block = min(QUERY_BLOCK, t)
    if t % block:
        block = t
    starts = jnp.arange(0, t, block)

    @jax.checkpoint
    def rows(start):
        qb = lax.dynamic_slice_in_dim(qh, start, block, axis=1)
        sc = q.grad(jnp.einsum("bqhd,bkhd->bhqk", q(qb), q(kh),
                               precision=HIGHEST)) * scale
        seen = (jnp.arange(t)[None, :]
                <= (start + jnp.arange(block))[:, None])
        w = jax.nn.softmax(jnp.where(seen[None, None], sc, -jnp.inf),
                           axis=-1)
        return q.grad(jnp.einsum("bhqk,bkhd->bqhd", q(w), q(vh),
                                 precision=HIGHEST))

    o = lax.map(rows, starts)                       # [n, b, block, h, vd]
    o = jnp.moveaxis(o, 0, 1).reshape(b, t, h * vd)
    return mm(o, p["Wo"])


def _gated(x, wg, wu, wd, mm):
    return mm(jax.nn.silu(mm(x, wg)) * mm(x, wu), wd)


def _experts(s, p, x, mm, bias):
    tokens = x.reshape(-1, x.shape[-1])
    score = jax.nn.sigmoid(mm(tokens, p["router"]))
    _, chosen = lax.top_k(lax.stop_gradient(score) + bias, s["k"])
    w = jnp.take_along_axis(score, chosen, axis=-1)
    w = w / jnp.sum(w, axis=-1, keepdims=True) * s["scaling"]

    def one(y, per):
        e, wg, wu, wd = per
        mine = jnp.sum(jnp.where(chosen == e + s["first"], w, 0.0), axis=-1)
        return y + mine[:, None] * jax.checkpoint(
            lambda t_, a, b_, c: _gated(t_, a, b_, c, mm))(
                tokens, wg, wu, wd), None

    y = _gated(tokens, p["Sg"], p["Su"], p["Sd"], mm)
    y, _ = lax.scan(one, y, (jnp.arange(s["held"]), p["Eg"], p["Eu"],
                             p["Ed"]))
    return y.reshape(x.shape)


def _block(s, p, x, mm, q, bias=None):
    """A block's output; a dense block has no selection ``bias``."""
    h = x + _attention(s, p, _rms(x, p["attn_norm"], s["eps"]), mm, q)
    f = _rms(h, p["ffn_norm"], s["eps"])
    if bias is None:
        return h + _gated(f, p["Wg"], p["Wu"], p["Wd"], mm)
    return h + _experts(s, p, f, mm, bias)


def _mean_loss(h, w, labels, mm):
    rows = h.reshape(-1, h.shape[-1])
    ids = labels.reshape(-1)
    block = LOSS_BLOCK if rows.shape[0] % LOSS_BLOCK == 0 else rows.shape[0]

    @jax.checkpoint
    def part(hb, lb):
        logp = jax.nn.log_softmax(mm(hb, w), axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, lb[:, None], axis=-1))

    def step(total, per):
        return total + part(*per), None

    total, _ = lax.scan(
        step, jnp.zeros((), jnp.float32),
        (rows.reshape(-1, block, rows.shape[-1]), ids.reshape(-1, block)))
    return total / ids.size


def loss(cfg, params, state, x, y, q=_exact):
    """Mean cross-entropy of the next id over every position, plus
    ``training.mtp_loss_weight`` times the prediction module's of the
    id after it. ``q`` rounds the operands of every product, and
    ``q.grad`` the cotangent that comes back to its result: both the
    identity for the reference, a lower precision for the control."""
    s = _sizes(cfg)
    ids, labels = x.astype(jnp.int32), y.astype(jnp.int32)
    t = ids.shape[1]

    def mm(a, w):
        return q.grad(jnp.matmul(q(a), q(w), precision=HIGHEST))

    h = params["0"]["W"][ids]
    for i in range(s["layers"]):
        name = str(1 + i)
        bias = None if i < s["dense"] else state[name]["route_bias"]
        h = jax.checkpoint(
            lambda p, h_, b: _block(s, p, h_, mm, q, b))(
                params[name], h, bias)
    name = str(1 + s["layers"])
    last = params[name]
    total = _mean_loss(_rms(h, last["norm"], s["eps"]), last["W"],
                       labels[:, :t], mm)
    if s["modules"]:
        @jax.checkpoint
        def module(last, embed, h, bias):
            e = embed[labels[:, :t]]
            joined = jnp.concatenate(
                [_rms(h, last["mtp_hnorm"], s["eps"]),
                 _rms(e, last["mtp_enorm"], s["eps"])], axis=-1)
            p = {k[4:]: v for k, v in last.items() if k.startswith("mtp_")}
            out = _block(s, p, mm(joined, last["mtp_proj"]), mm, q, bias)
            return _rms(out, last["mtp_norm"], s["eps"])

        h2 = module(
            last, params["0"]["W"], h, state[name]["mtp_route_bias"])
        extra = _mean_loss(h2, last["W"], labels[:, 1:], mm)
        total = total + cfg["training"]["mtp_loss_weight"] * extra
    return total, state
