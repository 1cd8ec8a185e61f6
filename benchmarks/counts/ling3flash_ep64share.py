"""Operations and bytes of ``ling3flash_ep64share`` from its shapes: the
weight products, the latent-attention layer's causal attention, the
delta rule's chunk products and the held experts' grouped products,
forward and backward; no norm, convolution, gate, rotary turn, softmax,
routing sort or updater, and nothing recomputed (the configuration
recomputes every block's forward in its backward pass, so a perfect
program reads 3/4 here). One example is one sequence of ``length``
tokens.

The routed products depend on the routing: ``held_share`` is the share
of token-slots the router sent to experts held here (the counters
report it; 8 of 512 experts under even routing give 1/64).

Attention at the published widths (query and key 192, value 128; the
program pads them to 256, which shows as lost roofline): on average a
position attends to ``length / 2`` keys.

The delta rule's chunk of ``C`` positions and a head of ``d``: the two
decayed products ``A_kk`` and ``A_qk``, ``T``'s products with ``β K γ``
and ``β V`` and ``A_qk·V'`` at half the chunk's square (the least work:
they are triangular), the inverse ``T`` at ``C³/6`` (a triangular
solve), and the three products with the ``d × d`` state (``W·S``,
``(Q ⊙ γ)·S``, the state's update). Its bytes: q, k, v and the output in
bfloat16, the log-decays in float32 a key channel and ``β`` a head, and
in the backward pass the same again with the output's cotangent and
the gradients.
"""

BYTES = 2  # bfloat16 operands
DECAY_BYTES = 4  # the log-decays and β are float32


def _sizes(cfg):
    dep = cfg["deployment"]
    h, dk = cfg["num_attention_heads"], cfg["head_dim"]
    first = dep["first_layer"]
    layers = range(first, first + cfg["num_hidden_layers"])
    return {
        "t": cfg["input"]["length"], "d": cfg["hidden_size"], "h": h,
        "dk": dk, "w": h * dk, "taps": cfg["short_conv_kernel_size"],
        "kvr": cfg["kv_lora_rank"], "nope": cfg["qk_nope_head_dim"],
        "rope": cfg["qk_rope_head_dim"], "vd": cfg["v_head_dim"],
        "qk": cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
        "ff": cfg["intermediate_size"], "ef": cfg["moe_intermediate_size"],
        "shared": cfg["num_shared_experts"],
        "experts": dep["router_experts"], "held": cfg["num_experts"],
        "k": cfg["num_experts_per_tok"],
        "chunk": cfg["program"]["kwargs"]["kda_chunk"],
        "kinds": [("mla" if (i + 1) % cfg["layer_group_size"] == 0
                   else "kda", i < cfg["first_k_dense_replace"])
                  for i in layers],
        "vocab": cfg["vocab_size"],
    }


def even_share(cfg):
    s = _sizes(cfg)
    return s["held"] / s["experts"]


def _mixer_products(s, kind, name):
    d, h, w = s["d"], s["h"], s["w"]
    if kind == "kda":
        return [(f"{name}.qkv", 1.0, d, 3 * w), (f"{name}.beta", 1.0, d, h),
                (f"{name}.decay", 1.0, d, w), (f"{name}.gate", 1.0, d, w),
                (f"{name}.o", 1.0, w, d)]
    return [(f"{name}.q", 1.0, d, h * s["qk"]),
            (f"{name}.kv_a", 1.0, d, s["kvr"] + s["rope"]),
            (f"{name}.kv_b", 1.0, s["kvr"], h * (s["nope"] + s["vd"])),
            (f"{name}.gate", 1.0, d, h), (f"{name}.o", 1.0, h * s["vd"], d)]


def matmuls(cfg, held_share=None):
    """[(name, rows per token, k, n)] of every weight product; the
    rows of a held expert's products are the token-slots routed to the
    held experts (their weights are read whole whatever the rows)."""
    s = _sizes(cfg)
    share = even_share(cfg) if held_share is None else held_share
    d, ef = s["d"], s["ef"]
    rows = []
    for i, (kind, dense) in enumerate(s["kinds"]):
        rows += _mixer_products(s, kind, f"l{i}")
        if dense:
            rows += [(f"l{i}.gate_up", 1.0, d, 2 * s["ff"]),
                     (f"l{i}.down", 1.0, s["ff"], d)]
            continue
        slots = s["k"] * share          # held token-slots per token
        rows += [(f"l{i}.router", 1.0, d, s["experts"]),
                 (f"l{i}.shared_gate_up", 1.0, d, 2 * ef * s["shared"]),
                 (f"l{i}.shared_down", 1.0, ef * s["shared"], d),
                 (f"l{i}.experts_gate_up", slots, d, 2 * ef),
                 (f"l{i}.experts_down", slots, ef, d)]
    rows.append(("head", 1.0, d, s["vocab"]))
    return rows


def parameters(cfg):
    """Every trained number held here: the products' weights (an expert
    stack once for each held expert), gains, the delta rule's
    convolution, decay rates and biases, and the embedding."""
    s = _sizes(cfg)
    d, w = s["d"], s["w"]
    total = s["vocab"] * d + d
    for name, _, k, n in matmuls(cfg):
        total += k * n * (s["held"] if ".experts_" in name else 1)
    for kind, _ in s["kinds"]:
        total += 2 * d
        if kind == "kda":
            total += s["taps"] * 3 * w + s["h"] + w + s["dk"]
        else:
            total += s["qk"] + s["kvr"]
    return total


def mla_layers(cfg):
    return sum(kind == "mla" for kind, _ in _sizes(cfg)["kinds"])


def kda_layers(cfg):
    return sum(kind == "kda" for kind, _ in _sizes(cfg)["kinds"])


def attention_macs_per_example(cfg):
    """Scores and weighted values of one latent-attention layer, causal,
    at the published widths."""
    s = _sizes(cfg)
    return (s["t"] * s["t"] // 2) * s["h"] * (s["qk"] + s["vd"])


def kda_chunk_macs_per_example(cfg):
    """One delta-rule layer's chunk products (the module's docstring)."""
    s = _sizes(cfg)
    c, d = min(s["chunk"], s["t"]), s["dk"]
    per_chunk = 5 * (c * c // 2) * d + c ** 3 // 6 + 3 * c * d * d
    return -(-s["t"] // c) * per_chunk * s["h"]


def forward_macs_per_example(cfg, held_share=None):
    t = cfg["input"]["length"]
    return (sum(r * t * k * n for _, r, k, n in matmuls(cfg, held_share))
            + mla_layers(cfg) * attention_macs_per_example(cfg)
            + kda_layers(cfg) * kda_chunk_macs_per_example(cfg))


def flops_per_example(cfg, held_share=None):
    """Forward and backward: each product costs two more of its size
    (the embedding is a look-up and costs none)."""
    return 6.0 * forward_macs_per_example(cfg, held_share)


def weight_bytes(cfg, name, k, n):
    """Bytes of a product's weights: a held expert stack is read whole,
    once for each of the held experts."""
    held = _sizes(cfg)["held"] if ".experts_" in name else 1
    return held * k * n * BYTES


def _kda_chunk_least(cfg, batch, peaks, passes):
    """Least seconds of one delta-rule layer's chunk, forward (``passes``
    1) or forward and backward (3: the backward does twice the
    forward's products and reads q, k, v, Γ, β and the output's
    cotangent, writing their gradients)."""
    s = _sizes(cfg)
    tokens = s["t"] * batch
    flops = passes * 2 * kda_chunk_macs_per_example(cfg) * batch
    fwd = s["w"] * (4 * BYTES + DECAY_BYTES) + s["h"] * DECAY_BYTES
    bwd = s["w"] * (7 * BYTES + 2 * DECAY_BYTES) + 2 * s["h"] * DECAY_BYTES
    moved = tokens * (fwd + (bwd if passes == 3 else 0))
    return max(flops / peaks["flops_bf16"], moved / peaks["hbm_bytes_per_s"])


def kda_chunk_seconds_per_step(cfg, batch, peaks, backward=True):
    """Least seconds a step of the delta rule's chunk, once a layer
    (nothing recomputed): forward and backward, or the forward alone
    (``backward`` false, for a program whose backward is no kernel of
    that name)."""
    return kda_layers(cfg) * _kda_chunk_least(
        cfg, batch, peaks, 3 if backward else 1)


def roofline_seconds_per_step(cfg, batch, peaks, held_share=None):
    """Per product and per pass the larger of operations over peak
    FLOP/s and bytes over peak bytes/s; attention per layer as the
    scores' operations against the bytes of q, k, v and the output; the
    delta rule's chunk per layer as its products against its bytes."""
    s = _sizes(cfg)
    tokens = s["t"] * batch
    total = 0.0
    for name, r, k, n in matmuls(cfg, held_share):
        rows = r * tokens
        flops = 2 * rows * k * n
        moved = (rows * k + rows * n) * BYTES + weight_bytes(cfg, name, k, n)
        total += 3 * max(flops / peaks["flops_bf16"],
                         moved / peaks["hbm_bytes_per_s"])
    att_flops = 2 * attention_macs_per_example(cfg) * batch
    att_bytes = tokens * s["h"] * (3 * s["qk"] + s["vd"]) * BYTES
    total += mla_layers(cfg) * 3 * max(
        att_flops / peaks["flops_bf16"],
        att_bytes / peaks["hbm_bytes_per_s"])
    return total + kda_chunk_seconds_per_step(cfg, batch, peaks)
