"""Operations and bytes of ``glm47flash_ep8share`` from its shapes: the
weight products, the causal attention and the held experts' grouped
products, forward and backward; no norm, rotary turn, softmax, routing
sort or updater, and nothing recomputed (the configuration recomputes
every block's forward in its backward pass, so a perfect program reads
3/4 here). One example is one sequence of ``length`` tokens.

The routed products depend on the routing: ``held_share`` is the share
of token-slots the router sent to experts held here (the counters
report it; 8 of 64 experts under even routing give 1/8).

Causal attention needs half of the full score matrix: on average a
position attends to ``length / 2`` keys.
"""

BYTES = 2  # bfloat16 operands


def _sizes(cfg):
    h = cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return {
        "t": cfg["input"]["length"], "d": cfg["hidden_size"], "h": h,
        "qk": qk, "vd": cfg["v_head_dim"], "qr": cfg["q_lora_rank"],
        "kvr": cfg["kv_lora_rank"], "rope": cfg["qk_rope_head_dim"],
        "nope": cfg["qk_nope_head_dim"], "ff": cfg["intermediate_size"],
        "ef": cfg["moe_intermediate_size"],
        "shared": cfg["n_shared_experts"],
        "experts": cfg["deployment"]["router_experts"],
        "held": cfg["n_routed_experts"], "k": cfg["num_experts_per_tok"],
        "dense": cfg["first_k_dense_replace"],
        "layers": cfg["num_hidden_layers"],
        "modules": cfg["num_nextn_predict_layers"],
        "vocab": cfg["vocab_size"],
    }


def even_share(cfg):
    s = _sizes(cfg)
    return s["held"] / s["experts"]


def _attention_products(s, name):
    d, h = s["d"], s["h"]
    return [(f"{name}.q_a", 1.0, d, s["qr"]),
            (f"{name}.q_b", 1.0, s["qr"], h * s["qk"]),
            (f"{name}.kv_a", 1.0, d, s["kvr"] + s["rope"]),
            (f"{name}.kv_b", 1.0, s["kvr"], h * (s["nope"] + s["vd"])),
            (f"{name}.o", 1.0, h * s["vd"], d)]


def _expert_layer(s, name, held_share):
    d, ef = s["d"], s["ef"]
    rows = s["k"] * held_share          # held token-slots per token
    out = _attention_products(s, name)
    out.append((f"{name}.router", 1.0, d, s["experts"]))
    if s["shared"]:
        out += [(f"{name}.shared_gate_up", 1.0, d, 2 * ef * s["shared"]),
                (f"{name}.shared_down", 1.0, ef * s["shared"], d)]
    out += [(f"{name}.experts_gate_up", rows, d, 2 * ef),
            (f"{name}.experts_down", rows, ef, d)]
    return out


def matmuls(cfg, held_share=None):
    """[(name, rows per token, k, n)] of every weight product; the
    rows of a held expert's products are the token-slots routed to the
    held experts (their weights are read whole whatever the rows)."""
    s = _sizes(cfg)
    share = even_share(cfg) if held_share is None else held_share
    rows = []
    for i in range(s["layers"]):
        if i < s["dense"]:
            rows += _attention_products(s, f"l{i}")
            rows += [(f"l{i}.gate_up", 1.0, s["d"], 2 * s["ff"]),
                     (f"l{i}.down", 1.0, s["ff"], s["d"])]
        else:
            rows += _expert_layer(s, f"l{i}", share)
    rows.append(("head", 1.0, s["d"], s["vocab"]))
    for m in range(s["modules"]):
        rows.append((f"mtp{m}.proj", 1.0, 2 * s["d"], s["d"]))
        rows += _expert_layer(s, f"mtp{m}", share)
        rows.append((f"mtp{m}.head", 1.0, s["d"], s["vocab"]))
    return rows


def attention_layers(cfg):
    s = _sizes(cfg)
    return s["layers"] + s["modules"]


def attention_macs_per_example(cfg):
    """Scores and weighted values of one layer, causal."""
    s = _sizes(cfg)
    return (s["t"] * s["t"] // 2) * s["h"] * (s["qk"] + s["vd"])


def forward_macs_per_example(cfg, held_share=None):
    t = cfg["input"]["length"]
    return (sum(r * t * k * n for _, r, k, n in matmuls(cfg, held_share))
            + attention_layers(cfg) * attention_macs_per_example(cfg))


def flops_per_example(cfg, held_share=None):
    """Forward and backward: each product costs two more of its size
    (the embedding is a look-up and costs none)."""
    return 6.0 * forward_macs_per_example(cfg, held_share)


def weight_bytes(cfg, name, k, n):
    """Bytes of a product's weights: a held expert stack is read whole,
    once for each of the held experts."""
    held = _sizes(cfg)["held"] if ".experts_" in name else 1
    return held * k * n * BYTES


def roofline_seconds_per_step(cfg, batch, peaks, held_share=None):
    """Per product and per pass the larger of operations over peak
    FLOP/s and bytes over peak bytes/s; attention per layer as the
    scores' operations against the bytes of q, k, v and the output."""
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
    att_bytes = 4 * tokens * s["h"] * s["qk"] * BYTES
    total += attention_layers(cfg) * 3 * max(
        att_flops / peaks["flops_bf16"],
        att_bytes / peaks["hbm_bytes_per_s"])
    return total
