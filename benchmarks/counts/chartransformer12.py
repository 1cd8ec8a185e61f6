"""Operations and bytes of ``chartransformer12`` from its shapes: the
matrix products and the causal attention, forward and backward; no
layer norm, GELU, softmax or updater, and nothing recomputed. One
example is one sequence of ``length`` characters.

Causal attention needs half of the full score matrix: on average a
position attends to ``length / 2`` keys.
"""

BYTES = 2  # bfloat16 operands


def matmuls(cfg):
    """[(name, rows per example, k, n)] of every weight product."""
    m = cfg["model"]
    t, d, ff, v = (cfg["input"]["length"], m["d_model"],
                   m["ffn_hidden"], m["vocab"])
    rows = [("embed", t, v, d)]
    for i in range(m["n_layers"]):
        rows += [(f"l{i}.qkv", t, d, 3 * d), (f"l{i}.o", t, d, d),
                 (f"l{i}.ff1", t, d, ff), (f"l{i}.ff2", t, ff, d)]
    rows.append(("head", t, d, v))
    return rows


def attention_macs_per_example(cfg):
    """Scores and weighted values of one layer, causal."""
    t, d = cfg["input"]["length"], cfg["model"]["d_model"]
    return 2 * (t * t // 2) * d


def forward_macs_per_example(cfg):
    return (sum(r * k * n for _, r, k, n in matmuls(cfg))
            + cfg["model"]["n_layers"] * attention_macs_per_example(cfg))


def flops_per_example(cfg):
    """Forward and backward: each product costs two more of its size
    (the one-hot input needs no gradient, so the embedding costs one)."""
    total = 0
    for name, r, k, n in matmuls(cfg):
        total += 2 * r * k * n * (2 if name == "embed" else 3)
    total += 2 * 3 * cfg["model"]["n_layers"] * \
        attention_macs_per_example(cfg)
    return total


def roofline_seconds_per_step(cfg, batch, peaks):
    """Per product and per pass the larger of operations over peak
    FLOP/s and bytes over peak bytes/s; attention per layer as the
    scores' operations against the bytes of q, k, v and the output."""
    m = cfg["model"]
    t, d = cfg["input"]["length"], m["d_model"]
    total = 0.0
    for name, r, k, n in matmuls(cfg):
        rows = r * batch
        flops = 2 * rows * k * n
        moved = (rows * k + k * n + rows * n) * BYTES
        passes = 2 if name == "embed" else 3
        total += passes * max(flops / peaks["flops_bf16"],
                              moved / peaks["hbm_bytes_per_s"])
    att_flops = 2 * attention_macs_per_example(cfg) * batch
    att_bytes = 4 * t * d * batch * BYTES
    total += m["n_layers"] * 3 * max(
        att_flops / peaks["flops_bf16"],
        att_bytes / peaks["hbm_bytes_per_s"])
    return total
