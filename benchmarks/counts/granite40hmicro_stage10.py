"""Operations and bytes of ``granite40hmicro_stage10`` from its shapes:
every weight product, the attention block's causal scores, the head
over the held rows and the selective scan's four products at the
configuration's chunk, forward and backward; no norm, convolution,
decay, softmax or updater, and nothing recomputed (the configuration
recomputes every block's forward in its backward pass, so a perfect
program reads 3/4 here). One example is one sequence of ``length``
tokens.

Causal attention needs half of the full score matrix. The scan's two
products inside a chunk (``C·Bᵀ`` and ``(L ∘ C Bᵀ)·X``) are counted at
half the chunk's square too: the least work, whatever implements it (a
program that computes the masked half as well does more than is counted
here; a kernel that skips it cannot read over 100%). The other two are
a chunk's own state ``B ⊗ X`` and the entering state's contribution
``C·S``. Passing the states from chunk to chunk is a multiply-add over
``chunks`` states and is not counted.
"""

BYTES = 2  # bfloat16 operands
STATE_BYTES = 4  # the chunk states are float32


def _sizes(cfg):
    h, p = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    g, n = cfg["mamba_n_groups"], cfg["mamba_d_state"]
    kinds = cfg["layer_types"]
    return {
        "t": cfg["input"]["length"], "d": cfg["hidden_size"],
        "ff": cfg["shared_intermediate_size"],
        "qh": cfg["num_attention_heads"], "kvh": cfg["num_key_value_heads"],
        "hd": cfg["attention"]["head_dim"], "h": h, "p": p, "g": g, "n": n,
        "inner": h * p, "conv": h * p + 2 * g * n,
        "taps": cfg["mamba_d_conv"], "chunk": cfg["mamba_chunk_size"],
        "mamba": sum(k == "mamba" for k in kinds),
        "attention": sum(k == "attention" for k in kinds),
        "kinds": kinds, "vocab": cfg["vocab_size"],
    }


def parameters(cfg):
    """Every trained number held here; the tied table counts once."""
    s = _sizes(cfg)
    d = s["d"]
    mlp = 3 * d * s["ff"]
    mamba = (d * (s["inner"] + s["conv"] + s["h"])
             + s["conv"] * s["taps"] + s["conv"] + 3 * s["h"]
             + s["inner"] + s["inner"] * d)
    attention = 2 * d * s["qh"] * s["hd"] + 2 * d * s["kvh"] * s["hd"]
    return (s["mamba"] * (mamba + mlp + 2 * d)
            + s["attention"] * (attention + mlp + 2 * d)
            + s["vocab"] * d + d)


def matmuls(cfg):
    """[(name, k, n)] of every weight product, one row a token."""
    s = _sizes(cfg)
    d = s["d"]
    rows = []
    for i, kind in enumerate(s["kinds"]):
        if kind == "mamba":
            rows += [(f"l{i}.in_proj", d, s["inner"] + s["conv"] + s["h"]),
                     (f"l{i}.out_proj", s["inner"], d)]
        else:
            rows += [(f"l{i}.q", d, s["qh"] * s["hd"]),
                     (f"l{i}.kv", d, 2 * s["kvh"] * s["hd"]),
                     (f"l{i}.o", s["qh"] * s["hd"], d)]
        rows += [(f"l{i}.gate_up", d, 2 * s["ff"]),
                 (f"l{i}.down", s["ff"], d)]
    rows.append(("head", d, s["vocab"]))
    return rows


def attention_macs_per_example(cfg):
    """Scores and weighted values of the attention block, causal."""
    s = _sizes(cfg)
    return (s["t"] * s["t"] // 2) * s["qh"] * 2 * s["hd"]


def scan_macs_per_example(cfg):
    """One state-space layer's four products: ``C·Bᵀ`` and
    ``(L ∘ C Bᵀ)·X`` at half the chunk's square, ``B ⊗ X`` and
    ``C·S``."""
    s = _sizes(cfg)
    half = min(s["chunk"], s["t"]) // 2
    hpn = s["h"] * s["p"] * s["n"]
    return s["t"] * (half * s["g"] * s["n"] + half * s["h"] * s["p"]
                     + 2 * hpn)


def forward_macs_per_example(cfg):
    s = _sizes(cfg)
    return (s["t"] * sum(k * n for _, k, n in matmuls(cfg))
            + s["attention"] * attention_macs_per_example(cfg)
            + s["mamba"] * scan_macs_per_example(cfg))


def flops_per_example(cfg):
    """Forward and backward: each product costs two more of its size
    (the embedding is a look-up and costs none)."""
    return 6.0 * forward_macs_per_example(cfg)


def roofline_seconds_per_step(cfg, batch, peaks):
    """Per product and per pass the larger of operations over peak
    FLOP/s and bytes over peak bytes/s. Attention: the scores'
    operations against the bytes of q and the output at 32 heads and k
    and v at 8. A scan: its four products' operations against the
    bytes of x, y, B and C and of the chunk states written and read."""
    s = _sizes(cfg)
    tokens = s["t"] * batch

    def least(flops, moved):
        return 3 * max(flops / peaks["flops_bf16"],
                       moved / peaks["hbm_bytes_per_s"])

    total = 0.0
    for _, k, n in matmuls(cfg):
        total += least(2 * tokens * k * n,
                       (tokens * k + k * n + tokens * n) * BYTES)
    total += s["attention"] * least(
        2 * attention_macs_per_example(cfg) * batch,
        2 * tokens * (s["qh"] + s["kvh"]) * s["hd"] * BYTES)
    chunks = -(-s["t"] // s["chunk"]) * batch
    total += s["mamba"] * least(
        2 * scan_macs_per_example(cfg) * batch,
        2 * tokens * (s["inner"] + s["g"] * s["n"]) * BYTES
        + 2 * chunks * s["h"] * s["p"] * s["n"] * STATE_BYTES)
    return total
