"""Operations and bytes of ``lfm2moe24b_ep8share`` from its shapes: the
weight products, the attention layers' causal scores, the held experts'
grouped products and the tied head over the held rows, forward and
backward, and the gated short convolution's bytes; no norm, rotary
turn, softmax, routing sort or updater, and nothing recomputed where
not said (the configuration recomputes every block's forward in its
backward pass, so a perfect program reads 3/4 of its products here).
One example is one sequence of ``length`` tokens.

The routed products depend on the routing: ``held_share`` is the share
of token-slots the router sent to experts held here (the counters
report it; 8 of 64 experts under even routing give 1/8).

The short convolution (``ops/short_conv.py``) is a few multiplies a
channel and is bound by its bytes: its forward kernel reads ``[B | C |
x̃]`` ``[n, 3d]`` and writes ``y`` ``[n, d]``, 4·n·d values a call, and
full remat calls it twice a layer; its backward reads ``[n, 3d]`` and
``dy`` ``[n, d]`` and writes ``d[B | C | x̃]`` ``[n, 3d]``, 7·n·d values,
once a layer (n = b·t). Its multiplies are not counted as operations.
"""

BYTES = 2  # bfloat16 operands


def _sizes(cfg):
    dep = cfg["deployment"]
    h = cfg["num_attention_heads"]
    first = dep["first_layer"]
    layers = range(first, first + cfg["num_hidden_layers"])
    return {
        "t": cfg["input"]["length"], "d": cfg["hidden_size"], "h": h,
        "kvh": cfg["num_key_value_heads"], "hd": cfg["hidden_size"] // h,
        "taps": cfg["conv_L_cache"],
        "ff": cfg["intermediate_size"], "ef": cfg["moe_intermediate_size"],
        "experts": dep["router_experts"], "held": cfg["num_experts"],
        "k": cfg["num_experts_per_tok"],
        "kinds": [(cfg["layer_types"][i], i < cfg["num_dense_layers"])
                  for i in layers],
        "vocab": cfg["vocab_size"],
    }


def even_share(cfg):
    s = _sizes(cfg)
    return s["held"] / s["experts"]


def matmuls(cfg, held_share=None):
    """[(name, rows per token, k, n)] of every weight product; the
    rows of a held expert's products are the token-slots routed to the
    held experts (their weights are read whole whatever the rows)."""
    s = _sizes(cfg)
    share = even_share(cfg) if held_share is None else held_share
    d, ef, hd = s["d"], s["ef"], s["hd"]
    rows = []
    for i, (kind, dense) in enumerate(s["kinds"]):
        if kind == "conv":
            rows += [(f"l{i}.in_proj", 1.0, d, 3 * d),
                     (f"l{i}.out_proj", 1.0, d, d)]
        else:
            rows += [(f"l{i}.q", 1.0, d, s["h"] * hd),
                     (f"l{i}.kv", 1.0, d, 2 * s["kvh"] * hd),
                     (f"l{i}.o", 1.0, s["h"] * hd, d)]
        if dense:
            rows += [(f"l{i}.gate_up", 1.0, d, 2 * s["ff"]),
                     (f"l{i}.down", 1.0, s["ff"], d)]
            continue
        slots = s["k"] * share          # held token-slots per token
        rows += [(f"l{i}.router", 1.0, d, s["experts"]),
                 (f"l{i}.experts_gate_up", slots, d, 2 * ef),
                 (f"l{i}.experts_down", slots, ef, d)]
    rows.append(("head", 1.0, d, s["vocab"]))
    return rows


def parameters(cfg):
    """Every trained number held here: the products' weights (an expert
    stack once for each held expert), the taps, the gains (two a block,
    the q/k norms', the final one) and the tied table once."""
    s = _sizes(cfg)
    d = s["d"]
    total = s["vocab"] * d + d
    for name, _, k, n in matmuls(cfg):
        if name != "head":
            total += k * n * (s["held"] if ".experts_" in name else 1)
    for kind, _ in s["kinds"]:
        total += 2 * d + (s["taps"] * d if kind == "conv" else 2 * s["hd"])
    return total


def conv_layers(cfg):
    return sum(kind == "conv" for kind, _ in _sizes(cfg)["kinds"])


def attention_layers(cfg):
    return sum(kind == "full_attention" for kind, _ in _sizes(cfg)["kinds"])


def attention_macs_per_example(cfg):
    """Scores and weighted values of one attention layer, causal."""
    s = _sizes(cfg)
    return (s["t"] * s["t"] // 2) * s["h"] * 2 * s["hd"]


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


def short_conv_seconds_per_step(cfg, batch, peaks, forward=True,
                                backward=True, recomputed=True):
    """Least seconds a step of the short convolution's kernels, its bytes
    over peak bytes/s: the forward kernel's 4·n·d values a call, twice
    a layer under full remat (``recomputed``), where the program's
    forward is that kernel, and the backward kernel's 7·n·d once a
    layer where its backward is."""
    s = _sizes(cfg)
    nd = s["t"] * batch * s["d"] * BYTES
    moved = (forward * (1 + recomputed) * 4 * nd) + backward * 7 * nd
    return conv_layers(cfg) * moved / peaks["hbm_bytes_per_s"]


def roofline_seconds_per_step(cfg, batch, peaks, held_share=None):
    """Per product and per pass the larger of operations over peak
    FLOP/s and bytes over peak bytes/s; attention per layer as the
    scores' operations against the bytes of q, k and v at 32 heads and
    the output; the short convolution's bytes, forward once and
    backward, per layer."""
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
    att_bytes = tokens * s["h"] * 4 * s["hd"] * BYTES
    total += attention_layers(cfg) * 3 * max(
        att_flops / peaks["flops_bf16"],
        att_bytes / peaks["hbm_bytes_per_s"])
    return total + short_conv_seconds_per_step(
        cfg, batch, peaks, recomputed=False)
