"""Model zoo: builder functions for the benchmark/model families the
framework targets (BASELINE.md configs; the reference ships these as
hand-built examples — e.g. LeNet in `deeplearning4j-core` examples and
the Spark ResNet-style CNNs — rather than a zoo module, so these
builders are the capability equivalent).

Every function returns a built configuration (MultiLayerConfiguration
or ComputationGraphConfiguration); callers wrap it in
``MultiLayerNetwork``/``ComputationGraph`` and ``.init()`` it. All
configs are TPU-shaped: static shapes, conv stacks that XLA tiles onto
the MXU, optional pure-bf16 compute via ``data_type``.
"""

from deeplearning4j_tpu.nn.conf import InputType, NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.graph_conf import (
    ElementWiseVertex,
    MergeVertex,
)
from deeplearning4j_tpu.nn.layers import (
    ActivationLayer,
    BatchNormalization,
    ConvolutionLayer,
    DenseLayer,
    GravesLSTM,
    OutputLayer,
    RnnOutputLayer,
    SubsamplingLayer,
)


def lenet(height=28, width=28, channels=1, n_classes=10, *,
          dense_width=512, updater="ADAM", learning_rate=0.01, seed=42,
          dtype="float32", compute_dtype=None):
    """LeNet-5 (BASELINE.md config #1; reference
    ``nn/multilayer/MultiLayerNetwork.java`` + ``nn/layers/convolution``
    stack)."""
    return (
        NeuralNetConfiguration.Builder()
        .seed(seed).learning_rate(learning_rate).updater(updater)
        .data_type(dtype).compute_data_type(compute_dtype)
        .list()
        .layer(ConvolutionLayer(n_out=20, kernel_size=(5, 5),
                                activation="relu"))
        .layer(SubsamplingLayer(pooling_type="MAX"))
        .layer(ConvolutionLayer(n_out=50, kernel_size=(5, 5),
                                activation="relu"))
        .layer(SubsamplingLayer(pooling_type="MAX"))
        .layer(DenseLayer(n_out=dense_width, activation="relu"))
        .layer(OutputLayer(n_out=n_classes, loss="MCXENT"))
        .set_input_type(
            InputType.convolutional_flat(height, width, channels)
        )
        .build()
    )


def alexnet(height=224, width=224, channels=3, n_classes=1000, *,
            updater="NESTEROVS", learning_rate=0.01, seed=42,
            dtype="float32", compute_dtype=None):
    """AlexNet (the reference era's standard large CNN; conv stack per
    Krizhevsky et al. 2012, grouped convs dropped — XLA fuses the
    full-width convs onto the MXU instead)."""
    return (
        NeuralNetConfiguration.Builder()
        .seed(seed).learning_rate(learning_rate).updater(updater)
        .data_type(dtype).compute_data_type(compute_dtype)
        .list()
        .layer(ConvolutionLayer(n_out=96, kernel_size=(11, 11),
                                stride=(4, 4), padding=(2, 2),
                                activation="relu"))
        .layer(SubsamplingLayer(pooling_type="MAX", kernel_size=(3, 3),
                                stride=(2, 2)))
        .layer(ConvolutionLayer(n_out=256, kernel_size=(5, 5),
                                padding=(2, 2), activation="relu"))
        .layer(SubsamplingLayer(pooling_type="MAX", kernel_size=(3, 3),
                                stride=(2, 2)))
        .layer(ConvolutionLayer(n_out=384, kernel_size=(3, 3),
                                padding=(1, 1), activation="relu"))
        .layer(ConvolutionLayer(n_out=384, kernel_size=(3, 3),
                                padding=(1, 1), activation="relu"))
        .layer(ConvolutionLayer(n_out=256, kernel_size=(3, 3),
                                padding=(1, 1), activation="relu"))
        .layer(SubsamplingLayer(pooling_type="MAX", kernel_size=(3, 3),
                                stride=(2, 2)))
        .layer(DenseLayer(n_out=4096, activation="relu", dropout=0.5))
        .layer(DenseLayer(n_out=4096, activation="relu", dropout=0.5))
        .layer(OutputLayer(n_out=n_classes, loss="MCXENT"))
        .set_input_type(InputType.convolutional(height, width, channels))
        .build()
    )


def vgg16(height=32, width=32, channels=3, n_classes=10, *,
          dense_width=512, updater="NESTEROVS", learning_rate=0.01,
          seed=42, dtype="float32", compute_dtype=None):
    """VGG-16 as a ComputationGraph (BASELINE.md config #2; reference
    DAG engine ``nn/graph/ComputationGraph.java``). For MXU-native
    speed pass ``dtype="bfloat16"`` (pure bf16 — momentum SGD is
    bf16-safe) or ``compute_dtype="bfloat16"`` (f32 master weights)."""
    b = (
        NeuralNetConfiguration.Builder()
        .seed(seed).learning_rate(learning_rate).updater(updater)
        .data_type(dtype).compute_data_type(compute_dtype)
        .graph_builder()
        .add_inputs("in")
    )
    prev = "in"
    idx = 0
    for block, (n_layers, width_) in enumerate(
        [(2, 64), (2, 128), (3, 256), (3, 512), (3, 512)]
    ):
        for _ in range(n_layers):
            name = f"conv{idx}"
            b.add_layer(name, ConvolutionLayer(
                n_out=width_, kernel_size=(3, 3), padding=(1, 1),
                activation="relu",
            ), prev)
            prev = name
            idx += 1
        pname = f"pool{block}"
        b.add_layer(pname, SubsamplingLayer(pooling_type="MAX"), prev)
        prev = pname
    b.add_layer("fc0", DenseLayer(n_out=dense_width, activation="relu"),
                prev)
    b.add_layer("fc1", DenseLayer(n_out=dense_width, activation="relu"),
                "fc0")
    b.add_layer("out", OutputLayer(n_out=n_classes, loss="MCXENT"), "fc1")
    b.set_outputs("out")
    b.set_input_types(InputType.convolutional(height, width, channels))
    return b.build()


def _resnet_bottleneck(b, name, in_name, width, *, stride=1,
                       project=False):
    """conv1x1 -> conv3x3 -> conv1x1 (4*width) + identity/projection
    shortcut, joined by an ElementWiseVertex Add and a ReLU."""
    b.add_layer(f"{name}_c1", ConvolutionLayer(
        n_out=width, kernel_size=(1, 1), activation="identity",
    ), in_name)
    b.add_layer(f"{name}_bn1", BatchNormalization(activation="relu"),
                f"{name}_c1")
    b.add_layer(f"{name}_c2", ConvolutionLayer(
        n_out=width, kernel_size=(3, 3), stride=(stride, stride),
        padding=(1, 1), activation="identity",
    ), f"{name}_bn1")
    b.add_layer(f"{name}_bn2", BatchNormalization(activation="relu"),
                f"{name}_c2")
    b.add_layer(f"{name}_c3", ConvolutionLayer(
        n_out=4 * width, kernel_size=(1, 1), activation="identity",
    ), f"{name}_bn2")
    b.add_layer(f"{name}_bn3", BatchNormalization(activation="identity"),
                f"{name}_c3")
    shortcut = in_name
    if project:
        b.add_layer(f"{name}_proj", ConvolutionLayer(
            n_out=4 * width, kernel_size=(1, 1),
            stride=(stride, stride), activation="identity",
        ), in_name)
        b.add_layer(f"{name}_projbn",
                    BatchNormalization(activation="identity"),
                    f"{name}_proj")
        shortcut = f"{name}_projbn"
    b.add_vertex(f"{name}_add", ElementWiseVertex(op="Add"),
                 f"{name}_bn3", shortcut)
    b.add_layer(f"{name}_relu", ActivationLayer(activation="relu"),
                f"{name}_add")
    return f"{name}_relu"


def resnet50(height=224, width=224, channels=3, n_classes=1000, *,
             updater="NESTEROVS", learning_rate=0.1, seed=42,
             dtype="float32", compute_dtype=None, cifar_stem=False,
             depths=(3, 4, 6, 3), base_width=64, remat="none",
             loss_scale=None):
    """ResNet-50 v1 as a ComputationGraph (BASELINE.md config #5 —
    the data-parallel scaling model; residual Add via the reference's
    ``ElementWiseVertex``, bottleneck stacks ``depths`` — default
    [3, 4, 6, 3]; shrink ``depths``/``base_width`` for test-scale
    variants).

    ``cifar_stem=True`` swaps the 7x7/s2 stem + maxpool for a 3x3/s1
    conv (the standard CIFAR adaptation) so 32x32 inputs keep spatial
    extent through the stages.

    ``remat`` (``none | dots_saveable | full``) enables activation
    rematerialization on every bottleneck conv — the conv stack's
    activations dominate peak HBM at training batch sizes, so remat
    buys batch at the cost of a second forward in the backward pass
    (``nn/core.py``); ``loss_scale`` arms dynamic loss scaling for
    ``compute_dtype="float16"``."""
    # total stride: stem (1 or 4, incl. maxpool) x 2 per later stage
    div = (1 if cifar_stem else 4) * (2 ** (len(depths) - 1))
    if height % div or width % div:
        raise ValueError(
            f"resnet50 input extent must be divisible by {div} "
            f"(total stride{' with cifar_stem' if cifar_stem else ''}); "
            f"got {height}x{width} — the global average pool would "
            "silently drop edge cells otherwise"
        )
    b = (
        NeuralNetConfiguration.Builder()
        .seed(seed).learning_rate(learning_rate).updater(updater)
        .data_type(dtype).compute_data_type(compute_dtype)
        .remat(remat).loss_scale(loss_scale)
        .graph_builder()
        .add_inputs("in")
    )
    if cifar_stem:
        b.add_layer("stem", ConvolutionLayer(
            n_out=base_width, kernel_size=(3, 3), padding=(1, 1),
            activation="identity",
        ), "in")
        b.add_layer("stem_bn", BatchNormalization(activation="relu"),
                    "stem")
        prev = "stem_bn"
    else:
        b.add_layer("stem", ConvolutionLayer(
            n_out=base_width, kernel_size=(7, 7), stride=(2, 2),
            padding=(3, 3), activation="identity",
        ), "in")
        b.add_layer("stem_bn", BatchNormalization(activation="relu"),
                    "stem")
        b.add_layer("stem_pool", SubsamplingLayer(
            pooling_type="MAX", kernel_size=(3, 3), stride=(2, 2),
            padding=(1, 1),
        ), "stem_bn")
        prev = "stem_pool"
    widths = [base_width * 2 ** i for i in range(len(depths))]
    for stage, (w, d) in enumerate(zip(widths, depths)):
        for block in range(d):
            stride = 2 if (block == 0 and stage > 0) else 1
            prev = _resnet_bottleneck(
                b, f"s{stage}b{block}", prev, w,
                stride=stride, project=(block == 0),
            )
    # global average pool: AVG-pool over the full remaining extent
    final_hw = (height // div, width // div)
    b.add_layer("gap", SubsamplingLayer(
        pooling_type="AVG", kernel_size=final_hw, stride=final_hw,
    ), prev)
    b.add_layer("out", OutputLayer(n_out=n_classes, loss="MCXENT"), "gap")
    b.set_outputs("out")
    b.set_input_types(InputType.convolutional(height, width, channels))
    return b.build()


def _inception_module(b, name, in_name, c1, c3r, c3, c5r, c5, pp):
    """GoogLeNet inception module: 1x1 / 1x1->3x3 / 1x1->5x5 /
    maxpool->1x1 branches concatenated over channels (MergeVertex)."""
    b.add_layer(f"{name}_b1", ConvolutionLayer(
        n_out=c1, kernel_size=(1, 1), activation="relu"), in_name)
    b.add_layer(f"{name}_b3r", ConvolutionLayer(
        n_out=c3r, kernel_size=(1, 1), activation="relu"), in_name)
    b.add_layer(f"{name}_b3", ConvolutionLayer(
        n_out=c3, kernel_size=(3, 3), padding=(1, 1),
        activation="relu"), f"{name}_b3r")
    b.add_layer(f"{name}_b5r", ConvolutionLayer(
        n_out=c5r, kernel_size=(1, 1), activation="relu"), in_name)
    b.add_layer(f"{name}_b5", ConvolutionLayer(
        n_out=c5, kernel_size=(5, 5), padding=(2, 2),
        activation="relu"), f"{name}_b5r")
    b.add_layer(f"{name}_pool", SubsamplingLayer(
        pooling_type="MAX", kernel_size=(3, 3), stride=(1, 1),
        padding=(1, 1)), in_name)
    b.add_layer(f"{name}_pp", ConvolutionLayer(
        n_out=pp, kernel_size=(1, 1), activation="relu"),
        f"{name}_pool")
    b.add_vertex(f"{name}_cat", MergeVertex(), f"{name}_b1",
                 f"{name}_b3", f"{name}_b5", f"{name}_pp")
    return f"{name}_cat"


def googlenet(height=224, width=224, channels=3, n_classes=1000, *,
              updater="NESTEROVS", learning_rate=0.01, seed=42,
              dtype="float32", compute_dtype=None):
    """GoogLeNet / Inception v1 (Szegedy et al. 2014; the reference
    era's MergeVertex-concat showcase — aux classifier heads omitted,
    as in modern replications). ~6M params."""
    if height % 32 or width % 32:
        raise ValueError(
            "googlenet input extent must be divisible by 32; got "
            f"{height}x{width}"
        )
    b = (
        NeuralNetConfiguration.Builder()
        .seed(seed).learning_rate(learning_rate).updater(updater)
        .data_type(dtype).compute_data_type(compute_dtype)
        .graph_builder()
        .add_inputs("in")
    )
    b.add_layer("stem1", ConvolutionLayer(
        n_out=64, kernel_size=(7, 7), stride=(2, 2), padding=(3, 3),
        activation="relu"), "in")
    b.add_layer("pool1", SubsamplingLayer(
        pooling_type="MAX", kernel_size=(3, 3), stride=(2, 2),
        padding=(1, 1)), "stem1")
    b.add_layer("stem2r", ConvolutionLayer(
        n_out=64, kernel_size=(1, 1), activation="relu"), "pool1")
    b.add_layer("stem2", ConvolutionLayer(
        n_out=192, kernel_size=(3, 3), padding=(1, 1),
        activation="relu"), "stem2r")
    b.add_layer("pool2", SubsamplingLayer(
        pooling_type="MAX", kernel_size=(3, 3), stride=(2, 2),
        padding=(1, 1)), "stem2")
    spec = [
        ("3a", 64, 96, 128, 16, 32, 32),
        ("3b", 128, 128, 192, 32, 96, 64),
        ("pool", 0, 0, 0, 0, 0, 0),
        ("4a", 192, 96, 208, 16, 48, 64),
        ("4b", 160, 112, 224, 24, 64, 64),
        ("4c", 128, 128, 256, 24, 64, 64),
        ("4d", 112, 144, 288, 32, 64, 64),
        ("4e", 256, 160, 320, 32, 128, 128),
        ("pool", 0, 0, 0, 0, 0, 0),
        ("5a", 256, 160, 320, 32, 128, 128),
        ("5b", 384, 192, 384, 48, 128, 128),
    ]
    prev = "pool2"
    n_pools = 0
    for name, c1, c3r, c3, c5r, c5, pp in spec:
        if name == "pool":
            n_pools += 1
            pname = f"pool{2 + n_pools}"
            b.add_layer(pname, SubsamplingLayer(
                pooling_type="MAX", kernel_size=(3, 3), stride=(2, 2),
                padding=(1, 1)), prev)
            prev = pname
        else:
            prev = _inception_module(
                b, f"inc{name}", prev, c1, c3r, c3, c5r, c5, pp
            )
    gap = (height // 32, width // 32)
    b.add_layer("gap", SubsamplingLayer(
        pooling_type="AVG", kernel_size=gap, stride=gap), prev)
    b.add_layer("out", OutputLayer(n_out=n_classes, loss="MCXENT",
                                   dropout=0.4), "gap")
    b.set_outputs("out")
    b.set_input_types(InputType.convolutional(height, width, channels))
    return b.build()


def transformer_lm(vocab=77, d_model=256, n_layers=4, n_heads=8, *,
                   ffn_hidden=None, n_experts=0, updater="ADAM",
                   learning_rate=1e-3, seed=42, dtype="float32",
                   compute_dtype=None, scan_layers=False,
                   remat="none", loss_scale=None):
    """Decoder-only transformer language model (net-new family beyond
    the reference's RNN era): causal MultiHeadSelfAttention via the
    Pallas flash-attention kernel on TPU, sinusoidal positional
    encoding, dense or Switch-MoE FFN (``n_experts > 0``).
    Inputs/labels are [b, vocab, t] one-hots like the char-RNN
    configs.

    The repeated TransformerBlocks are THE scan-over-layers workload:
    ``scan_layers=True`` collapses the n_layers-deep stack's HLO to a
    single scanned block (compile time stops growing with depth), and
    ``remat`` (``none | dots_saveable | full``) trades recompute for
    activation HBM; ``loss_scale`` arms dynamic loss scaling for
    ``compute_dtype="float16"`` — all trajectory-preserving whole-net
    transforms from ``nn/core.py``."""
    from deeplearning4j_tpu.nn.layers import (
        PositionalEncoding,
        TransformerBlock,
    )

    b = (
        NeuralNetConfiguration.Builder()
        .seed(seed).learning_rate(learning_rate).updater(updater)
        .data_type(dtype).compute_data_type(compute_dtype)
        .scan_layers(scan_layers).remat(remat).loss_scale(loss_scale)
        .list()
        .layer(DenseLayer(n_out=d_model, activation="identity"))
        .layer(PositionalEncoding())
    )
    for _ in range(n_layers):
        b.layer(TransformerBlock(
            n_heads=n_heads, causal=True,
            ffn_hidden=ffn_hidden or 4 * d_model,
            n_experts=n_experts,
        ))
    b.layer(RnnOutputLayer(n_out=vocab, loss="MCXENT"))
    b.set_input_type(InputType.recurrent(vocab))
    return b.build()


def graves_lstm_char_rnn(vocab=77, hidden=200, n_layers=2, *,
                         updater="RMSPROP", learning_rate=0.1, seed=42,
                         tbptt_length=None, dtype="float32",
                         compute_dtype=None):
    """Stacked GravesLSTM character model (BASELINE.md config #3;
    reference ``nn/layers/recurrent/LSTMHelpers.java``)."""
    b = (
        NeuralNetConfiguration.Builder()
        .seed(seed).learning_rate(learning_rate).updater(updater)
        .data_type(dtype).compute_data_type(compute_dtype)
        .list()
    )
    n_in = vocab
    for _ in range(n_layers):
        b.layer(GravesLSTM(n_in=n_in, n_out=hidden, activation="tanh"))
        n_in = hidden
    b.layer(RnnOutputLayer(n_out=vocab, loss="MCXENT"))
    if tbptt_length:
        b.backprop_type("TruncatedBPTT")
        b.t_bptt_forward_length(tbptt_length)
        b.t_bptt_backward_length(tbptt_length)
    return b.build()


def latent_moe_lm(vocab=512, d_model=256, n_layers=3, n_heads=4, *,
                  q_rank=96, kv_rank=64, nope_dim=48, rope_dim=16,
                  v_dim=64, ffn_hidden=1024, n_dense_layers=1,
                  n_experts=8, held_experts=None, top_k=2,
                  expert_hidden=128, n_shared=1, routed_scaling=1.0,
                  norm_topk=True, next_token_modules=1,
                  next_token_weight=0.3, rope_theta=10000.0,
                  rms_eps=1e-5, init_std=0.02, loss_block_rows=1024,
                  updater="ADAM", learning_rate=1e-4, seed=42,
                  dtype="float32", compute_dtype=None,
                  scan_layers=True, remat="none"):
    """Decoder-only language model of the latent-attention, routed-
    expert family (DeepSeek-V2/V3 and their descendants): a token
    embedding, ``n_dense_layers`` blocks with a gated feed-forward
    layer, ``n_layers - n_dense_layers`` blocks with sigmoid top-k
    routed experts beside shared ones, a final RMS norm and an untied
    head, with ``next_token_modules`` (0 or 1) multi-token-prediction
    modules that share the embedding and the head.

    ``held_experts`` ``(first, last)`` makes every expert layer one
    chip's share of an expert-parallel group: it routes over all
    ``n_experts`` and computes the held range's part; ``vocab`` is the
    rows of the vocabulary held here. Inputs are ids ``[b, t]``, labels
    the ids one position on (``[b, t + 1]`` with a prediction module:
    the label and the one after it). The expert blocks keep routing
    statistics as state, so ``scan_layers`` finds no run among them."""
    from deeplearning4j_tpu.nn.layers import (
        DecoderBlock,
        GatedFeedForward,
        LatentAttention,
        LMOutputLayer,
        RoutedExperts,
        TokenEmbedding,
    )
    from deeplearning4j_tpu.nn.weights import Distribution

    if next_token_modules not in (0, 1):
        raise ValueError("next_token_modules is 0 or 1")
    first, last = held_experts or (0, n_experts - 1)
    attention = LatentAttention(
        n_in=d_model, n_heads=n_heads, q_rank=q_rank, kv_rank=kv_rank,
        nope_dim=nope_dim, rope_dim=rope_dim, v_dim=v_dim,
        rope_theta=rope_theta, eps=rms_eps)
    dense = DecoderBlock(
        attention=attention, n_in=d_model, eps=rms_eps,
        ffn=GatedFeedForward(n_in=d_model, hidden_size=ffn_hidden))
    sparse = DecoderBlock(
        attention=attention, n_in=d_model, eps=rms_eps,
        ffn=RoutedExperts(
            n_in=d_model, hidden_size=expert_hidden, n_experts=n_experts,
            held_first=first, held_last=last, top_k=top_k,
            n_shared=n_shared, scaling=routed_scaling,
            norm_topk=norm_topk))
    b = (
        NeuralNetConfiguration.Builder()
        .seed(seed).learning_rate(learning_rate).updater(updater)
        .data_type(dtype).compute_data_type(compute_dtype)
        .scan_layers(scan_layers).remat(remat)
        .weight_init("DISTRIBUTION")
        .dist(Distribution(kind="normal", mean=0.0, std=init_std))
        .list()
        .layer(TokenEmbedding(n_in=vocab, n_out=d_model))
    )
    for i in range(n_layers):
        b.layer(dense if i < n_dense_layers else sparse)
    b.layer(LMOutputLayer(
        n_in=d_model, n_out=vocab, eps=rms_eps,
        block_rows=loss_block_rows,
        next_token=sparse if next_token_modules else None,
        next_token_weight=next_token_weight, embedding_layer=0))
    return b.build()


def hybrid_ssm_lm(vocab=512, d_model=256,
                  layer_types=("mamba", "mamba", "attention", "mamba"), *,
                  n_heads=4, n_kv_heads=2, head_dim=64, ssm_heads=8,
                  ssm_head_dim=64, ssm_state=128, ssm_groups=1,
                  ssm_conv=4, ssm_chunk=256, ffn_hidden=1024,
                  embedding_multiplier=1.0, residual_multiplier=1.0,
                  attention_multiplier=0.0, logits_scaling=1.0,
                  tie_embeddings=True, rms_eps=1e-5, init_std=0.02,
                  loss_block_rows=1024, updater="ADAM",
                  learning_rate=1e-4, seed=42, dtype="float32",
                  compute_dtype=None, scan_layers=False, remat="none"):
    """Decoder-only language model of the hybrid state-space family
    (Granite 4.0-H and its kind): a token embedding times
    ``embedding_multiplier``; one pre-norm block a ``layer_types``
    entry, its mixer a Mamba-2 ``StateSpaceMixer`` (``"mamba"``) or a
    position-free ``GroupedQueryAttention`` (``"attention"``, scores
    times ``attention_multiplier``; 0 means ``1/sqrt(head_dim)``), a
    gated feed-forward layer in every block, both branches times
    ``residual_multiplier`` before they join the residual; a final RMS
    norm and a head that is the embedding transposed
    (``tie_embeddings``), logits over ``logits_scaling``.

    Inputs are ids ``[b, t]``, labels the ids one position on. No
    block keeps state, so ``scan_layers`` scans each run of like
    blocks (``[mamba x5, attention, mamba x4]`` gives two). It is off
    by default: at Granite's widths the scanned runs hand their
    gradients over as stacked float32 arrays and the training step
    compiles to 15.5 GiB for a v5e against 12.2 GiB unrolled (PERF.md
    section 4), and only the unrolled program has run on a chip."""
    from deeplearning4j_tpu.nn.layers import (
        DecoderBlock,
        GatedFeedForward,
        GroupedQueryAttention,
        LMOutputLayer,
        StateSpaceMixer,
        TokenEmbedding,
    )
    from deeplearning4j_tpu.nn.weights import Distribution

    mixers = {
        "mamba": StateSpaceMixer(
            n_in=d_model, n_heads=ssm_heads, head_dim=ssm_head_dim,
            state_size=ssm_state, n_groups=ssm_groups,
            conv_width=ssm_conv, chunk=ssm_chunk, eps=rms_eps),
        "attention": GroupedQueryAttention(
            n_in=d_model, n_heads=n_heads, n_kv_heads=n_kv_heads,
            head_dim=head_dim, scale=attention_multiplier),
    }
    unknown = set(layer_types) - set(mixers)
    if unknown:
        raise ValueError(
            f"layer_types holds {sorted(unknown)}; known: {sorted(mixers)}")
    b = (
        NeuralNetConfiguration.Builder()
        .seed(seed).learning_rate(learning_rate).updater(updater)
        .data_type(dtype).compute_data_type(compute_dtype)
        .scan_layers(scan_layers).remat(remat)
        .weight_init("DISTRIBUTION")
        .dist(Distribution(kind="normal", mean=0.0, std=init_std))
        .list()
        .layer(TokenEmbedding(n_in=vocab, n_out=d_model,
                              multiplier=embedding_multiplier))
    )
    for kind in layer_types:
        b.layer(DecoderBlock(
            attention=mixers[kind], n_in=d_model, eps=rms_eps,
            residual_multiplier=residual_multiplier,
            ffn=GatedFeedForward(n_in=d_model, hidden_size=ffn_hidden)))
    b.layer(LMOutputLayer(
        n_in=d_model, n_out=vocab, eps=rms_eps,
        block_rows=loss_block_rows, embedding_layer=0,
        tie_embeddings=tie_embeddings, logits_scaling=logits_scaling))
    return b.build()
