"""The main path's kernels, compiled for a described TPU v5e at real
widths — no chip attached, nothing runs. The chip's compiler is
installed beside JAX and refuses here exactly what it would refuse on
the machine: block shapes off the (8, 128) tile, gathers it cannot
lower, tilings that overflow fast memory. Interpret-mode parity tests
(``test_matmul_block.py``, ``test_pallas_ops.py``) cannot see any of
that.

The rule under test (docs/ARCHITECTURE.md, "Kernel eligibility"): a
call site routes to a kernel only where the ``*_ok`` predicate holds,
and the predicate holds only where the compiler accepts the kernel —
eligible implies compiles; what the compiler refuses is reported
ineligible from shape and dtype alone. A convolution has no kernel:
``ConvolutionLayer`` hands XLA the NCHW call on a TPU, and the cases
below compile that call as the chip process lowers it.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every xdist worker imports
every test file. All such tests live in this one file for the same
reason.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from deeplearning4j_tpu.nn.layers import (
    ConvolutionLayer,
    DenseLayer,
    MultiHeadSelfAttention,
)
from deeplearning4j_tpu.ops import dispatch, tiling
from deeplearning4j_tpu.ops.flash_attention import mha
from deeplearning4j_tpu.ops.lstm_cell import lstm_sequence, lstm_sequence_ok
from deeplearning4j_tpu.ops.matmul_block import matmul_block, matmul_block_ok

BF16, F32 = "bfloat16", "float32"


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies

    # the compiler otherwise writes its logs under the temp dir
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no libtpu / lock held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def as_on_chip(monkeypatch):
    """Route and lower as the chip process would: this process sees
    the CPU, so the platform predicate is steered here, in the test."""
    monkeypatch.setattr(dispatch, "effective_platform", lambda: "tpu")
    monkeypatch.setenv("DL4J_TPU_PALLAS", "auto")
    monkeypatch.setenv("DL4J_TPU_TUNE", "off")
    dispatch.reset_for_tests()
    yield
    dispatch.reset_for_tests()


@pytest.fixture()
def kernels_forced(as_on_chip, monkeypatch):
    """``DL4J_TPU_PALLAS=1`` on the chip: a layer sends every call the
    compiler accepts to its kernel."""
    monkeypatch.setenv("DL4J_TPU_PALLAS", "1")
    dispatch.reset_for_tests()


def _specs(sharding, shapes, dtype):
    return [jax.ShapeDtypeStruct(s, jnp.dtype(dtype), sharding=sharding)
            for s in shapes]


def _compile_fwd(one_chip, fn, shapes, dtype) -> str:
    """Compile ``fn`` for the described chip; the compiled text."""
    return jax.jit(fn).lower(
        *_specs(one_chip, shapes, dtype)).compile().as_text()


def _compile_grad(one_chip, fn, shapes, dtype) -> str:
    """Compile ``jax.grad`` of ``fn``'s f32 sum, all arguments."""
    def loss(*a):
        return fn(*a).astype(jnp.float32).sum()

    return jax.jit(
        jax.grad(loss, argnums=tuple(range(len(shapes))))
    ).lower(*_specs(one_chip, shapes, dtype)).compile().as_text()


# (id, x NCHW, w OIHW, stride, padding) — three of ResNet-50's
# convolutions (zoo/models.py: a bottleneck's 3x3 and its 1x1
# expansion, and the stem). The batches are those at which the chip's
# compiler is quickest with each (the 3x3 takes it 5 s at 32 and 18 s
# at 128, the stem 7 s at 128 and 15-45 s at 32)
CONVS = [
    ("3x3_s1_56", (32, 64, 56, 56), (64, 64, 3, 3), (1, 1), (1, 1)),
    ("1x1_s1_56_to256", (32, 64, 56, 56), (256, 64, 1, 1), (1, 1), (0, 0)),
    ("7x7_s2_stem", (128, 3, 224, 224), (64, 3, 7, 7), (2, 2), (3, 3)),
]
MATMULS = [
    ("head_2048x1000", 128, 2048, 1000),
    ("mlp_784x500", 256, 784, 500),
]
LSTM = ("lstm_T64_b256_n1024", 64, 256, 1024)
# attention classes are (b, h, t, d); the arrays are [b, t, h*d]
ATTN = ("attn_8x8x1024x64", (8, 8, 1024, 64))
# chartransformer12.fit's class (two heads a program), the same width
# as four heads of 128 (one a program), and the longest sequences the
# resident schedule (and with it the fused backward) takes with 128
# lanes a program: 8192 in bfloat16, 4096 in float32
ATTN_GRAD = [
    ((64, 8, 512, 64), BF16), ((64, 8, 512, 64), F32),
    ((64, 4, 512, 128), BF16), ((64, 4, 512, 128), F32),
    ((1, 8, 8192, 64), BF16), ((4, 8, 4096, 64), F32),
]


def _matmul_fn(x, w, b):
    return matmul_block(x, w, b, activation="relu")


def _lstm_fn(xproj, h0, c0, rw):
    return lstm_sequence(xproj, h0, c0, rw)[0]


def _lstm_shapes(T, b, n):
    return [(T, b, 4 * n), (b, n), (b, n), (n, 4 * n)]


def _attn_fn(n_heads):
    return lambda q, k, v: mha(q, k, v, n_heads, causal=True)


def _attn_shapes(shape):
    b, h, t, d = shape
    return [(b, t, h * d)] * 3


@pytest.mark.parametrize("dtype", [BF16, F32])
@pytest.mark.parametrize("case", CONVS, ids=[c[0] for c in CONVS])
def test_convolution_layer_compiles_from_the_nchw_call(
        one_chip, kernels_forced, case, dtype):
    """The call both cells' chip runs make: ``ConvolutionLayer`` on a
    TPU hands XLA the convolution with NCHW/OIHW operands, and its
    forward with its gradient compiles for the chip with no kernel in
    the program, even where kernels are forced on (``ConcatBitcast``
    and the like are XLA's own custom calls, so the kernels' target
    is what is looked for)."""
    _, xs, ws, stride, padding = case
    layer = ConvolutionLayer(n_in=ws[1], n_out=ws[0],
                             kernel_size=ws[2:], stride=stride,
                             padding=padding, activation="relu")

    def loss(x, w, b):
        y = layer.apply({"W": w, "b": b}, x, {})[0]
        return y.astype(jnp.float32).sum()

    lowered = jax.jit(
        jax.value_and_grad(loss, argnums=(0, 1, 2))
    ).lower(*_specs(one_chip, [xs, ws, (ws[0],)], dtype))
    assert "dim_numbers = [b, f, 0, 1]x[o, i, 0, 1]->[b, f, 0, 1]" \
        in lowered.as_text()
    text = lowered.compile().as_text()
    assert "conv_general_dilated" in text  # in the fusions' op_name
    assert "tpu_custom_call" not in text


@pytest.mark.parametrize("dtype", [BF16, F32])
@pytest.mark.parametrize(
    "case", MATMULS, ids=[c[0] for c in MATMULS])
def test_matmul_block_compiles(one_chip, as_on_chip, case, dtype):
    _, m, k, n = case
    assert matmul_block_ok(m, k, n, jnp.dtype(dtype))
    bm, bn = tiling.pick_matmul_blocks(m, k, n, jnp.dtype(dtype).itemsize)
    assert tiling.block_dim_ok(bm, m, 8) and tiling.block_dim_ok(bn, n, 128)
    shapes = [(m, k), (k, n), (n,)]
    assert "tpu_custom_call" in _compile_fwd(one_chip, _matmul_fn, shapes,
                                             dtype)
    _compile_grad(one_chip, _matmul_fn, shapes, dtype)  # XLA backward


def test_vmem_accounting_matches_the_compiler(one_chip, as_on_chip):
    """Tilings the compiler refused for fast memory ("Ran out of
    memory in memory space vmem") under the old accounting, which
    counted every block once and unpadded: the compiler double-buffers
    a block that moves over the grid and pads the last two dims to the
    tile. ``tiling.vmem_block_bytes`` now says the same, so a matmul
    whose row block and weight panel both move gets a tile that fits
    — and compiles."""
    # a 3-channel image block costs 128 lanes, and moves: two buffers
    assert tiling.vmem_block_bytes((226, 226, 3), 2, moves=True) \
        == 2 * 226 * 240 * 128 * 2
    m, k, n = 512, 4096, 4096
    bm, bn = tiling.pick_matmul_blocks(m, k, n, 4)
    moving = 2 * 4 * (bm * k + k * bn + bm * bn)  # all three blocks move
    assert moving <= 16 * 2 ** 20, (bm, bn)
    assert "tpu_custom_call" in _compile_fwd(
        one_chip, _matmul_fn, [(m, k), (k, n), (n,)], F32)


def test_lstm_sequence_compiles(one_chip, as_on_chip):
    _, T, b, n = LSTM
    assert lstm_sequence_ok(n, 4 * n, jnp.bfloat16, b)
    shapes = _lstm_shapes(T, b, n)
    assert "tpu_custom_call" in _compile_fwd(one_chip, _lstm_fn, shapes,
                                             BF16)
    assert "tpu_custom_call" in _compile_grad(one_chip, _lstm_fn, shapes,
                                              BF16)


@pytest.mark.parametrize("dtype", [BF16, F32])
def test_flash_attention_compiles(one_chip, as_on_chip, dtype):
    _, shape = ATTN
    assert tiling.attention_seq_ok(shape[2])
    assert "tpu_custom_call" in _compile_fwd(
        one_chip, _attn_fn(shape[1]), _attn_shapes(shape), dtype)
    _compile_grad(one_chip, _attn_fn(shape[1]), _attn_shapes(shape), dtype)


@pytest.mark.parametrize(
    "shape,dtype", ATTN_GRAD,
    ids=lambda v: v if isinstance(v, str) else f"t{v[2]}d{v[3]}")
def test_flash_attention_gradient_is_a_kernel_pair(one_chip, as_on_chip,
                                                   shape, dtype):
    """The differentiated call compiles for the chip as the forward
    kernel (with its logsumexp) and the fused backward kernel: no
    [t, t] score matrix among the program's arrays."""
    import re

    text = _compile_grad(one_chip, _attn_fn(shape[1]), _attn_shapes(shape),
                         dtype)
    names = _kernel_names(text)
    assert any("flash_attention_fwd_" in n for n in names), names
    assert any("flash_attention_bwd_" in n for n in names), names
    b, h, t, _ = shape
    assert not re.search(rf"\[({b},{h}|{b * h}),{t},{t}\]", text)


def test_flash_attention_streams_beyond_the_resident_reach(one_chip,
                                                           as_on_chip):
    """float32 K/V of 8192 rows by 128 lanes are twice what the
    resident schedule may hold (the compiler refuses that kernel for
    VMEM): the call is still eligible, streams its forward, and
    compiles."""
    text = _compile_grad(one_chip, _attn_fn(8), [(1, 8192, 512)] * 3, F32)
    names = _kernel_names(text)
    assert any("flash_attention_fwd_streamed_" in n for n in names), names
    assert not any("flash_attention_bwd_" in n for n in names), names


# (id, x [b, t, c], dtype, taps): granite40hmicro.fit_4k's class, a
# length the time block does not divide in float32, short rows of two
# taps
DEPTHWISE = [
    ("granite_2x4096x4352", (2, 4096, 4352), BF16, 4),
    ("t5000_f32", (1, 5000, 2304), F32, 4),
    ("k2_8x1024x4352", (8, 1024, 4352), BF16, 2),
]


@pytest.mark.parametrize("case", DEPTHWISE, ids=[c[0] for c in DEPTHWISE])
def test_depthwise_conv_gradient_is_the_backward_kernel(one_chip,
                                                        as_on_chip, case):
    """The Mamba-2 convolution's gradient compiles for the chip as XLA's
    forward and the one backward kernel, named at its shape."""
    from deeplearning4j_tpu.nn.layers.state_space import causal_conv_silu
    from deeplearning4j_tpu.ops.depthwise_conv import depthwise_conv_bwd_ok

    _, (b, t, c), dtype, taps = case
    assert depthwise_conv_bwd_ok((b, t, c), dtype, taps)
    names = _kernel_names(_compile_grad(
        one_chip, causal_conv_silu, [(b, t, c), (taps, c), (c,)], dtype))
    assert names and all(
        f"depthwise_conv_bwd_{dtype}_{b}b_{t}t_{c}c_{taps}k" in n
        for n in names), names


def test_attention_layer_gradient_moves_no_heads(one_chip, as_on_chip):
    """``MultiHeadSelfAttention.apply`` at chartransformer12.fit's
    class, forward with backward: the q, k, v and output products
    hand ``[b, t, h*d]`` arrays to the flash pair and take them from
    it as they are — no transpose or copy of a head-split array
    anywhere in the compiled program, and no score matrix."""
    import re

    b, t, f, h = 64, 512, 512, 8
    layer = MultiHeadSelfAttention(n_in=f, n_out=f, n_heads=h, causal=True)
    params = dict(zip(
        ("Wq", "Wk", "Wv", "Wo", "bo"),
        _specs(one_chip, [(f, f)] * 4 + [(f,)], BF16)))
    (x,) = _specs(one_chip, [(b, f, t)], BF16)

    def loss(p, a):
        return layer.apply(p, a, {})[0].astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        params, x).compile().as_text()
    names = _kernel_names(text)
    assert any("flash_attention_fwd_" in n for n in names), names
    assert any("flash_attention_bwd_" in n for n in names), names
    d = f // h
    moved = re.findall(
        rf"\[(?:{b},{h},{t},{d}|{b},{t},{h},{d})\][^\n]*"
        r" (?:copy|transpose)\(", text)
    assert not moved, moved
    assert not re.search(rf"\[({b},{h}|{b * h}),{t},{t}\]", text)


def _kernel_names(text):
    """The name of every ``tpu_custom_call`` instruction in a compiled
    program's text."""
    import re

    return re.findall(
        r"%([\w.\-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"",
        text)


# what the device trace, the ledger's ``device_ops`` and the per-kernel
# metrics (benchmarks/metrics/*_ms.py) read: XLA names a custom call
# after the pallas_call's ``name=`` (inside its transform scopes, so
# ``transpose_jvp_<name>__`` in a backward pass), never after the
# enclosing scope alone
@pytest.mark.parametrize("program,expected", [
    ("matmul", ["matmul_block_fwd_bfloat16_128m_2048k_1000n"]),
    ("attention", ["flash_attention_fwd_bfloat16_8b_8h_1024t_64d"]),
    ("attention_grad", ["flash_attention_fwd_bfloat16_8b_8h_1024t_64d",
                        "flash_attention_bwd_bfloat16_8b_8h_1024t_64d"]),
    ("lstm_grad", ["lstm_sequence_bwd_bfloat16_", "lstm_sequence_fwd_"]),
])
def test_custom_calls_are_named_after_kernel_and_pass(
        one_chip, as_on_chip, program, expected):
    if program == "matmul":
        _, m, k, n = MATMULS[0]
        text = _compile_fwd(one_chip, _matmul_fn,
                            [(m, k), (k, n), (n,)], BF16)
    elif program == "attention":
        text = _compile_fwd(one_chip, _attn_fn(ATTN[1][1]),
                            _attn_shapes(ATTN[1]), BF16)
    elif program == "attention_grad":
        text = _compile_grad(one_chip, _attn_fn(ATTN[1][1]),
                             _attn_shapes(ATTN[1]), BF16)
    else:
        _, T, b, n = LSTM
        text = _compile_grad(one_chip, _lstm_fn, _lstm_shapes(T, b, n),
                             BF16)
    names = _kernel_names(text)
    assert names, "no tpu_custom_call in the compiled text"
    for name in names:
        assert any(e in name for e in expected), (name, expected)
    for e in expected:
        assert any(e in name for name in names), (e, names)
    # a shape tag ends in a letter: trace readers strip trailing
    # digits and dots to fold an operation's runs together
    # (a transform scope closes with underscores: jvp_<name>_)
    import re

    for name in names:
        assert re.sub(r"[.\d]+$", "", name).rstrip("_")[-1].isalpha(), name


def test_eligible_implies_compiles(one_chip, as_on_chip):
    """Table-driven form of the rule, over every shape in this file:
    where a predicate says yes the forward compiles for the chip (a
    refusal raises here); what the compiler refuses must be reported
    ineligible — never eligible-and-refused."""
    table = []
    for dtype in (BF16, F32):
        dt = jnp.dtype(dtype)
        for name, m, k, n in MATMULS:
            table.append((
                f"{name}-{dtype}", matmul_block_ok(m, k, n, dt),
                _matmul_fn, [(m, k), (k, n), (n,)], dtype,
            ))
        name, T, b, n = LSTM
        table.append((
            f"{name}-{dtype}", lstm_sequence_ok(n, 4 * n, dt, b),
            _lstm_fn, _lstm_shapes(T, b, n), dtype,
        ))
    eligible = {}
    for name, ok, fn, shapes, dtype in table:
        eligible[name] = ok
        if not ok:
            continue
        assert "tpu_custom_call" in _compile_fwd(one_chip, fn, shapes,
                                                 dtype), name
    # f32 at this size keeps RW out of fast memory: gated, not refused
    assert not eligible[f"{LSTM[0]}-{F32}"]
    assert eligible[f"{LSTM[0]}-{BF16}"]


def test_gspmd_over_four_chips_takes_xla(topo, kernels_forced):
    """A program the compiler partitions over the 2x2 mesh by itself
    cannot hold a Mosaic kernel: traced in ``dispatch.auto_partitioned``
    (as ``DistributedTrainer``'s GSPMD step is) the layer compiles with
    XLA's dot and an all-reduce, even with the kernels forced on;
    without the scope the compiler's own refusal is what a user who
    forces them would meet."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from deeplearning4j_tpu.parallel.mesh import build_mesh

    mesh = build_mesh(devices=topo.devices)
    rep, batch = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
    layer = DenseLayer(n_in=512, n_out=512, activation="relu")
    params = {
        "W": jax.ShapeDtypeStruct((512, 512), jnp.bfloat16,
                                  sharding=rep),
        "b": jax.ShapeDtypeStruct((512,), jnp.bfloat16, sharding=rep),
    }
    x = jax.ShapeDtypeStruct((256, 512), jnp.bfloat16, sharding=batch)

    def grad_w(scoped):
        def loss(p, a):
            with dispatch.auto_partitioned(scoped):
                y = layer.apply(p, a, {})[0]
            return y.astype(jnp.float32).sum()
        # the loss with the gradient: the kernel's backward is XLA's
        # and recomputes, so the gradient alone holds no kernel
        return jax.jit(jax.value_and_grad(loss), out_shardings=rep)

    text = grad_w(True).lower(params, x).compile().as_text()
    assert "tpu_custom_call" not in text
    assert "all-reduce" in text
    with pytest.raises(NotImplementedError,
                       match="cannot be automatically partitioned"):
        grad_w(False).lower(params, x)


def _glm_scan_program(text, net, batch):
    """Latent attention's six layers run the flash pair at 20 heads of
    256 (resident schedule: a program's K or V is exactly the 2 MiB it
    may hold), the held experts' products are the compiler's grouped
    kernels, on the 8,192 rows of the row ladder's first rung among
    others, no conditional hands out an array of all 32,768
    token-slots (the backward pass differentiates inside the rung it
    takes, so no rung's residuals cross a switch)."""
    import re

    names = _kernel_names(text)
    for kernel in ("flash_attention_fwd_", "flash_attention_bwd_"):
        mine = {n for n in names if kernel in n}
        assert mine and all(
            re.search(rf"{kernel}bfloat16_{batch}b_20h_4096t_256d", n)
            for n in mine), names
    assert "ragged-dot" in text
    grouped = [line for line in text.splitlines()
               if "ragged-dot" in line and "custom-call(" in line]
    assert any(re.search(r"= bf16\[8192,(2048|1536)\]\S* custom-call\(",
                         line) for line in grouped), grouped[:3]
    switches = [line.split(" conditional(")[0]
                for line in text.splitlines() if " conditional(" in line]
    assert switches and not any("[32768," in out for out in switches)
    assert net._active_layer_runs() == ()     # stateful blocks unroll
    return 20


def _granite_scan_program(text, net, batch):
    """The one attention block runs the flash pair at 32 heads of 64
    (k and v repeated from 8), the nine state-space blocks their
    chunked scan in XLA under its scopes with decay matrices of one
    chunk's square and never of the sequence's and their convolution's
    backward as the Pallas kernel under ``ssm.conv`` (the forward is
    XLA's, so no forward kernel of that name), the head is the
    embedding's own array, and the two runs of like blocks are found
    and left unrolled (the configuration's ``assumed`` says why)."""
    import re

    names = _kernel_names(text)
    for kernel in ("flash_attention_fwd_", "flash_attention_bwd_"):
        mine = {n for n in names if kernel in n}
        assert mine and all(
            re.search(rf"{kernel}bfloat16_{batch}b_32h_4096t_64d", n)
            for n in mine), names
    conv = [line for line in text.splitlines()
            if re.match(r"\s*%[\w.\-]*depthwise_conv_bwd_[\w.\-]* = ", line)]
    assert len(conv) == 9 and all(
        f"depthwise_conv_bwd_bfloat16_{batch}b_4096t_4352c_4k" in line
        and "ssm.conv" in line for line in conv), names
    assert not any("depthwise_conv_fwd" in n for n in names)
    for scope in ("ssm.scan.intra", "ssm.scan.states", "ssm.scan.pass",
                  "ssm.scan.inter", "ssm.conv", "ssm.gate_norm", "gqa.qkv",
                  "mlp", "lm_head"):
        assert scope in text, scope
    assert re.search(rf"bf16\[{batch},64,16,256,256\]", text)
    assert not re.search(rf"\[({batch},64|{batch * 64}),4096,4096\]", text)
    assert not net.scan_layers
    assert net._active_layer_runs() == ((1, 6), (7, 11))
    assert net.conf.layers[-1].tied_params() == (("embed", 0, "W"),)
    return 32


@pytest.mark.parametrize("workload, state_bytes, check", [
    ("glm47flash.fit_4k", 8e9, _glm_scan_program),
    ("granite40hmicro.fit_4k", 9e9, _granite_scan_program)],
    ids=["glm47flash.fit_4k", "granite40hmicro.fit_4k"])
def test_token_cell_scan_program_fits_the_chip(one_chip, as_on_chip,
                                               workload, state_bytes,
                                               check):
    """A token cell's window program — ``fit()``'s scan of 16
    optimizer steps on ids, at the cell's own sizes, no weight made —
    compiles for the described v5e: arguments (weights and Adam's
    moments, at least ``state_bytes``) and temporaries fit the 16 GB
    the harness counts, the cell's own kernels and shapes are there
    (``check``), and no score matrix is among the program's arrays."""
    import re

    from benchmarks.tools.compile_described_tokens import (
        compile_scan_program,
    )

    compiled, net, batch = compile_scan_program(workload, one_chip)
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9
    assert mem.argument_size_in_bytes > state_bytes
    text = compiled.as_text()
    h, t = check(text, net, batch), 4096
    assert not re.search(rf"\[({batch},{h}|{batch * h}),{t},{t}\]", text)
