"""Which convolutions reach ``conv_block`` (``ops/dispatch.py``,
``ConvolutionLayer._kernel_eligible``, ``ops.conv_block.conv_block_faster``).

The rule under test: with ``DL4J_TPU_PALLAS`` unset (``auto``) on a TPU
a convolution goes to the kernel only where the chip's compiler accepts
the call (``conv_block_ok``) AND the chip has shown the kernel faster
than XLA's convolution on that shape class; ``=1`` sends every accepted
call (the parity tests' and the A/B's knob); ``=0`` none. PR 29
measured ResNet-50's 16 unit-stride classes on one v5e and none stayed,
so ``auto`` on a TPU now counts all 53 of its convolutions as ``xla``.

No chip here: the platform gate is steered in the test (ROADMAP D9's
pattern), and the network is walked for shapes only (``jax.eval_shape``),
which meters every routing decision without running a kernel.
"""

import importlib

import jax
import jax.numpy as jnp
import pytest

from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.nn.layers import BatchNormalization, ConvolutionLayer
from deeplearning4j_tpu.nn.layers.convolution import maybe_fused_conv_bn
from deeplearning4j_tpu.observability.metrics import default_registry
from deeplearning4j_tpu.ops import conv_block_faster, conv_block_ok, dispatch
from deeplearning4j_tpu.ops.conv_block import conv_shape_class
from deeplearning4j_tpu.zoo import resnet50

# the module, not the function of the same name that the package exports
conv_block_mod = importlib.import_module("deeplearning4j_tpu.ops.conv_block")

BF16 = "bfloat16"

# ResNet-50's unit-stride convolutions at 224x224 by shape class, with
# how many of the model's 53 fall in each (zoo/models.py; the stem, three
# 3x3 and three 1x1 projections have stride 2 and are not accepted):
# (kh, kw, c_in, c_out, h, w, dtype, epilogue fused) -> count
RESNET50_CLASSES = {
    (1, 1, 64, 64, 56, 56, BF16, False): 1,
    (1, 1, 64, 256, 56, 56, BF16, False): 4,
    (3, 3, 64, 64, 56, 56, BF16, False): 3,
    (1, 1, 256, 64, 56, 56, BF16, False): 2,
    (1, 1, 256, 128, 56, 56, BF16, False): 1,
    (1, 1, 128, 512, 28, 28, BF16, False): 4,
    (1, 1, 512, 128, 28, 28, BF16, False): 3,
    (3, 3, 128, 128, 28, 28, BF16, False): 3,
    (1, 1, 512, 256, 28, 28, BF16, False): 1,
    (1, 1, 256, 1024, 14, 14, BF16, False): 6,
    (1, 1, 1024, 256, 14, 14, BF16, False): 5,
    (3, 3, 256, 256, 14, 14, BF16, False): 5,
    (1, 1, 1024, 512, 14, 14, BF16, False): 1,
    (1, 1, 512, 2048, 7, 7, BF16, False): 3,
    (1, 1, 2048, 512, 7, 7, BF16, False): 2,
    (3, 3, 512, 512, 7, 7, BF16, False): 2,
}
# what the chip decided (PERF.md section 6, PR 29): no class stays
CHIP_KEEPS = frozenset()


def _class_call(cls, batch=128):
    kh, kw, c, o, h, w, dt, _ = cls
    return (batch, c, h, w), (o, c, kh, kw), jnp.dtype(dt)


@pytest.fixture()
def on_tpu(monkeypatch):
    """Steer the platform gate as the chip process would see it."""
    monkeypatch.setattr(dispatch, "effective_platform", lambda: "tpu")


def _set_mode(monkeypatch, mode):
    if mode is None:
        monkeypatch.delenv("DL4J_TPU_PALLAS", raising=False)
    else:
        monkeypatch.setenv("DL4J_TPU_PALLAS", mode)
    dispatch.reset_for_tests()


def _conv_counts():
    family = default_registry().get("pallas_dispatch_total")
    return {
        m: 0 if family is None
        else int(family.labels(kernel="conv_block", mode=m).value)
        for m in ("pallas", "xla", "interpret")
    }


@pytest.fixture(scope="module")
def net():
    return ComputationGraph(resnet50(compute_dtype=BF16)).init()


def _routed(net):
    """``pallas_dispatch_total{kernel="conv_block"}`` of one training
    forward of ``net``, traced for shapes only."""
    before = _conv_counts()
    jax.eval_shape(
        lambda p, s, x: net._forward_values(
            p, s, [x], train=True, rng=jax.random.PRNGKey(0))[0],
        net.params, net.state,
        jax.ShapeDtypeStruct((2, 3, 224, 224), jnp.float32))
    after = _conv_counts()
    return {m: after[m] - before[m] for m in before}


@pytest.mark.parametrize("cls", sorted(RESNET50_CLASSES), ids=lambda c: (
    f"{c[0]}x{c[1]}_{c[2]}to{c[3]}_at{c[4]}"))
def test_resnet50_class_is_accepted_and_routes_as_the_chip_decided(cls):
    xs, ws, dt = _class_call(cls)
    pad = ((cls[0] - 1) // 2, (cls[1] - 1) // 2)
    assert conv_block_ok(xs, ws, (1, 1), pad, dt)
    assert conv_shape_class(xs, ws, dt, cls[-1]) == cls
    assert conv_block_faster(xs, ws, dt, fused_epilogue=cls[-1]) \
        == (cls in CHIP_KEEPS)


def test_faster_table_is_what_the_chip_decided():
    assert conv_block_mod._FASTER_THAN_XLA == CHIP_KEEPS
    assert sum(RESNET50_CLASSES.values()) == 46


# of the model's 53, how many the chip's table sends to the kernel
N_KEPT = sum(n for c, n in RESNET50_CLASSES.items() if c in CHIP_KEEPS)
AUTO_ON_TPU = {"pallas": N_KEPT, "xla": 53 - N_KEPT, "interpret": 0}


@pytest.mark.parametrize("mode,expect", [
    # auto (unset or spelled out) on a TPU: only the classes the chip kept
    (None, AUTO_ON_TPU),
    ("auto", AUTO_ON_TPU),
    # forced: every call the compiler accepts, measured or not
    ("1", {"pallas": 46, "xla": 7, "interpret": 0}),
    ("0", {"pallas": 0, "xla": 53, "interpret": 0}),
])
def test_resnet50_dispatch_counts_on_tpu(net, on_tpu, monkeypatch, mode,
                                         expect):
    _set_mode(monkeypatch, mode)
    assert _routed(net) == expect


def test_resnet50_dispatch_counts_off_tpu(net, monkeypatch):
    """The CPU's own platform: ``auto`` routes nothing; forced, the 46
    accepted calls take the kernel, interpreted."""
    _set_mode(monkeypatch, "auto")
    assert _routed(net) == {"pallas": 0, "xla": 53, "interpret": 0}
    _set_mode(monkeypatch, "1")
    assert _routed(net) == {"pallas": 0, "xla": 7, "interpret": 46}


def test_a_kept_class_routes_under_auto_and_only_that_class(
        net, on_tpu, monkeypatch):
    """The mechanism, with an entry the chip has not given: the class's
    three calls take the kernel under ``auto``, the other 50 XLA."""
    cls = (3, 3, 64, 64, 56, 56, BF16, False)
    monkeypatch.setattr(conv_block_mod, "_FASTER_THAN_XLA",
                        frozenset({cls}))
    _set_mode(monkeypatch, "auto")
    assert _routed(net) == {"pallas": RESNET50_CLASSES[cls],
                            "xla": 53 - RESNET50_CLASSES[cls],
                            "interpret": 0}
    xs, ws, dt = _class_call(cls)
    assert conv_block_faster(xs, ws, dt)
    # the class is its whole key: another dtype, extent or a fused
    # epilogue is another class, unmeasured, and takes XLA
    assert not conv_block_faster(xs, ws, jnp.float32)
    assert not conv_block_faster(xs, ws, dt, fused_epilogue=True)
    assert not conv_block_faster((128, 64, 28, 28), ws, dt)


def test_a_kept_class_the_compiler_refuses_still_takes_xla(
        on_tpu, monkeypatch):
    """``conv_block_faster`` never overrides ``conv_block_ok``: a stride-2
    call of a listed class is not sent."""
    cls = (3, 3, 128, 128, 56, 56, BF16, False)
    monkeypatch.setattr(conv_block_mod, "_FASTER_THAN_XLA",
                        frozenset({cls}))
    _set_mode(monkeypatch, "auto")
    x = jax.ShapeDtypeStruct((2, 128, 56, 56), jnp.bfloat16)
    params = {"W": jax.ShapeDtypeStruct((128, 128, 3, 3), jnp.bfloat16),
              "b": jax.ShapeDtypeStruct((128,), jnp.bfloat16)}
    strided = ConvolutionLayer(n_in=128, n_out=128, kernel_size=(3, 3),
                               stride=(2, 2), padding=(1, 1))
    unit = ConvolutionLayer(n_in=128, n_out=128, kernel_size=(3, 3),
                            padding=(1, 1))
    assert not strided._kernel_eligible(params, x, "identity")
    assert unit._kernel_eligible(params, x, "identity")
    # the layer's own activation is a fused epilogue: another class
    assert not unit._kernel_eligible(params, x, "relu")


@pytest.mark.parametrize("mode,fuses", [("auto", False), ("1", True),
                                        ("0", False)])
def test_inference_conv_bn_peephole_follows_the_same_rule(
        on_tpu, monkeypatch, mode, fuses):
    """Conv -> BN at inference is a fused-epilogue class nobody has
    measured: under ``auto`` it takes the unfused walk."""
    _set_mode(monkeypatch, mode)
    conv = ConvolutionLayer(n_in=64, n_out=64, kernel_size=(3, 3),
                            padding=(1, 1), activation="identity")
    bn = BatchNormalization(n_out=64, activation="relu")

    def walk(x, w, b, gamma, beta, mean, var):
        y = maybe_fused_conv_bn(conv, bn, {"W": w, "b": b},
                                {"gamma": gamma, "beta": beta},
                                {"mean": mean, "var": var}, x)
        return jnp.zeros(()) if y is None else y

    vec = jax.ShapeDtypeStruct((64,), jnp.float32)
    out = jax.eval_shape(
        walk, jax.ShapeDtypeStruct((2, 64, 14, 14), jnp.float32),
        jax.ShapeDtypeStruct((64, 64, 3, 3), jnp.float32),
        vec, vec, vec, vec, vec)
    assert (out.shape == (2, 64, 14, 14)) == fuses


@pytest.mark.parametrize("value,forced", [
    ("1", True), ("true", True), ("ON", True), ("auto", False),
    ("0", False), (None, False),
])
def test_pallas_forced_reads_the_one_variable(monkeypatch, value, forced):
    _set_mode(monkeypatch, value)
    assert dispatch.pallas_forced() == forced
