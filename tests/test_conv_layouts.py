"""Who computes a convolution: XLA, on every platform
(``ConvolutionLayer.pre_output``), from the API's NCHW/OIHW operands on
a TPU and through NHWC/HWIO on the CPU, whose fast convolutions exist
for that layout alone. No kernel, routing table or switch is involved,
and ``DL4J_TPU_PALLAS`` changes nothing about it.

No chip here: the platform gate is steered in the test (ROADMAP D9's
pattern), so the branch both benchmark cells' chip runs take is
executed on the CPU and held against the CPU's own branch and against
``lax.conv_general_dilated`` at the highest precision, per convolution
class of ResNet-50 at 224x224.
"""

import collections
import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from conftest import dispatch_counts
from deeplearning4j_tpu.nn import core
from deeplearning4j_tpu.nn.conf import InputType, NeuralNetConfiguration
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.nn.layers import (
    BatchNormalization,
    ConvolutionLayer,
    DenseLayer,
    OutputLayer,
)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.ops import dispatch
from deeplearning4j_tpu.zoo import resnet50

# ResNet-50's convolutions at 224x224 by class, with how many of the
# model's 53 fall in each (zoo/models.py):
# (kernel, stride, c_in, c_out, input extent) -> count
RESNET50_CLASSES = {
    (7, 2, 3, 64, 224): 1,
    (1, 1, 64, 64, 56): 1,
    (1, 1, 64, 256, 56): 4,
    (3, 1, 64, 64, 56): 3,
    (1, 1, 256, 64, 56): 2,
    (1, 1, 256, 128, 56): 1,
    (3, 2, 128, 128, 56): 1,
    (1, 2, 256, 512, 56): 1,
    (1, 1, 128, 512, 28): 4,
    (1, 1, 512, 128, 28): 3,
    (3, 1, 128, 128, 28): 3,
    (1, 1, 512, 256, 28): 1,
    (3, 2, 256, 256, 28): 1,
    (1, 2, 512, 1024, 28): 1,
    (1, 1, 256, 1024, 14): 6,
    (1, 1, 1024, 256, 14): 5,
    (3, 1, 256, 256, 14): 5,
    (1, 1, 1024, 512, 14): 1,
    (3, 2, 512, 512, 14): 1,
    (1, 2, 1024, 2048, 14): 1,
    (1, 1, 512, 2048, 7): 3,
    (1, 1, 2048, 512, 7): 2,
    (3, 1, 512, 512, 7): 2,
}
CLASSES = sorted(RESNET50_CLASSES)


def _class_id(cls):
    k, s, c, o, hw = cls
    return f"{k}x{k}s{s}_{c}to{o}_at{hw}"


@contextlib.contextmanager
def platform(name):
    """Steer the platform gate as a process on ``name`` would see it."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(dispatch, "effective_platform", lambda: name)
        yield


def _set_mode(monkeypatch, mode):
    if mode is None:
        monkeypatch.delenv("DL4J_TPU_PALLAS", raising=False)
    else:
        monkeypatch.setenv("DL4J_TPU_PALLAS", mode)
    dispatch.reset_for_tests()


# ---------------------------------------------------------------------------
# (a) the two layouts, per class
# ---------------------------------------------------------------------------


def _layer_and_data(cls, dtype):
    k, s, c, o, hw = cls
    layer = ConvolutionLayer(n_in=c, n_out=o, kernel_size=(k, k),
                             stride=(s, s), padding=(k // 2, k // 2))
    rng = np.random.RandomState(CLASSES.index(cls))
    x = jnp.asarray(rng.randn(1, c, hw, hw), dtype)
    w = jnp.asarray(rng.randn(o, c, k, k) / np.sqrt(c * k * k), dtype)
    b = jnp.asarray(rng.randn(o) * 0.1, dtype)
    return layer, x, w, b


def _layer_call(layer):
    """A new function each time: JAX caches a trace by the function
    traced, and each side must be traced under its own platform."""
    def call(x, w, b):
        return layer.apply({"W": w, "b": b}, x, {})[0]
    return call


def _reference_call(cls):
    k, s, _, _, _ = cls

    def call(x, w, b):
        y = lax.conv_general_dilated(
            x.astype(jnp.float32), w.astype(jnp.float32), (s, s),
            ((k // 2, k // 2),) * 2,
            dimension_numbers=("NCHW", "OIHW", "NCHW"),
            precision=lax.Precision.HIGHEST)
        return y + b.astype(jnp.float32).reshape(1, -1, 1, 1)
    return call


def _conv_layouts(fn, *args):
    """The operand layout of every convolution ``fn`` traces to, as
    the position of (batch, feature) among the left operand's axes."""
    return [e.params["dimension_numbers"].lhs_spec[:2]
            for e in jax.make_jaxpr(fn)(*args).eqns
            if e.primitive.name == "conv_general_dilated"]


@pytest.mark.parametrize("cls", CLASSES, ids=_class_id)
def test_forward_float32_same_on_both_layouts(cls):
    layer, x, w, b = _layer_and_data(cls, jnp.float32)
    with platform("tpu"):
        call = _layer_call(layer)
        assert _conv_layouts(call, x, w, b) == [(0, 1)]  # NCHW as given
        y_tpu = jax.jit(call)(x, w, b)
    with platform("cpu"):
        call = _layer_call(layer)
        assert _conv_layouts(call, x, w, b) == [(0, 3)]  # NHWC
        y_cpu = jax.jit(call)(x, w, b)
    ref = np.asarray(jax.jit(_reference_call(cls))(x, w, b))
    k, s, _, o, hw = cls
    assert y_tpu.shape == y_cpu.shape == (1, o, -(-hw // s), -(-hw // s))
    assert y_tpu.dtype == y_cpu.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(y_tpu), ref, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(y_cpu), ref, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("cls", CLASSES, ids=_class_id)
def test_forward_bfloat16_same_on_both_layouts(cls):
    """bfloat16 operands (both cells' compute type): either branch
    returns bfloat16, within the type's rounding of the float32
    convolution of the same operands."""
    layer, x, w, b = _layer_and_data(cls, jnp.bfloat16)
    with platform("tpu"):
        y_tpu = jax.jit(_layer_call(layer))(x, w, b)
    with platform("cpu"):
        y_cpu = jax.jit(_layer_call(layer))(x, w, b)
    ref = np.asarray(jax.jit(_reference_call(cls))(x, w, b))
    assert y_tpu.dtype == y_cpu.dtype == jnp.bfloat16
    for y in (y_tpu, y_cpu):
        np.testing.assert_allclose(
            np.asarray(y.astype(jnp.float32)), ref, rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("cls", CLASSES, ids=_class_id)
def test_gradients_float32_same_on_both_layouts(cls):
    """dL/dx, dL/dW and dL/db of a weighted sum of the outputs."""
    layer, x, w, b = _layer_and_data(cls, jnp.float32)
    k, s, _, o, hw = cls
    out = -(-hw // s)
    g = jnp.asarray(
        np.random.RandomState(7).randn(1, o, out, out), jnp.float32)

    def grads(call):
        return jax.jit(jax.grad(
            lambda *a: jnp.sum(call(*a) * g), argnums=(0, 1, 2)))(x, w, b)

    with platform("tpu"):
        g_tpu = grads(_layer_call(layer))
    with platform("cpu"):
        g_cpu = grads(_layer_call(layer))
    g_ref = grads(_reference_call(cls))
    for name, a_tpu, a_cpu, a_ref in zip(("dx", "dW", "db"), g_tpu, g_cpu,
                                         g_ref):
        scale = float(np.abs(np.asarray(a_ref)).max()) + 1e-6
        for side, a in (("tpu", a_tpu), ("cpu", a_cpu)):
            np.testing.assert_allclose(
                np.asarray(a) / scale, np.asarray(a_ref) / scale,
                rtol=1e-4, atol=1e-5, err_msg=f"{name} {side}")


# ---------------------------------------------------------------------------
# (a, b) the table is the model's, and no convolution meets the switch
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def net():
    return ComputationGraph(resnet50(compute_dtype="bfloat16")).init()


def _walk(net):
    """One training forward of ``net``, traced for shapes only."""
    return jax.eval_shape(
        lambda p, s, x: net._forward_values(
            p, s, [x], train=True, rng=jax.random.PRNGKey(0))[0],
        net.params, net.state,
        jax.ShapeDtypeStruct((2, 3, 224, 224), jnp.float32))


def test_table_is_what_zoo_resnet50_holds(net, monkeypatch):
    seen = collections.Counter()
    pre_output = ConvolutionLayer.pre_output

    def recording(self, params, x):
        kh, kw = self.kernel_size
        sh, sw = self.stride
        assert kh == kw and sh == sw and x.shape[2] == x.shape[3]
        assert tuple(self.padding) == (kh // 2, kw // 2)
        assert x.dtype == jnp.bfloat16
        seen[(kh, sh, x.shape[1], self.n_out, x.shape[2])] += 1
        return pre_output(self, params, x)

    monkeypatch.setattr(ConvolutionLayer, "pre_output", recording)
    _walk(net)
    assert dict(seen) == RESNET50_CLASSES
    assert sum(RESNET50_CLASSES.values()) == 53
    assert sum(n for c, n in RESNET50_CLASSES.items() if c[1] == 1) == 46


@pytest.mark.parametrize("where,mode", [
    ("tpu", None), ("tpu", "auto"), ("tpu", "1"), ("tpu", "0"), ("cpu", "1"),
])
def test_resnet50_walk_meters_the_head_alone(net, monkeypatch, where, mode):
    """Whatever ``DL4J_TPU_PALLAS`` says, on a TPU and off it: the 53
    convolutions leave no trace in ``pallas_dispatch_total``; the one
    decision metered is the softmax head's, which stays on XLA."""
    _set_mode(monkeypatch, mode)
    with platform(where):
        before = dispatch_counts()
        _walk(net)
        after = dispatch_counts()
    assert after - before == {("matmul_block", "xla"): 1}
    assert not [k for k in after if k[0].startswith("conv")]


# ---------------------------------------------------------------------------
# (c) output() of a conv -> BN -> ReLU stack: one walk, kernels or not
# ---------------------------------------------------------------------------


def _conv_bn_mln():
    conf = (
        NeuralNetConfiguration.Builder().seed(3).learning_rate(0.05)
        .list()
        .layer(ConvolutionLayer(n_out=4, kernel_size=(3, 3),
                                padding=(1, 1), activation="identity"))
        .layer(BatchNormalization(activation="relu"))
        .layer(ConvolutionLayer(n_out=6, kernel_size=(3, 3),
                                stride=(2, 2), activation="identity"))
        .layer(BatchNormalization(activation="relu"))
        .layer(OutputLayer(n_out=3))
        .set_input_type(InputType.convolutional(8, 8, 3))
        .build()
    )
    return MultiLayerNetwork(conf).init()


def _conv_bn_graph():
    b = (NeuralNetConfiguration.Builder().seed(4).learning_rate(0.05)
         .graph_builder().add_inputs("in"))
    b.add_layer("c0", ConvolutionLayer(n_out=4, kernel_size=(3, 3),
                                       padding=(1, 1),
                                       activation="identity"), "in")
    b.add_layer("bn0", BatchNormalization(activation="relu"), "c0")
    b.add_layer("c1", ConvolutionLayer(n_out=6, kernel_size=(3, 3),
                                       stride=(2, 2),
                                       activation="identity"), "bn0")
    b.add_layer("bn1", BatchNormalization(activation="relu"), "c1")
    b.add_layer("out", OutputLayer(n_out=3), "bn1")
    b.set_outputs("out")
    b.set_input_types(InputType.convolutional(8, 8, 3))
    return ComputationGraph(b.build()).init()


@pytest.mark.parametrize("build", [_conv_bn_mln, _conv_bn_graph],
                         ids=["multilayer", "graph"])
def test_output_of_conv_bn_stack_is_bitwise_kernels_on_or_off(
        build, monkeypatch):
    """Nothing in this network has a kernel (the softmax head stays on
    XLA), so forcing kernels on traces the program kernels off does:
    the inference walk holds no convolution special case."""
    r = np.random.RandomState(0)
    x = r.randn(4, 3, 8, 8).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[r.randint(0, 3, 4)]

    _set_mode(monkeypatch, "0")
    trained = build()
    for _ in range(2):
        trained.fit(x, y)  # running statistics off their initial values
    y_off = trained.output(x)

    _set_mode(monkeypatch, "1")
    # a fresh network: a jitted forward keeps the path it was traced with
    forced = build()
    forced.params, forced.state = trained.params, trained.state
    before = dispatch_counts()
    y_on = forced.output(x)
    routed = dispatch_counts() - before
    assert routed == {("matmul_block", "xla"): 1}
    first = (lambda o: o[0] if isinstance(o, (list, tuple)) else o)
    np.testing.assert_array_equal(np.asarray(first(y_on)),
                                  np.asarray(first(y_off)))


# ---------------------------------------------------------------------------
# (d) the AOT kind follows the dense layers alone
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("head,suffix", [
    (None, ""), (DenseLayer(n_out=3, activation="relu"), "+kernels"),
], ids=["convolutions_alone", "dense_head"])
def test_kernel_kind_suffix_asks_for_a_dense_layer(monkeypatch, head,
                                                   suffix):
    monkeypatch.setenv("DL4J_TPU_TUNE", "off")
    _set_mode(monkeypatch, "1")
    b = (NeuralNetConfiguration.Builder().seed(1).list()
         .layer(ConvolutionLayer(n_out=4, kernel_size=(3, 3),
                                 activation="relu"))
         .layer(BatchNormalization()))
    if head is not None:
        b.layer(head)
    net = MultiLayerNetwork(
        b.set_input_type(InputType.convolutional(8, 8, 3)).build()).init()
    assert core.kernel_kind_suffix(net) == suffix
    assert net._output_kind() == "output" + suffix
    _set_mode(monkeypatch, "0")
    assert core.kernel_kind_suffix(net) == ""
