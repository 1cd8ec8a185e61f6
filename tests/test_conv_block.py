"""Fused conv/matmul epilogue kernels (``ops/conv_block.py`` +
``ops/matmul_block.py``) and their layer wiring.

Contract under test (the backend-vs-backend strategy of SURVEY.md §4,
as for the LSTM/flash-attention kernels): the Pallas kernels are pure
drop-ins for the XLA path — forward and gradients match the reference
at kernel tolerance, ``DL4J_TPU_PALLAS`` flips routing without
changing WHAT IS TRAINED, and every whole-net transform (scan-over-
layers, remat, grad accumulation, ZeRO) composes with the kernels on.

Tolerances (documented): on the CPU profile the kernels run in
interpret mode with f32 accumulators against an f32 reference, so
trajectories agree to ~1e-6 and assertions use ``kernel_tols()``
(2e-4/2e-5); the bench gate (``scripts/bench_kernels.py``) holds the
single-op forward to <= 1e-5. On TPU both the kernel and the XLA
reference round MXU inputs to bf16 independently, so ``kernel_tols``
widens to 2e-2/8e-3 — numerical agreement, not bit equality, is the
cross-backend contract (bit equality per backend is still asserted
where both sides run the same program).
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import kernel_tols, pallas_interpret, require_devices
from deeplearning4j_tpu.datasets.api import DataSet
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
from deeplearning4j_tpu.observability.metrics import default_registry
from deeplearning4j_tpu.ops import (
    SUPPORTED_EPILOGUES,
    conv_block,
    conv_block_ok,
    conv_block_reference,
    dispatch,
    matmul_block,
    matmul_block_ok,
    matmul_block_reference,
)

CHAOS_SEED = int(os.environ.get("DL4J_TPU_CHAOS_SEED", "1337"))


def _conv_data(n=2, c=3, h=9, w=7, o=5, kh=3, kw=3, dtype=jnp.float32,
               seed=0):
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(n, c, h, w), dtype)
    wgt = jnp.asarray(rng.randn(o, c, kh, kw) * 0.2, dtype)
    bias = jnp.asarray(rng.randn(o) * 0.1, jnp.float32)
    scale = jnp.asarray(rng.rand(o) + 0.5, jnp.float32)
    shift = jnp.asarray(rng.randn(o) * 0.1, jnp.float32)
    return x, wgt, bias, scale, shift


def _dispatch_children():
    fam = default_registry().get("pallas_dispatch_total")
    return {} if fam is None else {
        k: v.value for k, v in fam._children.items()
    }


# ---------------------------------------------------------------------------
# kernel vs reference (single op)
# ---------------------------------------------------------------------------


class TestConvBlockKernel:
    @pytest.mark.parametrize("activation", sorted(SUPPORTED_EPILOGUES))
    @pytest.mark.parametrize("stride,padding", [
        ((1, 1), (0, 0)),
        ((1, 1), (1, 1)),
        ((2, 2), (1, 1)),
        ((2, 1), (2, 0)),  # asymmetric stride AND padding
    ])
    def test_forward_matches_reference(self, activation, stride,
                                       padding):
        x, w, b, a, s = _conv_data()
        out = conv_block(x, w, b, a, s, stride=stride, padding=padding,
                         activation=activation,
                         interpret=pallas_interpret())
        ref = conv_block_reference(x, w, b, a, s, stride=stride,
                                   padding=padding,
                                   activation=activation)
        rtol, atol = kernel_tols()
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=rtol, atol=atol)

    def test_forward_without_epilogue_terms(self):
        """bias/bn default to the identity epilogue (None)."""
        x, w, _, _, _ = _conv_data()
        out = conv_block(x, w, stride=(1, 1), padding=(1, 1),
                         activation="relu", interpret=pallas_interpret())
        ref = conv_block_reference(x, w, stride=(1, 1), padding=(1, 1),
                                   activation="relu")
        rtol, atol = kernel_tols()
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=rtol, atol=atol)

    def test_bf16_forward(self):
        x, w, b, a, s = _conv_data(dtype=jnp.bfloat16)
        out = conv_block(x, w, b, a, s, stride=(1, 1), padding=(1, 1),
                         activation="tanh", interpret=pallas_interpret())
        assert out.dtype == jnp.bfloat16
        ref = conv_block_reference(x, w, b, a, s, stride=(1, 1),
                                   padding=(1, 1), activation="tanh")
        # both sides accumulate in f32 and round once to bf16 on the
        # writeback, so they agree to bf16 eps
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            rtol=2e-2, atol=1e-2,
        )

    def test_grads_match_reference(self):
        x, w, b, a, s = _conv_data()
        cot = jnp.asarray(
            np.random.RandomState(1).randn(2, 5, 9, 7), jnp.float32
        )

        def loss(fn, x_, w_, b_, a_, s_):
            y = fn(x_, w_, b_, a_, s_)
            return jnp.sum(y * cot) + jnp.sum(y ** 2)

        g_k = jax.grad(
            lambda *p: loss(
                lambda *q: conv_block(
                    *q, stride=(1, 1), padding=(1, 1),
                    activation="leakyrelu",
                    interpret=pallas_interpret()),
                *p),
            argnums=(0, 1, 2, 3, 4))(x, w, b, a, s)
        g_r = jax.grad(
            lambda *p: loss(
                lambda *q: conv_block_reference(
                    *q, stride=(1, 1), padding=(1, 1),
                    activation="leakyrelu"),
                *p),
            argnums=(0, 1, 2, 3, 4))(x, w, b, a, s)
        rtol, atol = kernel_tols()
        for name, ka, ra in zip(("dx", "dw", "db", "dscale", "dshift"),
                                g_k, g_r):
            np.testing.assert_allclose(
                np.asarray(ka), np.asarray(ra), rtol=rtol, atol=atol,
                err_msg=name,
            )

    def test_size_gate(self):
        # typical training geometry fits the VMEM budget
        assert conv_block_ok((8, 3, 28, 28), (16, 3, 5, 5), (1, 1),
                             (0, 0), jnp.float32)
        # a whole padded 512x512x64 image per grid step does not
        assert not conv_block_ok((1, 64, 512, 512), (64, 64, 3, 3),
                                 (1, 1), (1, 1), jnp.float32)
        # kernel larger than the padded input: nothing to compute
        assert not conv_block_ok((1, 3, 4, 4), (8, 3, 7, 7), (1, 1),
                                 (0, 0), jnp.float32)

    def test_unsupported_activation_raises(self):
        x, w, b, a, s = _conv_data()
        with pytest.raises(ValueError, match="epilogue"):
            conv_block(x, w, b, a, s, stride=(1, 1), padding=(0, 0),
                       activation="softmax", interpret=True)


# stride/padding/odd-geometry sweep for the hand-written backward:
# asymmetric strides, stride > kernel, padding > kernel//2, prime-ish
# spatial dims (edge remainders), every epilogue family
_BWD_SWEEP = [
    ((2, 3, 9, 7), (5, 3, 3, 3), (1, 1), (1, 1), "relu"),
    ((2, 3, 10, 10), (4, 3, 5, 5), (2, 1), (2, 2), "leakyrelu"),
    ((1, 2, 8, 5), (3, 2, 3, 2), (2, 2), (0, 1), "tanh"),
    ((2, 4, 7, 7), (8, 4, 3, 3), (3, 3), (1, 1), "identity"),
    ((1, 1, 5, 6), (2, 1, 2, 3), (1, 2), (1, 0), "relu"),
]


class TestConvBlockBackward:
    """The hand-written Pallas backward (dL/dx via the dilated-
    gradient x flipped-weights forward kernel, dL/dw via the dedicated
    per-tap kernel) against ``jax.vjp`` through the XLA reference.

    Comparison uses a per-array magnitude-scaled tolerance
    (``atol + rtol * max|ref|``): the kernel accumulates taps in a
    fixed order, XLA schedules its conv reduction differently, so
    elements of an ill-conditioned sum legitimately differ by ~1 ulp
    of the LARGEST gradient in the array, not of each element."""

    @staticmethod
    def _assert_grads(g_k, g_r, rtol, atol):
        for name, ka, ra in zip(("dx", "dw", "db", "dscale", "dshift"),
                                g_k, g_r):
            ka = np.asarray(ka, np.float32)
            ra = np.asarray(ra, np.float32)
            tol = atol + rtol * max(1.0, float(np.abs(ra).max()))
            err = float(np.abs(ka - ra).max())
            assert err <= tol, (name, err, tol)

    @pytest.mark.parametrize(
        "x_shape,w_shape,stride,padding,activation", _BWD_SWEEP)
    def test_f32_grad_sweep(self, x_shape, w_shape, stride, padding,
                            activation):
        n, c, h, w_in = x_shape
        o = w_shape[0]
        rng = np.random.RandomState(3)
        x = jnp.asarray(rng.randn(*x_shape), jnp.float32)
        w = jnp.asarray(rng.randn(*w_shape) * 0.2, jnp.float32)
        b = jnp.asarray(rng.randn(o) * 0.1, jnp.float32)
        a = jnp.asarray(rng.rand(o) + 0.5, jnp.float32)
        s = jnp.asarray(rng.randn(o) * 0.1, jnp.float32)
        # layers route here only at unit stride (the chip's compiler
        # refuses the strided tap); called directly, the strided
        # branch still runs in interpret mode, so its numerics stay
        # under test
        assert conv_block_ok(x_shape, w_shape, stride, padding,
                             jnp.float32) == (tuple(stride) == (1, 1))

        def loss(fn, *p):
            y = fn(*p)
            return jnp.sum(y ** 2) + jnp.sum(y)

        g_k = jax.grad(
            lambda *p: loss(
                lambda *q: conv_block(
                    *q, stride=stride, padding=padding,
                    activation=activation,
                    interpret=pallas_interpret()), *p),
            argnums=(0, 1, 2, 3, 4))(x, w, b, a, s)
        g_r = jax.grad(
            lambda *p: loss(
                lambda *q: conv_block_reference(
                    *q, stride=stride, padding=padding,
                    activation=activation), *p),
            argnums=(0, 1, 2, 3, 4))(x, w, b, a, s)
        rtol, atol = kernel_tols()
        self._assert_grads(g_k, g_r, rtol, atol)

    @pytest.mark.parametrize(
        "x_shape,w_shape,stride,padding,activation", _BWD_SWEEP)
    def test_bf16_grad_sweep(self, x_shape, w_shape, stride, padding,
                             activation):
        """bf16 primals, f32 accumulators on both sides. The reference
        upcasts to f32 and rounds once at the end (jax.vjp through a
        mixed bf16/f32 conv_general_dilated is broken in this jaxlib —
        its transpose emits a dtype-mismatched conv), which is also
        exactly the kernel's accumulation contract."""
        o = w_shape[0]
        rng = np.random.RandomState(4)
        x = jnp.asarray(rng.randn(*x_shape), jnp.bfloat16)
        w = jnp.asarray(rng.randn(*w_shape) * 0.2, jnp.bfloat16)
        b = jnp.asarray(rng.randn(o) * 0.1, jnp.float32)
        a = jnp.asarray(rng.rand(o) + 0.5, jnp.float32)
        s = jnp.asarray(rng.randn(o) * 0.1, jnp.float32)

        def ref(x_, w_, b_, a_, s_):
            y = conv_block_reference(
                x_.astype(jnp.float32), w_.astype(jnp.float32),
                b_, a_, s_, stride=stride, padding=padding,
                activation=activation)
            return y.astype(jnp.bfloat16)

        def loss(fn, *p):
            y = fn(*p)
            return jnp.sum(y.astype(jnp.float32) ** 2)

        g_k = jax.grad(
            lambda *p: loss(
                lambda *q: conv_block(
                    *q, stride=stride, padding=padding,
                    activation=activation,
                    interpret=pallas_interpret()), *p),
            argnums=(0, 1, 2, 3, 4))(x, w, b, a, s)
        g_r = jax.grad(
            lambda *p: loss(ref, *p),
            argnums=(0, 1, 2, 3, 4))(x, w, b, a, s)
        # bf16 grads round to 8 mantissa bits: fixed bf16-eps band,
        # magnitude-scaled like the f32 sweep
        self._assert_grads(g_k, g_r, 2e-2, 8e-3)

    def test_relu_tie_at_zero_matches_reference(self):
        """The epilogue-grad table must reproduce lax.max's balanced
        0.5 subgradient at z == 0, or grads drift on exact-zero
        pre-activations (common with zero bias/shift)."""
        x = jnp.zeros((1, 1, 3, 3), jnp.float32)
        w = jnp.zeros((2, 1, 2, 2), jnp.float32)

        def k_loss(w_):
            return jnp.sum(conv_block(
                x, w_, stride=(1, 1), padding=(0, 0),
                activation="relu", interpret=pallas_interpret()))

        def r_loss(w_):
            return jnp.sum(conv_block_reference(
                x, w_, stride=(1, 1), padding=(0, 0),
                activation="relu"))

        gk = np.asarray(jax.grad(k_loss)(w))
        gr = np.asarray(jax.grad(r_loss)(w))
        np.testing.assert_array_equal(gk, gr)


class TestMatmulBlockKernel:
    @pytest.mark.parametrize("activation", sorted(SUPPORTED_EPILOGUES))
    def test_forward_matches_reference(self, activation):
        rng = np.random.RandomState(5)
        x = jnp.asarray(rng.randn(6, 10), jnp.float32)
        w = jnp.asarray(rng.randn(10, 12) * 0.2, jnp.float32)
        b = jnp.asarray(rng.randn(12) * 0.1, jnp.float32)
        out = matmul_block(x, w, b, activation=activation,
                           interpret=pallas_interpret())
        ref = matmul_block_reference(x, w, b, activation=activation)
        rtol, atol = kernel_tols()
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=rtol, atol=atol)

    def test_grads_match_reference(self):
        rng = np.random.RandomState(6)
        x = jnp.asarray(rng.randn(6, 10), jnp.float32)
        w = jnp.asarray(rng.randn(10, 12) * 0.2, jnp.float32)
        b = jnp.asarray(rng.randn(12) * 0.1, jnp.float32)

        g_k = jax.grad(
            lambda *p: jnp.sum(matmul_block(
                *p, activation="tanh",
                interpret=pallas_interpret()) ** 2),
            argnums=(0, 1, 2))(x, w, b)
        g_r = jax.grad(
            lambda *p: jnp.sum(matmul_block_reference(
                *p, activation="tanh") ** 2),
            argnums=(0, 1, 2))(x, w, b)
        rtol, atol = kernel_tols()
        for name, ka, ra in zip(("dx", "dw", "db"), g_k, g_r):
            np.testing.assert_allclose(
                np.asarray(ka), np.asarray(ra), rtol=rtol, atol=atol,
                err_msg=name,
            )

    @pytest.mark.parametrize("activation", ["identity", "relu"])
    def test_residual_forward_matches_reference(self, activation):
        """The widened epilogue: activation(x @ w + b + residual) as
        the same single kernel (pre-activation skip add)."""
        rng = np.random.RandomState(7)
        x = jnp.asarray(rng.randn(6, 10), jnp.float32)
        w = jnp.asarray(rng.randn(10, 12) * 0.2, jnp.float32)
        b = jnp.asarray(rng.randn(12) * 0.1, jnp.float32)
        r = jnp.asarray(rng.randn(6, 12) * 0.3, jnp.float32)
        out = matmul_block(x, w, b, r, activation=activation,
                           interpret=pallas_interpret())
        ref = matmul_block_reference(x, w, b, r,
                                     activation=activation)
        rtol, atol = kernel_tols()
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=rtol, atol=atol)

    def test_residual_grads_match_reference(self):
        rng = np.random.RandomState(8)
        x = jnp.asarray(rng.randn(6, 10), jnp.float32)
        w = jnp.asarray(rng.randn(10, 12) * 0.2, jnp.float32)
        b = jnp.asarray(rng.randn(12) * 0.1, jnp.float32)
        r = jnp.asarray(rng.randn(6, 12) * 0.3, jnp.float32)

        g_k = jax.grad(
            lambda *p: jnp.sum(matmul_block(
                *p, activation="tanh",
                interpret=pallas_interpret()) ** 2),
            argnums=(0, 1, 2, 3))(x, w, b, r)
        g_r = jax.grad(
            lambda *p: jnp.sum(matmul_block_reference(
                *p, activation="tanh") ** 2),
            argnums=(0, 1, 2, 3))(x, w, b, r)
        rtol, atol = kernel_tols()
        for name, ka, ra in zip(("dx", "dw", "db", "dresidual"),
                                g_k, g_r):
            np.testing.assert_allclose(
                np.asarray(ka), np.asarray(ra), rtol=rtol, atol=atol,
                err_msg=name,
            )

    def test_residual_free_path_unchanged(self):
        """No residual -> the original kernel variant (bit-identical
        to a pre-residual build): same output with and without the
        residual argument explicitly None."""
        rng = np.random.RandomState(9)
        x = jnp.asarray(rng.randn(6, 10), jnp.float32)
        w = jnp.asarray(rng.randn(10, 12) * 0.2, jnp.float32)
        b = jnp.asarray(rng.randn(12) * 0.1, jnp.float32)
        a = matmul_block(x, w, b, activation="relu",
                         interpret=pallas_interpret())
        c = matmul_block(x, w, b, None, activation="relu",
                         interpret=pallas_interpret())
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))

    def test_size_gate(self):
        assert matmul_block_ok(32, 64, 128, jnp.float32)
        # K too large for any (bm, bn) block pair under the budget
        assert not matmul_block_ok(8, 4_000_000, 8, jnp.float32)


# ---------------------------------------------------------------------------
# dispatch: env cache + layer routing + metrics
# ---------------------------------------------------------------------------


class TestDispatchEnvCache:
    def test_env_flip_needs_the_reset_hook(self, monkeypatch):
        """DL4J_TPU_PALLAS is read ONCE per process: flipping the env
        mid-process does nothing until ``reset_for_tests()`` re-arms
        the read (the regression this pins: the old per-call re-read
        made every dispatch an implicit getenv)."""
        monkeypatch.setenv("DL4J_TPU_PALLAS", "0")
        dispatch.reset_for_tests()
        assert not dispatch.use_pallas()
        monkeypatch.setenv("DL4J_TPU_PALLAS", "1")
        assert not dispatch.use_pallas()  # cached: flip alone inert
        dispatch.reset_for_tests()
        assert dispatch.use_pallas()  # hook re-reads -> path switches

    def test_flip_switches_the_layer_path(self, monkeypatch):
        """The cached flag actually routes: same layer apply records
        an XLA dispatch at =0 and a kernel dispatch after the flip +
        reset."""
        layer = ConvolutionLayer(n_in=3, n_out=4, kernel_size=(3, 3),
                                 padding=(1, 1), activation="relu")
        params = layer.init_params(jax.random.PRNGKey(0))
        x = jnp.asarray(
            np.random.RandomState(2).randn(2, 3, 8, 8), jnp.float32
        )
        mode = "interpret" if pallas_interpret() else "pallas"

        monkeypatch.setenv("DL4J_TPU_PALLAS", "0")
        dispatch.reset_for_tests()
        before = _dispatch_children()
        y_off, _ = layer.apply(params, x, {}, train=False)
        mid = _dispatch_children()
        assert mid.get(("conv_block", "xla"), 0) == \
            before.get(("conv_block", "xla"), 0) + 1

        monkeypatch.setenv("DL4J_TPU_PALLAS", "1")
        dispatch.reset_for_tests()
        y_on, _ = layer.apply(params, x, {}, train=False)
        after = _dispatch_children()
        assert after.get(("conv_block", mode), 0) == \
            mid.get(("conv_block", mode), 0) + 1
        rtol, atol = kernel_tols()
        np.testing.assert_allclose(np.asarray(y_on), np.asarray(y_off),
                                   rtol=rtol, atol=atol)

    def test_softmax_head_stays_on_xla(self, monkeypatch):
        """OutputLayer's softmax is not a supported epilogue — the
        dense kernel must refuse it (and meter the refusal)."""
        monkeypatch.setenv("DL4J_TPU_PALLAS", "1")
        dispatch.reset_for_tests()
        layer = OutputLayer(n_in=6, n_out=3)
        params = layer.init_params(jax.random.PRNGKey(1))
        x = jnp.asarray(
            np.random.RandomState(3).randn(4, 6), jnp.float32
        )
        before = _dispatch_children()
        layer.apply(params, x, {}, train=False)
        after = _dispatch_children()
        assert after.get(("matmul_block", "xla"), 0) == \
            before.get(("matmul_block", "xla"), 0) + 1
        mode = "interpret" if pallas_interpret() else "pallas"
        assert after.get(("matmul_block", mode), 0) == \
            before.get(("matmul_block", mode), 0)


# ---------------------------------------------------------------------------
# trajectory equivalence + transform composition
# ---------------------------------------------------------------------------


def _cnn_mln(seed=3):
    conf = (
        NeuralNetConfiguration.Builder().seed(seed).learning_rate(0.05)
        .list()
        .layer(ConvolutionLayer(n_out=4, kernel_size=(3, 3),
                                padding=(1, 1), activation="identity"))
        .layer(BatchNormalization(activation="relu"))
        .layer(ConvolutionLayer(n_out=6, kernel_size=(3, 3),
                                stride=(2, 2), activation="relu"))
        .layer(DenseLayer(n_out=16, activation="tanh"))
        .layer(OutputLayer(n_out=3))
        .set_input_type(InputType.convolutional(8, 8, 3))
        .build()
    )
    return MultiLayerNetwork(conf).init()


def _cnn_graph(seed=4):
    b = (NeuralNetConfiguration.Builder().seed(seed).learning_rate(0.05)
         .graph_builder().add_inputs("in"))
    b.add_layer("c0", ConvolutionLayer(n_out=4, kernel_size=(3, 3),
                                       padding=(1, 1),
                                       activation="identity"), "in")
    b.add_layer("bn", BatchNormalization(activation="relu"), "c0")
    b.add_layer("c1", ConvolutionLayer(n_out=6, kernel_size=(3, 3),
                                       stride=(2, 2),
                                       activation="relu"), "bn")
    b.add_layer("d0", DenseLayer(n_out=16, activation="tanh"), "c1")
    b.add_layer("out", OutputLayer(n_out=3), "d0")
    b.set_outputs("out")
    b.set_input_types(InputType.convolutional(8, 8, 3))
    return ComputationGraph(b.build()).init()


def _image_batches(n=3, batch=4, seed=0):
    r = np.random.RandomState(seed)
    return [
        DataSet(
            features=r.randn(batch, 3, 8, 8).astype(np.float32),
            labels=np.eye(3, dtype=np.float32)[r.randint(0, 3, batch)],
        )
        for _ in range(n)
    ]


def _assert_close_params(a, b, rtol, atol):
    for ln in a.params:
        for pn in a.params[ln]:
            np.testing.assert_allclose(
                np.asarray(a.params[ln][pn]),
                np.asarray(b.params[ln][pn]),
                rtol=rtol, atol=atol, err_msg=f"{ln}/{pn}",
            )


@pytest.mark.parametrize("build", [_cnn_mln, _cnn_graph],
                         ids=["multilayer", "graph"])
def test_training_trajectory_kernel_on_vs_off(build, monkeypatch):
    """Both engines: N fit steps + an eval forward agree between
    DL4J_TPU_PALLAS=0 and =1 (interpret on CPU). Observed drift on the
    CPU profile is ~1e-7 (f32 accumulate both sides); asserted at
    kernel_tols."""
    data = _image_batches()

    def run(flag):
        monkeypatch.setenv("DL4J_TPU_PALLAS", flag)
        dispatch.reset_for_tests()
        net = build()
        for ds in data:
            net.fit(ds)
        out = net.output(data[0].features)
        out = out[0] if isinstance(out, (list, tuple)) else out
        return net, np.asarray(out)

    net_off, y_off = run("0")
    net_on, y_on = run("1")
    rtol, atol = kernel_tols()
    np.testing.assert_allclose(y_on, y_off, rtol=rtol, atol=atol)
    _assert_close_params(net_on, net_off, rtol, atol)


def test_kernels_compose_with_scan_remat_accum(monkeypatch):
    """scan-over-layers + remat + in-jit grad accumulation with the
    dense kernel routed: same trajectory as the kernels-off build, and
    the AOT fingerprint carries every active transform."""
    r = np.random.RandomState(1)
    data = [
        DataSet(features=r.randn(8, 12).astype(np.float32),
                labels=np.eye(3, dtype=np.float32)[r.randint(0, 3, 8)])
        for _ in range(4)
    ]

    def run(flag):
        monkeypatch.setenv("DL4J_TPU_PALLAS", flag)
        dispatch.reset_for_tests()
        b = (NeuralNetConfiguration.Builder().seed(11)
             .learning_rate(0.1).list())
        for _ in range(3):
            b.layer(DenseLayer(n_in=12, n_out=12, activation="relu"))
        b.layer(OutputLayer(n_in=12, n_out=3))
        net = MultiLayerNetwork(b.build()).init()
        net.set_transforms(scan_layers=True, remat="full")
        net.fit(data, grad_accum=2)
        # the suffix reflects the LIVE dispatch state — snapshot it
        # under the same flag the net trained with
        return net, core.transform_kind_suffix(net)

    net_off, suffix_off = run("0")
    net_on, suffix_on = run("1")
    assert "scan" in suffix_on and "remat:full" in suffix_on
    # default DL4J_TPU_TUNE=cached means tuning is active alongside
    # the kernels: the suffix carries both parts
    assert suffix_on.endswith("+convblock+tuned")
    assert "convblock" not in suffix_off
    assert "tuned" not in suffix_off
    rtol, atol = kernel_tols()
    _assert_close_params(net_on, net_off, rtol, atol)


def test_gspmd_step_on_several_devices_takes_xla(monkeypatch):
    """The chip's compiler cannot partition a Mosaic kernel
    (``Mosaic kernels cannot be automatically partitioned``), so a
    GSPMD step over several devices — here ZeRO-sharded, 8 virtual
    devices — routes every kernel call site to XLA and says so, even
    with dispatch forced on; the same trainer on a one-device mesh
    still takes the kernel. Trained params agree on vs off."""
    require_devices(8)
    from deeplearning4j_tpu.datasets.api import ListDataSetIterator
    from deeplearning4j_tpu.parallel import DistributedTrainer
    from deeplearning4j_tpu.parallel.mesh import build_mesh

    r = np.random.RandomState(2)
    data = [
        DataSet(features=r.randn(8, 12).astype(np.float32),
                labels=np.eye(3, dtype=np.float32)[r.randint(0, 3, 8)])
        for _ in range(3)
    ]
    kernel_mode = "interpret" if pallas_interpret() else "pallas"

    def run(flag, n_devices, zero):
        monkeypatch.setenv("DL4J_TPU_PALLAS", flag)
        dispatch.reset_for_tests()
        b = (NeuralNetConfiguration.Builder().seed(13)
             .learning_rate(0.1).updater("ADAM").list())
        b.layer(DenseLayer(n_in=12, n_out=16, activation="relu"))
        b.layer(OutputLayer(n_in=16, n_out=3))
        net = MultiLayerNetwork(b.build()).init()
        before = _dispatch_children()
        DistributedTrainer(
            net, mesh=build_mesh(devices=jax.devices()[:n_devices]),
            zero=zero, batch_stats="sync",
        ).fit(ListDataSetIterator(data), epochs=1)
        after = _dispatch_children()
        routed = {
            mode: after.get(("matmul_block", mode), 0)
            - before.get(("matmul_block", mode), 0)
            for mode in ("xla", kernel_mode)
        }
        return net, routed

    net_off, _ = run("0", 8, True)
    net_on, routed = run("1", 8, True)
    assert routed[kernel_mode] == 0 and routed["xla"] > 0, routed
    rtol, atol = kernel_tols()
    _assert_close_params(net_on, net_off, rtol, atol)
    _, routed_one = run("1", 1, False)
    assert routed_one[kernel_mode] > 0, routed_one


# ---------------------------------------------------------------------------
# eval-mode conv->BN peephole
# ---------------------------------------------------------------------------


def test_eval_conv_bn_fuses_and_matches(monkeypatch):
    """Inference forward with an identity-activation conv feeding BN:
    the peephole folds BN's running stats into the kernel epilogue
    (metered as ``conv_bn_block``) and matches the kernels-off
    forward; training-mode forwards never take the peephole (batch
    stats must still be collected)."""
    data = _image_batches(n=2)
    monkeypatch.setenv("DL4J_TPU_PALLAS", "0")
    dispatch.reset_for_tests()
    net = _cnn_mln()
    for ds in data:
        net.fit(ds)  # populate BN running stats
    y_off = np.asarray(net.output(data[0].features))

    monkeypatch.setenv("DL4J_TPU_PALLAS", "1")
    dispatch.reset_for_tests()
    # fresh instance: a net's jitted forward keeps the path it was
    # traced with, so dispatch flips take effect on new traces (the
    # supported pattern — one process-level flag, set before building)
    net_on = _cnn_mln()
    net_on.params, net_on.state = net.params, net.state
    mode = "interpret" if pallas_interpret() else "pallas"
    before = _dispatch_children()
    y_on = np.asarray(net_on.output(data[0].features))
    after = _dispatch_children()
    assert after.get(("conv_bn_block", mode), 0) == \
        before.get(("conv_bn_block", mode), 0) + 1
    rtol, atol = kernel_tols()
    np.testing.assert_allclose(y_on, y_off, rtol=rtol, atol=atol)


# ---------------------------------------------------------------------------
# AOT fingerprinting
# ---------------------------------------------------------------------------


def test_aot_artifact_refused_across_kernel_flip(monkeypatch):
    """A step exported with the kernels OFF must not install once
    dispatch turns them ON (+convblock changes the artifact kind) —
    and must still install into a matching kernels-off model."""
    ds = _image_batches(n=1)[0]
    monkeypatch.setenv("DL4J_TPU_PALLAS", "0")
    dispatch.reset_for_tests()
    blob = _cnn_mln().aot_export_step(ds)
    twin = _cnn_mln()
    assert twin.aot_install_step(blob) is True

    monkeypatch.setenv("DL4J_TPU_PALLAS", "1")
    dispatch.reset_for_tests()
    flipped = _cnn_mln()
    assert flipped.aot_install_step(blob) is False


# ---------------------------------------------------------------------------
# chaos storm: seeded geometry fuzz
# ---------------------------------------------------------------------------


@pytest.mark.chaos
def test_chaos_conv_geometry_fuzz():
    """Seeded random conv geometries (channels, kernel, stride,
    padding, activation): every geometry the gate admits must match
    the reference; gate refusals must be for a stated reason (budget,
    degenerate output, or a stride the chip's compiler refuses — three
    draws in four), never a wrong answer."""
    rng = np.random.RandomState(CHAOS_SEED)
    rtol, atol = kernel_tols()
    admitted = 0
    for _ in range(48):
        n = int(rng.randint(1, 4))
        c = int(rng.randint(1, 6))
        h = int(rng.randint(4, 12))
        w = int(rng.randint(4, 12))
        o = int(rng.randint(1, 8))
        kh = int(rng.randint(1, min(4, h) + 1))
        kw = int(rng.randint(1, min(4, w) + 1))
        stride = (int(rng.randint(1, 3)), int(rng.randint(1, 3)))
        padding = (int(rng.randint(0, 2)), int(rng.randint(0, 2)))
        activation = sorted(SUPPORTED_EPILOGUES)[rng.randint(0, 4)]
        x_shape, w_shape = (n, c, h, w), (o, c, kh, kw)
        if not conv_block_ok(x_shape, w_shape, stride, padding,
                             jnp.float32):
            continue
        admitted += 1
        r = np.random.RandomState(CHAOS_SEED + admitted)
        x = jnp.asarray(r.randn(*x_shape), jnp.float32)
        wgt = jnp.asarray(r.randn(*w_shape) * 0.2, jnp.float32)
        bias = jnp.asarray(r.randn(o) * 0.1, jnp.float32)
        scale = jnp.asarray(r.rand(o) + 0.5, jnp.float32)
        shift = jnp.asarray(r.randn(o) * 0.1, jnp.float32)
        out = conv_block(x, wgt, bias, scale, shift, stride=stride,
                         padding=padding, activation=activation,
                         interpret=pallas_interpret())
        ref = conv_block_reference(x, wgt, bias, scale, shift,
                                   stride=stride, padding=padding,
                                   activation=activation)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=rtol, atol=atol,
            err_msg=f"geometry x={x_shape} w={w_shape} s={stride} "
                    f"p={padding} act={activation}",
        )
    assert admitted >= 4, "fuzz degenerated: almost no geometry admitted"
