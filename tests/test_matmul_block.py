"""The fused dense epilogue kernel (``ops/matmul_block.py``), the
dispatch switch (``ops/dispatch.py``) and their layer wiring.

Contract under test (the backend-vs-backend strategy of SURVEY.md §4,
as for the LSTM/flash-attention kernels): the Pallas kernel is a pure
drop-in for the XLA path — forward and gradients match the reference
at kernel tolerance, ``DL4J_TPU_PALLAS`` flips routing without
changing WHAT IS TRAINED, and every whole-net transform (scan-over-
layers, remat, grad accumulation, ZeRO) composes with the kernel on.
The small CNNs below carry convolutions on purpose: a convolution is
XLA's on every platform, and only their dense layers route.

Tolerances (documented): on the CPU profile the kernel runs in
interpret mode with f32 accumulators against an f32 reference, so
trajectories agree to ~1e-6 and assertions use ``kernel_tols()``
(2e-4/2e-5). On TPU both the kernel and the XLA reference round MXU
inputs to bf16 independently, so ``kernel_tols`` widens to
2e-2/8e-3 — numerical agreement, not bit equality, is the
cross-backend contract (bit equality per backend is still asserted
where both sides run the same program).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import (
    dispatch_counts,
    kernel_tols,
    pallas_interpret,
    require_devices,
)
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
from deeplearning4j_tpu.ops import (
    SUPPORTED_EPILOGUES,
    dispatch,
    matmul_block,
    matmul_block_ok,
    matmul_block_reference,
)

# ---------------------------------------------------------------------------
# kernel vs reference (single op)
# ---------------------------------------------------------------------------


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
        layer = DenseLayer(n_in=10, n_out=12, activation="relu")
        params = layer.init_params(jax.random.PRNGKey(0))
        x = jnp.asarray(
            np.random.RandomState(2).randn(6, 10), jnp.float32
        )
        mode = "interpret" if pallas_interpret() else "pallas"

        monkeypatch.setenv("DL4J_TPU_PALLAS", "0")
        dispatch.reset_for_tests()
        before = dispatch_counts()
        y_off, _ = layer.apply(params, x, {}, train=False)
        mid = dispatch_counts()
        assert mid.get(("matmul_block", "xla"), 0) == \
            before.get(("matmul_block", "xla"), 0) + 1

        monkeypatch.setenv("DL4J_TPU_PALLAS", "1")
        dispatch.reset_for_tests()
        y_on, _ = layer.apply(params, x, {}, train=False)
        after = dispatch_counts()
        assert after.get(("matmul_block", mode), 0) == \
            mid.get(("matmul_block", mode), 0) + 1
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
        before = dispatch_counts()
        layer.apply(params, x, {}, train=False)
        after = dispatch_counts()
        assert after.get(("matmul_block", "xla"), 0) == \
            before.get(("matmul_block", "xla"), 0) + 1
        mode = "interpret" if pallas_interpret() else "pallas"
        assert after.get(("matmul_block", mode), 0) == \
            before.get(("matmul_block", mode), 0)


@pytest.mark.parametrize("value,forced", [
    ("1", True), ("true", True), ("ON", True), ("auto", False),
    ("0", False), (None, False),
])
def test_pallas_forced_reads_the_one_variable(monkeypatch, value, forced):
    if value is None:
        monkeypatch.delenv("DL4J_TPU_PALLAS", raising=False)
    else:
        monkeypatch.setenv("DL4J_TPU_PALLAS", value)
    dispatch.reset_for_tests()
    assert dispatch.pallas_forced() == forced


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
    assert suffix_on.endswith("+kernels+tuned")
    assert "kernels" not in suffix_off
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
        before = dispatch_counts()
        DistributedTrainer(
            net, mesh=build_mesh(devices=jax.devices()[:n_devices]),
            zero=zero, batch_stats="sync",
        ).fit(ListDataSetIterator(data), epochs=1)
        after = dispatch_counts()
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
# AOT fingerprinting
# ---------------------------------------------------------------------------


def test_aot_artifact_refused_across_kernel_flip(monkeypatch):
    """A step exported with the kernels OFF must not install once
    dispatch turns them ON (+kernels changes the artifact kind) —
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
