"""Tests for the absolute-performance accounting (util.flops) and the
device-derived HBM cache budget (util.device)."""

import numpy as np
import pytest

from deeplearning4j_tpu.datasets.api import DataSet
from deeplearning4j_tpu.nn.conf import InputType, NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.util.device import device_cache_budget_bytes
from deeplearning4j_tpu.util.flops import (
    device_peak_flops,
    train_step_cost,
)


def _mlp(n_in=32, hidden=64, n_out=10):
    return (
        NeuralNetConfiguration.Builder().seed(1).learning_rate(0.1)
        .updater("SGD").list()
        .layer(DenseLayer(n_out=hidden, activation="relu"))
        .layer(OutputLayer(n_out=n_out, loss="MCXENT"))
        .set_input_type(InputType.feed_forward(n_in))
        .build()
    )


def test_device_cache_budget_positive_and_cached():
    b = device_cache_budget_bytes()
    assert b >= 256 << 20
    assert device_cache_budget_bytes() == b  # per-process cache
    # engines pick the budget up at construction
    net = MultiLayerNetwork(_mlp())
    assert net.device_cache_bytes == b


def test_device_peak_flops_shape():
    peak, kind = device_peak_flops()
    assert isinstance(kind, str) and kind
    # CPU profile: no roofline; TPU profile: a positive peak
    import jax

    if jax.devices()[0].platform == "tpu":
        assert peak and peak > 1e12
    else:
        assert peak is None


class _FakeDevice:
    """Stands in for a chip this sandbox does not have."""

    def __init__(self, platform, kind, stats=None):
        self.platform = platform
        self.device_kind = kind
        self._stats = stats

    def memory_stats(self):
        return self._stats


@pytest.mark.parametrize("lookup", ["flops", "bytes"])
def test_unknown_tpu_kind_raises_known_kind_answers(lookup):
    """No fallback that hides the device: a TPU missing from a peak
    table is an error, not ``None`` and not an assumed chip."""
    from deeplearning4j_tpu.observability import profiler

    fn = (device_peak_flops if lookup == "flops"
          else profiler.peak_bytes_per_sec)
    value, kind = fn(_FakeDevice("tpu", "TPU v5 lite"))
    assert kind == "TPU v5 lite"
    assert value == (197e12 if lookup == "flops" else 819e9)
    with pytest.raises(ValueError, match="TPU v99"):
        fn(_FakeDevice("tpu", "TPU v99"))
    assert fn(_FakeDevice("cpu", "cpu"))[0] is None


def test_device_cache_budget_reads_tpu_limit_or_raises():
    limit = 16 << 30
    assert device_cache_budget_bytes(
        _FakeDevice("tpu", "TPU v5 lite", {"bytes_limit": limit})
    ) == limit // 4
    with pytest.raises(RuntimeError, match="bytes_limit"):
        device_cache_budget_bytes(_FakeDevice("tpu", "TPU v5 lite"))
    # the CPU backend reports nothing: host bound, not a guessed chip
    assert device_cache_budget_bytes(_FakeDevice("cpu", "cpu")) == 4 << 30


def test_train_step_cost_counts_dominant_matmuls():
    batch, n_in, hidden, n_out = 64, 32, 64, 10
    net = MultiLayerNetwork(_mlp(n_in, hidden, n_out)).init()
    rng = np.random.RandomState(0)
    ds = DataSet(
        features=rng.rand(batch, n_in).astype(np.float32),
        labels=np.eye(n_out, dtype=np.float32)[
            rng.randint(0, n_out, batch)
        ],
    )
    cost = train_step_cost(net, ds)
    assert cost["batch"] == batch
    # fwd matmuls: 2*b*(n_in*h + h*out); fwd+bwd ~ 3x that. XLA's
    # count includes elementwise/updater ops, so bound loosely: at
    # least the forward matmuls, at most 10x the analytic fwd+bwd.
    fwd = 2 * batch * (n_in * hidden + hidden * n_out)
    assert cost["flops"] >= fwd
    assert cost["flops"] <= 10 * 3 * fwd
    assert cost["flops_per_example"] * batch == cost["flops"]
    # the model still trains after costing (lower() must not corrupt
    # the donated-buffer path)
    net.fit(ds)
    assert np.isfinite(float(net.score_value))


def test_train_step_cost_graph_engine():
    from deeplearning4j_tpu.nn.graph import ComputationGraph

    b = (
        NeuralNetConfiguration.Builder().seed(1).learning_rate(0.1)
        .updater("SGD").graph_builder().add_inputs("in")
    )
    b.add_layer("d", DenseLayer(n_out=16, activation="relu"), "in")
    b.add_layer("out", OutputLayer(n_out=4, loss="MCXENT"), "d")
    b.set_outputs("out")
    b.set_input_types(InputType.feed_forward(8))
    g = ComputationGraph(b.build()).init()
    rng = np.random.RandomState(0)
    ds = DataSet(
        features=rng.rand(32, 8).astype(np.float32),
        labels=np.eye(4, dtype=np.float32)[rng.randint(0, 4, 32)],
    )
    cost = train_step_cost(g, ds)
    assert cost["batch"] == 32
    assert cost["flops"] > 0
    g.fit(ds)
    assert np.isfinite(float(g.score_value))
