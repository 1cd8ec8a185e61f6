"""Loss-function x output-activation gradient matrix (reference:
``gradientcheck/LossFunctionGradientCheck.java`` — every ILossFunction
checked against central differences under the activations it is used
with, labels generated per-loss).

Covers every loss in the registry. Non-smooth losses (L1/MAE/HINGE
family) are checked at random points where ties/kinks have measure
zero; the seeded data avoids the kink exactly like the reference's
fixed-seed Nd4j.rand does.
"""

import numpy as np
import pytest

from deeplearning4j_tpu.nn import losses
from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
from deeplearning4j_tpu.nn.gradient_check import check_gradients
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

N, D, K = 6, 4, 3


def _onehot(rng):
    y = np.zeros((N, K))
    y[np.arange(N), rng.randint(0, K, N)] = 1.0
    return y


def _binary(rng):
    return (rng.rand(N, K) > 0.5).astype(np.float64)


def _real(rng):
    return rng.randn(N, K)


def _positive(rng):
    return rng.rand(N, K) + 0.5


def _distribution(rng):
    p = rng.rand(N, K) + 0.1
    return p / p.sum(axis=1, keepdims=True)


def _class_ids(rng):
    return rng.randint(0, K, (N, 1)).astype(np.float64)


def _pm_one(rng):
    return np.sign(rng.randn(N, K)) + (rng.randn(N, K) == 0)


# (loss, output activation, label generator) — mirrors the pairing
# table in LossFunctionGradientCheck.java
MATRIX = [
    ("MSE", "identity", _real),
    ("MSE", "tanh", _real),
    ("L2", "identity", _real),
    ("SQUARED_LOSS", "sigmoid", _binary),
    ("L1", "identity", _real),
    ("L1", "tanh", _real),
    ("MEAN_ABSOLUTE_ERROR", "identity", _real),
    ("MEAN_ABSOLUTE_PERCENTAGE_ERROR", "identity", _positive),
    ("MEAN_SQUARED_LOGARITHMIC_ERROR", "sigmoid", _positive),
    ("XENT", "sigmoid", _binary),
    ("RECONSTRUCTION_CROSSENTROPY", "sigmoid", _binary),
    ("MCXENT", "softmax", _onehot),
    ("MCXENT", "softmax", _distribution),
    ("NEGATIVELOGLIKELIHOOD", "softmax", _onehot),
    ("SPARSE_MCXENT", "softmax", _class_ids),
    ("KL_DIVERGENCE", "softmax", _distribution),
    ("COSINE_PROXIMITY", "identity", _real),
    ("COSINE_PROXIMITY", "tanh", _real),
    ("HINGE", "identity", _pm_one),
    ("SQUARED_HINGE", "identity", _pm_one),
    ("SQUARED_HINGE", "tanh", _pm_one),
    ("POISSON", "softplus", _positive),
    ("POISSON", "exp", _positive),
]


def test_matrix_covers_every_registered_loss():
    covered = {loss for loss, _, _ in MATRIX}
    assert covered == set(losses.names())


@pytest.mark.parametrize(
    "loss,out_act,labels_fn", MATRIX,
    ids=[f"{l}-{a}-{g.__name__}" for l, a, g in MATRIX],
)
def test_loss_activation_gradient(rng, loss, out_act, labels_fn):
    conf = (
        NeuralNetConfiguration.Builder()
        .seed(12345)
        .list()
        .layer(DenseLayer(n_in=D, n_out=5, activation="tanh"))
        .layer(OutputLayer(n_out=K, loss=loss, activation=out_act))
        .build()
    )
    net = MultiLayerNetwork(conf).init()
    x = rng.randn(N, D)
    y = labels_fn(rng)
    assert check_gradients(net, x, y, print_results=True), (
        f"{loss} x {out_act}"
    )


@pytest.mark.parametrize("loss,out_act,labels_fn", [
    ("MCXENT", "softmax", _onehot),
    ("MSE", "identity", _real),
    ("XENT", "sigmoid", _binary),
])
def test_loss_gradient_with_weighted_hidden_activations(
    rng, loss, out_act, labels_fn
):
    """Second sweep with a different hidden activation + regularization
    (reference runs each loss under multiple net shapes)."""
    conf = (
        NeuralNetConfiguration.Builder()
        .seed(999)
        .list()
        .layer(DenseLayer(n_in=D, n_out=6, activation="elu",
                          l2=0.01))
        .layer(DenseLayer(n_out=5, activation="softsign", l1=0.005))
        .layer(OutputLayer(n_out=K, loss=loss, activation=out_act))
        .build()
    )
    net = MultiLayerNetwork(conf).init()
    x = rng.randn(N, D)
    y = labels_fn(rng)
    assert check_gradients(net, x, y, print_results=True)
