"""Layer SPI and registry.

The reference splits each layer into a config bean
(``nn/conf/layers/*.java``), a ``ParamInitializer``
(``nn/params/*.java``) and a runtime impl (``nn/layers/**``) with
hand-written ``activate``/``backpropGradient`` pairs. In a functional
JAX design those collapse into one class per layer: a frozen dataclass
that is simultaneously the JSON-serializable config and the pure
``init_params``/``apply`` implementation. Backprop is ``jax.grad``
through ``apply`` — there is no second code path to keep consistent
(the reference's gradient checks validated exactly that consistency;
ours validate the whole jitted composition instead).

Contract:
- ``init_params(key, dtype) -> {name: array}`` named like the
  reference's param keys ("W", "b", "gamma", ...): checkpoints stay
  humanly mappable to the reference's flat-view layout.
- ``apply(params, x, state, *, train, rng) -> (y, state)`` — ``state``
  carries non-trainable buffers (batch-norm running stats); stateless
  layers pass {} through.
- ``output_type(input)`` / ``with_input_type(input)`` implement the
  reference's InputType shape inference (``setNIn``).
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Dict, Type

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn import activations
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.updaters import UpdaterSettings
from deeplearning4j_tpu.nn.weights import Distribution, init_weights

# JSON subtype registry (reference: Jackson subtype scan,
# ``NeuralNetConfiguration.java:328-462``; here an explicit registry —
# custom layers call ``register_layer`` instead of being discovered by
# classpath scan).
LAYER_REGISTRY: Dict[str, Type["LayerSpec"]] = {}


def register_layer(cls):
    LAYER_REGISTRY[cls.__name__] = cls
    return cls


def layer_to_json(layer: "LayerSpec") -> dict:
    d = {"@class": type(layer).__name__}
    for f in dataclasses.fields(layer):
        v = getattr(layer, f.name)
        if isinstance(v, Distribution):
            v = {"@dist": True, **v.to_json()}
        elif isinstance(v, InputType):
            v = {"@input_type": True, **v.to_json()}
        elif isinstance(v, LayerSpec):
            v = layer_to_json(v)
        elif hasattr(v, "to_json") and hasattr(v, "neg_log_prob"):
            v = v.to_json()  # ReconstructionDistribution (tagged @dist_class)
        elif isinstance(v, tuple):
            v = list(v)
        d[f.name] = v
    return d


def layer_from_json(d: dict) -> "LayerSpec":
    d = dict(d)
    name = d.pop("@class")
    try:
        cls = LAYER_REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"Unknown layer type '{name}' — register custom layers with "
            f"@register_layer before deserializing"
        ) from None
    kwargs = {}
    field_types = {f.name: f for f in dataclasses.fields(cls)}
    for k, v in d.items():
        if k not in field_types:
            continue  # forward compat: ignore unknown fields
        if isinstance(v, dict) and v.get("@dist"):
            v = Distribution.from_json({
                kk: vv for kk, vv in v.items() if kk != "@dist"
            })
        elif isinstance(v, dict) and v.get("@input_type"):
            v = InputType.from_json({
                kk: vv for kk, vv in v.items() if kk != "@input_type"
            })
        elif isinstance(v, dict) and "@dist_class" in v:
            from deeplearning4j_tpu.nn.layers.variational import (
                ReconstructionDistribution,
            )

            v = ReconstructionDistribution.from_json(v)
        elif isinstance(v, dict) and "@class" in v:
            v = layer_from_json(v)
        elif isinstance(v, list):
            v = tuple(
                layer_from_json(x) if isinstance(x, dict) and "@class" in x else x
                for x in v
            )
        kwargs[k] = v
    return cls(**kwargs)


@dataclass(frozen=True)
class LayerSpec:
    """Base config+impl for all layers (reference
    ``nn/conf/layers/Layer.java`` bean fields)."""

    name: str = ""
    activation: str = "sigmoid"
    weight_init: str = "XAVIER"
    dist: Distribution | None = None
    bias_init: float = 0.0
    dropout: float = 0.0
    # weight-level DropConnect (reference NeuralNetConfiguration
    # ``useDropConnect``, NeuralNetConfiguration.java:96,509): when
    # True the ``dropout`` rate masks WEIGHTS in pre-output instead of
    # masking the layer input (BaseLayer.java:365,480)
    drop_connect: bool = False
    # optimizer settings (per-layer overrides; reference clones the
    # global NeuralNetConfiguration per layer)
    updater: str = "SGD"
    learning_rate: float = 0.1
    bias_learning_rate: float | None = None
    momentum: float = 0.9
    adam_mean_decay: float = 0.9
    adam_var_decay: float = 0.999
    rho: float = 0.95
    rms_decay: float = 0.95
    epsilon: float = 1e-8
    l1: float = 0.0
    l2: float = 0.0
    gradient_normalization: str = "None"
    gradient_normalization_threshold: float = 1.0
    lr_policy: str = "None"
    lr_policy_decay_rate: float = 0.0
    lr_policy_steps: float = 1.0
    lr_policy_power: float = 1.0
    lr_schedule: dict | None = None

    # -- shape inference ---------------------------------------------------

    def with_input_type(self, input_type: InputType) -> "LayerSpec":
        """Return a copy with nIn etc. inferred (reference
        ``Layer.setNIn``); default: unchanged."""
        return self

    def output_type(self, input_type: InputType) -> InputType:
        return input_type

    # -- params / state ----------------------------------------------------

    def init_params(self, key: jax.Array, dtype=jnp.float32) -> dict:
        return {}

    def init_state(self, dtype=jnp.float32) -> dict:
        return {}

    def regularizable_params(self) -> tuple:
        return ("W",)

    # -- forward -----------------------------------------------------------

    def apply(self, params, x, state, *, train: bool = False, rng=None,
              mask=None):
        """``mask``: optional [batch, time] features mask, consumed by
        recurrent layers; others ignore it."""
        raise NotImplementedError

    def is_recurrent(self) -> bool:
        """True for layers with streaming/TBPTT carry state (reference
        ``RecurrentLayer`` interface)."""
        return False

    def can_stream(self) -> bool:
        """False for layers that need the whole sequence (bidirectional
        RNNs) and therefore cannot be used with rnn_time_step."""
        return True

    def uses_batch_statistics(self) -> bool:
        """True for layers whose TRAINING math couples examples across
        the batch (BatchNormalization): under data parallelism these
        decide sync-vs-local batch stats (see
        ``parallel.trainer.DistributedTrainer``)."""
        return False

    def streams_state(self) -> bool:
        """True for layers that carry state across ``rnn_time_step``
        calls: recurrent layers (h/c) and attention layers (KV cache).
        Distinct from ``is_recurrent`` — attention layers stream at
        inference but train with whole-sequence scan fusion."""
        return self.is_recurrent()

    def stream_state_keys(self) -> tuple:
        """State-dict keys ``rnn_time_step`` carries across calls."""
        return ("h", "c")

    def stream_capacity(self):
        """Max total timesteps this layer can stream (None =
        unbounded; recurrent carry is O(1)). KV caches are finite."""
        return None

    # -- helpers -----------------------------------------------------------

    def activate_fn(self):
        return activations.get(self.activation)

    def supports_drop_connect(self) -> bool:
        """True for layers whose ``apply`` routes weights through
        :meth:`maybe_drop_connect` (dense/conv/LSTM/pretrain families,
        mirroring the reference's BaseLayer/ConvolutionLayer/
        LSTMHelpers DropConnect sites). Layers without weight-level
        masking keep their INPUT dropout even when the global
        ``drop_connect`` flag is set — otherwise the flag would
        silently strip their only regularization."""
        return False

    def maybe_dropout(self, x, *, train: bool, rng):
        """Inverted dropout on the layer *input* (reference BaseLayer
        applies dropout to input when training, ``conf.dropOut``).
        Suppressed when ``drop_connect`` is set AND this layer
        implements weight masking — the reference routes the rate to
        the weights instead (BaseLayer.java:480 checks
        ``!conf.isUseDropConnect()``)."""
        if (not train or self.dropout <= 0.0 or rng is None
                or (self.drop_connect and self.supports_drop_connect())):
            return x
        keep = 1.0 - self.dropout
        mask = jax.random.bernoulli(rng, keep, x.shape)
        return jnp.where(mask, x / keep, 0.0)

    # distinct stream from input dropout so a hypothetical layer using
    # both would not correlate masks
    _DROP_CONNECT_SALT = 0x7C

    def maybe_drop_connect(self, params, *, train: bool, rng,
                           keys=("W",)):
        """DropConnect: return ``params`` with the weight tensors in
        ``keys`` masked at rate ``dropout`` (reference
        ``Dropout.applyDropConnect``, applied by BaseLayer.java:365,
        ConvolutionLayer.java:223 and LSTMHelpers.java:93 to the
        input-weight matrices). Inverted scaling (W/keep) keeps
        pre-activation expectations unchanged, matching this
        framework's input-dropout convention. Deterministic in ``rng``
        so the engine's separate pre-output call sees the same mask as
        ``apply``."""
        if (not train or not self.drop_connect or self.dropout <= 0.0
                or rng is None or not self.supports_drop_connect()):
            return params
        keep = 1.0 - self.dropout
        out = dict(params)
        for i, k in enumerate(keys):
            if k not in out:
                continue
            w = out[k]
            m = jax.random.bernoulli(
                jax.random.fold_in(rng, self._DROP_CONNECT_SALT + i),
                keep, w.shape,
            )
            out[k] = jnp.where(m, w / keep, 0.0)
        return out

    def supports_layer_scan(self) -> bool:
        """True when this layer may join a scan-over-layers run
        (``nn/core.py``): its per-step program must be self-contained —
        no recurrent/TBPTT carry, no loss head, no pretrain phase, no
        cross-example batch statistics. Layers with non-empty
        ``init_state`` are additionally excluded at detection time
        (their state would have to thread through the scan carry)."""
        return not (
            self.is_recurrent()
            or self.has_loss()
            or self.is_pretrainable()
            or self.uses_batch_statistics()
        )

    def takes_indices(self) -> bool:
        """True for a layer whose input is integer ids (an embedding
        look-up): as a network's first layer its input is left out of
        the mixed-precision cast, which would merge neighbouring ids."""
        return False

    def tied_params(self) -> tuple:
        """``((local name, layer index, param name), ...)``: arrays of
        other layers this one reads under local names (weight tying).
        The sequential engine lays them into the params ``apply`` and
        ``score_input`` get."""
        return ()

    def scores_input(self) -> bool:
        """True for a loss layer that computes its score from its own
        input and the labels (``score_input``), so that the engine
        never holds its whole pre-output: a language-model head whose
        logits exist a block of rows at a time."""
        return False

    def updater_settings(self) -> UpdaterSettings:
        return UpdaterSettings(
            updater=self.updater,
            learning_rate=self.learning_rate,
            bias_learning_rate=self.bias_learning_rate,
            momentum=self.momentum,
            adam_mean_decay=self.adam_mean_decay,
            adam_var_decay=self.adam_var_decay,
            rho=self.rho,
            rms_decay=self.rms_decay,
            epsilon=self.epsilon,
            l1=self.l1,
            l2=self.l2,
            gradient_normalization=self.gradient_normalization,
            gradient_normalization_threshold=self.gradient_normalization_threshold,
            lr_policy=self.lr_policy,
            lr_policy_decay_rate=self.lr_policy_decay_rate,
            lr_policy_steps=self.lr_policy_steps,
            lr_policy_power=self.lr_policy_power,
            lr_schedule=self.lr_schedule,
            regularizable=self.regularizable_params(),
        )

    # -- pretraining hook --------------------------------------------------

    def is_pretrainable(self) -> bool:
        return False

    def has_loss(self) -> bool:
        return False

    def input_kind(self) -> str:
        """Data family this layer consumes: feedforward | convolutional
        | recurrent | any. Drives auto-preprocessor insertion."""
        return "feedforward"


@dataclass(frozen=True)
class FeedForwardLayerSpec(LayerSpec):
    """Base for layers with nIn/nOut (reference
    ``nn/conf/layers/FeedForwardLayer.java``)."""

    n_in: int = 0
    n_out: int = 0

    def with_input_type(self, input_type: InputType) -> "FeedForwardLayerSpec":
        if self.n_in == 0:
            return dataclasses.replace(self, n_in=input_type.flat_size())
        return self

    def output_type(self, input_type: InputType) -> InputType:
        return InputType.feed_forward(self.n_out)
