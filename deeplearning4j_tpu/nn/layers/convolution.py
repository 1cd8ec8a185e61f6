"""Convolutional stack (reference: ``nn/layers/convolution/
ConvolutionLayer.java`` im2col+gemm path, ``SubsamplingLayer.java``,
and the whole ``deeplearning4j-cuda`` module's four cuDNN helpers —
``CudnnConvolutionHelper``, ``CudnnSubsamplingHelper``,
``CudnnBatchNormalizationHelper``, ``CudnnLocalResponseNormalizationHelper``).

TPU-first design: the reference needs im2col+gemm OR a cuDNN helper
per layer because it schedules ops by hand; on TPU a single
``lax.conv_general_dilated`` lowers straight to MXU convolutions and
XLA fuses bias+activation into it, so the helper-vs-builtin split
(and the ``AlgoMode`` autotune knob) dissolves — XLA autotunes tile
shapes itself. Pooling is ``lax.reduce_window``; batch-norm is inlined
arithmetic XLA fuses with the surrounding conv.

Data layout is NCHW at the API (reference parity); weights are OIHW
``[nOut, nIn, kh, kw]`` matching the reference's param shape so
checkpoints map 1:1.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.layers.base import LayerSpec, register_layer
from deeplearning4j_tpu.nn.weights import init_weights


def _pair(v) -> tuple:
    if isinstance(v, (tuple, list)):
        return (int(v[0]), int(v[1]))
    return (int(v), int(v))


def _out_size(size: int, k: int, s: int, p: int) -> int:
    """Reference ``KernelValidationUtil`` output-shape math."""
    out = (size + 2 * p - k) // s + 1
    if out <= 0:
        raise ValueError(
            f"Invalid conv/pool geometry: input {size}, kernel {k}, "
            f"stride {s}, padding {p} -> output {out}"
        )
    return out


@register_layer
@dataclass(frozen=True)
class ConvolutionLayer(LayerSpec):
    """2-D convolution (reference ``nn/conf/layers/ConvolutionLayer`` +
    impl). ``algo_mode`` is accepted for config parity but is a no-op:
    XLA autotunes (reference uses it to pick cuDNN algorithms)."""

    n_in: int = 0
    n_out: int = 0
    kernel_size: tuple = (5, 5)
    stride: tuple = (1, 1)
    padding: tuple = (0, 0)
    algo_mode: str = "PREFER_FASTEST"
    activation: str = "identity"
    weight_init: str = "XAVIER"

    def input_kind(self) -> str:
        return "convolutional"

    def with_input_type(self, it: InputType) -> "ConvolutionLayer":
        if self.n_in == 0 and it.kind in ("convolutional", "convolutionalFlat"):
            return dataclasses.replace(self, n_in=it.channels)
        return self

    def output_type(self, it: InputType) -> InputType:
        kh, kw = _pair(self.kernel_size)
        sh, sw = _pair(self.stride)
        ph, pw = _pair(self.padding)
        return InputType.convolutional(
            _out_size(it.height, kh, sh, ph),
            _out_size(it.width, kw, sw, pw),
            self.n_out,
        )

    def init_params(self, key, dtype=jnp.float32) -> dict:
        kh, kw = _pair(self.kernel_size)
        fan_in = self.n_in * kh * kw
        fan_out = self.n_out * kh * kw
        w = init_weights(
            key, (self.n_out, self.n_in, kh, kw), self.weight_init,
            fan_in=fan_in, fan_out=fan_out, distribution=self.dist,
            dtype=dtype,
        )
        b = jnp.full((self.n_out,), self.bias_init, dtype)
        return {"W": w, "b": b}

    def pre_output(self, params, x):
        from deeplearning4j_tpu.ops.dispatch import effective_platform

        sh, sw = _pair(self.stride)
        ph, pw = _pair(self.padding)
        if effective_platform() == "tpu":
            # TPU: XLA picks its own layouts inside a program, so the
            # NCHW of the API costs nothing there
            y = lax.conv_general_dilated(
                x, params["W"],
                window_strides=(sh, sw),
                padding=((ph, ph), (pw, pw)),
                dimension_numbers=("NCHW", "OIHW", "NCHW"),
            )
        else:
            # CPU: XLA's fast (Eigen) conv kernels exist ONLY for
            # NHWC — the NCHW lowering is a naive loop, measured 38x
            # slower at ResNet shapes. The API stays NCHW (reference
            # parity); the transposes fuse into the surrounding ops.
            y = lax.conv_general_dilated(
                jnp.transpose(x, (0, 2, 3, 1)),
                jnp.transpose(params["W"], (2, 3, 1, 0)),  # OIHW->HWIO
                window_strides=(sh, sw),
                padding=((ph, ph), (pw, pw)),
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
            )
            y = jnp.transpose(y, (0, 3, 1, 2))
        return y + params["b"].reshape(1, -1, 1, 1)

    def supports_drop_connect(self) -> bool:
        return True

    def apply(self, params, x, state, *, train=False, rng=None, mask=None):
        x = self.maybe_dropout(x, train=train, rng=rng)
        params = self.maybe_drop_connect(params, train=train, rng=rng)
        return self.activate_fn()(self.pre_output(params, x)), state


@register_layer
@dataclass(frozen=True)
class SubsamplingLayer(LayerSpec):
    """Spatial pooling: MAX / AVG / SUM (reference
    ``nn/conf/layers/SubsamplingLayer`` ``PoolingType`` +
    ``CudnnSubsamplingHelper``) via ``lax.reduce_window``."""

    pooling_type: str = "MAX"
    kernel_size: tuple = (2, 2)
    stride: tuple = (2, 2)
    padding: tuple = (0, 0)
    activation: str = "identity"

    def input_kind(self) -> str:
        return "convolutional"

    def output_type(self, it: InputType) -> InputType:
        kh, kw = _pair(self.kernel_size)
        sh, sw = _pair(self.stride)
        ph, pw = _pair(self.padding)
        return InputType.convolutional(
            _out_size(it.height, kh, sh, ph),
            _out_size(it.width, kw, sw, pw),
            it.channels,
        )

    def apply(self, params, x, state, *, train=False, rng=None, mask=None):
        kh, kw = _pair(self.kernel_size)
        sh, sw = _pair(self.stride)
        ph, pw = _pair(self.padding)
        dims = (1, 1, kh, kw)
        strides = (1, 1, sh, sw)
        pads = ((0, 0), (0, 0), (ph, ph), (pw, pw))
        pt = self.pooling_type.upper()
        if pt == "MAX":
            init = -jnp.inf
            y = lax.reduce_window(x, init, lax.max, dims, strides, pads)
        elif pt in ("AVG", "SUM"):
            y = lax.reduce_window(x, 0.0, lax.add, dims, strides, pads)
            if pt == "AVG":
                y = y / (kh * kw)
        else:
            raise ValueError(f"Unknown pooling type '{self.pooling_type}'")
        return y, state


@register_layer
@dataclass(frozen=True)
class BatchNormalization(LayerSpec):
    """Batch normalization (reference ``nn/layers/normalization/
    BatchNormalization.java`` + ``CudnnBatchNormalizationHelper``).

    Works on CNN [b,c,h,w] (per-channel) and FF [b,n] (per-feature)
    activations like the reference. Running mean/var live in the layer
    *state* pytree and are updated functionally inside the jitted step
    (the reference mutates INDArray fields)."""

    n_out: int = 0
    decay: float = 0.9
    eps: float = 1e-5
    gamma_init: float = 1.0
    beta_init: float = 0.0
    lock_gamma_beta: bool = False
    activation: str = "identity"

    def input_kind(self) -> str:
        return "any"

    def with_input_type(self, it: InputType) -> "BatchNormalization":
        if self.n_out == 0:
            n = it.channels if it.kind == "convolutional" else it.flat_size()
            return dataclasses.replace(self, n_out=n)
        return self

    def output_type(self, it: InputType) -> InputType:
        return it

    def regularizable_params(self) -> tuple:
        return ()  # reference: gamma/beta not regularized

    def uses_batch_statistics(self) -> bool:
        return True

    def init_params(self, key, dtype=jnp.float32) -> dict:
        if self.lock_gamma_beta:
            return {}
        return {
            "gamma": jnp.full((self.n_out,), self.gamma_init, dtype),
            "beta": jnp.full((self.n_out,), self.beta_init, dtype),
        }

    def init_state(self, dtype=jnp.float32) -> dict:
        return {
            "mean": jnp.zeros((self.n_out,), dtype),
            "var": jnp.ones((self.n_out,), dtype),
        }

    def apply(self, params, x, state, *, train=False, rng=None, mask=None):
        if x.ndim == 4:
            axes = (0, 2, 3)
            bshape = (1, -1, 1, 1)
        else:
            axes = (0,)
            bshape = (1, -1)
        if train:
            cnt = float(np.prod([x.shape[a] for a in axes]))
            if x.dtype in (jnp.bfloat16, jnp.float16):
                # ONE pass over x: sum and sum-of-squares are
                # independent reductions XLA multi-output-fuses into a
                # single read (jnp.mean-then-jnp.var chains the passes
                # — var's input depends on mean — costing an extra
                # full read of the [b,c,h,w] activation per BN layer;
                # measured on the ResNet-50 trace as part of the 34%
                # loop-fusion share). E[x^2]-E[x]^2 cancels only when
                # mean^2/var >> 2^24 in the f32 accumulator — far
                # beyond anything a half-precision activation can
                # even represent distinctly, so the one-pass form is
                # reserved for the low-precision compute dtypes where
                # the bandwidth matters and the cancellation cannot.
                xf = x.astype(jnp.float32)
                s1 = jnp.sum(xf, axis=axes)
                s2 = jnp.sum(xf * xf, axis=axes)
                mean = s1 / cnt
                var = jnp.maximum(s2 / cnt - mean * mean, 0.0)
            else:
                # f32/f64: numerically safe two-pass centered variance
                mean = jnp.mean(x, axis=axes)
                var = jnp.mean(
                    jnp.square(x - mean.reshape(bshape)), axis=axes
                )
            new_state = {
                "mean": (self.decay * state["mean"]
                         + (1 - self.decay) * mean.astype(state["mean"].dtype)),
                "var": (self.decay * state["var"]
                        + (1 - self.decay) * var.astype(state["var"].dtype)),
            }
        else:
            # running stats live in master precision; normalize in the
            # activation dtype so mixed-precision inference stays in
            # the compute dtype instead of promoting downstream to f32
            acc_dt = jnp.promote_types(x.dtype, jnp.float32)
            mean = state["mean"].astype(acc_dt)
            var = state["var"].astype(acc_dt)
            new_state = state
        # fold to a per-channel affine (y = a*x + b): the apply pass
        # is then a single fused elementwise read-modify-write, and
        # the [C]-sized coefficient math stays off the hot pass
        a, b = self._affine_from_stats(params, mean, var)
        y = x * a.astype(x.dtype).reshape(bshape) + \
            b.astype(x.dtype).reshape(bshape)
        return self.activate_fn()(y), new_state

    def _affine_from_stats(self, params, mean, var):
        inv = lax.rsqrt(var + self.eps)
        if self.lock_gamma_beta:
            return inv, -mean * inv
        a = params["gamma"].astype(inv.dtype) * inv
        b = params["beta"].astype(inv.dtype) - mean * a
        return a, b


@register_layer
@dataclass(frozen=True)
class LocalResponseNormalization(LayerSpec):
    """Cross-channel LRN (reference ``nn/layers/normalization/
    LocalResponseNormalization.java`` +
    ``CudnnLocalResponseNormalizationHelper``), Krizhevsky form as in
    the reference's builtin path: y = x / (k + alpha * sum_{j in
    window} x_j^2)^beta."""

    k: float = 2.0
    n: int = 5
    alpha: float = 1e-4
    beta: float = 0.75
    activation: str = "identity"

    def input_kind(self) -> str:
        return "convolutional"

    def apply(self, params, x, state, *, train=False, rng=None, mask=None):
        half = self.n // 2
        sq = x * x
        # windowed sum over the channel axis via reduce_window;
        # asymmetric padding keeps the channel count for even n
        summed = lax.reduce_window(
            sq, 0.0, lax.add,
            window_dimensions=(1, self.n, 1, 1),
            window_strides=(1, 1, 1, 1),
            padding=((0, 0), (half, self.n - 1 - half), (0, 0), (0, 0)),
        )
        denom = (self.k + self.alpha * summed) ** self.beta
        return x / denom, state
