"""Fused convolution Pallas kernel: ``activation(BN_affine(conv2d(x, w)
+ bias))`` as ONE kernel.

Where it stands on the chip (PR 29, one v5e, ResNet-50 at batch 128 in
bfloat16; PERF.md section 6): it loses to ``lax.conv_general_dilated``
on every one of the model's 16 unit-stride shape classes — forward and
backward of one call 1.5x to 4.7x slower, the eval forward with the BN
affine and ReLU fused 1.4x to 3.2x slower — and a training step with
its 46 accepted convolutions here took 168.5 ms on the device against
49.6 ms with all 53 on XLA, which fuses the batch-norm statistics into
its own convolutions. So ``auto`` dispatch sends a convolution here
only for a shape class listed in ``_FASTER_THAN_XLA`` below, and none
is; the kernel runs where ``DL4J_TPU_PALLAS=1`` forces it (the parity
tests, ``scripts/conv_class_ab.py``). The "conv owns 61.6% of the
step" of the removed round-5 roofline note predates every chip run of
this file and is no longer a reason for it.

Design (register/cache blocking per "Anatomy of High-Performance Deep
Learning Convolutions on SIMD Architectures"): im2col-free direct
convolution, grid = (batch, out-channel blocks, out-row blocks) with the
spatial axis innermost — the weight block's index is constant over it,
so Mosaic's pipeline fetches each [kh, kw, C, oc_b] weight tile once and
keeps it VMEM-resident while output rows stream. The kh*kw taps unroll
at trace time; each tap is one MXU matmul ([oh_b*OW, C] x [C, oc_b])
accumulated in f32 (half-precision inputs stay bf16/f16 into the MXU).
The epilogue — bias add, the folded per-channel ``a*x + b`` BN affine,
then identity/relu/leaky-relu/tanh — applies to the f32 accumulator
in-register, followed by a single cast + HBM writeback.

Layout: NCHW at the API (layer/checkpoint parity); internally NHWC +
HWIO so the channel axis is the (contiguous) lane axis of every MXU
operand. The transposes and the explicit zero-pad sit OUTSIDE the
kernel where XLA fuses them; the epilogue round-trips are what this
kernel deletes, not the relayout.

Backward is hand-written Pallas too (same paper's recipe; it casts the
cotangent and the flipped weights to float32, which costs the MXU
several bf16 passes: backward-data was the largest kernel of
ResNet-50's step, 38.95 of 168.5 ms, PR 29): dL/dx is a stride-1
direct conv of the interior-dilated, edge-padded gradient with the
flipped/transposed weights — the SAME forward kernel on transformed
operands; dL/dw is a dedicated kernel with batch as the innermost
(revisited) grid axis,
accumulating per-tap [C, oh*ow] x [oh*ow, oc_b] MXU products into an
f32-resident [kh, kw, C, oc_b] output block. Both carry f32
accumulators and fall back to ``jax.vjp`` through the XLA reference
when their tilings don't fit VMEM — the same gate pattern as the
forward.

Block sizes come from ``ops/tiling.py`` (the shared divisor heuristic)
and, when ``DL4J_TPU_TUNE`` is active, from the measured winners in
``ops/autotune.py``. Both are resolved HERE at the public entry,
before the custom-vjp boundary, so forward and backward always agree.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.ops import autotune, tiling


# Epilogue nonlinearities the kernel applies in-register (in f32,
# before the single cast + writeback). Numerics must match
# nn/activations.py exactly — the parity tests compare against the
# layer path (leaky_relu's reference slope is 0.01).
_EPILOGUES = {
    "identity": lambda z: z,
    "relu": lambda z: jnp.maximum(z, 0.0),
    "leakyrelu": lambda z: jnp.where(z >= 0, z, z * 0.01),
    "tanh": jnp.tanh,
}
SUPPORTED_EPILOGUES = tuple(_EPILOGUES)

# d(act)/dz on the f32 pre-activation — the backward's epilogue.
# Numerics match jax.vjp through _EPILOGUES exactly: lax.max splits
# the tie at z == 0 evenly (balanced_eq), hence relu's 0.5 there.
_EPILOGUE_GRADS = {
    "identity": lambda z: jnp.ones_like(z),
    "relu": lambda z: jnp.where(
        z > 0, 1.0, jnp.where(z == 0, 0.5, 0.0)),
    "leakyrelu": lambda z: jnp.where(z >= 0, 1.0, 0.01),
    "tanh": lambda z: 1.0 - jnp.square(jnp.tanh(z)),
}


def conv_block_ok(x_shape, w_shape, stride=(1, 1), padding=(0, 0),
                  dtype=jnp.float32) -> bool:
    """Gate: 4-d NCHW/OIHW geometry with matching channels, unit
    stride and a VMEM-fitting tiling. Callers route to ``conv_block``
    only when this holds (else the plain XLA layer path). Keyed to the
    divisor HEURISTIC on purpose: tuning changes block shapes, never
    routing.

    Stride > 1 is ineligible because the chip's compiler refuses both
    ways the kernel could take the strided tap: slicing the loaded
    window (``NotImplementedError: Only 2D gather is supported``) and
    a strided ref load on bf16 (``Strided load with non 32-bit
    data``). The kernel's strided branch still runs in interpret mode
    for the parity tests."""
    if len(x_shape) != 4 or len(w_shape) != 4:
        return False
    if int(x_shape[1]) != int(w_shape[1]):
        return False
    if int(stride[0]) != 1 or int(stride[1]) != 1:
        return False
    try:
        itemsize = np.dtype(dtype).itemsize
        return tiling.pick_conv_blocks(
            x_shape, w_shape,
            (int(stride[0]), int(stride[1])),
            (int(padding[0]), int(padding[1])),
            itemsize) is not None
    except (TypeError, ValueError):
        return False


# Shape classes — (kh, kw, c_in, c_out, h, w, dtype name, epilogue
# fused) — on which the chip showed this kernel faster than
# ``lax.conv_general_dilated``, forward and backward together, alone
# AND inside a training step. Empty: PR 29 measured ResNet-50's 16
# unit-stride classes on one v5e at batch 128 (PERF.md section 6,
# ``scripts/conv_class_ab.py``) and XLA won each by 1.5x or more. A
# class nobody measured is not listed and takes XLA, the path the
# chip's compiler owns; an entry is added only with a ledger line
# behind it.
_FASTER_THAN_XLA: frozenset = frozenset()


def conv_shape_class(x_shape, w_shape, dtype, fused_epilogue) -> tuple:
    """What ``conv_block_faster`` decides on, all of it observable at
    the call: kernel size, channel counts, spatial extent, dtype, and
    whether the kernel's epilogue would fuse anything beyond the
    bias add (an activation or a BN affine)."""
    return (int(w_shape[2]), int(w_shape[3]), int(x_shape[1]),
            int(w_shape[0]), int(x_shape[2]), int(x_shape[3]),
            np.dtype(dtype).name, bool(fused_epilogue))


def conv_block_faster(x_shape, w_shape, dtype=jnp.float32,
                      fused_epilogue=False) -> bool:
    """Whether the chip has shown the kernel to beat XLA's convolution
    on this call's shape class. ``conv_block_ok`` says the compiler
    accepts a call; this says it is worth sending. Under
    ``DL4J_TPU_PALLAS=auto`` a call site routes only where both hold
    (``ConvolutionLayer._kernel_eligible``)."""
    return conv_shape_class(x_shape, w_shape, dtype,
                            fused_epilogue) in _FASTER_THAN_XLA


# --- forward (and backward-data) direct-conv kernel ------------------------


def _conv_kernel(x_ref, w_ref, scale_ref, shift_ref, out_ref, *,
                 kh, kw, sh, sw, act):
    k = pl.program_id(2)
    oh_b, ow, oc_b = (out_ref.shape[1], out_ref.shape[2],
                      out_ref.shape[3])
    c = x_ref.shape[3]
    rows = (oh_b - 1) * sh + 1
    cols = (ow - 1) * sw + 1
    row0 = k * (oh_b * sh)
    acc = jnp.zeros((oh_b * ow, oc_b), jnp.float32)
    for dh in range(kh):
        for dw in range(kw):
            # one tap: the strided window of the resident image that
            # feeds this output block, flattened to an MXU matmul
            patch = x_ref[0, pl.ds(row0 + dh, rows), pl.ds(dw, cols), :]
            if sh > 1 or sw > 1:
                patch = patch[::sh, ::sw, :]
            acc = acc + jnp.dot(
                patch.reshape(oh_b * ow, c), w_ref[dh, dw],
                preferred_element_type=jnp.float32,
            )
    z = acc * scale_ref[0] + shift_ref[0]
    out_ref[0] = act(z).reshape(oh_b, ow, oc_b).astype(out_ref.dtype)


def _direct_conv_call(xh, wh, scale2, shift2, sh, sw, oc_b, oh_b,
                      activation, out_dtype, interpret,
                      kernel_pass="fwd"):
    """The raw blocked direct-conv dispatch on NHWC/HWIO operands that
    are ALREADY padded/transposed: xh [n, hp, wp, c], wh
    [kh, kw, c, o], scale2/shift2 f32 [1, o]. Shared by the forward
    (out_dtype = x.dtype) and the backward-data pass (identity
    epilogue on the dilated gradient, f32 out), and the unit the
    autotuner measures candidates through. ``kernel_pass`` (``fwd``,
    ``fwd_recompute`` for the backward's pre-epilogue accumulator,
    ``bwd_data``) is only the kernel's name in the device trace."""
    n, hp, wp, c = (int(v) for v in xh.shape)
    kh, kw, _, o = (int(v) for v in wh.shape)
    oh = (hp - kh) // sh + 1
    ow = (wp - kw) // sw + 1
    kern = functools.partial(_conv_kernel, kh=kh, kw=kw, sh=sh, sw=sw,
                             act=_EPILOGUES[activation])
    out = pl.pallas_call(
        kern,
        grid=(n, o // oc_b, oh // oh_b),
        in_specs=[
            pl.BlockSpec((1, hp, wp, c), lambda i, j, k: (i, 0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((kh, kw, c, oc_b),
                         lambda i, j, k: (0, 0, 0, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, oc_b), lambda i, j, k: (0, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, oc_b), lambda i, j, k: (0, j),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, oh_b, ow, oc_b),
                               lambda i, j, k: (i, k, 0, j),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((n, oh, ow, o), out_dtype),
        interpret=interpret,
        name=tiling.kernel_name(
            f"conv_block_{kernel_pass}", xh.dtype, n=n, h=hp, w=wp, c=c,
            o=o, kh=kh, kw=kw, s=sh),
    )(xh, wh, scale2, shift2)
    return out


def _conv_block_call(x, w, scale, shift, sh, sw, ph, pw, activation,
                     blocks, interpret):
    oc_b, oh_b = blocks
    o = int(w.shape[0])
    xh = jnp.transpose(x, (0, 2, 3, 1))        # NCHW -> NHWC
    if ph or pw:
        xh = jnp.pad(xh, ((0, 0), (ph, ph), (pw, pw), (0, 0)))
    wh = jnp.transpose(w, (2, 3, 1, 0))        # OIHW -> HWIO
    scale2 = scale.astype(jnp.float32).reshape(1, o)
    shift2 = shift.astype(jnp.float32).reshape(1, o)
    out = _direct_conv_call(xh, wh, scale2, shift2, sh, sw, oc_b, oh_b,
                            activation, x.dtype, interpret)
    return jnp.transpose(out, (0, 3, 1, 2))    # NHWC -> NCHW


# --- backward-weights kernel ------------------------------------------------


def _conv_bwd_w_kernel(x_ref, g_ref, out_ref, *, kh, kw, sh, sw):
    """dL/dw: batch is the innermost grid axis and the [kh, kw, C,
    oc_b] output block's index is constant over it — the block stays
    VMEM-resident (f32) while batch items stream, zero-initialized on
    the first visit then accumulated (the standard Pallas reduction
    idiom). Each tap contracts the strided image window with the
    gradient block over the oh*ow axis: one [C, oh*ow] x [oh*ow, oc_b]
    MXU product per (dh, dw)."""
    i = pl.program_id(1)
    oh, ow, oc_b = g_ref.shape[1], g_ref.shape[2], g_ref.shape[3]
    c = x_ref.shape[3]
    rows = (oh - 1) * sh + 1
    cols = (ow - 1) * sw + 1

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)

    g2 = g_ref[0].reshape(oh * ow, oc_b)
    for dh in range(kh):
        for dw in range(kw):
            patch = x_ref[0, pl.ds(dh, rows), pl.ds(dw, cols), :]
            if sh > 1 or sw > 1:
                patch = patch[::sh, ::sw, :]
            tap = jax.lax.dot_general(
                patch.reshape(oh * ow, c), g2,
                dimension_numbers=(((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [c, oc_b]
            out_ref[dh, dw] = out_ref[dh, dw] + tap


def _conv_bwd_w_call(xh, dacc, kh, kw, sh, sw, oc_b, interpret):
    """Blocked dL/dw on padded NHWC image xh [n, hp, wp, c] and the f32
    pre-epilogue gradient dacc [n, oh, ow, o]; returns [kh, kw, c, o]
    f32 (HWIO — the caller transposes back to OIHW)."""
    n, hp, wp, c = (int(v) for v in xh.shape)
    _, oh, ow, o = (int(v) for v in dacc.shape)
    kern = functools.partial(_conv_bwd_w_kernel, kh=kh, kw=kw, sh=sh,
                             sw=sw)
    return pl.pallas_call(
        kern,
        grid=(o // oc_b, n),
        in_specs=[
            pl.BlockSpec((1, hp, wp, c), lambda j, i: (i, 0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, oh, ow, oc_b), lambda j, i: (i, 0, 0, j),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((kh, kw, c, oc_b),
                               lambda j, i: (0, 0, 0, j),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((kh, kw, c, o), jnp.float32),
        interpret=interpret,
        name=tiling.kernel_name(
            "conv_block_bwd_weights", xh.dtype, n=n, h=hp, w=wp, c=c,
            o=o, kh=kh, kw=kw, s=sh),
    )(xh, dacc)


# --- block resolution (tiling heuristic + autotuner) ------------------------


def _identity(x_shape, w_shape, stride, padding, dtype):
    return {
        "x": [int(v) for v in x_shape],
        "w": [int(v) for v in w_shape],
        "stride": [int(v) for v in stride],
        "padding": [int(v) for v in padding],
        "dtype": str(jnp.dtype(dtype)),
    }


def _fwd_measure_factory(x_shape, w_shape, stride, padding, dtype,
                         interpret):
    """measure_factory for the forward/backward-data kernel: canned
    deterministic inputs, one eager blocked dispatch per call."""
    def factory(cfg):
        oc_b, oh_b = cfg
        n, c, hp, wp, o, kh, kw, oh, ow = tiling.conv_geometry(
            x_shape, w_shape, stride, padding)
        rng = np.random.RandomState(0)
        xh = jnp.asarray(rng.standard_normal((n, hp, wp, c)), dtype)
        wh = jnp.asarray(rng.standard_normal((kh, kw, c, o)), dtype)
        scale2 = jnp.ones((1, o), jnp.float32)
        shift2 = jnp.zeros((1, o), jnp.float32)
        sh, sw = stride

        def run():
            out = _direct_conv_call(xh, wh, scale2, shift2, sh, sw,
                                    oc_b, oh_b, "identity", dtype,
                                    interpret)
            jax.block_until_ready(out)
        return run
    return factory


def _bwd_w_measure_factory(x_shape, w_shape, stride, padding, dtype,
                           interpret):
    def factory(cfg):
        (oc_b,) = cfg
        n, c, hp, wp, o, kh, kw, oh, ow = tiling.conv_geometry(
            x_shape, w_shape, stride, padding)
        rng = np.random.RandomState(0)
        xh = jnp.asarray(rng.standard_normal((n, hp, wp, c)), dtype)
        dacc = jnp.asarray(rng.standard_normal((n, oh, ow, o)),
                           jnp.float32)
        sh, sw = stride

        def run():
            out = _conv_bwd_w_call(xh, dacc, kh, kw, sh, sw, oc_b,
                                   interpret)
            jax.block_until_ready(out)
        return run
    return factory


def _resolve_fwd_blocks(x_shape, w_shape, stride, padding, dtype,
                        interpret, kernel="conv_block"):
    itemsize = jnp.dtype(dtype).itemsize
    heur = tiling.pick_conv_blocks(x_shape, w_shape, stride, padding,
                                   itemsize)
    if heur is None or not autotune.tuning_active():
        return heur
    factory = None
    if autotune.tuning_mode() == "on":
        factory = _fwd_measure_factory(x_shape, w_shape, stride,
                                       padding, dtype, interpret)
    return autotune.resolve(
        kernel,
        _identity(x_shape, w_shape, stride, padding, dtype),
        heur,
        tiling.conv_candidates(x_shape, w_shape, stride, padding,
                               itemsize),
        lambda cfg: tiling.conv_candidate_cost(
            cfg, x_shape, w_shape, stride, padding, itemsize),
        factory,
    )


def _resolve_bwd_blocks(x_shape, w_shape, stride, padding, dtype,
                        interpret):
    """((dx_oc_b, dx_oh_b), dw_oc_b) for the hand-written backward, or
    None → the ``jax.vjp`` reference fallback. dL/dx reuses the
    forward kernel on the equivalent stride-1 conv (dilated gradient x
    flipped weights, f32), so its tiling comes from the SAME picker on
    the equivalent geometry."""
    n, c, hp, wp, o, kh, kw, oh, ow = tiling.conv_geometry(
        x_shape, w_shape, stride, padding)
    if oh <= 0 or ow <= 0:
        return None
    dx_x_shape = (n, o, hp + kh - 1, wp + kw - 1)
    dx_w_shape = (c, o, kh, kw)
    dx = _resolve_fwd_blocks(dx_x_shape, dx_w_shape, (1, 1), (0, 0),
                             jnp.float32, interpret,
                             kernel="conv_bwd_data")
    itemsize = jnp.dtype(dtype).itemsize
    dw_heur = tiling.pick_conv_bwd_w_block(x_shape, w_shape, stride,
                                           padding, itemsize)
    if dx is None or dw_heur is None:
        return None
    dw = (dw_heur,)
    if autotune.tuning_active():
        factory = None
        if autotune.tuning_mode() == "on":
            factory = _bwd_w_measure_factory(x_shape, w_shape, stride,
                                             padding, dtype, interpret)
        dw = autotune.resolve(
            "conv_bwd_w",
            _identity(x_shape, w_shape, stride, padding, dtype),
            dw,
            tiling.conv_bwd_w_candidates(x_shape, w_shape, stride,
                                         padding, itemsize),
            lambda cfg: tiling.conv_bwd_w_candidate_cost(
                cfg, x_shape, w_shape, stride, padding, itemsize),
            factory,
        )
    return (tuple(int(v) for v in dx), int(dw[0]))


# --- reference + custom-vjp boundary ---------------------------------------


def _reference_core(sh, sw, ph, pw, activation, x, w, scale, shift):
    """XLA reference math — the parity baseline and the backward
    fallback when the hand-written tilings don't fit VMEM. Same
    semantics as the kernel: f32 accumulation, f32 epilogue, one final
    cast. The CPU branch mirrors the layer's NHWC detour (Eigen has no
    fast NCHW conv)."""
    from deeplearning4j_tpu.ops.dispatch import effective_platform

    # f32 operands, not bf16 operands with preferred_element_type=f32:
    # the mixed-dtype conv has no transpose rule, and this function is
    # differentiated by the backward fallback. Same values either way
    # (a bf16 product is exact in f32).
    xf = x.astype(jnp.float32)
    wf = w.astype(jnp.float32)
    if effective_platform() == "tpu":
        y = jax.lax.conv_general_dilated(
            xf, wf, window_strides=(sh, sw),
            padding=((ph, ph), (pw, pw)),
            dimension_numbers=("NCHW", "OIHW", "NCHW"),
        )
    else:
        y = jax.lax.conv_general_dilated(
            jnp.transpose(xf, (0, 2, 3, 1)),
            jnp.transpose(wf, (2, 3, 1, 0)),
            window_strides=(sh, sw),
            padding=((ph, ph), (pw, pw)),
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )
        y = jnp.transpose(y, (0, 3, 1, 2))
    z = (y * scale.astype(jnp.float32).reshape(1, -1, 1, 1)
         + shift.astype(jnp.float32).reshape(1, -1, 1, 1))
    return _EPILOGUES[activation](z).astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _conv_block_vjp(meta, x, w, scale, shift):
    sh, sw, ph, pw, activation, interpret, fwd_blocks, _ = meta
    return _conv_block_call(x, w, scale, shift, sh, sw, ph, pw,
                            activation, fwd_blocks, interpret)


def _conv_block_fwd(meta, x, w, scale, shift):
    sh, sw, ph, pw, activation, interpret, fwd_blocks, _ = meta
    return (
        _conv_block_call(x, w, scale, shift, sh, sw, ph, pw,
                         activation, fwd_blocks, interpret),
        (x, w, scale, shift),
    )


def _conv_block_bwd(meta, res, g):
    """Hand-written backward (see module docstring). Recomputes the
    f32 pre-epilogue accumulator through the forward kernel (cheaper
    than saving it: one recompute vs an [n, oh, ow, o] f32 residual
    held across the whole backward), applies the epilogue gradient in
    f32, then one Pallas dispatch each for dL/dx and dL/dw."""
    sh, sw, ph, pw, activation, interpret, fwd_blocks, bwd = meta
    x, w, scale, shift = res
    if bwd is None:
        _, vjp = jax.vjp(
            lambda *a: _reference_core(sh, sw, ph, pw, activation, *a),
            x, w, scale, shift,
        )
        return vjp(g)

    (dx_oc_b, dx_oh_b), dw_oc_b = bwd
    n, c, h, w_in = (int(v) for v in x.shape)
    o, _, kh, kw = (int(v) for v in w.shape)
    hp, wp = h + 2 * ph, w_in + 2 * pw

    xh = jnp.transpose(x, (0, 2, 3, 1))
    if ph or pw:
        xh = jnp.pad(xh, ((0, 0), (ph, ph), (pw, pw), (0, 0)))
    wh = jnp.transpose(w, (2, 3, 1, 0))
    o_ones = jnp.ones((1, o), jnp.float32)
    o_zeros = jnp.zeros((1, o), jnp.float32)
    fwd_oc_b, fwd_oh_b = fwd_blocks
    acc = _direct_conv_call(xh, wh, o_ones, o_zeros, sh, sw, fwd_oc_b,
                            fwd_oh_b, "identity", jnp.float32,
                            interpret,
                            kernel_pass="fwd_recompute")  # [n,oh,ow,o] f32

    # epilogue gradient in f32 (cast vjp: g comes in as x.dtype)
    g_nhwc = jnp.transpose(g, (0, 2, 3, 1)).astype(jnp.float32)
    scale_f = scale.astype(jnp.float32)
    z = acc * scale_f + shift.astype(jnp.float32)
    dz = g_nhwc * _EPILOGUE_GRADS[activation](z)
    dshift = dz.sum((0, 1, 2)).astype(shift.dtype)
    dscale = (dz * acc).sum((0, 1, 2)).astype(scale.dtype)
    dacc = dz * scale_f  # [n, oh, ow, o] f32

    # dL/dx: interior-dilate dacc by the stride, pad by (k-1) plus the
    # edge rows the strided forward never read, then a stride-1 direct
    # conv with the spatially-flipped, in/out-transposed weights — the
    # SAME forward kernel on transformed operands.
    rh = tiling.conv_edge_remainder(hp, kh, sh)
    rw = tiling.conv_edge_remainder(wp, kw, sw)
    gdil = jax.lax.pad(
        dacc, jnp.float32(0),
        ((0, 0, 0), (kh - 1, kh - 1 + rh, sh - 1),
         (kw - 1, kw - 1 + rw, sw - 1), (0, 0, 0)),
    )  # [n, hp + kh - 1, wp + kw - 1, o]
    wflip = jnp.transpose(w[:, :, ::-1, ::-1],
                          (2, 3, 0, 1)).astype(jnp.float32)
    c_ones = jnp.ones((1, c), jnp.float32)
    c_zeros = jnp.zeros((1, c), jnp.float32)
    dxp = _direct_conv_call(gdil, wflip, c_ones, c_zeros, 1, 1,
                            dx_oc_b, dx_oh_b, "identity", jnp.float32,
                            interpret,
                            kernel_pass="bwd_data")  # [n, hp, wp, c]
    if ph or pw:
        dxp = dxp[:, ph:ph + h, pw:pw + w_in, :]
    dx = jnp.transpose(dxp, (0, 3, 1, 2)).astype(x.dtype)

    # dL/dw: direct correlation of the padded image with dacc
    dw_hwio = _conv_bwd_w_call(xh, dacc, kh, kw, sh, sw, dw_oc_b,
                               interpret)  # [kh, kw, c, o] f32
    dw = jnp.transpose(dw_hwio, (3, 2, 0, 1)).astype(w.dtype)
    return dx, dw, dscale, dshift


_conv_block_vjp.defvjp(_conv_block_fwd, _conv_block_bwd)


def _fold_epilogue(o, bias, bn_scale, bn_shift):
    """Collapse bias + BN affine to one f32 (scale, shift) pair OUTSIDE
    the kernel boundary: activation((conv+bias)*a + b) ==
    activation(conv*a + (bias*a + b)). The fold is ordinary traced ops,
    so grads flow to bias/gamma/beta automatically while the kernel
    sees exactly two [O] vectors."""
    scale = (bn_scale.astype(jnp.float32) if bn_scale is not None
             else jnp.ones((o,), jnp.float32))
    shift = (bn_shift.astype(jnp.float32) if bn_shift is not None
             else jnp.zeros((o,), jnp.float32))
    if bias is not None:
        shift = shift + bias.astype(jnp.float32) * scale
    return scale, shift


def conv_block(x, w, bias=None, bn_scale=None, bn_shift=None, *,
               stride=(1, 1), padding=(0, 0), activation="identity",
               interpret: bool = False):
    """Fused ``activation((conv2d(x, w) + bias) * bn_scale + bn_shift)``
    via ONE Pallas kernel, with a hand-written Pallas backward. x NCHW
    [n,c,h,w], w OIHW [o,c,kh,kw], bias/bn_scale/bn_shift per-channel
    [o] (each optional). ``interpret`` and every block config are
    resolved HERE, before the custom-vjp boundary (nondiff arguments:
    forward and backward must agree on them) — off-TPU the kernel
    self-arms interpreter mode even when ``DL4J_TPU_PALLAS=1`` forces
    routing."""
    from deeplearning4j_tpu.ops.dispatch import pallas_interpret

    if activation not in _EPILOGUES:
        raise ValueError(
            f"conv_block: unsupported epilogue '{activation}' "
            f"(supported: {SUPPORTED_EPILOGUES})"
        )
    scale, shift = _fold_epilogue(int(w.shape[0]), bias, bn_scale,
                                  bn_shift)
    stride = (int(stride[0]), int(stride[1]))
    padding = (int(padding[0]), int(padding[1]))
    interp = bool(interpret or pallas_interpret())
    fwd_blocks = _resolve_fwd_blocks(
        tuple(int(v) for v in x.shape), tuple(int(v) for v in w.shape),
        stride, padding, x.dtype, interp)
    if fwd_blocks is None:
        raise ValueError("conv_block: no VMEM-fitting tiling (callers "
                         "must gate on conv_block_ok)")
    bwd = _resolve_bwd_blocks(
        tuple(int(v) for v in x.shape), tuple(int(v) for v in w.shape),
        stride, padding, x.dtype, interp)
    meta = (stride[0], stride[1], padding[0], padding[1], activation,
            interp, tuple(int(v) for v in fwd_blocks), bwd)
    return _conv_block_vjp(meta, x, w, scale, shift)


def conv_block_reference(x, w, bias=None, bn_scale=None, bn_shift=None,
                         *, stride=(1, 1), padding=(0, 0),
                         activation="identity"):
    """The XLA-fused reference path: identical semantics, no Pallas —
    the A/B baseline for ``scripts/bench_kernels.py`` and the parity
    tests, and the math the backward fallback recomputes through."""
    if activation not in _EPILOGUES:
        raise ValueError(
            f"conv_block: unsupported epilogue '{activation}' "
            f"(supported: {SUPPORTED_EPILOGUES})"
        )
    scale, shift = _fold_epilogue(int(w.shape[0]), bias, bn_scale,
                                  bn_shift)
    return _reference_core(int(stride[0]), int(stride[1]),
                           int(padding[0]), int(padding[1]),
                           activation, x, w, scale, shift)
