"""The gated short convolution of LFM2's convolution blocks, as a pair of
Pallas kernels over the ``[b, t, 3d]`` output of the block's input
projection, ``[B | C | x̃]`` (B, C and x̃ its first, second and third
``d`` columns):

    u_t = B_t ⊙ x̃_t
    z_t = Σ_j w[j] ⊙ u_{t-K+1+j}        depthwise, causal, no bias, no activation
    y_t = C_t ⊙ z_t

Written as XLA operations this is three gating and shifting passes over
``[b, t, 3d]`` with float32 arrays between them, fused as the compiler
sees fit and named after nothing. ``short_conv_fwd`` reads ``[B | C |
x̃]`` once and writes ``y`` ``[b, t, d]``; ``short_conv_bwd`` reads it
and ``dy`` once and writes ``d[B | C | x̃]`` ``[b, t, 3d]`` and ``dw``
``[K, d]`` (float32, summed over rows and positions in an output block
that stays resident while the grid runs).

The kernels work on the arrays as the projections give them: **channels
on lanes, time on rows** (a matrix product's ``[b·t, 3d]`` result is
row-major, and its consumers, the output projection and the weight
gradients, take row-major operands, so there is nothing to transpose
around the custom calls). A program takes ``bt`` positions of one batch
row and every column (``tiling.pick_short_conv_rows``), so the three
column blocks are read in place, and walks them ``SHORT_CONV_ROWS``
positions by up to ``SHORT_CONV_COLS`` channels at a time. The
``K - 1`` positions of history come from a second ``BlockSpec`` on the
same array (the ``HALO`` rows before the tile; nought before position
0), and the backward, whose ``du`` at a position needs ``C ⊙ dy`` of
the ``K - 1`` positions after it, reads the ``HALO`` rows behind its tile
of ``[B | C | x̃]`` and ``dy`` the same way. ``u`` (and ``C ⊙ dy``) of a
column chunk live in float32 scratch with their halo, and a tap reads
its positions as an aligned window rolled along the rows
(``depthwise_conv.back`` and ``ahead`` with ``axis`` 0, and the sums
of ``dw`` fold whole row tiles with its ``fold_lanes``).

Arithmetic: the operands widened to float32, ``u``, ``z``, the products
and the sums of ``dw`` in float32, each output rounded once.
``short_conv_xla`` is the same equations in ``jax.numpy``, float32,
differentiated by autodiff: the path wherever dispatch is off or the
shape is not admitted, and the kernels' oracle. ``short_conv`` routes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.ops import tiling
from deeplearning4j_tpu.ops.depthwise_conv import ahead, back, fold_lanes

_F32 = jnp.float32
HALO = tiling.SHORT_CONV_HALO
ROWS = tiling.SHORT_CONV_ROWS


def short_conv_xla(bcx, w):
    """``C ⊙ conv(B ⊙ x̃, w)`` in ``bcx``'s dtype, from ``bcx`` ``[b, t,
    3d]`` and the taps ``w`` ``[K, d]``; positions before the start read
    as nought; float32 inside."""
    k, t = w.shape[0], bcx.shape[1]
    b_, c_, x_ = jnp.split(bcx.astype(_F32), 3, axis=-1)
    u = jnp.pad(b_ * x_, ((0, 0), (k - 1, 0), (0, 0)))
    w = w.astype(_F32)
    z = sum(u[:, i:i + t] * w[i] for i in range(k))
    return (c_ * z).astype(bcx.dtype)


def short_conv_ok(shape, dtype, taps: int) -> bool:
    """Gate: ``[b, t, 3d]`` of ``dtype`` under ``taps`` taps has row
    blocks the chip's compiler accepts for both kernels
    (``tiling.pick_short_conv_rows``)."""
    if len(shape) != 3 or jnp.dtype(dtype) not in (
            jnp.dtype(jnp.bfloat16), jnp.dtype(_F32)):
        return False
    d = tiling.short_conv_channels(int(shape[2]))
    size = jnp.dtype(dtype).itemsize
    return d is not None and all(
        tiling.pick_short_conv_rows(int(shape[1]), d, size, int(taps), bwd)
        for bwd in (False, True))


def _cols(ci, cols, d, part):
    """Column block ``part`` (0 B, 1 C, 2 x̃) of chunk ``ci``."""
    return pl.ds(pl.multiple_of(part * d + ci * cols, cols), cols)


def _rows(r):
    return pl.ds(pl.multiple_of(r * ROWS, ROWS), ROWS)


def _fwd_kernel(prev_ref, x_ref, w_ref, y_ref, us_ref, *, d: int,
                taps: int):
    """``us_ref`` ``[HALO + bt, cols]`` float32: ``u`` of the halo and
    the tile over one column chunk."""
    bt, cols = x_ref.shape[1], us_ref.shape[1]
    first = pl.program_id(1) == 0

    def columns(ci, carry):
        at = functools.partial(_cols, ci, cols, d)
        hu = (prev_ref[0, :, at(0)].astype(_F32)
              * prev_ref[0, :, at(2)].astype(_F32))
        us_ref[0:HALO] = jnp.where(first, jnp.zeros_like(hu), hu)
        w = [w_ref[j:j + 1, at(0)] for j in range(taps)]

        def some_rows(r, carry):
            rows = _rows(r)
            us_ref[pl.ds(HALO + r * ROWS, ROWS)] = (
                x_ref[0, rows, at(0)].astype(_F32)
                * x_ref[0, rows, at(2)].astype(_F32))
            lo = pl.multiple_of(HALO + r * ROWS, ROWS)
            z = None
            for j in range(taps):
                term = w[j] * back(us_ref, lo, ROWS, taps - 1 - j, axis=0)
                z = term if z is None else z + term
            y_ref[0, rows, at(0)] = (
                x_ref[0, rows, at(1)].astype(_F32) * z).astype(y_ref.dtype)
            return carry

        return jax.lax.fori_loop(0, bt // ROWS, some_rows, carry)

    jax.lax.fori_loop(0, d // cols, columns, None)


def _bwd_kernel(prev_ref, x_ref, next_ref, dy_ref, dy_next_ref, w_ref,
                dbcx_ref, dw_ref, us_ref, gs_ref, *, d: int, taps: int):
    """``us_ref`` ``[HALO + bt, cols]`` float32: ``u`` of the halo
    before the tile and of the tile; ``gs_ref`` ``[bt + HALO, cols]``
    float32: ``g = C ⊙ dy`` of the tile and of the look-ahead behind it
    (nought past the last position)."""
    bt, cols = x_ref.shape[1], us_ref.shape[1]
    ti = pl.program_id(1)
    first, last = ti == 0, ti == pl.num_programs(1) - 1

    @pl.when((pl.program_id(0) == 0) & first)
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    def columns(ci, carry):
        at = functools.partial(_cols, ci, cols, d)
        hu = (prev_ref[0, :, at(0)].astype(_F32)
              * prev_ref[0, :, at(2)].astype(_F32))
        us_ref[0:HALO] = jnp.where(first, jnp.zeros_like(hu), hu)
        hg = (next_ref[0, :, at(1)].astype(_F32)
              * dy_next_ref[0, :, at(0)].astype(_F32))
        gs_ref[bt:bt + HALO] = jnp.where(last, jnp.zeros_like(hg), hg)

        def fill(r, carry):
            rows = _rows(r)
            us_ref[pl.ds(HALO + r * ROWS, ROWS)] = (
                x_ref[0, rows, at(0)].astype(_F32)
                * x_ref[0, rows, at(2)].astype(_F32))
            gs_ref[rows] = (x_ref[0, rows, at(1)].astype(_F32)
                            * dy_ref[0, rows, at(0)].astype(_F32))
            return carry

        jax.lax.fori_loop(0, bt // ROWS, fill, None)
        w = [w_ref[j:j + 1, at(0)] for j in range(taps)]

        def some_rows(r, sums):
            rows = _rows(r)
            lo = pl.multiple_of(r * ROWS, ROWS)
            reads = [back(us_ref, HALO + lo, ROWS, taps - 1 - j, axis=0)
                     for j in range(taps)]
            z = du = None
            for j in range(taps):
                zt = w[j] * reads[j]
                dt = w[j] * ahead(gs_ref, lo, ROWS, taps - 1 - j, axis=0)
                z = zt if z is None else z + zt
                du = dt if du is None else du + dt
            b_ = x_ref[0, rows, at(0)].astype(_F32)
            x_ = x_ref[0, rows, at(2)].astype(_F32)
            dy = dy_ref[0, rows, at(0)].astype(_F32)
            out = dbcx_ref.dtype
            dbcx_ref[0, rows, at(0)] = (du * x_).astype(out)
            dbcx_ref[0, rows, at(1)] = (dy * z).astype(out)
            dbcx_ref[0, rows, at(2)] = (du * b_).astype(out)
            g = gs_ref[rows]
            return tuple(s + fold_lanes(g * reads[j], axis=0)
                         for j, s in enumerate(sums))

        sums = jax.lax.fori_loop(
            0, bt // ROWS, some_rows,
            tuple(jnp.zeros((8, cols), _F32) for _ in range(taps)))
        for j in range(taps):
            dw_ref[j:j + 1, at(0)] += jnp.sum(sums[j], axis=0,
                                              keepdims=True)
        return carry

    jax.lax.fori_loop(0, d // cols, columns, None)


def _specs(t: int, d: int, bt: int, taps: int):
    """(the tile of ``[b, t, 3d]``, its halo before, its look-ahead
    behind, the tile of ``[b, t, d]``, its look-ahead, the taps) for the
    grid (batch row, tile)."""
    per_tile, last = bt // HALO, t // HALO - 1
    vmem = functools.partial(pl.BlockSpec, memory_space=pltpu.VMEM)
    before = lambda bi, ti: (  # noqa: E731
        bi, jnp.maximum(ti * per_tile - 1, 0), 0)
    behind = lambda bi, ti: (  # noqa: E731
        bi, jnp.minimum((ti + 1) * per_tile, last), 0)
    return (vmem((1, bt, 3 * d), lambda bi, ti: (bi, ti, 0)),
            vmem((1, HALO, 3 * d), before),
            vmem((1, HALO, 3 * d), behind),
            vmem((1, bt, d), lambda bi, ti: (bi, ti, 0)),
            vmem((1, HALO, d), behind),
            vmem((taps, d), lambda bi, ti: (0, 0)))


def _shape(bcx, w, backward):
    b, t, wide = (int(v) for v in bcx.shape)
    d, taps = wide // 3, int(w.shape[0])
    bt = tiling.pick_short_conv_rows(t, d, bcx.dtype.itemsize, taps,
                                     backward)
    return b, t, d, taps, bt


def short_conv_fwd(bcx, w, interpret: bool = False):
    """``y`` ``[b, t, d]`` in ``bcx``'s dtype from ``bcx`` ``[b, t, 3d]``
    and the float32 taps ``w`` ``[K, d]``, where ``short_conv_ok``
    holds. Grid (batch row, tile of positions)."""
    b, t, d, taps, bt = _shape(bcx, w, False)
    tile, before, _, out, _, per_channel = _specs(t, d, bt, taps)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, d=d, taps=taps),
        grid=(b, t // bt),
        in_specs=[before, tile, per_channel],
        out_specs=out,
        out_shape=jax.ShapeDtypeStruct((b, t, d), bcx.dtype),
        scratch_shapes=[pltpu.VMEM((HALO + bt, tiling.short_conv_cols(d)),
                                   _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        name=tiling.kernel_name("short_conv_fwd", bcx.dtype, b=b, t=t, d=d,
                                k=taps),
        interpret=interpret,
    )(bcx, bcx, w)


def short_conv_bwd(bcx, w, dy, interpret: bool = False):
    """(``d[B | C | x̃]`` in ``bcx``'s dtype, ``dw`` float32) at the
    cotangent ``dy`` ``[b, t, d]``. Grid (batch row, tile of
    positions), both walked in order: ``dw`` adds up over them."""
    b, t, d, taps, bt = _shape(bcx, w, True)
    tile, before, behind, out, out_behind, per_channel = _specs(
        t, d, bt, taps)
    cols = tiling.short_conv_cols(d)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, d=d, taps=taps),
        grid=(b, t // bt),
        in_specs=[before, tile, behind, out, out_behind, per_channel],
        out_specs=[tile, per_channel],
        out_shape=[jax.ShapeDtypeStruct(bcx.shape, bcx.dtype),
                   jax.ShapeDtypeStruct(w.shape, _F32)],
        scratch_shapes=[pltpu.VMEM((HALO + bt, cols), _F32),
                        pltpu.VMEM((bt + HALO, cols), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        name=tiling.kernel_name("short_conv_bwd", bcx.dtype, b=b, t=t, d=d,
                                k=taps),
        interpret=interpret,
    )(bcx, bcx, bcx, dy, dy, w)


@jax.custom_vjp
def _kernel_pair(bcx, w):
    from deeplearning4j_tpu.ops.dispatch import pallas_interpret

    return short_conv_fwd(bcx, w, pallas_interpret())


def _kernel_pair_fwd(bcx, w):
    from deeplearning4j_tpu.ops.dispatch import pallas_interpret

    return short_conv_fwd(bcx, w, pallas_interpret()), (bcx, w)


def _kernel_pair_bwd(res, dy):
    from deeplearning4j_tpu.ops.dispatch import pallas_interpret, route

    bcx, w = res
    route("short_conv_bwd")
    return short_conv_bwd(bcx, w, dy, pallas_interpret())


_kernel_pair.defvjp(_kernel_pair_fwd, _kernel_pair_bwd)


def short_conv(bcx, w):
    """``C ⊙ conv(B ⊙ x̃, w)`` from ``bcx`` ``[b, t, 3d]`` and the taps
    ``w`` ``[K, d]``, in ``bcx``'s dtype. The kernel pair where
    ``short_conv_ok`` admits the shape — channels in whole lane tiles,
    positions in whole 16-row tiles, bfloat16 or float32 — and dispatch
    is on, the XLA form otherwise; decided here, once, and counted
    (``pallas_dispatch_total{kernel="short_conv_fwd"}``, and
    ``short_conv_bwd`` where the pair's backward is traced). The taps
    are widened ahead of the ``custom_vjp``, so their cotangent is
    rounded where autodiff rounds it."""
    from deeplearning4j_tpu.ops.dispatch import route

    if route("short_conv_fwd", short_conv_ok(bcx.shape, bcx.dtype,
                                             w.shape[0])):
        return _kernel_pair(bcx, w.astype(_F32))
    return short_conv_xla(bcx, w)
