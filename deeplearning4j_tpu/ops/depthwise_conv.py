"""Backward pass of the causal depthwise convolution over time with its
bias and SiLU, as one Pallas kernel: one pass over ``x`` and ``dy``.

    y[t, c] = silu(Σ_k w[k, c] · x[t - (K-1) + k, c] + bias[c])

Under autodiff XLA turns each of the ``K`` shifted float32 products into
a pad and each multiply into a reduction over every position: float32
passes over ``[b, t, c]`` with the pre-activation and ``dy · silu'``
written to HBM between them. Here the pre-activation is rebuilt from
``x`` in VMEM, ``g = dy · silu'(pre)`` lives there too, and the only HBM
arrays are ``x``, ``dy``, ``dx`` and the two small gradients.

The kernel works on ``[b, c, t]``: **time on lanes**, channels on
sublanes. That is the layout XLA itself gives the inside of a
state-space mixer (the chunked scan's products contract over positions,
so the compiler keeps positions minor from the input projection's
result to the gated norm), and the ``swapaxes`` around the kernel are
layout changes it folds away there. A kernel on ``[b, t, c]`` as stored
row-major was as fast alone but turned the whole mixer round on the
chip (27 layout copies of the scan's float32 arrays), and a forward
kernel beside this one let XLA's memory scheduler put all 30
weight-gradient products of the MLPs at the end of the step, where they
run half as fast: both cost more than they saved, so the forward stays
XLA's shifted products (PERF.md §6, PR 38).

A program takes one ``[block_c, block_t]`` tile of one batch row and
walks it ``ROWS`` channels at a time. The ``K-1`` positions of history
come from a second ``BlockSpec`` on the same array (the ``HALO`` lanes
before the tile; nought before position 0), and because ``dx`` at a
position needs ``g`` of the ``K-1`` positions after it, the ``HALO``
lanes behind the tile of ``x`` and ``dy`` come the same way. A tap
reads its positions as an aligned load rolled along the lanes
(``pltpu.roll``: a read at a lane offset cost three times as much on the
chip). ``dW`` and ``db`` add up in a float32 output block that stays
resident while the batch and time axes of the grid run.

Arithmetic, as autodiff of the XLA form has it: ``x``, the taps and the
bias widened to float32, the pre-activation, SiLU's slope, ``g``, the
``K`` products of ``dx`` and the sums of ``dW`` and ``db`` in float32,
``dx`` rounded once.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.ops import tiling

_F32 = jnp.float32
HALO = tiling.DEPTHWISE_CONV_HALO
# channels of a tile taken at a time: one bfloat16 tile of sublanes
ROWS = tiling.DEPTHWISE_CONV_ROWS
# positions whose products are formed at a time
LANES = 512


def depthwise_conv_bwd_ok(shape, dtype, taps: int) -> bool:
    """Gate: ``[b, t, c]`` of ``dtype`` under ``taps`` taps has blocks
    the chip's compiler accepts
    (``tiling.pick_depthwise_conv_blocks``)."""
    if len(shape) != 3 or jnp.dtype(dtype) not in (
            jnp.dtype(jnp.bfloat16), jnp.dtype(_F32)):
        return False
    return tiling.pick_depthwise_conv_blocks(
        int(shape[1]), int(shape[2]), jnp.dtype(dtype).itemsize,
        int(taps)) is not None


def silu_and_slope(pre):
    sig = jax.nn.sigmoid(pre)
    return pre * sig, sig * (1.0 + pre * (1.0 - sig))


def fold_lanes(a):
    """``[r, n]`` summed to ``[r, 128]`` by whole lane tiles (plain
    adds; the one reduce across lanes comes once a tile)."""
    return functools.reduce(
        jnp.add, [a[:, i:i + 128] for i in range(0, a.shape[1], 128)])


def lane_chunks(n: int):
    """Static (start, size) of the pieces a tile's positions are taken
    in."""
    return [(l0, min(LANES, n - l0)) for l0 in range(0, n, LANES)]


def back(ref, lo, n, j):
    """``ref[:, lo - j:lo - j + n]`` for ``lo`` a multiple of 128: the
    ``n + 128`` lanes from the tile before ``lo``, rolled right by
    ``j``."""
    if j == 0:
        return ref[:, lo:lo + n]
    return pltpu.roll(ref[:, lo - 128:lo + n], j, 1)[:, 128:]


def ahead(ref, lo, n, j):
    """``ref[:, lo + j:lo + j + n]`` for ``lo`` a multiple of 128."""
    if j == 0:
        return ref[:, lo:lo + n]
    return pltpu.roll(ref[:, lo:lo + n + 128], n + 128 - j, 1)[:, :n]


def _bwd_kernel(prev_ref, x_ref, next_ref, dy_ref, dy_next_ref, wb_ref,
                dx_ref, dwb_ref, xs_ref, gs_ref, acc_ref, *, taps: int,
                t: int):
    """``wb_ref`` ``[block_c, taps + 1]``: a channel's taps, then its
    bias. Of the rows at hand, ``xs_ref`` ``[ROWS, HALO + block_t +
    HALO]`` float32: history, tile, the lanes behind it; ``gs_ref``
    ``[ROWS, block_t + 2 * HALO]`` float32: ``g`` of the tile and of the
    lanes behind it, nought from position ``t`` on (a last tile the
    length does not fill, and the lanes behind the last tile), and one
    more tile of lanes that the rolled reads touch and drop.
    ``acc_ref`` ``[taps + 1, block_c, 128]`` float32: the tile's sums
    for ``dW`` and ``db`` before the one reduce across lanes."""
    bc, bt = x_ref.shape[1:]
    ti = pl.program_id(2)
    live = lambda l0, n: (  # noqa: E731  [1, n]: positions before t
        ti * bt + l0 + jax.lax.broadcasted_iota(jnp.int32, (1, n), 1) < t)
    zero = jnp.zeros_like

    @pl.when((pl.program_id(1) == 0) & (ti == 0))
    def _():
        dwb_ref[...] = zero(dwb_ref)

    def slope_times_dy(wb, l0, n, dy):
        """(g of positions [l0, l0 + n), the taps' reads of x)."""
        reads = [back(xs_ref, HALO + l0, n, taps - 1 - k)
                 for k in range(taps)]
        pre = wb[:, taps:taps + 1]
        for k in range(taps):
            pre = pre + wb[:, k:k + 1] * reads[k]
        g = dy.astype(_F32) * silu_and_slope(pre)[1]
        return jnp.where(live(l0, n), g, zero(g)), reads

    def some_rows(i, carry):
        at = pl.ds(pl.multiple_of(i * ROWS, ROWS), ROWS)
        prev = prev_ref[0, at].astype(_F32)
        xs_ref[:, 0:HALO] = jnp.where(ti == 0, zero(prev), prev)
        x = x_ref[0, at].astype(_F32)
        xs_ref[:, HALO:HALO + bt] = jnp.where(live(0, bt), x, zero(x))
        nxt = next_ref[0, at].astype(_F32)
        xs_ref[:, HALO + bt:] = jnp.where(live(bt, HALO), nxt, zero(nxt))
        wb = wb_ref[at]
        sums = [jnp.zeros((ROWS, 128), _F32) for _ in range(taps + 1)]
        for l0, n in lane_chunks(bt):
            g, reads = slope_times_dy(wb, l0, n, dy_ref[0, at, l0:l0 + n])
            gs_ref[:, l0:l0 + n] = g
            for k in range(taps):
                sums[k] = sums[k] + fold_lanes(g * reads[k])
            sums[taps] = sums[taps] + fold_lanes(g)
        gs_ref[:, bt:bt + HALO] = slope_times_dy(
            wb, bt, HALO, dy_next_ref[0, at])[0]
        for k in range(taps + 1):
            acc_ref[k, at] = sums[k]
        for l0, n in lane_chunks(bt):
            acc = None
            for k in range(taps):
                term = wb[:, k:k + 1] * ahead(gs_ref, l0, n, taps - 1 - k)
                acc = term if acc is None else acc + term
            dx_ref[0, at, l0:l0 + n] = acc.astype(dx_ref.dtype)
        return carry

    jax.lax.fori_loop(0, bc // ROWS, some_rows, None)
    for k in range(taps + 1):
        dwb_ref[:, k:k + 1] += jnp.sum(acc_ref[k], axis=1, keepdims=True)


def tile_specs(t: int, bc: int, bt: int, taps: int):
    """(tile, lanes before it, lanes behind it, the channels' taps and
    bias) for the grid (channel block, batch, time block)."""
    per_tile = bt // HALO
    last = pl.cdiv(t, HALO) - 1
    vmem = functools.partial(pl.BlockSpec, memory_space=pltpu.VMEM)
    return (
        vmem((1, bc, bt), lambda ci, bi, ti: (bi, ci, ti)),
        vmem((1, bc, HALO), lambda ci, bi, ti: (
            bi, ci, jnp.maximum(ti * per_tile - 1, 0))),
        vmem((1, bc, HALO), lambda ci, bi, ti: (
            bi, ci, jnp.minimum((ti + 1) * per_tile, last))),
        vmem((bc, taps + 1), lambda ci, bi, ti: (ci, 0)),
    )


def depthwise_conv_bwd(x, wb, dy, blocks, interpret: bool = False):
    """(dx in ``x``'s dtype, d ``wb`` float32) at the cotangent ``dy``,
    on ``x`` and ``dy`` ``[b, c, t]``; ``wb`` ``[c, K + 1]`` float32, a
    channel's taps then its bias."""
    b, c, t = (int(v) for v in x.shape)
    taps = int(wb.shape[1]) - 1
    bt, bc = blocks
    tile, before, behind, per_channel = tile_specs(t, bc, bt, taps)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, taps=taps, t=t),
        grid=(pl.cdiv(c, bc), b, pl.cdiv(t, bt)),
        in_specs=[before, tile, behind, tile, behind, per_channel],
        out_specs=[tile, per_channel],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(wb.shape, _F32)],
        scratch_shapes=[pltpu.VMEM((ROWS, HALO + bt + HALO), _F32),
                        pltpu.VMEM((ROWS, bt + 2 * HALO), _F32),
                        pltpu.VMEM((taps + 1, bc, 128), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        name=tiling.kernel_name("depthwise_conv_bwd", x.dtype, b=b, t=t,
                                c=c, k=taps),
        interpret=interpret,
    )(x, x, x, dy, dy, wb)


def conv_silu_bwd(x, w, bias, dy):
    """(dx, dW, db) of ``silu(conv(x, w) + bias)`` at ``dy``, on
    ``[b, t, c]`` arrays where ``depthwise_conv_bwd_ok`` holds; ``w``
    ``[K, c]`` and ``bias`` ``[c]`` float32, as their gradients."""
    from deeplearning4j_tpu.ops.dispatch import pallas_interpret

    taps = int(w.shape[0])
    blocks = tiling.pick_depthwise_conv_blocks(
        int(x.shape[1]), int(x.shape[2]), jnp.dtype(x.dtype).itemsize, taps)
    dx, dwb = depthwise_conv_bwd(
        jnp.swapaxes(x, 1, 2), jnp.concatenate([w.T, bias[:, None]], axis=1),
        jnp.swapaxes(dy, 1, 2), blocks, pallas_interpret())
    return jnp.swapaxes(dx, 1, 2), dwb[:, :taps].T, dwb[:, taps]
