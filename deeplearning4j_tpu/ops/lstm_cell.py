"""Fused LSTM cell Pallas kernel (SURVEY.md §2.3: the LSTM cell is a
named Pallas-fusion target; reference hot loop
``LSTMHelpers.activateHelper:159`` does the ``ifog`` gate matmul +
five elementwise stages as separate nd4j ops).

One kernel per timestep fuses the recurrent matmul (MXU) with every
gate nonlinearity and the cell/hidden updates (VPU) — the [b, 4n]
pre-activation tensor never leaves VMEM. The input projection
``x @ W`` for ALL timesteps stays outside (one big MXU matmul, already
optimal).

Gate order matches the layer convention: i, f, o, g."""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.ops import autotune, tiling


def _cell_kernel(xproj_ref, h_ref, c_ref, rw_ref, h_out, c_out, *,
                 peephole_refs=None):
    n = h_ref.shape[1]
    z = xproj_ref[:] + jnp.dot(
        h_ref[:], rw_ref[:], preferred_element_type=jnp.float32
    )
    zi = z[:, 0 * n:1 * n]
    zf = z[:, 1 * n:2 * n]
    zo = z[:, 2 * n:3 * n]
    zg = z[:, 3 * n:4 * n]
    c = c_ref[:]
    if peephole_refs is not None:
        pI, pF, pO = peephole_refs
        zi = zi + c * pI[:]
        zf = zf + c * pF[:]
    i = jax.nn.sigmoid(zi)
    f = jax.nn.sigmoid(zf)
    g = jnp.tanh(zg)
    c_new = f * c + i * g
    if peephole_refs is not None:
        zo = zo + c_new * pO[:]
    o = jax.nn.sigmoid(zo)
    h_out[:] = (o * jnp.tanh(c_new)).astype(h_out.dtype)
    c_out[:] = c_new.astype(c_out.dtype)


def _peephole_kernel(xproj_ref, h_ref, c_ref, rw_ref, pi_ref, pf_ref,
                     po_ref, h_out, c_out):
    _cell_kernel(xproj_ref, h_ref, c_ref, rw_ref, h_out, c_out,
                 peephole_refs=(pi_ref, pf_ref, po_ref))


def lstm_cell(xproj, h, c, rw, peepholes=None, interpret: bool = False):
    """One fused cell step. xproj [b, 4n] (= x_t @ W + b), h/c [b, n],
    rw [n, 4n], peepholes optional (pI, pF, pO) each [n].
    Returns (h_new, c_new). Off-TPU (``DL4J_TPU_PALLAS=1`` forced on a
    CPU host) the kernel self-arms interpreter mode instead of failing
    to lower TPU memory spaces."""
    from deeplearning4j_tpu.ops.dispatch import pallas_interpret

    interpret = interpret or pallas_interpret()
    b, n = h.shape
    out_shape = (
        jax.ShapeDtypeStruct((b, n), h.dtype),
        jax.ShapeDtypeStruct((b, n), c.dtype),
    )
    vm = pl.BlockSpec(memory_space=pltpu.VMEM)
    if peepholes is None:
        return pl.pallas_call(
            _cell_kernel,
            out_shape=out_shape,
            in_specs=[vm, vm, vm, vm],
            out_specs=(vm, vm),
            interpret=interpret,
            name=tiling.kernel_name("lstm_cell_fwd", h.dtype, b=b, n=n),
        )(xproj, h, c, rw)
    pI, pF, pO = (p.reshape(1, n) for p in peepholes)
    return pl.pallas_call(
        _peephole_kernel,
        out_shape=out_shape,
        in_specs=[vm] * 7,
        out_specs=(vm, vm),
        interpret=interpret,
        name=tiling.kernel_name("lstm_cell_peephole_fwd", h.dtype, b=b,
                                n=n),
    )(xproj, h, c, rw, pI, pF, pO)


def _reference_cell(xproj, h, c, rw, peepholes):
    """XLA reference math — also the backward path (pallas_call has no
    automatic transpose, so grads recompute through this)."""
    z = xproj + h @ rw
    zi, zf, zo, zg = jnp.split(z, 4, axis=-1)
    if peepholes is not None:
        pI, pF, pO = peepholes
        zi = zi + c * pI
        zf = zf + c * pF
    i = jax.nn.sigmoid(zi)
    f = jax.nn.sigmoid(zf)
    g = jnp.tanh(zg)
    c_new = f * c + i * g
    if peepholes is not None:
        zo = zo + c_new * peepholes[2]
    o = jax.nn.sigmoid(zo)
    return o * jnp.tanh(c_new), c_new


@jax.custom_vjp
def lstm_cell_diff(xproj, h, c, rw, peepholes):
    return lstm_cell(xproj, h, c, rw, peepholes)


def _cell_fwd(xproj, h, c, rw, peepholes):
    return lstm_cell(xproj, h, c, rw, peepholes), (
        xproj, h, c, rw, peepholes,
    )


def _cell_bwd(res, g):
    xproj, h, c, rw, peepholes = res
    _, vjp = jax.vjp(
        lambda *a: _reference_cell(*a), xproj, h, c, rw, peepholes
    )
    return vjp(g)


lstm_cell_diff.defvjp(_cell_fwd, _cell_bwd)


def use_pallas_lstm() -> bool:
    from deeplearning4j_tpu.ops.dispatch import use_pallas

    return use_pallas()


# ---------------------------------------------------------------------------
# Sequence-level kernel: weights resident in VMEM across ALL timesteps
# ---------------------------------------------------------------------------
#
# The per-step cell above re-fetches RW [n, 4n] from HBM every
# timestep (lax.scan invokes the kernel T times): at the saturated
# bench shape (n=1024, b=256, bf16) that is 8 MB of weight traffic per
# step against 2 MB of actual data (xproj) — the measured 12.5% MFU is
# the HBM roofline of that reload (artifacts/lstm_roofline_r5.md).
# Here ONE pallas_call runs the whole sequence: grid=(T,), RW's block
# index is constant so Mosaic's pipeline fetches it once and keeps it
# in VMEM; h/c carry lives in f32 VMEM scratch across grid steps
# (the TPU grid is sequential). The backward kernel streams dgates
# out per step with RW again resident; dW/dRW reduce to two big MXU
# matmuls outside the kernel.
#
# VMEM budget at the saturated shape: RW 8 MB (bf16) + xproj block
# 2 MB + h/c scratch 2x1 MB (f32) + out blocks 2x0.5 MB + z temp 4 MB
# (f32) ~ 16 MB — one core's VMEM. Larger n needs batch-blocking
# (outer batch grid dim); gated to n*4n*itemsize <=
# tiling.SEQ_RW_BYTES_MAX. The batch block comes from
# tiling.pick_lstm_batch_block (the shared divisor heuristic) or, when
# DL4J_TPU_TUNE is active, the autotuner's measured winner — the block
# is numerics-neutral (batch rows are independent), so it resolves at
# trace time without threading through the vjp meta.


def _seq_fwd_core(xproj_ref, rw_ref, h0_ref, c0_ref,
                  hseq_ref, cseq_ref, hT_ref, cT_ref,
                  h_scr, c_scr):
    t = pl.program_id(1)   # grid = (batch blocks, T); t innermost
    n = h0_ref.shape[1]

    @pl.when(t == 0)
    def _():
        h_scr[:] = h0_ref[:].astype(h_scr.dtype)
        c_scr[:] = c0_ref[:].astype(c_scr.dtype)

    z = xproj_ref[0].astype(jnp.float32) + jnp.dot(
        h_scr[:].astype(rw_ref.dtype), rw_ref[:],
        preferred_element_type=jnp.float32,
    )
    zi = z[:, 0 * n:1 * n]
    zf = z[:, 1 * n:2 * n]
    zo = z[:, 2 * n:3 * n]
    zg = z[:, 3 * n:4 * n]
    c = c_scr[:]
    i = jax.nn.sigmoid(zi)
    f = jax.nn.sigmoid(zf)
    g = jnp.tanh(zg)
    c_new = f * c + i * g
    o = jax.nn.sigmoid(zo)
    h_new = o * jnp.tanh(c_new)
    h_scr[:] = h_new
    c_scr[:] = c_new
    hseq_ref[0] = h_new.astype(hseq_ref.dtype)
    if cseq_ref is not None:
        cseq_ref[0] = c_new.astype(cseq_ref.dtype)
    hT_ref[:] = h_new.astype(hT_ref.dtype)
    cT_ref[:] = c_new.astype(cT_ref.dtype)


def _seq_fwd_kernel(xproj_ref, rw_ref, h0_ref, c0_ref,
                    hseq_ref, cseq_ref, hT_ref, cT_ref,
                    h_scr, c_scr):
    _seq_fwd_core(xproj_ref, rw_ref, h0_ref, c0_ref,
                  hseq_ref, cseq_ref, hT_ref, cT_ref, h_scr, c_scr)


def _seq_fwd_kernel_nocseq(xproj_ref, rw_ref, h0_ref, c0_ref,
                           hseq_ref, hT_ref, cT_ref, h_scr, c_scr):
    """Inference variant: c_seq is only a vjp residual — skipping it
    saves a T*b*n HBM stream per forward call."""
    _seq_fwd_core(xproj_ref, rw_ref, h0_ref, c0_ref,
                  hseq_ref, None, hT_ref, cT_ref, h_scr, c_scr)


def _seq_bwd_kernel(xproj_ref, hprev_ref, cprev_ref, cseq_ref, rw_ref,
                    dhseq_ref, dhT_ref, dcT_ref,
                    dgates_ref, dh0_ref, dc0_ref,
                    dh_scr, dc_scr):
    """Reverse-time pass (the grid index maps feed blocks in reverse
    order): recompute gates from the saved h_{t-1}/c_{t-1}/c_t, chain
    dh/dc through VMEM scratch, stream dgates to HBM."""
    t = pl.program_id(1)           # 0 .. T-1 in REVERSE time order
    T = pl.num_programs(1)
    n = dh0_ref.shape[1]

    @pl.when(t == 0)
    def _():
        dh_scr[:] = dhT_ref[:].astype(dh_scr.dtype)
        dc_scr[:] = dcT_ref[:].astype(dc_scr.dtype)

    z = xproj_ref[0].astype(jnp.float32) + jnp.dot(
        hprev_ref[0].astype(rw_ref.dtype), rw_ref[:],
        preferred_element_type=jnp.float32,
    )
    zi = z[:, 0 * n:1 * n]
    zf = z[:, 1 * n:2 * n]
    zo = z[:, 2 * n:3 * n]
    zg = z[:, 3 * n:4 * n]
    c_prev = cprev_ref[0].astype(jnp.float32)
    c_t = cseq_ref[0].astype(jnp.float32)
    i = jax.nn.sigmoid(zi)
    f = jax.nn.sigmoid(zf)
    o = jax.nn.sigmoid(zo)
    g = jnp.tanh(zg)
    tc = jnp.tanh(c_t)
    dh = dhseq_ref[0].astype(jnp.float32) + dh_scr[:]
    do = dh * tc
    dct = dh * o * (1.0 - tc * tc) + dc_scr[:]
    dzo = do * o * (1.0 - o)
    dzf = (dct * c_prev) * f * (1.0 - f)
    dzi = (dct * g) * i * (1.0 - i)
    dzg = (dct * i) * (1.0 - g * g)
    dc_scr[:] = dct * f
    dz = jnp.concatenate([dzi, dzf, dzo, dzg], axis=1)
    # dh_{t-1} = dz @ RW^T without materializing the transpose
    dh_prev = jax.lax.dot_general(
        dz.astype(rw_ref.dtype), rw_ref[:],
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    dh_scr[:] = dh_prev
    dgates_ref[0] = dz.astype(dgates_ref.dtype)
    dh0_ref[:] = dh_prev.astype(dh0_ref.dtype)
    dc0_ref[:] = dc_scr[:].astype(dc0_ref.dtype)


def _seq_measure_factory(T, b, n, four_n, dtype, bwd, interpret):
    """measure_factory for the sequence kernels: canned deterministic
    inputs, one eager dispatch per call with the candidate batch
    block."""
    def factory(cfg):
        (bb,) = cfg
        rng = np.random.RandomState(0)
        xproj = jnp.asarray(
            rng.standard_normal((T, b, four_n)) * 0.1, dtype)
        rw = jnp.asarray(rng.standard_normal((n, four_n)) * 0.1, dtype)
        if not bwd:
            h0 = jnp.zeros((b, n), dtype)
            c0 = jnp.zeros((b, n), dtype)

            def run():
                out = _lstm_sequence_fwd_call(xproj, h0, c0, rw,
                                              interpret, bb=bb)
                jax.block_until_ready(out)
            return run
        hprev = jnp.asarray(rng.standard_normal((T, b, n)) * 0.1,
                            dtype)
        cprev = jnp.asarray(rng.standard_normal((T, b, n)) * 0.1,
                            dtype)
        cseq = jnp.asarray(rng.standard_normal((T, b, n)) * 0.1, dtype)
        dhseq = jnp.asarray(rng.standard_normal((T, b, n)) * 0.1,
                            dtype)
        dhT = jnp.zeros((b, n), dtype)
        dcT = jnp.zeros((b, n), dtype)

        def run():
            out = _lstm_sequence_bwd_call(xproj, hprev, cprev, cseq,
                                          rw, dhseq, dhT, dcT,
                                          interpret, bb=bb)
            jax.block_until_ready(out)
        return run
    return factory


def _resolve_seq_block(T, b, n, four_n, dtype, bwd, interpret):
    """The batch block one sequence dispatch uses: the shared divisor
    heuristic, or the autotuner's measured winner when tuning is
    active (forward and backward kernels tune independently — the
    block is numerics-neutral)."""
    itemsize = jnp.dtype(dtype).itemsize
    heur = tiling.pick_lstm_batch_block(b, n, four_n, itemsize,
                                        bwd=bwd)
    if heur is None or not autotune.tuning_active():
        return heur
    factory = None
    if autotune.tuning_mode() == "on":
        factory = _seq_measure_factory(T, b, n, four_n, dtype, bwd,
                                       interpret)
    got = autotune.resolve(
        "lstm_seq_bwd" if bwd else "lstm_seq_fwd",
        {"T": int(T), "b": int(b), "n": int(n),
         "dtype": str(jnp.dtype(dtype))},
        (heur,),
        tiling.lstm_batch_candidates(b, n, four_n, itemsize, bwd=bwd),
        lambda cfg: tiling.lstm_candidate_cost(cfg, b, n, four_n, T,
                                               itemsize),
        factory,
    )
    return int(got[0])


def _lstm_sequence_fwd_call(xproj, h0, c0, rw, interpret,
                            save_cseq=True, bb=None):
    T, b, four_n = xproj.shape
    n = four_n // 4
    dt = h0.dtype
    if bb is None:
        bb = _resolve_seq_block(T, b, n, four_n, rw.dtype, False,
                                interpret)
    if bb is None:
        raise ValueError("lstm_sequence: no VMEM-fitting batch block "
                         "(callers must gate on lstm_sequence_ok)")
    nb = b // bb
    seq_out = lambda: pl.BlockSpec(
        (1, bb, n), lambda j, t: (t, j, 0), memory_space=pltpu.VMEM
    )
    fin_out = lambda: pl.BlockSpec(
        (bb, n), lambda j, t: (j, 0), memory_space=pltpu.VMEM
    )
    out_specs = [seq_out()]
    out_shape = [jax.ShapeDtypeStruct((T, b, n), dt)]   # h_seq
    if save_cseq:
        out_specs.append(seq_out())
        out_shape.append(jax.ShapeDtypeStruct((T, b, n), dt))
    out_specs += [fin_out(), fin_out()]
    out_shape += [jax.ShapeDtypeStruct((b, n), dt),
                  jax.ShapeDtypeStruct((b, n), dt)]
    out = pl.pallas_call(
        _seq_fwd_kernel if save_cseq else _seq_fwd_kernel_nocseq,
        grid=(nb, T),
        in_specs=[
            pl.BlockSpec((1, bb, four_n), lambda j, t: (t, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((n, four_n), lambda j, t: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bb, n), lambda j, t: (j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bb, n), lambda j, t: (j, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=tuple(out_specs),
        out_shape=tuple(out_shape),
        scratch_shapes=[
            pltpu.VMEM((bb, n), jnp.float32),
            pltpu.VMEM((bb, n), jnp.float32),
        ],
        interpret=interpret,
        name=tiling.kernel_name("lstm_sequence_fwd", rw.dtype, t=T, b=b,
                                n=n),
    )(xproj, rw, h0, c0)
    if save_cseq:
        return out
    hseq, hT, cT = out
    return hseq, None, hT, cT


def _lstm_sequence_bwd_call(xproj, hprev, cprev, cseq, rw, dhseq,
                            dhT, dcT, interpret, bb=None):
    T, b, four_n = xproj.shape
    n = four_n // 4
    dt = rw.dtype
    if bb is None:
        bb = _resolve_seq_block(T, b, n, four_n, rw.dtype, True,
                                interpret)
    if bb is None:
        raise ValueError("lstm_sequence: no VMEM-fitting batch block "
                         "(callers must gate on lstm_sequence_ok)")
    rev = lambda j, t: (T - 1 - t, j, 0)
    blk = lambda j, t: (j, 0)
    cst = lambda j, t: (0, 0)
    return pl.pallas_call(
        _seq_bwd_kernel,
        grid=(b // bb, T),
        in_specs=[
            pl.BlockSpec((1, bb, four_n), rev, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bb, n), rev, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bb, n), rev, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bb, n), rev, memory_space=pltpu.VMEM),
            pl.BlockSpec((n, four_n), cst, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bb, n), rev, memory_space=pltpu.VMEM),
            pl.BlockSpec((bb, n), blk, memory_space=pltpu.VMEM),
            pl.BlockSpec((bb, n), blk, memory_space=pltpu.VMEM),
        ],
        out_specs=(
            pl.BlockSpec((1, bb, four_n), rev, memory_space=pltpu.VMEM),
            pl.BlockSpec((bb, n), blk, memory_space=pltpu.VMEM),
            pl.BlockSpec((bb, n), blk, memory_space=pltpu.VMEM),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((T, b, four_n), dt),  # dgates
            jax.ShapeDtypeStruct((b, n), jnp.float32),  # dh0
            jax.ShapeDtypeStruct((b, n), jnp.float32),  # dc0
        ),
        scratch_shapes=[
            pltpu.VMEM((bb, n), jnp.float32),
            pltpu.VMEM((bb, n), jnp.float32),
        ],
        interpret=interpret,
        name=tiling.kernel_name("lstm_sequence_bwd", rw.dtype, t=T, b=b,
                                n=n),
    )(xproj, hprev, cprev, cseq, rw, dhseq, dhT, dcT)


def lstm_sequence_ok(n: int, four_n: int, dtype, b: int) -> bool:
    """Gate: standard gates, no peephole/mask, RW small enough to sit
    resident in VMEM, and a batch block exists that divides b and
    fits BOTH kernels' VMEM budgets. Keyed to the divisor HEURISTIC:
    tuning changes block shapes, never routing."""
    itemsize = np.dtype(dtype).itemsize
    return (
        four_n == 4 * n
        and itemsize * n * four_n <= tiling.SEQ_RW_BYTES_MAX
        and tiling.pick_lstm_batch_block(b, n, four_n, itemsize)
        is not None
        and tiling.pick_lstm_batch_block(b, n, four_n, itemsize,
                                         bwd=True) is not None
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _lstm_sequence_vjp(xproj, h0, c0, rw, interpret):
    hseq, _cseq, hT, cT = _lstm_sequence_fwd_call(
        xproj, h0, c0, rw, interpret, save_cseq=False
    )
    return hseq, hT, cT


def lstm_sequence(xproj, h0, c0, rw, interpret=False):
    """Whole-sequence fused LSTM (no peephole, no mask):
    xproj [T, b, 4n] = x@W+b precomputed, h0/c0 [b, n], rw [n, 4n].
    Returns (h_seq [T, b, n], hT, cT). ``interpret`` is resolved HERE,
    before the custom-vjp boundary (it is a nondiff argument, so the
    forward and backward kernels must agree on it): off-TPU the
    kernels run in interpreter mode even when ``DL4J_TPU_PALLAS=1``
    forces routing."""
    from deeplearning4j_tpu.ops.dispatch import pallas_interpret

    return _lstm_sequence_vjp(
        xproj, h0, c0, rw, bool(interpret or pallas_interpret())
    )


def _lstm_sequence_fwd(xproj, h0, c0, rw, interpret):
    hseq, cseq, hT, cT = _lstm_sequence_fwd_call(
        xproj, h0, c0, rw, interpret
    )
    return (hseq, hT, cT), (xproj, h0, c0, rw, hseq, cseq)


def _lstm_sequence_bwd(interpret, res, grads):
    xproj, h0, c0, rw, hseq, cseq = res
    dhseq, dhT, dcT = grads
    hprev = jnp.concatenate([h0[None], hseq[:-1]], axis=0)
    cprev = jnp.concatenate([c0[None], cseq[:-1]], axis=0)
    dgates, dh0, dc0 = _lstm_sequence_bwd_call(
        xproj, hprev, cprev, cseq, rw, dhseq, dhT, dcT, interpret
    )
    # weight gradient: ONE MXU matmul over the whole sequence
    T, b, four_n = dgates.shape
    n = rw.shape[0]
    drw = jax.lax.dot_general(
        hprev.reshape(T * b, n), dgates.reshape(T * b, four_n),
        dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(rw.dtype)
    return (dgates.astype(xproj.dtype), dh0.astype(h0.dtype),
            dc0.astype(c0.dtype), drw)


_lstm_sequence_vjp.defvjp(_lstm_sequence_fwd, _lstm_sequence_bwd)
