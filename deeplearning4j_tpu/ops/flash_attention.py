"""Flash-attention Pallas kernel (SURVEY.md §2.3 native-component
checklist: "custom Pallas kernels where fusion matters").

The XLA fallback materializes the [t, t] score matrix in HBM between
the two matmuls; this kernel streams K/V through VMEM in blocks with
an online-softmax accumulator, so HBM traffic is O(t·d) instead of
O(t²) — the standard flash-attention scheme, with the MXU doing the
[BQ, d]×[d, BK] tiles. Numerics match
``deeplearning4j_tpu.parallel.sequence.attention`` (same masking
convention) to ~1e-5.

Dispatch: ``mha(q, k, v, causal)`` uses the kernel on the TPU backend
(override with env DL4J_TPU_PALLAS=0/1); elsewhere it falls back to
the fused-by-XLA reference implementation."""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.ops import autotune, tiling

_NEG = -1e9


def _attention_kernel(q_ref, k_ref, v_ref, o_ref, *, block_k: int,
                      causal: bool, scale: float):
    """One program handles one (batch·head, q-block) tile.
    q_ref [BQ, d]; k_ref/v_ref [t, d] resident in VMEM; K/V consumed
    in block_k chunks with the online softmax."""
    _, bq, d = q_ref.shape
    t = k_ref.shape[1]
    qi = pl.program_id(1)
    q = q_ref[0, :, :] * scale

    m0 = jnp.full((bq, 1), 2.0 * _NEG, jnp.float32)
    l0 = jnp.zeros((bq, 1), jnp.float32)
    o0 = jnp.zeros((bq, d), jnp.float32)

    n_blocks = t // block_k
    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)

    def body(j, carry):
        o, l, m = carry
        k_blk = k_ref[0, pl.ds(j * block_k, block_k), :]
        v_blk = v_ref[0, pl.ds(j * block_k, block_k), :]
        s = jnp.dot(q, k_blk.T, preferred_element_type=jnp.float32)
        if causal:
            k_pos = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (1, block_k), 1
            )
            s = jnp.where(q_pos >= k_pos, s, _NEG)
        m_blk = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m, m_blk)
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        o_new = o * corr + jnp.dot(
            p, v_blk, preferred_element_type=jnp.float32
        )
        return o_new, l_new, m_new

    if causal:
        # blocks strictly after this q block are fully masked — skip.
        # int32 throughout: pl.cdiv would promote its Python-int
        # divisor to int64 when x64 is globally enabled.
        last = (qi + 1) * bq  # first masked key position
        n_iter = jnp.minimum(
            jnp.asarray(n_blocks, jnp.int32),
            (last + jnp.asarray(block_k - 1, jnp.int32))
            // jnp.asarray(block_k, jnp.int32),
        )
    else:
        n_iter = n_blocks
    o, l, _ = jax.lax.fori_loop(0, n_iter, body, (o0, l0, m0))
    o_ref[0, :, :] = (o / jnp.maximum(l, 1e-20)).astype(o_ref.dtype)


# above this many K/V ELEMENTS (t*d) per head the whole-K/V-in-VMEM
# kernel would overflow VMEM (two t*d arrays + q/out blocks vs ~16MB);
# the blocked-grid kernel streams K/V instead. 512k elements = 2MB
# bf16 / 4MB f32 per array — comfortable with headroom.
_RESIDENT_TD_LIMIT = 8192 * 64


def flash_attention(q, k, v, causal: bool = False,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False):
    """q/k/v: [b, h, t, d] → [b, h, t, d]. t must divide by the block
    sizes after clamping (blocks clamp to t when t is smaller).

    Two schedules behind one entry point:
    - t*d <= ~512k elements: K/V live in VMEM per (bh, q-block)
      program and a fori_loop walks them (skipping fully-masked
      blocks when causal).
    - larger: the grid gains a k-block axis and K/V stream through
      VMEM block-by-block with the online-softmax accumulator in
      scratch — HBM-resident K/V, so sequence length is bounded by
      HBM, not VMEM. The matching backward
      (``_blockwise_attention_bwd``) scans K/V blocks the same way,
      so long-context TRAINING never materializes [t, t] either
      (verified: t=16k causal train steps on one v5e). Beyond one
      chip's HBM/FLOPs, shard the sequence with ring attention
      (``parallel.sequence``).
    """
    b, h, t, d = q.shape
    block_q = min(block_q, t)
    block_k = min(block_k, t)
    if not tiling.attention_blocks_ok(t, block_q, block_k):
        raise ValueError(
            f"sequence length {t} must be divisible by block sizes "
            f"({block_q}, {block_k})"
        )
    scale = 1.0 / (d ** 0.5)
    qr = q.reshape(b * h, t, d)
    kr = k.reshape(b * h, t, d)
    vr = v.reshape(b * h, t, d)
    if t * d <= _RESIDENT_TD_LIMIT:
        kernel = functools.partial(
            _attention_kernel, block_k=block_k, causal=causal,
            scale=scale,
        )
        out = pl.pallas_call(
            kernel,
            grid=(b * h, t // block_q),
            in_specs=[
                pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, t, d), lambda i, j: (i, 0, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, t, d), lambda i, j: (i, 0, 0),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec(
                (1, block_q, d), lambda i, j: (i, j, 0),
                memory_space=pltpu.VMEM,
            ),
            out_shape=jax.ShapeDtypeStruct((b * h, t, d), q.dtype),
            interpret=interpret,
            name=tiling.kernel_name("flash_attention_fwd", q.dtype, b=b,
                                    h=h, t=t, d=d),
        )(qr, kr, vr)
        return out.reshape(b, h, t, d)
    kernel = functools.partial(
        _attention_kernel_streamed, block_q=block_q, block_k=block_k,
        n_k=t // block_k, causal=causal, scale=scale,
    )
    out = pl.pallas_call(
        kernel,
        # k-blocks innermost: the scratch accumulator carries across
        # them and flushes on the last one
        grid=(b * h, t // block_q, t // block_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j, kk: (i, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, d), lambda i, j, kk: (i, kk, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, d), lambda i, j, kk: (i, kk, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(
            (1, block_q, d), lambda i, j, kk: (i, j, 0),
            memory_space=pltpu.VMEM,
        ),
        out_shape=jax.ShapeDtypeStruct((b * h, t, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        interpret=interpret,
        name=tiling.kernel_name("flash_attention_fwd_streamed", q.dtype,
                                b=b, h=h, t=t, d=d),
    )(qr, kr, vr)
    return out.reshape(b, h, t, d)


def _attention_kernel_streamed(q_ref, k_ref, v_ref, o_ref, acc, l, m,
                               *, block_q: int, block_k: int, n_k: int,
                               causal: bool, scale: float):
    """One program = one (bh, q-block, k-block) grid cell; the online
    softmax state lives in VMEM scratch across the k axis."""
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        l[...] = jnp.zeros_like(l)
        m[...] = jnp.full_like(m, 2.0 * _NEG)

    q_start = qi * block_q
    k_start = ki * block_k

    def _step():
        q = q_ref[0, :, :].astype(jnp.float32) * scale
        k_blk = k_ref[0, :, :].astype(jnp.float32)
        v_blk = v_ref[0, :, :].astype(jnp.float32)
        s = jnp.dot(q, k_blk.T, preferred_element_type=jnp.float32)
        if causal:
            q_pos = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, 1), 0
            )
            k_pos = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (1, block_k), 1
            )
            s = jnp.where(q_pos >= k_pos, s, _NEG)
        m_prev = m[...]
        m_blk = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_blk)
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l[...] = l[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc[...] = acc[...] * corr + jnp.dot(
            p, v_blk, preferred_element_type=jnp.float32
        )
        m[...] = m_new

    if causal:
        # skip k-blocks strictly after this q-block (fully masked)
        pl.when(k_start <= q_start + block_q - 1)(_step)
    else:
        _step()

    @pl.when(ki == n_k - 1)
    def _flush():
        o_ref[0, :, :] = (
            acc[...] / jnp.maximum(l[...], 1e-20)
        ).astype(o_ref.dtype)


# beyond this many timesteps the backward's hazard — the [t, t] score
# matrix the XLA-recompute path materializes (t^2 * 4B per (b, h):
# 16MB at t=2048, 1GB at t=16k) — outweighs the blockwise backward's
# extra QK^T sweep. Distinct from the forward's VMEM bound: the
# backward pressure is HBM and quadratic in t alone.
_BWD_MATERIALIZE_T_LIMIT = 2048


def _use_blockwise_bwd(t: int) -> bool:
    return t > _BWD_MATERIALIZE_T_LIMIT


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_diff(q, k, v, causal, interpret=False, block_q=128,
                block_k=128):
    """Differentiable wrapper: Pallas forward; backward is the XLA
    reference recompute at short sequences (cheapest to compile) and
    the blockwise flash backward beyond ``_BWD_MATERIALIZE_T_LIMIT``
    — O(t*block) memory instead of the [t, t] score matrix, so
    long-context TRAINING is HBM-bound like the forward.
    ``interpret`` exists for off-TPU tests of this exact path; the
    block sizes are nondiff arguments so tuned configs resolve OUTSIDE
    the vjp boundary (in ``mha``) and forward/backward agree."""
    return flash_attention(q, k, v, causal=causal, block_q=block_q,
                           block_k=block_k, interpret=interpret)


def _flash_fwd(q, k, v, causal, interpret=False, block_q=128,
               block_k=128):
    out = flash_attention(q, k, v, causal=causal, block_q=block_q,
                          block_k=block_k, interpret=interpret)
    # the recompute branch never reads `out`; saving it there would
    # pin an extra O(b*h*t*d) activation per layer for nothing
    keep = out if _use_blockwise_bwd(q.shape[2]) else None
    return out, (q, k, v, keep)


def _flash_bwd(causal, interpret, block_q, block_k, res, g):
    q, k, v, out = res
    if _use_blockwise_bwd(q.shape[2]):
        return _blockwise_attention_bwd(q, k, v, out, g, causal)
    from deeplearning4j_tpu.parallel.sequence import attention

    _, vjp = jax.vjp(
        lambda q_, k_, v_: attention(q_, k_, v_, causal=causal), q, k, v
    )
    return vjp(g)


_flash_diff.defvjp(_flash_fwd, _flash_bwd)


def _blockwise_attention_bwd(q, k, v, out, do, causal,
                             block_k: int = 512):
    """Flash-attention backward as a ``lax.scan`` over K/V blocks
    (Dao et al. 2022, in XLA rather than Pallas): per block it
    rebuilds P_b = exp(QK_b^T*scale - L) from a first logsumexp pass,
    then dV_b = P_b^T dO, dS_b = P_b*(dO V_b^T - D), dQ += dS_b K_b,
    dK_b = dS_b^T Q. Peak live memory is O(t*block_k) — the [t, t]
    matrix never materializes.

    Known (accepted) inefficiencies vs a fully tuned flash backward:
    the logsumexp is recomputed with one extra QK^T sweep (the
    forward kernel does not return its l/m scratch), and the causal
    path still computes fully-masked key blocks (a scan has static
    per-iteration shapes) — both trade FLOPs, never memory."""
    b, h, t, d = q.shape
    # shrink to a power-of-2 divisor: block_k = t would rebuild the
    # [t, t] intermediates this path exists to avoid
    block_k = tiling.pow2_divisor_leq(t, min(block_k, t))
    n_blk = t // block_k
    f32 = jnp.float32
    scale = 1.0 / (d ** 0.5)
    qf = q.astype(f32) * scale
    dof = do.astype(f32)
    q_pos = jnp.arange(t)[:, None]

    def mask_block(s, j):
        if not causal:
            return s
        k_pos = j * block_k + jnp.arange(block_k)[None, :]
        return jnp.where(q_pos >= k_pos, s, _NEG)

    # pass 1: per-row logsumexp L over all key blocks (O(t) carry)
    def lse_step(carry, j):
        m_run, l_run = carry
        k_blk = jax.lax.dynamic_slice_in_dim(
            k, j * block_k, block_k, axis=2
        ).astype(f32)
        s = mask_block(
            jnp.einsum("bhqd,bhkd->bhqk", qf, k_blk), j
        )
        m_blk = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_run, m_blk)
        l_run = l_run * jnp.exp(m_run - m_new) + jnp.sum(
            jnp.exp(s - m_new), axis=-1, keepdims=True
        )
        return (m_new, l_run), None

    m0 = jnp.full((b, h, t, 1), 2.0 * _NEG, f32)
    l0 = jnp.zeros((b, h, t, 1), f32)
    (m_fin, l_fin), _ = jax.lax.scan(
        lse_step, (m0, l0), jnp.arange(n_blk)
    )
    lse = m_fin + jnp.log(jnp.maximum(l_fin, 1e-20))

    # D_i = sum_j P_ij dP_ij = rowsum(dO * O)
    dvec = jnp.sum(dof * out.astype(f32), axis=-1, keepdims=True)

    # pass 2: per-block gradients; dQ accumulates, dK/dV stack
    def bwd_step(dq_acc, j):
        k_blk = jax.lax.dynamic_slice_in_dim(
            k, j * block_k, block_k, axis=2
        ).astype(f32)
        v_blk = jax.lax.dynamic_slice_in_dim(
            v, j * block_k, block_k, axis=2
        ).astype(f32)
        s = mask_block(
            jnp.einsum("bhqd,bhkd->bhqk", qf, k_blk), j
        )
        p = jnp.exp(s - lse)                       # [b,h,t,bk]
        dp = jnp.einsum("bhqd,bhkd->bhqk", dof, v_blk)
        ds = p * (dp - dvec)
        dq_acc = dq_acc + jnp.einsum(
            "bhqk,bhkd->bhqd", ds, k_blk
        )
        dk_blk = jnp.einsum("bhqk,bhqd->bhkd", ds, qf)
        dv_blk = jnp.einsum("bhqk,bhqd->bhkd", p, dof)
        return dq_acc, (dk_blk, dv_blk)

    dq0 = jnp.zeros((b, h, t, d), f32)
    dq, (dk_blocks, dv_blocks) = jax.lax.scan(
        bwd_step, dq0, jnp.arange(n_blk)
    )
    dk = jnp.moveaxis(dk_blocks, 0, 2).reshape(b, h, t, d)
    dv = jnp.moveaxis(dv_blocks, 0, 2).reshape(b, h, t, d)
    return (
        (dq * scale).astype(q.dtype),
        dk.astype(k.dtype),
        dv.astype(v.dtype),
    )


def _use_pallas() -> bool:
    from deeplearning4j_tpu.ops.dispatch import use_pallas

    return use_pallas()


def _attn_measure_factory(b, h, t, d, dtype, causal, interpret):
    def factory(cfg):
        bq, bk = cfg
        rng = np.random.RandomState(0)
        q = jnp.asarray(rng.standard_normal((b, h, t, d)), dtype)
        k = jnp.asarray(rng.standard_normal((b, h, t, d)), dtype)
        v = jnp.asarray(rng.standard_normal((b, h, t, d)), dtype)

        def run():
            out = flash_attention(q, k, v, causal=causal, block_q=bq,
                                  block_k=bk, interpret=interpret)
            jax.block_until_ready(out)
        return run
    return factory


def _resolve_attention_blocks(b, h, t, d, dtype, causal):
    """(block_q, block_k) for one dispatch: the historical 128s
    heuristic, or the autotuner's measured winner when tuning is
    active. Measurement runs in interpreter mode off-TPU (eager,
    outside any trace) regardless of how the dispatch itself lowers."""
    from deeplearning4j_tpu.ops.dispatch import pallas_interpret

    heur = tiling.pick_attention_blocks(t)
    if not autotune.tuning_active():
        return heur
    itemsize = jnp.dtype(dtype).itemsize
    factory = None
    if autotune.tuning_mode() == "on":
        factory = _attn_measure_factory(int(b), int(h), int(t), int(d),
                                        dtype, causal,
                                        pallas_interpret())
    got = autotune.resolve(
        "flash_attention",
        {"b": int(b), "h": int(h), "t": int(t), "d": int(d),
         "dtype": str(jnp.dtype(dtype)), "causal": bool(causal)},
        heur,
        tiling.attention_candidates(int(t), int(d), itemsize),
        lambda cfg: tiling.attention_candidate_cost(cfg, int(t),
                                                    int(d), itemsize),
        factory,
    )
    return int(got[0]), int(got[1])


def mha(q, k, v, causal: bool = False, mask=None):
    """Dispatching attention: the Pallas kernel where dispatch is on,
    no key mask is present and ``tiling.attention_seq_ok`` admits the
    sequence; XLA reference attention otherwise. The choice is made
    from what can be observed here (mask, sequence length, platform),
    once: a kernel error raises — it is never caught and answered by
    the reference, which would hide a refused kernel from whoever
    reads the numbers."""
    from deeplearning4j_tpu.ops.dispatch import pallas_interpret
    from deeplearning4j_tpu.parallel.sequence import attention

    b, h, t, d = q.shape
    if mask is None and _use_pallas() and tiling.attention_seq_ok(t):
        bq, bk = _resolve_attention_blocks(b, h, t, d, q.dtype, causal)
        return _flash_diff(q, k, v, causal, pallas_interpret(), bq, bk)
    return attention(q, k, v, causal=causal, mask=mask)
