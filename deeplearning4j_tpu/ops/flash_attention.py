"""Flash-attention Pallas kernels (SURVEY.md §2.3 native-component
checklist: "custom Pallas kernels where fusion matters").

The XLA fallback materializes the [t, t] score matrix in HBM between
the two matmuls; the forward kernel streams K/V through VMEM in blocks
with an online-softmax accumulator, so HBM traffic is O(t·d) instead
of O(t²) — the standard flash-attention scheme, with the MXU doing the
[BQ, d]×[d, BK] tiles on operands of the input dtype (bfloat16 stays
bfloat16) and float32 scores, statistics and accumulator. Numerics
match ``deeplearning4j_tpu.parallel.sequence.attention`` (same masking
convention) to ~1e-5 in float32.

Arrays are ``[b, t, h*d]`` in and out, head i in columns
``[i*d, (i+1)*d)``: what the q/k/v projections give and the output
projection takes, so nothing is transposed around the kernels. A
program takes the heads of one 128-lane column block (two at d = 64,
one where d is a multiple of 128) and picks each out with a lane
mask.

Training is a flash pair: under differentiation the forward kernel
also writes each row's logsumexp, and the backward is one fused kernel
(``flash_attention_bwd``) that rebuilds the probabilities tile by tile
from q, k and that logsumexp and accumulates dQ, dK, dV in float32 —
no score matrix in HBM, no second forward. Sequences too long for K/V
to sit in VMEM stream the forward and take the blockwise XLA scan
backward instead.

Dispatch: ``mha(q, k, v, n_heads, causal)`` uses the kernels on the
TPU backend (override with env DL4J_TPU_PALLAS=0/1); elsewhere, with
a key mask, and for heads that fill no 128-lane block it falls back
to the fused-by-XLA reference implementation."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.ops import autotune, tiling

_NEG = -1e9
_NT = (((1,), (1,)), ((), ()))  # a . b^T: contract the last dims
_TN = (((0,), (0,)), ((), ()))  # a^T . b: contract the first dims


def _loop(lo, hi, body, carry):
    """``fori_loop``, written out where the bounds are Python ints and
    few: the compiler then schedules across the steps."""
    if isinstance(lo, int) and isinstance(hi, int) and hi - lo <= 4:
        for i in range(lo, hi):
            carry = body(i, carry)
        return carry
    return jax.lax.fori_loop(lo, hi, body, carry)


def _visible_blocks(first, size, block, n_blocks):
    """Causal bounds of the rows ``[first, first + size)`` over blocks
    of ``block`` positions on the other axis: ``(n_clear, n_seen)`` —
    blocks below ``n_clear`` lie wholly at or before ``first`` (no
    mask), blocks from ``n_seen`` on wholly after the last row
    (skipped). int32 throughout: a Python-int divisor would promote
    to int64 when x64 is globally enabled."""
    if isinstance(first, int):
        return (min(n_blocks, (first + 1) // block),
                min(n_blocks, (first + size + block - 1) // block))
    i32 = functools.partial(jnp.asarray, dtype=jnp.int32)
    return (jnp.minimum(i32(n_blocks), (first + i32(1)) // i32(block)),
            jnp.minimum(i32(n_blocks),
                        (first + i32(size + block - 1)) // i32(block)))


def _own_lanes(w: int, d: int, head: int):
    """[1, w] bool: the columns of head ``head`` in a program's block
    of ``w // d`` heads; ``None`` where the block is one head."""
    if w == d:
        return None
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, w), 1)
    return (lane >= head * d) & (lane < (head + 1) * d)


def _only(own, x):
    """``x`` with the other heads' lanes zeroed (``own`` None: one
    head, nothing to zero)."""
    return x if own is None else jnp.where(own, x, jnp.zeros_like(x))


def _as_row(col):
    """An [n, 1] float32 column as a [1, n] row, the sequence on
    lanes: [b*h, 1, t] in HBM is t*4 bytes a slice, a trailing dim of
    1 would be padded to 128 lanes. A transpose of the broadcast
    column was the cheapest way round on the chip (0.24 ms a call
    under a reshape at [64, 8, 512, 64]: PERF.md §6, PR 32)."""
    return jnp.transpose(
        jnp.broadcast_to(col, (col.shape[0], 128)))[0:1, :]


def _attention_kernel(q_ref, k_ref, v_ref, o_ref, *lse_ref, d: int,
                      block_k: int, n_q: int, causal: bool, scale: float):
    """One program handles one (batch, head group, q-block) tile of
    ``[b, t, h*d]`` arrays: q_ref/o_ref [1, BQ, W], k_ref/v_ref
    [1, t, W] resident in VMEM, W = max(d, 128) columns holding
    ``W // d`` heads, handled one after the other. The 128-lane
    operands stay whole: a head's scores are ``q . k^T`` with the
    other heads' lanes of q zeroed (exact: the added terms are zeros
    in a float32 accumulation, and at d = 64 the contraction fills
    the MXU's depth where a 64-lane one filled half), and of the W
    columns ``p . v`` gives, the head's own are kept by a select.
    K/V are consumed in block_k chunks with the online softmax: the
    chunks wholly before the q-block without a mask, those on the
    diagonal with one, those after it not at all. MXU operands stay
    in the input dtype; scores, statistics and the accumulator are
    float32. ``lse_ref`` ([W // d, 1, BQ] float32, the differentiated
    path only) takes each row's logsumexp ``m + log l``, a head a
    row."""
    _, bq, w = q_ref.shape
    t = k_ref.shape[1]
    n_blocks = t // block_k
    # a single q-block has static loop bounds
    qi = 0 if n_q == 1 else pl.program_id(2)
    if causal:
        n_clear, n_seen = _visible_blocks(qi * bq, bq, block_k, n_blocks)
    else:
        n_clear = n_seen = n_blocks
    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
    out = None
    for head in range(w // d):
        own = _own_lanes(w, d, head)
        q = _only(own, q_ref[0]) * scale

        def step(masked, j, carry, q=q):
            o, l, m = carry
            start = j * block_k
            if not isinstance(start, int):
                start = pl.multiple_of(start, block_k)
            k_blk = k_ref[0, pl.ds(start, block_k), :]
            v_blk = v_ref[0, pl.ds(start, block_k), :]
            s = jax.lax.dot_general(q, k_blk, _NT,
                                    preferred_element_type=jnp.float32)
            if masked:
                k_pos = start + jax.lax.broadcasted_iota(
                    jnp.int32, (1, block_k), 1
                )
                s = jnp.where(q_pos >= k_pos, s, _NEG)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
            o_new = o * corr + jnp.dot(
                p.astype(v_blk.dtype), v_blk,
                preferred_element_type=jnp.float32,
            )
            return o_new, l_new, m_new

        carry = (jnp.zeros((bq, w), jnp.float32),
                 jnp.zeros((bq, 1), jnp.float32),
                 jnp.full((bq, 1), 2.0 * _NEG, jnp.float32))
        carry = _loop(0, n_clear, functools.partial(step, False), carry)
        o, l, m = _loop(n_clear, n_seen, functools.partial(step, True),
                        carry)
        l = jnp.maximum(l, 1e-20)
        o = o * (1.0 / l)
        out = o if out is None else jnp.where(own, o, out)
        if lse_ref:
            lse_ref[0][head] = _as_row(m + jnp.log(l))
    o_ref[0] = out.astype(o_ref.dtype)


# K or V of one program may hold this much VMEM for the resident
# schedule: t rows of W = max(d, 128) lanes (a program's column block;
# two heads at d = 64), two buffers each, so the pair takes 8 of the
# 16 MiB a kernel gets — t = 8192 in bfloat16 and 4096 in float32 at
# W = 128, which is where the chip's compiler stops accepting the
# kernel. Beyond it the blocked-grid kernel streams K/V instead.
_RESIDENT_KV_BYTES = 2 * 2 ** 20


def _resident(t: int, d: int, dtype) -> bool:
    return (t * tiling.attention_block_width(d)
            * jnp.dtype(dtype).itemsize <= _RESIDENT_KV_BYTES)


def _fwd_vmem_request(t: int, d: int, dtype, block_q: int, block_k: int):
    """The ``vmem_limit_bytes`` the forward kernel asks for
    (``tiling.attention_fwd_vmem_request`` on the resident schedule),
    ``None`` where it takes the compiler's default: the streamed
    schedule holds K/V a block at a time."""
    if not _resident(t, d, dtype):
        return None
    return tiling.attention_fwd_vmem_request(
        t, d, jnp.dtype(dtype).itemsize, min(block_q, t), min(block_k, t))


def flash_attention(q, k, v, n_heads: int, causal: bool = False,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False, with_lse: bool = False):
    """q/k/v: [b, t, h*d] → [b, t, h*d], the layout the q/k/v
    projections give and the output projection takes: head i is
    columns ``[i*d, (i+1)*d)``, and no array is transposed on the way
    in or out. A program handles the ``tiling.attention_heads_per_
    program`` heads one 128-lane column block holds (two at d = 64,
    one where d is a multiple of 128); a head size with no such block
    raises (``mha`` routes it to XLA). t must divide by the block
    sizes after clamping (blocks clamp to t when t is smaller).

    Two schedules behind one entry point:
    - K and V of a program up to 2 MiB each (``_resident``): they
      live in VMEM per (batch, head group, q-block) program and a loop walks them (skipping
      fully-masked blocks when causal). ``with_lse`` (this schedule
      only) also returns each row's logsumexp, float32 [b*h, 1, t]:
      what the backward kernel rebuilds the probabilities from.
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
    b, t, f = q.shape
    h = n_heads
    d = f // h
    g = tiling.attention_heads_per_program(h, d)
    if g is None or h * d != f:
        raise ValueError(
            f"{h} heads of size {d} fill no 128-lane column block of "
            f"[b, t, {f}]"
        )
    w = g * d
    block_q = min(block_q, t)
    block_k = min(block_k, t)
    if not tiling.attention_blocks_ok(t, block_q, block_k):
        raise ValueError(
            f"sequence length {t} must be divisible by block sizes "
            f"({block_q}, {block_k})"
        )
    scale = 1.0 / (d ** 0.5)
    n_g = h // g
    out_shape = jax.ShapeDtypeStruct((b, t, f), q.dtype)
    if _resident(t, d, q.dtype):
        n_q = t // block_q
        kernel = functools.partial(
            _attention_kernel, d=d, block_k=block_k, n_q=n_q,
            causal=causal, scale=scale,
        )
        q_spec = pl.BlockSpec((1, block_q, w), lambda i, gi, j: (i, j, gi),
                              memory_space=pltpu.VMEM)
        kv_spec = pl.BlockSpec((1, t, w), lambda i, gi, j: (i, 0, gi),
                               memory_space=pltpu.VMEM)
        out_specs = [q_spec]
        out_shapes = [out_shape]
        if with_lse:
            out_specs.append(pl.BlockSpec(
                (g, 1, block_q), lambda i, gi, j: (i * n_g + gi, 0, j),
                memory_space=pltpu.VMEM))
            out_shapes.append(
                jax.ShapeDtypeStruct((b * h, 1, t), jnp.float32))
        # residents past the default budget ask for what they need;
        # every other call takes the compiler's default
        vmem = _fwd_vmem_request(t, d, q.dtype, block_q, block_k)
        params = {} if vmem is None else {
            "compiler_params": pltpu.CompilerParams(vmem_limit_bytes=vmem)}
        out, *lse = pl.pallas_call(
            kernel,
            grid=(b, n_g, n_q),
            in_specs=[q_spec, kv_spec, kv_spec],
            out_specs=out_specs,
            out_shape=out_shapes,
            interpret=interpret,
            **params,
            name=tiling.kernel_name("flash_attention_fwd", q.dtype, b=b,
                                    h=h, t=t, d=d),
        )(q, k, v)
        return (out, lse[0]) if with_lse else out
    if with_lse:
        raise ValueError("the streamed schedule hands out no logsumexp")
    kernel = functools.partial(
        _attention_kernel_streamed, d=d, block_q=block_q,
        block_k=block_k, n_k=t // block_k, causal=causal, scale=scale,
    )
    q_spec = pl.BlockSpec((1, block_q, w), lambda i, gi, j, kk: (i, j, gi),
                          memory_space=pltpu.VMEM)
    kv_spec = pl.BlockSpec((1, block_k, w),
                           lambda i, gi, j, kk: (i, kk, gi),
                           memory_space=pltpu.VMEM)
    return pl.pallas_call(
        kernel,
        # k-blocks innermost: the scratch accumulators carry across
        # them and flush on the last one
        grid=(b, n_g, t // block_q, t // block_k),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        out_shape=out_shape,
        # a head has scratch of its own
        scratch_shapes=[
            pltpu.VMEM((g, block_q, w), jnp.float32),
            pltpu.VMEM((g, block_q, 1), jnp.float32),
            pltpu.VMEM((g, block_q, 1), jnp.float32),
        ],
        interpret=interpret,
        name=tiling.kernel_name("flash_attention_fwd_streamed", q.dtype,
                                b=b, h=h, t=t, d=d),
    )(q, k, v)


def _attention_kernel_streamed(q_ref, k_ref, v_ref, o_ref, acc, l, m,
                               *, d: int, block_q: int, block_k: int,
                               n_k: int, causal: bool, scale: float):
    """One program = one (batch, head group, q-block, k-block) grid
    cell; each head's online softmax state lives in VMEM scratch of
    its own across the k axis. Heads are picked out of the 128-lane
    block as in ``_attention_kernel``."""
    w = q_ref.shape[2]
    heads = w // d
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        l[...] = jnp.zeros_like(l)
        m[...] = jnp.full_like(m, 2.0 * _NEG)

    q_start = qi * block_q
    k_start = ki * block_k

    def _step():
        k_blk = k_ref[0, :, :].astype(jnp.float32)
        v_blk = v_ref[0, :, :].astype(jnp.float32)
        for head in range(heads):
            q = _only(_own_lanes(w, d, head),
                      q_ref[0, :, :]).astype(jnp.float32) * scale
            s = jnp.dot(q, k_blk.T, preferred_element_type=jnp.float32)
            if causal:
                q_pos = q_start + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, 1), 0
                )
                k_pos = k_start + jax.lax.broadcasted_iota(
                    jnp.int32, (1, block_k), 1
                )
                s = jnp.where(q_pos >= k_pos, s, _NEG)
            m_prev = m[head]
            m_blk = jnp.max(s, axis=-1, keepdims=True)
            m_new = jnp.maximum(m_prev, m_blk)
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l[head] = l[head] * corr + jnp.sum(p, axis=-1, keepdims=True)
            acc[head] = acc[head] * corr + jnp.dot(
                p, v_blk, preferred_element_type=jnp.float32
            )
            m[head] = m_new

    if causal:
        # skip k-blocks strictly after this q-block (fully masked)
        pl.when(k_start <= q_start + block_q - 1)(_step)
    else:
        _step()

    @pl.when(ki == n_k - 1)
    def _flush():
        out = None
        for head in range(heads):
            o = acc[head] / jnp.maximum(l[head], 1e-20)
            out = o if out is None else jnp.where(
                _own_lanes(w, d, head), o, out)
        o_ref[0, :, :] = out.astype(o_ref.dtype)


def _attention_bwd_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                          dq_ref, dk_ref, dv_ref, qs, dq_acc, dvec, *,
                          d: int, block_q: int, block_k: int, causal: bool,
                          scale: float):
    """One program handles one (batch, head group) slice of
    ``[b, t, h*d]`` arrays: all of q, k, v, O, dO [1, t, W] (W =
    max(d, 128) columns, ``W // d`` heads) and each head's rows'
    logsumexp [W // d, 1, t] sit in VMEM. It first writes each head's
    D = rowsum(dO*O) beside the logsumexp, the sequence on lanes
    (scratch ``dvec``, float32: as an XLA reduction over
    ``[b, t, h, d]`` into ``[b, h, t]`` it cost a transposing copy of
    both operands). Per key block and head it walks the query blocks at or after it (all of
    them when not causal), rebuilds the probabilities of one [BK, BQ]
    tile from q, k and the logsumexp — transposed, keys on sublanes,
    so that the per-query statistics broadcast as rows — and
    accumulates dV and dK in float32 values, dQ in the float32
    scratch ``dq_acc``. The operands keep their 128 lanes: k and v
    with the other heads' lanes zeroed give the head's scores, dP and
    dQ exactly (dQ zero in the other heads' columns, so every head
    adds into the one ``dq_acc``), and of the W columns of ``p^T .
    dO`` and ``dS^T . q`` the head's own are kept by a select. The
    scratches are written once before and read once after all heads,
    as with one head a program: nothing is flushed and reused between
    heads (PERF.md §6, PR 32: a shared scratch reused across slices
    was wrong on the chip). Only the tiles the diagonal crosses are
    masked; those after it are never computed. MXU operands are in
    the input dtype; scores, probabilities and dS are float32 until
    they enter a product."""
    _, t, w = q_ref.shape
    n_q, n_k = t // block_q, t // block_k
    dt = q_ref.dtype
    qs[...] = q_ref[0] * scale
    dq_acc[...] = jnp.zeros_like(dq_acc)

    def d_rows(i, _):
        q_start = i * block_q
        if not isinstance(q_start, int):
            q_start = pl.multiple_of(q_start, block_q)
        rows = pl.ds(q_start, block_q)
        prod = (do_ref[0, rows, :].astype(jnp.float32)
                * o_ref[0, rows, :].astype(jnp.float32))
        for head in range(w // d):
            col = jnp.sum(_only(_own_lanes(w, d, head), prod), axis=-1,
                          keepdims=True)
            dvec[head, :, rows] = _as_row(col)

    _loop(0, n_q, d_rows, None)

    def key_block(j, _):
        k_start = j * block_k
        if not isinstance(k_start, int):
            k_start = pl.multiple_of(k_start, block_k)
        keys = pl.ds(k_start, block_k)
        k_pos = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_k, 1), 0
        )
        if causal:
            # a query sees the keys at or before it, so from the keys'
            # side the bounds are those of the positions one earlier:
            # the query blocks that end before this key block are
            # skipped, those the diagonal crosses masked
            n_skip, n_cross = _visible_blocks(
                k_start - 1, block_k, block_q, n_q)
        else:
            n_skip = n_cross = 0
        dk_all = dv_all = None
        for head in range(w // d):
            own = _own_lanes(w, d, head)
            k_blk = _only(own, k_ref[0, keys, :])
            v_blk = _only(own, v_ref[0, keys, :])

            def query_block(masked, i, carry, head=head, k_blk=k_blk,
                            v_blk=v_blk):
                dk, dv = carry
                q_start = i * block_q
                if not isinstance(q_start, int):
                    q_start = pl.multiple_of(q_start, block_q)
                rows = pl.ds(q_start, block_q)
                q_blk = qs[rows, :]
                do_blk = do_ref[0, rows, :]
                s = jax.lax.dot_general(
                    k_blk, q_blk, _NT, preferred_element_type=jnp.float32
                )
                if masked:
                    q_pos = q_start + jax.lax.broadcasted_iota(
                        jnp.int32, (1, block_q), 1
                    )
                    s = jnp.where(q_pos >= k_pos, s, _NEG)
                p = jnp.exp(s - lse_ref[head, :, rows])
                dv = dv + jnp.dot(p.astype(dt), do_blk,
                                  preferred_element_type=jnp.float32)
                dp = jax.lax.dot_general(
                    v_blk, do_blk, _NT, preferred_element_type=jnp.float32
                )
                ds = (p * (dp - dvec[head, :, rows])).astype(dt)
                dk = dk + jnp.dot(ds, q_blk,
                                  preferred_element_type=jnp.float32)
                dq_acc[rows, :] += jax.lax.dot_general(
                    ds, k_blk, _TN, preferred_element_type=jnp.float32
                )
                return dk, dv

            carry = (jnp.zeros((block_k, w), jnp.float32),) * 2
            carry = _loop(n_skip, n_cross,
                          functools.partial(query_block, True), carry)
            dk, dv = _loop(n_cross, n_q,
                           functools.partial(query_block, False), carry)
            if dk_all is None:
                dk_all, dv_all = dk, dv
            else:
                dk_all = jnp.where(own, dk, dk_all)
                dv_all = jnp.where(own, dv, dv_all)
        dk_ref[0, keys, :] = dk_all.astype(dk_ref.dtype)
        dv_ref[0, keys, :] = dv_all.astype(dv_ref.dtype)

    _loop(0, n_k, key_block, None)
    dq_ref[0] = (dq_acc[...] * scale).astype(dq_ref.dtype)


def flash_attention_bwd(q, k, v, out, lse, do, n_heads: int, causal: bool,
                        block_q: int, block_k: int,
                        interpret: bool = False):
    """(dq, dk, dv) of ``flash_attention`` from its inputs, its output
    (all [b, t, h*d]), the rows' logsumexp it handed out ([b*h, 1, t]
    float32) and the output's cotangent: one fused kernel, the score
    matrix lives a [block_k, block_q] tile at a time in VMEM."""
    b, t, f = q.shape
    h = n_heads
    d = f // h
    g = tiling.attention_heads_per_program(h, d)
    w, n_g = g * d, h // g
    block_q = min(block_q, t)
    block_k = min(block_k, t)
    kernel = functools.partial(
        _attention_bwd_kernel, d=d, block_q=block_q, block_k=block_k,
        causal=causal, scale=1.0 / (d ** 0.5),
    )
    whole = pl.BlockSpec((1, t, w), lambda i, gi: (i, 0, gi),
                         memory_space=pltpu.VMEM)
    row = pl.BlockSpec((g, 1, t), lambda i, gi: (i * n_g + gi, 0, 0),
                       memory_space=pltpu.VMEM)
    like = jax.ShapeDtypeStruct((b, t, f), q.dtype)
    return tuple(pl.pallas_call(
        kernel,
        grid=(b, n_g),
        in_specs=[whole, whole, whole, whole, whole, row],
        out_specs=[whole, whole, whole],
        out_shape=[like, like, like],
        scratch_shapes=[
            pltpu.VMEM((t, w), q.dtype),
            pltpu.VMEM((t, w), jnp.float32),
            pltpu.VMEM((g, 1, t), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=tiling.ATTENTION_BWD_VMEM_BYTES),
        interpret=interpret,
        name=tiling.kernel_name("flash_attention_bwd", q.dtype, b=b, h=h,
                                t=t, d=d),
    )(q, k, v, out, do, lse))


def _pair_ok(t: int, d: int, dtype, block_q: int, block_k: int) -> bool:
    """Whether the differentiated call runs as the flash pair: the
    forward's resident schedule (the one that hands out the
    logsumexp) and a backward whose residents fit VMEM."""
    return _resident(t, d, dtype) and tiling.attention_bwd_fits(
        t, d, jnp.dtype(dtype).itemsize, min(block_q, t),
        min(block_k, t))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_diff(q, k, v, n_heads, causal, interpret=False, block_q=128,
                block_k=128):
    """Differentiable wrapper, a flash pair where ``_pair_ok``: the
    forward kernel also hands out each row's logsumexp, and the
    backward is the fused Pallas kernel that rebuilds the
    probabilities tile by tile from q, k and that logsumexp — the
    [t, t] score matrix never reaches HBM and the forward is not
    computed twice. Beyond it (K/V too long to sit in VMEM) the
    forward streams and the backward is the blockwise XLA scan, which
    never materializes [t, t] either. Arrays and cotangents are
    [b, t, h*d] throughout. ``interpret`` exists for off-TPU tests of
    this exact path; the block sizes are nondiff arguments so tuned
    configs resolve OUTSIDE the vjp boundary (in ``mha``) and
    forward/backward agree."""
    return flash_attention(q, k, v, n_heads, causal=causal,
                           block_q=block_q, block_k=block_k,
                           interpret=interpret)


def _flash_fwd(q, k, v, n_heads, causal, interpret=False, block_q=128,
               block_k=128):
    t = q.shape[1]
    pair = _pair_ok(t, q.shape[2] // n_heads, q.dtype, block_q, block_k)
    got = flash_attention(q, k, v, n_heads, causal=causal,
                          block_q=block_q, block_k=block_k,
                          interpret=interpret, with_lse=pair)
    out, lse = got if pair else (got, None)
    return out, (q, k, v, out, lse)


def _flash_bwd(n_heads, causal, interpret, block_q, block_k, res, g):
    from deeplearning4j_tpu.ops.dispatch import note_dispatch

    q, k, v, out, lse = res
    if lse is None:
        note_dispatch("flash_attention_bwd", "xla")
        return _blockwise_attention_bwd(q, k, v, out, g, n_heads, causal)
    note_dispatch("flash_attention_bwd",
                  "interpret" if interpret else "pallas")
    return flash_attention_bwd(q, k, v, out, lse, g, n_heads, causal,
                               block_q, block_k, interpret)


_flash_diff.defvjp(_flash_fwd, _flash_bwd)


def _blockwise_attention_bwd(q, k, v, out, do, n_heads, causal,
                             block_k: int = 512):
    """Flash-attention backward as a ``lax.scan`` over K/V blocks
    (Dao et al. 2022, in XLA rather than Pallas): per block it
    rebuilds P_b = exp(QK_b^T*scale - L) from a first logsumexp pass,
    then dV_b = P_b^T dO, dS_b = P_b*(dO V_b^T - D), dQ += dS_b K_b,
    dK_b = dS_b^T Q. Peak live memory is O(t*block_k) — the [t, t]
    matrix never materializes. Arrays are [b, t, h*d] like the
    kernels'; the products contract them as [b, t, h, d] (a reshape,
    nothing moves).

    Runs behind the streamed forward only (``_flash_diff``); where
    K/V fit VMEM the fused kernel ``flash_attention_bwd`` does this
    work. Known (accepted) inefficiencies vs that kernel: the
    logsumexp is recomputed with one extra QK^T sweep (the streamed
    forward does not return its l/m scratch), and the causal
    path still computes fully-masked key blocks (a scan has static
    per-iteration shapes) — both trade FLOPs, never memory."""
    b, t, f = q.shape
    h = n_heads
    d = f // h
    # shrink to a power-of-2 divisor: block_k = t would rebuild the
    # [t, t] intermediates this path exists to avoid
    block_k = tiling.pow2_divisor_leq(t, min(block_k, t))
    n_blk = t // block_k
    f32 = jnp.float32
    scale = 1.0 / (d ** 0.5)
    heads = lambda a: a.reshape(b, t, h, d)  # noqa: E731
    qf = heads(q).astype(f32) * scale
    dof = heads(do).astype(f32)
    k, v = heads(k), heads(v)
    q_pos = jnp.arange(t)[:, None]

    def mask_block(s, j):
        if not causal:
            return s
        k_pos = j * block_k + jnp.arange(block_k)[None, :]
        return jnp.where(q_pos >= k_pos, s, _NEG)

    def block(a, j):
        return jax.lax.dynamic_slice_in_dim(
            a, j * block_k, block_k, axis=1).astype(f32)

    # pass 1: per-row logsumexp L over all key blocks (O(t) carry)
    def lse_step(carry, j):
        m_run, l_run = carry
        s = mask_block(
            jnp.einsum("bqhd,bkhd->bhqk", qf, block(k, j)), j
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

    # D_i = sum_j P_ij dP_ij = rowsum(dO * O), [b, h, t, 1]
    dvec = jnp.sum(dof * heads(out).astype(f32), axis=-1)
    dvec = jnp.transpose(dvec, (0, 2, 1))[..., None]

    # pass 2: per-block gradients; dQ accumulates, dK/dV stack
    def bwd_step(dq_acc, j):
        k_blk, v_blk = block(k, j), block(v, j)
        s = mask_block(
            jnp.einsum("bqhd,bkhd->bhqk", qf, k_blk), j
        )
        p = jnp.exp(s - lse)                       # [b,h,t,bk]
        dp = jnp.einsum("bqhd,bkhd->bhqk", dof, v_blk)
        ds = p * (dp - dvec)
        dq_acc = dq_acc + jnp.einsum(
            "bhqk,bkhd->bqhd", ds, k_blk
        )
        dk_blk = jnp.einsum("bhqk,bqhd->bkhd", ds, qf)
        dv_blk = jnp.einsum("bhqk,bqhd->bkhd", p, dof)
        return dq_acc, (dk_blk, dv_blk)

    dq0 = jnp.zeros((b, t, h, d), f32)
    dq, (dk_blocks, dv_blocks) = jax.lax.scan(
        bwd_step, dq0, jnp.arange(n_blk)
    )
    # [n_blk, b, block_k, h, d] -> [b, t, h*d]
    dk = jnp.moveaxis(dk_blocks, 0, 1).reshape(b, t, f)
    dv = jnp.moveaxis(dv_blocks, 0, 1).reshape(b, t, f)
    return (
        (dq * scale).reshape(b, t, f).astype(q.dtype),
        dk.astype(k.dtype),
        dv.astype(v.dtype),
    )


def _attn_measure_factory(b, h, t, d, dtype, causal, interpret):
    def factory(cfg):
        bq, bk = cfg
        rng = np.random.RandomState(0)
        q = jnp.asarray(rng.standard_normal((b, t, h * d)), dtype)
        k = jnp.asarray(rng.standard_normal((b, t, h * d)), dtype)
        v = jnp.asarray(rng.standard_normal((b, t, h * d)), dtype)

        def run():
            out = flash_attention(q, k, v, h, causal=causal, block_q=bq,
                                  block_k=bk, interpret=interpret)
            jax.block_until_ready(out)
        return run
    return factory


def _resolve_attention_blocks(b, h, t, d, dtype, causal):
    """(block_q, block_k) for one dispatch: the heuristic the chip's
    readings set (``tiling.pick_attention_blocks``), or the
    autotuner's measured winner when tuning is active. Measurement
    runs in interpreter mode off-TPU (eager, outside any trace)
    regardless of how the dispatch itself lowers."""
    from deeplearning4j_tpu.ops.dispatch import pallas_interpret

    itemsize = jnp.dtype(dtype).itemsize
    heur = tiling.pick_attention_blocks(int(t), int(d), itemsize)
    if not autotune.tuning_active():
        return heur
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


def _note_blocks(t: int, d: int, dtype, block_q: int, block_k: int) -> None:
    """``flash_attention_blocks_total{d, block_q, block_k, vmem}``: one
    per traced call that takes the kernels, ``vmem`` the MiB its
    forward asks for or ``default``, as ``pallas_dispatch_total``
    counts routing decisions."""
    from deeplearning4j_tpu.observability.metrics import default_registry

    vmem = _fwd_vmem_request(t, d, dtype, block_q, block_k)
    default_registry().counter(
        "flash_attention_blocks_total",
        help="flash attention calls traced, by head size, the blocks "
             "resolved and the VMEM the forward asks for",
        labels=("d", "block_q", "block_k", "vmem"),
    ).labels(d=str(d), block_q=str(block_q), block_k=str(block_k),
             vmem="default" if vmem is None else str(vmem // 2 ** 20)
             ).inc()


def mha(q, k, v, n_heads: int, causal: bool = False, mask=None):
    """Dispatching attention on [b, t, h*d] arrays — what the q/k/v
    projections give and the output projection takes, head i being
    columns ``[i*d, (i+1)*d)``: the Pallas kernels where dispatch is
    on, no key mask is present, ``tiling.attention_seq_ok`` admits the
    sequence and the heads fill 128-lane column blocks
    (``tiling.attention_heads_per_program``) — forward alone the
    kernel with one output, under differentiation the flash pair
    (``_flash_diff``), and no array is transposed on the way in or
    out; XLA reference attention otherwise, which moves to
    [b, h, t, d] and back here, at its own entry. The choice is made
    from what can be observed here (mask, sequence length, head size
    and count, platform), once, and counted
    (``pallas_dispatch_total{kernel="flash_attention"}`` per traced
    call, ``flash_attention_bwd`` when its backward is traced; the
    kernels' blocks under ``flash_attention_blocks_total``): a
    kernel error raises — it is never caught and answered by the
    reference, which would hide a refused kernel from whoever reads
    the numbers."""
    from deeplearning4j_tpu.ops.dispatch import pallas_interpret, route
    from deeplearning4j_tpu.parallel.sequence import (
        attention,
        merge_heads,
        split_heads,
    )

    b, t, f = q.shape
    h = n_heads
    d = f // h
    if route("flash_attention",
             mask is None and tiling.attention_seq_ok(t)
             and tiling.attention_heads_per_program(h, d) is not None):
        bq, bk = _resolve_attention_blocks(b, h, t, d, q.dtype, causal)
        _note_blocks(t, d, q.dtype, bq, bk)
        return _flash_diff(q, k, v, h, causal, pallas_interpret(), bq, bk)
    return merge_heads(attention(
        *(split_heads(a, h) for a in (q, k, v)), causal=causal, mask=mask))
