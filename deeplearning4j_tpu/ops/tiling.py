"""Shared block-size selection for the Pallas kernel library.

One module owns every tiling decision the kernels make — the VMEM
budget constant, the divisor heuristics that used to be copy-pasted
into ``matmul_block``/``lstm_cell``, and the candidate
enumeration the autotuner (``ops/autotune.py``) searches over. The
heuristic pickers and the candidate enumerators share the same
feasibility formulas, so the heuristic and the measured search can
never disagree about what fits.

Every block a picker or an enumerator returns obeys Mosaic's
block-shape rule (``block_dim_ok``): the last two dims of a BlockSpec
are multiples of the (8, 128) tile or the whole extent. A shape with
no such block gets ``None`` / no candidates, which the kernels'
``*_ok`` predicates report as ineligible — the call site then takes
XLA, decided from the shape, never by catching the compiler.

``scripts/lint_parity.py`` enforces the locality: kernel modules under
``ops/`` may not carry inline divisor math — block selection goes
through this module (or the autotuner, which enumerates from it).

Per-candidate cost priors: each ``*_candidate_cost`` returns a
``(flops, bytes)`` pair modeling the candidate's *scheduled* work —
MXU-padding waste (sublane multiples of 8, lane multiples of 128) and
the HBM refetch traffic implied by the kernel's grid/index maps. The
autotuner wraps these in the PR-15 ``CostModel`` record and ranks the
search by the prior; measurement decides the winner.
"""

from __future__ import annotations

import itertools
from typing import List, Optional, Tuple

import numpy as np

# A kernel may use 16 MiB of VMEM (the compiler's scoped limit on a
# v5e: "Ran out of memory in memory space vmem" past it); 3 MiB stay
# free for the compiler's own temporaries. THE single budget constant
# for every kernel's tiling but the flash pair's, whose calls may ask
# the compiler for more (``ATTENTION_BWD_VMEM_BYTES``). What a block
# costs against it is ``vmem_block_bytes`` — one place, measured against the chip's
# compiler, not a per-call estimate.
VMEM_BUDGET_BYTES = 13 * 2 ** 20

# lstm_sequence additionally requires the recurrent weight matrix to
# sit resident across all timesteps.
SEQ_RW_BYTES_MAX = 9 * 2 ** 20

# MXU geometry: output lanes come in 128s, sublanes in 8s — the cost
# priors charge candidates for the padding waste of partial tiles.
_LANES = 128
_SUBLANES = 8


def kernel_name(kernel_pass: str, dtype, **dims: int) -> str:
    """The ``name=`` of a ``pl.pallas_call``: ``<kernel>_<pass>``, the
    operand dtype, and the call's static shapes with each number
    before its letter — ``matmul_block_fwd_bfloat16_32768m_512k_256n``.
    XLA names the custom call after it (and appends ``.N``), so the
    device trace says which kernel and which pass an operation is; the
    name ends in a letter because readers that fold an operation's
    runs together strip trailing digits and dots."""
    tag = "_".join(f"{int(v)}{k}" for k, v in dims.items())
    return f"{kernel_pass}_{np.dtype(dtype).name}_{tag}"


def block_dim_ok(block: int, full: int, multiple: int) -> bool:
    """Mosaic's rule for one of the last two dims of a block: a
    multiple of the tile (``_SUBLANES`` for the second-to-last dim,
    ``_LANES`` for the last) or the whole extent. The chip's compiler
    refuses anything else ("block shape ... not a multiple of
    (8, 128)")."""
    return block == full or block % multiple == 0


def divisors_desc(v: int, cap: int) -> List[int]:
    return [d for d in range(min(v, cap), 0, -1) if v % d == 0]


def _legal_blocks_desc(n: int, cap: int, multiple: int) -> List[int]:
    """Divisors of ``n`` up to ``cap`` that ``block_dim_ok`` admits
    (``multiple``: ``_LANES`` for a block's last dim, ``_SUBLANES`` for
    its second-to-last), largest first; the whole extent (always
    legal) when no tile multiple under the cap divides ``n``."""
    return [d for d in divisors_desc(n, cap)
            if block_dim_ok(d, n, multiple)] or [n]


def pow2_divisor_leq(n: int, cap: int) -> int:
    """Largest power-of-two divisor of ``n`` that is <= cap (>= 1)."""
    p = 1
    while p * 2 <= cap and n % (p * 2) == 0:
        p *= 2
    return p


def _pad_up(v: int, mult: int) -> int:
    return ((v + mult - 1) // mult) * mult


def vmem_block_bytes(shape, itemsize: int, moves: bool = False) -> int:
    """VMEM one array of ``shape`` occupies. Its last two dims are
    padded to the dtype's tile — 128 lanes by 8 sublanes of 32 bits,
    so 16 rows of bf16: a 3-channel image costs what a 128-channel one
    does. ``moves``: a BlockSpec'd operand whose block index changes
    over the grid is double-buffered by the compiler's pipeline; one
    that stays put (and every value the kernel body holds) has one
    buffer. Calibrated on the chip's compiler: f32 ``[128, 2048] x
    [2048, bn]`` with the weight panel fixed compiles to 14 MiB of
    panel and is refused at 16; with the panel moving (two n-blocks)
    it compiles to 7 MiB and is refused at 8."""
    *lead, rows, cols = (int(v) for v in shape)
    sublanes = _SUBLANES * max(1, 4 // itemsize)
    n = _pad_up(rows, sublanes) * _pad_up(cols, _LANES) * itemsize
    for d in lead:
        n *= d
    return n * (2 if moves else 1)


# ---------------------------------------------------------------------------
# matmul_block
# ---------------------------------------------------------------------------


def _matmul_bytes(m, k, n, bm, bn, itemsize) -> int:
    """Residents per grid step: one [bm, K] row block, one [K, bn]
    weight panel, the f32 bias slice, the output block, and the f32
    accumulator the body holds."""
    m_moves, n_moves = m > bm, n > bn
    return (
        vmem_block_bytes((bm, k), itemsize, moves=m_moves)
        + vmem_block_bytes((k, bn), itemsize, moves=n_moves)
        + vmem_block_bytes((1, bn), 4, moves=n_moves)
        + vmem_block_bytes((bm, bn), itemsize, moves=m_moves or n_moves)
        + vmem_block_bytes((bm, bn), 4)
    )


def _matmul_blocks(m: int, k: int, n: int, itemsize: int, bm_cap: int,
                   bn_cap: int):
    """Legal (bm, bn) tiles that fit VMEM, larger row blocks first."""
    for bm in _legal_blocks_desc(m, bm_cap, _SUBLANES):
        for bn in _legal_blocks_desc(n, bn_cap, _LANES):
            if _matmul_bytes(m, k, n, bm, bn,
                             itemsize) <= VMEM_BUDGET_BYTES:
                yield bm, bn


def pick_matmul_blocks(m: int, k: int, n: int,
                       itemsize: int) -> Optional[Tuple[int, int]]:
    """(bm, bn) heuristic tile, or None when no tile fits VMEM."""
    return next(_matmul_blocks(m, k, n, itemsize, 256, 512), None)


def matmul_candidates(m: int, k: int, n: int, itemsize: int,
                      limit: int = 24) -> List[Tuple[int, int]]:
    return list(itertools.islice(
        _matmul_blocks(m, k, n, itemsize, 1024, 1024), limit))


def matmul_candidate_cost(cfg, m: int, k: int, n: int,
                          itemsize: int) -> Tuple[float, float]:
    """Prior for one (bm, bn): padded MXU work plus the weight-panel
    refetch traffic — the [K, bn] panel is re-fetched once per row
    block, so larger bm means less HBM traffic."""
    bm, bn = cfg
    tiles = (m // bm) * (n // bn)
    flops = tiles * 2.0 * _pad_up(bm, _SUBLANES) * k * _pad_up(bn, _LANES)
    bytes_ = (m * k * itemsize                  # x: once per row block
              + (m // bm) * k * n * itemsize    # w panels refetched
              + m * n * itemsize + n * 4)       # out + bias
    return flops, float(bytes_)


# ---------------------------------------------------------------------------
# lstm_sequence batch block
# ---------------------------------------------------------------------------


def _lstm_per_row_bytes(n: int, four_n: int, itemsize: int,
                        bwd: bool) -> int:
    if bwd:
        # xproj + dgates blocks + dz/z f32 temps on the 4n axis;
        # hprev/cprev/cseq/dhseq blocks + dh0/dc0 + scratches on n
        return (four_n * (2 * itemsize + 8)
                + n * (4 * itemsize + 4 * 4))
    return (four_n * (itemsize + 4)        # xproj block + z f32
            + n * (4 * 4 + 2 * itemsize))  # scratches + outs


def pick_lstm_batch_block(b: int, n: int, four_n: int, itemsize: int,
                          bwd: bool = False) -> Optional[int]:
    """Largest legal batch block DIVIDING b (the batch is the
    second-to-last dim of every block) that keeps the sequence
    kernel's VMEM residents under the budget. The backward kernel
    holds roughly twice the forward's per-row state, so it sizes with
    its own formula. None when no legal block fits (callers fall back
    to the per-step cell)."""
    rw_bytes = n * four_n * itemsize
    per_row = _lstm_per_row_bytes(n, four_n, itemsize, bwd)
    for bb in _legal_blocks_desc(b, b, _SUBLANES):
        if rw_bytes + bb * per_row <= VMEM_BUDGET_BYTES:
            return bb
    return None


def lstm_batch_candidates(b: int, n: int, four_n: int, itemsize: int,
                          bwd: bool = False,
                          limit: int = 16) -> List[Tuple[int]]:
    rw_bytes = n * four_n * itemsize
    per_row = _lstm_per_row_bytes(n, four_n, itemsize, bwd)
    out: List[Tuple[int]] = []
    for bb in _legal_blocks_desc(b, b, _SUBLANES):
        if rw_bytes + bb * per_row <= VMEM_BUDGET_BYTES:
            out.append((bb,))
        if len(out) >= limit:
            break
    return out


def lstm_candidate_cost(cfg, b: int, n: int, four_n: int, seq_len: int,
                        itemsize: int) -> Tuple[float, float]:
    """Prior for one (bb,): the recurrent matmul padded to sublane
    multiples per (batch-block, timestep) grid cell; RW's index map is
    constant so its traffic is block-independent."""
    (bb,) = cfg
    tiles = (b // bb) * max(1, seq_len)
    flops = tiles * 2.0 * _pad_up(bb, _SUBLANES) * n * _pad_up(four_n,
                                                               _LANES)
    bytes_ = (n * four_n * itemsize
              + max(1, seq_len) * b * four_n * itemsize
              + max(1, seq_len) * b * n * 2 * itemsize)
    return flops, float(bytes_)


# ---------------------------------------------------------------------------
# flash_attention blocks
# ---------------------------------------------------------------------------


def attention_seq_ok(t: int) -> bool:
    """The dispatch eligibility the ``mha`` entry point applies: the
    sequence must divide by the default (clamped) block size."""
    return t >= 8 and t % min(128, t) == 0


def attention_blocks_ok(t: int, block_q: int, block_k: int) -> bool:
    """Divisibility feasibility after clamping — the check the kernel
    entry raises on."""
    return t % block_q == 0 and t % block_k == 0


def attention_block_width(d: int) -> int:
    """Columns W of ``[b, t, h*d]`` one program of the flash kernels
    reads: a block's last dim is a multiple of 128 lanes."""
    return max(d, _LANES)


def attention_heads_per_program(h: int, d: int) -> Optional[int]:
    """Heads in one program's column block of ``[b, t, h*d]``:
    ``128 // d`` where the head size divides 128 and the heads come
    in whole groups of that many (two at d = 64), one where it is a
    multiple of 128. ``None``: no block of whole heads satisfies
    Mosaic's rule for a block's last dim, and the call takes XLA's
    attention."""
    if d > 0 and d % _LANES == 0:
        return 1
    if d > 0 and _LANES % d == 0 and h % (_LANES // d) == 0:
        return _LANES // d
    return None


def _attention_fwd_bytes(t, d, itemsize, bq, bk) -> int:
    """Residents of one program of the resident schedule: K and V
    whole, the q and output blocks (and the logsumexp rows), all W
    columns wide, the float32 accumulator and output of a head and
    the [bq, bk] score and probability tiles the body holds."""
    w = attention_block_width(d)
    return (
        2 * vmem_block_bytes((t, w), itemsize, moves=True)
        + 2 * vmem_block_bytes((bq, w), itemsize, moves=True)
        + vmem_block_bytes((w // d, 1, bq), 4, moves=True)
        + 2 * vmem_block_bytes((bq, w), 4)
        + 3 * vmem_block_bytes((bq, bk), 4)
    )


# The flash pair's kernels hold whole [t, W] slices, so they may ask
# the compiler for more than the 16 MiB a kernel gets by default
# (``vmem_limit_bytes``), up to this; a v5e core has 128 MiB. Of what
# a kernel asks for, 3 MiB stay free for the compiler's own
# temporaries, as under ``VMEM_BUDGET_BYTES``.
ATTENTION_BWD_VMEM_BYTES = 100 * 2 ** 20
_ATTENTION_VMEM_MARGIN = 3 * 2 ** 20


def attention_fwd_fits(t: int, d: int, itemsize: int, block_q: int,
                       block_k: int) -> bool:
    """Whether the resident forward's residents fit the most VMEM it
    may ask for (``attention_fwd_vmem_request``)."""
    return (_attention_fwd_bytes(t, d, itemsize, block_q, block_k)
            <= ATTENTION_BWD_VMEM_BYTES - _ATTENTION_VMEM_MARGIN)


def attention_fwd_vmem_request(t: int, d: int, itemsize: int,
                               block_q: int,
                               block_k: int) -> Optional[int]:
    """``vmem_limit_bytes`` of the resident forward: ``None`` where its
    residents fit ``VMEM_BUDGET_BYTES`` (the call then takes the
    compiler's default, as every such call always has), else the
    residents and the margin, rounded up to a whole MiB."""
    need = _attention_fwd_bytes(t, d, itemsize, block_q, block_k)
    if need <= VMEM_BUDGET_BYTES:
        return None
    return _pad_up(need + _ATTENTION_VMEM_MARGIN, 2 ** 20)


def pick_attention_blocks(t: int, d: int, itemsize: int) -> Tuple[int, int]:
    """Heuristic (block_q, block_k) of the resident schedule and of
    the fused backward: the largest legal block up to 512, on both
    axes, whose forward residents fit the VMEM the forward may ask
    for (``attention_fwd_fits``) — the whole sequence up to 512,
    where one [t, t] tile a head beat every split of it on
    the chip, causal or not (at [64, 8, 512, 64] bfloat16, forward
    with backward: 2.84 ms against 3.59 at 256s and 4.84 at 128s; at
    t = 4096, 4.56 against 14.4 at 128s: scripts/attention_ab.py
    --sweep, PERF.md §6 PR 32). A tile's time grows far slower than
    its area, so skipping the masked half of a causal tile by
    splitting it loses. Judged against ``VMEM_BUDGET_BYTES`` instead,
    head size 256 at t = 4096 in bfloat16 (13.03 MiB at 512) would
    run 256-row tiles, four times as many a head (PERF.md §6)."""
    legal = _legal_blocks_desc(t, 512, _LANES)
    block = next(
        (b for b in legal if attention_fwd_fits(t, d, itemsize, b, b)),
        legal[-1])
    return block, block


def attention_bwd_fits(t: int, d: int, itemsize: int, block_q: int,
                       block_k: int) -> bool:
    """Whether the fused backward's residents fit the VMEM it asks
    for: per program q, k, v, O, dO in and dq, dk, dv out (whole
    [t, W] slices of W = max(d, 128) columns, double-buffered), the
    [W // d, 1, t] float32 rows of the logsumexp (moving) and of D,
    the scaled-q and dq scratches, the float32 [block_k, block_q]
    tiles the body holds (scores, probabilities, dP, dS and a
    transposed dS) and the [block_k, W] dK and dV of the head at hand
    and of the block."""
    w = attention_block_width(d)
    resident = (
        8 * vmem_block_bytes((t, w), itemsize, moves=True)
        + vmem_block_bytes((w // d, 1, t), 4, moves=True)
        + vmem_block_bytes((w // d, 1, t), 4)
        + vmem_block_bytes((t, w), itemsize)
        + vmem_block_bytes((t, w), 4)
        + 5 * vmem_block_bytes((block_k, block_q), 4)
        + 4 * vmem_block_bytes((block_k, w), 4)
    )
    return resident <= ATTENTION_BWD_VMEM_BYTES - _ATTENTION_VMEM_MARGIN


def attention_candidates(t: int, d: int, itemsize: int,
                         limit: int = 16) -> List[Tuple[int, int]]:
    """Power-of-two divisor block pairs whose resident-schedule
    residents fit (the picker's test, ``attention_fwd_fits``; the
    streamed schedule holds K/V a block at a time and is strictly
    smaller)."""
    sizes = []
    p = pow2_divisor_leq(t, 512)
    while p > 1:
        # block_q/block_k are the second-to-last dim of the q/k/v
        # blocks and the last dim of the logsumexp rows' blocks
        if block_dim_ok(p, t, _LANES):
            sizes.append(p)
        p //= 2
    out: List[Tuple[int, int]] = []
    for bq in sizes:
        for bk in sizes:
            if attention_fwd_fits(t, d, itemsize, bq, bk):
                out.append((bq, bk))
            if len(out) >= limit:
                return out
    return out


def attention_candidate_cost(cfg, t: int, d: int,
                             itemsize: int) -> Tuple[float, float]:
    """Prior for one (bq, bk): padded QK^T + PV work per tile, plus
    K/V refetch traffic (each k-block streams once per q-block)."""
    bq, bk = cfg
    tiles = (t // bq) * (t // bk)
    flops = tiles * 2.0 * 2.0 * _pad_up(bq, _SUBLANES) * d * _pad_up(
        bk, _LANES)
    bytes_ = ((t // bq) * 2 * t * d * itemsize   # K/V per q-block
              + 2 * t * d * itemsize)            # q in + out
    return flops, float(bytes_)


# ---------------------------------------------------------------------------
# depthwise_conv blocks
# ---------------------------------------------------------------------------

# Positions of history a program of the depthwise convolution's
# backward reads before and behind its tile through BlockSpecs of their
# own: one tile of lanes (time is on lanes), so the taps may reach 128
# positions back.
DEPTHWISE_CONV_HALO = _LANES
# channels come, and are walked, in whole bfloat16 sublane tiles
DEPTHWISE_CONV_ROWS = 16


def _depthwise_conv_bytes(bt: int, bc: int, itemsize: int,
                          taps: int) -> int:
    """Residents of one program: the tiles of x, dy and dx and the
    three halos, moving; the float32 copies of a few rows of x and of
    ``dy * silu'`` with their halos; the taps with the bias and their
    gradients; the sums before the reduce across lanes."""
    halo, rows = DEPTHWISE_CONV_HALO, DEPTHWISE_CONV_ROWS
    return (
        3 * vmem_block_bytes((bc, bt), itemsize, moves=True)
        + 3 * vmem_block_bytes((bc, halo), itemsize, moves=True)
        + 2 * vmem_block_bytes((rows, bt + 2 * halo), 4)
        + 2 * vmem_block_bytes((bc, taps + 1), 4, moves=True)
        + vmem_block_bytes((taps + 1, bc, _LANES), 4)
    )


def kda_chunk_length(t: int, chunk: int, sub: int) -> Tuple[int, int]:
    """(C, padding) of the delta rule's chunk form over ``t`` positions:
    ``chunk``, or ``t`` rounded up to whole blocks of ``sub`` where that
    is shorter, and the positions added behind to fill whole chunks."""
    c = min(chunk, -(-t // sub) * sub)
    return c, -t % c


def kda_chunk_fits(d: int, chunk: int, sub: int) -> bool:
    """Whether the delta rule's kernel pair has blocks Mosaic accepts on
    its grid (row, head group, chunk): a head of one or two whole lane
    tiles (a group of heads a block of the ``[b, t, h·d]`` arrays, and
    one head always a group), a chunk of whole blocks of ``sub``
    positions up to 128 (its ``[C, C]`` products and the ``[d, d]``
    state stay a few tiles of VMEM)."""
    return (d % _LANES == 0 and d <= 2 * _LANES and chunk % sub == 0
            and chunk <= _LANES)


# Columns of ``[b, t, h·d]`` one program of the delta rule's kernel pair
# takes at most: the per-chunk functions run batched over the group's
# heads. On a v5e at ``[2, 4096, 32·128]`` bfloat16, chunks of 64, ms a
# call forward / backward (PERF.md §6): one head 9.51 / 13.26,
# four 4.35 / 7.44, eight 4.25 / 7.36, sixteen 4.22 / 7.38; past four
# heads a call gains 1–3% for twice the body.
KDA_CHUNK_LANES = 512
# What one head's chunk holds at once in the kernels' bodies, by
# inspection of ``chunk_prep`` / ``chunk_step`` and ``chunk_bwd``, with
# the normalised q and k in float32: ``[C, C]``, ``[C, d]`` and ``[d, d]``
# values (forward, backward). An upper count: at the class above, four
# heads a program, the compiler uses 2.95 / 8.09 MiB of scoped VMEM, its
# blocks included (described v5e), against 5.34 / 9.47 by
# ``kda_chunk_bytes``.
_KDA_LIVE = {False: (8, 18, 2), True: (14, 34, 4)}


def kda_chunk_bytes(h: int, d: int, chunk: int, itemsize: int, heads: int,
                    bwd: bool) -> int:
    """VMEM residents of one program of ``kda_chunk_fwd`` (with the
    states it keeps) or ``kda_chunk_bwd`` over ``heads`` heads: the
    moving ``[C, heads·d]`` blocks of q, k, v and o (with dO, dq, dk, dv
    in the backward), of the log-decays g (and dg) in float32, every
    head's β, the ``[heads, d, d]`` states kept or read, the dβ columns,
    the state's (or its cotangent's) scratch, the ``[C, C]`` triangle of
    ones the chunk's sums take, and each head's live values."""
    cols = heads * d
    cc, cd, dd = _KDA_LIVE[bwd]
    blocks = (4 + 3 * bwd) * vmem_block_bytes((chunk, cols), itemsize,
                                              moves=True)
    blocks += (1 + bwd) * vmem_block_bytes((chunk, cols), 4, moves=True)
    blocks += vmem_block_bytes((chunk, h), 4, moves=True)
    blocks += vmem_block_bytes((heads, d, d), 4, moves=True)
    if bwd:
        blocks += vmem_block_bytes((heads, chunk, 1), 4, moves=True)
    live = (cc * vmem_block_bytes((chunk, chunk), 4)
            + cd * vmem_block_bytes((chunk, d), 4)
            + dd * vmem_block_bytes((d, d), 4))
    return (blocks + vmem_block_bytes((heads, d, d), 4)
            + vmem_block_bytes((chunk, chunk), 4) + heads * live)


def kda_chunk_heads(h: int, d: int, chunk: int, itemsize: int,
                    bwd: bool = False) -> int:
    """Heads of one program of the delta rule's forward (or, ``bwd``,
    backward) kernel: the largest divisor of ``h`` whose columns stay
    within ``KDA_CHUNK_LANES`` and whose residents (``kda_chunk_bytes``)
    fit ``VMEM_BUDGET_BYTES``; 1, a head a program, where none does."""
    return next((g for g in divisors_desc(h, KDA_CHUNK_LANES // d)
                 if kda_chunk_bytes(h, d, chunk, itemsize, g, bwd)
                 <= VMEM_BUDGET_BYTES), 1)


def pick_depthwise_conv_blocks(t: int, c: int, itemsize: int,
                               taps: int) -> Optional[Tuple[int, int]]:
    """(block_t, block_c) of the depthwise convolution's backward over
    ``[b, t, c]``, which the kernel takes as ``[b, c, t]``, or None
    where the call takes XLA: time in whole 128-lane tiles (the most up
    to 4,096 and ``t``; a last tile that ``t`` does not fill is masked
    in the kernel), channels in whole bfloat16 sublane tiles (the widest
    divisor of ``c`` up to 128), the taps within one halo, residents
    within the VMEM budget. Long rows of few channels won on the chip:
    at ``[2, 4096, 4352]`` bfloat16 (4096, 128) took 0.64 ms, (2048,
    256) 0.74, (1024, 512) 1.02 (PERF.md §6, PR 38)."""
    halo, rows = DEPTHWISE_CONV_HALO, DEPTHWISE_CONV_ROWS
    if c <= 0 or c % rows or t < halo or not 1 <= taps <= halo:
        return None
    bc = next(d for d in divisors_desc(c, _LANES) if d % rows == 0)
    for bt in range(min(t, 4096) // halo * halo, 0, -halo):
        if _depthwise_conv_bytes(bt, bc, itemsize,
                                 taps) <= VMEM_BUDGET_BYTES:
            return bt, bc
    return None


# ---------------------------------------------------------------------------
# short_conv rows
# ---------------------------------------------------------------------------

# Positions of history a program of the gated short convolution reads
# before its tile (and its backward, of look-ahead, behind it) through
# BlockSpecs of their own: one bfloat16 tile of rows. Time is on rows
# here, so a tap reads a rolled window padded by 8 float32 rows, and the
# taps may reach 8 positions back.
SHORT_CONV_HALO = 16
# positions a kernel body takes at a time (one bfloat16 tile of rows) and
# the most channels: float32 ``[16, 512]`` values are eight vregs
SHORT_CONV_ROWS = 16
SHORT_CONV_COLS = 512


def short_conv_channels(wide: int) -> Optional[int]:
    """``d`` of an ``[b, t, 3d]`` input of the short convolution, or
    None where the width is no three equal column blocks."""
    return wide // 3 if wide > 0 and wide % 3 == 0 else None


def short_conv_cols(d: int) -> int:
    """Channels the short convolution's kernels take at a time: the
    widest whole lane tiles up to ``SHORT_CONV_COLS`` that divide
    ``d``."""
    return next(c for c in (SHORT_CONV_COLS, 256, _LANES) if d % c == 0)


def short_conv_bytes(bt: int, d: int, itemsize: int, taps: int,
                     backward: bool) -> int:
    """Residents of one program over ``bt`` positions and every column
    of ``[b, t, 3d]``: the moving tile and the halo before it (the
    backward also the look-ahead of C and ``dy``, the tile of ``dy`` and
    the tile of gradients it writes), the output tile, the taps (and
    their gradients), and the float32 scratch of ``u = B ⊙ x̃`` (and
    ``C ⊙ dy``) over one column chunk with its halo."""
    halo, wide = SHORT_CONV_HALO, 3 * d
    cols = short_conv_cols(d)
    n = (vmem_block_bytes((bt, wide), itemsize, moves=True)
         + vmem_block_bytes((halo, wide), itemsize, moves=True)
         + vmem_block_bytes((taps, d), 4, moves=True))
    if not backward:
        return (n + vmem_block_bytes((bt, d), itemsize, moves=True)
                + vmem_block_bytes((halo + bt, cols), 4))
    return (n + vmem_block_bytes((bt, wide), itemsize, moves=True)
            + vmem_block_bytes((halo, wide), itemsize, moves=True)
            + vmem_block_bytes((bt, d), itemsize, moves=True)
            + vmem_block_bytes((halo, d), itemsize, moves=True)
            + vmem_block_bytes((taps, d), 4, moves=True)
            + 2 * vmem_block_bytes((halo + bt, cols), 4))


def pick_short_conv_rows(t: int, d: int, itemsize: int, taps: int,
                         backward: bool) -> Optional[int]:
    """Positions one program of the short convolution's forward (or,
    ``backward``, backward) kernel takes, or None where the call takes
    XLA: the most, up to 512, in whole halos dividing ``t``, whose
    residents (``short_conv_bytes``) fit ``VMEM_BUDGET_BYTES``; the
    channels ``d`` in whole lane tiles and the taps within one rolled
    window (at most ``_SUBLANES + 1``)."""
    halo = SHORT_CONV_HALO
    if (d <= 0 or d % _LANES or t < halo or t % halo
            or not 1 <= taps <= _SUBLANES + 1):
        return None
    return next((bt for bt in range(min(t, 512) // halo * halo, 0, -halo)
                 if t % bt == 0 and short_conv_bytes(
                     bt, d, itemsize, taps, backward) <= VMEM_BUDGET_BYTES),
                None)
