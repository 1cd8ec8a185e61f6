"""Kimi Delta Attention's chunk (Kimi Linear, arXiv:2510.26692): the
gated delta rule with one decay per key channel, a chunk of ``C``
positions at a time. ``kda_chunk`` takes q and k as they leave the
projections, and normalises each head's: ``x · rsqrt(Σ_d x² + 1e-6)`` in
float32 (``l2_normalize``), q also times ``d^-1/2``, both rounded to q's
dtype (``normed_qk``); below, q and k are those. Per head, with a state
``S`` ``[d_k, d_v]`` from zero and ``g_t`` the per-channel log-decay (in
``(lower, 0)``):

    S_t = (I - β_t k_t k_tᵀ) · Diag(exp(g_t)) · S_{t-1} + β_t k_t v_tᵀ
    o_t = S_tᵀ q_t

In a chunk, with ``Γ`` the cumulative log-decay from the chunk's start
and ``γ = exp(Γ)`` (``chunk_prep`` and ``chunk_step``):

    A_kk[r, s] = β_r Σ_d k_rd k_sd exp(Γ_rd - Γ_sd)   (s < r)
    T = (I + A_kk)⁻¹                                   (unit lower triangular)
    W = T·(β ⊙ K ⊙ γ),  U = T·(β ⊙ V),  V' = U - W·S
    A_qk[r, s] = Σ_d q_rd k_sd exp(Γ_rd - Γ_sd)        (s ≤ r)
    O = (Q ⊙ γ)·S + A_qk·V'      S ← Diag(γ_C)·S + (K ⊙ exp(Γ_C - Γ))ᵀ·V'

The decay factor of ``A_kk`` and ``A_qk`` is split into a row factor and
a column factor about the first position of each block of ``SUB``
columns: a step's log-decay is above ``lower`` = -5, so a column factor
stays under exp(75) and the products run on the MXU; over a whole chunk
of 64 they would overflow float32. ``T`` comes without a sequential
solve: the diagonal blocks of ``SUB`` by the product form ``(I + N)⁻¹ =
Π_j (I + (-N)^(2^j))`` of a nilpotent ``N``, the rest by the same form
over ``M = T_d·N_off``, which is nilpotent over the blocks.

Two implementations of the same per-chunk functions: ``kda_chunked``,
plain XLA (a ``lax.scan`` over chunks, ``vmap`` over rows and heads),
on every platform and differentiable as it stands; and a pair of Pallas
kernels that read and write the ``[b, t, h·d]`` arrays the projections
give, grid (batch row, head group, chunk). A program takes one chunk of
a group of heads (``tiling.kda_chunk_heads``, from the shape alone) and
runs the per-chunk functions batched over the group (``jax.vmap``): one
head's chunk is a chain of small dependent products, and a group's
chains, which share nothing, reach the MXU as one product over a leading
head axis. The pair reads the per-step log-decays ``g`` and forms each
chunk's ``Γ`` in VMEM (``chunk_sums``); the backward writes ``dg``, the
reverse sums of ``dΓ`` within the chunk (``chunk_sums_t``), so XLA runs
no cumulative sum around the kernels. Likewise the pair reads q and k
raw and normalises them in VMEM, each head's rows over its own lanes;
the backward takes its float32 ``dq̂`` and ``dk̂`` through the norms'
transpose (``unit_rows_t``) before it writes dq and dk, so XLA runs no
norm around the kernels either. ``kda_chunk_fwd`` runs the chunks
in order with each head's float32 state in VMEM scratch and, under
differentiation, writes the state each chunk starts from;
``kda_chunk_bwd`` runs them last first with the states' cotangents in
scratch, rebuilding each chunk's parts from its inputs and its starting
state (``chunk_bwd``). ``kda_chunk_group_calls_total{kernel, heads}``
counts each kernel call traced by the heads a program runs. ``kda_chunk``
routes: the pair where ``kda_chunk_ok`` admits the shape and dispatch is
on, the XLA form (``Γ`` from ``chunk_cumsum``, q and k from
``normed_qk``) otherwise; ``kda_chunk_decay_sums_total{where}`` and
``kda_chunk_qk_norms_total{where}`` count where each delta rule's ``Γ``
is summed and its q and k normalised.

Arithmetic: the products of q, k, v and the state take the compute type
with float32 accumulation; decays, ``β``, ``T``, ``W``, ``U`` and the
state are float32, and ``T``, ``W`` and ``U`` come from products at
``Precision.HIGHEST``, as do the kernels' sums ``Γ`` and ``dg`` (a
product with a constant triangle of ones). The norms of q and k and
their transpose are float32; the backward rounds dq and dk once, after
the transpose.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.ops import tiling

_F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
# positions whose decays are factored about one reference: 15 steps of
# at most 5 each stay under the 80 a float32 exponent allows
SUB = 16
# largest exponent a column factor is allowed (positions outside its
# block, masked anyway)
_CAP = 80.0

# the norms' epsilon under the square root
QK_EPS = 1e-6

_NN = ((1,), (0,))
_NT = ((1,), (1,))
_TN = ((0,), (0,))


def _unit_rows(x):
    """(``x`` over its rows' L2 norms, the reciprocal norms ``r``
    ``[..., 1]``), float32: ``x̂ = x · r``, ``r = rsqrt(Σ_d x² + eps)``."""
    xf = x.astype(_F32)
    r = jax.lax.rsqrt(jnp.sum(jnp.square(xf), axis=-1, keepdims=True)
                      + QK_EPS)
    return xf * r, r


def l2_normalize(x):
    """``x`` over its L2 norm along the last axis, in float32."""
    return _unit_rows(x)[0]


def unit_rows_t(xn, r, dxn):
    """The transpose of ``l2_normalize`` at the ``x̂`` it gave and its
    ``r``: ``dx = r · (dx̂ - x̂ Σ_d x̂ dx̂)``, float32."""
    return r * (dxn - xn * jnp.sum(xn * dxn, axis=-1, keepdims=True))


def normed_qk(q, k):
    """q and k ``[..., d]`` as the chunk takes them: each row over its L2
    norm, q also times ``d^-1/2``, rounded to q's dtype."""
    return ((l2_normalize(q) * q.shape[-1] ** -0.5).astype(q.dtype),
            l2_normalize(k).astype(q.dtype))


def _dot(a, b, dims, cdt):
    """``a·b`` contracted over ``dims`` in ``cdt``, float32 sums."""
    return jax.lax.dot_general(a.astype(cdt), b.astype(cdt),
                               (dims, ((), ())), preferred_element_type=_F32)


def _exact(a, b, dims=_NN):
    return jax.lax.dot_general(a, b, (dims, ((), ())), precision=HIGHEST,
                               preferred_element_type=_F32)


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _row(x, i):
    """Row ``i`` of ``x`` ``[C, d]`` as ``[1, d]`` (a masked sum: no
    slice at a sublane offset)."""
    return jnp.sum(jnp.where(_iota(x.shape, 0) == i, x, 0.0), axis=0,
                   keepdims=True)


def decayed_products(q, k, gam, cdt):
    """``(A_qk, A_kk)`` before their masks, ``[C, C]``: ``Σ_d x_rd k_sd
    exp(Γ_rd - Γ_sd)`` for ``x`` = q and k, each block of ``SUB``
    columns as its own product, factored about the block's first
    position."""
    c = k.shape[0]
    pos = _iota((c, 1), 0)
    aqk = akk = jnp.zeros((c, c), _F32)
    kf, qf = k.astype(_F32), q.astype(_F32)
    for j in range(-(-c // SUB)):
        ref = _row(gam, j * SUB)
        mine = (pos >= j * SUB) & (pos < (j + 1) * SUB)
        right = jnp.where(mine, kf * jnp.exp(jnp.minimum(ref - gam, _CAP)),
                          0.0)
        left = jnp.exp(jnp.minimum(gam - ref, 0.0))
        akk = akk + _dot(kf * left, right, _NT, cdt)
        aqk = aqk + _dot(qf * left, right, _NT, cdt)
    return aqk, akk


def _inverse(nil, order, eye):
    """``(I + nil)⁻¹`` for ``nil`` with ``nil^order = 0``."""
    p = -nil
    acc = eye + p
    size = 2
    while size < order:
        p = _exact(p, p)
        acc = _exact(acc, eye + p)
        size *= 2
    return acc


def unit_lower_inverse(n):
    """``(I + n)⁻¹`` for ``n`` ``[C, C]`` float32, strictly lower
    triangular: the diagonal blocks of ``SUB`` by the product form,
    then ``(I + M)⁻¹·T_d`` with ``M = T_d·n_off``."""
    c = n.shape[0]
    r, col = _iota((c, c), 0), _iota((c, c), 1)
    eye = (r == col).astype(_F32)
    if c <= SUB:
        return _inverse(n, c, eye)
    nd = jnp.where(r // SUB == col // SUB, n, 0.0)
    td = _inverse(nd, SUB, eye)
    return _exact(_inverse(_exact(td, n - nd), -(-c // SUB), eye), td)


def _parts(q, k, v, gam, beta, cdt):
    """A chunk's parts before the state it starts from, with what they
    are built of: the decayed products, ``T``, the decays to each
    position and to the chunk's end, ``β K γ`` and ``β V``, ``W``,
    ``U``, ``Q ⊙ γ``, the masked ``A_qk``, ``K ⊙ exp(Γ_C - Γ)``,
    ``γ_C``."""
    c = k.shape[0]
    r, col = _iota((c, c), 0), _iota((c, c), 1)
    aqk, akk = decayed_products(q, k, gam, cdt)
    t = unit_lower_inverse(jnp.where(col < r, beta * akk, 0.0))
    decay = jnp.exp(gam)
    last = _row(gam, c - 1)
    to_end = jnp.exp(last - gam)
    kf = k.astype(_F32)
    xw, xu = beta * kf * decay, beta * v.astype(_F32)
    return {"akk": akk, "t": t, "decay": decay, "to_end": to_end, "xw": xw,
            "xu": xu, "w": _exact(t, xw), "u": _exact(t, xu),
            "qg": q.astype(_F32) * decay, "aqk": jnp.where(col <= r, aqk, 0.0),
            "kend": kf * to_end, "gend": jnp.exp(last)}


_STEP_PARTS = ("w", "u", "qg", "aqk", "kend", "gend")


def chunk_prep(q, k, v, gam, beta, cdt):
    """What a chunk needs before the state it starts from: ``(W, U,
    Q ⊙ γ, A_qk, K ⊙ exp(Γ_C - Γ), γ_C)``; q, k, v ``[C, d]``, ``gam``
    (Γ) ``[C, d_k]`` and ``beta`` ``[C, 1]`` float32."""
    p = _parts(q, k, v, gam, beta, cdt)
    return tuple(p[name] for name in _STEP_PARTS)


def chunk_step(st, w, u, qg, aqk, kend, gend, cdt):
    """One chunk from the state ``st`` = ``Sᵀ`` ``[d_v, d_k]`` float32:
    ``(Sᵀ at the chunk's end, O [C, d_v])``."""
    vp = u - _dot(w, st, _NT, cdt)
    o = _dot(qg, st, _NT, cdt) + _dot(aqk, vp, _NN, cdt)
    return st * gend + _dot(vp, kend, _TN, cdt), o


def chunk_bwd(q, k, v, gam, beta, st, do, dst, cdt):
    """The gradients of one chunk (``chunk_prep`` then ``chunk_step``
    from the state ``st``) at the output's cotangent ``do`` ``[C, d_v]``
    and the cotangent ``dst`` of the state it hands on: ``(dq, dk, dv,
    dΓ, dβ, d st)``, all float32. The chunk's parts are rebuilt from
    its inputs; the decayed products' gradients are products of the
    same factors, one column block of ``SUB`` at a time."""
    c = k.shape[0]
    r, col = _iota((c, c), 0), _iota((c, c), 1)
    pos = _iota((c, 1), 0)
    qf, kf, vf = q.astype(_F32), k.astype(_F32), v.astype(_F32)
    p = _parts(q, k, v, gam, beta, cdt)
    t, w, decay = p["t"], p["w"], p["decay"]
    vp = p["u"] - _dot(w, st, _NT, cdt)
    # the step
    d_vp = _dot(p["aqk"], do, _TN, cdt) + _dot(p["kend"], dst, _NT, cdt)
    d_st = (dst * p["gend"] + _dot(do, p["qg"], _TN, cdt)
            - _dot(d_vp, w, _TN, cdt))
    g_qk = jnp.where(col <= r, _dot(do, vp, _NT, cdt), 0.0)
    d_qg = _dot(do, st, _NN, cdt)
    d_w = -_dot(d_vp, st, _NN, cdt)
    d_kend = _dot(vp, dst, _NN, cdt)
    # the chunk's parts: T = (I + N)⁻¹ gives dN = -Tᵀ·dT·Tᵀ
    d_t = _exact(d_w, p["xw"], _NT) + _exact(d_vp, p["xu"], _NT)
    d_xw, d_xu = _exact(t, d_w, _TN), _exact(t, d_vp, _TN)
    d_n = jnp.where(col < r, -_exact(_exact(t, d_t, _TN), t, _NT), 0.0)
    # each product summed on its own: a multiply-add across two of them
    # rounds by how the compiler fuses it, which varies with the group
    # of heads (the interpreter's compile for three heads did)
    d_beta = (jnp.sum(d_n * p["akk"], axis=1, keepdims=True)
              + jnp.sum(d_xw * kf * decay, axis=1, keepdims=True)
              + jnp.sum(d_xu * vf, axis=1, keepdims=True))
    g_kk = beta * d_n
    dq = d_qg * decay
    dk = d_kend * p["to_end"] + d_xw * beta * decay
    dgam = d_qg * p["qg"] - d_kend * p["kend"] + d_xw * p["xw"]
    d_last = (jnp.sum(dst * st, axis=0, keepdims=True) * p["gend"]
              + jnp.sum(d_kend * p["kend"], axis=0, keepdims=True))
    for j in range(-(-c // SUB)):
        ref = _row(gam, j * SUB)
        mine = (pos >= j * SUB) & (pos < (j + 1) * SUB)
        right = jnp.where(mine, jnp.exp(jnp.minimum(ref - gam, _CAP)), 0.0)
        left = jnp.exp(jnp.minimum(gam - ref, 0.0))
        kr = kf * right
        dq_a = left * _dot(g_qk, kr, _NN, cdt)
        dk_row = left * _dot(g_kk, kr, _NN, cdt)
        dk_col = right * (_dot(g_qk, qf * left, _TN, cdt)
                          + _dot(g_kk, kf * left, _TN, cdt))
        dq = dq + dq_a
        dk = dk + dk_row + dk_col
        dgam = dgam + qf * dq_a + kf * (dk_row - dk_col)
    dgam = dgam + jnp.where(pos == c - 1, d_last, 0.0)
    return dq, dk, d_xu * beta, dgam, d_beta, d_st


def chunk_cumsum(g, chunk):
    """Γ: the cumulative sum of ``g`` ``[b, t, ...]`` (float32) from the
    start of each chunk of ``chunk`` positions; ``t`` a multiple of
    it."""
    b, t = g.shape[:2]
    return jnp.cumsum(g.reshape((b, t // chunk, chunk) + g.shape[2:]),
                      axis=2).reshape(g.shape)


def _ones(c, below):
    """``[C, C]`` float32 ones where the column is at or before the row
    (``below``) or at or after it, noughts elsewhere."""
    r, col = _iota((c, c), 0), _iota((c, c), 1)
    return ((col <= r) if below else (col >= r)).astype(_F32)


def chunk_sums(g):
    """Γ of one chunk in a kernel: the cumulative sum of ``g`` ``[C, n]``
    float32 down its rows, one product with a triangle of ones."""
    return _exact(_ones(g.shape[0], True), g)


def chunk_sums_t(dgam):
    """The transpose of ``chunk_sums``: ``dg`` ``[C, n]``, row ``r`` the
    sum of ``dΓ``'s rows ``r`` to the chunk's last."""
    return _exact(_ones(dgam.shape[0], False), dgam)


def kda_chunked(q, k, v, gam, beta, chunk: int):
    """The chunk form in XLA: q, k, v ``[b, t, h, d]`` (q and k
    normalised, ``normed_qk``), ``gam`` (Γ, ``chunk_cumsum`` of the
    log-decays) ``[b, t, h, d_k]`` float32,
    ``beta`` ``[b, t, h]`` float32, ``t`` a multiple of ``chunk``;
    returns o ``[b, t, h, d_v]`` in q's dtype. One chunk of every row
    and head a step of a ``lax.scan`` whose step is recomputed in the
    backward pass: what autodiff keeps is the state at each chunk's
    start, not every chunk's ``[C, C]`` and ``[C, d]`` parts at once."""
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    nc = t // chunk
    cdt = q.dtype

    def by_chunk(x):  # [b, t, h, ...] -> [nc, b, h, C, ...]
        x = x.reshape((b, nc, chunk, h) + x.shape[3:])
        return jnp.moveaxis(jnp.moveaxis(x, 1, 0), 3, 2)

    prep = jax.vmap(jax.vmap(functools.partial(chunk_prep, cdt=cdt)))
    step = jax.vmap(jax.vmap(functools.partial(chunk_step, cdt=cdt)))

    @jax.checkpoint
    def one(st, xs):
        return step(st, *prep(*xs))

    _, o = jax.lax.scan(one, jnp.zeros((b, h, dv, dk), _F32), tuple(
        by_chunk(x) for x in (q, k, v, gam, beta[..., None])))
    # [nc, b, h, C, dv] -> [b, t, h, dv]
    return jnp.moveaxis(o, 0, 1).swapaxes(2, 3).reshape(
        b, t, h, dv).astype(cdt)


def kda_chunk_ok(shape, dtype, chunk: int) -> bool:
    """Gate: q ``[b, t, h, d]`` of ``dtype`` at ``chunk`` positions has
    blocks the chip's compiler accepts: a head of whole 128-lane tiles
    (a group of heads a block of the ``[b, t, h·d]`` arrays), a chunk of
    whole blocks of ``SUB`` positions, bfloat16 or float32."""
    if len(shape) != 4 or jnp.dtype(dtype) not in (
            jnp.dtype(jnp.bfloat16), jnp.dtype(_F32)):
        return False
    return tiling.kda_chunk_fits(int(shape[3]), chunk, SUB)


def _beta_of_head(b_ref, hi):
    """This head's β ``[C, 1]`` from the block of every head's."""
    braw = b_ref[0]
    return jnp.sum(jnp.where(_iota(braw.shape, 1) == hi, braw, 0.0),
                   axis=1, keepdims=True)


def _group(ref, heads):
    """The group's heads of a ``[1, C, heads·d]`` block as ``[heads, C,
    d]``: each head's 128-lane-aligned columns, stacked."""
    d = ref.shape[2] // heads
    return jnp.stack([ref[0, :, j * d:(j + 1) * d] for j in range(heads)])


def _group_sums(g_ref, heads):
    """The group's Γ ``[heads, C, d]``: ``chunk_sums`` of the block's
    log-decays ``[1, C, heads·d]``, every head's columns at once."""
    gam = chunk_sums(g_ref[0])
    d = gam.shape[1] // heads
    return jnp.stack([gam[:, j * d:(j + 1) * d] for j in range(heads)])


def _ungroup(ref, x):
    """``x`` ``[heads, C, d]`` into a ``[1, C, heads·d]`` block, a head's
    columns at a time."""
    d = x.shape[2]
    for j in range(x.shape[0]):
        ref[0, :, j * d:(j + 1) * d] = x[j].astype(ref.dtype)


def _group_betas(b_ref, heads):
    """The group's β ``[heads, C, 1]``, each head's taken from the block
    of every head's."""
    gi = pl.program_id(1)
    return jnp.stack([_beta_of_head(b_ref, gi * heads + j)
                      for j in range(heads)])


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, *rest, cdt,
                heads):
    """One (batch row, head group, chunk): ``q_ref`` .. ``g_ref`` ``[1, C,
    heads·d]`` (q and k raw, normalised here by ``normed_qk``; ``g_ref``
    the per-step log-decays, summed into Γ here), ``b_ref`` ``[1, C,
    h]`` (every head's β, each head's taken by a
    mask), the group's states ``Sᵀ`` in the last scratch ``[heads, d,
    d]`` from the chunk before; with a states output, the states this
    chunk starts from are written there. ``chunk_prep`` and
    ``chunk_step`` run batched over the group's heads (``jax.vmap``: a
    product of each becomes one product over a leading head axis), so
    the group's chains, which share nothing, go to the MXU together."""
    st_ref = rest[-1]

    @pl.when(pl.program_id(2) == 0)
    def _():
        st_ref[...] = jnp.zeros_like(st_ref)

    if len(rest) == 2:
        rest[0][0, :, 0] = st_ref[...]
    q, k = normed_qk(_group(q_ref, heads), _group(k_ref, heads))
    parts = jax.vmap(functools.partial(chunk_prep, cdt=cdt))(
        q, k, _group(v_ref, heads), _group_sums(g_ref, heads),
        _group_betas(b_ref, heads))
    st, o = jax.vmap(functools.partial(chunk_step, cdt=cdt))(
        st_ref[...], *parts)
    st_ref[...] = st
    _ungroup(o_ref, o)


def _specs(chunk, d, h, heads, reverse_of=None):
    """(a head group's ``[1, C, heads·d]`` block, every head's β ``[1, C,
    h]``, the group's states ``[1, heads, 1, d, d]``) for the grid (row,
    head group, chunk); with ``reverse_of`` (the number of chunks) the
    chunks run last first."""
    at = (lambda ci: ci) if reverse_of is None else (
        lambda ci: reverse_of - 1 - ci)
    vmem = functools.partial(pl.BlockSpec, memory_space=pltpu.VMEM)
    return (vmem((1, chunk, heads * d), lambda bi, gi, ci: (bi, at(ci), gi)),
            vmem((1, chunk, h), lambda bi, gi, ci: (bi, at(ci), 0)),
            vmem((1, heads, 1, d, d),
                 lambda bi, gi, ci: (bi, gi, at(ci), 0, 0)))


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))


def _note_group_call(kernel: str, heads: int) -> None:
    """``kda_chunk_group_calls_total{kernel, heads}``: one per kernel
    call traced, by the heads a program runs."""
    from deeplearning4j_tpu.observability.metrics import default_registry

    default_registry().counter(
        "kda_chunk_group_calls_total",
        help="delta-rule chunk kernel calls traced, by kernel and heads a "
             "program",
        labels=("kernel", "heads"),
    ).labels(kernel=kernel, heads=str(heads)).inc()


def _shape(q, beta, chunk, heads, bwd):
    """(b, t, h, d, chunks, heads a program) of a call; ``heads`` None
    takes ``tiling.kda_chunk_heads``'s pick for the shape."""
    b, t, hd = (int(s) for s in q.shape)
    h = int(beta.shape[2])
    d = hd // h
    if heads is None:
        heads = tiling.kda_chunk_heads(h, d, chunk, q.dtype.itemsize, bwd)
    return b, t, h, d, t // chunk, heads


def kda_chunk_fwd(q, k, v, g, beta, chunk: int, interpret: bool = False,
                  keep_states: bool = False, heads: int | None = None):
    """The kernel on q, k, v, g ``[b, t, h·d]`` (q and k raw, each head's
    rows normalised in the kernel; g the per-step log-decays, float32;
    each chunk's Γ is summed in the kernel) and ``beta`` ``[b, t, h]``
    float32, ``t`` a multiple of ``chunk``: o ``[b, t,
    h·d]`` in q's dtype, and with ``keep_states`` also the state each
    chunk starts from, ``Sᵀ`` ``[b, h, chunks, d, d]`` float32 (what the
    backward kernel reads). Grid (row, head group, chunk), ``heads`` a
    group (a divisor of h; by default ``tiling.kda_chunk_heads``'s)."""
    b, t, h, d, nc, heads = _shape(q, beta, chunk, heads, False)
    head, betas, state = _specs(chunk, d, h, heads)
    out_specs = [head]
    out_shape = [jax.ShapeDtypeStruct(q.shape, q.dtype)]
    if keep_states:
        out_specs.append(state)
        out_shape.append(jax.ShapeDtypeStruct((b, h, nc, d, d), _F32))
    _note_group_call("kda_chunk_fwd", heads)
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, cdt=q.dtype, heads=heads),
        grid=(b, h // heads, nc),
        in_specs=[head, head, head, head, betas],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((heads, d, d), _F32)],
        compiler_params=_params(),
        name=tiling.kernel_name("kda_chunk_fwd", q.dtype, b=b, t=t, h=h,
                                d=d, c=chunk),
        interpret=interpret,
    )(q, k, v, g, beta)
    return out if keep_states else out[0]


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, s_ref, do_ref, dq_ref,
                dk_ref, dv_ref, dg_ref, db_ref, dst_ref, *, cdt, heads):
    """One (batch row, head group, chunk), chunks last first: each head's
    gradients from its inputs, the state it started from and the
    cotangents of its output and of the state it handed on (``dst_ref``
    ``[heads, d, d]``, which then holds the cotangent of the state it
    started from); ``chunk_bwd`` batched over the group's heads, as in
    the forward, from q and k normalised and Γ summed here, its float32
    ``dq̂`` and ``dk̂`` taken through the norms' transpose
    (``unit_rows_t``) and each head's ``dΓ`` summed back into ``dg``
    (``chunk_sums_t``) before they are written."""

    @pl.when(pl.program_id(2) == 0)
    def _():
        dst_ref[...] = jnp.zeros_like(dst_ref)

    qn, rq = _unit_rows(_group(q_ref, heads))
    kn, rk = _unit_rows(_group(k_ref, heads))
    scale = qn.shape[2] ** -0.5
    dq, dk, dv, dgam, dbeta, dst = jax.vmap(
        functools.partial(chunk_bwd, cdt=cdt))(
        (qn * scale).astype(cdt), kn.astype(cdt), _group(v_ref, heads),
        _group_sums(g_ref, heads), _group_betas(b_ref, heads),
        s_ref[0, :, 0], _group(do_ref, heads), dst_ref[...])
    dq, dk = unit_rows_t(qn, rq, dq * scale), unit_rows_t(kn, rk, dk)
    dst_ref[...] = dst
    for ref, x in ((dq_ref, dq), (dk_ref, dk), (dv_ref, dv)):
        _ungroup(ref, x)
    _ungroup(dg_ref, jnp.stack([chunk_sums_t(x) for x in dgam]))
    db_ref[0] = dbeta


def kda_chunk_bwd(q, k, v, g, beta, states, do, chunk: int,
                  interpret: bool = False, heads: int | None = None):
    """The backward kernel: (dq, dk, dv in q's dtype, dg float32 ``[b, t,
    h·d]``, dβ float32 ``[b, t, h]``) at the output's cotangent ``do``,
    from ``kda_chunk_fwd``'s inputs and the states it kept; dq and dk are
    the raw q's and k's. Grid (row, head group, chunk), ``heads`` a group
    (by default
    ``tiling.kda_chunk_heads``'s pick for the backward)."""
    b, t, h, d, nc, heads = _shape(q, beta, chunk, heads, True)
    head, betas, state = _specs(chunk, d, h, heads, reverse_of=nc)
    col = pl.BlockSpec((1, heads, chunk, 1),
                       lambda bi, gi, ci: (bi, gi, nc - 1 - ci, 0),
                       memory_space=pltpu.VMEM)
    _note_group_call("kda_chunk_bwd", heads)
    dq, dk, dv, dg, dbeta = pl.pallas_call(
        functools.partial(_bwd_kernel, cdt=q.dtype, heads=heads),
        grid=(b, h // heads, nc),
        in_specs=[head, head, head, head, betas, state, head],
        out_specs=[head, head, head, head, col],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype)] * 3 + [
            jax.ShapeDtypeStruct(q.shape, _F32),
            jax.ShapeDtypeStruct((b, h, t, 1), _F32)],
        scratch_shapes=[pltpu.VMEM((heads, d, d), _F32)],
        compiler_params=_params(),
        name=tiling.kernel_name("kda_chunk_bwd", q.dtype, b=b, t=t, h=h,
                                d=d, c=chunk),
        interpret=interpret,
    )(q, k, v, g, beta, states, do)
    return dq, dk, dv, dg, jnp.swapaxes(dbeta[..., 0], 1, 2)


def _flat(x):
    """``[b, t, h, d]`` -> ``[b, t, h·d]``."""
    return x.reshape(x.shape[:2] + (-1,))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _kernel_pair(q, k, v, g, beta, chunk):
    from deeplearning4j_tpu.ops.dispatch import pallas_interpret

    return kda_chunk_fwd(_flat(q), _flat(k), _flat(v), _flat(g), beta,
                         chunk, pallas_interpret()).reshape(v.shape)


def _kernel_pair_fwd(q, k, v, g, beta, chunk):
    from deeplearning4j_tpu.ops.dispatch import pallas_interpret

    o, states = kda_chunk_fwd(_flat(q), _flat(k), _flat(v), _flat(g), beta,
                              chunk, pallas_interpret(), keep_states=True)
    return o.reshape(v.shape), (q, k, v, g, beta, states)


def _kernel_pair_bwd(chunk, res, do):
    from deeplearning4j_tpu.ops.dispatch import pallas_interpret, route

    q, k, v, g, beta, states = res
    route("kda_chunk_bwd")
    dq, dk, dv, dg, dbeta = kda_chunk_bwd(
        _flat(q), _flat(k), _flat(v), _flat(g), beta, states, _flat(do),
        chunk, pallas_interpret())
    return (dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape),
            dg.reshape(g.shape), dbeta)


_kernel_pair.defvjp(_kernel_pair_fwd, _kernel_pair_bwd)


def _note_chunk_call(chunk: int, chunks: int) -> None:
    """``kda_chunk_calls_total{chunk, chunks}``: one per delta rule
    traced, as ``ssm_scan_calls_total`` counts the state-space scans."""
    from deeplearning4j_tpu.observability.metrics import default_registry

    default_registry().counter(
        "kda_chunk_calls_total",
        help="chunked delta rules traced, by chunk length and number of "
             "chunks",
        labels=("chunk", "chunks"),
    ).labels(chunk=str(chunk), chunks=str(chunks)).inc()


def _note_where(where: str) -> None:
    """``kda_chunk_decay_sums_total{where}`` and
    ``kda_chunk_qk_norms_total{where}``: one each per delta rule traced,
    by where its Γ is summed and its q and k are normalised (``kernel``:
    inside the kernel pair; ``xla``: ``chunk_cumsum`` and ``normed_qk``
    ahead of the XLA form)."""
    from deeplearning4j_tpu.observability.metrics import default_registry

    reg = default_registry()
    reg.counter(
        "kda_chunk_decay_sums_total",
        help="chunked delta rules traced, by where the chunks' cumulative "
             "log-decays are summed",
        labels=("where",),
    ).labels(where=where).inc()
    reg.counter(
        "kda_chunk_qk_norms_total",
        help="chunked delta rules traced, by where each head's q and k "
             "are L2-normalised",
        labels=("where",),
    ).labels(where=where).inc()


def kda_chunk(q, k, v, g, beta, chunk: int):
    """The recurrence from a zero state: q, k, v ``[b, t, h, d]`` (q and
    k raw: each head's rows are normalised here, ``normed_qk``), ``g``
    ``[b, t, h, d_k]`` and ``beta`` ``[b, t, h]`` float32; o ``[b, t, h,
    d_v]`` in q's dtype. A length that ``chunk`` does not divide is
    padded behind with positions that neither decay nor add. Routed
    once, here, from the shape
    (``pallas_dispatch_total{kernel="kda_chunk_fwd"}``, and
    ``kda_chunk_bwd`` where the pair's backward is traced), and counted
    (``kda_chunk_calls_total``, ``kda_chunk_decay_sums_total``,
    ``kda_chunk_qk_norms_total``)."""
    from deeplearning4j_tpu.ops.dispatch import route

    t = q.shape[1]
    c, pad = tiling.kda_chunk_length(t, chunk, SUB)
    if pad:
        q, k, v, g, beta = (
            jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
            for x in (q, k, v, g, beta))
    _note_chunk_call(c, q.shape[1] // c)
    g, beta = g.astype(_F32), beta.astype(_F32)
    if route("kda_chunk_fwd", kda_chunk_ok(q.shape, q.dtype, c)):
        _note_where("kernel")
        o = _kernel_pair(q, k, v, g, beta, c)
    else:
        _note_where("xla")
        o = kda_chunked(*normed_qk(q, k), v, chunk_cumsum(g, c), beta, c)
    return o[:, :t]
