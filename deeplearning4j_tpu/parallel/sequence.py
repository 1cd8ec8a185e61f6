"""Sequence/context parallelism: ring attention over a ``seq`` mesh
axis.

Net-new capability vs the reference (which predates attention — its
only long-sequence tools are truncated BPTT and masking, SURVEY.md
§5), but first-class here: sequences too long for one chip's HBM are
sharded along time across the mesh, and attention runs blockwise with
an online-softmax accumulator while K/V blocks rotate around the ring
via ``lax.ppermute`` — each hop rides ICI, overlapping with the local
block's compute (the RingAttention / blockwise-parallel-transformer
scheme).

Use ``ring_self_attention`` inside ``shard_map`` over a mesh with a
``seq`` axis; time-sharded q/k/v stay resident, only one K/V block is
in flight per step, so memory is O(t_local) instead of O(t), and the
score matrix never materializes beyond [t_local, t_local] tiles —
XLA tiles those onto the MXU."""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_NEG = -1e9  # masked-score fill; exp(_NEG - m) underflows to exactly 0


from deeplearning4j_tpu.parallel.compat import shard_map_compat as _shard_map


def split_heads(a, n_heads: int):
    """[b, t, h*d], head i in columns ``[i*d, (i+1)*d)`` (what a
    q/k/v projection gives) → head-major [b, h, t, d]."""
    b, t, f = a.shape
    return jnp.transpose(
        a.reshape(b, t, n_heads, f // n_heads), (0, 2, 1, 3))


def merge_heads(a):
    """Head-major [b, h, t, d] → [b, t, h*d]: ``split_heads`` back."""
    b, h, t, d = a.shape
    return jnp.transpose(a, (0, 2, 1, 3)).reshape(b, t, h * d)


def attention(q, k, v, causal: bool = False, mask=None):
    """Plain (single-shard) scaled-dot-product attention on
    [b, h, t, d] — the reference semantics ring_attention must match;
    XLA fuses softmax into the two matmuls."""
    scale = 1.0 / jnp.sqrt(jnp.asarray(q.shape[-1], q.dtype))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    t = q.shape[2]
    if causal:
        cm = jnp.tril(jnp.ones((t, t), bool))
        s = jnp.where(cm[None, None], s, _NEG)
    if mask is not None:
        # mask: [b, t] validity of keys
        s = jnp.where(mask[:, None, None, :] > 0, s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


def ring_attention(q, k, v, axis_name: str, axis_size: int,
                   causal: bool = False, mask=None):
    """Blockwise ring attention. Call inside ``shard_map`` with q/k/v
    (and mask) sharded on their time axis over ``axis_name``:
    q/k/v [b, h, t_local, d], mask [b, t_local] or None.

    Per ring step every device holds one K/V block, computes its
    [t_local, t_local] score tile, folds it into the online-softmax
    accumulator (m running max, l running denominator, o running
    numerator), and forwards the block to the next device with
    ``ppermute`` — after ``axis_size`` hops each query has seen every
    key, and the result equals single-device softmax attention. The
    whole loop is a ``lax.scan``, so it jits once and autodiff gives
    the ring backward pass (a reverse rotation) for free."""
    tl = q.shape[2]
    my = jax.lax.axis_index(axis_name)
    scale = 1.0 / jnp.sqrt(jnp.asarray(q.shape[-1], q.dtype))
    q_pos = my * tl + jnp.arange(tl)
    perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]

    def step(carry, i):
        o, l, m, k_cur, v_cur, mask_cur = carry
        src = (my - i) % axis_size
        k_pos = src * tl + jnp.arange(tl)
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k_cur) * scale
        if causal:
            cm = q_pos[:, None] >= k_pos[None, :]
            s = jnp.where(cm[None, None], s, _NEG)
        if mask_cur is not None:
            s = jnp.where(mask_cur[:, None, None, :] > 0, s, _NEG)
        m_blk = jnp.max(s, axis=-1, keepdims=True)       # [b,h,tl,1]
        m_new = jnp.maximum(m, m_blk)
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        o_new = o * corr + jnp.einsum("bhqk,bhkd->bhqd", p, v_cur)
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        mask_nxt = (
            jax.lax.ppermute(mask_cur, axis_name, perm)
            if mask_cur is not None else None
        )
        return (o_new, l_new, m_new, k_nxt, v_nxt, mask_nxt), None

    o0 = jnp.zeros_like(q)
    l0 = jnp.zeros(q.shape[:3] + (1,), q.dtype)
    # start far below any real score so the first correction is 0
    m0 = jnp.full(q.shape[:3] + (1,), 2.0 * _NEG, q.dtype)
    (o, l, _, _, _, _), _ = jax.lax.scan(
        step, (o0, l0, m0, k, v, mask), jnp.arange(axis_size)
    )
    return o / jnp.maximum(l, 1e-20)


def ring_self_attention_sharded(mesh: Mesh, q, k, v,
                                causal: bool = False, mask=None,
                                seq_axis: str = "seq"):
    """Convenience wrapper: shard [b, h, t, d] q/k/v on the time axis
    over ``mesh[seq_axis]`` and run ring attention; returns the
    gathered [b, h, t, d] result. For full control (e.g. keeping
    activations sharded through a whole transformer block), call
    ``ring_attention`` inside your own ``shard_map``."""
    shard_map = _shard_map()

    axis_size = mesh.shape[seq_axis]
    qkv_spec = P(None, None, seq_axis, None)
    mask_spec = P(None, seq_axis)

    if mask is None:
        fn = shard_map(
            functools.partial(
                ring_attention, axis_name=seq_axis,
                axis_size=axis_size, causal=causal, mask=None,
            ),
            mesh=mesh, in_specs=(qkv_spec, qkv_spec, qkv_spec),
            out_specs=qkv_spec, check_rep=False,
        )
        return fn(q, k, v)

    def body(q_, k_, v_, mask_):
        return ring_attention(
            q_, k_, v_, axis_name=seq_axis, axis_size=axis_size,
            causal=causal, mask=mask_,
        )

    fn = shard_map(
        body, mesh=mesh,
        in_specs=(qkv_spec, qkv_spec, qkv_spec, mask_spec),
        out_specs=qkv_spec, check_rep=False,
    )
    return fn(q, k, v, mask)


def build_seq_mesh(data: int = 1, seq: Optional[int] = None,
                   devices=None) -> Mesh:
    """(data, seq) mesh for context parallelism; defaults to all
    devices on ``seq``."""
    import numpy as np

    devices = list(devices if devices is not None else jax.devices())
    if seq is None:
        if len(devices) % data != 0:
            raise ValueError(
                f"{len(devices)} devices not divisible by data={data}"
            )
        seq = len(devices) // data
    if data * seq > len(devices):
        raise ValueError(
            f"data({data}) x seq({seq}) > {len(devices)} devices"
        )
    devices = devices[:data * seq]
    n = len(devices)
    return Mesh(
        np.asarray(devices).reshape(data, seq), axis_names=("data", "seq")
    )
