"""Fused dense epilogue Pallas kernel: ``activation(x @ w + b [+ r])``
as ONE kernel for the feedforward/projection layers that dominate the
MLP and transformer configs (the matmul is MXU-bound; the separate bias
add, residual add and activation each cost a full HBM round-trip of the
[m, n] activation — this kernel applies them to the f32 accumulator
in-register before the single writeback).

Tiling: grid = (m blocks, n blocks); the K axis stays whole per tile
(one [bm, K] x [K, bn] MXU contraction, f32 accumulation for
half-precision inputs). Block sizes come from ``ops/tiling.py`` and,
when ``DL4J_TPU_TUNE`` is active, from the measured winners in
``ops/autotune.py`` — resolved at the public entry, before the
custom-vjp boundary. Backward falls back to XLA through the reference
math — dW/dx are plain matmuls XLA already schedules optimally (same
measured-first policy as ``lstm_cell``).

The optional ``residual`` widens the epilogue with a pre-activation
skip add (``activation(x @ w + b + residual)``) — a separate kernel
variant so the residual-free path stays byte-identical."""

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


def matmul_block_ok(m: int, k: int, n: int, dtype=jnp.float32) -> bool:
    """Gate: a VMEM-fitting (bm, bn) tile exists for [m,k] x [k,n].
    Callers route to ``matmul_block`` only when this holds. Keyed to
    the divisor HEURISTIC: tuning changes block shapes, never
    routing."""
    try:
        m, k, n = int(m), int(k), int(n)
        if m <= 0 or k <= 0 or n <= 0:
            return False
        itemsize = np.dtype(dtype).itemsize
        return tiling.pick_matmul_blocks(m, k, n, itemsize) is not None
    except (TypeError, ValueError):
        return False


def _matmul_kernel(x_ref, w_ref, b_ref, out_ref, *, act):
    acc = jnp.dot(x_ref[:], w_ref[:],
                  preferred_element_type=jnp.float32)
    out_ref[:] = act(acc + b_ref[0]).astype(out_ref.dtype)


def _matmul_res_kernel(x_ref, w_ref, b_ref, r_ref, out_ref, *, act):
    acc = jnp.dot(x_ref[:], w_ref[:],
                  preferred_element_type=jnp.float32)
    z = acc + b_ref[0] + r_ref[:].astype(jnp.float32)
    out_ref[:] = act(z).astype(out_ref.dtype)


def _matmul_block_call(x, w, bias, residual, activation, blocks,
                       interpret):
    m, k = (int(v) for v in x.shape)
    n = int(w.shape[1])
    bm, bn = blocks
    bias2 = bias.astype(jnp.float32).reshape(1, n)
    in_specs = [
        pl.BlockSpec((bm, k), lambda i, j: (i, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((k, bn), lambda i, j: (0, j),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, bn), lambda i, j: (0, j),
                     memory_space=pltpu.VMEM),
    ]
    operands = [x, w, bias2]
    if residual is None:
        kern = functools.partial(_matmul_kernel,
                                 act=_EPILOGUES[activation])
    else:
        kern = functools.partial(_matmul_res_kernel,
                                 act=_EPILOGUES[activation])
        in_specs.append(pl.BlockSpec((bm, bn), lambda i, j: (i, j),
                                     memory_space=pltpu.VMEM))
        operands.append(residual)
    return pl.pallas_call(
        kern,
        grid=(m // bm, n // bn),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        name=tiling.kernel_name("matmul_block_fwd", x.dtype, m=m, k=k,
                                n=n),
        interpret=interpret,
    )(*operands)


def _measure_factory(m, k, n, dtype, with_residual, interpret):
    def factory(cfg):
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.standard_normal((m, k)), dtype)
        w = jnp.asarray(rng.standard_normal((k, n)), dtype)
        bias = jnp.zeros((n,), jnp.float32)
        residual = (jnp.asarray(rng.standard_normal((m, n)), dtype)
                    if with_residual else None)

        def run():
            out = _matmul_block_call(x, w, bias, residual, "identity",
                                     cfg, interpret)
            jax.block_until_ready(out)
        return run
    return factory


def _resolve_blocks(m, k, n, dtype, with_residual, interpret):
    itemsize = jnp.dtype(dtype).itemsize
    heur = tiling.pick_matmul_blocks(m, k, n, itemsize)
    if heur is None or not autotune.tuning_active():
        return heur
    factory = None
    if autotune.tuning_mode() == "on":
        factory = _measure_factory(m, k, n, dtype, with_residual,
                                   interpret)
    return autotune.resolve(
        "matmul_block",
        {"m": m, "k": k, "n": n, "dtype": str(jnp.dtype(dtype)),
         "residual": bool(with_residual)},
        heur,
        tiling.matmul_candidates(m, k, n, itemsize),
        lambda cfg: tiling.matmul_candidate_cost(cfg, m, k, n,
                                                 itemsize),
        factory,
    )


def _reference_core(activation, x, w, bias):
    """XLA reference math — also the backward path (pallas_call has no
    automatic transpose; grads recompute through this). Same semantics
    as the kernel: f32 accumulation + f32 epilogue, one final cast."""
    z = jnp.dot(x, w, preferred_element_type=jnp.float32)
    z = z + bias.astype(jnp.float32)
    return _EPILOGUES[activation](z).astype(x.dtype)


def _reference_core_res(activation, x, w, bias, residual):
    z = jnp.dot(x, w, preferred_element_type=jnp.float32)
    z = z + bias.astype(jnp.float32) + residual.astype(jnp.float32)
    return _EPILOGUES[activation](z).astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _matmul_block_vjp(meta, x, w, bias):
    activation, interpret, blocks = meta
    return _matmul_block_call(x, w, bias, None, activation, blocks,
                              interpret)


def _matmul_block_fwd(meta, x, w, bias):
    activation, interpret, blocks = meta
    return _matmul_block_call(x, w, bias, None, activation, blocks,
                              interpret), (x, w, bias)


def _matmul_block_bwd(meta, res, g):
    activation, _, _ = meta
    x, w, bias = res
    _, vjp = jax.vjp(
        lambda *a: _reference_core(activation, *a), x, w, bias
    )
    return vjp(g)


_matmul_block_vjp.defvjp(_matmul_block_fwd, _matmul_block_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _matmul_block_res_vjp(meta, x, w, bias, residual):
    activation, interpret, blocks = meta
    return _matmul_block_call(x, w, bias, residual, activation, blocks,
                              interpret)


def _matmul_block_res_fwd(meta, x, w, bias, residual):
    activation, interpret, blocks = meta
    return _matmul_block_call(x, w, bias, residual, activation, blocks,
                              interpret), (x, w, bias, residual)


def _matmul_block_res_bwd(meta, res, g):
    activation, _, _ = meta
    x, w, bias, residual = res
    _, vjp = jax.vjp(
        lambda *a: _reference_core_res(activation, *a),
        x, w, bias, residual,
    )
    return vjp(g)


_matmul_block_res_vjp.defvjp(_matmul_block_res_fwd,
                             _matmul_block_res_bwd)


def matmul_block(x, w, b=None, residual=None, *,
                 activation="identity", interpret: bool = False):
    """Fused ``activation(x @ w + b [+ residual])`` via ONE Pallas
    kernel. x [m, k], w [k, n], b [n] (optional), residual [m, n]
    (optional — the pre-activation skip add). Differentiable (backward
    recomputes through the XLA reference). ``interpret`` and the block
    config are resolved HERE, before the custom-vjp boundary — off-TPU
    the kernel self-arms interpreter mode even when
    ``DL4J_TPU_PALLAS=1`` forces routing."""
    from deeplearning4j_tpu.ops.dispatch import pallas_interpret

    if activation not in _EPILOGUES:
        raise ValueError(
            f"matmul_block: unsupported epilogue '{activation}' "
            f"(supported: {SUPPORTED_EPILOGUES})"
        )
    m, k = (int(v) for v in x.shape)
    n = int(w.shape[1])
    bias = (b.astype(jnp.float32) if b is not None
            else jnp.zeros((n,), jnp.float32))
    interp = bool(interpret or pallas_interpret())
    blocks = _resolve_blocks(m, k, n, x.dtype, residual is not None,
                             interp)
    if blocks is None:
        raise ValueError("matmul_block: no VMEM-fitting tile (callers "
                         "must gate on matmul_block_ok)")
    meta = (activation, interp, tuple(int(v) for v in blocks))
    if residual is None:
        return _matmul_block_vjp(meta, x, w, bias)
    return _matmul_block_res_vjp(meta, x, w, bias, residual)


def matmul_block_reference(x, w, b=None, residual=None, *,
                           activation="identity"):
    """The XLA-fused reference path (same math, no Pallas): what the
    parity tests compare the kernel with."""
    if activation not in _EPILOGUES:
        raise ValueError(
            f"matmul_block: unsupported epilogue '{activation}' "
            f"(supported: {SUPPORTED_EPILOGUES})"
        )
    n = int(w.shape[1])
    bias = (b.astype(jnp.float32) if b is not None
            else jnp.zeros((n,), jnp.float32))
    if residual is None:
        return _reference_core(activation, x, w, bias)
    return _reference_core_res(activation, x, w, bias, residual)
