"""The unified functional engine core.

``MultiLayerNetwork`` (sequential stack) and ``ComputationGraph``
(DAG) used to duplicate every hot path: each carried its own jitted
train-step builder, scan-fused multi-step, pretrain step, epoch/fit
drivers, and scan-chunk plumbing — so every performance PR paid its
tax twice. This module is the single implementation both engines wrap:

- **Pure step builders** (``build_step`` / ``build_multi_step`` /
  ``build_pretrain_step``): forward -> loss -> ``jax.value_and_grad``
  -> updater -> (optional) divergence-guard select, telemetry
  grad-norm, dynamic loss scaling — with params/updater-state/state
  donation. An engine contributes only a ``score_fn`` closure (its
  pure forward+loss) and an optional in-jit ``cast`` for the
  cast-on-device input contract.
- **Whole-net transforms**, implemented once and applied through the
  engines' pure forwards:

  * *scan-over-layers* (``detect_layer_runs`` / ``detect_vertex_chains``
    + ``apply_layer_run``): maximal runs of identical, stateless
    layers (transformer blocks, repeated dense groups) have their
    params stacked and the run body traced ONCE under
    ``jax.lax.scan`` — collapsing O(depth) HLO into O(1), which is
    what bounds deep-stack compile time (BENCH r05/r06).
  * *activation rematerialization* (``maybe_remat``): a
    ``none | dots_saveable | full`` policy via ``jax.checkpoint``
    that trades recompute FLOPs for activation HBM, unlocking larger
    batches at fixed peak memory.
  * *dynamic loss scaling* for ``compute_dtype="float16"``
    (``loss_scale_state`` + the ``loss_scale`` step mode): the loss
    is scaled before the backward pass, gradients unscaled after,
    and a non-finite gradient skips the update in-jit and halves the
    scale; ``growth_interval`` clean steps double it back. bf16
    needs none of this (same exponent range as f32) and is unchanged.

- **Fit drivers** (``fit_batches`` / ``fit_epoch_scan`` /
  ``run_scan_chunk`` / ``fit_epochs_device_cached``): the epoch loop,
  scan-chunk grouping, async-dispatch window wiring and listener
  protocol, shared verbatim by both engines. Their boundaries are
  spans of the global tracer (``fit`` > ``fit.epoch`` >
  ``fit.feed_wait`` / ``fit.stack`` / ``fit.dispatch`` /
  ``fit.listeners``, and ``fit.backpressure`` where the scan driver
  waits for the device), recorded whenever a JAX profiler session
  runs.

``scripts/lint_parity.py`` enforces the split: the engine modules may
not re-grow a ``value_and_grad`` / ``lax.scan`` of their own.
"""

from __future__ import annotations

import contextlib
import functools
import json
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.observability import profiler as _prof_mod
from deeplearning4j_tpu.observability.trace import get_tracer

# ---------------------------------------------------------------------------
# dtype / device helpers (shared cast-on-device contract)
# ---------------------------------------------------------------------------


def dtype_of(conf):
    return jnp.dtype(conf.dtype)


def compute_dtype_of(conf) -> jnp.dtype:
    """Forward/backward compute dtype: ``conf.compute_dtype`` when set
    (mixed precision — bf16/f16 on the MXU with f32 master params),
    else the storage dtype."""
    return jnp.dtype(getattr(conf, "compute_dtype", None) or conf.dtype)


def cast_floats(tree, dtype):
    """Cast floating leaves of a pytree to ``dtype`` (ints — embedding
    indices, native-width inputs — pass through untouched)."""
    return jax.tree_util.tree_map(
        lambda a: (
            a.astype(dtype)
            if hasattr(a, "dtype") and jnp.issubdtype(a.dtype, jnp.inexact)
            else a
        ),
        tree,
    )


def to_device(a, dtype):
    """Convert a host array for the jitted step. Integer inputs (e.g.
    uint8 one-hot/pixel data) transfer in their native width and are
    cast to the compute dtype ON DEVICE by the step — 4x less
    host->device traffic than converting to float32 first. Already-
    device-resident arrays pass straight through (no host round
    trip)."""
    if isinstance(a, jax.Array):
        return a.astype(dtype) if a.dtype != dtype else a
    a = np.asarray(a)
    if a.dtype.kind in ("u", "i") and a.dtype.itemsize <= 2:
        return jnp.asarray(a)
    return jnp.asarray(a, dtype)


def cast_stacked(a, dtype):
    """The cast-on-device contract shared by stack_on_device and the
    prestacked-chunk paths of both engines: narrow integers ride at
    native width (the step casts on device); everything else casts to
    the model dtype."""
    return (
        a
        if a.dtype.kind in ("u", "i") and a.dtype.itemsize <= 2
        else a.astype(dtype)
    )


def place(a, dtype):
    """A minibatch array for the scan path's chunk, resident on the
    device: a host array goes there at once through ``to_device``
    (narrow integers at native width, the rest in the model's type),
    one that already is there passes through untouched
    (``stack_on_device`` casts the stacked chunk)."""
    return a if isinstance(a, jax.Array) else to_device(a, dtype)


@functools.partial(jax.jit, static_argnums=1)
def _stack_placed(arrs, dtype):
    return cast_stacked(jnp.stack(arrs), dtype)


def stack_on_device(arrs, dtype):
    """Stack k same-shaped minibatch arrays for a fused dispatch,
    preserving the cast-on-device contract in ONE place for both
    engines: narrow integer inputs (uint8 pixels/one-hots) keep their
    native width — the step casts them on device. The chunk is built
    in device memory by one jitted program per (k, shape, dtype):
    every array still on the host is copied over by itself first (the
    scan driver has done that batch by batch, as each arrived), so no
    chunk-sized host copy is ever made."""
    return _stack_placed([place(a, dtype) for a in arrs], dtype)


def nbytes(a) -> int:
    nb = getattr(a, "nbytes", None)
    return int(nb) if nb is not None else int(np.asarray(a).nbytes)


def iter_unchunked(data):
    """Iterate minibatches, expanding any ChunkedDataSet elements
    (streamed pipelines may deliver pre-stacked chunks; consumers
    without a fused path unstack here)."""
    from deeplearning4j_tpu.datasets.api import ChunkedDataSet

    for d in data:
        if isinstance(d, ChunkedDataSet):
            yield from d.to_datasets()
        else:
            yield d


def reg_penalty(layer, layer_params):
    """L1/L2 penalty for one layer (reference calcL1/calcL2)."""
    reg = 0.0
    if layer.l1 > 0.0 or layer.l2 > 0.0:
        for pn in layer.regularizable_params():
            if pn in layer_params:
                w = layer_params[pn]
                if layer.l2 > 0.0:
                    reg = reg + 0.5 * layer.l2 * jnp.sum(w * w)
                if layer.l1 > 0.0:
                    reg = reg + layer.l1 * jnp.sum(jnp.abs(w))
    return reg


# ---------------------------------------------------------------------------
# scan constants (device-resident lr stacks / iteration counter)
# ---------------------------------------------------------------------------


def scan_consts(model, k: int, it0: int):
    """Device-resident (lr_stack, it0) for a fused k-step dispatch.

    Both are tiny, but transferring the per-layer lr dict —
    ~n_layers small arrays — EVERY chunk is one host->device copy per
    layer per dispatch. Constant schedules (the common case) repeat the
    same values every chunk, so the device copy is cached by value;
    the it0 scalar is reused from the multi-step program's own
    device-computed ``it0 + k`` output (``note_it0``) so steady-state
    chunks transfer nothing host-side at all."""
    rows = [model.updater_def.scheduled_lrs(it0 + i) for i in range(k)]
    names = list(model.updater_def.settings)
    key = (k, tuple(
        tuple(float(r[n]) for n in names) for r in rows
    ))
    cache = model._scan_const_cache
    lr = cache.get(key)
    if lr is None:
        if len(cache) >= 64:  # unbounded only for pathological schedules
            cache.clear()
        lr = {
            n: jnp.asarray([r[n] for r in rows], jnp.float32)
            for n in names
        }
        cache[key] = lr
    if model._it0_shadow == it0 and model._it0_dev is not None:
        it0_dev = model._it0_dev
    else:
        it0_dev = jnp.asarray(it0, jnp.int32)
    return lr, it0_dev


def note_it0(model, it0_dev, host_value: int) -> None:
    """Record the device-side iteration counter a multi-step program
    returned, for reuse by the next chunk's ``scan_consts``."""
    model._it0_dev = it0_dev
    model._it0_shadow = host_value


# ---------------------------------------------------------------------------
# streaming (rnn_time_step) bookkeeping
# ---------------------------------------------------------------------------


def stream_guard_and_prime(named_layers, rnn_state, stream_steps,
                           t_new, batch, dtype) -> None:
    """Shared ``rnn_time_step`` bookkeeping for both engines: raise
    before a finite streaming cache (KV) would silently wrap, and
    prime missing streaming state (zero caches / carries).
    ``named_layers``: (name, layer_conf) pairs."""
    caps = [
        lc.stream_capacity() for _, lc in named_layers
        if lc.streams_state() and lc.stream_capacity()
    ]
    if caps and stream_steps + t_new > min(caps):
        raise ValueError(
            f"rnn_time_step overflow: {stream_steps} + {t_new} "
            f"timesteps exceeds the smallest streaming cache "
            f"({min(caps)}); raise kv_cache or call "
            "rnn_clear_previous_state()"
        )
    for name, lc in named_layers:
        if (
            lc.streams_state()
            and name not in rnn_state
            and getattr(lc, "init_stream_state", None) is not None
        ):
            rnn_state[name] = lc.init_stream_state(batch, dtype)


def extract_stream_state(named_layers, new_state, rnn_state) -> None:
    """Pull each streaming layer's carry keys out of the step's state
    into the host-held ``rnn_state`` (the reference's stateMap)."""
    for name, lc in named_layers:
        if lc.streams_state():
            rnn_state[name] = {
                k: new_state[name][k]
                for k in lc.stream_state_keys()
                if k in new_state[name]
            }


# ---------------------------------------------------------------------------
# whole-net transform: activation rematerialization
# ---------------------------------------------------------------------------

REMAT_POLICIES = ("none", "dots_saveable", "full")


def check_remat_policy(policy: str) -> str:
    if policy not in REMAT_POLICIES:
        raise ValueError(
            f"remat policy must be one of {REMAT_POLICIES}, "
            f"got {policy!r}"
        )
    return policy


def maybe_remat(fn: Callable, policy: str) -> Callable:
    """Wrap ``fn`` in ``jax.checkpoint`` per the remat policy:
    ``"full"`` saves only the inputs (recompute everything in the
    backward pass), ``"dots_saveable"`` keeps matmul/conv outputs (the
    MXU results that are expensive to recompute) and drops the cheap
    elementwise intermediates, ``"none"`` is the identity. The primal
    forward is untouched — only what the backward pass reads changes —
    so outputs (and, op-for-op, gradients) match the unwrapped fn."""
    if policy == "none":
        return fn
    if policy == "full":
        return jax.checkpoint(fn)
    check_remat_policy(policy)
    return jax.checkpoint(
        fn, policy=jax.checkpoint_policies.dots_saveable
    )


# ---------------------------------------------------------------------------
# whole-net transform: scan-over-layers
# ---------------------------------------------------------------------------


def layer_scan_signature(layer) -> str:
    """Config identity for run detection: two layers with equal
    signatures are the SAME program modulo parameter values (the name
    is display-only)."""
    from deeplearning4j_tpu.nn.layers.base import layer_to_json

    d = layer_to_json(layer)
    d.pop("name", None)
    return json.dumps(d, sort_keys=True, default=str)


def scannable_layer(layer) -> bool:
    """A layer may join a scanned run when its per-step program is
    self-contained and stateless: no recurrent/TBPTT carry, no loss
    head, no pretrain phase, no batch statistics, and an empty state
    pytree (BatchNorm's running stats would have to thread through the
    scan carry — excluded instead)."""
    try:
        return bool(
            layer.supports_layer_scan() and not layer.init_state()
        )
    except Exception:
        return False


def detect_layer_runs(layers, preprocessors=None,
                      min_run: int = 2) -> List[Tuple[int, int]]:
    """Maximal runs ``[(start, end))`` of consecutive identical,
    scannable layers in a sequential stack. A preprocessor on an inner
    member breaks the run (its reshape is part of the program); one on
    the head is fine — it applies before the run is entered."""
    pre = preprocessors or {}
    runs: List[Tuple[int, int]] = []
    i, n = 0, len(layers)
    while i < n:
        if not scannable_layer(layers[i]):
            i += 1
            continue
        sig = layer_scan_signature(layers[i])
        j = i + 1
        while (
            j < n
            and j not in pre
            and scannable_layer(layers[j])
            and layer_scan_signature(layers[j]) == sig
        ):
            j += 1
        if j - i >= min_run:
            runs.append((i, j))
        i = max(j, i + 1)
    return runs


def detect_vertex_chains(conf, topo) -> List[Tuple[int, int]]:
    """Scan-over-layers for the DAG engine: maximal linear chains
    ``[(start, end))`` over consecutive TOPO positions where every
    member is a single-input, preprocessor-less LayerVertex with an
    identical scannable layer config, each inner member feeds ONLY the
    next, and no member is an output vertex. (Consecutive topo
    positions keep the per-layer PRNG fold-in indices a contiguous
    range, bitwise-matching the unrolled walk.)"""
    from deeplearning4j_tpu.nn.conf.graph_conf import LayerVertex

    consumers: Dict[str, int] = {}
    for name in topo:
        for s in conf.vertex_inputs.get(name, []):
            consumers[s] = consumers.get(s, 0) + 1

    def eligible(name: str) -> bool:
        v = conf.vertices[name]
        return (
            isinstance(v, LayerVertex)
            and v.preprocessor is None
            and name not in conf.outputs
            and len(conf.vertex_inputs.get(name, [])) == 1
            and scannable_layer(v.layer_conf)
        )

    chains: List[Tuple[int, int]] = []
    i, n = 0, len(topo)
    while i < n:
        if not eligible(topo[i]):
            i += 1
            continue
        sig = layer_scan_signature(conf.vertices[topo[i]].layer_conf)
        j = i
        while (
            j + 1 < n
            and eligible(topo[j + 1])
            and tuple(conf.vertex_inputs[topo[j + 1]]) == (topo[j],)
            and consumers.get(topo[j], 0) == 1
            and layer_scan_signature(
                conf.vertices[topo[j + 1]].layer_conf
            ) == sig
        ):
            j += 1
        if j > i:
            chains.append((i, j + 1))
        i = max(j + 1, i + 1)
    return chains


def apply_layer_run(layer, names, params, x, *, train, rng, idx0,
                    mask=None, remat: str = "none"):
    """Apply ``len(names)`` identical layers as ONE ``lax.scan`` over
    their stacked params. The run body is traced once, so the HLO for
    a depth-d run is O(1) instead of O(d) — the compile-time win. The
    per-layer PRNG keys are the same ``fold_in(rng, layer_index)``
    stream the unrolled walk draws, so dropout/DropConnect masks are
    bitwise identical with the transform on or off."""
    pnames = list(params[names[0]])
    stacked = {
        pn: jnp.stack([params[n][pn] for n in names]) for pn in pnames
    }
    k = len(names)
    rngs = None
    if rng is not None:
        rngs = jax.vmap(
            lambda i: jax.random.fold_in(rng, i)
        )(idx0 + jnp.arange(k))

    def body(h, per):
        p, r = per
        y, _ = layer.apply(p, h, {}, train=train, rng=r, mask=mask)
        return y, None

    body = maybe_remat(body, remat if train else "none")
    out, _ = jax.lax.scan(body, x, (stacked, rngs))
    return out


def with_tied_params(layer, layer_names, params, name) -> dict:
    """``params[name]`` with the arrays ``layer.tied_params()`` names
    laid in under their local names: ``(local, layer index, param)``
    reads another layer's array (an embedding shared with the output
    side). The owner keeps the leaf; its gradient is the sum over
    every use, as autodiff gives it."""
    tied = {local: params[layer_names[idx]][pn]
            for local, idx, pn in layer.tied_params()}
    return {**params[name], **tied} if tied else params[name]


def run_is_ready(names, params, state) -> bool:
    """Trace-time gate for a detected run: params exist (a run of
    param-less layers gives the scan nothing to iterate) and no member
    carries live state (streaming KV caches in ``rnn_time_step`` fall
    back to the unrolled walk)."""
    return bool(params.get(names[0])) and all(
        not state.get(n) for n in names
    )


# ---------------------------------------------------------------------------
# the sequential pure forward (MultiLayerNetwork's apply)
# ---------------------------------------------------------------------------


def sequential_forward(conf, layer_names, params, state, x, *,
                       train: bool, rng, upto: Optional[int] = None,
                       collect: bool = False, fmask=None,
                       scan_layers: bool = False, remat: str = "none",
                       runs: Sequence[Tuple[int, int]] = ()):
    """Pure forward through layers [0, upto]; returns (activation,
    preout of last executed layer, new_state, [activations]).

    ``fmask``: [batch, time] features mask threaded to recurrent
    layers (reference ``setLayerMaskArrays``). ``scan_layers``/
    ``remat``/``runs`` are the whole-net transform knobs — with all
    off this is exactly the classic unrolled walk."""
    from deeplearning4j_tpu.nn.conf.preprocessors import ShapeContext

    cdt = compute_dtype_of(conf)
    if cdt != dtype_of(conf):
        # mixed precision: master params stay in the storage dtype
        # (grads flow back through the cast, so the updater applies
        # them in master precision); compute runs in cdt
        params = cast_floats(params, cdt)
        if not conf.layers[0].takes_indices():
            # indices ride as they came: a narrower float would merge
            # neighbouring ids
            x = cast_floats(x, cdt)
        fmask = cast_floats(fmask, cdt) if fmask is not None else None
    t = x.shape[2] if x.ndim == 3 else -1
    ctx = ShapeContext(batch=x.shape[0], time=t)
    n = len(conf.layers) if upto is None else upto + 1
    new_state = dict(state)
    acts: List[Any] = []
    preout = None
    # collect/upto need every per-layer activation — runs disabled
    run_at = (
        {s: e for s, e in runs}
        if scan_layers and not collect and upto is None else {}
    )
    rem = remat if train else "none"
    i = 0
    while i < n:
        name = layer_names[i]
        layer = conf.layers[i]
        if i in conf.preprocessors:
            x = conf.preprocessors[i].preprocess(x, ctx)
        end = run_at.get(i)
        if end is not None and end <= n:
            names = layer_names[i:end]
            if run_is_ready(names, params, state):
                x = apply_layer_run(
                    layer, names, params, x, train=train, rng=rng,
                    idx0=i, mask=fmask, remat=rem,
                )
                for rn in names:
                    new_state[rn] = state.get(rn, {})
                i = end
                continue
        lrng = jax.random.fold_in(rng, i) if rng is not None else None
        lparams = with_tied_params(layer, layer_names, params, name)
        if i == n - 1 and layer.scores_input():
            # the layer computes its loss from its input itself
            # (``sequential_score`` hands it the labels)
            preout = layer.maybe_dropout(x, train=train, rng=lrng)
        elif (i == n - 1 and hasattr(layer, "pre_output")
              and layer.has_loss()):
            xin = layer.maybe_dropout(x, train=train, rng=lrng)
            # same lrng as apply -> identical DropConnect mask
            pw = layer.maybe_drop_connect(
                params[name], train=train, rng=lrng
            )
            preout = layer.pre_output(pw, xin)

        def apply_one(p, h, st, *, _layer=layer, _rng=lrng):
            return _layer.apply(
                p, h, st, train=train, rng=_rng, mask=fmask
            )

        if rem != "none" and not layer.has_loss():
            apply_one = maybe_remat(apply_one, rem)
        x, st = apply_one(lparams, x, state.get(name, {}))
        new_state[name] = st
        if collect:
            acts.append(x)
        i += 1
    return x, preout, new_state, acts


def sequential_score(conf, layer_names, params, state, x, labels,
                     mask, rng, *, train: bool, fmask=None,
                     scan_layers: bool = False, remat: str = "none",
                     runs: Sequence[Tuple[int, int]] = ()):
    """Loss score incl. L1/L2 penalty (reference
    computeGradientAndScore adds calcL1/calcL2 to the loss). ``mask``
    is the labels mask (falls back to ``fmask`` for 3-d labels, like
    the reference's output-layer masking)."""
    from deeplearning4j_tpu.nn import losses as losses_mod

    out, preout, new_state, _ = sequential_forward(
        conf, layer_names, params, state, x, train=train, rng=rng,
        fmask=fmask, scan_layers=scan_layers, remat=remat, runs=runs,
    )
    last = conf.layers[-1]
    if not last.has_loss():
        raise ValueError(
            "Last layer has no loss function; use an OutputLayer/LossLayer"
        )
    if preout is None:
        preout = out
    loss_mask = mask
    if loss_mask is None and labels.ndim == 3:
        loss_mask = fmask
    if last.scores_input():
        last_name = layer_names[-1]
        lparams = with_tied_params(last, layer_names, params, last_name)
        cdt = compute_dtype_of(conf)
        if cdt != dtype_of(conf):
            lparams = cast_floats(lparams, cdt)
        score, new_state[last_name] = last.score_input(
            lparams, preout, labels, state.get(last_name, {}),
            mask=loss_mask, train=train,
            rng=(jax.random.fold_in(rng, len(layer_names) - 1)
                 if rng is not None else None),
            remat=remat if train else "none")
    else:
        score = losses_mod.score(
            last.loss, labels, preout, last.activation, loss_mask, True
        )
    reg = 0.0
    for lname, layer in zip(layer_names, conf.layers):
        reg = reg + reg_penalty(layer, params[lname])
    return score + reg, new_state


# ---------------------------------------------------------------------------
# dynamic loss scaling (compute_dtype="float16")
# ---------------------------------------------------------------------------

DEFAULT_LOSS_SCALE = 2.0 ** 15
LOSS_SCALE_GROWTH_INTERVAL = 2000
MAX_LOSS_SCALE = 2.0 ** 24


def loss_scale_state(initial: float = DEFAULT_LOSS_SCALE) -> dict:
    """Device-resident dynamic loss-scale state threaded through the
    jitted step: current scale, clean steps since the last change,
    cumulative overflow count (read lazily by telemetry — no per-step
    host sync)."""
    return {
        "scale": jnp.asarray(float(initial), jnp.float32),
        "good_steps": jnp.asarray(0, jnp.int32),
        "overflows": jnp.asarray(0, jnp.int32),
    }


def _scale_tree(tree, factor):
    return jax.tree_util.tree_map(
        lambda g: (
            g * factor.astype(g.dtype)
            if jnp.issubdtype(jnp.asarray(g).dtype, jnp.inexact)
            else g
        ),
        tree,
    )


# ---------------------------------------------------------------------------
# ZeRO-1 optimizer-state sharding (flattened-leaf layout)
# ---------------------------------------------------------------------------
#
# The data-parallel trainer replicates updater state (Adam/RMSProp
# moments) on every device, so its HBM cost is O(params) per chip no
# matter how wide the mesh is. The zero layout instead stores each
# state leaf as a 1-d vector, zero-padded to a multiple of the shard
# count and sharded P("data"): each device holds 1/N of every moment.
# The updater rules are elementwise, so running them on the flat
# vectors is bitwise the canonical-shape math, and the padding slots
# (grad 0, state 0) provably produce step 0 / state 0 under every rule
# — the trajectory is bitwise identical to the replicated baseline.
# Checkpoints/snapshots always store the CANONICAL layout
# (zero_gather_updater_state), so a save on an 8-device mesh restores
# bitwise on 4 or 1.

_ZERO_GATHER_MS = None


def _zero_gather_summary():
    global _ZERO_GATHER_MS
    if _ZERO_GATHER_MS is None:
        from deeplearning4j_tpu.observability.metrics import (
            default_registry,
        )

        _ZERO_GATHER_MS = default_registry().summary(
            "zero_allgather_ms",
            help="host gather of zero-sharded optimizer state back to "
                 "canonical per-param shapes (checkpoint/snapshot/"
                 "re-shard path, ms)",
        )._default()
    return _ZERO_GATHER_MS


def zero_flat_size(shape, shards: int) -> int:
    """Padded flat length of one leaf under the zero layout: the
    element count rounded up to a multiple of the shard count so
    ``P("data")`` splits it evenly."""
    n = int(np.prod(shape)) if len(shape) else 1
    return -(-n // int(shards)) * int(shards)


def zero_flatten_leaf(a, shards: int):
    """Canonical leaf -> flat zero-padded vector (pure; runs in-jit)."""
    v = jnp.reshape(a, (-1,))
    pad = zero_flat_size(a.shape, shards) - v.shape[0]
    if pad:
        v = jnp.concatenate([v, jnp.zeros((pad,), v.dtype)])
    return v


def zero_unflatten_leaf(v, shape):
    """Inverse of ``zero_flatten_leaf``: drop the padding, restore the
    canonical shape."""
    n = int(np.prod(shape)) if len(shape) else 1
    return jnp.reshape(v[:n], shape)


def zero_layout_closures(zero_layout):
    """(flatten, unflatten) for a ``{"shards": n}`` layout, or
    ``(None, None)`` — the pair ``MultiLayerUpdaterDef.update`` takes."""
    if not zero_layout:
        return None, None
    shards = int(zero_layout["shards"])
    return (lambda a: zero_flatten_leaf(a, shards)), zero_unflatten_leaf


def _host_gather_leaf(a):
    """Device->host copy of one (possibly sharded) leaf. A leaf whose
    shards span OTHER processes (zero on a multi-process mesh) is not
    locally readable — replicate it first via a jitted identity, a
    real all-gather collective, which is safe because every caller
    (snapshot push, checkpoint save, re-shard) runs in barrier-kept
    lockstep across ranks."""
    import jax

    if isinstance(a, jax.Array) and not a.is_fully_addressable:
        from jax.sharding import NamedSharding, PartitionSpec

        mesh = a.sharding.mesh
        a = jax.jit(
            lambda x: x,
            out_shardings=NamedSharding(mesh, PartitionSpec()),
        )(a)
    return np.asarray(a)


def host_snapshot_tree(tree):
    """Buffer-isolated host copy of a pytree — the ``SnapshotRing``
    copy discipline, shared with checkpoint snapshots: every leaf
    comes back as a fresh ``np.ndarray`` sharing no buffers with the
    input, so the caller may hand the copy to a background thread
    (write-behind checkpointing) or park it in host RAM (snapshot
    ring) while the live tree keeps training. Cross-process-sharded
    leaves ride ``_host_gather_leaf``'s replicating collective, so on
    a multi-process mesh this must run in lockstep across ranks."""
    import jax

    def _copy(a):
        if isinstance(a, np.ndarray):
            return np.array(a)
        return np.asarray(_host_gather_leaf(a))

    return jax.tree_util.tree_map(_copy, tree)


def zero_gather_updater_state(upd_state, params):
    """Gather a zero-laid-out updater state back to canonical
    per-param shapes on HOST (numpy) — the checkpoint / snapshot /
    cross-mesh re-shard form. Idempotent: a leaf already in canonical
    shape passes through (modulo the host copy), so callers may apply
    it without knowing the live layout; ``np.asarray`` on a sharded
    leaf performs the device->host all-gather (cross-process shards
    ride a replicating collective first, see ``_host_gather_leaf``)."""
    t0 = time.perf_counter()
    out: Dict[str, Any] = {}
    for ln, lp in upd_state.items():
        out[ln] = {}
        for pn, tup in lp.items():
            shape = tuple(np.shape(params[ln][pn]))
            n = int(np.prod(shape)) if len(shape) else 1
            gathered = []
            for a in tup:
                h = _host_gather_leaf(a)
                if h.shape != shape:
                    h = h.reshape(-1)[:n].reshape(shape)
                gathered.append(h)
            out[ln][pn] = tuple(gathered)
    _zero_gather_summary().observe(
        (time.perf_counter() - t0) * 1000.0
    )
    return out


# ---------------------------------------------------------------------------
# in-jit gradient accumulation
# ---------------------------------------------------------------------------

_GRAD_ACCUM_GAUGE = None


def note_grad_accum(k: int) -> None:
    """Publish the microbatch count an optimizer step accumulates."""
    global _GRAD_ACCUM_GAUGE
    if _GRAD_ACCUM_GAUGE is None:
        from deeplearning4j_tpu.observability.metrics import (
            default_registry,
        )

        _GRAD_ACCUM_GAUGE = default_registry().gauge(
            "grad_accum_microbatches",
            help="microbatches accumulated in-jit per optimizer step "
                 "(1 = plain single-batch steps)",
        )._default()
    _GRAD_ACCUM_GAUGE.set(float(k))


def _model_layer_confs(model):
    conf = model.conf
    if hasattr(conf, "vertices"):
        return [
            v.layer_conf for v in conf.vertices.values()
            if getattr(v, "layer_conf", None) is not None
        ]
    return list(conf.layers)


def check_grad_accum(model, k) -> int:
    """Validate a ``grad_accum`` knob for ``model``: a positive
    microbatch count, and no batch-statistics layer (each microbatch
    would see its own BatchNormalization stats — different math from
    the full batch, so the config is rejected rather than silently
    diverging)."""
    k = int(k)
    if k < 1:
        raise ValueError(f"grad_accum must be >= 1, got {k}")
    if k > 1 and any(
        layer.uses_batch_statistics()
        for layer in _model_layer_confs(model)
    ):
        raise ValueError(
            "grad_accum > 1 is incompatible with batch-statistics "
            "layers (BatchNormalization): each microbatch would "
            "compute its own batch stats, changing the math vs the "
            "single-big-batch step"
        )
    return k


def set_grad_accum(model, k) -> None:
    """Set the in-jit gradient-accumulation knob on either engine;
    a change invalidates every compiled step that bakes it in."""
    k = check_grad_accum(model, k)
    if k != getattr(model, "grad_accum", 1):
        model.grad_accum = k
        model._jit_step = None
        model._jit_multi_step = None
        model._jit_megastep = None
        if hasattr(model, "_jit_tbptt_multi_step"):
            model._jit_tbptt_multi_step = None
    note_grad_accum(k)


def check_grad_accum_batch(k: int, batch_n: int) -> None:
    if k > 1 and batch_n % k != 0:
        raise ValueError(
            f"grad_accum={k} needs the batch to split into equal "
            f"microbatches; got batch size {batch_n}"
        )


def accum_grad_step(score_fn, params, state, x, labels, mask, fmask,
                    rng, k: int, scale=None,
                    recurrent_names: Sequence[str] = ()):
    """``grad_step`` over K microbatches fused into one program: a
    ``lax.scan`` splits the batch leaves ``[n, ...] -> [k, n/k, ...]``
    (contiguous row blocks — microbatch j is rows ``[j*n/k, (j+1)*
    n/k)``), accumulates f32 gradients + the f32 score, and returns
    their means — ``((score, new_state), grads)``, the same contract
    as ``grad_step``, so one updater apply follows K backward passes
    at one microbatch's activation memory. ``1/k`` is exact for
    power-of-two k; per-microbatch PRNG keys fold the microbatch
    index into ``rng``. Recurrent carry entries are restored per
    microbatch (standard-backprop semantics + a constant scan-carry
    structure), matching ``build_multi_step``."""

    def split(a):
        return jnp.reshape(a, (k, a.shape[0] // k) + a.shape[1:])

    micro = jax.tree_util.tree_map(split, (x, labels, mask, fmask))
    rngs = None
    if rng is not None:
        rngs = jax.vmap(
            lambda j: jax.random.fold_in(rng, j)
        )(jnp.arange(k))
    acc0 = jax.tree_util.tree_map(
        lambda p: jnp.zeros(jnp.shape(p), jnp.float32), params
    )

    def body(carry, per):
        acc, ssum, st = carry
        (xj, yj, mj, fj), rj = per
        (score, new_st), grads = grad_step(
            score_fn, params, st, xj, yj, mj, fj, rj, scale=scale
        )
        new_st = dict(new_st)
        for name in recurrent_names:
            if name in new_st:
                new_st[name] = st[name]
        acc = jax.tree_util.tree_map(
            lambda a, g: a + g.astype(jnp.float32), acc, grads
        )
        return (acc, ssum + score.astype(jnp.float32), new_st), None

    (acc, ssum, last_state), _ = jax.lax.scan(
        body, (acc0, jnp.zeros((), jnp.float32), state),
        (micro, rngs),
    )
    inv = 1.0 / k
    grads = jax.tree_util.tree_map(
        lambda a, p: (a * inv).astype(jnp.asarray(p).dtype),
        acc, params,
    )
    return (ssum * inv, last_state), grads


# ---------------------------------------------------------------------------
# jitted step builders (ONE implementation for both engines)
# ---------------------------------------------------------------------------


def grad_step(score_fn, params, state, x, labels, mask, fmask, rng,
              scale=None):
    """The forward+backward half every step flavor shares:
    ``((score, new_state), grads)`` of the engine's pure score. With
    ``scale`` (dynamic loss scaling) the loss is scaled in f32 before
    the backward pass so small f16 gradients stay representable; the
    caller unscales."""
    def loss_fn(p):
        s, new_state = score_fn(p, state, x, labels, mask, fmask, rng)
        if scale is not None:
            s = s.astype(jnp.float32) * scale
        return s, new_state

    return jax.value_and_grad(loss_fn, has_aux=True)(params)


def finish_step(updater, grads, score, new_state, params, upd_state,
                state, lrs, t, *, guarded: bool, telemetry: bool,
                ls=None, flatten=None, unflatten=None,
                sg=None, sg_cfg=None):
    """The post-gradient half shared by the engine steps AND the
    distributed trainer's shard_map/GSPMD steps: dynamic loss-scale
    unscale/adjust (when ``ls``, the incoming loss-scale state dict,
    is given — the caller already scaled the loss via ``grad_step``'s
    ``scale``), updater application (optionally through the zero
    flattened-leaf layout via ``flatten``/``unflatten``), optional
    telemetry grad-norm, optional in-jit divergence-guard select —
    statistical when ``sg``/``sg_cfg`` (the incoming EWMA state dict
    + its ``StatGuardConfig``) ride along: a finite-but-anomalous
    loss or grad-norm is suppressed by the SAME select.
    Returns the step output tuple
    ``(params, upd_state, state, score[, grad_norm]
    [, loss_scale_state][, stat_guard_state][, ok])``."""
    from deeplearning4j_tpu.resilience.guard import (
        divergence_ok,
        grad_global_norm_sq,
        select_updates,
        stat_guard_update,
    )

    tail = ()
    if ls is not None:
        scale = ls["scale"]
        inv = 1.0 / scale
        grads = _scale_tree(grads, inv)
        score = score * inv
        # the overflow probe: a non-finite gradient skips the update
        # in-jit and halves the scale; growth_interval clean steps
        # double it back (capped)
        finite = jnp.isfinite(grad_global_norm_sq(grads))
        new_params, new_upd = updater.update(
            grads, upd_state, params, lrs, t,
            flatten=flatten, unflatten=unflatten,
        )
        new_params, new_upd, new_state = select_updates(
            finite, new_params, params, new_upd, upd_state,
            new_state, state,
        )
        good = jnp.where(finite, ls["good_steps"] + 1, 0)
        grow = good >= LOSS_SCALE_GROWTH_INTERVAL
        new_scale = jnp.where(
            finite,
            jnp.where(
                grow,
                jnp.minimum(scale * 2.0, MAX_LOSS_SCALE),
                scale,
            ),
            jnp.maximum(scale * 0.5, 1.0),
        )
        tail = ({
            "scale": new_scale,
            "good_steps": jnp.where(grow, 0, good),
            "overflows": ls["overflows"]
            + (1 - finite.astype(jnp.int32)),
        },)
    else:
        new_params, new_upd = updater.update(
            grads, upd_state, params, lrs, t,
            flatten=flatten, unflatten=unflatten,
        )
    extras = ()
    gnorm = None
    if telemetry:
        gnorm = jnp.sqrt(grad_global_norm_sq(grads))
        extras = (gnorm,)
    if not guarded:
        return (new_params, new_upd, new_state, score) + extras + tail
    ok = divergence_ok(score, grads)
    sg_tail = ()
    if sg is not None:
        if gnorm is None:
            gnorm = jnp.sqrt(grad_global_norm_sq(grads))
        sg_ok, new_sg = stat_guard_update(sg, sg_cfg, score, gnorm, ok)
        ok = jnp.logical_and(ok, sg_ok)
        sg_tail = (new_sg,)
    new_params, new_upd, new_state = select_updates(
        ok, new_params, params, new_upd, upd_state, new_state, state,
    )
    return (
        (new_params, new_upd, new_state, score)
        + extras + tail + sg_tail + (ok,)
    )


def build_step(score_fn, updater, *, cast=None, guarded: bool = False,
               telemetry: bool = False, loss_scale: bool = False,
               grad_accum: int = 1,
               recurrent_names: Sequence[str] = (),
               zero_layout=None, stat_guard=None) -> Callable:
    """ONE jitted SGD train step for both engines.

    ``score_fn(params, state, x, labels, mask, fmask, rng) ->
    (score, new_state)`` is the engine's pure forward+loss; ``cast``
    is its in-jit cast-on-device hook (integer inputs ride in native
    width and cast here). Step output layout:
    ``params, upd_state, state, score[, grad_norm][, loss_scale_state]
    [, ok]`` — unpacked by ``apply_step_out``. With ``loss_scale``
    the step takes the loss-scale state dict as a trailing argument,
    skips the update in-jit on a non-finite gradient (the overflow
    probe), and adjusts the scale — no host round trip. With
    ``grad_accum=K`` the forward/backward runs as a ``lax.scan`` over
    K microbatches (``accum_grad_step``) before the ONE updater apply.
    ``zero_layout`` (``{"shards": n}``) runs the updater through the
    zero flattened-leaf layout — ``upd_state`` leaves are 1-d padded
    vectors (see the ZeRO section above). ``stat_guard`` (a
    ``StatGuardConfig``; requires ``guarded``) threads the statistical
    anomaly guard's EWMA state as a further trailing argument, after
    the loss-scale state."""
    if stat_guard is not None and not guarded:
        raise ValueError(
            "stat_guard requires guarded=True (it shares the "
            "divergence guard's in-jit select and ok flag)"
        )
    flatten, unflatten = zero_layout_closures(zero_layout)
    k = int(grad_accum)

    def step(params, upd_state, state, x, labels, mask, fmask, lrs, t,
             rng, *ls_args):
        if cast is not None:
            x, labels, mask, fmask = cast(x, labels, mask, fmask)
        ls = ls_args[0] if loss_scale else None
        sg = (
            ls_args[1 if loss_scale else 0]
            if stat_guard is not None else None
        )
        scale = ls["scale"] if loss_scale else None
        if k > 1:
            (score, new_state), grads = accum_grad_step(
                score_fn, params, state, x, labels, mask, fmask, rng,
                k, scale=scale, recurrent_names=recurrent_names,
            )
        else:
            (score, new_state), grads = grad_step(
                score_fn, params, state, x, labels, mask, fmask, rng,
                scale=scale,
            )
        return finish_step(
            updater, grads, score, new_state, params, upd_state,
            state, lrs, t, guarded=guarded, telemetry=telemetry,
            ls=ls, flatten=flatten, unflatten=unflatten,
            sg=sg, sg_cfg=stat_guard,
        )

    return jax.jit(step, donate_argnums=(0, 1, 2))


def apply_step_out(model, out):
    """Unpack one core step's output tuple (base 4 fields, plus the
    optional telemetry grad-norm, loss-scale state, stat-guard state,
    and guard ok flag) into model state; returns ``(score, ok)``."""
    model.params, model.updater_state, model.state = out[:3]
    score = out[3]
    i = 4
    if getattr(model, "_telemetry_grad_norm", False):
        model._last_grad_norm = out[i]
        i += 1
    if getattr(model, "_loss_scale_active", False):
        model._loss_scale_state = out[i]
        i += 1
    if stat_guard_active(model):
        model._stat_guard_state = out[i]
        i += 1
    ok = (
        out[i] if getattr(model, "divergence_guard", None) is not None
        else None
    )
    return score, ok


def build_multi_step(score_fn, updater, *, cast,
                     recurrent_names: Sequence[str] = (),
                     tbptt: bool = False, grad_accum: int = 1,
                     zero_layout=None) -> Callable:
    """k optimizer steps fused into ONE XLA program via lax.scan.

    The reference dispatches one native-op sequence per minibatch
    (SURVEY.md §3.1 hot loop); the per-dispatch latency is what bounds
    small-model throughput on TPU (host->device hop per step).
    Scanning k steps amortizes it k-fold: per-step PRNG keys and
    Adam's t are computed on device, lr schedules stay host-side
    (arbitrary Python) and ride in as a tiny stacked array.

    Standard mode restores the recurrent carry per minibatch
    (standard-backprop semantics). ``tbptt=True`` instead THREADS the
    carry through the scan and takes a per-step ``resets`` flag (one
    0/1 per step) that zeroes the carry at minibatch boundaries, so
    MANY minibatches' TBPTT chunk stacks ride in a single dispatch
    (the reference's host-side chunk loop, ``doTruncatedBPTT:1210``,
    pays a dispatch per chunk).

    ``grad_accum``/``zero_layout`` compose exactly as in
    ``build_step``: each scanned optimizer step accumulates K
    microbatch gradients, and the updater runs through the zero
    flattened-leaf layout (TBPTT mode excludes grad_accum — the
    recurrent carry threads BETWEEN chunks, so a chunk cannot split
    into independent microbatches)."""
    flatten, unflatten = zero_layout_closures(zero_layout)
    k_accum = int(grad_accum)
    if tbptt and k_accum > 1:
        raise ValueError(
            "grad_accum > 1 is incompatible with the fused TBPTT "
            "path: the recurrent carry threads between chunks"
        )

    def body(carry, per_step):
        params, upd_state, state = carry
        if tbptt:
            x, labels, mask, fmask, lrs, t, rng, reset = per_step
        else:
            x, labels, mask, fmask, lrs, t, rng = per_step
        x, labels, mask, fmask = cast(x, labels, mask, fmask)
        if tbptt:
            state = dict(state)
            keep = 1.0 - reset
            for name in recurrent_names:
                # reset==1 at a new minibatch's first chunk; v*0 is
                # bitwise the zeros the primed initial state holds
                state[name] = {
                    k2: v * keep.astype(v.dtype)
                    for k2, v in state[name].items()
                }
        if k_accum > 1:
            (score, new_state), grads = accum_grad_step(
                score_fn, params, state, x, labels, mask, fmask, rng,
                k_accum, recurrent_names=recurrent_names,
            )
        else:
            (score, new_state), grads = grad_step(
                score_fn, params, state, x, labels, mask, fmask, rng
            )
        new_params, new_upd = updater.update(
            grads, upd_state, params, lrs, t,
            flatten=flatten, unflatten=unflatten,
        )
        if not tbptt:
            # standard-backprop semantics: recurrent carry resets per
            # minibatch — keep the carry structure constant by
            # restoring the empty input entries
            for name in recurrent_names:
                new_state[name] = state[name]
        return (new_params, new_upd, new_state), score

    def multi_step(params, upd_state, state, xs, ys, masks, fmasks,
                   lr_stack, it0, base_key, *resets):
        k = jax.tree_util.tree_leaves(xs)[0].shape[0]
        ts = (it0 + 1 + jnp.arange(k)).astype(jnp.float32)
        rngs = jax.vmap(
            lambda i: jax.random.fold_in(base_key, i)
        )(it0 + jnp.arange(k))
        (params, upd_state, state), scores = jax.lax.scan(
            body, (params, upd_state, state),
            (xs, ys, masks, fmasks, lr_stack, ts, rngs) + resets,
        )
        # next chunk's it0, computed on device: the caller keeps it
        # resident so consecutive chunks transfer no host scalars
        return params, upd_state, state, scores, it0 + k

    return jax.jit(multi_step, donate_argnums=(0, 1, 2))


# ---------------------------------------------------------------------------
# the megastep executor: K full train steps + metric accumulation in
# ONE XLA dispatch
# ---------------------------------------------------------------------------
#
# build_multi_step fuses k steps but only for the bare step flavor
# (no guard / telemetry / loss scale / stat guard — _can_scan_steps
# refuses those configs). The megastep generalizes it: the scanned
# body is the FULL build_step body (grad_step/accum_grad_step +
# finish_step), so divergence-guard selects, the statistical guard's
# EWMA state, and the dynamic loss-scale state all thread through the
# scan carry, and the chunk's metrics (per-step scores, grad norms,
# guard ok flags, plus their on-device aggregates) come back in ONE
# readback instead of K host syncs. Because each scanned step is the
# same math as the per-step program, the trajectory is bitwise equal
# to the per-step loop (tier-1-asserted on both engines).


def build_megastep(score_fn, updater, *, cast,
                   recurrent_names: Sequence[str] = (),
                   guarded: bool = False, telemetry: bool = False,
                   loss_scale: bool = False, stat_guard=None,
                   grad_accum: int = 1, zero_layout=None,
                   flatten=None, unflatten=None,
                   jit: bool = True) -> Callable:
    """K optimizer steps fused into ONE XLA program, full step flavor.

    Signature of the returned function::

        megastep(params, upd_state, state, xs, ys, masks, fmasks,
                 lr_stack, it0, base_key[, ls_state][, sg_state])
        -> (params, upd_state, state, metrics, it0 + k)
           [+ (ls_state,)][+ (sg_state,)]

    ``metrics`` is the on-device accumulator dict read back once per
    chunk by ``megastep_readback``: ``scores`` [k], ``loss_sum``,
    ``examples``, plus ``grad_norms`` [k] under ``telemetry`` and
    ``oks`` [k] / ``guard_trips`` under ``guarded``. Per-step rng is
    ``fold_in(base_key, it0 + i)`` and Adam's t is ``it0 + 1 + i`` —
    identical to the per-step loop, so the trajectory is bitwise.
    ``flatten``/``unflatten`` override the ``zero_layout`` closures
    (the GSPMD trainer passes sharding-pinned ones); ``jit=False``
    returns the raw function for the trainer to wrap with explicit
    in/out shardings."""
    if stat_guard is not None and not guarded:
        raise ValueError(
            "stat_guard requires guarded=True (it shares the "
            "divergence guard's in-jit select and ok flag)"
        )
    if flatten is None and unflatten is None:
        flatten, unflatten = zero_layout_closures(zero_layout)
    k_accum = int(grad_accum)

    def body(carry, per_step):
        params, upd_state, state, ls, sg = carry
        x, labels, mask, fmask, lrs, t, rng = per_step
        if cast is not None:
            x, labels, mask, fmask = cast(x, labels, mask, fmask)
        scale = ls["scale"] if loss_scale else None
        if k_accum > 1:
            (score, new_state), grads = accum_grad_step(
                score_fn, params, state, x, labels, mask, fmask, rng,
                k_accum, scale=scale,
                recurrent_names=recurrent_names,
            )
        else:
            (score, new_state), grads = grad_step(
                score_fn, params, state, x, labels, mask, fmask, rng,
                scale=scale,
            )
        # standard-backprop semantics: recurrent carry resets per
        # minibatch; restoring the (empty) input entries BEFORE the
        # guard select keeps the carry structure constant
        new_state = dict(new_state)
        for name in recurrent_names:
            if name in new_state:
                new_state[name] = state[name]
        out = finish_step(
            updater, grads, score, new_state, params, upd_state,
            state, lrs, t, guarded=guarded, telemetry=telemetry,
            ls=ls if loss_scale else None,
            flatten=flatten, unflatten=unflatten,
            sg=sg if stat_guard is not None else None,
            sg_cfg=stat_guard,
        )
        new_params, new_upd, new_state, score = out[:4]
        i = 4
        per_out = {"score": score}
        if telemetry:
            per_out["grad_norm"] = out[i]
            i += 1
        new_ls = ls
        if loss_scale:
            new_ls = out[i]
            i += 1
        new_sg = sg
        if stat_guard is not None:
            new_sg = out[i]
            i += 1
        if guarded:
            per_out["ok"] = out[i]
        return (new_params, new_upd, new_state, new_ls, new_sg), per_out

    def megastep(params, upd_state, state, xs, ys, masks, fmasks,
                 lr_stack, it0, base_key, *extra):
        leaf = jax.tree_util.tree_leaves(xs)[0]
        k, rows = leaf.shape[0], leaf.shape[1]
        ts = (it0 + 1 + jnp.arange(k)).astype(jnp.float32)
        rngs = jax.vmap(
            lambda i: jax.random.fold_in(base_key, i)
        )(it0 + jnp.arange(k))
        i = 0
        ls = None
        if loss_scale:
            ls = extra[i]
            i += 1
        sg = extra[i] if stat_guard is not None else None
        (params, upd_state, state, ls, sg), per = jax.lax.scan(
            body, (params, upd_state, state, ls, sg),
            (xs, ys, masks, fmasks, lr_stack, ts, rngs),
        )
        scores = per["score"]
        metrics = {
            "scores": scores,
            "loss_sum": jnp.sum(scores.astype(jnp.float32)),
            "examples": jnp.asarray(k * rows, jnp.int32),
        }
        if telemetry:
            metrics["grad_norms"] = per["grad_norm"]
        if guarded:
            oks = per["ok"]
            metrics["oks"] = oks
            metrics["guard_trips"] = jnp.sum(1 - oks.astype(jnp.int32))
        tail = ()
        if loss_scale:
            tail += (ls,)
        if stat_guard is not None:
            tail += (sg,)
        return (params, upd_state, state, metrics, it0 + k) + tail

    if not jit:
        return megastep
    return jax.jit(megastep, donate_argnums=(0, 1, 2))


_MEGASTEP_GAUGE = None
_MEGASTEP_DISPATCHES = None
_MEGASTEP_READBACK_MS = None


def note_megastep(k: int) -> None:
    """Publish one fused megastep dispatch covering ``k`` steps."""
    global _MEGASTEP_GAUGE, _MEGASTEP_DISPATCHES
    if _MEGASTEP_GAUGE is None:
        from deeplearning4j_tpu.observability.metrics import (
            default_registry,
        )

        reg = default_registry()
        _MEGASTEP_GAUGE = reg.gauge(
            "megastep_chunk_size",
            help="optimizer steps fused into the last megastep "
                 "dispatch (K; trailing partial blocks show smaller)",
        )._default()
        _MEGASTEP_DISPATCHES = reg.counter(
            "megastep_dispatches_total",
            help="fused megastep dispatches executed (steps/dispatch "
                 "= iteration delta / this delta)",
        )._default()
    _MEGASTEP_GAUGE.set(float(k))
    _MEGASTEP_DISPATCHES.inc()


def megastep_readback(metrics):
    """THE designated host-readback site of the megastep path: one
    device->host transfer of the chunk's accumulated metric dict.
    ``scripts/lint_parity.py`` forbids every other host read inside
    the per-chunk driver (``run_megastep_chunk`` /
    ``fit_epoch_megastep``), so the host never re-enters the hot loop
    between dispatches."""
    global _MEGASTEP_READBACK_MS
    if _MEGASTEP_READBACK_MS is None:
        from deeplearning4j_tpu.observability.metrics import (
            default_registry,
        )

        _MEGASTEP_READBACK_MS = default_registry().summary(
            "megastep_readback_ms",
            help="per-chunk device->host readback of the megastep "
                 "metric accumulator (ms; one per K fused steps)",
        )._default()
    t0 = time.perf_counter()
    host = jax.device_get(metrics)
    _MEGASTEP_READBACK_MS.observe((time.perf_counter() - t0) * 1000.0)
    return host


def build_pretrain_step(layer, name: str, upd_def) -> Callable:
    """Jitted single-layer pretrain update; takes the layer's input
    tensor precomputed (the frozen lower stack runs once per batch,
    not once per optimizer iteration — reference feedForwardToLayer
    once per batch). Shared verbatim by both engines."""

    def step(lparams, upd_state, xin, lrs, t, rng):
        def loss_fn(p):
            return layer.pretrain_loss(p, xin, rng) + reg_penalty(
                layer, p
            )

        loss, grads = jax.value_and_grad(loss_fn)(lparams)
        new_p, new_upd = upd_def.update(
            {name: grads}, upd_state, {name: lparams}, lrs, t
        )
        return new_p[name], new_upd, loss

    return jax.jit(step, donate_argnums=(0, 1))


# ---------------------------------------------------------------------------
# fit drivers (epoch loop / scan-chunk grouping / device-cached epochs)
# ---------------------------------------------------------------------------


def driver_span(model, name: str, attrs: Optional[dict] = None):
    """A span at one of the fit drivers' boundaries: under the open
    ``train.step`` while a ``StepProfiler`` is installed, else under
    the span ``fit_batches`` left on the model (``fit.epoch``; ``fit``
    on the device-cached path), and a root of its own where
    ``fit_minibatch`` is called outside ``fit()``. ``NOOP_SPAN``
    unless the global tracer records."""
    prof = _prof_mod.get_active_profiler()
    parent = prof.open_span() if prof is not None else None
    if parent is None:
        parent = getattr(model, "_fit_span", None)
    return get_tracer().start_span(name, parent=parent, attrs=attrs)


def feed(model, iterator):
    """``iter(iterator)`` for the epoch drivers, with each ``next()``
    of whatever the user handed in timed as one ``fit.feed_wait`` span
    (attr ``batches``: the minibatches the item holds; 0, with status
    ``exhausted``, for the last, empty-handed call). An installed
    ``StepProfiler`` opens its record where the first wait of a step
    or chunk opens, and takes the wait as ``input_stall_ms``."""
    it = iter(iterator)
    while True:
        prof = _prof_mod.get_active_profiler()
        opened = prof is not None and prof.open_feed(
            parent=getattr(model, "_fit_span", None))
        span = driver_span(model, "fit.feed_wait")
        t0 = time.perf_counter()
        try:
            ds = next(it)
        except StopIteration:
            span.set_attr("batches", 0).end("exhausted")
            if opened:
                prof.abandon_step("unused")
            return
        except BaseException:
            span.end("error")
            raise
        span.set_attr("batches", getattr(ds, "k", 1)).end()
        if prof is not None:
            prof.note_input_wait_ms((time.perf_counter() - t0) * 1e3)
        yield ds


def host_bytes(item) -> int:
    """Bytes of an item's features and labels that are still on the
    host: what a ``fit.stack`` span copies to the device, its
    ``bytes``."""
    from deeplearning4j_tpu.datasets.api import payload_arrays

    return sum(int(getattr(a, "nbytes", 0) or 0)
               for a in payload_arrays(item)
               if not isinstance(a, jax.Array))


def place_batch(model, ds):
    """The minibatch as the scan path keeps it until its chunk is
    full: every array on the device (``place``), copied there as the
    batch arrives so that the transfers run while ``fit()`` waits for
    the next batches and no chunk is ever assembled on the host. One
    ``fit.stack`` span (``batches`` 1, ``bytes`` copied, ``part``
    ``place``). A batch that is device-resident already is returned as
    it is; the host batch stays what an activation listener reads."""
    from deeplearning4j_tpu.datasets.api import PlacedDataSet

    if _wants_last_features(model):
        model._last_features = ds.features
    dtype = dtype_of(model.conf)

    def put(v):
        if v is None:
            return None
        if isinstance(v, (list, tuple)):
            return [put(a) for a in v]
        return place(v, dtype)

    with driver_span(model, "fit.stack", {
            "batches": 1, "bytes": host_bytes(ds), "part": "place"}):
        return PlacedDataSet(
            features=put(ds.features), labels=put(ds.labels),
            features_mask=put(getattr(ds, "features_masks", None)
                              or getattr(ds, "features_mask", None)),
            labels_mask=put(getattr(ds, "labels_masks", None)
                            or getattr(ds, "labels_mask", None)),
        )


def stack_chunk(model, batches):
    """``model._stack_chunk(batches)`` as one ``fit.stack`` span
    (``part`` ``stack``): the enqueue of the program that stacks the
    chunk in device memory, and of the copies of whatever is still on
    the host (``bytes``: nothing on the scan path, whose batches
    ``place_batch`` has placed)."""
    with driver_span(model, "fit.stack", {
            "batches": len(batches), "part": "stack",
            "bytes": sum(host_bytes(b) for b in batches)}):
        return model._stack_chunk(batches)


# scan chunks enqueued and not known to have finished, at most: one
# runs, one is queued behind it (the host builds the third meanwhile)
SCAN_CHUNKS_AHEAD = 2


def await_scan_slot(model) -> None:
    """The scan path's bound on how far the host runs ahead of the
    device. Nothing else in it waits: a chunk is enqueued and the next
    one built at once, so an iterator that never blocks would queue
    chunks without limit, each with its inputs in device memory.
    Called before a chunk is enqueued, this waits for the scores of
    the chunk ``SCAN_CHUNKS_AHEAD`` before it (``run_scan_chunk`` keeps
    them in ``model._scan_inflight``): one chunk runs, one is queued,
    the host builds the third. The wait is a ``fit.backpressure``
    span."""
    inflight = model._scan_inflight
    if len(inflight) == inflight.maxlen:
        with driver_span(model, "fit.backpressure"):
            jax.block_until_ready(inflight[0])


@contextlib.contextmanager
def dispatch_span(model, prof, steps: int, it0: int, rows: int):
    """The enqueue of one program run as a ``fit.dispatch`` span (a
    compile, or a full launch queue, shows here). ``prof``, where the
    caller passes its ``StepProfiler``, takes the same interval as
    ``dispatch_ms``."""
    t0 = time.perf_counter()
    with driver_span(model, "fit.dispatch", {
            "steps": steps, "rows": rows, "first_step": it0 + 1}):
        yield
    if prof is not None:
        prof.note_dispatch_ms((time.perf_counter() - t0) * 1e3)


@contextlib.contextmanager
def listeners_span(model, prof, steps: int):
    """The listeners' callbacks for ``steps`` optimizer steps as one
    ``fit.listeners`` span, and as the profiler's ``listener_ms``."""
    t0 = time.perf_counter()
    with driver_span(model, "fit.listeners", {"steps": steps}):
        yield
    if prof is not None:
        prof.note_listener_ms((time.perf_counter() - t0) * 1e3)


def build_scan_plan(seq, sig_fn, stack_fn, scan_chunk: int):
    """Group consecutive same-signature minibatches into fused chunks
    (the same boundaries ``fit_epoch_scan`` produces). Returns a list
    of ``("chunk", stacked_device_arrays, last_host_batch)`` /
    ``("single", ds, ds)`` entries, shared by MultiLayerNetwork and
    ComputationGraph."""
    plan: List[Any] = []
    buf: List[Any] = []
    sig = None

    def flush(batches):
        if len(batches) == 1:
            plan.append(("single", batches[0], batches[0]))
        elif batches:
            plan.append(("chunk", stack_fn(batches), batches[-1]))

    for ds in seq:
        s = sig_fn(ds)
        if buf and (s != sig or len(buf) >= scan_chunk):
            flush(buf)
            buf = []
        sig = s
        buf.append(ds)
    flush(buf)
    return plan


def cached_epoch_plan(model, iterator, epochs: int, arrays_of):
    """Shared eligibility gate + HBM size accounting + plan building
    for the device-cached multi-epoch fit path (MultiLayerNetwork and
    ComputationGraph). ``arrays_of(ds)`` yields every array the stacked
    chunks will hold. Returns the scan plan, or None when the caller
    must stream (single epoch, iterator input, non-scannable config, or
    dataset larger than ``model.device_cache_bytes``)."""
    if (
        epochs <= 1
        or not isinstance(iterator, (list, tuple))
        or len(iterator) == 0
        or not model._can_scan_steps()
        or model.scan_chunk <= 1
    ):
        return None
    total = 0
    for ds in iterator:
        if not hasattr(ds, "features"):
            return None
        for a in arrays_of(ds):
            if a is not None:
                total += nbytes(a)
    if total > model.device_cache_bytes:
        return None
    return build_scan_plan(
        iterator, model._ds_scan_sig, model._stack_chunk,
        model.scan_chunk,
    )


def _wants_last_features(model) -> bool:
    fn = getattr(model, "_wants_last_features", None)
    return bool(fn()) if fn is not None else False


def _chunk_rows(xs) -> int:
    """Rows per sub-step of a stacked [k, b, ...] chunk payload."""
    leaf = jax.tree_util.tree_leaves(xs)[0]
    return int(leaf.shape[1]) if getattr(leaf, "ndim", 0) > 1 else 0


def run_scan_chunk(model, stacked) -> None:
    """One fused k-step dispatch from pre-stacked device arrays
    ``(x, y, labels_mask, features_mask, k)`` — the same driver for
    both engines (the arrays are plain arrays for the sequential
    engine, lists for the DAG engine)."""
    xs, ys, masks, fmasks, k = stacked
    it0 = model.iteration_count
    prof = _prof_mod.get_active_profiler()
    if prof is not None:
        # one fused dispatch = one profiler "step" covering k
        # optimizer steps (the record carries the final step index);
        # on the streaming path the feed opened it at its first wait
        prof.begin_step(it0 + k,
                        parent=getattr(model, "_fit_span", None))
    rows = k * _chunk_rows(xs)
    with dispatch_span(model, prof, k, it0, rows):
        lr_stack, it0_dev = scan_consts(model, k, it0)
        if model._jit_multi_step is None:
            model._jit_multi_step = model._build_multi_step()
        (
            model.params, model.updater_state, model.state, scores,
            it0_next,
        ) = model._jit_multi_step(
            model.params, model.updater_state, model.state,
            xs, ys, masks, fmasks, lr_stack, it0_dev, model._base_key,
        )
        note_it0(model, it0_next, it0 + k)
    model._scan_inflight.append(scores)  # await_scan_slot's markers
    model.iteration_count += k
    model._last_score = scores[-1]
    if model.listeners:
        with listeners_span(model, prof, k):
            for i in range(k):
                model._last_score = scores[i]
                for listener in model.listeners:
                    listener.iteration_done(model, it0 + i + 1)
            model._last_score = scores[-1]
    if prof is not None:
        # no per-chunk cost model: the fused multi-step program has
        # its own HLO — decomposition + record only
        prof.end_step(score=model._last_score, rows=rows)


def flush_scan_chunk(model, batches: List[Any]) -> None:
    if len(batches) == 1:
        model.fit_minibatch(batches[0])
        return
    await_scan_slot(model)
    run_scan_chunk(model, stack_chunk(model, batches))


def fit_epoch_scan(model, it) -> int:
    """Buffer same-shaped minibatches into chunks of
    ``model.scan_chunk`` and run each chunk as one fused dispatch.
    A batch goes to the device as it arrives (``place_batch``) and the
    chunk is stacked there (``stack_chunk``); before a chunk is
    enqueued the host waits until at most one other is queued behind
    the one that runs (``await_scan_slot``). ``ChunkedDataSet`` items
    (pre-stacked [k, b, ...] payloads from an input pipeline) feed
    the dispatch directly."""
    from deeplearning4j_tpu.datasets.api import ChunkedDataSet

    from deeplearning4j_tpu.parallel import control_plane
    from deeplearning4j_tpu.resilience import preemption

    model._reset_recurrent_state()  # scan carries empty rnn entries
    buf: List[Any] = []
    sig = None
    n = 0
    for ds in it:
        # chunk boundary is the scan path's step boundary: an
        # un-flushed buffer holds no dispatched work, so an emergency
        # checkpoint here is consistent at the last flushed step
        preemption.check_fit(model)
        control_plane.check_fit(model)
        if isinstance(ds, ChunkedDataSet):
            if buf:
                flush_scan_chunk(model, buf)
                buf, sig = [], None
            await_scan_slot(model)
            model._run_prestacked_chunk(ds)
            n += ds.k
            continue
        s = model._ds_scan_sig(ds)
        if buf and s != sig:
            flush_scan_chunk(model, buf)
            buf = []
        sig = s
        ds = place_batch(model, ds)
        buf.append(ds)
        n += 1
        if len(buf) >= model.scan_chunk:
            flush_scan_chunk(model, buf)
            buf = []
    if buf:
        flush_scan_chunk(model, buf)
    return n


# ---------------------------------------------------------------------------
# megastep epoch driver (K steps / dispatch, one readback / chunk)
# ---------------------------------------------------------------------------


def megastep_active(model) -> bool:
    """True when the ``megastep`` knob asks for fused K-step
    dispatches (K > 1)."""
    return int(getattr(model, "megastep", 1) or 1) > 1


def can_megastep(model) -> bool:
    """Megastep eligibility. Unlike ``_can_scan_steps`` the fused
    chunk here runs the FULL step flavor, so divergence guard,
    telemetry, stat guard, and dynamic loss scaling all stay eligible
    (their state threads through the scan carry). Still refused:
    TBPTT (host-side carry between chunks), non-SGD algorithms,
    recurrent models (conservative — per-step semantics preserved via
    fallback), a ROLLBACK-policy guard (its host restore must
    interrupt the trajectory mid-chunk, which a fused dispatch cannot
    do), row-sharded embeddings (the K-step scan carry would bake the
    ``P("data", None)`` table layout into a program the megastep
    cache/AOT identity doesn't key on — per-step dispatch preserves
    semantics), and listeners that neither declare
    ``supports_batched_iterations`` nor implement ``chunk_done``."""
    from deeplearning4j_tpu.resilience.guard import ROLLBACK

    if not megastep_active(model):
        return False
    if has_row_sharded_embedding(model):
        return False
    conf = model.conf
    guard = getattr(model, "divergence_guard", None)
    return (
        getattr(conf, "iterations", 1) == 1
        and bool(getattr(conf, "backprop", True))
        and getattr(conf, "backprop_type", None) != "TruncatedBPTT"
        and getattr(
            conf, "optimization_algo", "STOCHASTIC_GRADIENT_DESCENT"
        ) == "STOCHASTIC_GRADIENT_DESCENT"
        and not model._recurrent_names()
        and (guard is None or guard.policy != ROLLBACK)
        and all(
            getattr(l, "supports_batched_iterations", False)
            or hasattr(l, "chunk_done")
            for l in model.listeners
        )
    )


def run_megastep_chunk(model, stacked, *, step_fn=None, extra=None,
                       guard=None, on_restore=None, rows=None,
                       ls_active=None, sg_active=None) -> None:
    """One fused K-step megastep dispatch from pre-stacked device
    arrays ``(x, y, labels_mask, features_mask, k)``, followed by THE
    single per-chunk host readback (``megastep_readback``) and the
    host-side fan-out of what used to be per-step work: guard policy
    (from the read-back ok flags — consecutive-bad aborts fire at
    most K−1 steps late), listener callbacks (``chunk_done`` when the
    listener has one, else per-step ``iteration_done`` replayed from
    already-host scores at zero extra syncs), and one profiler
    record covering the chunk. ``step_fn``/``extra``/``guard``/
    ``on_restore``/``ls_active``/``sg_active`` let the distributed
    trainer substitute its sharded executable and its own guard's
    step flavor; the defaults serve the single-host engines."""
    xs, ys, masks, fmasks, k = stacked
    it0 = model.iteration_count
    prof = _prof_mod.get_active_profiler()
    if prof is not None:
        prof.begin_step(it0 + k,
                        parent=getattr(model, "_fit_span", None))
    if rows is None:
        rows = k * _chunk_rows(xs)
    with dispatch_span(model, prof, k, it0, rows):
        lr_stack, it0_dev = scan_consts(model, k, it0)
        if step_fn is None:
            if model._jit_megastep is None:
                model._jit_megastep = model._build_megastep()
            step_fn = model._jit_megastep
        if extra is None:
            extra = model._step_extra_args()
        out = step_fn(
            model.params, model.updater_state, model.state,
            xs, ys, masks, fmasks, lr_stack, it0_dev, model._base_key,
            *extra,
        )
    model.params, model.updater_state, model.state = out[:3]
    metrics, it0_next = out[3], out[4]
    i = 5
    if ls_active is None:
        ls_active = bool(getattr(model, "_loss_scale_active", False))
    if sg_active is None:
        sg_active = stat_guard_active(model)
    if ls_active:
        model._loss_scale_state = out[i]
        i += 1
    if sg_active:
        model._stat_guard_state = out[i]
    note_it0(model, it0_next, it0 + k)
    model.iteration_count += k
    note_megastep(k)
    host = megastep_readback(metrics)
    scores = host["scores"]
    model._last_score = float(scores[-1])
    if "grad_norms" in host:
        model._last_grad_norm = float(host["grad_norms"][-1])
    if guard is None:
        guard = getattr(model, "divergence_guard", None)
    if guard is not None and "oks" in host:
        # the in-jit select already suppressed each bad update, so
        # the trajectory needs nothing from the host — this only
        # applies the SKIP policy's ledger/abort bookkeeping, once
        # per chunk instead of once per step
        for j in range(k):
            if bool(host["oks"][j]):
                guard.good_step()
            else:
                guard.bad_step(model, on_restore=on_restore,
                               step_index=it0 + j + 1)
    if model.listeners:
        with listeners_span(model, prof, k):
            for listener in model.listeners:
                cd = getattr(listener, "chunk_done", None)
                if cd is not None:
                    cd(model, it0, k, host)
            per_step = [l for l in model.listeners
                        if not hasattr(l, "chunk_done")]
            if per_step:
                for j in range(k):
                    model._last_score = float(scores[j])
                    for listener in per_step:
                        listener.iteration_done(model, it0 + j + 1)
                model._last_score = float(scores[-1])
    if prof is not None:
        prof.end_step(score=model._last_score, rows=rows, chunk=k)


def flush_megastep(model, batches: List[Any]) -> None:
    if len(batches) == 1:
        model.fit_minibatch(batches[0])
        return
    if _wants_last_features(model):
        model._last_features = batches[-1].features
    run_megastep_chunk(model, stack_chunk(model, batches))


def fit_epoch_megastep(model, it, prefetch=None) -> int:
    """Buffer same-shaped minibatches into blocks of
    ``model.megastep`` and run each block as one fused megastep
    dispatch. ``ChunkedDataSet``/``PlacedChunk`` items (pre-stacked
    [k, b, ...] payloads from a chunk-mode ``PrefetchIterator``) feed
    the dispatch directly — the double-buffered path where the next
    block's host->device copy overlaps the current dispatch. Partial
    or signature-changing tails fall back to the per-step program
    (same math — the mixed trajectory stays bitwise equal to the pure
    per-step loop). Chunk boundaries are the preemption/emergency
    checkpoint boundaries: an un-flushed buffer holds no dispatched
    work, so checkpoint staleness is bounded by K−1 steps."""
    from deeplearning4j_tpu.datasets.api import (
        ChunkedDataSet, PlacedChunk,
    )
    from deeplearning4j_tpu.parallel import control_plane
    from deeplearning4j_tpu.resilience import preemption

    model._reset_recurrent_state()
    k_target = int(model.megastep)
    buf: List[Any] = []
    sig = None
    n = 0
    for ds in it:
        preemption.check_fit(model, prefetch=prefetch)
        control_plane.check_fit(model)
        if isinstance(ds, (ChunkedDataSet, PlacedChunk)):
            if buf:
                flush_megastep(model, buf)
                buf, sig = [], None
            if ds.k >= 2:
                if _wants_last_features(model):
                    model._last_features = ds.features[-1]
                run_megastep_chunk(model, model._prep_prestacked(ds))
            else:
                for b in ds.to_datasets():
                    model.fit_minibatch(b)
            n += ds.k
            continue
        s = model._ds_scan_sig(ds)
        if buf and s != sig:
            flush_megastep(model, buf)
            buf = []
        sig = s
        buf.append(ds)
        n += 1
        if len(buf) >= k_target:
            flush_megastep(model, buf)
            buf = []
    if buf:
        flush_megastep(model, buf)
    return n


def fit_epochs_device_cached(model, iterator, epochs: int, arrays_of,
                             extra_plan_fn=None) -> bool:
    """Multi-epoch fit over a materialized dataset with the batches
    kept HBM-resident across epochs.

    The reference re-reads host data every epoch and re-copies it
    over PCIe (`MultipleEpochsIterator` + the per-op JNI hop,
    SURVEY.md §3.1); on TPU the host->device link is the scarce
    resource, so when the data is a fixed sequence that fits in
    device memory we transfer each fused chunk ONCE and re-run the
    scanned train step over the cached arrays every epoch. lr
    schedules/iteration counts are recomputed per chunk per epoch,
    so training semantics are identical to the streaming path.
    Returns False (caller streams as before) when ineligible."""
    plan = extra_plan_fn(iterator, epochs) if extra_plan_fn else None
    if plan is None:
        plan = cached_epoch_plan(model, iterator, epochs, arrays_of)
    if plan is None:
        return False
    for epoch in range(epochs):
        for listener in model.listeners:
            if hasattr(listener, "on_epoch_start"):
                listener.on_epoch_start(model)
        model._reset_recurrent_state()
        from deeplearning4j_tpu.parallel import control_plane
        from deeplearning4j_tpu.resilience import preemption

        for kind, item, last in plan:
            preemption.check_fit(model)
            control_plane.check_fit(model)
            if kind == "chunk":
                if _wants_last_features(model):
                    model._last_features = last.features
                run_scan_chunk(model, item)
            elif kind == "tbptt":
                if _wants_last_features(model):
                    model._last_features = last.features
                model._run_tbptt_stacked(item)
            else:
                model.fit_minibatch(item)
        for listener in model.listeners:
            if hasattr(listener, "on_epoch_end"):
                listener.on_epoch_end(model)
        model.epoch_count += 1
    return True


def _prepare_fit(model, iterator):
    """What ``fit()`` does before its first epoch: the persistent
    compile cache, lazy ``init()``, the batch validator around the
    iterator, layer-wise pretraining. Returns the iterator the epochs
    read."""
    # the step compiled below is a disk read on the next start: the
    # persistent cache is on by default where the backend is a TPU
    # (compile.persistent.default_cache_dir; a no-op on the CPU)
    from deeplearning4j_tpu.compile.persistent import (
        enable_persistent_cache,
    )

    enable_persistent_cache()
    if model.params is None:
        model.init()
    validator = getattr(model, "_batch_validator", None)
    if validator is not None:
        from deeplearning4j_tpu.datasets.validate import (
            ValidatingIterator,
        )

        if not isinstance(iterator, ValidatingIterator):
            # data-plane defense: rejects are quarantined before they
            # reach a step; the surviving stream is what trains
            iterator = ValidatingIterator(
                iterator, validator,
                quarantine=getattr(model, "_quarantine_store", None),
            )
    if model.conf.pretrain and not model._pretrain_done:
        # reference fit():1064 — layer-wise pretrain before backprop
        if not hasattr(iterator, "reset") and not isinstance(
            iterator, (list, tuple)
        ):
            iterator = list(iterator)
        model.pretrain(iterator)
    return iterator


def fit_batches(model, iterator, epochs: int) -> None:
    """The epoch fit loop shared by both engines: optional pretrain,
    device-cached multi-epoch replay, scan-fused or per-step epochs
    through an ``AsyncDispatchWindow`` (bounded in-flight dispatch,
    guard flags collected late), epoch listener hooks, and iterator
    reset protocol. The whole call is one ``fit`` span, the root of
    the drivers' trace; each streamed epoch one ``fit.epoch``.

    The frames from here to the jitted call (this one, the epoch
    driver, ``flush_*``, ``run_*_chunk``) are on the stack while JAX
    traces and lowers the step program, and their footprint decides
    where CPython 3.12's 16 KiB data-stack chunks end in that
    recursion: a chunk is unmapped the moment its first frame pops, so
    a hot call that straddles a boundary maps and unmaps one per call.
    46 slots more in this chain cost 6.7 s (character transformer) and
    11 s (ResNet-50) of set-up on the chip (PERF.md, PR 28), which is
    why the set-up before the first epoch is a helper of its own and
    ``tests/test_fit_spans.py`` pins the chain's footprint: re-measure
    ``setup_s`` on the chip before moving it."""
    from deeplearning4j_tpu.parallel import control_plane
    from deeplearning4j_tpu.parallel.dispatch import (
        AsyncDispatchWindow,
    )
    from deeplearning4j_tpu.resilience import preemption

    root = get_tracer().start_span("fit", attrs={"epochs": epochs})
    model._fit_span = root if root.recording else None
    window = None
    try:
        iterator = _prepare_fit(model, iterator)
        if not model.conf.backprop:
            return
        # megastep=K outranks the device-cached replay: the caller
        # asked for the fused-K executor (and its per-chunk readback
        # contract)
        if not can_megastep(model) and model._fit_epochs_device_cached(
            iterator, epochs
        ):
            root.set_attr("path", "device_cached")
            return
        window = AsyncDispatchWindow(
            model=model,
            guard_fn=lambda: getattr(model, "divergence_guard", None),
            max_in_flight=model.max_in_flight,
            guard_lag=model.guard_lag,
        )
        for epoch in range(epochs):
            with get_tracer().start_span(
                    "fit.epoch", parent=root,
                    attrs={"epoch": epoch}) as epoch_span:
                if epoch_span.recording:
                    model._fit_span = epoch_span
                for listener in model.listeners:
                    if hasattr(listener, "on_epoch_start"):
                        listener.on_epoch_start(model)
                it = feed(model, iterator)
                if can_megastep(model):
                    root.set_attr("path", "megastep")
                    n_batches = fit_epoch_megastep(
                        model, it,
                        prefetch=iterator
                        if hasattr(iterator, "shutdown") else None,
                    )
                elif model._can_scan_steps() and model.scan_chunk > 1:
                    root.set_attr("path", "scan")
                    n_batches = fit_epoch_scan(model, it)
                else:
                    root.set_attr("path", "step")
                    n_batches = 0
                    model._dispatch_window = window
                    try:
                        for ds in it:
                            # preemption notice -> drain + emergency
                            # checkpoint + PreemptedException (prefetch
                            # sources are shut down with a bounded join)
                            preemption.check_fit(
                                model, window=window,
                                prefetch=iterator
                                if hasattr(iterator, "shutdown")
                                else None,
                            )
                            control_plane.check_fit(model)
                            model.fit_minibatch(ds)
                            n_batches += 1
                    finally:
                        model._dispatch_window = None
                    window.drain()  # guard aborts surface per epoch
                prof = _prof_mod.get_active_profiler()
                if prof is not None:
                    # a record the feed opened and no step claimed (a
                    # solver algorithm's minibatch) ends with the epoch
                    prof.abandon_step("unused")
                epoch_span.set_attr("batches", n_batches)
                if epoch > 0 and n_batches == 0:
                    raise ValueError(
                        "Iterator yielded no batches after the first "
                        "epoch — a plain generator cannot be "
                        "re-iterated; pass a list, a DataSetIterator "
                        "with reset(), or epochs=1"
                    )
                if hasattr(iterator, "reset"):
                    iterator.reset()
                for listener in model.listeners:
                    if hasattr(listener, "on_epoch_end"):
                        listener.on_epoch_end(model)
                model.epoch_count += 1
    except BaseException as e:
        root.set_attr("error_type", type(e).__name__).end("error")
        if window is None:  # before the first streamed epoch
            raise
        window.abandon()  # keep the original exception
        from deeplearning4j_tpu.observability import flightrec
        from deeplearning4j_tpu.resilience.preemption import (
            PreemptedException,
        )

        prof = _prof_mod.get_active_profiler()
        if prof is not None:
            prof.abandon_step()
        if not isinstance(e, PreemptedException):
            # preemption already attached the ring to the emergency
            # checkpoint manifest; everything else dumps to disk here
            flightrec.dump_on_crash("fit_exception")
        raise
    finally:
        model._fit_span = None
        root.end()


# ---------------------------------------------------------------------------
# transform knob plumbing (shared by both engine wrappers)
# ---------------------------------------------------------------------------


def init_transforms(model, conf) -> None:
    """Seed the model's transform knobs from the (non-serialized)
    config hints and reset the derived caches. Called from both
    engines' constructors."""
    model.scan_layers = bool(getattr(conf, "scan_layers", False))
    model.remat = check_remat_policy(
        getattr(conf, "remat", None) or "none"
    )
    ls = getattr(conf, "loss_scale", None)
    model.loss_scale = (
        DEFAULT_LOSS_SCALE if ls is True else ls
    )
    model._layer_runs_cache = None
    model._loss_scale_state = None
    model._stat_guard_state = None
    model._batch_validator = None
    model._quarantine_store = None
    model.grad_accum = 1
    # K>1 folds K optimizer steps into one XLA dispatch (the
    # megastep executor); 1 = classic per-step dispatch
    model.megastep = int(getattr(conf, "megastep", 1) or 1)
    model._jit_megastep = None
    # {"shards": n} while the updater state lives in the zero
    # flattened-leaf layout (set/cleared by the distributed trainer's
    # placement); None = canonical per-param shapes
    model._zero_layout = None


def set_transforms(model, scan_layers=None, remat=None,
                   loss_scale=None, megastep=None) -> None:
    """Runtime (re)configuration of the whole-net transforms on either
    engine. ``None`` leaves a knob unchanged; changed knobs invalidate
    every compiled program that bakes them in. Transforms never change
    the math — trajectories are bitwise identical with them on or off
    (tier-1-asserted) — only the compiled program's shape (scan),
    memory plan (remat), or f16 gradient dynamic range (loss scale),
    or how many optimizer steps one dispatch covers (megastep)."""
    changed = False
    if megastep is not None:
        k = int(megastep)
        if k < 1:
            raise ValueError(f"megastep must be >= 1, got {megastep}")
        if k != int(getattr(model, "megastep", 1) or 1):
            model.megastep = k
            changed = True
    if scan_layers is not None and bool(scan_layers) != model.scan_layers:
        model.scan_layers = bool(scan_layers)
        model._layer_runs_cache = None
        changed = True
    if remat is not None and check_remat_policy(remat) != model.remat:
        model.remat = remat
        changed = True
    if loss_scale is not None:
        ls = DEFAULT_LOSS_SCALE if loss_scale is True else (
            loss_scale or None
        )
        if ls != model.loss_scale:
            model.loss_scale = ls
            model._loss_scale_state = None
            changed = True
    if changed:
        model._jit_step = None
        model._jit_multi_step = None
        model._jit_megastep = None
        model._jit_output = None
        model._jit_rnn_step = None
        if hasattr(model, "_jit_tbptt_multi_step"):
            model._jit_tbptt_multi_step = None


def set_batch_validator(model, validator, quarantine=None) -> None:
    """(Un)install the data-plane defense on a model's fit loops:
    ``fit_batches`` wraps its iterator in a ``ValidatingIterator``
    quarantining rejects to ``quarantine``. Host-side only — the
    compiled step is untouched."""
    model._batch_validator = validator
    model._quarantine_store = quarantine


def loss_scale_active(model) -> bool:
    """Dynamic loss scaling engages only for float16 compute (bf16
    shares f32's exponent range and needs none of it — unchanged)."""
    return (
        model.loss_scale is not None
        and compute_dtype_of(model.conf) == jnp.dtype(jnp.float16)
    )


def ensure_loss_scale_state(model):
    if model._loss_scale_state is None:
        model._loss_scale_state = loss_scale_state(model.loss_scale)
    return model._loss_scale_state


def stat_guard_active(model) -> bool:
    """The statistical anomaly guard engages when the installed
    divergence guard carries a ``StatGuardConfig``."""
    guard = getattr(model, "divergence_guard", None)
    return guard is not None and getattr(guard, "stats", None) is not None


def stat_guard_config(model):
    guard = getattr(model, "divergence_guard", None)
    return getattr(guard, "stats", None) if guard is not None else None


def ensure_stat_guard_state(model):
    """The model's device-resident EWMA state dict, created on first
    use (a checkpoint restore may have installed one already — the
    bitwise-resume path)."""
    if getattr(model, "_stat_guard_state", None) is None:
        from deeplearning4j_tpu.resilience.guard import stat_guard_state

        model._stat_guard_state = stat_guard_state()
    return model._stat_guard_state


def transform_kind_suffix(model) -> str:
    """AOT artifact-kind suffix for the transform knobs that change
    the compiled program (loss-scale changes the step's arity, scan/
    remat its HLO): part of the artifact identity so a stale
    executable is refused, not mis-dispatched."""
    parts = []
    if model.scan_layers:
        parts.append("scan")
    if model.remat != "none":
        parts.append(f"remat:{model.remat}")
    if getattr(model, "_loss_scale_active", False):
        parts.append("lossscale")
    if stat_guard_active(model):
        # a +statguard executable takes (and returns) the EWMA state;
        # refusing a stale plain artifact beats mis-dispatching it
        parts.append("statguard")
    if int(getattr(model, "grad_accum", 1)) > 1:
        parts.append(f"accum:{model.grad_accum}")
    if megastep_active(model):
        # a +mega:K executable is the K-step scanned program with a
        # different arity and return contract than the per-step one;
        # a stale artifact at any other K (or none) must be refused
        parts.append(f"mega:{model.megastep}")
    if getattr(model, "_zero_layout", None):
        # a +zero executable bakes in the flattened-leaf updater
        # layout; a stale plain-step artifact must be refused, not
        # fed flat state (and vice versa)
        parts.append("zero")
    kernels = kernel_kind_suffix(model)
    if kernels:
        # a Pallas dense kernel is different HLO from XLA's dot; an
        # executable compiled with the kernels off must be refused
        # when dispatch is on (and vice versa).
        # "+tuned" extends the same refusal to the autotuner: measured
        # block configs change the kernels' tiling (and thus the HLO),
        # so an artifact compiled with tuning off must not install
        # while tuning is active (and vice versa).
        parts.extend(kernels.lstrip("+").split("+"))
    if has_row_sharded_embedding(model):
        # a +semb executable was traced with the embedding table's
        # rows sharded P("data", None); feeding it replicated params
        # (or vice versa) would silently recompile or mis-place — the
        # suffix forces the refusal path instead
        parts.append("semb")
    return ("+" + "+".join(parts)) if parts else ""


def kernel_kind_suffix(model) -> str:
    """The Pallas-kernel part of an AOT artifact kind, shared by the
    training-step suffix above and both engines' inference
    ``_output_kind``: ``+kernels`` when fused kernel dispatch is
    active, plus ``+tuned`` when the autotuner may swap in measured
    block configs (``DL4J_TPU_TUNE`` != off) — tuned tilings compile
    different HLO, so a mixed artifact must be refused, not
    mis-dispatched."""
    if not kernel_dispatch_active(model):
        return ""
    from deeplearning4j_tpu.ops import autotune

    return "+kernels" + ("+tuned" if autotune.tuning_active() else "")


def _model_layer_confs(model):
    """Layer specs of either engine's config: the sequential list, or
    the layer-bearing vertices of a graph."""
    conf = model.conf
    layers = getattr(conf, "layers", None)
    if layers is not None:
        return list(layers)
    verts = getattr(conf, "vertices", None) or {}
    return [lc for lc in (v.layer() for v in verts.values())
            if lc is not None]


def has_row_sharded_embedding(model) -> bool:
    """True when either engine's config carries a
    ``SparseEmbeddingLayer`` with ``row_sharded=True`` — the marker
    the eligibility gates key on: ``DistributedTrainer`` shards that
    layer's ``W`` rows ``P("data", None)`` and must take the GSPMD
    step, ZeRO keeps the param replicated, and megastep refuses the
    model (see each gate's comment)."""
    from deeplearning4j_tpu.nn.layers.feedforward import (
        SparseEmbeddingLayer,
    )

    return any(
        isinstance(lc, SparseEmbeddingLayer)
        and getattr(lc, "row_sharded", False)
        for lc in _model_layer_confs(model)
    )


def kernel_dispatch_active(model) -> bool:
    """True when Pallas fused-kernel dispatch is on AND the model has
    a layer of the dense family (``DenseLayer``, and the output layers
    that derive from it), whose product may go to ``matmul_block``.
    Coarse on purpose: it asks no shapes, so a model whose dense layers
    all stay on XLA still reports active. That over-refuses a stale
    artifact, which then falls back to JIT, and is safe; the converse
    (mis-dispatching an executable traced with different kernels)
    is not."""
    from deeplearning4j_tpu.ops.dispatch import use_pallas

    if not use_pallas():
        return False
    from deeplearning4j_tpu.nn.layers.feedforward import DenseLayer

    return any(
        isinstance(lc, DenseLayer)
        for lc in _model_layer_confs(model)
    )
