"""What a language-model cell whose inputs are token ids needs beside
``data.py`` and ``reference_train.py`` (neither knows ids, and both keep
a second copy of the weights on the device for the parameter-change
reading, which a cell that fills the chip with its weights and Adam's
moments cannot afford): host batches of ids from the seed, the readings
``compare.py`` takes with the starting weights made again from the seed
inside the program that needs them, and the reference's first steps.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.harness import reference_train
from benchmarks.harness.data import seed_rng
from deeplearning4j_tpu.datasets import DataSet


def make_batches(spec, batch, n, seed, vocab, labels_ahead):
    """``n`` DataSets of ``batch`` rows of ids in uint16: ``length``
    inputs and, one position on, ``length + labels_ahead - 1`` labels
    (the next id, and the one after it for a prediction module). Ids
    come from a fixed skewed distribution over the ``vocab`` rows held,
    rolled by the row's place in the batch so that a batch with a row
    left out is another batch."""
    if vocab > 2 ** 16:
        raise ValueError(f"{vocab} ids do not fit uint16")
    rng = seed_rng(seed, 1)
    t = spec["length"]
    p = 1.0 / (np.arange(vocab) + spec.get("skew_offset", 10.0))
    p /= p.sum()
    out = []
    for _ in range(n):
        ids = rng.choice(vocab, size=(batch, t + labels_ahead), p=p)
        if spec.get("row_roll"):
            ids = (ids + (np.arange(batch) * vocab // batch)[:, None]) % vocab
        ids = ids.astype(np.uint16)
        out.append(DataSet(
            features=np.ascontiguousarray(ids[:, :t]),
            labels=np.ascontiguousarray(ids[:, 1:])))
    return out


_NORMS = {}


def _norms_program(ref, cfg):
    """One jitted program: the norm of every leaf's change from the
    weights ``ref.init`` makes from the key, and of every leaf of the
    first moment. The starting weights exist leaf by leaf inside it."""
    cache_key = (ref.__name__, json.dumps(cfg, sort_keys=True))
    if cache_key not in _NORMS:
        def norms(key, params, moment):
            start = ref.init(cfg, key)[0]
            delta = jax.tree.map(
                lambda a, b: b.astype(jnp.float32) - a, start, params)
            return (reference_train.leaf_norms(delta),
                    reference_train.leaf_norms(moment))

        _NORMS[cache_key] = jax.jit(norms)
    return _NORMS[cache_key]


def take_readings(ref, cfg, key, losses, params, first_moment, grad1=None):
    """``reference_train.take_readings`` with the starting weights made
    again from ``key``: per-step losses, per-leaf norms of the
    parameters' change and of the first moment, a host copy of the
    first moment."""
    held = lambda tree: {k: v for k, v in tree.items() if v}  # noqa: E731
    delta, moment = _norms_program(ref, cfg)(
        key, held(params), held(first_moment))
    flat = jax.tree_util.tree_flatten_with_path(
        jax.device_get(held(first_moment)))[0]
    out = {
        "losses": [float(v) for v in losses],
        "delta": {k: float(v) for k, v in delta.items()},
        "moment": {k: float(v) for k, v in moment.items()},
        "moment_arrays": {"/".join(str(k.key) for k in path): leaf
                          for path, leaf in flat},
    }
    if grad1 is not None:
        out["grad1"] = {k: float(v) for k, v in grad1.items()}
    return out


_STEPS = {}


def _step_program(ref, cfg, compute, fault):
    cache_key = (ref.__name__, json.dumps(cfg, sort_keys=True), compute,
                 fault)
    if cache_key in _STEPS:
        return _STEPS[cache_key]
    q = reference_train.QUANTIZERS[compute]
    upd = cfg["updater"]

    def step(params, moments, state, x, y, t, lr):
        if fault == "row_left_out":
            x, y = x[:-1], y[:-1]
        (loss, new_state), grads = jax.value_and_grad(
            lambda p: ref.loss(cfg, p, state, x, y, q), has_aux=True
        )(params)
        new_params, new_moments = reference_train.apply_updater(
            upd, params, grads, moments, t, lr)
        return (new_params, new_moments, new_state, loss,
                reference_train.leaf_norms(grads))

    _STEPS[cache_key] = jax.jit(step, donate_argnums=(0, 1, 2))
    return _STEPS[cache_key]


def run_reference(ref, cfg, key, batches, n_steps, compute="float32",
                  fault=None):
    """The reference's first ``n_steps`` optimizer steps from ``key``
    on ``batches``, one jitted step at a time. ``compute`` as in
    ``reference_train``; ``fault`` ``row_left_out`` drops the last row
    of every batch (the mean is over the rest)."""
    step = _step_program(ref, cfg, compute, fault)
    upd = cfg["updater"]
    params, state = jax.jit(lambda k: ref.init(cfg, k))(key)
    moments = tuple(jax.tree.map(jnp.zeros_like, params)
                    for _ in range(reference_train.n_moments(upd)))
    losses, grad1 = [], None
    rates = reference_train.learning_rates(upd, n_steps)
    for i, lr in enumerate(rates):
        ds = batches[i % len(batches)]
        params, moments, state, loss, gnorm = step(
            params, moments, state, jnp.asarray(ds.features),
            jnp.asarray(ds.labels), jnp.float32(i + 1), jnp.float32(lr))
        losses.append(loss)
        if i == 0:
            grad1 = gnorm
    return take_readings(ref, cfg, key, losses, params, moments[0], grad1)
