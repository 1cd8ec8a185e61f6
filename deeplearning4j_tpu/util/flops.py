"""Absolute-performance accounting: XLA cost-analysis FLOPs for one
train step, plus device peak-FLOP/s lookup, so throughput numbers can
be stated as achieved TFLOP/s and MFU rather than bare examples/sec.

The reference's only performance instrument is relative —
``optimize/listeners/PerformanceListener.java:71-86`` prints
examples/sec — so "fast" is unfalsifiable there. Here the compiled
train step itself is the source of truth: ``jit(step).lower(args)
.compile().cost_analysis()`` returns the FLOPs XLA actually scheduled
(forward + backward + updater), and MFU = achieved / chip peak.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

# Dense bf16 peak FLOP/s per chip, by device_kind substring (public
# cloud specs). Matching is ordered: first hit wins.
_PEAKS: Tuple[Tuple[str, float], ...] = (
    ("v6 lite", 918e12),  # Trillium / v6e
    ("v6e", 918e12),
    ("v5 lite", 197e12),  # v5e
    ("v5e", 197e12),
    ("v5p", 459e12),
    ("v5", 459e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 46e12),
)


def device_peak_flops(device=None) -> Tuple[Optional[float], str]:
    """(bf16 peak FLOP/s, device_kind) for ``device`` (default: the
    first addressable device). Peak is None off-TPU — MFU is only
    defined against a known roofline. A TPU whose ``device_kind`` is
    not in the table raises: a chip without a stated peak is an error
    to fix in the table, not a reason to report no utilisation."""
    d = device if device is not None else jax.devices()[0]
    kind = getattr(d, "device_kind", d.platform)
    if d.platform != "tpu":
        return None, kind
    return table_lookup(_PEAKS, kind, "peak FLOP/s"), kind


def table_lookup(table, kind: str, what: str) -> float:
    """First entry of a ``(device_kind substring, value)`` table that
    matches the TPU ``kind``; raises when none does."""
    low = kind.lower()
    for key, value in table:
        if key in low:
            return value
    raise ValueError(
        f"no {what} entry for TPU device_kind {kind!r}: add the "
        "chip's published figure to the table"
    )


def _cost_dict(compiled) -> dict:
    ca = compiled.cost_analysis()
    if ca is None:
        return {}
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return dict(ca)


def lower_train_step(model, ds):
    """``(jax.stages.Lowered, batch rows)`` of ONE jitted train step
    (forward + loss + backward + updater) for ``model`` on minibatch
    ``ds``: the model's own ``_build_step`` program — the same XLA
    program ``fit_minibatch`` executes (the scan-fused multi-step path
    runs this body k times). ``.compile()`` it for the cost analysis
    or for the program text (which kernels and collectives are in
    it)."""
    if model.params is None:
        model.init()
    if model._jit_step is None:
        model._jit_step = model._build_step()
    is_graph = hasattr(model.conf, "vertices")
    lrs = {
        k: jnp.asarray(v, jnp.float32)
        for k, v in model.updater_def.scheduled_lrs(0).items()
    }
    t = jnp.asarray(1, jnp.float32)
    rng = jax.random.fold_in(model._base_key, 0)
    if is_graph:
        dtype = model._dtype()

        def aslist(v):
            if v is None:
                return None
            seq = v if isinstance(v, (list, tuple)) else [v]
            out = [
                jnp.asarray(a, dtype) if a is not None else None
                for a in seq
            ]
            return out if any(a is not None for a in out) else None

        x = aslist(ds.features)
        y = aslist(ds.labels)
        lmask = aslist(getattr(ds, "labels_masks", None)
                       or getattr(ds, "labels_mask", None))
        fmask = aslist(getattr(ds, "features_masks", None)
                       or getattr(ds, "features_mask", None))
        batch = int(x[0].shape[0])
    else:
        from deeplearning4j_tpu.nn.core import dtype_of, to_device

        dtype = dtype_of(model.conf)
        x = to_device(ds.features, dtype)
        y = to_device(ds.labels, dtype)
        lmask = getattr(ds, "labels_mask", None)
        fmask = getattr(ds, "features_mask", None)
        lmask = jnp.asarray(lmask, dtype) if lmask is not None else None
        fmask = jnp.asarray(fmask, dtype) if fmask is not None else None
        batch = int(x.shape[0])
    lowered = model._jit_step.lower(
        model.params, model.updater_state, model.state,
        x, y, lmask, fmask, lrs, t, rng,
    )
    return lowered, batch


def train_step_cost(model, ds) -> dict:
    """XLA's cost analysis of the step ``lower_train_step`` lowers:
    ``{"flops", "bytes_accessed", "batch", "flops_per_example"}`` —
    what actually runs, not an analytic estimate. For TBPTT models
    pass a ds whose sequence length equals the tbptt window;
    per-example cost then scales by (full_seq / tbptt_len) chunks.
    """
    lowered, batch = lower_train_step(model, ds)
    cost = _cost_dict(lowered.compile())
    flops = float(cost.get("flops", 0.0))
    return {
        "flops": flops,
        "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        "batch": batch,
        "flops_per_example": flops / batch if batch else 0.0,
    }


def jit_cost(jitted, *args, **kwargs) -> dict:
    """Cost analysis of an arbitrary jitted callable on concrete args
    (for paths that don't go through an engine ``_build_step`` — e.g.
    the word2vec fused skip-gram update)."""
    cost = _cost_dict(jitted.lower(*args, **kwargs).compile())
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
    }
