"""ComputationGraph — the DAG engine (reference:
``nn/graph/ComputationGraph.java``, 4.1k LoC; forward = topo-ordered
``doForward`` per vertex, backward = reverse topo ``doBackward``).

TPU-first: the topo walk happens at *trace* time — the whole DAG
(all vertices, multi-input fan-in, multi-output losses) flattens into
one XLA program per input shape, and the reverse-order backward pass
is ``jax.grad`` of that program. Multi-output losses sum (reference
sums output-layer scores).

Like ``MultiLayerNetwork``, this engine is a wrapper over the unified
functional core (``nn/core.py``): the jitted step builders, scan-fused
multi-step, pretrain step, fit drivers, and whole-net transforms
(scan-over-layers on linear vertex chains, activation remat, dynamic
loss scaling) are implemented there once — only the DAG walk itself is
engine-specific (``scripts/lint_parity.py`` enforces the split). The
core also brings the divergence guard and step telemetry to this
engine, which previously only the sequential engine wired in.
"""

from __future__ import annotations

import collections
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.nn import core
from deeplearning4j_tpu.observability import profiler
from deeplearning4j_tpu.nn.conf.graph_conf import (
    ComputationGraphConfiguration,
    DuplicateToTimeSeriesVertex,
    LastTimeStepVertex,
    LayerVertex,
)
from deeplearning4j_tpu.nn.updaters import MultiLayerUpdaterDef, UpdaterSettings


def _as_list(x) -> list:
    if x is None:
        return []
    if isinstance(x, (list, tuple)):
        return list(x)
    return [x]


class ComputationGraph:
    def __init__(self, conf: ComputationGraphConfiguration):
        self.conf = conf
        self.topo: List[str] = conf.topological_order()
        self.layer_vertex_names: List[str] = [
            n for n in self.topo
            if isinstance(conf.vertices[n], LayerVertex)
        ]
        settings: Dict[str, UpdaterSettings] = {}
        for n in self.layer_vertex_names:
            settings[n] = conf.vertices[n].layer_conf.updater_settings()
        self.updater_def = MultiLayerUpdaterDef(settings)
        self.params: Optional[dict] = None
        self.state: Dict[str, dict] = {}
        self.updater_state = None
        self.iteration_count = 0
        self.epoch_count = 0
        self._last_score = float("nan")
        self.listeners: List[Any] = []
        self._jit_step = None
        self._jit_multi_step = None
        self._solver = None  # lazily built for LBFGS/CG/line-search
        self.scan_chunk = 16  # minibatches fused per dispatch
        # multi-epoch fits keep the dataset HBM-resident up to this
        # size, derived from the device's reported memory limit
        from deeplearning4j_tpu.util.device import device_cache_budget_bytes

        self.device_cache_bytes = device_cache_budget_bytes()
        self._jit_output = None
        # AOT-restored inference executables by exact input-shape key
        # (compile/aot.py): consulted by output() before the jit path
        self._aot_outputs: Dict[tuple, Any] = {}
        self._jit_rnn_step = None
        self._rnn_state: Dict[str, Any] = {}  # streaming rnnTimeStep
        self._stream_steps = 0  # timesteps consumed vs finite caches
        self._jit_pretrain_steps: Dict[str, Any] = {}
        self._jit_pretrain_inputs: Dict[str, Any] = {}
        # device-resident scan constants (see core.scan_consts)
        self._scan_const_cache: Dict[Any, Any] = {}
        self._it0_dev = None
        self._it0_shadow = -1
        # scores of the newest scan chunks enqueued: what the scan
        # path's run-ahead bound waits on (core.await_scan_slot)
        self._scan_inflight = collections.deque(
            maxlen=core.SCAN_CHUNKS_AHEAD)
        self._pretrain_done = False
        self._base_key = jax.random.PRNGKey(conf.seed)
        # resilience.DivergenceGuard — wired through the core step
        # builder exactly like MultiLayerNetwork (in-jit suppression,
        # host-side skip/rollback policy)
        self.divergence_guard = None
        # observability step telemetry (in-jit grad global norm)
        self._telemetry_grad_norm = False
        self._last_grad_norm = None
        # async dispatch knobs (core.fit_batches runs the per-step
        # loop through an AsyncDispatchWindow)
        self.max_in_flight = 2
        self.guard_lag = None
        self._dispatch_window = None
        self._fit_span = None  # core.fit_batches' open span, if traced
        self._last_batch_rows = None  # host int; examples/sec signal
        # whole-net transform knobs — see core.set_transforms
        core.init_transforms(self, conf)

    @property
    def score_value(self) -> float:
        """Latest minibatch score (reading syncs with the device)."""
        return float(self._last_score)

    @score_value.setter
    def score_value(self, v) -> None:
        self._last_score = v

    def _dtype(self):
        return jnp.dtype(self.conf.dtype)

    # ------------------------------------------------------------------

    def init(self, params: Optional[dict] = None) -> "ComputationGraph":
        dtype = self._dtype()
        conf = self.conf
        if params is not None:
            # checkpoint npz round-trips drop empty entries; param-less
            # layer vertices get their {} slot back, but a missing
            # PARAMETERIZED vertex is checkpoint corruption — fail here
            restored = {}
            for n in self.layer_vertex_names:
                if n in params:
                    restored[n] = params[n]
                elif conf.vertices[n].init_params(self._base_key, dtype):
                    raise ValueError(
                        f"checkpoint has no params for vertex '{n}'"
                    )
                else:
                    restored[n] = {}
            self.params = restored
        else:
            keys = jax.random.split(
                self._base_key, max(len(self.layer_vertex_names), 1)
            )
            self.params = {
                n: conf.vertices[n].init_params(k, dtype)
                for n, k in zip(self.layer_vertex_names, keys)
            }
        self.state = {
            n: conf.vertices[n].init_state(dtype)
            for n in self.layer_vertex_names
        }
        self.updater_state = self.updater_def.init(self.params)
        self._pretrain_done = False  # fresh params => pretrain again
        return self

    # ------------------------------------------------------------------
    # whole-net transforms (implemented once in nn/core.py)
    # ------------------------------------------------------------------

    def set_transforms(self, scan_layers=None, remat=None,
                       loss_scale=None,
                       megastep=None) -> "ComputationGraph":
        """(Re)configure the whole-net transforms — same contract as
        ``MultiLayerNetwork.set_transforms``. ``scan_layers`` here
        scans LINEAR CHAINS of identical layer vertices (consecutive
        topo positions, single consumer each); ``megastep=K`` folds K
        optimizer steps into one dispatch."""
        core.set_transforms(self, scan_layers, remat, loss_scale,
                            megastep)
        return self

    @property
    def _loss_scale_active(self) -> bool:
        return core.loss_scale_active(self)

    def _active_vertex_chains(self) -> tuple:
        if self._layer_runs_cache is None:
            self._layer_runs_cache = tuple(core.detect_vertex_chains(
                self.conf, self.topo
            ))
        return self._layer_runs_cache

    def scan_layer_run_count(self) -> int:
        """Active scanned vertex chains (telemetry signal)."""
        return (
            len(self._active_vertex_chains()) if self.scan_layers else 0
        )

    def set_divergence_guard(self, guard) -> None:
        """(Un)install a resilience.DivergenceGuard on the train step
        (in-jit NaN/Inf suppression + host-side skip/rollback; with
        ``guard.stats`` also the statistical anomaly guard) — the
        core step builder gives the DAG engine the same machinery as
        the sequential engine."""
        self.divergence_guard = guard
        self._jit_step = None
        self._jit_megastep = None

    def set_batch_validator(self, validator, quarantine=None
                            ) -> "ComputationGraph":
        """(Un)install the data-plane defense (``datasets.validate``)
        on this model's ``fit`` loops."""
        core.set_batch_validator(self, validator, quarantine)
        return self

    def enable_step_telemetry(self, enabled: bool = True) -> None:
        """(Un)install step telemetry: the jitted step additionally
        returns the gradient global L2 norm (one fused scalar)."""
        if enabled != self._telemetry_grad_norm:
            self._telemetry_grad_norm = enabled
            self._jit_step = None
            self._jit_megastep = None

    # ------------------------------------------------------------------

    def _forward_values(self, params, state, inputs: Sequence, *,
                        train: bool, rng, fmasks=None,
                        use_scan: bool = False):
        """Walk the topo order; returns ({vertex: value}, preouts,
        new_state). ``fmasks``: per-graph-input [b, t] masks.
        ``use_scan=True`` (score/output paths, which only read the
        output vertices) lets detected linear chains of identical
        layer vertices run under one ``lax.scan`` — their inner
        values are then not materialized, so callers that need every
        vertex's activation (``feed_forward``) keep it off."""
        conf = self.conf
        cdt = core.compute_dtype_of(conf)
        if cdt != self._dtype():
            # mixed precision (same contract as MultiLayerNetwork):
            # master params keep the storage dtype, compute runs in cdt
            params = core.cast_floats(params, cdt)
            inputs = [core.cast_floats(x, cdt) for x in inputs]
            if fmasks is not None:
                fmasks = [
                    None if m is None else core.cast_floats(m, cdt)
                    for m in fmasks
                ]
        # engine-global shape context for preprocessors: batch/time of
        # the ORIGINAL minibatch (vertex-local inputs may be flattened)
        from deeplearning4j_tpu.nn.conf.preprocessors import ShapeContext

        g_time = max(
            (int(x.shape[2]) for x in inputs if x.ndim == 3), default=-1
        )
        gctx = ShapeContext(
            batch=int(inputs[0].shape[0]) if inputs else 0, time=g_time
        )
        values: Dict[str, Any] = dict(zip(conf.inputs, inputs))
        masks: Dict[str, Any] = {}
        if fmasks is not None:
            masks = {
                name: m for name, m in zip(conf.inputs, fmasks)
                if m is not None
            }
        new_state = dict(state)
        preouts: Dict[str, Any] = {}
        # Per-input masks follow the DAG: each vertex sees the mask
        # propagated from whichever graph input feeds its branch
        # (reference feedForwardMaskArrays). Time-collapsing vertices
        # (LastTimeStep) clear the mask downstream.
        vmask: Dict[str, Any] = dict(masks)
        chain_at = (
            {s: e for s, e in self._active_vertex_chains()}
            if (use_scan and self.scan_layers) else {}
        )
        rem = self.remat if train else "none"
        i, n_topo = 0, len(self.topo)
        while i < n_topo:
            name = self.topo[i]
            v = conf.vertices[name]
            end = chain_at.get(i)
            if end is not None:
                names = self.topo[i:end]
                if core.run_is_ready(names, params, state):
                    # scan-over-layers on a linear vertex chain: the
                    # per-vertex rng indices are the topo positions,
                    # bitwise-matching the unrolled walk
                    src = conf.vertex_inputs[name][0]
                    x = values[src]
                    mask = vmask.get(src)
                    out = core.apply_layer_run(
                        v.layer_conf, names, params, x, train=train,
                        rng=rng, idx0=i, mask=mask, remat=rem,
                    )
                    last = names[-1]
                    values[last] = out
                    vmask[last] = mask
                    for cn in names:
                        new_state[cn] = state.get(cn, {})
                    i = end
                    continue
            vin = [values[s] for s in conf.vertex_inputs[name]]
            in_masks = [
                vmask.get(s) for s in conf.vertex_inputs[name]
            ]
            mask = next((m for m in in_masks if m is not None), None)
            lrng = jax.random.fold_in(rng, i) if rng is not None else None
            vparams = params.get(name, {}) if isinstance(v, LayerVertex) else {}
            vstate = state.get(name, {})
            if isinstance(v, DuplicateToTimeSeriesVertex):
                ref = values[v.reference_input]
                out, st = v.apply(
                    vparams, vin, vstate, train=train, rng=lrng,
                    time=ref.shape[2],
                )
                vmask[name] = vmask.get(v.reference_input)
            elif isinstance(v, LastTimeStepVertex):
                m = masks.get(v.mask_input) if v.mask_input else mask
                out, st = v.apply(vparams, vin, vstate, train=train,
                                  rng=lrng, mask=m)
                vmask[name] = None  # time axis collapsed
            elif isinstance(v, LayerVertex):
                def apply_vertex(p, xs, st, *, _v=v, _rng=lrng,
                                 _mask=mask):
                    return _v.apply(p, xs, st, train=train, rng=_rng,
                                    mask=_mask, ctx=gctx)

                if rem != "none" and not v.layer_conf.has_loss():
                    # activation remat per vertex (jax.checkpoint):
                    # the backward pass recomputes this vertex's
                    # forward instead of keeping its activations
                    apply_vertex = core.maybe_remat(apply_vertex, rem)
                out, st = apply_vertex(vparams, vin, vstate)
                vmask[name] = mask
            else:
                out, st = v.apply(vparams, vin, vstate, train=train,
                                  rng=lrng, mask=mask)
                vmask[name] = mask
            if isinstance(v, LayerVertex):
                new_state[name] = st
                layer = v.layer_conf
                if name in conf.outputs and layer.has_loss():
                    x = vin[0]
                    if v.preprocessor is not None:
                        x = v.preprocessor.preprocess(x, gctx)
                    x = layer.maybe_dropout(x, train=train, rng=lrng)
                    # same lrng as apply -> identical DropConnect mask
                    pw = layer.maybe_drop_connect(
                        params[name], train=train, rng=lrng
                    )
                    preouts[name] = layer.pre_output(pw, x)
            values[name] = out
            i += 1
        return values, preouts, new_state

    def _score_pure(self, params, state, inputs, labels, lmasks, rng, *,
                    train: bool, fmasks=None):
        from deeplearning4j_tpu.nn import losses as losses_mod

        values, preouts, new_state = self._forward_values(
            params, state, inputs, train=train, rng=rng, fmasks=fmasks,
            use_scan=True,
        )
        score = 0.0
        for i, out_name in enumerate(self.conf.outputs):
            v = self.conf.vertices[out_name]
            layer = v.layer_conf if isinstance(v, LayerVertex) else None
            if layer is None or not layer.has_loss():
                raise ValueError(
                    f"Output vertex '{out_name}' has no loss function"
                )
            y = labels[i]
            m = lmasks[i] if lmasks is not None else None
            score = score + losses_mod.score(
                layer.loss, y, preouts[out_name], layer.activation, m, True
            )
        reg = 0.0
        for n in self.layer_vertex_names:
            layer = self.conf.vertices[n].layer_conf
            reg = reg + core.reg_penalty(layer, params[n])
        return score + reg, new_state

    # ------------------------------------------------------------------
    # jitted train step (built by the core)
    # ------------------------------------------------------------------

    def _score_fn(self):
        """The engine's contribution to the core step builders (the
        labels-mask slot carries this engine's per-output lmasks
        list, the features-mask slot its per-input fmasks list)."""
        def score_fn(p, state, inputs, labels, lmasks, fmasks, rng):
            return self._score_pure(
                p, state, inputs, labels, lmasks, rng, train=True,
                fmasks=fmasks,
            )
        return score_fn

    def _recurrent_names(self):
        return [
            n for n in self.layer_vertex_names
            if self.conf.vertices[n].layer_conf.is_recurrent()
        ]

    def _build_step(self):
        return core.build_step(
            self._score_fn(), self.updater_def,
            guarded=self.divergence_guard is not None,
            telemetry=self._telemetry_grad_norm,
            loss_scale=self._loss_scale_active,
            grad_accum=self.grad_accum,
            recurrent_names=self._recurrent_names(),
            zero_layout=self._zero_layout,
            stat_guard=core.stat_guard_config(self),
        )

    def _multi_cast(self):
        multi_dtype = self._dtype()

        def cast(x, labels, mask, fmask):
            c = lambda v: (  # noqa: E731 — cast-on-device contract
                None if v is None
                else [None if a is None else a.astype(multi_dtype)
                      for a in v]
            )
            return c(x), c(labels), c(mask), c(fmask)
        return cast

    def _build_multi_step(self):
        return core.build_multi_step(
            self._score_fn(), self.updater_def,
            cast=self._multi_cast(),
            recurrent_names=self._recurrent_names(),
            grad_accum=self.grad_accum,
            zero_layout=self._zero_layout,
        )

    def _build_megastep(self):
        """K full train steps fused into one dispatch, full step
        flavor (core.build_megastep) — same contract as the
        sequential engine's."""
        return core.build_megastep(
            self._score_fn(), self.updater_def,
            cast=self._multi_cast(),
            recurrent_names=self._recurrent_names(),
            guarded=self.divergence_guard is not None,
            telemetry=self._telemetry_grad_norm,
            loss_scale=self._loss_scale_active,
            stat_guard=core.stat_guard_config(self),
            grad_accum=self.grad_accum,
            zero_layout=self._zero_layout,
        )

    def _can_scan_steps(self) -> bool:
        return (
            self.conf.iterations == 1
            and self.conf.backprop_type != "TruncatedBPTT"
            and getattr(
                self.conf, "optimization_algo",
                "STOCHASTIC_GRADIENT_DESCENT",
            ) == "STOCHASTIC_GRADIENT_DESCENT"
            and self.divergence_guard is None
            and not self._loss_scale_active
            and not any(
                self.conf.vertices[n].layer_conf.is_recurrent()
                for n in self.layer_vertex_names
            )
            and all(
                getattr(l, "supports_batched_iterations", False)
                for l in self.listeners
            )
        )

    def _ds_scan_sig(self, ds) -> tuple:
        def sh(v):
            # np.shape, NOT np.asarray(a).shape — asarray would pull
            # device arrays to host per batch (see core.py)
            return tuple(
                None if a is None else tuple(np.shape(a))
                for a in v
            ) if v else None
        f, l, fm, lm = self._ds_arrays(ds)
        return (sh(f), sh(l), sh(fm or []), sh(lm or []))

    def _ds_arrays(self, ds):
        features = _as_list(getattr(ds, "features"))
        labels = _as_list(getattr(ds, "labels"))
        fmasks = _as_list(getattr(ds, "features_masks", None)
                          or getattr(ds, "features_mask", None))
        lmasks = _as_list(getattr(ds, "labels_masks", None)
                          or getattr(ds, "labels_mask", None))
        return features, labels, fmasks or None, lmasks or None

    def _stack_chunk(self, batches: list):
        """Stack k same-shaped minibatches into device-resident lists
        ``(x, y, labels_masks, features_masks, k)`` — the uniform
        stacked-chunk layout core.run_scan_chunk drives (integer
        inputs keep native width; already-device arrays stack ON
        DEVICE — no host round trip)."""
        dtype = self._dtype()
        rows = [self._ds_arrays(b) for b in batches]

        def stack_lists(idx):
            first = rows[0][idx]
            if first is None:
                return None
            return [
                None if first[j] is None
                else core.stack_on_device(
                    [r[idx][j] for r in rows], dtype
                )
                for j in range(len(first))
            ]

        return (
            stack_lists(0), stack_lists(1), stack_lists(3),
            stack_lists(2), len(batches),
        )

    def _prep_prestacked(self, ds):
        """Single-input [k, b, ...] chunk payload -> this engine's
        stacked device 5-tuple (per-slot lists; same dtype contract
        as stack_on_device)."""
        dtype = self._dtype()

        def prep(a):
            if a is None:
                return None
            a = a if isinstance(a, jax.Array) else jnp.asarray(a)
            return core.cast_stacked(a, dtype)

        lm = getattr(ds, "labels_mask", None)
        fm = getattr(ds, "features_mask", None)
        return (
            [prep(ds.features)], [prep(ds.labels)],
            None if lm is None else [prep(lm)],
            None if fm is None else [prep(fm)],
            ds.k,
        )

    def _run_prestacked_chunk(self, ds) -> None:
        """One fused dispatch from a single-input ChunkedDataSet's
        [k, b, ...] arrays."""
        if ds.k == 1:
            self.fit_minibatch(ds)  # fit_minibatch unstacks
            return
        core.run_scan_chunk(self, self._prep_prestacked(ds))

    # ------------------------------------------------------------------

    def fit(self, data, labels=None, *, epochs: int = 1,
            grad_accum=None, megastep=None) -> None:
        """Accepts a MultiDataSet/DataSet, an iterator of either, or
        (inputs, labels) lists (reference fit overloads
        ``ComputationGraph.java:614-760``). ``grad_accum=K``
        accumulates K microbatch gradients in-jit per optimizer step;
        ``megastep=K`` folds K optimizer steps into one dispatch
        (same contracts as ``MultiLayerNetwork.fit``)."""
        if megastep is not None:
            self.set_transforms(megastep=megastep)
        if grad_accum is not None:
            if (
                int(grad_accum) > 1
                and self.conf.backprop_type == "TruncatedBPTT"
            ):
                raise ValueError(
                    "grad_accum > 1 is incompatible with TBPTT: the "
                    "recurrent carry threads between chunks, so a "
                    "chunk cannot split into independent microbatches"
                )
            core.set_grad_accum(self, grad_accum)
        if labels is not None:
            from deeplearning4j_tpu.datasets.api import MultiDataSet

            mds = MultiDataSet(features=_as_list(data),
                               labels=_as_list(labels))
            core.fit_batches(self, [mds], epochs)
            return
        if hasattr(data, "features"):
            core.fit_batches(self, [data], epochs)
            return
        core.fit_batches(self, data, epochs)

    def _fit_epochs_device_cached(self, iterator, epochs: int) -> bool:
        def arrays_of(ds):
            for group in self._ds_arrays(ds):
                yield from group or []

        return core.fit_epochs_device_cached(
            self, iterator, epochs, arrays_of
        )

    def pretrain(self, data, epochs: int = 1) -> None:
        """Greedy layer-wise unsupervised pretraining of every
        pretrainable layer vertex (VAE/RBM/AutoEncoder), in topological
        order, each on the activations the frozen graph feeds it
        (reference ``ComputationGraph.pretrain``,
        ``ComputationGraph.java:509``)."""
        if self.params is None:
            self.init()
        if hasattr(data, "features"):
            data = [data]
        elif not isinstance(data, (list, tuple)) and not hasattr(
            data, "reset"
        ):
            data = list(data)
        dtype = self._dtype()
        for topo_idx, n in enumerate(self.topo):
            v = self.conf.vertices.get(n)
            if not isinstance(v, LayerVertex):
                continue
            layer = v.layer_conf
            if not layer.is_pretrainable():
                continue
            upd_def = MultiLayerUpdaterDef({n: layer.updater_settings()})
            upd_state = upd_def.init({n: self.params[n]})
            if n not in self._jit_pretrain_steps:
                def make_input(n=n, v=v):
                    from deeplearning4j_tpu.nn.conf.preprocessors import (
                        ShapeContext,
                    )

                    def input_fn(params, state, inputs):
                        values, _, _ = self._forward_values(
                            params, state, inputs, train=False, rng=None
                        )
                        x = values[self.conf.vertex_inputs[n][0]]
                        if v.preprocessor is not None:
                            t = x.shape[2] if x.ndim == 3 else -1
                            x = v.preprocessor.preprocess(
                                x, ShapeContext(batch=x.shape[0], time=t)
                            )
                        return x

                    return jax.jit(input_fn)

                self._jit_pretrain_steps[n] = core.build_pretrain_step(
                    layer, n, upd_def
                )
                self._jit_pretrain_inputs[n] = make_input()
            step = self._jit_pretrain_steps[n]
            jit_input = self._jit_pretrain_inputs[n]
            it = 0
            # the frozen lower graph never changes while vertex n
            # trains: for materialized data, compute each batch's input
            # activation once and reuse it across all epochs — bounded
            # by device_cache_bytes like every other caching path
            xin_cache = None
            if isinstance(data, (list, tuple)):
                xin_cache = []
                cached_bytes = 0
                for ds in data:
                    xin = jit_input(self.params, self.state, [
                        jnp.asarray(f, dtype)
                        for f in _as_list(ds.features)
                    ])
                    cached_bytes += core.nbytes(xin)
                    if cached_bytes > self.device_cache_bytes:
                        xin_cache = None  # too big: recompute per epoch
                        break
                    xin_cache.append(xin)
            for _ in range(epochs):
                batches = (
                    xin_cache if xin_cache is not None else (
                        jit_input(self.params, self.state, [
                            jnp.asarray(f, dtype)
                            for f in _as_list(ds.features)
                        ])
                        for ds in data
                    )
                )
                for xin in batches:
                    for _ in range(self.conf.iterations):
                        lrs = {
                            k: jnp.asarray(val, jnp.float32)
                            for k, val in upd_def.scheduled_lrs(it).items()
                        }
                        t = jnp.asarray(it + 1, jnp.float32)
                        rng = jax.random.fold_in(
                            jax.random.fold_in(
                                self._base_key, 7919 + topo_idx
                            ),
                            it,
                        )
                        (
                            self.params[n], upd_state, loss,
                        ) = step(
                            self.params[n], upd_state, xin, lrs, t, rng
                        )
                        self._last_score = loss
                        it += 1
                if hasattr(data, "reset"):
                    data.reset()
        self._pretrain_done = True

    def _step_extra_args(self) -> tuple:
        extra = ()
        if self._loss_scale_active:
            extra += (core.ensure_loss_scale_state(self),)
        if core.stat_guard_active(self):
            extra += (core.ensure_stat_guard_state(self),)
        return extra

    def fit_minibatch(self, ds) -> float:
        from deeplearning4j_tpu.datasets.api import ChunkedDataSet

        if isinstance(ds, ChunkedDataSet):
            # non-scan fallback: unstack and train per batch
            score = None
            for b in ds.to_datasets():
                score = self.fit_minibatch(b)
            return score
        if self.params is None:
            self.init()
        if self.conf.optimization_algo != "STOCHASTIC_GRADIENT_DESCENT":
            from deeplearning4j_tpu.optimize.solvers import (
                Solver,
                is_solver_algo,
            )

            if is_solver_algo(self.conf.optimization_algo):
                if self._solver is None:
                    self._solver = Solver(self)
                f, l, fm, lm = self._ds_arrays(ds)
                return self._solver.optimize(f, l, mask=lm, fmask=fm)
            raise ValueError(
                "Unknown optimization_algo "
                f"'{self.conf.optimization_algo}'"
            )
        if self._jit_step is None:
            self._jit_step = self._build_step()
        dtype = self._dtype()
        features = _as_list(getattr(ds, "features"))
        labels = _as_list(getattr(ds, "labels"))
        fmasks = _as_list(getattr(ds, "features_masks", None)
                          or getattr(ds, "features_mask", None))
        lmasks = _as_list(getattr(ds, "labels_masks", None)
                          or getattr(ds, "labels_mask", None))
        inputs = [jnp.asarray(f, dtype) for f in features]
        labels = [jnp.asarray(l, dtype) for l in labels]
        fmasks = [
            jnp.asarray(m, dtype) if m is not None else None for m in fmasks
        ] or None
        lmasks = [
            jnp.asarray(m, dtype) if m is not None else None for m in lmasks
        ] or None
        fwd = self.conf.tbptt_fwd_length
        if self.conf.backprop_type == "TruncatedBPTT" and any(
            x.ndim == 3 and x.shape[2] > fwd for x in inputs
        ):
            return self._fit_tbptt(inputs, labels, lmasks, fmasks)
        self._last_batch_rows = int(inputs[0].shape[0])
        core.check_grad_accum_batch(
            self.grad_accum, int(inputs[0].shape[0])
        )
        prof = profiler.get_active_profiler()
        if prof is not None:
            prof.begin_step(self.iteration_count + 1,
                            parent=self._fit_span)
        score = None
        for _ in range(self.conf.iterations):
            if self._jit_step is None:
                # a listener may flip telemetry/guard mid-fit
                self._jit_step = self._build_step()
            with core.dispatch_span(self, None, 1, self.iteration_count,
                                    self._last_batch_rows):
                lrs = self.updater_def.scheduled_lrs(
                    self.iteration_count)
                t = jnp.asarray(self.iteration_count + 1, jnp.float32)
                rng = jax.random.fold_in(self._base_key,
                                         self.iteration_count)
                out = self._jit_step(
                    self.params, self.updater_state, self.state,
                    inputs, labels, lmasks, fmasks,
                    {k: jnp.asarray(v, jnp.float32)
                     for k, v in lrs.items()},
                    t, rng, *self._step_extra_args(),
                )
                score, ok = core.apply_step_out(self, out)
            guard = self.divergence_guard
            self.iteration_count += 1
            self._last_score = score  # device array; sync deferred
            window = self._dispatch_window
            if window is not None:
                window.push(score, ok)
            elif guard is not None:
                if bool(ok):  # device sync — the cost of supervision
                    guard.good_step()
                else:
                    guard.bad_step(self)
            if self.listeners:
                with core.listeners_span(self, prof, 1):
                    for listener in self.listeners:
                        listener.iteration_done(self,
                                                self.iteration_count)
            self._reset_recurrent_state()
        if prof is not None:
            prof.end_step(model=self, ds=ds, score=self._last_score,
                          grad_norm=getattr(self, "_last_grad_norm",
                                            None),
                          rows=self._last_batch_rows)
        return score  # 0-d device array; float() to sync

    def _fit_tbptt(self, inputs, labels, lmasks, fmasks) -> float:
        """Truncated BPTT for the DAG engine: slice every time-bearing
        array into ``tbptt_fwd_length`` chunks and carry recurrent
        state between chunks via the layer-state pytree (reference
        ``ComputationGraph.doTruncatedBPTT``). Non-time inputs ride
        along unchanged each chunk."""
        fwd = self.conf.tbptt_fwd_length
        t_lens = {x.shape[2] for x in inputs if x.ndim == 3}
        for group in (labels, lmasks, fmasks):
            for v in group or []:
                if v is not None and v.ndim == 3:
                    t_lens.add(v.shape[2])
        if len(t_lens) > 1:
            raise ValueError(
                "TruncatedBPTT requires every time-series input/label "
                f"to share one sequence length; got {sorted(t_lens)} "
                "(chunking mixed lengths would re-feed the shorter "
                "series each chunk with stale recurrent carry)"
            )
        t_total = t_lens.pop()

        def cut3(vs, s, e):
            if vs is None:
                return None
            return [
                v[:, :, s:e]
                if v is not None and v.ndim == 3 and v.shape[2] == t_total
                else v
                for v in vs
            ]

        def cut_mask(vs, s, e):
            if vs is None:
                return None
            return [
                m[:, s:e]
                if m is not None and m.ndim == 2 and m.shape[1] == t_total
                else m
                for m in vs
            ]

        if self._jit_step is None:
            self._jit_step = self._build_step()
        self._reset_recurrent_state()
        score = None
        for start in range(0, t_total, fwd):
            end = min(start + fwd, t_total)
            lrs = self.updater_def.scheduled_lrs(self.iteration_count)
            t = jnp.asarray(self.iteration_count + 1, jnp.float32)
            rng = jax.random.fold_in(
                self._base_key, self.iteration_count
            )
            out = self._jit_step(
                self.params, self.updater_state, self.state,
                cut3(inputs, start, end), cut3(labels, start, end),
                cut_mask(lmasks, start, end),
                cut_mask(fmasks, start, end),
                {k: jnp.asarray(v, jnp.float32) for k, v in lrs.items()},
                t, rng, *self._step_extra_args(),
            )
            guard = self.divergence_guard
            score, ok = core.apply_step_out(self, out)
            self.iteration_count += 1
            self._last_score = score
            if guard is not None:
                if bool(ok):
                    guard.good_step()
                else:
                    guard.bad_step(self)
            for listener in self.listeners:
                listener.iteration_done(self, self.iteration_count)
        self._reset_recurrent_state()
        return score

    def _reset_recurrent_state(self) -> None:
        for n in self.layer_vertex_names:
            layer = self.conf.vertices[n].layer_conf
            if layer.is_recurrent():
                self.state[n] = {}

    # ------------------------------------------------------------------

    def _output_fn(self):
        """Pure inference forward closure shared by the jitted
        ``output`` path and the AOT export (identical trace ->
        bitwise identical results)."""
        def out_fn(params, state, inputs, fmasks):
            values, _, _ = self._forward_values(
                params, state, inputs, train=False, rng=None,
                fmasks=fmasks, use_scan=True,
            )
            return [values[n] for n in self.conf.outputs]
        return out_fn

    def output(self, *inputs, features_masks=None) -> List[jax.Array]:
        """Activated values of the output vertices (reference
        ``ComputationGraph.output``). ``features_masks``: per-graph-
        input [b, t] masks threaded to recurrent branches (reference
        ``output(..., featureMaskArrays)``)."""
        if self.params is None:
            self.init()
        dtype = self._dtype()
        if self._aot_outputs and features_masks is None:
            fn = self._aot_outputs.get(tuple(
                tuple(int(d) for d in np.shape(x)) for x in inputs
            ))
            if fn is not None:
                return fn(self.params, self.state,
                          [jnp.asarray(x, dtype) for x in inputs])
        if self._jit_output is None:
            self._jit_output = jax.jit(self._output_fn())
        arr = [jnp.asarray(x, dtype) for x in inputs]
        fm = None
        if features_masks is not None:
            fm = [
                None if m is None else jnp.asarray(m, dtype)
                for m in _as_list(features_masks)
            ]
        return self._jit_output(self.params, self.state, arr, fm)

    # -- AOT export/install (compile/aot.py) ---------------------------

    def _aot_shape_key(self, shapes) -> tuple:
        """Normalize to the nested key form: one shape -> a 1-tuple
        of shape tuples (the DAG engine is list-of-inputs shaped)."""
        shapes = tuple(shapes)
        if shapes and not isinstance(shapes[0], (tuple, list)):
            shapes = (shapes,)
        return tuple(tuple(int(d) for d in s) for s in shapes)

    def _output_kind(self) -> str:
        # scan AND kernel dispatch both change the compiled inference
        # program
        return ("output" + ("+scan" if self.scan_layers else "")
                + core.kernel_kind_suffix(self))

    def aot_fingerprint(self, shapes, kind: Optional[str] = None) -> str:
        from deeplearning4j_tpu.compile.aot import artifact_fingerprint

        return artifact_fingerprint(
            self.conf.to_dict(), self._aot_shape_key(shapes),
            str(self._dtype()),
            kind if kind is not None else self._output_kind(),
        )

    def aot_export_output(self, shapes, registry=None) -> bytes:
        """Serialize the compiled inference forward for inputs of
        exactly ``shapes`` (one shape tuple, or a tuple of them for
        multi-input graphs; inference mode, no masks)."""
        if self.params is None:
            self.init()
        from deeplearning4j_tpu.compile.aot import export_artifact

        key = self._aot_shape_key(shapes)
        dtype = self._dtype()
        base = self._output_fn()
        fn = jax.jit(lambda p, s, arr: base(p, s, arr, None))
        specs = [jax.ShapeDtypeStruct(s, dtype) for s in key]
        return export_artifact(
            fn, (self.params, self.state, specs),
            fingerprint=self.aot_fingerprint(key),
            shape=key, kind=self._output_kind(),
            name="output-" + "+".join(
                "x".join(str(d) for d in s) for s in key
            ),
            registry=registry,
        )

    def aot_install_output(self, shapes, artifact,
                           registry=None) -> bool:
        """Install an inference executable for exactly ``shapes``
        from artifact bytes (fingerprint-checked; stale/corrupt
        artifacts are refused silently) or a callable."""
        key = self._aot_shape_key(shapes)
        if callable(artifact):
            self._aot_outputs[key] = artifact
            return True
        from deeplearning4j_tpu.compile.aot import load_artifact

        fn = load_artifact(
            artifact,
            expected_fingerprint=self.aot_fingerprint(key),
            registry=registry,
        )
        if fn is None:
            return False
        self._aot_outputs[key] = fn
        return True

    def aot_output_shapes(self) -> List[tuple]:
        return list(self._aot_outputs)

    def _step_kind(self) -> str:
        """AOT kind string for the train step: guard/telemetry flags
        and whole-net transforms are part of the artifact identity
        (same scheme as MultiLayerNetwork)."""
        return (
            "step"
            + ("+guard" if self.divergence_guard is not None else "")
            + ("+telemetry" if self._telemetry_grad_norm else "")
            + core.transform_kind_suffix(self)
        )

    def aot_export_step(self, ds, registry=None) -> bytes:
        """Serialize the compiled train step specialized to ``ds``'s
        input/label shapes (no masks)."""
        if self.params is None:
            self.init()
        from deeplearning4j_tpu.compile.aot import export_artifact

        dtype = self._dtype()
        inputs = [jnp.asarray(f, dtype)
                  for f in _as_list(ds.features)]
        labels = [jnp.asarray(l, dtype) for l in _as_list(ds.labels)]
        lrs = {
            k: jnp.asarray(v, jnp.float32) for k, v in
            self.updater_def.scheduled_lrs(self.iteration_count).items()
        }
        t = jnp.asarray(1, jnp.float32)
        rng = jax.random.fold_in(self._base_key, 0)
        x_key = tuple(tuple(int(d) for d in a.shape) for a in inputs)
        y_key = tuple(tuple(int(d) for d in a.shape) for a in labels)
        return export_artifact(
            self._build_step(),
            (self.params, self.updater_state, self.state, inputs,
             labels, None, None, lrs, t, rng)
            + self._step_extra_args(),
            fingerprint=self.aot_fingerprint(
                x_key, kind=self._step_kind()
            ),
            shape=x_key, kind=self._step_kind(),
            name="step-" + "+".join(
                "x".join(str(d) for d in s) for s in x_key
            ),
            meta_extra={"label_shape": [list(s) for s in y_key]},
            registry=registry,
        )

    def aot_install_step(self, artifact, registry=None) -> bool:
        """Install an AOT train-step executable as ``_jit_step``
        (matching shapes run the restored executable; anything else
        lazily JITs — ``compile.aot.AotStepFunction``)."""
        from deeplearning4j_tpu.compile.aot import (
            AotStepFunction,
            load_artifact,
            peek_meta,
        )

        try:
            meta = peek_meta(artifact)
            x_key = self._aot_shape_key(meta["shape"])
            y_key = self._aot_shape_key(meta["label_shape"])
        except Exception:
            return False
        fn = load_artifact(
            artifact,
            expected_fingerprint=self.aot_fingerprint(
                x_key, kind=self._step_kind()
            ),
            registry=registry,
        )
        if fn is None:
            return False
        self._jit_step = AotStepFunction(
            fn, x_key, y_key, self._build_step
        )
        return True

    def output_padded(self, *inputs, n_valid, features_masks=None):
        """Inference on row-padded batches: every graph input is
        padded to the same bucketed row count; returns each output
        vertex's activations sliced back to the first ``n_valid``
        rows. Same contract as ``MultiLayerNetwork.output_padded`` —
        shares ``output``'s jitted program (one executable per bucket
        shape), relies on row-independence of inference-mode vertices
        (enforced bitwise by ``tests/test_batching.py``), and
        composes ``features_masks`` that cover only the valid rows
        with all-ones padding rows."""
        n = int(n_valid)
        if not inputs:
            raise ValueError("output_padded needs at least one input")
        b = int(np.shape(inputs[0])[0])
        if not 0 < n <= b:
            raise ValueError(
                f"n_valid must be in [1, {b}] for a {b}-row batch; "
                f"got {n}"
            )
        fms = features_masks
        if fms is not None:
            padded_fms = []
            for m in _as_list(fms):
                if m is not None:
                    m = np.asarray(m)
                    if m.shape[0] == n and n < b:
                        m = np.concatenate(
                            [m, np.ones((b - n,) + m.shape[1:],
                                        m.dtype)],
                            axis=0,
                        )
                    elif m.shape[0] != b:
                        raise ValueError(
                            f"features_mask covers {m.shape[0]} rows;"
                            f" expected {n} (valid) or {b} (padded)"
                        )
                padded_fms.append(m)
            fms = padded_fms
        outs = self.output(*inputs, features_masks=fms)
        return [o[:n] for o in outs]

    def feed_forward(self, *inputs, train: bool = False) -> Dict[str, Any]:
        """Activations of EVERY vertex by name (reference
        ``ComputationGraph.feedForward`` returns the activation map) —
        scan-over-layers stays off here so inner chain members'
        values are materialized."""
        if self.params is None:
            self.init()
        dtype = self._dtype()
        arr = [jnp.asarray(x, dtype) for x in inputs]
        # train=True must apply dropout like the fit path does
        rng = (
            jax.random.fold_in(self._base_key, self.iteration_count)
            if train else None
        )
        values, _, _ = self._forward_values(
            self.params, self.state, arr, train=train, rng=rng
        )
        return values

    def rnn_time_step(self, *inputs) -> List[jax.Array]:
        """Feed one (or a few) timesteps per input, carrying recurrent
        state across calls (reference ``ComputationGraph.rnnTimeStep``,
        ``ComputationGraph.java:1748``). Inputs [b, size] or
        [b, size, t]; returns the output vertices' activations with the
        same time-axis convention as the inputs."""
        if self.params is None:
            self.init()
        for n in self.layer_vertex_names:
            lc = self.conf.vertices[n].layer_conf
            if not lc.can_stream():
                raise ValueError(
                    f"Vertex '{n}' ({type(lc).__name__}) cannot be used "
                    "with rnn_time_step — it needs the full sequence "
                    "(reference throws UnsupportedOperationException)"
                )
        dtype = self._dtype()
        arr = [jnp.asarray(x, dtype) for x in inputs]
        # each [b, size] input gets a singleton time axis independently;
        # outputs come back 2-d only when EVERY input arrived 2-d
        was_2d = [x.ndim == 2 for x in arr]
        squeeze = bool(arr) and all(was_2d)
        arr = [x[:, :, None] if w else x for x, w in zip(arr, was_2d)]
        t_new = max(
            (int(x.shape[2]) for x in arr if x.ndim == 3), default=1
        )
        named = [
            (n, self.conf.vertices[n].layer_conf)
            for n in self.layer_vertex_names
        ]
        core.stream_guard_and_prime(
            named, self._rnn_state, self._stream_steps, t_new,
            int(arr[0].shape[0]) if arr else 1, dtype,
        )
        merged = dict(self.state)
        for name, carry in self._rnn_state.items():
            merged[name] = {**merged.get(name, {}), **carry}
        if self._jit_rnn_step is None:
            def rnn_step(params, state, inputs):
                values, _, new_state = self._forward_values(
                    params, state, inputs, train=False, rng=None
                )
                return [values[n] for n in self.conf.outputs], new_state
            self._jit_rnn_step = jax.jit(rnn_step)
        outs, new_state = self._jit_rnn_step(self.params, merged, arr)
        core.extract_stream_state(named, new_state, self._rnn_state)
        self._stream_steps += t_new
        return [o[:, :, 0] if squeeze and o.ndim == 3 else o
                for o in outs]

    def rnn_clear_previous_state(self) -> None:
        """Reference ``rnnClearPreviousState``."""
        self._rnn_state = {}
        self._stream_steps = 0

    def score(self, ds) -> float:
        dtype = self._dtype()
        features = [jnp.asarray(f, dtype) for f in _as_list(ds.features)]
        labels = [jnp.asarray(l, dtype) for l in _as_list(ds.labels)]
        lmasks = _as_list(getattr(ds, "labels_masks", None)
                          or getattr(ds, "labels_mask", None)) or None
        fmasks = _as_list(getattr(ds, "features_masks", None)
                          or getattr(ds, "features_mask", None)) or None
        if lmasks:
            lmasks = [
                jnp.asarray(m, dtype) if m is not None else None
                for m in lmasks
            ]
        if fmasks:
            fmasks = [
                jnp.asarray(m, dtype) if m is not None else None
                for m in fmasks
            ]
        s, _ = self._score_pure(
            self.params, self.state, features, labels, lmasks, None,
            train=False, fmasks=fmasks,
        )
        return float(s)

    def evaluate(self, iterator):
        from deeplearning4j_tpu.datasets.api import ChunkedDataSet
        from deeplearning4j_tpu.eval.evaluation import Evaluation

        e = Evaluation()
        for item in iterator:
            batches = (
                item.to_datasets() if isinstance(item, ChunkedDataSet)
                else [item]
            )
            for ds in batches:
                self._evaluate_one(e, ds)
        if hasattr(iterator, "reset"):
            iterator.reset()
        return e

    def _evaluate_one(self, e, ds) -> None:
        fm = (getattr(ds, "features_masks", None)
              or getattr(ds, "features_mask", None))
        out = self.output(
            *_as_list(ds.features), features_masks=fm
        )[0]
        labels = np.asarray(_as_list(ds.labels)[0])
        m = _as_list(getattr(ds, "labels_masks", None)
                     or getattr(ds, "labels_mask", None))
        mask = m[0] if m else None
        if mask is None and labels.ndim == 3:
            # per-timestep labels without a labels mask: fall back
            # to the features mask (same rule as MLN.evaluate);
            # 2-d per-sequence labels must not take a [b, t] mask
            fml = _as_list(fm)
            mask = fml[0] if fml else None
        e.eval(labels, np.asarray(out),
               mask=np.asarray(mask) if mask is not None else None)

    # ------------------------------------------------------------------

    def set_listeners(self, *listeners) -> None:
        self.listeners = list(listeners)

    def copy(self) -> "ComputationGraph":
        # Deep-copy device buffers (the jitted step donates them).
        clone = lambda a: jnp.array(a, copy=True)
        g = ComputationGraph(self.conf)
        g.init(params=jax.tree_util.tree_map(clone, self.params))
        g.updater_state = jax.tree_util.tree_map(clone, self.updater_state)
        g.state = jax.tree_util.tree_map(clone, self.state)
        return g

    def num_params(self) -> int:
        return sum(
            int(np.prod(p.shape))
            for lp in self.params.values()
            for p in lp.values()
        )

    def _flat_order(self) -> List[Tuple[str, str]]:
        order = []
        for name in self.layer_vertex_names:
            pnames = list(self.params[name].keys())
            preferred = [p for p in ("W", "b") if p in pnames]
            rest = [p for p in pnames if p not in ("W", "b")]
            for pn in preferred + sorted(rest):
                order.append((name, pn))
        return order

    def params_flat(self) -> np.ndarray:
        return np.concatenate([
            np.asarray(self.params[ln][pn]).ravel()
            for ln, pn in self._flat_order()
        ]) if self.params else np.zeros((0,))

    def set_params_flat(self, vec) -> None:
        vec = np.asarray(vec)
        off = 0
        for ln, pn in self._flat_order():
            p = self.params[ln][pn]
            n = int(np.prod(p.shape))
            self.params[ln][pn] = jnp.asarray(
                vec[off:off + n].reshape(p.shape), p.dtype
            )
            off += n

    def summary(self) -> str:
        lines = ["=" * 72]
        lines.append(f"{'vertex':<20}{'type':<30}{'params':>10}")
        lines.append("-" * 72)
        total = 0
        for name in self.topo:
            v = self.conf.vertices[name]
            n = 0
            if self.params and name in self.params:
                n = sum(
                    int(np.prod(p.shape))
                    for p in self.params[name].values()
                )
            total += n
            tname = (
                type(v.layer_conf).__name__ if isinstance(v, LayerVertex)
                else type(v).__name__
            )
            lines.append(f"{name:<20}{tname:<30}{n:>10}")
        lines.append("-" * 72)
        lines.append(f"Total params: {total}")
        lines.append("=" * 72)
        return "\n".join(lines)
