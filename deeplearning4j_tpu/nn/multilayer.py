"""MultiLayerNetwork — the sequential-stack model (reference:
``nn/multilayer/MultiLayerNetwork.java``, 2,534 LoC).

TPU-first redesign of the reference's imperative engine:

- The reference's ``fit`` path crosses JVM->JNI->libnd4j per op
  (SURVEY.md §3.1); here the ENTIRE minibatch step — forward, loss,
  backward (``jax.grad``), gradient normalization, updater, parameter
  step — is one jitted XLA program per input shape, compiled once and
  cached. Parameters/updater-state buffers are donated so the step
  updates in place in HBM.
- The reference flattens params into one 1-D view array
  (``init():367``); the idiomatic equivalent is a pytree
  ``{layer: {name: array}}`` (shards naturally under pjit). A flat view
  is still offered for serializer/tooling parity
  (``params_flat``/``set_params_flat``).
- Backprop (``calcBackpropGradients:1134``) does not exist as code:
  ``jax.grad`` differentiates the same forward used for inference.

This class is a thin wrapper around the unified functional core
(``nn/core.py``): the pure forward/score, the jitted step builders,
the scan-fused multi-step, the fit drivers, and the whole-net
transforms (scan-over-layers, activation remat, dynamic loss scaling)
are all implemented there ONCE and shared with ``ComputationGraph``
(enforced by ``scripts/lint_parity.py``).
"""

from __future__ import annotations

import collections
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.nn import core
from deeplearning4j_tpu.observability import profiler
from deeplearning4j_tpu.nn.conf.multi_layer import MultiLayerConfiguration
from deeplearning4j_tpu.nn.conf.preprocessors import ShapeContext
from deeplearning4j_tpu.nn.updaters import MultiLayerUpdaterDef

# Compatibility aliases: these helpers grew up in this module and are
# imported from here by older call sites; the canonical definitions
# live in the functional core now.
_dtype_of = core.dtype_of
_compute_dtype_of = core.compute_dtype_of
_cast_floats = core.cast_floats
_to_device = core.to_device
_cast_stacked = core.cast_stacked
_stack_on_device = core.stack_on_device
_nbytes = core.nbytes
_iter_unchunked = core.iter_unchunked
_reg_penalty = core.reg_penalty
_scan_consts = core.scan_consts
_note_it0 = core.note_it0
_cached_epoch_plan = core.cached_epoch_plan
_build_scan_plan = core.build_scan_plan
_stream_guard_and_prime = core.stream_guard_and_prime
_extract_stream_state = core.extract_stream_state


class MultiLayerNetwork:
    def __init__(self, conf: MultiLayerConfiguration):
        self.conf = conf
        self.layer_names: List[str] = [
            conf.layer_name(i) for i in range(len(conf.layers))
        ]
        if len(set(self.layer_names)) != len(self.layer_names):
            from deeplearning4j_tpu.exceptions import (
                DL4JInvalidConfigException,
            )

            raise DL4JInvalidConfigException(
                "Duplicate layer names in configuration"
            )
        self.params: Optional[Dict[str, Dict[str, jax.Array]]] = None
        self.state: Dict[str, dict] = {}
        self.updater_def = MultiLayerUpdaterDef({
            name: layer.updater_settings()
            for name, layer in zip(self.layer_names, conf.layers)
        })
        self.updater_state = None
        self.iteration_count = 0
        self.epoch_count = 0
        self._last_score = float("nan")
        self.listeners: List[Any] = []
        self._rnn_state: Dict[str, Any] = {}   # streaming rnnTimeStep state
        self._stream_steps = 0  # timesteps consumed vs finite caches
        self._jit_step = None
        self._jit_multi_step = None
        self._jit_tbptt_multi_step = None
        self._solver = None  # lazily built for LBFGS/CG/line-search
        self.scan_chunk = 16  # minibatches fused per dispatch
        # multi-epoch fits keep the dataset HBM-resident up to this
        # size, derived from the device's reported memory limit
        # (4 GiB fallback when the runtime exposes no memory_stats())
        from deeplearning4j_tpu.util.device import device_cache_budget_bytes

        self.device_cache_bytes = device_cache_budget_bytes()
        self._jit_output = None
        # AOT-restored inference executables by exact input shape
        # (compile/aot.py): consulted by output() before the jit
        # path, so a warm restart serves without ever building
        # _jit_output. Empty dict = one falsy check on the hot path.
        self._aot_outputs: Dict[Tuple[int, ...], Callable] = {}
        self._jit_rnn_step = None
        self._jit_pretrain_steps: Dict[int, Callable] = {}
        self._jit_pretrain_input = None
        self._pretrain_done = False
        # device-resident scan constants (see core.scan_consts)
        self._scan_const_cache: Dict[Any, Any] = {}
        self._it0_dev = None
        self._it0_shadow = -1
        # scores of the newest scan chunks enqueued: what the scan
        # path's run-ahead bound waits on (core.await_scan_slot)
        self._scan_inflight = collections.deque(
            maxlen=core.SCAN_CHUNKS_AHEAD)
        self._base_key = jax.random.PRNGKey(conf.seed)
        # resilience.DivergenceGuard (set_divergence_guard): when set,
        # the jitted step suppresses non-finite updates in-jit and the
        # host applies skip/rollback policy; forces the per-step path
        # (the fused scan cannot consult the guard mid-dispatch)
        self.divergence_guard = None
        # async dispatch knobs (the fit loop runs through an
        # AsyncDispatchWindow): at most max_in_flight steps
        # dispatched-but-incomplete; the guard's ok-flag is collected
        # guard_lag steps late (None -> max_in_flight; rollback policy
        # forces 0 — see parallel/dispatch.py)
        self.max_in_flight = 2
        self.guard_lag = None
        self._dispatch_window = None
        self._fit_span = None  # core.fit_batches' open span, if traced
        # observability.TelemetryListener (enable_step_telemetry):
        # when set, the jitted step also returns the gradient global
        # L2 norm — one fused scalar, read lazily by the listener
        self._telemetry_grad_norm = False
        self._last_grad_norm = None  # 0-d device array; float() syncs
        self._last_batch_rows = None  # host int; examples/sec signal
        # whole-net transform knobs (scan_layers / remat / loss_scale)
        # — see core.set_transforms; seeded from config hints
        core.init_transforms(self, conf)

    @property
    def score_value(self) -> float:
        """Latest minibatch score. Reading this syncs with the device
        (the jitted step returns the score as a device scalar and does
        NOT block — throughput-critical loops should avoid reading it
        every step; PerformanceListener doesn't)."""
        return float(self._last_score)

    @score_value.setter
    def score_value(self, v) -> None:
        self._last_score = v

    # ------------------------------------------------------------------
    # init (reference MultiLayerNetwork.init():367)
    # ------------------------------------------------------------------

    def init(self, params: Optional[dict] = None) -> "MultiLayerNetwork":
        dtype = _dtype_of(self.conf)
        if params is not None:
            # checkpoint npz round-trips drop empty entries; param-less
            # layers (pooling, activation) get their {} slot back, but
            # a missing PARAMETERIZED layer is checkpoint corruption —
            # fail here, not at a KeyError deep inside the first trace
            restored = {}
            for name, layer in zip(self.layer_names, self.conf.layers):
                if name in params:
                    restored[name] = params[name]
                elif layer.init_params(self._base_key, dtype):
                    raise ValueError(
                        f"checkpoint has no params for layer '{name}' "
                        f"({type(layer).__name__})"
                    )
                else:
                    restored[name] = {}
            self.params = restored
        else:
            keys = jax.random.split(
                self._base_key, max(len(self.conf.layers), 1)
            )
            self.params = {
                name: layer.init_params(k, dtype)
                for name, layer, k in zip(
                    self.layer_names, self.conf.layers, keys
                )
            }
        self.state = {
            name: layer.init_state(dtype)
            for name, layer in zip(self.layer_names, self.conf.layers)
        }
        self.updater_state = self.updater_def.init(self.params)
        self._pretrain_done = False  # fresh params ⇒ pretrain again
        return self

    # ------------------------------------------------------------------
    # whole-net transforms (implemented once in nn/core.py)
    # ------------------------------------------------------------------

    def set_transforms(self, scan_layers=None, remat=None,
                       loss_scale=None,
                       megastep=None) -> "MultiLayerNetwork":
        """(Re)configure the whole-net transforms: ``scan_layers``
        (stack homogeneous layer runs under one ``lax.scan`` —
        O(depth) HLO becomes O(1), collapsing deep-stack compile
        time), ``remat`` (``none | dots_saveable | full`` activation
        rematerialization via ``jax.checkpoint`` — recompute FLOPs
        for activation HBM), ``loss_scale`` (dynamic loss scaling
        for ``compute_dtype="float16"``; True = default 2**15), and
        ``megastep`` (K>1 folds K optimizer steps + on-device metric
        accumulation into ONE XLA dispatch, read back once per
        chunk). Trajectories are bitwise identical with the
        transforms on or off; changed knobs invalidate the compiled
        programs."""
        core.set_transforms(self, scan_layers, remat, loss_scale,
                            megastep)
        return self

    @property
    def _loss_scale_active(self) -> bool:
        return core.loss_scale_active(self)

    def _active_layer_runs(self) -> tuple:
        if self._layer_runs_cache is None:
            self._layer_runs_cache = tuple(core.detect_layer_runs(
                self.conf.layers, self.conf.preprocessors
            ))
        return self._layer_runs_cache

    def scan_layer_run_count(self) -> int:
        """Active scanned layer runs (telemetry signal)."""
        return len(self._active_layer_runs()) if self.scan_layers else 0

    # ------------------------------------------------------------------
    # pure forward builders (these close over conf only — safe to jit)
    # ------------------------------------------------------------------

    def _forward_pure(
        self, params, state, x, *, train: bool, rng, upto: Optional[int] = None,
        collect: bool = False, fmask=None,
    ):
        """Forward through layers [0, upto]; returns (activation, preout
        of last executed layer, new_state, [activations]). Delegates to
        ``core.sequential_forward`` with this model's transform knobs."""
        return core.sequential_forward(
            self.conf, self.layer_names, params, state, x, train=train,
            rng=rng, upto=upto, collect=collect, fmask=fmask,
            scan_layers=self.scan_layers, remat=self.remat,
            runs=self._active_layer_runs() if self.scan_layers else (),
        )

    def _score_pure(self, params, state, x, labels, mask, rng, *,
                    train: bool, fmask=None):
        """Loss score incl. L1/L2 penalty (core.sequential_score)."""
        return core.sequential_score(
            self.conf, self.layer_names, params, state, x, labels,
            mask, rng, train=train, fmask=fmask,
            scan_layers=self.scan_layers, remat=self.remat,
            runs=self._active_layer_runs() if self.scan_layers else (),
        )

    # ------------------------------------------------------------------
    # jitted train step (built by the core)
    # ------------------------------------------------------------------

    def _score_fn(self):
        """The engine's contribution to the core step builders: a pure
        ``(params, state, x, labels, mask, fmask, rng) ->
        (score, new_state)`` closure."""
        def score_fn(p, state, x, labels, mask, fmask, rng):
            return self._score_pure(
                p, state, x, labels, mask, rng, train=True, fmask=fmask
            )
        return score_fn

    def _build_step(self) -> Callable:
        step_dtype = _dtype_of(self.conf)

        def cast(x, labels, mask, fmask):
            # on-device cast for integer-typed inputs
            return (
                x.astype(step_dtype), labels.astype(step_dtype),
                mask, fmask,
            )

        return core.build_step(
            self._score_fn(), self.updater_def, cast=cast,
            guarded=self.divergence_guard is not None,
            telemetry=self._telemetry_grad_norm,
            loss_scale=self._loss_scale_active,
            grad_accum=self.grad_accum,
            recurrent_names=self._recurrent_names(),
            zero_layout=self._zero_layout,
            stat_guard=core.stat_guard_config(self),
        )

    def set_divergence_guard(self, guard) -> None:
        """(Un)install a resilience.DivergenceGuard on the SGD train
        step (in-jit NaN/Inf suppression + host-side skip/rollback;
        with ``guard.stats`` also the statistical anomaly guard, whose
        EWMA state threads through the step). Rebuilds the jitted
        step: the guarded step returns extra outputs."""
        self.divergence_guard = guard
        self._jit_step = None
        self._jit_megastep = None

    def set_batch_validator(self, validator, quarantine=None
                            ) -> "MultiLayerNetwork":
        """(Un)install the data-plane defense (``datasets.validate``)
        on this model's ``fit`` loops."""
        core.set_batch_validator(self, validator, quarantine)
        return self

    def enable_step_telemetry(self, enabled: bool = True) -> None:
        """(Un)install step telemetry: the jitted per-step program
        additionally returns the gradient global L2 norm (one fused
        scalar — no second backward pass, no extra sync until
        something reads ``_last_grad_norm``). Rebuilds the step on
        change; observability.TelemetryListener flips this on."""
        if enabled != self._telemetry_grad_norm:
            self._telemetry_grad_norm = enabled
            self._jit_step = None
            self._jit_megastep = None

    def _multi_cast(self):
        multi_dtype = _dtype_of(self.conf)

        def cast(x, labels, mask, fmask):
            # keep the cast-on-device contract symmetric with the
            # per-step path, which converts masks to the compute dtype
            return (
                x.astype(multi_dtype), labels.astype(multi_dtype),
                None if mask is None else mask.astype(multi_dtype),
                None if fmask is None else fmask.astype(multi_dtype),
            )
        return cast

    def _recurrent_names(self) -> List[str]:
        return [
            name for name, layer in zip(self.layer_names, self.conf.layers)
            if layer.is_recurrent()
        ]

    def _build_multi_step(self) -> Callable:
        return core.build_multi_step(
            self._score_fn(), self.updater_def,
            cast=self._multi_cast(),
            recurrent_names=self._recurrent_names(),
            grad_accum=self.grad_accum,
            zero_layout=self._zero_layout,
        )

    def _build_megastep(self) -> Callable:
        """K full train steps fused into one dispatch — the multi
        step's scan discipline with the FULL per-step flavor (guard /
        telemetry / loss scale / stat guard / zero) threading through
        the carry (core.build_megastep)."""
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

    def _build_tbptt_multi_step(self) -> Callable:
        """TBPTT chunks fused into ONE XLA dispatch: the recurrent
        carry THREADS through the ``lax.scan`` and per-step ``resets``
        zero it at minibatch boundaries (core.build_multi_step in
        tbptt mode)."""
        return core.build_multi_step(
            self._score_fn(), self.updater_def,
            cast=self._multi_cast(),
            recurrent_names=self._recurrent_names(),
            tbptt=True,
        )

    def _can_fuse_tbptt(self, x, y, fwd: int) -> bool:
        """The fused single-dispatch TBPTT applies when chunks tile the
        sequence exactly, labels are per-timestep, every recurrent
        layer exposes an h/c streaming carry, and listeners accept
        batched iteration callbacks."""
        return (
            self.conf.iterations == 1
            and x.ndim == 3
            and x.shape[2] % fwd == 0
            and y.ndim == 3
            and y.shape[2] == x.shape[2]
            # guarded/loss-scaled runs use the per-chunk step (the
            # fused scan cannot consult either mid-dispatch)
            and self.divergence_guard is None
            and not self._loss_scale_active
            and all(
                layer.can_stream()
                and getattr(layer, "init_stream_state", None) is not None
                for layer in self.conf.layers
                if layer.is_recurrent()
            )
            and all(
                getattr(l, "supports_batched_iterations", False)
                for l in self.listeners
            )
        )

    def _stack_tbptt(self, x, y, mask, fmask):
        """Split one minibatch's device arrays into stacked TBPTT
        chunks for the fused scan: [b, n, k*fwd] -> [k, b, n, fwd]."""
        fwd = self.conf.tbptt_fwd_length
        b = x.shape[0]
        k = x.shape[2] // fwd

        def chunk3(v):
            return jnp.moveaxis(
                v.reshape(v.shape[0], v.shape[1], k, fwd), 2, 0
            )

        def chunk2(m):
            return (
                None if m is None
                else jnp.moveaxis(m.reshape(b, k, fwd), 1, 0)
            )

        resets = jnp.zeros(k, jnp.float32).at[0].set(1.0)
        return (
            chunk3(x), chunk3(y), chunk2(mask), chunk2(fmask), resets,
            k, b,
        )

    def _fit_tbptt_fused(self, x, y, mask, fmask) -> float:
        return self._run_tbptt_stacked(
            self._stack_tbptt(x, y, mask, fmask)
        )

    def _run_tbptt_stacked(self, stacked) -> float:
        xs, ys, masks, fmasks, resets, k, b = stacked
        cdt = _compute_dtype_of(self.conf)
        state = dict(self.state)
        for name, layer in zip(self.layer_names, self.conf.layers):
            if layer.is_recurrent():
                state[name] = layer.init_stream_state(b, cdt)
        it0 = self.iteration_count
        lr_stack, it0_dev = _scan_consts(self, k, it0)
        if self._jit_tbptt_multi_step is None:
            self._jit_tbptt_multi_step = self._build_tbptt_multi_step()
        (
            self.params, self.updater_state, new_state, scores,
            it0_next,
        ) = self._jit_tbptt_multi_step(
            self.params, self.updater_state, state,
            xs, ys, masks, fmasks,
            lr_stack, it0_dev, self._base_key,
            resets,
        )
        _note_it0(self, it0_next, it0 + k)
        self.state = new_state
        self.iteration_count += k
        self._last_score = scores[-1]
        if self.listeners:
            for i in range(k):
                self._last_score = scores[i]
                for listener in self.listeners:
                    listener.iteration_done(self, it0 + i + 1)
            self._last_score = scores[-1]
        self._reset_recurrent_state()
        return self._last_score

    def _can_scan_steps(self) -> bool:
        """Scan-fused fitting applies when per-minibatch semantics are
        stateless: standard backprop (recurrent carry resets each
        minibatch — the scan body restores the empty entries), not
        TBPTT (whose carry threads across host-side chunks), and
        neither divergence guard nor dynamic loss scaling is active
        (both need the per-step program). Listeners that time
        individual iterations would observe k near-simultaneous
        callbacks, so attached listeners also force the per-step path
        unless they declare ``supports_batched_iterations = True``."""
        return (
            self.conf.iterations == 1
            and self.conf.backprop
            and self.conf.backprop_type != "TruncatedBPTT"
            and self.conf.optimization_algo
            == "STOCHASTIC_GRADIENT_DESCENT"
            and self.divergence_guard is None
            and not self._loss_scale_active
            and all(
                getattr(l, "supports_batched_iterations", False)
                for l in self.listeners
            )
        )

    def _ds_scan_sig(self, ds) -> tuple:
        def sh(a):
            # np.shape, NOT np.asarray(a).shape: asarray on a device
            # array is a blocking device->host materialization — per
            # batch, on the streamed-iterator path
            return None if a is None else tuple(np.shape(a))
        return (
            sh(ds.features), sh(ds.labels),
            sh(getattr(ds, "labels_mask", None)),
            sh(getattr(ds, "features_mask", None)),
        )

    def _stack_chunk(self, batches: List[Any]):
        """Stack k same-shaped minibatches into device-resident arrays
        for one fused multi-step dispatch. Integer inputs keep their
        native width (cast on device); already-device arrays stack on
        device without a host round trip."""
        dtype = _dtype_of(self.conf)

        def stack(get):
            first = get(batches[0])
            if first is None:
                return None
            return _stack_on_device([get(b) for b in batches], dtype)

        return (
            stack(lambda b: b.features),
            stack(lambda b: b.labels),
            stack(lambda b: getattr(b, "labels_mask", None)),
            stack(lambda b: getattr(b, "features_mask", None)),
            len(batches),
        )

    def _prep_prestacked(self, ds):
        """[k, b, ...] chunk payload -> the stacked device 5-tuple the
        fused dispatch drivers take (same dtype contract as
        core.stack_on_device: narrow ints ride as-is and cast on
        device; already-placed device arrays pass through)."""
        dtype = _dtype_of(self.conf)

        def prep(a):
            if a is None:
                return None
            a = a if isinstance(a, jax.Array) else jnp.asarray(a)
            return _cast_stacked(a, dtype)

        return (
            prep(ds.features), prep(ds.labels),
            prep(getattr(ds, "labels_mask", None)),
            prep(getattr(ds, "features_mask", None)), ds.k,
        )

    def _run_prestacked_chunk(self, ds) -> None:
        """One fused dispatch from a ChunkedDataSet's [k, b, ...]
        arrays."""
        k = ds.k
        if k == 1:
            from deeplearning4j_tpu.datasets.api import DataSet

            def first(a):
                return None if a is None else a[0]

            self.fit_minibatch(DataSet(
                features=first(ds.features), labels=first(ds.labels),
                features_mask=first(ds.features_mask),
                labels_mask=first(ds.labels_mask),
            ))
            return
        if self._wants_last_features():
            self._last_features = ds.features[-1]
        core.run_scan_chunk(self, self._prep_prestacked(ds))

    # ------------------------------------------------------------------
    # public API (reference fit/output/score)
    # ------------------------------------------------------------------

    def resume(self, source, load_updater: bool = True) -> int:
        """Resume training from a checkpoint: restore params, updater
        state, layer state, and the iteration/epoch counters into THIS
        model (config must match — use ``restore_model`` for a fresh
        instance). ``source`` is a resilience.CheckpointManager (newest
        restorable version, with corrupted-newest fallback) or a
        checkpoint zip path. Returns the restored step.

        Continuation is exact: per-step dropout keys fold
        ``iteration_count`` into the seed-derived base key, and lr
        schedules / updater ``t`` derive from the same counter — so
        k steps + crash + resume for N−k steps retraces the N-step
        trajectory bit-for-bit given the same data order
        (``tests/test_resilience.py``)."""
        from deeplearning4j_tpu.resilience.checkpoint import restore_into

        _, step = restore_into(self, source, load_updater=load_updater)
        return step

    def fit(self, data, labels=None, *, epochs: int = 1,
            resume_from=None, grad_accum=None,
            megastep=None) -> None:
        """fit(DataSetIterator) / fit(x, y) (reference ``fit:1048``).

        ``data`` may be a DataSetIterator-style iterable of objects with
        ``.features``/``.labels`` (and optional ``.labels_mask``), a
        single such object, or a raw (x, y) pair.

        ``resume_from``: a resilience.CheckpointManager or checkpoint
        zip path — restores params/updater/step counter before fitting
        (see ``resume``); the caller supplies the data stream from the
        restored position.

        ``grad_accum=K``: each optimizer step accumulates K microbatch
        gradients in-jit (``core.accum_grad_step``) before ONE updater
        apply — the effective batch is K× the delivered batch at one
        microbatch's activation memory. Batches must split into K equal
        microbatches; BatchNormalization configs are rejected (per-
        microbatch batch stats would change the math). The knob
        persists until changed (``grad_accum=1`` restores plain steps).

        ``megastep=K``: fold K consecutive optimizer steps (plus
        on-device metric accumulation) into ONE XLA dispatch
        (``core.build_megastep``), read back once per chunk — the
        trajectory stays bitwise equal to the per-step loop. Persists
        until changed (``megastep=1`` restores per-step dispatch);
        ineligible configs (TBPTT, recurrent, rollback guard) fall
        back to the per-step path.
        """
        from deeplearning4j_tpu.datasets.api import DataSet

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
        if resume_from is not None:
            self.resume(resume_from)
        if labels is not None:
            batches: Any = [DataSet(features=data, labels=labels)]
            core.fit_batches(self, batches, epochs)
            return
        if hasattr(data, "features"):
            core.fit_batches(self, [data], epochs)
            return
        core.fit_batches(self, data, epochs)

    def _fit_epochs_device_cached(self, iterator, epochs: int) -> bool:
        return core.fit_epochs_device_cached(
            self, iterator, epochs,
            lambda ds: (
                ds.features, ds.labels,
                getattr(ds, "labels_mask", None),
                getattr(ds, "features_mask", None),
            ),
            extra_plan_fn=self._tbptt_cached_plan,
        )

    def _tbptt_cached_plan(self, iterator, epochs: int):
        """HBM-resident multi-epoch plan for fused-TBPTT configs: each
        minibatch's chunk stack transfers once and replays every epoch
        through the single-dispatch TBPTT scan. Returns None (caller
        tries the standard plan / streams) when the config or data is
        ineligible."""
        if (
            epochs <= 1
            or not isinstance(iterator, (list, tuple))
            or len(iterator) == 0
            or not all(hasattr(ds, "features") for ds in iterator)
            or self.conf.backprop_type != "TruncatedBPTT"
            or self.conf.iterations != 1
            or self.conf.optimization_algo
            != "STOCHASTIC_GRADIENT_DESCENT"
            or not all(
                getattr(l, "supports_batched_iterations", False)
                for l in self.listeners
            )
        ):
            return None
        fwd = self.conf.tbptt_fwd_length
        total = 0
        for ds in iterator:
            x = np.asarray(ds.features)
            y = np.asarray(ds.labels)
            if x.ndim != 3 or x.shape[2] <= fwd or not (
                self._can_fuse_tbptt(x, y, fwd)
            ):
                return None
            for a in (
                ds.features, ds.labels,
                getattr(ds, "labels_mask", None),
                getattr(ds, "features_mask", None),
            ):
                if a is not None:
                    total += _nbytes(a)
        if total > self.device_cache_bytes:
            return None
        dtype = _dtype_of(self.conf)
        stacks = []
        for ds in iterator:
            x = _to_device(ds.features, dtype)
            y = _to_device(ds.labels, dtype)
            mask = getattr(ds, "labels_mask", None)
            fmask = getattr(ds, "features_mask", None)
            mask = None if mask is None else jnp.asarray(mask, dtype)
            fmask = None if fmask is None else jnp.asarray(fmask, dtype)
            stacks.append((self._stack_tbptt(x, y, mask, fmask), ds))
        # fuse consecutive same-shape minibatches into ONE dispatch:
        # reset flags zero the recurrent carry at each batch boundary,
        # so the whole epoch can be a single scan. Reuses the shared
        # grouping policy over (stack, ds) items.
        def merge(items):
            parts = [st for st, _ in items]
            return tuple(
                jnp.concatenate([p[i] for p in parts])
                if parts[0][i] is not None else None
                for i in range(5)
            ) + (sum(p[5] for p in parts), parts[0][6])

        grouped = _build_scan_plan(
            stacks,
            sig_fn=lambda item: tuple(
                None if a is None else a.shape for a in item[0][:4]
            ),
            stack_fn=merge,
            scan_chunk=self.scan_chunk,
        )
        return [
            ("tbptt", item[0], item[1]) if kind == "single"
            else ("tbptt", item, last[1])
            for kind, item, last in grouped
        ]

    def _step_extra_args(self) -> tuple:
        """Trailing jitted-step arguments for the active transforms
        (the dynamic loss-scale state, then the statistical guard's
        EWMA state, when engaged)."""
        extra = ()
        if self._loss_scale_active:
            extra += (core.ensure_loss_scale_state(self),)
        if core.stat_guard_active(self):
            extra += (core.ensure_stat_guard_state(self),)
        return extra

    def fit_minibatch(self, ds) -> float:
        """One minibatch through ``conf.iterations`` optimizer steps
        (reference Solver/StochasticGradientDescent.optimize; LBFGS/
        ConjugateGradient/LineGradientDescent route through
        ``optimize.solvers.Solver``)."""
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
                return self._solver.optimize(
                    ds.features, ds.labels,
                    mask=getattr(ds, "labels_mask", None),
                    fmask=getattr(ds, "features_mask", None),
                )
            raise ValueError(
                "Unknown optimization_algo "
                f"'{self.conf.optimization_algo}'"
            )
        if self._jit_step is None:
            self._jit_step = self._build_step()
        dtype = _dtype_of(self.conf)
        x = _to_device(ds.features, dtype)
        y = _to_device(ds.labels, dtype)
        mask = getattr(ds, "labels_mask", None)
        fmask = getattr(ds, "features_mask", None)
        if (
            self.conf.backprop_type == "TruncatedBPTT"
            and x.ndim == 3
            and x.shape[2] > self.conf.tbptt_fwd_length
        ):
            return self._fit_tbptt(x, y, mask, fmask)
        if mask is not None:
            mask = jnp.asarray(mask, dtype)
        if fmask is not None:
            fmask = jnp.asarray(fmask, dtype)
        if self._wants_last_features():
            self._last_features = ds.features  # activation listeners
        self._last_batch_rows = int(x.shape[0])  # examples/sec signal
        core.check_grad_accum_batch(self.grad_accum, int(x.shape[0]))
        prof = profiler.get_active_profiler()
        if prof is not None:
            prof.begin_step(self.iteration_count + 1,
                            parent=self._fit_span)
        score = None
        for _ in range(self.conf.iterations):
            if self._jit_step is None:
                # a listener may flip telemetry/guard mid-fit (the
                # setters clear the step); rebuild before dispatch
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
                    x, y, mask, fmask,
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
                # async path (core.fit_batches): bounded in-flight,
                # guard flag collected guard_lag steps late — the
                # in-jit select already suppressed a bad update, so
                # the trajectory is unchanged (parallel/dispatch.py)
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
            # Reset per optimizer iteration: each pass over the same
            # minibatch starts from zero recurrent carry (also keeps
            # the step's state pytree structure stable -> no recompile)
            self._reset_recurrent_state()
        if prof is not None:
            prof.end_step(model=self, ds=ds, score=self._last_score,
                          grad_norm=getattr(self, "_last_grad_norm",
                                            None),
                          rows=self._last_batch_rows)
        return score  # 0-d device array; float() to sync

    def _wants_last_features(self) -> bool:
        """Snapshot the batch only when a listener needs it — holding a
        reference unconditionally would pin the user's feature array in
        memory for the model's lifetime."""
        return any(
            getattr(l, "needs_last_features", False)
            for l in self.listeners
        )

    def _reset_recurrent_state(self) -> None:
        """Standard-backprop mode: recurrent carry does not persist
        across minibatches (reference resets per fit call)."""
        for name, layer in zip(self.layer_names, self.conf.layers):
            if layer.is_recurrent():
                self.state[name] = {}

    def _fit_tbptt(self, x, y, mask, fmask=None) -> float:
        """Truncated BPTT: slice the time axis into fwdLen chunks and
        carry RNN state between chunks (reference
        ``doTruncatedBPTT:1210``, state carry ``:1259-1276``). The
        carry rides the layer-state pytree through the jitted step."""
        fwd = self.conf.tbptt_fwd_length
        if self._can_fuse_tbptt(x, y, fwd):
            return self._fit_tbptt_fused(x, y, mask, fmask)
        t_total = x.shape[2]
        self._reset_recurrent_state()
        score = 0.0
        for start in range(0, t_total, fwd):
            end = min(start + fwd, t_total)
            xs = x[:, :, start:end]
            ys = y[:, :, start:end] if y.ndim == 3 else y
            ms = mask[:, start:end] if mask is not None else None
            fs = fmask[:, start:end] if fmask is not None else None
            score = self._fit_chunk_with_carry(xs, ys, ms, fs)
        self._reset_recurrent_state()
        return score

    def _fit_chunk_with_carry(self, xs, ys, ms, fs=None) -> float:
        dtype = _dtype_of(self.conf)
        xs = jnp.asarray(xs, dtype)
        ys = jnp.asarray(ys, dtype)
        if ms is not None:
            ms = jnp.asarray(ms, dtype)
        if fs is not None:
            fs = jnp.asarray(fs, dtype)
        if self._jit_step is None:
            self._jit_step = self._build_step()
        self._last_batch_rows = int(xs.shape[0])  # examples/sec signal
        lrs = self.updater_def.scheduled_lrs(self.iteration_count)
        t = jnp.asarray(self.iteration_count + 1, jnp.float32)
        rng = jax.random.fold_in(self._base_key, self.iteration_count)
        out = self._jit_step(
            self.params, self.updater_state, self.state, xs, ys, ms, fs,
            {k: jnp.asarray(v, jnp.float32) for k, v in lrs.items()},
            t, rng, *self._step_extra_args(),
        )
        guard = self.divergence_guard
        score, ok = core.apply_step_out(self, out)
        self.iteration_count += 1
        self._last_score = score  # device array; sync deferred
        if guard is not None:
            if bool(ok):
                guard.good_step()
            else:
                guard.bad_step(self)
        for listener in self.listeners:
            listener.iteration_done(self, self.iteration_count)
        return score  # 0-d device array; float() to sync

    # -- layer-wise pretraining (reference pretrain(iter) -> :166) ------

    def _input_to_layer_pure(self, params, state, x, idx):
        """Input tensor as seen by layer ``idx`` — forward through
        layers [0, idx) including idx's own preprocessor."""
        ctx = ShapeContext(
            batch=x.shape[0], time=x.shape[2] if x.ndim == 3 else -1
        )
        for i in range(idx):
            if i in self.conf.preprocessors:
                x = self.conf.preprocessors[i].preprocess(x, ctx)
            x, _ = self.conf.layers[i].apply(
                params[self.layer_names[i]], x,
                state.get(self.layer_names[i], {}), train=False, rng=None,
            )
        if idx in self.conf.preprocessors:
            x = self.conf.preprocessors[idx].preprocess(x, ctx)
        return x

    def pretrain(self, data, epochs: int = 1) -> None:
        """Greedy layer-wise unsupervised pretraining: fit each
        pretrainable layer (VAE/RBM/AutoEncoder) on the activations of
        the stack below it (reference ``pretrain(DataSetIterator)`` →
        per-layer fit at ``MultiLayerNetwork.java:166``)."""
        from deeplearning4j_tpu.datasets.api import ChunkedDataSet, DataSet

        if self.params is None:
            self.init()
        if isinstance(data, ChunkedDataSet):
            data = data.to_datasets()
        elif hasattr(data, "features"):
            data = [data]
        elif (
            isinstance(data, tuple) and len(data) == 2
            and not hasattr(data[0], "features")
        ):
            data = [DataSet(features=data[0], labels=data[1])]
        elif not isinstance(data, (list, tuple)) and not hasattr(
            data, "reset"
        ):
            # one-shot generator: materialize so every layer/epoch sees
            # the full stream (multiple passes are required)
            data = list(data)
        dtype = _dtype_of(self.conf)
        if self._jit_pretrain_input is None:
            self._jit_pretrain_input = jax.jit(
                self._input_to_layer_pure, static_argnames=("idx",)
            )
        jit_input = self._jit_pretrain_input
        for idx, (name, layer) in enumerate(
            zip(self.layer_names, self.conf.layers)
        ):
            if not layer.is_pretrainable():
                continue
            upd_def = MultiLayerUpdaterDef({name: layer.updater_settings()})
            upd_state = upd_def.init({name: self.params[name]})
            if idx not in self._jit_pretrain_steps:
                self._jit_pretrain_steps[idx] = core.build_pretrain_step(
                    layer, name, upd_def
                )
            step = self._jit_pretrain_steps[idx]
            it = 0
            for _ in range(epochs):
                for ds in _iter_unchunked(data):
                    x = jnp.asarray(
                        ds.features if hasattr(ds, "features") else ds, dtype
                    )
                    xin = jit_input(self.params, self.state, x, idx=idx)
                    for _ in range(self.conf.iterations):
                        lrs = {
                            k: jnp.asarray(v, jnp.float32)
                            for k, v in upd_def.scheduled_lrs(it).items()
                        }
                        t = jnp.asarray(it + 1, jnp.float32)
                        rng = jax.random.fold_in(
                            jax.random.fold_in(self._base_key, 7919 + idx), it
                        )
                        # reassign atomically: argnum 0 is donated
                        (
                            self.params[name], upd_state, loss,
                        ) = step(
                            self.params[name], upd_state, xin, lrs, t, rng
                        )
                        self._last_score = loss
                        it += 1
                if hasattr(data, "reset"):
                    data.reset()
        self._pretrain_done = True

    # -- inference -----------------------------------------------------

    def _output_fn(self) -> Callable:
        """The pure inference forward closure — the single source of
        truth behind both the jitted ``output`` path and the AOT
        export (identical trace -> identical executable -> bitwise
        identical results between the two)."""
        def out_fn(params, state, x, fmask, rng, train):
            out, _, _, _ = self._forward_pure(
                params, state, x, train=train, rng=rng, fmask=fmask
            )
            return out
        return out_fn

    def output(self, x, train: bool = False, features_mask=None):
        """Activated network output (reference ``output:1638``;
        ``train=True`` applies training-mode ops like dropout, and
        ``features_mask`` is the RNN input mask, reference
        ``output(INDArray,...,featuresMask,labelsMask)``)."""
        if self.params is None:
            self.init()
        dtype = _dtype_of(self.conf)
        if self._aot_outputs and not train and features_mask is None:
            # AOT-restored executable for this exact shape: same
            # program output() would have jitted, deserialized from
            # disk instead of compiled (compile/aot.py)
            fn = self._aot_outputs.get(
                tuple(int(d) for d in np.shape(x))
            )
            if fn is not None:
                return fn(self.params, self.state,
                          jnp.asarray(x, dtype))
        if self._jit_output is None:
            self._jit_output = jax.jit(
                self._output_fn(), static_argnames=("train",)
            )
        fm = (
            None if features_mask is None
            else jnp.asarray(features_mask, dtype)
        )
        rng = (
            jax.random.fold_in(self._base_key, self.iteration_count)
            if train else None
        )
        return self._jit_output(
            self.params, self.state, jnp.asarray(x, dtype), fm, rng,
            train,
        )

    # -- AOT export/install (compile/aot.py) ---------------------------

    def _output_kind(self) -> str:
        """AOT kind for the inference forward: scan-over-layers and
        Pallas kernel dispatch change the compiled program
        (remat/loss-scale do not touch inference), so both are part of
        the artifact identity."""
        return ("output" + ("+scan" if self.scan_layers else "")
                + core.kernel_kind_suffix(self))

    def aot_fingerprint(self, shape, kind: Optional[str] = None) -> str:
        """Validity fingerprint for this model's AOT artifacts at
        ``shape``: config JSON + shape + dtype + backend + jax
        versions (see ``compile.aot.artifact_fingerprint``)."""
        from deeplearning4j_tpu.compile.aot import artifact_fingerprint

        return artifact_fingerprint(
            self.conf.to_dict(), shape,
            str(jnp.dtype(_dtype_of(self.conf))),
            kind if kind is not None else self._output_kind(),
        )

    def aot_export_output(self, x_shape, registry=None) -> bytes:
        """Serialize the compiled inference forward for inputs of
        exactly ``x_shape`` (inference mode, no mask — the serving
        bucket contract) into an AOT artifact."""
        if self.params is None:
            self.init()
        from deeplearning4j_tpu.compile.aot import export_artifact

        dtype = _dtype_of(self.conf)
        base = self._output_fn()
        fn = jax.jit(lambda p, s, xin: base(p, s, xin, None, None,
                                            False))
        spec = jax.ShapeDtypeStruct(
            tuple(int(d) for d in x_shape), dtype
        )
        return export_artifact(
            fn, (self.params, self.state, spec),
            fingerprint=self.aot_fingerprint(x_shape),
            shape=x_shape, kind=self._output_kind(),
            name=f"output-{'x'.join(str(int(d)) for d in x_shape)}",
            registry=registry,
        )

    def aot_install_output(self, x_shape, artifact,
                           registry=None) -> bool:
        """Install an inference executable for exactly ``x_shape``
        from artifact bytes (fingerprint-checked; silently refused
        and counted in ``aot_fallback_total`` when stale/corrupt) or
        a pre-loaded callable. Returns True when installed."""
        key = tuple(int(d) for d in x_shape)
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

    def aot_output_shapes(self) -> List[Tuple[int, ...]]:
        """Input shapes with an installed AOT inference executable."""
        return list(self._aot_outputs)

    def aot_export_step(self, ds, registry=None) -> bytes:
        """Serialize the compiled SGD train step specialized to
        ``ds``'s feature/label shapes (no masks) — the executable a
        warm restart installs via ``aot_install_step`` to resume
        fitting without a compile. Exported fresh (never from the
        live ``_jit_step``) so guard/telemetry/transform flags at
        export time are captured in the fingerprint."""
        if self.params is None:
            self.init()
        from deeplearning4j_tpu.compile.aot import export_artifact

        # the EXACT arrays fit_minibatch would dispatch (same device
        # conversion -> same dtypes -> the executable matches)
        dtype = _dtype_of(self.conf)
        x = _to_device(ds.features, dtype)
        y = _to_device(ds.labels, dtype)
        lrs = {
            k: jnp.asarray(v, jnp.float32) for k, v in
            self.updater_def.scheduled_lrs(self.iteration_count).items()
        }
        t = jnp.asarray(1, jnp.float32)
        rng = jax.random.fold_in(self._base_key, 0)
        return export_artifact(
            self._build_step(),
            (self.params, self.updater_state, self.state, x, y,
             None, None, lrs, t, rng) + self._step_extra_args(),
            fingerprint=self.aot_fingerprint(
                x.shape, kind=self._step_kind()
            ),
            shape=x.shape, kind=self._step_kind(),
            name=f"step-{'x'.join(str(d) for d in x.shape)}",
            meta_extra={"label_shape": [int(d) for d in y.shape]},
            registry=registry,
        )

    def aot_install_step(self, artifact, registry=None) -> bool:
        """Install an AOT train-step executable as ``_jit_step``
        (dispatching to it on matching shapes, JIT otherwise — see
        ``compile.aot.AotStepFunction``). Fingerprint-checked;
        returns True when installed."""
        from deeplearning4j_tpu.compile.aot import (
            AotStepFunction,
            load_artifact,
            peek_meta,
        )

        try:
            meta = peek_meta(artifact)
            x_shape = tuple(meta["shape"])
        except Exception:
            return False
        fn = load_artifact(
            artifact,
            expected_fingerprint=self.aot_fingerprint(
                x_shape, kind=self._step_kind()
            ),
            registry=registry,
        )
        if fn is None:
            return False
        y_shape = tuple(
            meta.get("label_shape")
            or self._step_label_shape(x_shape)
        )
        self._jit_step = AotStepFunction(
            fn, x_shape, y_shape, self._build_step
        )
        return True

    def _step_kind(self) -> str:
        """AOT kind string for the train step: the guard/telemetry
        flags and the whole-net transforms change the compiled
        program (extra outputs / different HLO), so they are part of
        the artifact identity."""
        return (
            "step"
            + ("+guard" if self.divergence_guard is not None else "")
            + ("+telemetry" if self._telemetry_grad_norm else "")
            + core.transform_kind_suffix(self)
        )

    def _step_label_shape(self, x_shape) -> Tuple[int, ...]:
        """Label shape implied by the config for a feature batch of
        ``x_shape`` (n_out of the last layer; 3-d for recurrent)."""
        n_out = getattr(self.conf.layers[-1], "n_out", None)
        if len(x_shape) == 3:
            return (x_shape[0], int(n_out), x_shape[2])
        return (x_shape[0], int(n_out))

    def output_padded(self, x, n_valid, features_mask=None):
        """Inference on a row-padded batch: the serving micro-batcher
        coalesces requests, pads the stack to a shape bucket, and
        needs the first ``n_valid`` rows back bitwise identical to a
        solo ``output`` on those rows. This entry pins that contract:

        - it runs the SAME jitted forward as ``output`` (one compiled
          executable per bucket shape, shared with direct callers);
        - padding rows cannot perturb the valid rows because every
          inference-mode layer is row-independent — BatchNorm applies
          running stats, dropout is off, masks are per-row — which
          ``tests/test_batching.py`` enforces bitwise per bucket;
        - masks compose: a ``features_mask`` covering only the valid
          rows is extended with all-ones rows for the padding (an
          all-zero mask row would poison masked reductions with 0/0).
        """
        n = int(n_valid)
        b = int(np.shape(x)[0])
        if not 0 < n <= b:
            raise ValueError(
                f"n_valid must be in [1, {b}] for a {b}-row batch; "
                f"got {n}"
            )
        fm = features_mask
        if fm is not None:
            fm = np.asarray(fm)
            if fm.shape[0] == n and n < b:
                fm = np.concatenate(
                    [fm, np.ones((b - n,) + fm.shape[1:], fm.dtype)],
                    axis=0,
                )
            elif fm.shape[0] != b:
                raise ValueError(
                    f"features_mask covers {fm.shape[0]} rows; "
                    f"expected {n} (valid) or {b} (padded)"
                )
        return self.output(x, features_mask=fm)[:n]

    def feed_forward(self, x, train: bool = False) -> List[jax.Array]:
        """All per-layer activations (reference ``feedForward``)."""
        if self.params is None:
            self.init()
        rng = self._base_key if train else None
        _, _, _, acts = self._forward_pure(
            self.params, self.state, jnp.asarray(x), train=train, rng=rng,
            collect=True,
        )
        return acts

    def feed_forward_to_layer(self, layer_idx: int, x, train: bool = False):
        _, _, _, acts = self._forward_pure(
            self.params, self.state, jnp.asarray(x), train=train,
            rng=self._base_key if train else None, upto=layer_idx,
            collect=True,
        )
        return acts

    def score(self, ds=None, x=None, labels=None) -> float:
        """Loss on a dataset (reference ``score(DataSet)``)."""
        if ds is not None:
            x, labels = ds.features, ds.labels
            mask = getattr(ds, "labels_mask", None)
            fmask = getattr(ds, "features_mask", None)
        else:
            mask = fmask = None
        dtype = _dtype_of(self.conf)
        s, _ = self._score_pure(
            self.params, self.state, jnp.asarray(x, dtype),
            jnp.asarray(labels, dtype),
            jnp.asarray(mask, dtype) if mask is not None else None,
            None, train=False,
            fmask=jnp.asarray(fmask, dtype) if fmask is not None else None,
        )
        return float(s)

    # -- streaming RNN inference (reference rnnTimeStep:2290) -----------

    def rnn_time_step(self, x):
        """Feed one (or a few) timesteps, carrying streaming state
        across calls (reference ``rnnTimeStep``; state in
        ``stateMap``). Input [b, size] or [b, size, t]. Recurrent
        layers carry h/c; attention layers carry a fixed-size KV
        cache (incremental decoding — the transformer analog of the
        reference's char-RNN sampling loop)."""
        if self.params is None:
            self.init()
        for name, layer in zip(self.layer_names, self.conf.layers):
            if not layer.can_stream():
                raise ValueError(
                    f"Layer '{name}' ({type(layer).__name__}) cannot be "
                    "used with rnn_time_step — it needs the full sequence "
                    "(reference throws UnsupportedOperationException)"
                )
        dtype = _dtype_of(self.conf)
        x = jnp.asarray(x, dtype)
        squeeze = x.ndim == 2
        if squeeze:
            x = x[:, :, None]
        t_new = int(x.shape[2])
        named = list(zip(self.layer_names, self.conf.layers))
        _stream_guard_and_prime(
            named, self._rnn_state, self._stream_steps, t_new,
            int(x.shape[0]), dtype,
        )
        merged = dict(self.state)
        for name, carry in self._rnn_state.items():
            merged[name] = {**merged.get(name, {}), **carry}
        if self._jit_rnn_step is None:
            def rnn_step(params, state, x):
                out, _, new_state, _ = self._forward_pure(
                    params, state, x, train=False, rng=None
                )
                return out, new_state
            self._jit_rnn_step = jax.jit(rnn_step)
        out, new_state = self._jit_rnn_step(self.params, merged, x)
        _extract_stream_state(named, new_state, self._rnn_state)
        self._stream_steps += t_new
        return out[:, :, 0] if squeeze else out

    def rnn_clear_previous_state(self) -> None:
        """Reference ``rnnClearPreviousState``."""
        self._rnn_state = {}
        self._stream_steps = 0

    def predict(self, x) -> np.ndarray:
        """Argmax class predictions (reference ``predict``)."""
        return np.asarray(jnp.argmax(self.output(x), axis=1))

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
        out = self.output(
            ds.features,
            features_mask=getattr(ds, "features_mask", None),
        )
        labels = np.asarray(ds.labels)
        m = getattr(ds, "labels_mask", None)
        if m is None and labels.ndim == 3:
            # per-timestep eval falls back to the features mask;
            # 2-d (per-sequence) labels must NOT — a [b, t] mask
            # cannot index b rows
            m = getattr(ds, "features_mask", None)
        e.eval(labels, np.asarray(out),
               mask=np.asarray(m) if m is not None else None)

    # -- listeners ------------------------------------------------------

    def set_listeners(self, *listeners) -> None:
        self.listeners = list(listeners)

    def add_listener(self, listener) -> None:
        self.listeners.append(listener)

    # -- parameter plumbing (flat-view parity) --------------------------

    def num_params(self) -> int:
        return sum(
            int(np.prod(p.shape))
            for lp in self.params.values()
            for p in lp.values()
        )

    def _flat_order(self) -> List[Tuple[str, str]]:
        order = []
        for name, layer in zip(self.layer_names, self.conf.layers):
            pnames = list(self.params[name].keys())
            preferred = [p for p in ("W", "b") if p in pnames]
            rest = [p for p in pnames if p not in ("W", "b")]
            for pn in preferred + sorted(rest):
                order.append((name, pn))
        return order

    def params_flat(self) -> np.ndarray:
        """1-D concatenated view (reference flat params array)."""
        chunks = [
            np.asarray(self.params[ln][pn]).ravel()
            for ln, pn in self._flat_order()
        ]
        return np.concatenate(chunks) if chunks else np.zeros((0,))

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
        if off != vec.size:
            raise ValueError(
                f"Param vector length {vec.size} != model params {off}"
            )

    def copy(self) -> "MultiLayerNetwork":
        # Deep-copy device buffers: the jitted step donates
        # params/updater-state/state, so sharing arrays between two
        # networks would let one fit() invalidate the other's buffers
        # on TPU ("Array has been deleted").
        clone = lambda a: jnp.array(a, copy=True)
        m = MultiLayerNetwork(self.conf)
        m.init(params=jax.tree_util.tree_map(clone, self.params))
        m.updater_state = jax.tree_util.tree_map(clone, self.updater_state)
        m.state = jax.tree_util.tree_map(clone, self.state)
        return m

    def summary(self) -> str:
        lines = ["=" * 70]
        lines.append(f"{'idx/name':<16}{'type':<28}{'params':>10}")
        lines.append("-" * 70)
        total = 0
        for name, layer in zip(self.layer_names, self.conf.layers):
            n = sum(
                int(np.prod(p.shape)) for p in self.params[name].values()
            ) if self.params else 0
            total += n
            lines.append(f"{name:<16}{type(layer).__name__:<28}{n:>10}")
        lines.append("-" * 70)
        lines.append(f"Total params: {total}")
        lines.append("=" * 70)
        return "\n".join(lines)
