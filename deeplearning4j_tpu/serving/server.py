"""Production-hardened model serving tier.

The reference's serving story (``routes/DL4jServeRouteBuilder.java:1``
— a Camel route: load checkpoint -> transform -> predict) assumed the
route never saturates, never hangs, and never changes models. This
module grows that route into a serving tier built for the failure
modes production traffic actually has:

- **admission control**: predicts run on a bounded worker pool behind
  a bounded queue. When both are full the request is *shed* —
  ``503`` + ``Retry-After`` in microseconds — instead of piling
  threads until the process dies (load shedding beats load collapse);
- **per-request deadlines**: one ``Deadline`` budget spans queue wait
  + transform + predict; expiry returns ``504`` with elapsed/budget
  so clients can tell a slow model from a dead one;
- **circuit breaking**: a ``CircuitBreaker`` guards the predict path.
  A poisoned model (every predict raising) trips it after N
  consecutive failures and subsequent requests fail fast with ``503
  circuit_open`` until a half-open probe proves recovery;
- **hot reload**: ``POST /admin/reload`` (or a
  ``CheckpointManager``-watching mode) restores the new version on
  the admin thread — never a predict worker — validates it with a
  canary predict, then swaps it atomically; in-flight requests finish
  on the version they started with, and a failed reload keeps serving
  the old model;
- **readiness vs liveness**: ``/healthz`` answers "is the process
  up" (always ok while serving); ``/readyz`` answers "should a
  balancer route here" and flips during reload, breaker-open,
  queue-high-water, and drain;
- **graceful drain**: ``stop(drain_timeout=)`` stops admitting,
  finishes in-flight work, then closes;
- **micro-batching**: the workers are batch-drain loops. Queued
  requests coalesce — up to ``max_batch_size`` rows or
  ``batch_timeout_ms``, whichever first — into ONE padded forward on
  a bucketed shape (``batcher.py``), and each request's response is
  sliced back out and completed individually. Deadline-expired items
  are dropped (``504``) before stacking; a request wider than the
  largest bucket falls back to the solo path. Every ladder bucket is
  compiled eagerly at ``start()``/``reload()`` (``compile_cache.py``)
  so steady traffic never compiles on the request path, and a
  recompile guard logs + counts any shape that escapes the ladder;
- **observability**: ``/metrics`` serves shed/timeout/breaker/reload
  counters, latency + queue-delay quantiles, batch-occupancy
  histogram, and compile counters (``metrics.py``).

Error responses all use the shared JSON envelope (``envelope.py``):
``400`` malformed payload, ``411`` missing Content-Length, ``413``
over the body cap, ``422`` shape-invalid features (expected vs got),
``500`` model/transform fault with an opaque deterministic
``error_id`` (never a stack trace), ``503`` shed / circuit open /
draining, ``504`` deadline exceeded.
"""

from __future__ import annotations

import json
import logging
import math
import queue
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Callable, List, Optional

import numpy as np

from deeplearning4j_tpu.observability.export import (
    CONTENT_TYPE as PROMETHEUS_CONTENT_TYPE,
    parse_format_query,
    prometheus_text,
)
from deeplearning4j_tpu.observability import flightrec, profiler
from deeplearning4j_tpu.observability.trace import Tracer
from deeplearning4j_tpu.resilience.breaker import OPEN, CircuitBreaker
from deeplearning4j_tpu.resilience.deadline import Deadline
from deeplearning4j_tpu.serving.batcher import (
    BucketLadder,
    MicroBatcher,
    fill_chunks,
    pad_rows,
)
from deeplearning4j_tpu.serving.compile_cache import CompileCache
from deeplearning4j_tpu.serving.envelope import (
    HttpBodyError,
    deadline_envelope,
    error_envelope,
    error_id_for,
    read_request_body,
)
from deeplearning4j_tpu.serving.metrics import ServingMetrics
from deeplearning4j_tpu.serving.registry import (
    ModelEntry,
    ModelRegistry,
    ModelVersion,
)

logger = logging.getLogger(__name__)

MAX_BODY = 64 * 1024 * 1024

# adaptive Retry-After clamp: a shed client should pace by observed
# queue drain, never told "come back immediately" nor parked longer
# than any queue this tier is allowed to build
RETRY_AFTER_MIN = 0.05
RETRY_AFTER_MAX = 5.0


def _feature_dim(model) -> Optional[int]:
    """Input width from the model's config (first layer's n_in), when
    it declares one — drives 422 validation and the default canary."""
    try:
        n_in = getattr(model.conf.layers[0], "n_in", None)
    except (AttributeError, IndexError, TypeError):
        return None
    if isinstance(n_in, int) and n_in > 0:
        return n_in
    return None


# the immutable (model, version) snapshot moved to registry.py with
# the multi-tenant registry; the name stays importable from here
_ModelVersion = ModelVersion


class _NoReloadSource(ValueError):
    pass


class _ServingHTTPServer(ThreadingHTTPServer):
    """stdlib default listen backlog is 5: a burst of 30+ concurrent
    connects gets TCP resets before admission control ever sees the
    requests. Shedding is the server's job (503 + Retry-After), not
    the kernel's."""

    request_queue_size = 128


class _WorkItem:
    """One admitted predict: features + deadline in, response out.
    The handler thread owns the socket; the worker only fills
    ``response`` and sets ``done``. ``lock`` arbitrates the
    queue-expiry race (handler cancels vs worker starts)."""

    __slots__ = ("features", "deadline", "done", "response", "lock",
                 "started", "cancelled", "timed_out", "rows",
                 "squeeze", "enqueued_at", "span", "queue_span",
                 "assembly_span", "entry")

    def __init__(self, features, deadline: Deadline,
                 entry: Optional[ModelEntry] = None):
        self.entry = entry  # the tenant this predict belongs to
        # trace handoff: the handler thread sets ``span`` (the
        # request's root) and ``queue_span`` before enqueueing; the
        # drain thread ends the queue span and parents its batch/
        # predict spans on the root — one trace id across threads
        self.span = None
        self.queue_span = None
        self.assembly_span = None
        self.features = features
        self.deadline = deadline
        self.done = threading.Event()
        self.response = None  # (code, body_dict, headers_dict)
        self.lock = threading.Lock()
        self.started = False
        self.cancelled = False   # handler gave up before worker start
        self.timed_out = False   # handler wrote a 504 already
        shape = np.shape(features)
        self.rows = int(shape[0]) if len(shape) >= 2 else 1
        self.squeeze = len(shape) == 1  # 1-d request: 1-d response
        self.enqueued_at = time.monotonic()

    def finish(self, code: int, body: dict, headers=None) -> bool:
        """Record the worker's result; returns False when the handler
        already answered 504 (result abandoned)."""
        with self.lock:
            abandoned = self.timed_out
            self.response = (code, body, headers or {})
        self.done.set()
        return not abandoned


class ModelServer:
    """Serve a model over HTTP (grown from the
    ``DL4jServeRouteBuilder`` analog into a hardened tier — see
    module docstring).

    Endpoints::

        GET  /healthz       liveness: process up
        GET  /readyz        readiness: routable (flips under stress)
        GET  /metrics       counters + latency quantiles (JSON)
        GET  /models        per-tenant registry + paging states
        POST /predict       {"features": [[...]], "model": name?}
        POST /admin/reload  {} | {"path"|"key": ..., "model": name?}

    Multi-tenant mode: ``models={name: model | path | spec-dict}``
    serves N named models from this one process. Each tenant gets
    its own admission quota (``{"quota": k}`` — overload sheds 503
    ``tenant_quota`` against the tenant's own bound, never its
    neighbors'), deadline override, optional bucket ladder, and
    paging state; ``max_device_models`` / ``max_device_bytes``
    LRU-page cold tenants' weights to host memory (``registry.py``),
    faulted back in on demand at transfer cost — never a compile.

    ``model_or_path`` may be a model instance, a checkpoint zip path,
    or None with ``checkpoint_manager=`` (restores the latest
    version). ``deadline`` (seconds) bounds queue wait + transform +
    predict per request; None disables. ``store`` (an ObjectStore,
    typically ``RetryingObjectStore(breaker=...)``) enables reload by
    object key.

    Micro-batching (on by default): queued requests coalesce into one
    padded forward per shape bucket — up to ``max_batch_size`` rows
    or ``batch_timeout_ms`` per batch, buckets from ``bucket_ladder``
    (powers of two up to ``max_batch_size`` when None). The drain
    pool is ``batch_workers`` threads (default 1: one accelerator is
    one dispatch stream, and a single continuous-batching drain
    collects the widest batches — splitting arrivals over k drain
    threads just shrinks every batch k-fold); ``workers`` keeps its
    capacity meaning in the k+q admission bound. Pass
    ``micro_batch=False`` for the PR-2 one-predict-per-request solo
    loop.

    Compile once, run anywhere (``deeplearning4j_tpu/compile/``):
    ``compile_cache`` (default on) enables JAX's persistent
    compilation cache where ``compile.persistent.default_cache_dir``
    names a directory (``JAX_COMPILATION_CACHE_DIR``, else
    ``<repo>/.jax_cache`` on a TPU backend) so every warmup/restart
    compile after the first is a disk read; ``aot`` (default on) additionally installs
    AOT-exported executables bundled in the checkpoint manifest
    (``CheckpointManager.save(model, artifacts=...)``) so
    ``start()``/``reload()`` from such a checkpoint *deserialize*
    the bucket ladder instead of compiling it — with silent
    per-artifact fallback to JIT when an artifact is missing, stale,
    or corrupt.
    """

    def __init__(self, model_or_path=None, host: str = "127.0.0.1",
                 port: int = 0, transform=None,
                 output_classes: bool = False, *,
                 workers: int = 4, queue_depth: int = 32,
                 deadline: Optional[float] = None,
                 retry_after: float = 1.0,
                 breaker: Optional[CircuitBreaker] = None,
                 checkpoint_manager=None, store=None, canary=None,
                 queue_high_water: Optional[int] = None,
                 reservoir_size: int = 1024,
                 micro_batch: bool = True,
                 max_batch_size: int = 32,
                 batch_timeout_ms: float = 2.0,
                 bucket_ladder=None,
                 batch_workers: int = 1,
                 tracer: Optional[Tracer] = None,
                 compile_cache=True,
                 aot: bool = True,
                 models: Optional[dict] = None,
                 max_device_models: Optional[int] = None,
                 max_device_bytes: Optional[int] = None):
        if workers < 1:
            raise ValueError("workers must be >= 1")
        if queue_depth < 0:
            raise ValueError("queue_depth must be >= 0")
        self.transform = transform
        self.output_classes = output_classes
        self.workers = workers
        self.queue_depth = queue_depth
        self.deadline = deadline
        self.retry_after = retry_after
        self.breaker = breaker or CircuitBreaker(name="predict")
        self.checkpoint_manager = checkpoint_manager
        self.store = store
        self.canary = canary
        self.queue_high_water = (
            queue_high_water if queue_high_water is not None
            else max(queue_depth, 1)
        )
        if micro_batch:
            if batch_workers < 1:
                raise ValueError("batch_workers must be >= 1")
            ladder = (
                bucket_ladder
                if isinstance(bucket_ladder, BucketLadder)
                else BucketLadder(bucket_ladder, max_batch_size)
            )
            self.batcher = MicroBatcher(ladder, batch_timeout_ms)
            self.batch_workers = batch_workers
            occupancy = ladder.buckets
        else:
            self.batcher = None
            self.batch_workers = workers
            occupancy = None
        self.metrics = ServingMetrics(reservoir_size, occupancy)
        # hardware-truth accounting per serving bucket: cost models
        # built off the request path at warmup ((model, bucket) ->
        # CostModel or None), published as bucket-labeled gauges on
        # the per-server registry per dispatch
        self._bucket_costs: dict = {}
        self._peak_flops = profiler.peak_flops()[0]
        self._peak_bw = profiler.peak_bytes_per_sec()[0]
        try:
            reg = self.metrics.registry
            self._g_bucket_mfu = reg.gauge(
                "step_mfu", labels=("bucket",),
                help="per-bucket MFU of the last batched forward",
            )
            self._g_bucket_fps = reg.gauge(
                "step_flops_per_sec", labels=("bucket",),
                help="per-bucket achieved FLOP/s (XLA cost model / "
                     "forward wall)",
            )
            self._g_bucket_bps = reg.gauge(
                "step_bytes_per_sec", labels=("bucket",),
                help="per-bucket achieved memory bytes/s",
            )
            self._g_bucket_roofline = reg.gauge(
                "step_roofline_class", labels=("bucket",),
                help="per-bucket roofline class (0 unknown / 1 "
                     "compute / 2 memory bound)",
            )
        except Exception:  # registry already holds the unlabeled kind
            self._g_bucket_mfu = self._g_bucket_fps = None
            self._g_bucket_bps = self._g_bucket_roofline = None
        # disabled by default: every span operation is a no-op costing
        # one branch; pass a Tracer(sink=JsonlSink(...)) to record
        self.tracer = tracer if tracer is not None else Tracer(
            enabled=False
        )
        self.compile_cache = CompileCache(self.metrics, self.tracer)
        # tier-1 persistent XLA cache: on by default (dir resolved
        # by compile.persistent.default_cache_dir) so restarts hit
        # disk instead of the compiler; pass compile_cache=False to
        # opt out, or a directory string to name one where
        # JAX_COMPILATION_CACHE_DIR does not. Never raises — a cache
        # problem costs compiles.
        self.compile_cache_dir: Optional[str] = None
        if compile_cache is not False:
            from deeplearning4j_tpu.compile.persistent import (
                enable_persistent_cache,
            )

            self.compile_cache_dir = enable_persistent_cache(
                compile_cache if isinstance(compile_cache, str)
                else None,
                registry=self.metrics.registry,
            )
        # tier-2 AOT: when the model comes from a CheckpointManager
        # whose manifest bundles exported executables, install them
        # so warmup deserializes instead of compiling
        self.aot = aot
        self._aot_buckets = 0

        self._source_path: Optional[str] = None
        self._watched_step: Optional[int] = None
        self._last_restore_info = None  # CheckpointInfo when manager-sourced
        # continuous-learning hook (loop/shadow.py): when set, every
        # successful default-tenant forward is offered to the scorer
        # AFTER the client responses complete — candidate results are
        # never returned to clients, and observe() never raises
        self.shadow = None
        # multi-tenant registry: the single-model constructor path
        # becomes the "default" tenant; ``models=`` adds named
        # tenants (instance | checkpoint path | spec dict with
        # quota/deadline/pinned/max_batch_size overrides). The
        # paging budget (max_device_models / max_device_bytes)
        # LRU-evicts cold tenants' weights to host memory.
        self.model_registry = ModelRegistry(
            max_device_models=max_device_models,
            max_device_bytes=max_device_bytes,
            metrics_registry=self.metrics.registry,
        )
        if (model_or_path is not None
                or self.checkpoint_manager is not None
                or not models):
            model, source = self._initial_model(model_or_path)
            self.model_registry.add(
                "default",
                _ModelVersion(model, 1, source,
                              self.compile_cache.register()),
                source_path=self._source_path, default=True,
            )
        for name, spec in (models or {}).items():
            self._add_model(name, spec)

        self._model_lock = threading.Lock()
        self._reload_lock = threading.Lock()
        self._reloading = False
        self._draining = False
        self._stop_workers = False
        self._queue: "queue.Queue[_WorkItem]" = queue.Queue(
            maxsize=queue_depth + workers
        )
        self._worker_threads: List[threading.Thread] = []
        self._watch_thread: Optional[threading.Thread] = None
        self._watch_stop = threading.Event()

        self._httpd = _ServingHTTPServer(
            (host, port), _make_handler(self)
        )
        self.port = self._httpd.server_address[1]
        self._thread: Optional[threading.Thread] = None

    # back-compat: the pre-hardening server exposed ``.model``, and
    # the single-tenant tier exposed ``._active`` — both now resolve
    # through the default tenant's entry
    @property
    def _active(self) -> ModelVersion:
        return self.model_registry.entry().current

    @property
    def model(self):
        return self._active.model

    @property
    def model_version(self) -> int:
        return self._active.version

    def _add_model(self, name: str, spec) -> ModelEntry:
        """Register one named tenant. ``spec`` is a model instance, a
        checkpoint zip path, or a dict: ``{"model": ... | "path":
        ..., "quota": int, "deadline": s, "pinned": bool,
        "max_batch_size": int | "ladder": [...]}`` — quota/deadline
        default to the server-wide knobs, the ladder to the shared
        one."""
        opts = {}
        source_path = None
        if isinstance(spec, dict):
            opts = spec
            spec = opts.get("model", opts.get("path"))
            if spec is None:
                raise ValueError(
                    f"model {name!r}: spec dict needs a 'model' "
                    "instance or a 'path'"
                )
        if isinstance(spec, str):
            from deeplearning4j_tpu.util.model_serializer import (
                restore_model,
            )

            source_path = spec
            model = restore_model(spec, load_updater=False)
        else:
            model = spec
        ladder = None
        if opts.get("ladder") is not None:
            ladder = BucketLadder(opts["ladder"])
        elif opts.get("max_batch_size") is not None:
            ladder = BucketLadder(None, opts["max_batch_size"])
        return self.model_registry.add(
            name,
            _ModelVersion(model, 1, source_path or type(model).__name__,
                          self.compile_cache.register()),
            quota=opts.get("quota"),
            deadline=opts.get("deadline"),
            pinned=bool(opts.get("pinned", False)),
            ladder=ladder,
            source_path=source_path,
        )

    def set_shadow(self, scorer) -> None:
        """(Un)install a shadow scorer (``loop.ShadowScorer`` or any
        object with ``observe(features, live_output, live_ms)``).
        Atomic attribute swap; in-flight forwards finish against
        whichever scorer they snapshotted."""
        self.shadow = scorer

    def _offer_shadow(self, entry: ModelEntry, feats, out,
                      live_ms: float) -> None:
        """Mirror one successful live forward to the shadow scorer —
        after the live responses completed, default tenant only,
        faults logged and swallowed (the live path is done; nothing
        here may affect it)."""
        sh = self.shadow
        if sh is None or entry.name != self.model_registry.default_name:
            return
        try:
            sh.observe(feats, out, live_ms)
        except Exception:
            logger.exception("shadow observe failed (ignored)")

    def _ladder_for(self, entry: ModelEntry) -> Optional[BucketLadder]:
        if self.batcher is None:
            return None
        return entry.ladder or self.batcher.ladder

    def _initial_model(self, model_or_path):
        if isinstance(model_or_path, str):
            from deeplearning4j_tpu.util.model_serializer import (
                restore_model,
            )

            self._source_path = model_or_path
            return restore_model(model_or_path), model_or_path
        if model_or_path is not None:
            return model_or_path, type(model_or_path).__name__
        if self.checkpoint_manager is not None:
            model, info = self.checkpoint_manager.restore_latest(
                load_updater=False
            )
            self._watched_step = info.step
            self._last_restore_info = info
            return model, f"checkpoint-step-{info.step}"
        raise ValueError(
            "provide a model, a checkpoint path, or checkpoint_manager="
        )

    # -- lifecycle ------------------------------------------------------

    def start(self) -> "ModelServer":
        # AOT first: executables bundled with the checkpoint install
        # before warmup, so warmup deserializes instead of compiling
        # (missing/stale/corrupt artifacts silently leave those
        # buckets on the JIT path)
        self._aot_buckets = self._install_aot(
            self._active.model, self._active.shapes,
            self._last_restore_info,
        )
        # eager warmup BEFORE the pool takes traffic: every tenant's
        # ladder buckets compile now, so the first requests never pay
        # an XLA compile inside their deadline budget. Best-effort
        # here — a faulty model/transform must keep surfacing as
        # per-request 500 envelopes, not kill start() (at reload()
        # the same failure DOES fail the reload and keeps the old
        # version)
        for name in self.model_registry.names():
            entry = self.model_registry.entry(name)
            try:
                self._warm_model(entry.current.model,
                                 entry.current.shapes,
                                 self._ladder_for(entry),
                                 name=entry.name)
            except Exception:
                logger.exception(
                    "bucket warmup failed for model %r; serving "
                    "unwarmed (requests will surface the fault "
                    "per-request)", name,
                )
        # warmup ran every tenant through the device on purpose (the
        # executables must exist); now page the over-budget tail out
        self.model_registry.enforce_budget()
        for i in range(self.batch_workers):
            t = threading.Thread(
                target=self._worker_loop, daemon=True,
                name=f"dl4j-serve-worker-{i}",
            )
            t.start()
            self._worker_threads.append(t)
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, daemon=True,
            name="dl4j-tpu-serve",
        )
        self._thread.start()
        return self

    def stop(self, drain_timeout: float = 5.0) -> bool:
        """Graceful drain: stop admitting (new work is shed with
        ``503 draining``), wait up to ``drain_timeout`` seconds for
        in-flight requests to finish, then close the listener and the
        pool. Returns True when the drain fully emptied."""
        self._draining = True
        deadline = time.monotonic() + max(drain_timeout, 0.0)
        drained = False
        while time.monotonic() < deadline:
            if self.metrics.inflight == 0 and self._queue.empty():
                drained = True
                break
            time.sleep(0.01)
        self.stop_watch()
        self._stop_workers = True
        for t in self._worker_threads:
            t.join(timeout=2)
        if self._thread is not None:  # shutdown() hangs if never served
            self._httpd.shutdown()
            self._thread.join(timeout=5)
        self._httpd.server_close()
        return drained or (
            self.metrics.inflight == 0 and self._queue.empty()
        )

    def install_preemption_drain(self, handler=None,
                                 drain_timeout: float = 5.0
                                 ) -> "ModelServer":
        """Translate a preemption notice (SIGTERM/SIGINT or a
        simulated one) into the graceful drain above: new work sheds
        with ``503 draining``, in-flight requests finish, then the
        listener closes. Uses the active ``resilience.preemption.
        PreemptionHandler``, installing a default one if none
        exists — so a bare serving process gets signal handling by
        calling this once after ``start()``."""
        from deeplearning4j_tpu.resilience import preemption

        h = handler if handler is not None else preemption.active_handler()
        if h is None:
            h = preemption.PreemptionHandler().install()
        h.on_preemption(
            lambda reason: self.stop(drain_timeout=drain_timeout)
        )
        return self

    # -- worker pool ----------------------------------------------------

    def _worker_loop(self) -> None:
        carry: Optional[_WorkItem] = None
        while not self._stop_workers:
            if carry is not None:
                item, carry = carry, None
            else:
                try:
                    item = self._queue.get(timeout=0.05)
                except queue.Empty:
                    continue
            try:
                if self.batcher is None:
                    self._process(item)
                else:
                    items, carry = self.batcher.collect(
                        self._queue, item,
                        lambda: self.metrics.inflight,
                    )
                    self._process_batch(items)
            except Exception:  # never kill a pool thread
                logger.exception("serve worker crashed on a request")
                item.finish(500, error_envelope(
                    "internal", 500, "internal server error",
                ))

    def _process(self, item: _WorkItem) -> None:
        with item.lock:
            if item.cancelled:
                return
            item.started = True
        if item.queue_span is not None:
            item.queue_span.end()  # idempotent; batch path ends first
        entry = item.entry or self.model_registry.entry()
        if item.deadline.expired():
            # expired while queued: report without touching the model
            self.metrics.incr("deadline_timeout_total")
            self.metrics.incr_model("model_deadline_timeout_total",
                                    entry.name)
            item.finish(504, deadline_envelope(
                item.deadline, "deadline expired while queued",
            ))
            return
        if not self.breaker.try_acquire():
            self.metrics.incr("breaker_rejected_total")
            item.finish(503, error_envelope(
                "circuit_open", 503,
                "model circuit is open; failing fast",
                retry_after=round(self.breaker.retry_after(), 3),
            ), {"Retry-After": self._retry_after_header()})
            return
        # the forward bracket: bump the tenant's LRU clock, fault its
        # weights in when paged out, and hold the executing mark so
        # the evictor cannot page it out mid-forward
        pagein_ms = self.model_registry.touch(entry)
        mv = entry.current  # snapshot: reloads swap for later requests
        pspan = self.tracer.start_span(
            "serving.predict", parent=item.span,
            attrs={"mode": "solo", "model": entry.name,
                   "model_version": mv.version},
        )
        if pagein_ms is not None:
            pspan.set_attr("weight_pagein_ms", round(pagein_ms, 3))
        try:
            feats = item.features
            if self.transform is not None:
                feats = self.transform(feats)
            self.compile_cache.note(mv.shapes, np.shape(feats),
                                    model=entry.name)
            fwd_t0 = time.perf_counter()
            out = mv.model.output(feats)
            out = np.asarray(
                out[0] if isinstance(out, (list, tuple)) else out
            )
            fwd_ms = (time.perf_counter() - fwd_t0) * 1000.0
        except Exception as e:
            self.breaker.record_failure()
            eid = error_id_for(e)
            logger.error("predict failed (error_id=%s)", eid,
                         exc_info=True)
            self.metrics.incr("server_error_total")
            pspan.set_attr("error_id", eid).end("error")
            item.finish(500, error_envelope(
                "model_error", 500,
                "prediction failed; see server log",
                error_id=eid,
            ))
            return
        finally:
            self.model_registry.release(entry)
        pspan.end()
        self.breaker.record_success()
        body = {"output": out.tolist(), "model_version": mv.version}
        if len(self.model_registry) > 1:
            body["model"] = entry.name
        if self.output_classes and out.ndim == 2:
            body["classes"] = out.argmax(axis=1).tolist()
        self.metrics.incr("predictions_total")
        self.metrics.incr_model("model_predictions_total", entry.name)
        if not item.finish(200, body):
            self.metrics.incr("abandoned_total")
        self._offer_shadow(entry, feats, out, fwd_ms)

    # -- micro-batch drain path -----------------------------------------

    def _process_batch(self, items: "List[_WorkItem]") -> None:
        """One coalesced batch: drop the dead, route the oversized to
        the solo path, transform per request, then pack what remains
        into bucket-padded chunks and run ONE forward per chunk."""
        now = time.monotonic()
        ready: List[tuple] = []
        for item in items:
            entry = item.entry or self.model_registry.entry()
            with item.lock:
                if item.cancelled:
                    continue
                item.started = True
            if item.queue_span is not None:
                item.queue_span.end()
            item.assembly_span = self.tracer.start_span(
                "serving.batch_assembly", parent=item.span,
                attrs={"batch_items": len(items)},
            )
            self.metrics.record_queue_delay(now - item.enqueued_at)
            if item.deadline.expired():
                # dropped BEFORE stacking: never pads a dead request
                # into a live batch
                self.metrics.incr("deadline_timeout_total")
                self.metrics.incr_model("model_deadline_timeout_total",
                                        entry.name)
                self.metrics.incr("batch_expired_total")
                item.assembly_span.end("timeout")
                item.finish(504, deadline_envelope(
                    item.deadline,
                    "deadline expired while coalescing",
                ))
                continue
            if item.rows > self._ladder_for(entry).max:
                # wider than the largest bucket: solo path, own compile
                self.metrics.incr("solo_fallback_total")
                item.assembly_span.set_attr(
                    "outcome", "solo_fallback"
                ).end()
                self._process(item)
                continue
            try:
                feats = item.features
                if self.transform is not None:
                    feats = self.transform(feats)
                feats = np.asarray(feats)
                if feats.ndim == 1:
                    feats = feats[None, :]
            except Exception as e:
                # a bad transform poisons only ITS request (solo
                # semantics), never its batchmates
                self.breaker.record_failure()
                eid = error_id_for(e)
                logger.error("transform failed (error_id=%s)", eid,
                             exc_info=True)
                self.metrics.incr("server_error_total")
                item.assembly_span.set_attr("error_id", eid).end(
                    "error"
                )
                item.finish(500, error_envelope(
                    "model_error", 500,
                    "prediction failed; see server log",
                    error_id=eid,
                ))
                continue
            ready.append((item, feats))
        if not ready:
            return
        # group by tenant + trailing shape + dtype: only same-model,
        # same-width requests can share a stacked forward (width
        # varies only when the model declares no n_in for
        # parse_features to enforce)
        groups: dict = {}
        for item, feats in ready:
            entry = item.entry or self.model_registry.entry()
            key = (entry.name, feats.shape[1:], feats.dtype.str)
            groups.setdefault(key, (entry, []))[1].append((item, feats))
        for entry, pairs in groups.values():
            ladder = self._ladder_for(entry)
            for chunk in fill_chunks(pairs, ladder.max):
                self._predict_chunk(entry, ladder, chunk)

    def _predict_chunk(self, entry: ModelEntry, ladder: BucketLadder,
                       chunk) -> None:
        """ONE padded forward for a chunk of (item, features) pairs
        of one tenant, sliced back out and completed per request."""
        for item, _ in chunk:
            if item.assembly_span is not None:
                item.assembly_span.end()
        if not self.breaker.try_acquire():
            self.metrics.incr("breaker_rejected_total", len(chunk))
            body = error_envelope(
                "circuit_open", 503,
                "model circuit is open; failing fast",
                retry_after=round(self.breaker.retry_after(), 3),
            )
            headers = {"Retry-After": self._retry_after_header()}
            for item, _ in chunk:
                item.finish(503, body, headers)
            return
        n_valid = sum(int(f.shape[0]) for _, f in chunk)
        bucket = ladder.bucket_for(n_valid)
        pagein_ms = self.model_registry.touch(entry)
        mv = entry.current  # snapshot: reloads swap for later requests
        pspans = [
            self.tracer.start_span(
                "serving.predict", parent=item.span,
                attrs={"mode": "batched", "bucket": bucket,
                       "n_valid": n_valid, "chunk_size": len(chunk),
                       "model": entry.name,
                       "model_version": mv.version},
            )
            for item, _ in chunk
        ]
        if pagein_ms is not None and pspans:
            pspans[0].set_attr("weight_pagein_ms",
                               round(pagein_ms, 3))
        try:
            stacked = (
                chunk[0][1] if len(chunk) == 1
                else np.concatenate([f for _, f in chunk], axis=0)
            )
            padded = pad_rows(stacked, bucket)
            self.compile_cache.note(mv.shapes, padded.shape,
                                    model=entry.name)
            fwd_t0 = time.perf_counter()
            out = self._padded_forward(mv.model, padded, n_valid)
            fwd_ms = (time.perf_counter() - fwd_t0) * 1000.0
        except Exception as e:
            self.breaker.record_failure()
            eid = error_id_for(e)
            logger.error("batched predict failed (error_id=%s)", eid,
                         exc_info=True)
            self.metrics.incr("server_error_total", len(chunk))
            body = error_envelope(
                "model_error", 500,
                "prediction failed; see server log",
                error_id=eid,
            )
            for sp in pspans:
                sp.set_attr("error_id", eid).end("error")
            for item, _ in chunk:
                item.finish(500, body)
            return
        finally:
            self.model_registry.release(entry)
        for sp in pspans:
            sp.end()
        self.breaker.record_success()
        self._publish_bucket_cost(entry.name, bucket, fwd_ms)
        self.metrics.record_batch(n_valid, bucket, entry.name)
        self.metrics.incr("batched_predictions_total", len(chunk))
        self.metrics.incr("predictions_total", len(chunk))
        self.metrics.incr_model("model_predictions_total", entry.name,
                                len(chunk))
        off = 0
        abandoned = 0
        multi = len(self.model_registry) > 1
        for item, feats in chunk:
            rows = int(feats.shape[0])
            o = out[off:off + rows]
            off += rows
            if item.squeeze:
                o = o[0]
            body = {"output": o.tolist(), "model_version": mv.version}
            if multi:
                body["model"] = entry.name
            if self.output_classes and o.ndim == 2:
                body["classes"] = o.argmax(axis=1).tolist()
            if not item.finish(200, body):
                abandoned += 1
        if abandoned:
            self.metrics.incr("abandoned_total", abandoned)
        self._offer_shadow(entry, stacked, out[:n_valid], fwd_ms)

    def _publish_bucket_cost(self, name: str, bucket: int,
                             fwd_ms: float) -> None:
        """Publish bucket-labeled MFU/throughput gauges from the
        warmup-built cost model; a dict lookup + a division on the
        dispatch path, nothing when no cost model exists."""
        cm = self._bucket_costs.get((name, bucket))
        if cm is None or self._g_bucket_mfu is None:
            return
        try:
            got = cm.achieved(fwd_ms / 1e3, self._peak_flops)
            label = str(bucket)
            self._g_bucket_fps.labels(label).set(
                got["flops_per_sec"]
            )
            self._g_bucket_bps.labels(label).set(
                got["bytes_per_sec"]
            )
            if got["mfu"] is not None:
                self._g_bucket_mfu.labels(label).set(got["mfu"])
            self._g_bucket_roofline.labels(label).set(
                cm.roofline_class(self._peak_flops, self._peak_bw)
            )
        except Exception:  # accounting must never fail a predict
            logger.debug("bucket cost publish failed", exc_info=True)

    def _padded_forward(self, model, padded, n_valid: int):
        """Run the model on a bucket-padded batch and return the valid
        rows. Engines expose ``output_padded`` (same jitted program as
        ``output``, masks composed over padding rows); plain models
        fall back to ``output`` + slice — valid because inference
        forwards are row-independent (the contract
        ``tests/test_batching.py`` enforces bitwise)."""
        fn = getattr(model, "output_padded", None)
        if fn is not None:
            out = fn(padded, n_valid=n_valid)
            out = out[0] if isinstance(out, (list, tuple)) else out
            return np.asarray(out)
        out = model.output(padded)
        out = out[0] if isinstance(out, (list, tuple)) else out
        return np.asarray(out)[:n_valid]

    def _warm_model(self, model, shapes, ladder=None,
                    name=None) -> int:
        """Eagerly run every ladder bucket through the padded forward
        so all steady-state executables exist BEFORE the model takes
        traffic. Returns the number of warmup forwards (0 when
        batching is off or the input width is unknowable)."""
        if self.batcher is None:
            return 0
        if ladder is None:
            ladder = self.batcher.ladder
        feats = self._canary_features(model)
        if feats is None:
            logger.info(
                "bucket warmup skipped: model declares no input width "
                "and no canary= was provided"
            )
            return 0
        if self.transform is not None:
            feats = self.transform(feats)
        feats = np.asarray(feats, np.float32)
        if feats.ndim == 1:
            feats = feats[None, :]
        n = 0
        for b in ladder.buckets:
            padded = pad_rows(feats[:b], b)
            self.compile_cache.note(shapes, padded.shape)
            self._padded_forward(model, padded, padded.shape[0])
            self.metrics.incr("warmup_predicts_total")
            # hardware-truth bucket accounting: the cost model is
            # built HERE, off the request path; per-dispatch MFU is
            # then a dict lookup + division
            try:
                self._bucket_costs[(name, b)] = (
                    profiler.output_cost_model(
                        model, padded.shape, str(padded.dtype)
                    )
                )
            except Exception:
                self._bucket_costs[(name, b)] = None
            n += 1
        shapes.mark_warmed()
        return n

    def _install_aot(self, model, shapes, info) -> int:
        """Install AOT-exported forward executables bundled with a
        checkpoint (manifest ``artifacts`` map) onto ``model`` and
        pre-mark their shapes compiled in the recompile-guard record.
        Returns the number installed; 0 — silently — when AOT is off,
        the model has no bundle, or every artifact is stale/corrupt
        (those buckets JIT at warmup exactly as without a bundle)."""
        if (not self.aot or info is None
                or self.checkpoint_manager is None
                or getattr(model, "aot_install_output", None) is None):
            return 0
        try:
            blobs = self.checkpoint_manager.load_artifacts(info)
            if not blobs:
                return 0
            from deeplearning4j_tpu.compile.aot import (
                install_serving_bundle,
            )

            installed = install_serving_bundle(
                model, blobs, registry=self.metrics.registry
            )
        except Exception:
            logger.exception(
                "AOT artifact install failed; serving will JIT-"
                "compile at warmup instead"
            )
            return 0
        if installed and shapes is not None:
            # first runs of these shapes are disk loads, not
            # compiles: keep xla_compiles_total flat for them. The
            # shape record tracks the (single) feature array's shape,
            # so unwrap the graph engine's nested 1-tuple keys.
            shapes.preload([
                k[0] if k and isinstance(k[0], tuple) else k
                for k in installed
            ])
        if installed:
            logger.info(
                "installed %d AOT executable(s) from checkpoint "
                "step %s", len(installed), info.step,
            )
        return len(installed)

    def _canary_features(self, model):
        if self.canary is not None:
            return np.asarray(self.canary, np.float32)
        n_in = _feature_dim(model)
        if n_in is None:
            return None
        return np.zeros((1, n_in), np.float32)

    def retry_after_value(self) -> float:
        """Adaptive Retry-After: how long until a retry would find a
        slot, estimated as queue depth over the observed drain rate
        (recent completions per second), clamped to
        [``RETRY_AFTER_MIN``, min(``RETRY_AFTER_MAX``, knob)]. Before
        any completion exists (cold start, wedged pool) the knob is
        the answer — it remains the upper bound, never the constant.
        """
        cap = min(RETRY_AFTER_MAX, self.retry_after)
        cap = max(cap, RETRY_AFTER_MIN)
        rate = self.metrics.drain_rate()
        if rate is None or rate <= 0:
            return cap
        est = self._queue.qsize() / rate
        return min(cap, max(RETRY_AFTER_MIN, est))

    def _retry_after_header(self) -> str:
        # HTTP Retry-After is integer seconds: round the adaptive
        # value up so the header never understates the JSON body's
        # precise ``retry_after`` float
        return str(max(1, int(math.ceil(self.retry_after_value()))))

    # -- admission (called from handler threads) ------------------------

    def submit(self, features,
               model: Optional[str] = None) -> "tuple[int, dict, dict]":
        """Admit one predict through the bounded pool and wait for its
        result under the request deadline. ``model`` routes to a
        named tenant (None = the default). Returns
        ``(status, body, headers)``. One root span brackets the whole
        request; the admission decision, queue wait, batch assembly,
        and predict are children sharing its trace id."""
        try:
            entry = self.model_registry.entry(model)
        except KeyError:
            self.metrics.incr("client_error_total")
            return 404, error_envelope(
                "model_not_found", 404,
                f"no model named {model!r}",
                models=self.model_registry.names(),
            ), {}
        started = time.monotonic()
        shape = np.shape(features)
        root = self.tracer.start_span("serving.request", attrs={
            "rows": int(shape[0]) if len(shape) >= 2 else 1,
            "model": entry.name,
        })
        adm = self.tracer.start_span("serving.admission",
                                     parent=root)
        self.metrics.incr_model("model_requests_total", entry.name)
        if self._draining:
            self.metrics.incr("shed_total")
            self.metrics.incr_model("model_shed_total", entry.name)
            adm.set_attr("outcome", "draining").end("shed")
            root.set_attr("status_code", 503).end("shed")
            return 503, error_envelope(
                "draining", 503, "server is draining; not admitting",
                retry_after=round(self.retry_after_value(), 3),
            ), {"Retry-After": self._retry_after_header()}
        if self.breaker.state == OPEN:
            # fail fast at admission: no queue slot for a doomed call
            self.metrics.incr("breaker_rejected_total")
            adm.set_attr("outcome", "circuit_open").end("shed")
            root.set_attr("status_code", 503).end("shed")
            return 503, error_envelope(
                "circuit_open", 503,
                "model circuit is open; failing fast",
                retry_after=round(self.breaker.retry_after(), 3),
            ), {"Retry-After": self._retry_after_header()}
        # per-tenant quota FIRST: one tenant at 10x its quota sheds
        # against its own bound and never consumes global slots its
        # neighbors are entitled to
        if not entry.admit():
            self.metrics.incr("shed_total")
            self.metrics.incr("quota_rejected_total")
            self.metrics.incr_model("model_shed_total", entry.name)
            adm.set_attr("outcome", "tenant_quota").end("shed")
            root.set_attr("status_code", 503).end("shed")
            return 503, error_envelope(
                "tenant_quota", 503,
                "model admission quota exceeded",
                model=entry.name, quota=entry.quota,
                retry_after=round(self.retry_after_value(), 3),
            ), {"Retry-After": self._retry_after_header()}
        # global admission bound: at most workers + queue_depth
        # requests in the system (executing + queued); excess sheds NOW
        if not self.metrics.try_enter(self.workers + self.queue_depth):
            entry.exit_admission()
            self.metrics.incr("shed_total")
            self.metrics.incr_model("model_shed_total", entry.name)
            adm.set_attr("outcome", "shed").end("shed")
            root.set_attr("status_code", 503).end("shed")
            return 503, error_envelope(
                "shed", 503,
                "worker pool and queue are full",
                retry_after=round(self.retry_after_value(), 3),
            ), {"Retry-After": self._retry_after_header()}
        adm.set_attr("outcome", "admitted").end()
        deadline = (entry.deadline if entry.deadline is not None
                    else self.deadline)
        item = _WorkItem(features, Deadline.after(deadline), entry)
        item.span = root
        item.queue_span = self.tracer.start_span("serving.queue",
                                                 parent=root)
        try:
            try:
                self._queue.put_nowait(item)
            except queue.Full:  # unreachable: sized to the bound
                self.metrics.incr("shed_total")
                self.metrics.incr_model("model_shed_total", entry.name)
                item.queue_span.end("shed")
                root.set_attr("status_code", 503).end("shed")
                return 503, error_envelope(
                    "shed", 503,
                    "worker pool and queue are full",
                    retry_after=round(self.retry_after_value(), 3),
                ), {"Retry-After": self._retry_after_header()}
            remaining = item.deadline.remaining()
            finished = item.done.wait(
                None if remaining is None else max(remaining, 0.0)
            )
            if not finished:
                with item.lock:
                    item.timed_out = True
                    if not item.started:
                        item.cancelled = True
                        item.queue_span.end("timeout")
                self.metrics.incr("deadline_timeout_total")
                self.metrics.incr_model("model_deadline_timeout_total",
                                        entry.name)
                root.set_attr("status_code", 504).end("timeout")
                return 504, deadline_envelope(item.deadline), {}
            code = item.response[0]
            root.set_attr("status_code", code).end(
                "ok" if code < 400 else "error"
            )
            return item.response
        finally:
            entry.exit_admission()
            self.metrics.exit()
            now = time.monotonic()
            self.metrics.note_completion(now)
            self.metrics.record_model_latency(entry.name,
                                              now - started)

    # -- hot reload -----------------------------------------------------

    def reload(self, spec: Optional[dict] = None) -> "tuple[int, dict]":
        """Restore a new model version (off the worker pool), canary-
        validate it, and swap atomically. ``spec`` may name a tenant
        (``{"model": name}``, default tenant otherwise) or pin a
        checkpoint version (``{"step": N}``, manager-backed default
        tenant); a failure at any stage keeps that tenant's current
        version serving — and never touches the others.

        Reloading the checkpoint step that is ALREADY serving is a
        counted no-op (``reload_skipped_total``, ``200 skipped``)
        instead of a full canary + warmup cycle — a polling promoter
        must not churn the server. ``{"force": true}`` overrides.
        Returns ``(status, body)``."""
        spec = dict(spec or {})
        name = spec.pop("model", None)
        force = bool(spec.pop("force", False))
        try:
            entry = self.model_registry.entry(name)
        except KeyError:
            return 404, error_envelope(
                "model_not_found", 404, f"no model named {name!r}",
                models=self.model_registry.names(),
            )
        if not self._reload_lock.acquire(blocking=False):
            return 409, error_envelope(
                "reload_in_progress", 409,
                "another reload is already running",
            )
        try:
            # idempotence: resolve the target checkpoint step WITHOUT
            # restoring anything; already serving it -> counted no-op
            # (never re-runs canary/warmup, never bumps the version)
            if not force:
                target = self._reload_target_step(spec, entry)
                if (target is not None
                        and target == self._watched_step):
                    self.metrics.incr("reload_skipped_total")
                    body = {"status": "skipped",
                            "step": int(target),
                            "version": entry.current.version,
                            "reason": "already serving this "
                                      "checkpoint step"}
                    if name is not None:
                        body["name"] = entry.name
                    return 200, body
            self._reloading = True  # /readyz flips for the duration
            try:
                model, source, info = self._load_for_reload(spec, entry)
                shapes = self.compile_cache.register()
                # AOT before canary/warmup: when the checkpoint
                # bundles exported executables, both the canary and
                # the bucket warmup run the deserialized programs —
                # a reload from a warm bundle performs zero compiles
                n_aot = self._install_aot(model, shapes, info)
                self._canary_check(model, self._ladder_for(entry))
                # warm every bucket on the ADMIN thread before the
                # swap: the new version has compiled all its shapes
                # before it sees its first request
                self._warm_model(model, shapes,
                                 self._ladder_for(entry),
                                 name=entry.name)
            except _NoReloadSource as e:
                return 400, error_envelope("no_reload_source", 400,
                                           str(e))
            except Exception as e:
                eid = error_id_for(e)
                logger.error("reload failed (error_id=%s)", eid,
                             exc_info=True)
                self.metrics.incr("reload_failure_total")
                return 503, error_envelope(
                    "reload_failed", 503,
                    "model reload failed; previous version still "
                    "serving", error_id=eid,
                )
            with self._model_lock:
                version = entry.current.version + 1
                self.model_registry.swap(
                    entry,
                    _ModelVersion(model, version, source, shapes),
                )
            self._aot_buckets = n_aot
            if info is not None:  # manager-sourced: step now serving
                self._watched_step = info.step
                self._last_restore_info = info
            self.metrics.incr("reload_total")
            body = {"status": "reloaded", "version": version,
                    "model": type(model).__name__,
                    "source": source}
            if name is not None:
                body["name"] = entry.name
            if n_aot:  # legacy response shape unless AOT landed
                body["aot_buckets"] = n_aot
            return 200, body
        finally:
            self._reloading = False
            self._reload_lock.release()

    def _reload_target_step(self, spec: dict,
                            entry: ModelEntry) -> Optional[int]:
        """The checkpoint step ``spec`` would load, resolvable without
        restoring — None when the source is not step-addressable
        (path/key/instance reloads never skip)."""
        if "path" in spec or "key" in spec:
            return None
        if (entry.name != self.model_registry.default_name
                or self.checkpoint_manager is None):
            return None
        if "step" in spec:
            try:
                return int(spec["step"])
            except (TypeError, ValueError):
                return None
        return self.checkpoint_manager.latest_step()

    def _load_for_reload(self, spec: dict, entry: ModelEntry):
        """(model, source, checkpoint_info_or_None) — the info rides
        along so reload can install the checkpoint's AOT bundle. The
        checkpoint manager and constructor path only back the DEFAULT
        tenant; named tenants reload from an explicit spec or the
        path they were registered from."""
        from deeplearning4j_tpu.util.model_serializer import (
            restore_model,
            restore_model_from_bytes,
        )

        if "step" in spec:
            # a specific published version (the promoter's path: the
            # candidate under promotion may no longer be the newest)
            if self.checkpoint_manager is None:
                raise _NoReloadSource(
                    "reload by step requires the server's "
                    "checkpoint_manager="
                )
            step = int(spec["step"])
            info = next(
                (i for i in self.checkpoint_manager.available()
                 if i.step == step), None,
            )
            if info is None:
                raise _NoReloadSource(
                    f"no checkpoint at step {step} in the store"
                )
            model = self.checkpoint_manager.restore(
                info, load_updater=False
            )
            return model, f"checkpoint-step-{step}", info
        if "path" in spec:
            return (
                restore_model(spec["path"], load_updater=False),
                str(spec["path"]), None,
            )
        if "key" in spec:
            if self.store is None:
                raise _NoReloadSource(
                    "reload by key requires the server's store="
                )
            data = self.store.read(spec["key"])
            return (
                restore_model_from_bytes(data, load_updater=False),
                str(spec["key"]), None,
            )
        is_default = entry.name == self.model_registry.default_name
        if is_default and self.checkpoint_manager is not None:
            model, info = self.checkpoint_manager.restore_latest(
                load_updater=False
            )
            return model, f"checkpoint-step-{info.step}", info
        source_path = entry.source_path or (
            self._source_path if is_default else None
        )
        if source_path is not None:
            return (
                restore_model(source_path, load_updater=False),
                source_path, None,
            )
        raise _NoReloadSource(
            "no reload source: pass {\"path\": ...} / {\"key\": ...} "
            "or construct the server with checkpoint_manager="
        )

    def _canary_check(self, model, ladder=None) -> None:
        """One predict on the candidate BEFORE it takes traffic — a
        restorable-but-broken checkpoint must fail the reload, not the
        next thousand user requests. With micro-batching on, the
        canary runs through the SAME bucketed padded path traffic
        uses (padded to the smallest bucket of the TENANT's ladder
        that fits), so a canary pass proves the shapes production
        requests will execute, not just a bespoke 1-row program."""
        feats = self._canary_features(model)
        if feats is None:
            return  # shape unknown and no canary provided: skip
        if self.transform is not None:
            feats = self.transform(feats)
        feats = np.asarray(feats, np.float32)
        if self.batcher is not None:
            if ladder is None:
                ladder = self.batcher.ladder
            if feats.ndim == 1:
                feats = feats[None, :]
            rows = int(feats.shape[0])
            bucket = ladder.bucket_for(rows)
            if bucket is not None:
                out = self._padded_forward(
                    model, pad_rows(feats, bucket), rows
                )
            else:
                out = self._padded_forward(model, feats, rows)
        else:
            out = model.output(feats)
            out = np.asarray(out[0] if isinstance(out, (list, tuple))
                             else out)
        if not np.all(np.isfinite(out)):
            raise ValueError("canary predict produced non-finite output")

    # -- checkpoint watching --------------------------------------------

    def check_for_update(self) -> bool:
        """One poll of the checkpoint manager: reload iff a newer step
        than the last loaded one exists. Returns True on a swap."""
        if self.checkpoint_manager is None:
            return False
        step = self.checkpoint_manager.last_step()
        if step is None or step == self._watched_step:
            return False
        code, _ = self.reload({})
        if code == 200:
            self._watched_step = step
            return True
        return False

    def watch(self, interval: float = 1.0) -> "ModelServer":
        """Poll the checkpoint manager every ``interval`` seconds on a
        daemon thread and hot-swap when a new version lands."""
        if self.checkpoint_manager is None:
            raise ValueError("watch() requires checkpoint_manager=")
        if self._watch_thread is not None:
            return self
        self._watch_stop.clear()

        def _loop():
            while not self._watch_stop.wait(interval):
                try:
                    self.check_for_update()
                except Exception:
                    logger.exception("checkpoint watch poll failed")

        self._watch_thread = threading.Thread(
            target=_loop, daemon=True, name="dl4j-serve-watch",
        )
        self._watch_thread.start()
        return self

    def stop_watch(self) -> None:
        self._watch_stop.set()
        if self._watch_thread is not None:
            self._watch_thread.join(timeout=2)
            self._watch_thread = None

    # -- health / metrics -----------------------------------------------

    def health(self) -> dict:
        out = {
            "status": "ok",
            "model": type(self._active.model).__name__,
            "version": self._active.version,
        }
        if len(self.model_registry) > 1:
            out["models"] = self.model_registry.names()
        return out

    def models_snapshot(self) -> dict:
        """``GET /models``: per-tenant registry + paging states, with
        each tenant's counter/latency view merged in."""
        stats = self.model_registry.stats()
        per_model = self.metrics.model_snapshot()
        for name, block in stats["models"].items():
            if name in per_model:
                block["metrics"] = per_model[name]
        stats["default"] = self.model_registry.default_name
        return stats

    def readiness(self) -> "tuple[int, dict]":
        reasons = []
        if self._draining:
            reasons.append("draining")
        if self._reloading:
            reasons.append("reloading")
        if self.breaker.state == OPEN:
            reasons.append("breaker_open")
        if self._queue.qsize() >= self.queue_high_water:
            reasons.append("queue_high_water")
        if reasons:
            return 503, {"status": "unready", "reasons": reasons}
        return 200, {"status": "ready",
                     "version": self._active.version}

    def prometheus_metrics(self) -> str:
        """Registry contents in Prometheus text exposition format
        (``GET /metrics?format=prometheus``). Scrape-time gauges
        mirror the snapshot-only fields so the exposition is
        self-contained."""
        reg = self.metrics.registry
        reg.gauge("queue_depth",
                  help="requests waiting in the bounded queue").set(
            self._queue.qsize()
        )
        reg.gauge("model_version",
                  help="active model version (bumps on reload)").set(
            self._active.version
        )
        reg.gauge("breaker_state",
                  help="predict breaker: 0 closed, 1 open, "
                       "2 half-open").set(
            {"closed": 0, "open": 1, "half_open": 2}[self.breaker.state]
        )
        return prometheus_text(reg)

    def metrics_snapshot(self) -> dict:
        out = self.metrics.snapshot()
        out["queue_depth"] = self._queue.qsize()
        out["queue_capacity"] = self.queue_depth
        out["workers"] = self.workers
        out["breaker"] = self.breaker.snapshot()
        out["model_version"] = self._active.version
        out["draining"] = self._draining
        out["retry_after"] = round(self.retry_after_value(), 3)
        out["paging"] = self.model_registry.stats()
        if self.batcher is not None:
            out["batching"] = {
                "enabled": True,
                "max_batch_size": self.batcher.ladder.max,
                "batch_timeout_ms": self.batcher.batch_timeout_ms,
                "buckets": list(self.batcher.ladder.buckets),
                "batch_workers": self.batch_workers,
                "warmed": bool(self._active.shapes.warmed),
            }
        else:
            out["batching"] = {"enabled": False}
        from deeplearning4j_tpu.compile.persistent import cache_stats

        out["compile"] = {
            "persistent_cache_dir": self.compile_cache_dir,
            "aot_enabled": self.aot,
            "aot_buckets_installed": self._aot_buckets,
            **cache_stats(),
        }
        return out

    def debug_snapshot(self) -> dict:
        """``GET /debugz``: one read-only, bounded JSON page with
        everything a first responder wants before attaching a
        debugger — versions, config, per-model state, the
        hardware-truth cost models, and the flight-recorder tail
        (capped at ``flightrec.DEBUG_TAIL_LIMIT`` records)."""
        import jax
        import jaxlib

        from deeplearning4j_tpu import __version__ as pkg_version

        out: dict = {
            "versions": {
                "deeplearning4j_tpu": pkg_version,
                "jax": jax.__version__,
                "jaxlib": jaxlib.__version__,
            },
            "backend": jax.default_backend(),
            "config": {
                "host": self._httpd.server_address[0],
                "port": self.port,
                "workers": self.workers,
                "queue_depth": self.queue_depth,
                "aot_enabled": self.aot,
                "compile_cache_dir": self.compile_cache_dir,
                "batching": self.batcher is not None,
            },
            "models": self.models_snapshot(),
            "metrics": self.metrics_snapshot(),
            "roofline": {
                "peak_flops": self._peak_flops,
                "peak_bytes_per_sec": self._peak_bw,
                "bucket_cost_models": {
                    f"{name}:{bucket}": {
                        "key": cm.key,
                        "flops": cm.flops,
                        "bytes_accessed": cm.bytes_accessed,
                        "arithmetic_intensity": round(
                            cm.arithmetic_intensity, 3),
                    }
                    for (name, bucket), cm
                    in sorted(self._bucket_costs.items())
                    if cm is not None
                },
            },
        }
        prof = profiler.get_active_profiler()
        if prof is not None:
            out["profiler"] = prof.snapshot()
        rec = flightrec.get_flight_recorder()
        if rec is not None:
            out["flight_recorder"] = {
                "capacity": rec.capacity,
                "last_step": rec.last_step(),
                "tail": flightrec._jsonable(
                    rec.tail(flightrec.DEBUG_TAIL_LIMIT)
                ),
            }
        return out

    # -- request validation ---------------------------------------------

    def parse_predict(self, data: bytes):
        """Body bytes -> ``(model_name_or_None, float32 features)``,
        or raise ``HttpBodyError`` with the right envelope: 400 for
        malformed payloads, 404 for an unknown ``"model"``, 422 for
        well-formed-but-shape-invalid features (expected vs got in
        the body). Width validates against the TARGET tenant's
        model."""
        try:
            payload = json.loads(data)
        except (ValueError, UnicodeDecodeError) as e:
            raise HttpBodyError(400, error_envelope(
                "malformed_json", 400, f"body is not valid JSON: {e}",
            )) from None
        if not isinstance(payload, dict) or "features" not in payload:
            raise HttpBodyError(400, error_envelope(
                "bad_request", 400,
                'body must be a JSON object with a "features" key',
            ))
        name = payload.get("model")
        if name is not None and not isinstance(name, str):
            raise HttpBodyError(400, error_envelope(
                "bad_request", 400,
                '"model" must be a string when present',
            ))
        try:
            entry = self.model_registry.entry(name)
        except KeyError:
            raise HttpBodyError(404, error_envelope(
                "model_not_found", 404, f"no model named {name!r}",
                models=self.model_registry.names(),
            )) from None
        try:
            feats = np.asarray(payload["features"], np.float32)
        except (ValueError, TypeError):
            raise HttpBodyError(422, error_envelope(
                "invalid_features", 422,
                "features are not a numeric array",
                expected="numeric array [n, d]",
                got=type(payload["features"]).__name__,
            )) from None
        if feats.ndim not in (1, 2) or feats.size == 0:
            raise HttpBodyError(422, error_envelope(
                "invalid_features", 422,
                "features must be a non-empty 1-d or 2-d array",
                expected="[n, d]", got=list(feats.shape),
            ))
        n_in = _feature_dim(entry.current.model)
        if n_in is not None and feats.shape[-1] != n_in:
            raise HttpBodyError(422, error_envelope(
                "invalid_features", 422,
                "feature width does not match the model input",
                expected=[int(feats.shape[0]) if feats.ndim == 2
                          else 1, n_in],
                got=list(feats.shape),
            ))
        return name, feats

    def parse_features(self, data: bytes):
        """Back-compat wrapper: features only, default tenant."""
        return self.parse_predict(data)[1]


def _make_handler(server: ModelServer):
    class Handler(BaseHTTPRequestHandler):
        def log_message(self, *a):
            pass

        def _json(self, obj, code: int = 200, headers=None):
            body = json.dumps(obj).encode()
            try:
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                for k, v in (headers or {}).items():
                    self.send_header(k, v)
                self.end_headers()
                self.wfile.write(body)
            except OSError:
                pass  # client went away; nothing to tell it

        def _text(self, body: str, content_type: str,
                  code: int = 200):
            data = body.encode()
            try:
                self.send_response(code)
                self.send_header("Content-Type", content_type)
                self.send_header("Content-Length", str(len(data)))
                self.end_headers()
                self.wfile.write(data)
            except OSError:
                pass

        def do_GET(self):
            server.metrics.incr("requests_total")
            route, fmt = parse_format_query(self.path)
            if route == "/healthz":
                self._json(server.health())
                return
            if route == "/readyz":
                code, body = server.readiness()
                self._json(body, code)
                return
            if route == "/metrics":
                if fmt == "prometheus":
                    self._text(server.prometheus_metrics(),
                               PROMETHEUS_CONTENT_TYPE)
                else:  # JSON stays the default
                    self._json(server.metrics_snapshot())
                return
            if route == "/models":
                self._json(server.models_snapshot())
                return
            if route == "/debugz":
                try:
                    self._json(server.debug_snapshot())
                except Exception as e:
                    eid = error_id_for(e)
                    logger.error(
                        "debugz failed (error_id=%s)", eid,
                        exc_info=True,
                    )
                    self._json(error_envelope(
                        "debug_error", 500,
                        "debug snapshot failed; see server log",
                        error_id=eid,
                    ), 500)
                return
            self._json(error_envelope("not_found", 404, "not found"),
                       404)

        def do_POST(self):
            server.metrics.incr("requests_total")
            if self.path == "/predict":
                started = time.monotonic()
                try:
                    data = read_request_body(self, MAX_BODY)
                    name, feats = server.parse_predict(data)
                except HttpBodyError as e:
                    server.metrics.incr("client_error_total")
                    self._json(e.envelope, e.code)
                    return
                code, body, headers = server.submit(feats, model=name)
                server.metrics.record_latency(
                    time.monotonic() - started
                )
                self._json(body, code, headers)
                return
            if self.path == "/admin/reload":
                try:
                    data = read_request_body(self, MAX_BODY)
                except HttpBodyError as e:
                    server.metrics.incr("client_error_total")
                    self._json(e.envelope, e.code)
                    return
                try:
                    spec = json.loads(data) if data.strip() else {}
                    if not isinstance(spec, dict):
                        raise ValueError("spec must be a JSON object")
                except ValueError as e:
                    server.metrics.incr("client_error_total")
                    self._json(error_envelope(
                        "malformed_json", 400,
                        f"reload spec is not valid JSON: {e}",
                    ), 400)
                    return
                code, body = server.reload(spec)
                self._json(body, code)
                return
            self._json(error_envelope("not_found", 404, "not found"),
                       404)

    return Handler
