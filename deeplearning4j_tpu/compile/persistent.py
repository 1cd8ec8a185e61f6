"""Tier 1: JAX's persistent (on-disk) XLA compilation cache, wired.

XLA compilation is deterministic: the same HLO + compile options on
the same backend produce the same executable, so a compile paid once
per *machine* (not once per process) is pure waste to ever pay again.
JAX ships the mechanism (``jax_compilation_cache_dir``); this module
supplies the operational wrapper the rest of the runtime uses:

- **placed from outside**: where JAX's own
  ``JAX_COMPILATION_CACHE_DIR`` is set, that directory is the cache
  and no other is ever set in code; where it is not, the cache is one
  fixed path inside the checkout, ``<repo>/.jax_cache`` (the path is
  part of a cache entry's key, so a directory that moves never hits).
  ``enable_persistent_cache()`` resolves env > arg > default, creates
  the directory and sets the thresholds — including
  ``jax_persistent_cache_min_compile_time_secs=0`` so *every*
  program is cached, not just slow ones (the default 1 s floor would
  leave the long tail of small programs recompiling forever). On a
  TPU backend the default is on; on the CPU it stays opt-in (see
  ``default_cache_dir``) — chosen from the observed platform;
- **size bounding**: ``bound_cache_size`` prunes the oldest entries
  down to ``DL4J_TPU_COMPILE_CACHE_MAX_BYTES`` (default 2 GiB) at
  enable time, so an unattended host never grows the cache without
  bound. jax's own bound (``JAX_COMPILATION_CACHE_MAX_SIZE``, least
  recently used first, at every write) is the environment's to set
  and is never touched here: where it is smaller than a program's
  executables, jax evicts one to write the next and every process
  start compiles again (PERF.md section 7, item 12);
- **accounting**: JAX's monitoring events are folded into process
  stats (``cache_stats()``) and into ``compile_cache_hits_total`` /
  ``compile_cache_misses_total`` / ``xla_backend_compiles_total`` /
  ``xla_backend_compile_seconds_total`` counters on every registry
  handed to ``install_cache_accounting`` — the serving tier passes
  its per-server registry — and each hit/miss/backend-compile also
  lands in the trace stream as an ``xla.compile.cache`` event (same
  family the serving recompile guard emits), so a slow boot's traces
  *show* the compiles it paid.

The JAX config and the monitoring listeners are process-global;
enabling twice with the same directory is idempotent, and a second
directory simply re-points the process-wide cache (last caller wins —
logged when it happens; never when the environment placed it).
"""

from __future__ import annotations

import logging
import os
import threading
from typing import Dict, List, Optional

logger = logging.getLogger(__name__)

# JAX's own variable: jax reads it into jax_compilation_cache_dir at
# import, so when it is set this module only adds thresholds and
# accounting on top and leaves the directory alone
ENV_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"
ENV_CACHE_MAX_BYTES = "DL4J_TPU_COMPILE_CACHE_MAX_BYTES"
DEFAULT_MAX_BYTES = 2 << 30  # 2 GiB

# the one fixed path used where the environment names none: inside the
# checkout (git-ignored), the same for every process that runs this tree
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)
    ))),
    ".jax_cache",
)

# jax monitoring event names this module folds into stats/counters
_EV_HIT = "/jax/compilation_cache/cache_hits"
_EV_MISS = "/jax/compilation_cache/cache_misses"
_EV_COMPILE = "/jax/core/compile/backend_compile_duration"
_EV_SAVED = "/jax/compilation_cache/compile_time_saved_sec"


class _CacheStats:
    """Process-wide compile/cache accounting (monotonic counters;
    read deltas around a region to attribute work to it). JAX's
    ``backend_compile_duration`` event brackets compile-OR-cache-
    retrieve, so the real-compile count is derived: calls minus
    persistent-cache hits."""

    def __init__(self):
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0
        self.compile_or_load_calls = 0
        self.compile_or_load_seconds = 0.0
        self.saved_seconds = 0.0

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "hits": self.hits,
                "misses": self.misses,
                # real XLA compiles: every compile-or-load dispatch
                # that was NOT answered from the persistent cache
                "backend_compiles": max(
                    self.compile_or_load_calls - self.hits, 0
                ),
                "compile_or_load_calls": self.compile_or_load_calls,
                # wall seconds inside compile-or-load (cache
                # retrieval included — milliseconds against the
                # seconds a real compile costs)
                "compile_seconds": round(
                    self.compile_or_load_seconds, 3
                ),
                "saved_seconds": round(self.saved_seconds, 3),
            }


_stats = _CacheStats()
_lock = threading.Lock()
_listeners_installed = False
_registry_sinks: List[Dict] = []  # [{"registry": reg, "hits": Counter, ...}]
_active_dir: Optional[str] = None  # last dir this module pointed jax at


def cache_stats() -> dict:
    """Process-wide persistent-cache stats snapshot (hits, misses,
    backend_compiles, compile_seconds, saved_seconds). Valid whether
    or not a disk cache is enabled — backend_compiles/compile_seconds
    count every real XLA compile the process performed."""
    return _stats.snapshot()


def default_cache_dir() -> Optional[str]:
    """The cache directory a caller gets without naming one:
    ``JAX_COMPILATION_CACHE_DIR`` where set; else ``REPO_CACHE_DIR``
    on a TPU backend; else ``None`` — on the CPU the cache stays
    opt-in (set the variable, or pass a directory). The caution is
    deliberate: a disk-loaded executable is the product of jaxlib's
    executable (de)serialization, which on the CPU backend has rough
    edges; silently enabling it under every process would put that
    machinery on paths that never asked for it."""
    env = os.environ.get(ENV_CACHE_DIR)
    if env:
        return env
    from deeplearning4j_tpu.ops.dispatch import effective_platform

    return REPO_CACHE_DIR if effective_platform() == "tpu" else None


def _trace_event(outcome: str, **attrs) -> None:
    # same xla.compile family the serving recompile guard uses; the
    # process-global tracer is disabled by default (one branch)
    from deeplearning4j_tpu.observability.trace import get_tracer
    from deeplearning4j_tpu.observability import flightrec

    get_tracer().event(
        "xla.compile.cache", attrs={"outcome": outcome, **attrs}
    )
    # compile events join the flight-recorder timeline too: a dump
    # whose last steps bracket a compile_or_load explains its own
    # step-time spike
    flightrec.record_event("xla_compile_cache", outcome=outcome,
                           **attrs)


def _on_event(event: str, **kw) -> None:
    try:
        if event == _EV_HIT:
            with _stats._lock:
                _stats.hits += 1
            for sink in _registry_sinks:
                sink["hits"].inc()
            _trace_event("hit")
        elif event == _EV_MISS:
            with _stats._lock:
                _stats.misses += 1
            for sink in _registry_sinks:
                sink["misses"].inc()
            _trace_event("miss")
    except Exception:  # accounting must never take down a compile
        logger.exception("compile-cache event accounting failed")


def _on_duration(event: str, duration: float, **kw) -> None:
    try:
        if event == _EV_COMPILE:
            with _stats._lock:
                _stats.compile_or_load_calls += 1
                _stats.compile_or_load_seconds += duration
            for sink in _registry_sinks:
                sink["compiles"].inc()
                sink["compile_seconds"].inc(duration)
            _trace_event("compile_or_load",
                         seconds=round(duration, 4))
        elif event == _EV_SAVED:
            with _stats._lock:
                _stats.saved_seconds += max(duration, 0.0)
    except Exception:
        logger.exception("compile-duration accounting failed")


def install_cache_accounting(registry=None) -> None:
    """Register the jax-monitoring listeners (once per process) and
    mirror hit/miss/compile counts into ``registry`` (default: the
    process-wide observability registry). Idempotent per registry."""
    from deeplearning4j_tpu.observability.metrics import (
        default_registry,
    )

    reg = registry if registry is not None else default_registry()
    global _listeners_installed
    with _lock:
        if not _listeners_installed:
            import jax.monitoring

            jax.monitoring.register_event_listener(_on_event)
            jax.monitoring.register_event_duration_secs_listener(
                _on_duration
            )
            _listeners_installed = True
        if any(s["registry"] is reg for s in _registry_sinks):
            return
        _registry_sinks.append({
            "registry": reg,
            "hits": reg.counter(
                "compile_cache_hits_total",
                help="persistent XLA cache: executables loaded from "
                     "disk instead of compiled",
            )._default(),
            "misses": reg.counter(
                "compile_cache_misses_total",
                help="persistent XLA cache: programs compiled and "
                     "written to disk",
            )._default(),
            "compiles": reg.counter(
                "xla_compile_or_load_total",
                help="XLA compile-or-cache-load dispatches (minus "
                     "compile_cache_hits_total = real compiles)",
            )._default(),
            "compile_seconds": reg.counter(
                "xla_compile_or_load_seconds_total",
                help="wall seconds inside XLA compile-or-cache-load",
            )._default(),
        })


def bound_cache_size(directory, max_bytes: int) -> int:
    """Prune the cache directory to ``max_bytes`` by deleting the
    oldest files first (mtime order: that of writing, since a hit
    rewrites nothing but jax's own ``-atime`` marker, and that only
    where jax's own bound is on). Returns bytes removed. Never
    raises: a shared cache dir may be mutated concurrently by sibling
    processes."""
    try:
        entries = []
        with os.scandir(os.fspath(directory)) as it:
            for e in it:
                if not e.is_file(follow_symlinks=False):
                    continue
                st = e.stat(follow_symlinks=False)
                entries.append((st.st_mtime, st.st_size, e.path))
    except OSError:
        return 0
    total = sum(size for _, size, _ in entries)
    if total <= max_bytes:
        return 0
    removed = 0
    for _, size, path in sorted(entries):
        if total - removed <= max_bytes:
            break
        try:
            os.unlink(path)
            removed += size
        except OSError:
            pass  # a sibling process got there first
    if removed:
        logger.info(
            "compile cache %s pruned %.1f MiB (bound %.1f MiB)",
            directory, removed / 2**20, max_bytes / 2**20,
        )
    return removed


def enable_persistent_cache(directory: Optional[str] = None, *,
                            registry=None,
                            min_compile_time_s: float = 0.0,
                            max_bytes: Optional[int] = None,
                            ) -> Optional[str]:
    """Turn on JAX's persistent compilation cache
    (``JAX_COMPILATION_CACHE_DIR`` > ``directory`` >
    ``default_cache_dir()``), creating the directory, bounding its
    size, and installing hit/miss accounting on ``registry``. Where
    the environment variable is set it wins over ``directory`` and
    ``jax_compilation_cache_dir`` is not touched: JAX already holds
    that value. Returns the directory in use, or ``None`` when there
    is none (CPU backend, nothing named). Never raises — a cache
    problem costs compiles, not the process."""
    env = os.environ.get(ENV_CACHE_DIR)
    d = env or directory or default_cache_dir()
    if d is None:
        return None
    d = os.fspath(d)
    try:
        global _active_dir
        if _active_dir != d:  # repeat calls (every fit) cost nothing
            _point_jax_at(d, placed_by_env=bool(env),
                          min_compile_time_s=min_compile_time_s,
                          max_bytes=max_bytes)
            _active_dir = d
        install_cache_accounting(registry)
        return d
    except Exception:
        logger.exception(
            "persistent compile cache setup failed; continuing "
            "without one (every process start will recompile)"
        )
        return None


def _point_jax_at(d: str, *, placed_by_env: bool,
                  min_compile_time_s: float,
                  max_bytes: Optional[int]) -> None:
    os.makedirs(d, exist_ok=True)
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    if not placed_by_env:
        prev = jax.config.jax_compilation_cache_dir
        if prev and os.path.abspath(prev) != os.path.abspath(d):
            logger.info(
                "re-pointing the process-wide compile cache: %s -> %s",
                prev, d,
            )
        jax.config.update("jax_compilation_cache_dir", d)
    # cache EVERYTHING: the default 1 s compile-time floor would
    # leave every small program recompiling on each boot forever
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      float(min_compile_time_s))
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # jax memoizes its cache-enabled decision at the FIRST compile of
    # the process; a server that enables the cache after anything has
    # compiled must reset that memo or the dir silently never takes
    # effect
    compilation_cache.reset_cache()
    if max_bytes is None:
        max_bytes = int(os.environ.get(
            ENV_CACHE_MAX_BYTES, DEFAULT_MAX_BYTES
        ))
    if max_bytes > 0:
        bound_cache_size(d, max_bytes)
