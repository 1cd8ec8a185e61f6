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
  ``compile_cache_misses_total`` / ``xla_compile_or_load_total`` /
  ``xla_compile_or_load_seconds_total`` counters on every registry
  handed to ``install_cache_accounting`` — the serving tier passes
  its per-server registry — and hit/miss/compile-or-load join the
  flight recorder's timeline;
- **phase records**: each of jax's three compile phases is kept as a
  finished span in a bounded ring (``compile_spans()``, always on,
  on ``time.perf_counter``): ``compile.trace`` (attr ``fun``, jax's
  name of the traced function; a jit traced inside another's trace
  is folded into that one's record, as ``nested`` and ``nested_s``,
  and so is one a lowering rule makes into the ``compile.lower``
  record), ``compile.lower`` (``fun``
  ``jit(<name>)``: the jaxpr's conversion to MLIR) and
  ``compile.backend`` (``fun``; ``outcome`` ``hit`` / ``miss`` /
  ``uncached`` from the persistent cache's event on the same thread
  inside the interval; ``retrieval_s`` on a hit). While the global
  tracer records (``set_global_tracer``, or a profiler session) each
  record is also a span of it, so a slow boot's traces *show* the
  compiles it paid.

The JAX config and the monitoring listeners are process-global;
enabling twice with the same directory is idempotent, and a second
directory simply re-points the process-wide cache (last caller wins —
logged when it happens; never when the environment placed it).
"""

from __future__ import annotations

import logging
import os
import threading
import time
from collections import deque
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
_EV_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
# jax's phase intervals (time-span listener) -> the records' names
_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "compile.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile.lower",
    _EV_COMPILE: "compile.backend",
}
# records kept; a trace nested in another's is folded into it, so a
# cell's set-up is tens of records (thousands of traces at its size)
MAX_COMPILE_SPANS = 4096
# a thread's traces held until it lowers are the callees of the trace
# still open (a model's step: thousands); the bound is for a thread
# that traces and never lowers
MAX_HELD_TRACES = 65536


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
        # finished phase records, oldest dropped (compile_spans()):
        # (number, name, start, end, attrs)
        self.spans: "deque[tuple]" = deque(maxlen=MAX_COMPILE_SPANS)
        # per thread: its traces that a later trace may still enclose
        self.held: Dict[int, List[tuple]] = {}
        self.span_count = 0
        self.spans_dropped = 0

    def keep(self, name: str, start: float, end: float,
             attrs: dict) -> List[tuple]:
        """Take one phase record; returns the records it made final,
        ``(name, start, end, attrs)``. A trace is held with its
        thread's until that thread lowers or compiles: until then a
        trace or a lowering ending later may enclose it (its caller,
        or a lowering rule that traces), and takes it in as
        ``nested`` (count) and ``nested_s`` (seconds, every level
        summed), so a model's thousands of nested traces take one
        slot and a trace's time is counted in one phase."""
        thread = threading.get_ident()
        rec = (name, start, end, attrs)
        with self._lock:
            held = self.held.pop(thread, [])
            if name != "compile.backend":
                # a thread's traces nest like its calls: the ones inside
                # this interval are the last it holds
                nested, nested_s = 0, 0.0
                while held and held[-1][1] >= start:
                    _, s, e, a = held.pop()
                    nested += 1 + a.get("nested", 0)
                    nested_s += e - s + a.get("nested_s", 0.0)
                if nested:
                    attrs["nested"] = nested
                    attrs["nested_s"] = nested_s
            final = []
            if name == "compile.trace":
                held.append(rec)
                if len(held) > MAX_HELD_TRACES:
                    final, held = held[:1], held[1:]
                self.held[thread] = held
            else:
                final = held + [rec]
            for n, s, e, a in final:
                if len(self.spans) == self.spans.maxlen:
                    self.spans_dropped += 1
                self.span_count += 1
                self.spans.append((self.span_count, n, s, e, a))
        return final

    def kept(self) -> List[tuple]:
        """The ring's records, then the traces still held."""
        with self._lock:
            held = sorted((h for hs in self.held.values() for h in hs),
                          key=lambda h: h[2])
            n = self.span_count
            return list(self.spans) + [
                (n + i + 1, *h) for i, h in enumerate(held)]

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
                # phase records the ring let go (compile_spans())
                "compile_spans_dropped": self.spans_dropped,
            }


_stats = _CacheStats()
# the persistent cache's verdict on the compile-or-load running on
# this thread: (outcome, perf_counter when seen, retrieval seconds)
_pending = threading.local()
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


def compile_spans() -> List[dict]:
    """A copy of the newest compile phase records, oldest first: dicts
    of ``Span.to_dict()``'s shape named ``compile.trace``,
    ``compile.lower`` and ``compile.backend`` (module docstring), at
    most ``MAX_COMPILE_SPANS`` (``cache_stats()``'s
    ``compile_spans_dropped`` counts the ones let go). Kept once
    ``install_cache_accounting`` has run, whether or not a tracer
    records."""
    return [{
        "kind": "span", "name": name,
        "trace_id": f"{n:032x}", "span_id": f"{n:016x}",
        "parent_id": None, "start": start, "end": end,
        "duration_ms": (end - start) * 1000.0, "status": "ok",
        "attrs": dict(attrs), "events": [],
    } for n, name, start, end, attrs in _stats.kept()]


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


def _flight_event(outcome: str, **attrs) -> None:
    # compile events join the flight-recorder timeline: a dump whose
    # last steps bracket a compile_or_load explains its own step-time
    # spike
    from deeplearning4j_tpu.observability import flightrec

    flightrec.record_event("xla_compile_cache", outcome=outcome,
                           **attrs)


def _on_event(event: str, **kw) -> None:
    try:
        if event == _EV_HIT:
            with _stats._lock:
                _stats.hits += 1
            for sink in _registry_sinks:
                sink["hits"].inc()
            _pending.verdict = ("hit", time.perf_counter(), None)
            _flight_event("hit")
        elif event == _EV_MISS:
            with _stats._lock:
                _stats.misses += 1
            for sink in _registry_sinks:
                sink["misses"].inc()
            _pending.verdict = ("miss", time.perf_counter(), None)
            _flight_event("miss")
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
            _flight_event("compile_or_load",
                          seconds=round(duration, 4))
        elif event == _EV_SAVED:
            with _stats._lock:
                _stats.saved_seconds += max(duration, 0.0)
        elif event == _EV_RETRIEVAL:
            verdict = getattr(_pending, "verdict", None)
            if verdict is not None and verdict[0] == "hit":
                _pending.verdict = (*verdict[:2], duration)
    except Exception:
        logger.exception("compile-duration accounting failed")


def _on_span(event: str, start_time: float, end_time: float,
             **kw) -> None:
    """One of jax's compile phases has ended: keep it as a record on
    ``time.perf_counter`` (jax times it on the wall clock, so the
    record ends now and starts its length earlier) and hand what
    ``_CacheStats.keep`` makes final to the global tracer while that
    records."""
    name = _PHASES.get(event)
    if name is None:
        return
    try:
        end = time.perf_counter()
        start = end - (end_time - start_time)
        attrs = {"fun": kw.get("fun_name", "?")}
        if name == "compile.backend":
            verdict = getattr(_pending, "verdict", None)
            _pending.verdict = None
            if verdict is None or verdict[1] < start:
                attrs["outcome"] = "uncached"
            else:
                attrs["outcome"] = verdict[0]
                if verdict[2] is not None:
                    attrs["retrieval_s"] = verdict[2]
        final = _stats.keep(name, start, end, attrs)
        if final:
            from deeplearning4j_tpu.observability.trace import get_tracer

            tracer = get_tracer()
            for n, s, e, a in final:
                tracer.record(n, s, e, attrs=dict(a))
    except Exception:
        logger.exception("compile-phase accounting failed")


def install_cache_accounting(registry=None) -> None:
    """Register the jax-monitoring listeners (once per process: the
    counters, and the phase records of ``compile_spans()``) and
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
            jax.monitoring.register_event_time_span_listener(_on_span)
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
