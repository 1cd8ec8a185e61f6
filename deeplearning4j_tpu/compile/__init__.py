"""Compilation-artifact subsystem: compile once, run anywhere.

Every process start used to re-pay full XLA compilation — serving
warmup compiled the whole bucket ladder on each boot and hot reload,
and compile time is what blew the bench budget (BENCH r05/r06: whole
sections timed out inside a single compile). This package adopts the
ahead-of-time, compile-once stance of TVM and the Julia→TPU
full-compilation paper (PAPERS.md): compiled code is a durable
artifact alongside the checkpoint, so restarts, reloads, and bench
sections hit disk instead of the compiler. Two tiers:

- **Tier 1 — persistent XLA compile cache** (``persistent.py``):
  JAX's on-disk compilation cache, placed by JAX's own
  ``JAX_COMPILATION_CACHE_DIR`` or else at ``<repo>/.jax_cache``; on
  by default where the backend is a TPU (the fit loop and the serving
  tier enable it), opt-in on the CPU, with cache-dir creation, LRU
  size bounding, and hit/miss accounting surfaced as
  ``compile_cache_hits_total`` / ``compile_cache_misses_total``
  through the observability registry, and each compile phase (trace,
  lower, compile-or-load) kept as a span record (``compile_spans()``).
  A *warm* cache turns every recompile of an
  already-seen program into a disk read.
- **Tier 2 — AOT-exported executables** (``aot.py``): true
  ahead-of-time export — ``jit(...).lower().compile()`` serialized
  via ``jax.experimental.serialize_executable`` (with a
  ``jax.export`` StableHLO fallback where the backend cannot
  serialize executables) of the serving forward per shape bucket and
  of the engines' train-step functions, keyed by (model config,
  shape, dtype, backend, jax version) fingerprints, bundled into the
  ``CheckpointManager`` manifest's ``artifacts`` map and loaded by
  serving ``start()``/``reload()`` so warmup *deserializes* instead
  of compiling. Every missing/stale/corrupt artifact degrades
  silently to JIT (``aot_fallback_total``) — an artifact problem may
  cost a compile, never a request.
"""

from deeplearning4j_tpu.compile.persistent import (  # noqa: F401
    bound_cache_size,
    cache_stats,
    compile_spans,
    default_cache_dir,
    enable_persistent_cache,
    install_cache_accounting,
)
from deeplearning4j_tpu.compile.aot import (  # noqa: F401
    AotArtifactError,
    artifact_fingerprint,
    export_artifact,
    export_serving_bundle,
    install_serving_bundle,
    load_artifact,
    peek_meta,
)
