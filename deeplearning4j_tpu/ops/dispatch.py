"""Kernel/backend dispatch helpers shared by the Pallas ops, the
gradient checker, and host-side analytics: one place decides which
platform the next computation actually targets and how to pin work to
the host CPU backend."""

from __future__ import annotations

import contextlib
import contextvars
import os
from typing import Optional

import jax


def effective_platform() -> str:
    """Platform the next computation targets: honors a
    ``jax.default_device`` override (which may hold a Device or a
    platform string like ``"cpu"``), else the default backend."""
    dev = jax.config.jax_default_device
    if dev is not None:
        return dev if isinstance(dev, str) else dev.platform
    return jax.default_backend()


def cpu_device() -> Optional["jax.Device"]:
    """The host CPU device, or None when the CPU backend is
    unavailable (e.g. JAX_PLATFORMS pinned elsewhere)."""
    try:
        return jax.local_devices(backend="cpu")[0]
    except RuntimeError:
        return None


# DL4J_TPU_PALLAS is read ONCE per process and cached: use_pallas()
# sits on every dense/LSTM/attention forward trace, and an os.environ read
# per call is both a needless syscall-shaped cost and a footgun (a
# mid-process setenv silently flipping kernel paths between traces of
# the same program). Tests flip the knob through reset_for_tests().
_ENV_CACHE: Optional[str] = None


def _pallas_env() -> str:
    global _ENV_CACHE
    if _ENV_CACHE is None:
        _ENV_CACHE = os.environ.get(
            "DL4J_TPU_PALLAS", "auto"
        ).strip().lower()
    return _ENV_CACHE


def reset_for_tests() -> None:
    """Drop the cached ``DL4J_TPU_PALLAS`` read so the NEXT
    ``use_pallas()`` call re-reads the environment, and cascade to the
    autotuner (its ``DL4J_TPU_TUNE*`` knobs follow the same
    read-once-per-process discipline, plus in-process resolution
    memos). The only supported way to flip kernel dispatch or tuning
    mid-process (tests, bench A/Bs); production processes read the
    knobs once at first dispatch."""
    global _ENV_CACHE
    _ENV_CACHE = None
    from deeplearning4j_tpu.ops import autotune

    autotune.reset_for_tests()


# True while tracing a program the compiler partitions over several
# devices by itself (per context, so a serving thread tracing its own
# forward at the same time is not affected)
_AUTO_PARTITIONED = contextvars.ContextVar(
    "dl4j_tpu_auto_partitioned", default=False
)


@contextlib.contextmanager
def auto_partitioned(active: bool = True):
    """Trace scope of a GSPMD ``jit`` over a multi-device mesh. The
    chip's compiler refuses a Mosaic kernel there
    (``NotImplementedError: Mosaic kernels cannot be automatically
    partitioned. Please wrap the call in a shard_map.``), so inside
    the scope no kernel is eligible: ``use_pallas()`` is false and
    every call site takes XLA, visibly
    (``pallas_dispatch_total{mode="xla"}``). ``shard_map`` programs
    and single-device meshes need no scope — the kernel sees whole
    per-device blocks there."""
    token = _AUTO_PARTITIONED.set(bool(active))
    try:
        yield
    finally:
        _AUTO_PARTITIONED.reset(token)


def pallas_forced() -> bool:
    """``DL4J_TPU_PALLAS=1``: every call the compiler accepts goes to
    its kernel, on any platform — the parity tests' and the chip
    A/B's knob."""
    return _pallas_env() in ("1", "true", "on")


def use_pallas() -> bool:
    """Env-gated Pallas dispatch (DL4J_TPU_PALLAS=1/0/auto): kernels
    engage only when the targeted platform is TPU, and never inside an
    ``auto_partitioned`` trace scope. A forced ``1`` off-TPU still
    routes through the kernels, but they self-arm interpreter mode
    (``pallas_interpret``) — same code path, correct-but-slow
    execution instead of a Mosaic lowering crash."""
    if _AUTO_PARTITIONED.get():
        return False
    if pallas_forced():
        return True
    if _pallas_env() in ("0", "false", "off"):
        return False
    return effective_platform() == "tpu"


def pallas_interpret() -> bool:
    """Whether a Pallas kernel must run in interpreter mode: anywhere
    but a real TPU. The kernels OR this into their ``interpret`` flag
    so ``DL4J_TPU_PALLAS=1`` on a CPU host (the classic local-repro
    footgun) executes instead of failing to lower TPU memory spaces."""
    return effective_platform() != "tpu"


# --- dispatch observability -----------------------------------------------
#
# Routing decisions happen at trace time (Python), once per compiled
# program — cheap enough to meter every one. The counter answers "which
# kernels actually engaged, and in which mode" without a TPU profiler;
# the gauge flags the classic silent-slowness footgun (forced-on Pallas
# interpreting on CPU).

_METRICS_FOR = None  # (registry, counter family, gauge child)


def _dispatch_metrics():
    global _METRICS_FOR
    from deeplearning4j_tpu.observability.metrics import default_registry

    reg = default_registry()
    if _METRICS_FOR is None or _METRICS_FOR[0] is not reg:
        counter = reg.counter(
            "pallas_dispatch_total",
            help="kernel routing decisions at dispatch (trace) time, "
                 "by kernel and mode (pallas/interpret/xla)",
            labels=("kernel", "mode"),
        )
        gauge = reg.gauge(
            "pallas_interpret_mode",
            help="1 when Pallas kernels run in interpreter mode "
                 "(off-TPU host) — correct but slow",
        )._default()
        _METRICS_FOR = (reg, counter, gauge)
    return _METRICS_FOR[1], _METRICS_FOR[2]


def note_dispatch(kernel: str, mode: str) -> None:
    """Record one kernel routing decision:
    ``pallas_dispatch_total{kernel, mode}`` (mode is ``pallas``,
    ``interpret`` or ``xla``) and the ``pallas_interpret_mode``
    gauge."""
    counter, gauge = _dispatch_metrics()
    counter.labels(kernel=kernel, mode=mode).inc()
    gauge.set(1.0 if pallas_interpret() else 0.0)


def route(kernel: str, eligible: bool = True) -> bool:
    """One-stop gate + telemetry for a kernel call site: returns
    whether ``kernel`` takes the Pallas path (``eligible`` carries the
    caller's shape/activation/VMEM gates) and meters the decision."""
    use = bool(eligible) and use_pallas()
    mode = ("interpret" if pallas_interpret() else "pallas") if use \
        else "xla"
    note_dispatch(kernel, mode)
    return use
