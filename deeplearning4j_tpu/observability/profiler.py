"""Hardware-truth profiling: HLO cost-model MFU accounting plus
per-step wall-time decomposition.

Two halves, both riding the PR-4 registry/tracer substrate:

**CostModel** — wraps ``jit(...).lower(...).compile().cost_analysis()``
into an immutable (flops, bytes accessed, arithmetic intensity) record
keyed by the same shape/kind identity ``CompileCache`` and the AOT
artifacts use (entry-point kind + transform-kind suffix + input shape
+ dtype — see ``compile/aot.py:artifact_fingerprint``). XLA's own
numbers for the program that actually runs, not an analytic estimate,
and deterministic per key: the same (kind, shape, dtype) always
resolves to the same cost. Combined with measured step wall time this
yields ``step_mfu`` / ``step_flops_per_sec`` / ``step_bytes_per_sec``
and a roofline classification, per engine step and per serving
bucket.

**StepProfiler** — per-step wall-time decomposition over the existing
seams:

- ``input_stall_ms``: how long the fit loop sat inside ``next()`` of
  the iterator it was handed (the ``fit.feed_wait`` boundary of
  ``nn/core.py``), so a record opens where its first wait opens;
- ``dispatch_ms``: the enqueue of the step's program — on the scan and
  megastep paths the ``fit.dispatch`` boundary (a compile or a full
  launch queue shows here), on the per-step path the
  ``AsyncDispatchWindow`` push block (back-pressure from the device);
- ``device_ms``: device sync time observed at retirement
  (``jax.block_until_ready`` wall inside the window / score sync);
- ``host_ms``: everything else — Python bookkeeping plus listener
  callbacks (``TelemetryListener`` et al.; the listener share is also
  measured separately into each record as ``listener_ms``).

The four components sum to the measured step wall time by
construction (host is the remainder, clamped at 0 when a component
measured on another thread overlaps) and are exported as histograms.
Each record is one ``train.step`` span, the parent of the fit drivers'
own ``fit.feed_wait`` / ``fit.stack`` / ``fit.dispatch`` /
``fit.listeners`` spans while a profiler is installed.

**Roofline classification** (gauge ``step_roofline_class``): a step is
``input_bound`` (3) when input stall exceeds ``input_bound_frac``
(default 25%) of wall; otherwise ``compute_bound`` (1) when the
executable's arithmetic intensity (flops / bytes accessed) is at or
above the machine balance (peak FLOP/s / peak bytes/s) and
``memory_bound`` (2) when below; ``unknown`` (0) when no peak is
known (CPU without the env override).

**Peak table**: dense bf16 peak FLOP/s lives in
``util/flops._PEAKS`` (keyed by TPU ``device_kind``); HBM bandwidth
per chip is tabled here. ``DL4J_TPU_PEAK_FLOPS`` and
``DL4J_TPU_PEAK_BYTES_PER_SEC`` override both so CPU CI (and any
machine the table doesn't know) still exercises the full MFU path
with a stated roofline.

Install with ``set_active_profiler(StepProfiler(...))`` — the fit
drivers, prefetch iterator, and dispatch window consult the
process-global at one attribute-read + None-check per touchpoint, so
uninstalled runs pay nothing and a ``StepProfiler(enabled=False)``
prices the fully-wired path at one branch per call.
"""

from __future__ import annotations

import os
import threading
import time
from dataclasses import dataclass
from typing import Callable, Dict, Optional, Tuple

ENV_PEAK_FLOPS = "DL4J_TPU_PEAK_FLOPS"
ENV_PEAK_BYTES = "DL4J_TPU_PEAK_BYTES_PER_SEC"

# HBM bandwidth (bytes/s) per chip by device_kind substring, public
# cloud specs; ordered, first hit wins (mirrors util/flops._PEAKS).
_HBM_BYTES_PER_SEC: Tuple[Tuple[str, float], ...] = (
    ("v6 lite", 1640e9),  # Trillium / v6e
    ("v6e", 1640e9),
    ("v5 lite", 819e9),   # v5e
    ("v5e", 819e9),
    ("v5p", 2765e9),
    ("v5", 2765e9),
    ("v4", 1228e9),
    ("v3", 900e9),
    ("v2", 700e9),
)

# roofline classification gauge values
ROOFLINE_UNKNOWN = 0
ROOFLINE_COMPUTE = 1
ROOFLINE_MEMORY = 2
ROOFLINE_INPUT = 3
ROOFLINE_NAMES = {
    ROOFLINE_UNKNOWN: "unknown",
    ROOFLINE_COMPUTE: "compute_bound",
    ROOFLINE_MEMORY: "memory_bound",
    ROOFLINE_INPUT: "input_bound",
}


def peak_flops(device=None) -> Tuple[Optional[float], str]:
    """(peak FLOP/s, source) — the ``DL4J_TPU_PEAK_FLOPS`` env
    override when set (CPU CI states its own roofline), else the
    documented per-chip table in ``util/flops``. None off-TPU with no
    override: MFU is only defined against a known roofline. On a TPU
    whose ``device_kind`` the table lacks, the lookup raises."""
    env = os.environ.get(ENV_PEAK_FLOPS)
    if env:
        try:
            v = float(env)
            if v > 0:
                return v, "env"
        except ValueError:
            pass
    from deeplearning4j_tpu.util.flops import device_peak_flops

    return device_peak_flops(device)


def peak_bytes_per_sec(device=None) -> Tuple[Optional[float], str]:
    """(peak HBM bytes/s, source): env override, else the per-chip
    table; None off-TPU, and a TPU ``device_kind`` missing from the
    table raises (``util.flops.table_lookup``)."""
    env = os.environ.get(ENV_PEAK_BYTES)
    if env:
        try:
            v = float(env)
            if v > 0:
                return v, "env"
        except ValueError:
            pass
    import jax

    from deeplearning4j_tpu.util.flops import table_lookup

    d = device if device is not None else jax.devices()[0]
    kind = getattr(d, "device_kind", d.platform)
    if d.platform != "tpu":
        return None, kind
    return table_lookup(_HBM_BYTES_PER_SEC, kind,
                        "peak HBM bytes/s"), kind


# -- cost model ---------------------------------------------------------


@dataclass(frozen=True)
class CostModel:
    """XLA's scheduled cost for ONE compiled executable: what the
    hardware was actually asked to do, keyed by the same shape/kind
    identity the compile cache and AOT artifacts use."""

    key: str
    flops: float
    bytes_accessed: float

    @property
    def arithmetic_intensity(self) -> float:
        """FLOPs per byte of memory traffic — the x-axis of the
        roofline plot."""
        return (self.flops / self.bytes_accessed
                if self.bytes_accessed else 0.0)

    def achieved(self, wall_s: float,
                 peak: Optional[float] = None) -> dict:
        """Achieved rates for one execution taking ``wall_s``
        seconds: flops_per_sec, bytes_per_sec, and mfu when a peak is
        known (else None)."""
        fps = self.flops / wall_s if wall_s > 0 else 0.0
        bps = self.bytes_accessed / wall_s if wall_s > 0 else 0.0
        return {
            "flops_per_sec": fps,
            "bytes_per_sec": bps,
            "mfu": (fps / peak) if peak else None,
        }

    def roofline_class(self, peak: Optional[float],
                       peak_bw: Optional[float]) -> int:
        """Compute- vs memory-bound from arithmetic intensity vs the
        machine balance point; unknown without a stated roofline.
        (Input-bound is a wall-time property, judged by the
        profiler, not the executable.)"""
        if not peak or not peak_bw or not self.bytes_accessed:
            return ROOFLINE_UNKNOWN
        balance = peak / peak_bw  # flops per byte at the ridge
        return (ROOFLINE_COMPUTE
                if self.arithmetic_intensity >= balance
                else ROOFLINE_MEMORY)

    @classmethod
    def from_cost_dict(cls, key: str, cost: dict) -> "CostModel":
        return cls(
            key=key,
            flops=float(cost.get("flops", 0.0)),
            # XLA spells it with a space; util/flops normalizes to _
            bytes_accessed=float(
                cost.get("bytes_accessed",
                         cost.get("bytes accessed", 0.0))),
        )

    @classmethod
    def from_jitted(cls, jitted, *args, key: str = "",
                    **kwargs) -> "CostModel":
        """Lower + compile an arbitrary jitted callable on concrete or
        abstract args and read XLA's cost analysis."""
        from deeplearning4j_tpu.util.flops import jit_cost

        return cls.from_cost_dict(key, jit_cost(jitted, *args,
                                                **kwargs))


def _shape_tag(shape) -> str:
    shape = tuple(shape)
    if shape and isinstance(shape[0], (tuple, list)):
        return ";".join("x".join(str(int(d)) for d in s)
                        for s in shape)
    return "x".join(str(int(d)) for d in shape)


def step_cost_key(model, batch_shape, dtype) -> str:
    """Cost-model identity of a train-step executable: entry-point
    kind + the transform-kind suffix (scan/remat/loss-scale/statguard/
    accum/zero/pallas change the HLO — same convention as the AOT
    artifact fingerprint) + input shape + dtype."""
    from deeplearning4j_tpu.nn.core import transform_kind_suffix

    return (f"step{transform_kind_suffix(model)}"
            f":{_shape_tag(batch_shape)}:{dtype}")


def output_cost_key(model, batch_shape, dtype) -> str:
    """Cost-model identity of an inference-forward executable (the
    serving bucket path) — mirrors the engine's AOT output kind."""
    kind = "output"
    fn = getattr(model, "_output_kind", None)
    if callable(fn):
        try:
            kind = fn()
        except Exception:
            pass
    return f"{kind}:{_shape_tag(batch_shape)}:{dtype}"


def kernel_cost_key(kernel: str, identity: dict,
                    config=None) -> str:
    """Cost-model identity of ONE Pallas kernel variant — the
    autotuner's prior records. Same spirit as ``step_cost_key``: the
    kernel kind plus the exact shape/dtype identity the tuning cache
    is keyed by, with the candidate block config appended when the
    record describes one specific tiling."""
    tag = ";".join(f"{k}={identity[k]}" for k in sorted(identity))
    key = f"kernel:{kernel}:{tag}"
    if config is not None:
        key += ":cfg=" + "x".join(str(int(v)) for v in config)
    return key


class CostModelCache:
    """Per-executable cost models, computed once per shape/kind key.

    The build (re-lower + compile) is host-side work that never
    touches the training trajectory; with the persistent XLA cache
    warm it is a cache read. Build failures are cached as None so a
    model that can't be lowered (stub models, exotic input
    marshalling) costs one attempt, not one per step."""

    def __init__(self):
        self._lock = threading.Lock()
        self._models: Dict[str, Optional[CostModel]] = {}

    def get_or_build(
            self, key: str,
            builder: Callable[[], Optional[CostModel]],
    ) -> Optional[CostModel]:
        with self._lock:
            if key in self._models:
                return self._models[key]
        try:
            cm = builder()
        except Exception:
            cm = None
        with self._lock:
            self._models.setdefault(key, cm)
            return self._models[key]

    def snapshot(self) -> Dict[str, dict]:
        with self._lock:
            return {
                k: ({"flops": v.flops,
                     "bytes_accessed": v.bytes_accessed,
                     "arithmetic_intensity":
                         round(v.arithmetic_intensity, 3)}
                    if v is not None else None)
                for k, v in self._models.items()
            }


def train_step_cost_model(model, ds) -> Optional[CostModel]:
    """CostModel of ``model``'s own train-step executable on
    minibatch ``ds`` (the program ``fit_minibatch`` runs), keyed by
    step kind + shape + dtype."""
    import numpy as np

    from deeplearning4j_tpu.util.flops import train_step_cost

    feats = ds.features
    if isinstance(feats, (list, tuple)):
        shape = tuple(tuple(np.shape(f)) for f in feats
                      if f is not None)
        dtype = str(np.asarray(
            [f for f in feats if f is not None][0]).dtype)
    else:
        shape = tuple(np.shape(feats))
        dtype = str(np.asarray(feats).dtype)
    key = step_cost_key(model, shape, dtype)
    cost = train_step_cost(model, ds)
    return CostModel.from_cost_dict(key, cost)


def output_cost_model(model, batch_shape,
                      dtype="float32") -> Optional[CostModel]:
    """CostModel of the model's jitted inference forward for one
    padded bucket shape — computed off the request path (serving
    warmup), then looked up per dispatch."""
    import jax

    jitted = getattr(model, "_jit_output", None)
    if jitted is None or getattr(model, "params", None) is None:
        return None
    key = output_cost_key(model, batch_shape, dtype)
    x = jax.ShapeDtypeStruct(tuple(int(d) for d in batch_shape),
                             dtype)
    lowered = jitted.lower(model.params, model.state, x, None, None,
                           False)
    from deeplearning4j_tpu.util.flops import _cost_dict

    return CostModel.from_cost_dict(key, _cost_dict(lowered.compile()))


# -- step profiler ------------------------------------------------------

# decomposition histogram buckets: fine at the bottom (a healthy
# component is ~0) and coarse at the top, in ms (shared with the
# prefetch-wait idiom)
DECOMP_MS_BUCKETS = (0.1, 0.5, 1.0, 2.5, 5.0, 10.0, 25.0, 50.0,
                     100.0, 250.0, 1000.0)


class _StepState:
    __slots__ = ("step", "t0", "input_ms", "dispatch_ms", "device_ms",
                 "listener_ms", "span")

    def __init__(self, step, t0, span):
        self.step = step
        self.t0 = t0
        self.input_ms = 0.0
        self.dispatch_ms = 0.0
        self.device_ms = 0.0
        self.listener_ms = 0.0
        self.span = span


class StepProfiler:
    """Per-step MFU accounting + wall-time decomposition (module
    docstring has the full story). One instance per training run;
    install process-globally with ``set_active_profiler``."""

    def __init__(self, registry=None, tracer=None, recorder=None,
                 enabled: bool = True,
                 peak: Optional[float] = None,
                 peak_bw: Optional[float] = None,
                 input_bound_frac: float = 0.25,
                 clock: Callable[[], float] = time.perf_counter):
        from deeplearning4j_tpu.observability.metrics import (
            default_registry,
        )
        from deeplearning4j_tpu.observability.trace import get_tracer

        self.enabled = enabled
        self.registry = (registry if registry is not None
                         else default_registry())
        self.tracer = tracer if tracer is not None else get_tracer()
        self.recorder = recorder
        self.costs = CostModelCache()
        self.input_bound_frac = float(input_bound_frac)
        self._clock = clock
        if peak is None:
            peak, self.peak_source = peak_flops()
        else:
            self.peak_source = "caller"
        if peak_bw is None:
            peak_bw, self.peak_bw_source = peak_bytes_per_sec()
        else:
            self.peak_bw_source = "caller"
        self.peak = peak
        self.peak_bw = peak_bw
        self._state: Optional[_StepState] = None
        self._cost_memo = None  # (sig, CostModel) steady-state memo
        reg = self.registry
        self._h_input = reg.histogram(
            "training_input_stall_ms", buckets=DECOMP_MS_BUCKETS,
            help="step decomposition: fit loop starved for the next "
                 "batch (prefetch consumer wait)",
        )._default()
        self._h_host = reg.histogram(
            "training_host_ms", buckets=DECOMP_MS_BUCKETS,
            help="step decomposition: host-side remainder — Python "
                 "bookkeeping + listener callbacks",
        )._default()
        self._h_dispatch = reg.histogram(
            "training_dispatch_ms", buckets=DECOMP_MS_BUCKETS,
            help="step decomposition: blocked pushing into the async "
                 "dispatch window (device back-pressure)",
        )._default()
        self._h_device = reg.histogram(
            "training_device_ms", buckets=DECOMP_MS_BUCKETS,
            help="step decomposition: device sync observed at "
                 "retirement (block_until_ready / score sync)",
        )._default()
        self._g_mfu = reg.gauge(
            "step_mfu",
            help="profiler: achieved / peak FLOP/s of the last step "
                 "(cost-model flops over measured wall; requires a "
                 "known peak — DL4J_TPU_PEAK_FLOPS off-TPU)",
        )._default()
        self._g_fps = reg.gauge(
            "step_flops_per_sec",
            help="profiler: cost-model FLOPs / measured step wall",
        )._default()
        self._g_bps = reg.gauge(
            "step_bytes_per_sec",
            help="profiler: cost-model bytes accessed / measured "
                 "step wall",
        )._default()
        self._g_class = reg.gauge(
            "step_roofline_class",
            help="profiler: roofline classification of the last step "
                 "(0 unknown / 1 compute-bound / 2 memory-bound / "
                 "3 input-bound)",
        )._default()

    # -- hot-path hooks (called by the seams) ---------------------------

    def open_feed(self, parent=None) -> bool:
        """The fit drivers are about to wait for a batch: open the next
        record here, unless one is open already (a chunk being
        buffered). ``begin_step`` gives it its step number once the
        driver knows it. True when this call opened the record."""
        if not self.enabled or self._state is not None:
            return False
        self._open(None, parent)
        return True

    def begin_step(self, step: int, parent=None) -> None:
        if not self.enabled:
            return
        st = self._state
        if st is not None and st.step is None:
            st.step = int(step)  # opened at its first feed wait
            if st.span is not None:
                st.span.set_attr("step", st.step)
            return
        self._open(int(step), parent)

    def _open(self, step, parent) -> None:
        span = None
        if self.tracer is not None:
            span = self.tracer.start_span(
                "train.step", parent=parent,
                attrs=None if step is None else {"step": step})
            if not span.recording:
                span = None
        self._state = _StepState(step, self._clock(), span)

    def open_span(self):
        """The open record's ``train.step`` span, if it has one."""
        st = self._state
        return st.span if st is not None else None

    def note_input_wait_ms(self, ms: float) -> None:
        st = self._state
        if st is not None:
            st.input_ms += ms

    def note_dispatch_ms(self, ms: float) -> None:
        st = self._state
        if st is not None:
            st.dispatch_ms += ms

    def note_device_ms(self, ms: float) -> None:
        st = self._state
        if st is not None:
            st.device_ms += ms

    def note_listener_ms(self, ms: float) -> None:
        st = self._state
        if st is not None:
            st.listener_ms += ms

    # -- end of step ----------------------------------------------------

    def end_step(self, model=None, ds=None, score=None,
                 grad_norm=None, rows=None,
                 cost: Optional[CostModel] = None,
                 chunk: Optional[int] = None) -> Optional[dict]:
        """Close the current step: decompose wall time, publish the
        gauges/histograms, append the flight-recorder record, and end
        the per-step span. Returns the
        record dict (None when disabled / unpaired). ``chunk=K``
        marks a fused megastep record covering K optimizer steps
        under ONE dispatch (``step`` is then the LAST covered step) —
        recorder-measured dispatches/step over a run is
        records/steps, ~1/K under megastep."""
        st = self._state
        if not self.enabled or st is None:
            return None
        self._state = None
        wall_ms = (self._clock() - st.t0) * 1000.0
        measured = st.input_ms + st.dispatch_ms + st.device_ms
        host_ms = max(0.0, wall_ms - measured)
        self._h_input.observe(st.input_ms)
        self._h_host.observe(host_ms)
        self._h_dispatch.observe(st.dispatch_ms)
        self._h_device.observe(st.device_ms)

        if cost is None and model is not None and ds is not None:
            feats = ds.features
            # steady-state fast path: same model + batch geometry as
            # last step -> reuse the resolved cost without rebuilding
            # the shape/kind key (the key walk costs more than the
            # rest of this method together on a small step)
            try:
                # _jit_step identity doubles as knob invalidation:
                # scan/remat/accum flips rebuild the jitted step
                sig = (id(model), id(model._jit_step),
                       feats.shape, feats.dtype)
            except AttributeError:
                sig = None
            memo = self._cost_memo
            if sig is not None and memo is not None \
                    and memo[0] == sig:
                cost = memo[1]
            else:
                key = None
                try:
                    import numpy as np

                    if isinstance(feats, (list, tuple)):
                        shape = tuple(
                            tuple(np.shape(f)) for f in feats
                            if f is not None)
                        dtype = str(np.asarray(
                            [f for f in feats
                             if f is not None][0]).dtype)
                    else:
                        shape = tuple(np.shape(feats))
                        dtype = str(np.asarray(feats).dtype)
                    key = step_cost_key(model, shape, dtype)
                except Exception:
                    key = None
                if key is not None:
                    cost = self.costs.get_or_build(
                        key, lambda: train_step_cost_model(model, ds))
                if sig is not None:
                    self._cost_memo = (sig, cost)

        mfu = fps = bps = intensity = None
        klass = ROOFLINE_UNKNOWN
        if cost is not None:
            ach = cost.achieved(wall_ms / 1000.0, self.peak)
            fps, bps, mfu = (ach["flops_per_sec"],
                             ach["bytes_per_sec"], ach["mfu"])
            intensity = cost.arithmetic_intensity
            klass = cost.roofline_class(self.peak, self.peak_bw)
            self._g_fps.set(fps)
            self._g_bps.set(bps)
            if mfu is not None:
                self._g_mfu.set(mfu)
        if (wall_ms > 0
                and st.input_ms >= self.input_bound_frac * wall_ms):
            klass = ROOFLINE_INPUT
        self._g_class.set(float(klass))

        rec = {
            "step": st.step,
            "wall_ms": round(wall_ms, 3),
            "input_stall_ms": round(st.input_ms, 3),
            "host_ms": round(host_ms, 3),
            "dispatch_ms": round(st.dispatch_ms, 3),
            "device_ms": round(st.device_ms, 3),
            "listener_ms": round(st.listener_ms, 3),
            "roofline": ROOFLINE_NAMES[klass],
        }
        if score is not None:
            rec["loss"] = score
        if grad_norm is not None:
            rec["grad_norm"] = grad_norm
        if rows is not None:
            rec["rows"] = int(rows)
        if chunk is not None:
            rec["chunk"] = int(chunk)
        if cost is not None:
            rec["cost_key"] = cost.key
            if mfu is not None:
                rec["mfu"] = round(mfu, 6)
            rec["flops_per_sec"] = fps
            rec["arithmetic_intensity"] = (
                round(intensity, 3) if intensity is not None else None)

        span = st.span
        if span is not None:
            span.set_attr("wall_ms", round(wall_ms, 3))
            span.set_attr("roofline", ROOFLINE_NAMES[klass])
            rec["trace_id"] = span.context.trace_id
            span.end()
        if self.recorder is not None:
            self.recorder.record(**rec)
        return rec

    def abandon_step(self, status: str = "error") -> None:
        """Drop an open step without recording (exception paths; the
        feed's last, empty-handed wait with status ``unused``)."""
        st = self._state
        self._state = None
        if st is not None and st.span is not None:
            st.span.end(status)

    def snapshot(self) -> dict:
        """Bounded JSON view for /debugz."""
        return {
            "enabled": self.enabled,
            "peak_flops": self.peak,
            "peak_flops_source": self.peak_source,
            "peak_bytes_per_sec": self.peak_bw,
            "peak_bytes_source": self.peak_bw_source,
            "input_bound_frac": self.input_bound_frac,
            "cost_models": self.costs.snapshot(),
        }


# -- process-global profiler (mirrors trace.get_tracer) ----------------

_ACTIVE: Optional[StepProfiler] = None


def get_active_profiler() -> Optional[StepProfiler]:
    return _ACTIVE


def set_active_profiler(
        prof: Optional[StepProfiler]) -> Optional[StepProfiler]:
    """Install ``prof`` as the process-global step profiler (the fit
    drivers / prefetch / dispatch seams consult it) and return the
    previous one."""
    global _ACTIVE
    prev = _ACTIVE
    _ACTIVE = prof
    return prev
