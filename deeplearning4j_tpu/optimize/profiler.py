"""Profiler hooks (SURVEY.md §5 tracing: "same listener SPI + jax
profiler hooks" — the reference has only PerformanceListener timing;
the TPU-era upgrade is a listener that brackets training with the XLA
profiler so traces open in TensorBoard/XProf/Perfetto).
"""

from __future__ import annotations

import logging
import os
from typing import Optional

from deeplearning4j_tpu.optimize.listeners import IterationListener


class ProfilerListener(IterationListener):
    """Capture a jax profiler trace from ``start_iteration`` for
    ``num_iterations`` iterations (device + host timelines, one trace
    directory per session) of the program the user runs: the listener
    keeps ``fit()`` on its fused scan path. The session starts at the
    first callback at or after ``start_iteration`` and stops at the
    first at or after ``start_iteration + num_iterations``; on the
    scan path the callbacks of a chunk fire together once the chunk is
    dispatched, so both fall on chunk boundaries and the chunks
    dispatched in between are traced whole (the dispatch is
    asynchronous; stopping waits for the device). The fit drivers'
    own spans (``fit.dispatch``, ``fit.feed_wait``, ...) record while
    the session runs and land in the same file, beside the device.

    Usage::

        net.listeners.append(ProfilerListener("/tmp/trace", 10, 5))
        net.fit(data)          # iterations 10..14 are traced
    """

    supports_batched_iterations = True

    def __init__(self, log_dir: str, start_iteration: int = 5,
                 num_iterations: int = 5):
        # fail fast: an unwritable trace directory must error HERE,
        # not after the run has trained start_iteration steps and the
        # profiler tries its first write
        try:
            os.makedirs(log_dir, exist_ok=True)
        except OSError as e:
            raise ValueError(
                f"ProfilerListener log_dir {log_dir!r} cannot be "
                f"created: {e}"
            ) from e
        if not os.access(log_dir, os.W_OK):
            raise ValueError(
                f"ProfilerListener log_dir {log_dir!r} is not "
                "writable"
            )
        self.log_dir = log_dir
        self.start_iteration = int(start_iteration)
        self.stop_iteration = int(start_iteration) + int(num_iterations)
        self._active = False
        self.trace_dir: Optional[str] = None  # set once traced

    def _start(self) -> None:
        import jax

        os.makedirs(self.log_dir, exist_ok=True)
        jax.profiler.start_trace(self.log_dir)
        self._active = True

    def _stop(self) -> None:
        import jax

        jax.profiler.stop_trace()
        self._active = False
        self.trace_dir = self.log_dir
        # surface the trace location in the event log (and the span
        # sink, when a global tracer is installed) instead of only
        # returning it to whoever remembers to read .trace_dir
        from deeplearning4j_tpu.observability.trace import get_tracer

        get_tracer().event("profiler.trace_ready", attrs={
            "trace_dir": self.trace_dir,
        })
        logging.getLogger(__name__).info(
            "profiler trace written to %s", self.trace_dir
        )

    def iteration_done(self, model, iteration: int) -> None:
        if self.trace_dir is not None:  # one session per listener
            return
        if not self._active and iteration >= self.start_iteration:
            self._start()
        elif self._active and iteration >= self.stop_iteration:
            # block so the trace includes finished device work
            try:
                float(model.score_value)
            except Exception:
                pass
            self._stop()

    def on_epoch_end(self, model) -> None:
        """Finalize an open trace when training ends before
        ``stop_iteration`` — an unfinalized jax trace blocks any later
        ``start_trace`` in the process."""
        if self._active:
            try:
                float(model.score_value)
            except Exception:
                pass
            self._stop()

    def close(self) -> None:
        if self._active:
            self._stop()


def annotate(name: str):
    """Named trace span for host-side phases (jax TraceAnnotation) —
    usable around data loading / eval to label the profile."""
    import jax

    return jax.profiler.TraceAnnotation(name)
