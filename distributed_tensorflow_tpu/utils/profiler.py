"""Profiling / tracing subsystem.

The reference's only "profiling" is wall-clock ``time.time()`` deltas printed
to stdout (``demo1/train.py:152,164``) plus graph visualisation via
``FileWriter(..., sess.graph)`` (``demo1/train.py:151``) — SURVEY §5.1. The
TPU-native upgrade is a real XLA trace: ``jax.profiler`` writes a
TensorBoard-loadable profile (XPlane) with per-op device timelines, HLO, and
memory-allocation views.

Three entry points:

* :class:`Profiler` — step-windowed tracing for training loops: arm it with a
  ``[start_step, start_step + num_steps)`` window and call ``.step(i)`` once
  per loop iteration; the trace starts/stops itself and each step inside the
  window is annotated with ``StepTraceAnnotation`` so TensorBoard groups
  device ops by step.
* :func:`trace` — context manager for ad-hoc tracing of any region.
* :func:`annotate` — named ``TraceAnnotation`` for host-side regions so they
  show up on the trace timeline.

All are no-ops when given an empty/None log dir, so call sites need no
conditionals.
"""

from __future__ import annotations

import contextlib
import os

from distributed_tensorflow_tpu.utils.logging import get_logger

log = get_logger(__name__)


class Profiler:
    """Step-windowed ``jax.profiler`` trace for a training loop.

    Usage::

        prof = Profiler(log_dir, start_step=10, num_steps=5)
        for step in range(n):
            with prof.step(step):
                run_one_step()
        prof.close()  # safety net if the loop exits inside the window

    ``start_step`` defaults past the compile steps so the trace captures
    steady-state device time, not XLA compilation.

    ``sync`` (if given) is called right before the trace is stopped. Training
    loops dispatch steps asynchronously, so without a device sync the host
    reaches the end of the window while the device is still executing traced
    steps and the XPlane is truncated; pass e.g.
    ``lambda: jax.block_until_ready(self.global_step)`` — device execution is
    in-order, so blocking on the window's last output flushes all of it.
    """

    def __init__(
        self,
        log_dir: str | None,
        start_step: int = 10,
        num_steps: int = 5,
        sync=None,
    ):
        self.log_dir = log_dir or None
        self.start_step = start_step
        self.num_steps = num_steps
        self.sync = sync
        self._active = False
        self._done = False
        self._seen_spans: set[int] = set()
        self._deferred = False
        self._traced = 0
        self._first_step: int | None = None

    @property
    def enabled(self) -> bool:
        return self.log_dir is not None

    def step(self, step: int, span: int = 1):
        """Context manager wrapping one training dispatch covering optimizer
        steps ``[step, step + span)`` (span > 1 = fused multi-step chunks);
        manages the trace window. The window triggers when it INTERSECTS the
        dispatch's range — with fused chunks a strict membership test could
        skip past the window entirely and never record a trace.

        One exception: if the window would open on a dispatch whose fused
        chunk length (``span``) has never been dispatched before, while
        ``start_step`` asks to skip past the run's beginning, the open is
        deferred to the next dispatch with an already-seen span. A
        never-seen span means a fresh jit compile (the cache is keyed on
        the chunk length): ``start_step`` exists precisely to skip
        compilation, and with fused chunks the bare intersection test
        would otherwise start the trace around the compile and swamp the
        XPlane with host time. Set ``start_step=0`` (or <= the resume
        step) to opt into tracing the first dispatch anyway. Once open,
        the trace covers at least ``num_steps`` optimizer steps' worth of
        dispatches."""
        if not self.enabled or self._done:
            return contextlib.nullcontext()
        if self._first_step is None:
            self._first_step = step
        if self._active and self._traced >= self.num_steps:
            self._stop()
            self._seen_spans.add(span)
            return contextlib.nullcontext()
        window_end = self.start_step + self.num_steps
        if not self._active:
            intersects = step < window_end and step + span > self.start_step
            # Opt-in: a start_step at/before the run's first step means the
            # caller wants the first (compiling) dispatch traced. Otherwise
            # never open around a chunk length's first-ever dispatch — that
            # is where its jit compile happens (including tail chunks whose
            # first appearance is mid-run, not just the run's first call).
            opt_in = self.start_step <= self._first_step
            if intersects or self._deferred:
                if not opt_in and span not in self._seen_spans:
                    self._deferred = True
                else:
                    self._start()
        self._seen_spans.add(span)
        if self._active:
            self._traced += span
            import jax

            return jax.profiler.StepTraceAnnotation("train", step_num=step)
        return contextlib.nullcontext()

    def _start(self) -> None:
        import jax

        os.makedirs(self.log_dir, exist_ok=True)
        jax.profiler.start_trace(self.log_dir)
        self._active = True
        log.info("profiler: trace started -> %s", self.log_dir)

    def _stop(self) -> None:
        import jax

        if self.sync is not None:
            self.sync()
        jax.profiler.stop_trace()
        self._active = False
        self._done = True
        log.info("profiler: trace written to %s", self.log_dir)

    def close(self) -> None:
        """Stop the trace if the loop ended while it was still active; warn if
        the run finished before the window ever opened (else an empty profile
        dir would be the only clue)."""
        if self._active:
            self._stop()
        elif self.enabled and not self._done:
            hint = (
                " (window deferred past the run's only dispatch — the first "
                "dispatch compiles; set start_step=0 to trace it anyway, or "
                "lower steps_per_call)"
                if self._deferred
                else ""
            )
            log.warning(
                "profiler: run ended before the trace window opened "
                "(start_step=%d, num_steps=%d) — no profile written to %s%s",
                self.start_step, self.num_steps, self.log_dir, hint,
            )


@contextlib.contextmanager
def trace(log_dir: str | None):
    """Trace an arbitrary region: ``with trace('./prof'): run()``. No-op when
    ``log_dir`` is falsy."""
    if not log_dir:
        yield
        return
    import jax

    os.makedirs(log_dir, exist_ok=True)
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
        log.info("profiler: trace written to %s", log_dir)


_trace_annotation = None


def annotate(name: str, **kwargs):
    """Named host-side region annotation visible on the trace timeline.
    With no profiler session open it is a TraceMe that records nothing
    (0.4 µs to open and close); ``obs.span`` puts every span through here,
    so the class is looked up once and not per call."""
    global _trace_annotation
    if _trace_annotation is None:
        import jax

        _trace_annotation = jax.profiler.TraceAnnotation
    return _trace_annotation(name, **kwargs)


def save_device_memory_profile(path: str) -> None:
    """Dump a pprof-format snapshot of live device (HBM) allocations."""
    import jax

    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    jax.profiler.save_device_memory_profile(path)
    log.info("profiler: device memory profile -> %s", path)
