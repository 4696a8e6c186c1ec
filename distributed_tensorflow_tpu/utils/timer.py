"""Wall-clock and step timing.

Parity with the reference's ``time.time()`` deltas printed as
``Training time:`` / ``Total time:`` (``demo1/train.py:152,164``;
``retrain1/retrain.py:373,423,468,476``), plus steps/sec tracking for the
bench harness.
"""

from __future__ import annotations

import time


class WallClock:
    """Elapsed wall-clock timer: ``WallClock()`` starts; ``.elapsed`` reads."""

    def __init__(self):
        self.start = time.time()

    @property
    def elapsed(self) -> float:
        return time.time() - self.start

    def lap(self) -> float:
        now = time.time()
        out = now - self.start
        self.start = now
        return out


class StepTimer:
    """Tracks steps/sec over a sliding window, excluding warmup/compile steps."""

    def __init__(self, warmup_steps: int = 2):
        self.warmup_steps = warmup_steps
        self._count = 0
        self._timed_steps = 0
        self._timed_seconds = 0.0
        self._last = None

    def tick(self, steps: int = 1) -> None:
        """Record one dispatch covering ``steps`` optimizer steps."""
        now = time.time()
        if self._last is not None and self._count >= self.warmup_steps:
            self._timed_steps += steps
            self._timed_seconds += now - self._last
        self._last = now
        self._count += 1

    def mark(self, step: int | None = None) -> None:
        """Restart the current window at 'now' WITHOUT counting anything —
        call after boundary work (eval, summaries, checkpoint) so its time
        is excluded from the next training window's steps/sec. Pass the
        current ``step`` when using the tick_to API: a MID-window mark
        (e.g. a timed autosave) must also drop the partial window's steps,
        or the next tick_to would attribute them to post-mark time only."""
        self._last = time.time()
        if step is not None:
            self._last_step = step

    # -- drained-window convenience API (the loop.py / CLI idiom) ----------
    # Dispatch is asynchronous, so a per-dispatch tick measures issue time,
    # not compute: tick ONLY at completion barriers.
    # ``start(step)`` marks t0 (and consumes one warmup slot, so with the
    # default warmup_steps=2 the first measured window — which contains the
    # jit compile — is dropped); ``tick_to(step)`` closes the window at a
    # barrier, attributing the steps since the last start/tick_to.

    def start(self, step: int) -> None:
        self.tick(0)
        self._last_step = step

    def tick_to(self, step: int) -> None:
        self.tick(step - self._last_step)
        self._last_step = step

    @property
    def steps_per_sec(self) -> float:
        if self._timed_seconds <= 0:
            return 0.0
        return self._timed_steps / self._timed_seconds
