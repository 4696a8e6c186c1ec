"""Flight recorder: fixed-size in-memory ring of the last N spans/events.

A multi-host incident rarely leaves usable evidence: stdout is interleaved,
TensorBoard events flush late, and the interesting part is the last few
seconds before the SIGTERM/exception. The recorder keeps a bounded deque of
recent telemetry (closed spans from :mod:`~distributed_tensorflow_tpu.obs.trace`,
plus instantaneous events) and dumps it as JSONL when something goes wrong:

* ``train/resilience.py`` calls :func:`FlightRecorder.dump` on preemption and
  rollback;
* :func:`install_excepthook` chains onto ``sys.excepthook`` so ANY unhandled
  exception in an obs-enabled process ships its timeline.

Recording cost is one re-entrant lock + deque.append (the deque is bounded,
so memory is fixed). Dumping is the only I/O, and it only happens on the
failure path.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
from collections import deque
from typing import Any

__all__ = [
    "FlightRecorder",
    "get_recorder",
    "set_recorder",
    "set_dump_dir",
    "install_excepthook",
]

DEFAULT_CAPACITY = 1024


class FlightRecorder:
    """Bounded ring buffer of telemetry events (dicts). Thread-safe."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY):
        if capacity <= 0:
            raise ValueError(f"capacity must be > 0, got {capacity}")
        self.capacity = capacity
        # Re-entrant: a full garbage collection is recorded from the
        # ``gc.callbacks`` hook (obs/trace.py), which can run on a thread
        # that is inside ``record`` already.
        self._lock = threading.RLock()
        self._events: deque[dict] = deque(maxlen=capacity)
        self._seq = 0

    def record(self, **event: Any) -> None:
        """Append one event. A monotonically increasing ``seq`` and a wall
        timestamp are stamped here so dump ordering is unambiguous even when
        two events land within clock resolution."""
        with self._lock:
            self._seq += 1
            event.setdefault("seq", self._seq)
            event.setdefault("t_wall", time.time())
            event.setdefault("t_mono", time.monotonic())
            self._events.append(event)

    def record_span(self, sp) -> None:
        """Entry point for :class:`~distributed_tensorflow_tpu.obs.trace.Span`
        — converts to a dict event (keeps the recorder span-class agnostic)."""
        ev = sp.to_event()
        with self._lock:
            self._seq += 1
            ev["seq"] = self._seq
            self._events.append(ev)

    def events(self) -> list[dict]:
        """Snapshot of the ring, oldest first."""
        with self._lock:
            return list(self._events)

    def clear(self) -> None:
        with self._lock:
            self._events.clear()

    def dump(self, path: str, *, reason: str = "") -> str:
        """Write the ring to ``path`` as JSONL (one event per line, oldest
        first), prefixed with a header line identifying the dump. Returns the
        path. Creates parent directories. Never raises on serialization —
        unserializable attrs are stringified (a crash dump must not crash)."""
        events = self.events()
        header = {
            "kind": "flight_record",
            "reason": reason,
            "pid": os.getpid(),
            "t_wall": time.time(),
            "num_events": len(events),
            "capacity": self.capacity,
        }
        parent = os.path.dirname(path)
        if parent:
            os.makedirs(parent, exist_ok=True)
        with open(path, "w") as f:
            f.write(json.dumps(header, default=str) + "\n")
            for ev in events:
                f.write(json.dumps(ev, default=str) + "\n")
        return path


_recorder_lock = threading.Lock()
_recorder = FlightRecorder()
_dump_dir: str = ""


def get_recorder() -> FlightRecorder:
    return _recorder


def set_recorder(recorder: FlightRecorder) -> None:
    global _recorder
    with _recorder_lock:
        _recorder = recorder


def set_dump_dir(path: str) -> None:
    """Where crash dumps land (``--obs_dir``). Empty disables dumping — the
    ring still records, but :func:`dump_to_dir` becomes a no-op."""
    global _dump_dir
    _dump_dir = path


def get_dump_dir() -> str:
    return _dump_dir


def dump_to_dir(reason: str) -> str | None:
    """Dump the process recorder into the configured dump dir, named
    ``flight_<reason>_p<process>_<pid>.jsonl``. Returns the path, or None
    when no dump dir is configured. Best-effort: I/O errors are swallowed
    (this runs on failure paths where a second exception helps nobody)."""
    if not _dump_dir:
        return None
    proc = 0
    jax = sys.modules.get("jax")
    if jax is not None:
        try:
            proc = int(jax.process_index())
        except Exception:  # noqa: BLE001
            proc = 0
    safe_reason = "".join(c if c.isalnum() or c in "-_" else "_" for c in reason)
    path = os.path.join(
        _dump_dir, f"flight_{safe_reason}_p{proc}_{os.getpid()}.jsonl"
    )
    try:
        return _recorder.dump(path, reason=reason)
    except OSError:
        return None


_hook_installed = False


def install_excepthook() -> None:
    """Chain a flight-record dump onto ``sys.excepthook`` AND
    ``threading.excepthook`` so any unhandled exception — main thread or a
    background one (checkpoint snapshot thread, scheduler loop) — writes
    its timeline before dying. Without the threading hook, a crashing
    daemon thread evaporates silently with no dump. Idempotent; the
    previous hooks (usually the default traceback printers) still run."""
    global _hook_installed
    with _recorder_lock:
        if _hook_installed:
            return
        _hook_installed = True
    prev = sys.excepthook

    def _hook(exc_type, exc, tb):
        try:
            get_recorder().record(
                kind="event",
                name="unhandled_exception",
                error=f"{exc_type.__name__}: {exc}",
            )
            dump_to_dir("unhandled_exception")
        finally:
            prev(exc_type, exc, tb)

    sys.excepthook = _hook

    prev_threading = threading.excepthook

    def _thread_hook(args):
        try:
            get_recorder().record(
                kind="event",
                name="unhandled_thread_exception",
                thread=getattr(args.thread, "name", None),
                error=f"{args.exc_type.__name__}: {args.exc_value}",
            )
            dump_to_dir("unhandled_thread_exception")
        finally:
            prev_threading(args)

    threading.excepthook = _thread_hook
