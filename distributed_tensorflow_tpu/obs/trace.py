"""Span tracing (Dapper-style, in-process): the program's one span mechanism.

``with span("checkpoint_save", step=120):`` measures a host-side region. A
closed span goes to three places:

* **a bounded ring per span name** (:class:`SpanRings`), each record
  ``(t0, t1, attrs)`` on ``time.monotonic`` — the scheduler's clock and, on
  Linux, the clock behind ``time.perf_counter``. One ring per NAME, so a
  thousand per-round spans never push out the one ``engine.warmup`` span.
  :func:`closed` returns the records that overlap an interval; an interval
  measured elsewhere (queue wait: submitted on a client thread, ended on the
  driver thread) enters through the same door, :func:`interval`.
* **the profiler's trace**, as a ``jax.profiler.TraceAnnotation`` of the same
  name (``utils/profiler.annotate``). With no profiler session open that is
  a TraceMe that records nothing; with one open (``utils/profiler.trace``
  around a running stack) the span sits in the host plane on the device
  trace's own clock. JAX is resolved lazily, once, and only in a process
  that has already imported it: ``obs`` itself imports without JAX.
* **the flight recorder** (:mod:`~distributed_tensorflow_tpu.obs.recorder`),
  with wall clock, span id and parent id, so the last N spans are what a
  crash dump ships. Spans that close every serving round pass
  ``flight=False`` and stay out of it: its 1,024 events would otherwise hold
  four seconds of rounds and nothing else.

Two kinds of span are recorded by the process itself, not by a ``with``
block (:func:`install_gc_spans` here, the compile listener in
:mod:`~distributed_tensorflow_tpu.obs.perf`; both installed once by
``obs.install_runtime_spans()``, before the first compile): every garbage
collection, as ``py.gc.<generation>``, and every jaxpr trace, lowering and
XLA compile, as ``jax.trace``, ``jax.lower`` and ``xla.compile``. Those are
the two events that stop every Python thread at once.

There is no switch: spans are always on, and "tracing off" is no profiler
session. A span closed with no session open costs about 2 µs
(``tests/test_obs_trace.py`` holds it under a loose ceiling; the benchmark's
``sched.metrics_sync_p50_ms`` and the paired runs in PERF.md §6 say what the
instrumentation costs a serving round).

Nesting is tracked per thread: a span opened inside another carries its
``parent_id``, so a dump reconstructs the call tree (emergency_shutdown →
checkpoint_save → …). Span ids are a process-local counter; the recorded
``process`` index disambiguates a multi-host job's per-process dumps.
"""

from __future__ import annotations

import gc
import itertools
import sys
import threading
import time
from collections import deque
from typing import Any

from distributed_tensorflow_tpu.obs import recorder as _recorder

__all__ = [
    "Span", "SpanRings", "span", "interval", "closed", "names",
    "trace_event", "current_span", "RING_CAPACITY", "install_gc_spans",
    "flight_interval",
]

# A whole benchmark run: cell 1 closes about 5,000 engine.round spans over
# its lead and 45 s window, and collects its youngest generation a few
# times a second (PERF.md §6, PR 39).
RING_CAPACITY = 16384

_ids = itertools.count(1)
_local = threading.local()
_process: int | None = None
_annotate = None  # utils/profiler.annotate, once jax is in the process


def _process_index() -> int:
    """jax.process_index(), resolved once: the first call made after the
    process imported jax settles it (the obs package must stay importable —
    and cheap — in non-JAX tooling, where this is 0)."""
    global _process
    if _process is None:
        jax = sys.modules.get("jax")
        if jax is None:
            return 0
        try:
            _process = int(jax.process_index())
        except Exception:  # noqa: BLE001 — uninitialized backend
            return 0
    return _process


def _resolve_annotate():
    """utils/profiler.annotate, once the process has imported jax; None
    before (a process without jax can hold no profiler session either)."""
    global _annotate
    if _annotate is None and "jax" in sys.modules:
        from distributed_tensorflow_tpu.utils.profiler import annotate

        _annotate = annotate
    return _annotate


def _annotation(name: str, attrs):
    """A TraceAnnotation for an opening span, or None in a process without
    jax."""
    annotate = _resolve_annotate()
    if annotate is None:
        return None
    return annotate(name, **attrs) if attrs else annotate(name)


class SpanRings:
    """One bounded ring of ``(t0, t1, attrs)`` per span name. Appends take
    no lock (``deque.append`` is atomic and the deque drops its oldest
    record itself); only the first record of a new name does."""

    def __init__(self, capacity: int = RING_CAPACITY):
        if capacity <= 0:
            raise ValueError(f"capacity must be > 0, got {capacity}")
        self.capacity = capacity
        self._rings: dict[str, deque] = {}
        self._lock = threading.Lock()

    def ring(self, name: str) -> deque:
        """``name``'s ring, made empty on first use."""
        ring = self._rings.get(name)
        if ring is None:
            with self._lock:
                ring = self._rings.setdefault(
                    name, deque(maxlen=self.capacity))
        return ring

    def record(self, name: str, t0: float, t1: float, attrs=None) -> None:
        ring = self._rings.get(name)
        if ring is None:
            ring = self.ring(name)
        ring.append((t0, t1, attrs))

    def closed(self, name: str, t_lo: float = float("-inf"),
               t_hi: float = float("inf")) -> list[tuple]:
        """Records of ``name`` that overlap ``[t_lo, t_hi]``, oldest first."""
        ring = self._rings.get(name)
        if ring is None:
            return []
        while True:
            try:
                snapshot = tuple(ring)
                break
            except RuntimeError:  # an append landed mid-copy: copy again
                continue
        return [r for r in snapshot if r[1] >= t_lo and r[0] <= t_hi]

    def names(self) -> list[str]:
        return sorted(self._rings)


_rings = SpanRings()


def _stack() -> list:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


def current_span() -> "Span | None":
    """The innermost open span on THIS thread (None outside any span)."""
    stack = _stack()
    return stack[-1] if stack else None


class Span:
    """One traced region. Context-manager use only — ``__exit__`` closes the
    span and records it; an exception inside the region is noted on the span
    (``error`` field) and re-raised."""

    __slots__ = (
        "name", "attrs", "flight", "span_id", "parent_id",
        "t_wall", "t_mono", "end_mono", "duration_s", "error", "_ann",
    )

    def __init__(self, name: str, attrs: dict[str, Any] | None = None,
                 flight: bool = True):
        self.name = name
        self.attrs = attrs or None
        self.flight = flight
        self.span_id = next(_ids)
        self.parent_id = 0
        self.t_wall = 0.0
        self.t_mono = 0.0
        self.end_mono = 0.0
        self.duration_s = 0.0
        self.error = ""
        self._ann = None

    @property
    def process(self) -> int:
        return _process_index()

    def note(self, **attrs: Any) -> None:
        """Attributes known only once the region has run (``completed``,
        ``produced``): they ride the ring record, the flight event and the
        profiler annotation like those given at the opening."""
        if self.attrs is None:
            self.attrs = attrs
        else:
            self.attrs.update(attrs)
        if self._ann is not None:
            self._ann.set_metadata(**attrs)

    def __enter__(self) -> "Span":
        stack = _stack()
        if stack:
            self.parent_id = stack[-1].span_id
        stack.append(self)
        ann = self._ann = _annotation(self.name, self.attrs)
        if ann is not None:
            ann.__enter__()
        if self.flight:
            self.t_wall = time.time()
        self.t_mono = time.monotonic()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.end_mono = time.monotonic()
        self.duration_s = self.end_mono - self.t_mono
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        if exc is not None:
            self.error = f"{type(exc).__name__}: {exc}"
        stack = _stack()
        if stack and stack[-1] is self:
            stack.pop()
        _rings.record(self.name, self.t_mono, self.end_mono, self.attrs)
        if self.flight:
            _recorder.get_recorder().record_span(self)
        return None  # never swallow

    def to_event(self) -> dict:
        ev = {
            "kind": "span",
            "name": self.name,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "process": self.process,
            "t_wall": self.t_wall,
            "t_mono": self.t_mono,
            "end_mono": self.end_mono,
            "duration_s": round(self.duration_s, 6),
        }
        if self.attrs:
            ev["attrs"] = self.attrs
        if self.error:
            ev["error"] = self.error
        return ev


def span(name: str, *, flight: bool = True, **attrs: Any) -> Span:
    """Open a traced region: ``with span("eval", step=200): ...``.
    ``flight=False`` keeps a span that closes every serving round out of
    the flight recorder (rings and profiler only)."""
    return Span(name, attrs, flight)


def interval(name: str, t0: float, t1: float, **attrs: Any) -> None:
    """Record an interval whose two ends were read elsewhere (on the
    caller's ``time.monotonic``-compatible clock) into ``name``'s ring."""
    _rings.record(name, t0, t1, attrs or None)


def flight_interval(name: str, t0: float, t1: float, attrs: dict) -> None:
    """An interval of ``time.monotonic`` ends into the flight recorder as a
    span event of the thread's open span. Its ``process`` is the index as
    far as a span has resolved it (0 before): the hooks that call this may
    run before the backend exists, and must not create it."""
    stack = getattr(_local, "stack", None)
    _recorder.get_recorder().record(
        kind="span", name=name, span_id=next(_ids),
        parent_id=stack[-1].span_id if stack else 0,
        process=_process or 0, t_wall=time.time() - (time.monotonic() - t0),
        t_mono=t0, end_mono=t1, duration_s=round(t1 - t0, 6), attrs=attrs)


def closed(name: str, t_lo: float = float("-inf"),
           t_hi: float = float("inf")) -> list[tuple]:
    """``(t0, t1, attrs)`` of every closed span or interval of ``name``
    that overlaps ``[t_lo, t_hi]``, oldest first (``attrs`` is None for a
    span that carried none)."""
    return _rings.closed(name, t_lo, t_hi)


def names() -> list[str]:
    """Every span name that has closed at least once in this process."""
    return _rings.names()


def trace_event(name: str, **attrs: Any) -> None:
    """Record an instantaneous event (no duration) into the flight recorder
    — preemption requests, vetoes, rollbacks."""
    parent = current_span()
    _recorder.get_recorder().record(
        kind="event",
        name=name,
        process=_process_index(),
        parent_id=parent.span_id if parent is not None else 0,
        **attrs,
    )


# ---------------------------------------------------------------------------
# garbage collections
# ---------------------------------------------------------------------------

_GC_NAMES = ("py.gc.0", "py.gc.1", "py.gc.2")
_gc_open = None  # (name, inside, annotation, t0) of the collection under way


def _on_gc(phase: str, info: dict) -> None:
    """``gc.callbacks`` hook: one span a collection, in a ring per
    generation, opened as a profiler annotation at ``start`` and closed at
    ``stop`` (so a pause sits in the host plane on the device trace's
    clock). CPython runs one collection at a time and calls both phases on
    the thread that collects. The hook reads the thread's span stack and
    never writes it, takes no lock (the rings exist from the install; the
    flight recorder's lock is re-entrant), never resolves
    ``jax.process_index()`` (a collection during start-up must not touch
    the backend) and never raises."""
    global _gc_open
    try:
        if phase == "start":
            stack = getattr(_local, "stack", None)
            name = _GC_NAMES[info["generation"]]
            ann = _annotate(name) if _annotate is not None else None
            if ann is not None:
                ann.__enter__()
            _gc_open = (name, stack[-1].name if stack else "", ann,
                        time.monotonic())
            return
        opened, _gc_open = _gc_open, None
        if opened is None:
            return
        t1 = time.monotonic()
        name, inside, ann, t0 = opened
        if ann is not None:
            ann.__exit__(None, None, None)
        attrs = {"collected": info["collected"], "inside": inside}
        _rings.record(name, t0, t1, attrs)
        if name == "py.gc.2":
            flight_interval(name, t0, t1, attrs)
    except Exception:  # noqa: BLE001 — a collection must never fail
        _gc_open = None


def install_gc_spans() -> None:
    """Record every garbage collection from now on (idempotent)."""
    for name in _GC_NAMES:
        _rings.ring(name)
    _resolve_annotate()
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)
