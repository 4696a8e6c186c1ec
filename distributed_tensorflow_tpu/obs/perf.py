"""Live performance accounting: MFU/throughput gauges, memory watermarks,
and the recompile sentinel.

``utils/flops.py`` already knows the model-FLOP and roofline math, but until
now it only fed offline ``bench.py`` records and boundary stdout prints.
This module turns the same arithmetic into registry gauges refreshed every
eval window, so a scraper sees the fleet's compute efficiency live:

* :class:`PerfGauges` — ``train_mfu`` (model FLOPs x steps/s over cluster
  peak; absent off-TPU, where ``chip_peak_flops`` correctly refuses to
  invent a denominator), ``tokens_per_second`` / ``examples_per_second``,
  and ``train_step_seconds`` (the SLO monitor's step-time selector).
* :func:`update_memory_gauges` — per-device ``bytes_in_use`` /
  ``peak_bytes_in_use`` watermarks from ``Device.memory_stats()``. The CPU
  backend returns None there; the gauges are then simply not touched
  (graceful null — no fake zeros in the scrape).
* :func:`install_runtime_spans` — the program's ONE ``jax.monitoring``
  registration (a time-span, an event and a scalar listener, installed
  once, never removed) and the garbage-collection hook of
  :mod:`~distributed_tensorflow_tpu.obs.trace`. Every jaxpr trace, MLIR
  lowering and backend compile becomes a span (``jax.trace``,
  ``jax.lower``, ``xla.compile``, attribute ``fun``; ``jax.trace`` also
  ``inner``, the traces nested in it and folded into it; ``xla.compile``
  also ``cache``: ``"hit"``, ``"miss"`` or ``"off"``) in the span rings,
  and ``xla.compile`` in the flight recorder too.
* :class:`RecompileSentinel` — the serving engine's zero-recompile-after-
  warmup invariant was a test-only ``compile_count()`` assert; this makes
  it an ALERTING runtime metric. Primary signal: the listener above, on
  backend compiles (one a XLA compilation), forwarded to whichever
  sentinels are currently open — ``close()`` detaches a sentinel without
  touching the listener. Version-guarded fallback: when the monitoring
  API is missing (or listener mode is explicitly declined), the sentinel
  counts deltas of an externally-polled compile-cache size
  (``SlotEngine.compile_count()`` feeds :meth:`RecompileSentinel.poll`
  every engine round). ``mark_warm()`` draws the line: compile events
  before it are warmup, events after it increment
  ``recompile_events_total`` — the metric the default serving SLO rule
  alerts on (threshold 0: ANY post-warmup compile is a breach).
"""

from __future__ import annotations

import threading
import time

from distributed_tensorflow_tpu.obs import registry as _registry
from distributed_tensorflow_tpu.obs import trace as _trace

__all__ = [
    "PerfGauges",
    "update_memory_gauges",
    "RecompileSentinel",
    "monitoring_available",
    "install_runtime_spans",
]


# ---------------------------------------------------------------------------
# throughput / MFU gauges
# ---------------------------------------------------------------------------


class PerfGauges:
    """Eval-window performance gauges on a registry (process default when
    ``registry`` is None). Call :meth:`update_window` at each boundary with
    whatever is known; unknown quantities leave their gauges untouched."""

    def __init__(self, registry=None):
        reg = registry if registry is not None else _registry.get_registry()
        self.mfu = reg.gauge(
            "train_mfu",
            "Model FLOPs utilization over the last drained window "
            "(absent off-TPU: no peak to divide by).")
        self.tokens_rate = reg.gauge(
            "tokens_per_second", "Global tokens/s over the last window.")
        self.examples_rate = reg.gauge(
            "examples_per_second", "Global examples/s over the last window.")
        self.step_seconds = reg.gauge(
            "train_step_seconds",
            "Mean seconds per optimizer step over the last window.")

    def update_window(
        self,
        *,
        steps_per_sec: float,
        tokens_per_step: int | None = None,
        examples_per_step: int | None = None,
        model_cfg=None,
        batch_size: int | None = None,
        seq_len: int | None = None,
        flops_per_step: float | None = None,
        peak_flops: float | None = None,
        num_devices: int | None = None,
    ) -> float | None:
        """Refresh rates for one drained window; returns the MFU (or None
        when it cannot be computed — off-TPU, or no model math given).

        MFU numerator: ``flops_per_step`` directly, else
        ``transformer_train_flops(model_cfg, batch_size, seq_len)``.
        Denominator: ``peak_flops`` per device (default
        ``chip_peak_flops()``) x ``num_devices`` (default all)."""
        if steps_per_sec <= 0:
            return None  # compile window — rates would be lies
        self.step_seconds.set(1.0 / steps_per_sec)
        if tokens_per_step:
            self.tokens_rate.set(steps_per_sec * tokens_per_step)
        if examples_per_step:
            self.examples_rate.set(steps_per_sec * examples_per_step)
        flops = flops_per_step
        if flops is None and model_cfg is not None and batch_size:
            from distributed_tensorflow_tpu.utils.flops import (
                transformer_train_flops,
            )

            flops = transformer_train_flops(model_cfg, batch_size, seq_len)
        if flops is None:
            return None
        if peak_flops is None:
            from distributed_tensorflow_tpu.utils.flops import chip_peak_flops

            peak_flops = chip_peak_flops()
        if peak_flops is None:
            return None  # graceful null: no invented denominator
        if num_devices is None:
            import jax

            num_devices = len(jax.devices())
        mfu = flops * steps_per_sec / (peak_flops * max(num_devices, 1))
        self.mfu.set(mfu)
        return mfu


def update_memory_gauges(registry=None) -> dict:
    """Refresh per-device HBM watermark gauges from
    ``Device.memory_stats()``. Returns ``{device_label: stats}`` for the
    devices that reported; empty on backends (CPU) whose ``memory_stats()``
    is None or missing — the graceful-null contract: gauges untouched, no
    zeros invented."""
    import jax

    reg = registry if registry is not None else _registry.get_registry()
    in_use = reg.gauge(
        "device_memory_bytes_in_use",
        "Live device allocation (memory_stats bytes_in_use).",
        labels=("device",))
    peak = reg.gauge(
        "device_memory_peak_bytes",
        "High-watermark device allocation this process lifetime.",
        labels=("device",))
    limit = reg.gauge(
        "device_memory_limit_bytes",
        "Allocator capacity (memory_stats bytes_limit).",
        labels=("device",))
    out: dict = {}
    for dev in jax.local_devices():
        stats = None
        try:
            stats = dev.memory_stats()
        except Exception:  # noqa: BLE001 — backend without the API at all
            stats = None
        if not stats:
            continue
        label = f"{dev.platform}:{dev.id}"
        if "bytes_in_use" in stats:
            in_use.labels(label).set(float(stats["bytes_in_use"]))
        if "peak_bytes_in_use" in stats:
            peak.labels(label).set(float(stats["peak_bytes_in_use"]))
        if "bytes_limit" in stats:
            limit.labels(label).set(float(stats["bytes_limit"]))
        out[label] = stats
    return out


# ---------------------------------------------------------------------------
# compile spans and the recompile sentinel: one jax.monitoring registration
# ---------------------------------------------------------------------------

_dispatch_lock = threading.Lock()
_dispatch_installed = False
_active_sentinels: list["RecompileSentinel"] = []

# jax's timed compile stages (jax._src.dispatch.log_elapsed_time: a
# time.time() start and end, and the function's name) -> span names.
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_COMPILE_SPANS = {
    _TRACE_EVENT: "jax.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax.lower",
    "/jax/core/compile/backend_compile_duration": "xla.compile",
}
# Fired on the compiling thread inside the backend-compile interval. A
# lookup that finds nothing is a miss even where the entry is then too small
# or too quick to write (cache_misses fires only on a write); a hit follows
# the lookup and overrides it.
_CACHE_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "miss",
    "/jax/compilation_cache/cache_misses": "miss",
    "/jax/compilation_cache/cache_hits": "hit",
}
# Per thread: .v = (cache outcome, time.time()) of the last cache event;
# .depth = jaxpr traces open, .inner = traces folded into the open one.
_compiling = threading.local()


def monitoring_available() -> bool:
    """Version guard: does this jax expose the listeners the compile spans
    and the sentinel's primary signal need?"""
    try:
        from jax import monitoring
    except ImportError:
        return False
    return all(callable(getattr(monitoring, f, None)) for f in (
        "register_event_time_span_listener", "register_event_listener",
        "register_scalar_listener"))


def _on_event(event: str, **kw) -> None:
    outcome = _CACHE_EVENTS.get(event)
    if outcome is not None:
        _compiling.v = (outcome, time.time())


def _on_scalar(event: str, value, **kw) -> None:
    # jax records its start time as a scalar when a timed stage opens.
    if event == _TRACE_EVENT:
        _compiling.depth = getattr(_compiling, "depth", 0) + 1


def _on_time_span(event: str, start: float, end: float, **kw) -> None:
    name = _COMPILE_SPANS.get(event)
    if name is None:
        return
    try:
        attrs = {"fun": str(kw.get("fun_name", ""))}
        if event == _TRACE_EVENT:
            # A trace inside another (every inner jit, every jnp function
            # called under an outer jit: 15,000 a serving warm-up) is
            # folded into the outermost one, which covers it: the ring
            # keeps a start-up's traces and their union is the same.
            depth = getattr(_compiling, "depth", 0)
            _compiling.depth = max(depth - 1, 0)
            inner = getattr(_compiling, "inner", 0)
            if depth > 1:
                _compiling.inner = inner + 1
                return
            _compiling.inner = 0
            attrs["inner"] = inner
        to_mono = time.monotonic() - time.time()
        t0, t1 = start + to_mono, end + to_mono
        if name != "xla.compile":
            _trace.interval(name, t0, t1, **attrs)
            return
        seen, _compiling.v = getattr(_compiling, "v", None), None
        attrs["cache"] = (seen[0] if seen is not None
                          and start <= seen[1] <= end else "off")
        _trace.interval(name, t0, t1, **attrs)
        _trace.flight_interval(name, t0, t1, attrs)
    except Exception:  # noqa: BLE001 — a listener must not fail the compile
        pass
    if name == "xla.compile":
        with _dispatch_lock:
            targets = list(_active_sentinels)
        for s in targets:
            s._on_compile_event()


def _ensure_dispatcher() -> bool:
    """Register the process-wide listeners once (they are never removed:
    they forward to the currently-open sentinels only)."""
    global _dispatch_installed
    with _dispatch_lock:
        if _dispatch_installed:
            return True
        if not monitoring_available():
            return False
        from jax import monitoring

        for name in _COMPILE_SPANS.values():
            _trace._rings.ring(name)
        monitoring.register_event_time_span_listener(_on_time_span)
        monitoring.register_event_listener(_on_event)
        monitoring.register_scalar_listener(_on_scalar)
        _dispatch_installed = True
        return True


def install_runtime_spans() -> None:
    """Record every garbage collection and every trace, lowering and XLA
    compile from now on, in the span rings (idempotent; no switch). Called
    by ``utils/compile_cache.enable_compilation_cache`` before the backend
    exists, and by ``tools/serve_lm.build_stack`` and
    ``parallel/data_parallel.build_lm_train_step`` for callers that skipped
    it. Touches no backend."""
    _trace.install_gc_spans()
    _ensure_dispatcher()


class RecompileSentinel:
    """Counts XLA compile events at runtime and alerts on any after warmup.

    Metrics (on ``registry``, process default when None):

    * ``xla_compile_events_total`` — every compile seen since install.
    * ``recompile_events_total`` — compiles AFTER :meth:`mark_warm`; the
      zero-recompile invariant says this stays 0 forever, so the default
      serving SLO rule breaches on value > 0.

    ``mode`` is ``"listener"`` when the jax.monitoring dispatcher is live
    (process-wide events), ``"poll"`` when falling back to cache-size
    deltas fed through :meth:`poll`. In listener mode ``poll()`` is a
    no-op so the two signals never double count.
    """

    def __init__(self, registry=None, *, use_listener: bool = True):
        reg = registry if registry is not None else _registry.get_registry()
        self._compiles = reg.counter(
            "xla_compile_events_total",
            "XLA compilations observed by the recompile sentinel.")
        self._post_warm = reg.counter(
            "recompile_events_total",
            "XLA compilations observed AFTER warmup — must stay 0.")
        self._lock = threading.Lock()
        self._warm = False
        self._poll_base: int | None = None
        self.mode = "poll"
        if use_listener and _ensure_dispatcher():
            self.mode = "listener"
            with _dispatch_lock:
                _active_sentinels.append(self)

    # -- signal paths -----------------------------------------------------

    def _on_compile_event(self) -> None:
        with self._lock:
            warm = self._warm
        self._compiles.inc()
        if warm:
            self._post_warm.inc()

    def poll(self, compile_count: int) -> None:
        """Fallback feed: an externally-observed monotone compile-cache
        size (e.g. ``SlotEngine.compile_count()``). Deltas become events.
        No-op in listener mode (the listener already saw them)."""
        if self.mode == "listener":
            return
        with self._lock:
            base, self._poll_base = self._poll_base, int(compile_count)
            warm = self._warm
        if base is None:
            return
        delta = int(compile_count) - base
        if delta > 0:
            self._compiles.inc(delta)
            if warm:
                self._post_warm.inc(delta)

    def mark_warm(self) -> None:
        """Everything compiled so far was warmup; anything after this is a
        recompile (the alert condition)."""
        with self._lock:
            self._warm = True

    def close(self) -> None:
        """Detach from the process-wide dispatcher (the listener itself
        stays registered — jax 0.4.x has no unregister)."""
        with _dispatch_lock:
            if self in _active_sentinels:
                _active_sentinels.remove(self)

    # -- readout ----------------------------------------------------------

    @property
    def events_total(self) -> int:
        return int(self._compiles.value)

    @property
    def post_warm_total(self) -> int:
        return int(self._post_warm.value)
