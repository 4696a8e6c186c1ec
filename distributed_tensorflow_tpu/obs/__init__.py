"""Unified observability: metrics registry, span tracing, flight recorder.

The reference's only telemetry was ``time.time()`` deltas printed to stdout
and ``tf.summary`` events (``demo1/train.py:151-164``). The reproduction had
outgrown that into scattered islands — ``utils/summary.py`` TensorBoard
events, ``utils/profiler.py`` XPlanes, ``serve/metrics.py`` histograms,
``train/checkpoint.py``'s ``stall_seconds`` — with no single registry, no
scrape surface, and no crash-time record. This package is the one layer they
all report into:

* :mod:`registry <.registry>` — thread-safe process-wide Counter / Gauge /
  Histogram families (Prometheus-style pull metrics). ``serve/metrics.py``
  is built on it; the train loops publish their step-time decomposition
  (data-wait vs device compute vs checkpoint stall), rates, and
  ``skipped_nonfinite`` into it.
* :mod:`trace <.trace>` — Dapper-style context-manager spans, the
  program's one span mechanism: a closed span lands in a bounded ring per
  span name (``closed(name, t_lo, t_hi)`` reads them back), in the
  profiler's trace when a session is open, and — unless it closes every
  serving round — in the flight recorder with parent/child nesting, wall +
  monotonic clocks, and the process index.
* :mod:`recorder <.recorder>` — a fixed-size in-memory ring buffer of the
  last N spans/events, dumped to JSONL on preemption, rollback, or any
  unhandled exception, so every crash ships its timeline.
* :mod:`export <.export>` — Prometheus text exposition, JSONL snapshots,
  and a bridge into the repo's own ``SummaryWriter``; wired into
  ``serve/server.py`` as ``/metrics`` and into the tool CLIs via
  ``--obs_dir``.
* :mod:`aggregate <.aggregate>` — cross-process merge of per-process
  registries into one fleet view (counters sum, gauges get a ``process``
  label + min/max/sum rollups, histograms merge buckets exactly), fed by
  atomic ``fleet_p<i>.json`` snapshots in a shared ``--obs_dir``.
* :mod:`perf <.perf>` — live MFU / tokens-per-second gauges from the
  ``utils/flops.py`` math, device-memory watermarks, and the recompile
  sentinel that turns the serving engine's zero-recompile-after-warmup
  invariant into an alerting runtime counter; and the ONE ``jax.monitoring``
  registration, ``install_runtime_spans()``, which puts every trace,
  lowering and XLA compile, and (through ``trace``) every garbage
  collection, into the span rings.
* :mod:`slo <.slo>` — declarative SLO rules (selector, aggregation,
  threshold, sustain window) evaluated on a ticker; sustained breaches
  bump ``slo_breach_total``, hit the trace + flight-recorder planes, and
  invoke registered callbacks (the autoscaling/drain hook).

Everything here is stdlib-only on the hot paths (numpy appears only in the
``SummaryWriter`` bridge; the profiler annotation of a span is resolved
lazily, in a process that already imported JAX). ``disable()`` swaps the
process default registry for a :class:`~.registry.NullRegistry`, whose
instruments are shared no-op singletons. What the instrumentation costs
where it is on is measured, not assumed: the benchmark's
``sched.metrics_sync_p50_ms`` (``benchmarks/layer_metrics/``) is the
serving round's metrics pass on the chip, and PERF.md §6 (PR 26) gives the
spans' cost from paired runs.
"""

from distributed_tensorflow_tpu.obs.aggregate import (
    FleetAggregator,
    full_snapshot,
    merge_snapshots,
    write_process_snapshot,
)
from distributed_tensorflow_tpu.obs.perf import (
    PerfGauges,
    RecompileSentinel,
    install_runtime_spans,
    update_memory_gauges,
)
from distributed_tensorflow_tpu.obs.recorder import (
    FlightRecorder,
    get_recorder,
    install_excepthook,
    set_dump_dir,
    set_recorder,
)
from distributed_tensorflow_tpu.obs.registry import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    NullRegistry,
    get_registry,
    set_registry,
)
from distributed_tensorflow_tpu.obs.slo import (
    SloMonitor,
    SloRule,
    default_fleet_rules,
    default_serving_rules,
    default_training_rules,
    parse_slo_flag,
    parse_slo_spec,
)
from distributed_tensorflow_tpu.obs.trace import current_span, span, trace_event

__all__ = [
    "FleetAggregator",
    "full_snapshot",
    "merge_snapshots",
    "write_process_snapshot",
    "PerfGauges",
    "RecompileSentinel",
    "install_runtime_spans",
    "update_memory_gauges",
    "SloMonitor",
    "SloRule",
    "default_fleet_rules",
    "default_serving_rules",
    "default_training_rules",
    "parse_slo_flag",
    "parse_slo_spec",
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "NullRegistry",
    "FlightRecorder",
    "get_registry",
    "set_registry",
    "get_recorder",
    "set_recorder",
    "set_dump_dir",
    "install_excepthook",
    "span",
    "trace_event",
    "current_span",
    "disable",
    "enable",
]


def disable() -> None:
    """Swap the process default registry for shared no-op instruments.
    Every call site that resolved its instruments from ``get_registry()``
    AFTER this point records nothing."""
    set_registry(NullRegistry())


def enable() -> "MetricsRegistry":
    """Install (and return) a fresh live default registry."""
    reg = MetricsRegistry()
    set_registry(reg)
    return reg
