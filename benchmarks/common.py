"""What every cell runner shares: spans, the device record, the result."""

from __future__ import annotations

import collections
import contextlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def process_start_time() -> float:
    """``time.time()`` at which this process started, from /proc; the
    import time of this module where /proc is not there."""
    try:
        with open("/proc/self/stat") as fh:
            ticks = int(fh.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as fh:
            uptime = float(fh.read().split()[0])
        return time.time() - (uptime - ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return _IMPORTED_AT


_IMPORTED_AT = time.time()


class Spans:
    """Host spans taken by wrapping bound methods of the instances the
    benchmark built. Each record is ``(t0, t1, extra...)`` on
    ``time.perf_counter``. With ``annotate`` on, each span is also written
    into the profiler's trace under ``bench:<name>`` so that idle gaps of
    the device can be attributed on the trace's own clock."""

    def __init__(self):
        self.rec = collections.defaultdict(list)
        self.annotate = False

    def wrap(self, obj, attr: str, name: str, before=None, after=None):
        fn = getattr(obj, attr)
        spans = self

        def wrapper(*args, **kwargs):
            pre = before(obj, args, kwargs) if before else ()
            ctx = contextlib.nullcontext()
            if spans.annotate:
                import jax

                ctx = jax.profiler.TraceAnnotation("bench:" + name)
            t0 = time.perf_counter()
            with ctx:
                out = fn(*args, **kwargs)
            t1 = time.perf_counter()
            post = after(obj, args, kwargs, out) if after else ()
            spans.rec[name].append((t0, t1) + tuple(pre) + tuple(post))
            return out

        setattr(obj, attr, wrapper)

    def within(self, name: str, t_open: float, t_close: float):
        return [r for r in self.rec.get(name, ())
                if r[1] > t_open and r[0] < t_close]

    def seconds_within(self, name: str, t_open: float, t_close: float):
        return sum(min(r[1], t_close) - max(r[0], t_open)
                   for r in self.within(name, t_open, t_close))


def start_trace(spans: Spans, scratch: str) -> str:
    """Start the profiler (host annotations on, Python tracing off) into a
    directory of the run's own under ``scratch``; the spans annotate too."""
    import jax

    trace_dir = os.path.join(scratch, f"trace-{os.getpid()}")
    spans.annotate = True
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    return trace_dir


def device_record(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def memory_peak_bytes(devices) -> int:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def emit(result: dict, compared: dict) -> None:
    """The run's last lines: every number compared beside its limit on
    standard error, then the one JSON object on standard output with the
    same numbers under ``compared``, its last key."""
    for name, c in compared.items():
        print(f"compared {name}: value={c['value']!r} limit={c['limit']!r} "
              f"ok={c['ok']}", file=sys.stderr)
    sys.stderr.flush()
    line = dict(result)
    line["compared"] = compared
    print(json.dumps(line), flush=True)


def metric(value, unit: str) -> dict:
    return {"value": float(value), "unit": unit}
