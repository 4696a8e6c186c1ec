"""From the profiler's trace to numbers: device busy and idle time, time by
operation and by program, the longest idle gaps and what the host was doing
in them.

``load_xplane`` reads an ``.xplane.pb`` with nothing but JAX
(``jax.profiler.ProfileData``) into a plain form::

    {"planes": [{"name": str, "lines": [{"name": str,
                 "events": [[name, start_ns, duration_ns], ...]}]}]}

``reduce`` works on that form only, so it is checked on the CPU against the
small recorded trace kept in ``benchmarks/tests/data``. Grown from
``tools/xplane_budget.py`` (listed in PERF.md for deletion).
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench:"


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load_xplane(path: str, keep_host=lambda name: name.startswith(SPAN_PREFIX)):
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        lines = []
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            events = []
            for ev in line.events:
                if device or keep_host(ev.name):
                    events.append([ev.name, int(ev.start_ns),
                                   int(ev.duration_ns)])
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def load_recorded(path: str) -> dict:
    with gzip.open(path, "rt") as fh:
        return json.load(fh)


def short_name(name: str) -> str:
    """'%fusion.412 = f32[8,128]{1,0:T(8,128)} fusion(...)' ->
    'fusion f32[8,128]': the operation without its serial number, with the
    shape it produces, so that the thirty layers' copies of one operation
    add up under one name; where the instruction's kind is not in its name
    (a Pallas kernel is '%block_3.1 = bf16[...] custom-call(...)') the kind
    follows. 'jit_step_fn(123)' -> 'jit_step_fn'. A name already shortened
    passes through."""
    m = re.match(r"^%?([\w\-]+?)(?:\.\d+)?\s*=\s*\(?(\w+\[[\d,]*\])?", name)
    if not m:
        return name.split("(")[0].strip()[:80]
    out = (m.group(1) + " " + (m.group(2) or "")).strip()
    kind = re.search(r"[}\])]\s+([a-z][\w\-]*)\(", name)
    if kind and kind.group(1) not in m.group(1):
        out += " " + kind.group(1)
    return out


def _union(intervals):
    """Merged (start, end) list of possibly overlapping intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _covered(merged, lo, hi):
    return sum(max(0, min(e, hi) - max(s, lo)) for s, e in merged)


def reduce(trace: dict, top: int = 10) -> dict:
    """The numbers the per-layer readers use. Times in seconds; busy time
    is averaged over the device planes."""
    dev = [p for p in trace["planes"] if DEVICE_PLANE.match(p["name"])]
    if not dev:
        return {}
    spans = []
    for p in trace["planes"]:
        if DEVICE_PLANE.match(p["name"]):
            continue
        for line in p["lines"]:
            spans += [(s, s + d, n[len(SPAN_PREFIX):])
                      for n, s, d in line["events"]
                      if n.startswith(SPAN_PREFIX)]
    spans.sort()
    starts, ends = [], []
    per_dev = []
    op_time: dict = {}
    module_time: dict = {}
    module_calls: dict = {}
    for p in dev:
        lines = {l["name"]: l["events"] for l in p["lines"]}
        ops = lines.get(OPS_LINE) or lines.get(MODULES_LINE) or []
        for n, s, d in ops:
            op_time[short_name(n)] = op_time.get(short_name(n), 0) + d
        for n, s, d in lines.get(MODULES_LINE, []) if p is dev[0] else []:
            key = short_name(n)
            module_time[key] = module_time.get(key, 0) + d
            module_calls[key] = module_calls.get(key, 0) + 1
        merged = _union([(s, s + d) for _, s, d in ops])
        if merged:
            starts.append(merged[0][0])
            ends.append(merged[-1][1])
        per_dev.append(merged)
    if not starts:
        return {}
    lo, hi = min(starts), max(ends)
    if spans:  # the traced window is what the host spans and the ops span
        lo, hi = min(lo, spans[0][0]), max(hi, max(e for _, e, _ in spans))
    n_dev = len(dev)
    busy = sum(_covered(m, lo, hi) for m in per_dev) / n_dev
    gaps = []
    merged = per_dev[0]
    edges = [lo] + [x for iv in merged for x in iv] + [hi]
    for a, b in zip(edges[0::2], edges[1::2]):
        if b > a:
            gaps.append((b - a, a, b))
    by_owner: dict = {}
    for length, a, b in gaps:
        mid = (a + b) // 2
        owner = "unowned"
        best = None
        for s, e, n in spans:
            if s <= mid < e and (best is None or e - s < best):
                owner, best = n, e - s
        by_owner[owner] = by_owner.get(owner, 0) + length
    ns = 1e-9
    return {
        "window_s": (hi - lo) * ns,
        "busy_s": busy * ns,
        "devices": n_dev,
        "op_time_s": {k: v * ns / n_dev for k, v in op_time.items()},
        # programs: the first device's (every device runs the same ones)
        "module_time_s": {k: v * ns for k, v in module_time.items()},
        "module_calls": module_calls,
        "breakdown": {
            "device_ops": [[k, v * ns / n_dev] for k, v in sorted(
                op_time.items(), key=lambda kv: -kv[1])[:top]],
            "idle_gaps": [[k, v * ns] for k, v in sorted(
                by_owner.items(), key=lambda kv: -kv[1])[:top]],
        },
    }


def record_small(trace: dict, seconds: float) -> dict:
    """The first ``seconds`` of a loaded trace with shortened names: the
    small recorded trace the CPU test checks the reduction on."""
    dev = [p for p in trace["planes"] if DEVICE_PLANE.match(p["name"])]
    if not dev:
        return {"planes": []}
    lo = min(e[1] for p in dev for l in p["lines"] for e in l["events"])
    hi = lo + int(seconds * 1e9)
    planes = []
    for p in trace["planes"]:
        lines = []
        for l in p["lines"]:
            evs = [[n if n.startswith(SPAN_PREFIX) else short_name(n), s, d]
                   for n, s, d in l["events"] if lo <= s and s + d <= hi]
            if evs:
                lines.append({"name": l["name"], "events": evs})
        if lines:
            planes.append({"name": p["name"], "lines": lines})
    return {"planes": planes}
