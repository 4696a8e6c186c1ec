"""The program's own spans, for the per-layer readers that time a serving
round from inside.

The program records every closed span in a ring per span name
(``distributed_tensorflow_tpu/obs/trace.py``: ``closed(name, t_lo, t_hi)``
gives the ``(t0, t1, attrs)`` that overlap an interval). The rings are
process-wide and outlive the stack that ``serve_cell`` frees before the
readers run, so no runner is edited to read them. They are on
``time.monotonic`` and the benchmark's window on ``time.perf_counter``: on
Linux the same clock, which :func:`same_clock` checks.

A program without such rings (a commit older than the spans) is read as
nothing: every function here returns None there, and never raises.
"""

from __future__ import annotations

import bisect
import time

from benchmarks import stats


def same_clock() -> bool:
    a = time.get_clock_info("monotonic")
    b = time.get_clock_info("perf_counter")
    return a.implementation == b.implementation and a.monotonic and b.monotonic


def records(name: str, lo: float, hi: float):
    """The program's closed spans of ``name`` that overlap ``[lo, hi]``,
    oldest first; None where the program has no span rings or its clock is
    not the window's."""
    try:
        from distributed_tensorflow_tpu.obs import trace
    except ImportError:
        return None
    closed = getattr(trace, "closed", None)
    if closed is None or not same_clock():
        return None
    return sorted(closed(name, lo, hi), key=lambda r: r[0])


def in_window(c: dict, name: str):
    return records(name, c["t_open"], c["t_close"])


def p_ms(seconds, q: float):
    """The q-th percentile in milliseconds; None for nothing to read."""
    p = stats.percentile(seconds, q) if seconds else None
    return None if p is None else 1000.0 * p


def duration_p50_ms(c: dict, name: str):
    recs = in_window(c, name)
    return None if recs is None else p_ms([r[1] - r[0] for r in recs], 50)


def rounds(c: dict, lo=None, hi=None):
    """The window's engine rounds in order, each a dict: the round's ends
    (``t0``, ``t1``), its attributes (``active``, ``live_tokens``,
    ``chunks_run``), the ends of the ``engine.dispatch``, ``engine.wait``
    and ``engine.readback`` inside it (None for a round that ran prefill
    chunks only), and ``left``: slots still decoding when the enclosing
    ``sched.step`` returned (``active`` less its ``completed``). None where
    the program has no rings."""
    lo = c["t_open"] if lo is None else lo
    hi = c["t_close"] if hi is None else hi
    recs = records("engine.round", lo, hi)
    if recs is None:
        return None
    parts = {n: records("engine." + n, lo, hi) or []
             for n in ("dispatch", "wait", "readback")}
    steps = records("sched.step", lo, hi) or []
    step_t0 = [s[0] for s in steps]
    out = []
    for t0, t1, attrs in recs:
        if t0 < lo or t1 > hi:
            continue  # cut by an edge of the window
        r = dict(attrs or {}, t0=t0, t1=t1)
        for n, rs in parts.items():
            i = bisect.bisect_left(rs, (t0,))
            inside = i < len(rs) and rs[i][1] <= t1
            r[n] = (rs[i][0], rs[i][1]) if inside else None
        i = bisect.bisect_right(step_t0, t0) - 1
        done = 0
        if i >= 0 and steps[i][1] >= t1:
            done = (steps[i][2] or {}).get("completed", 0)
        r["left"] = r.get("active", 0) - done
        out.append(r)
    return out


def pairs(rs):
    """Consecutive rounds with slots left decoding in between: the time
    from one to the next is the host's, not a wait for a request."""
    return [(a, b) for a, b in zip(rs, rs[1:]) if a["left"] > 0]


def between_rounds_s(rs):
    return [b["t0"] - a["t1"] for a, b in pairs(rs)]


def host_gaps_s(rs, chunks: bool = False):
    """End of ``engine.wait`` of one round to the end of ``engine.dispatch``
    of the next: the stretch in which the device has nothing queued. With
    ``chunks`` false only over pairs of rounds that ran no prefill chunk (a
    chunk keeps the device busy while the host dispatches)."""
    return [b["dispatch"][1] - a["wait"][1] for a, b in pairs(rs)
            if a["wait"] and b["dispatch"]
            and (chunks or not (a.get("chunks_run") or b.get("chunks_run")))]
