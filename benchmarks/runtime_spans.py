"""The spans the process records by itself (``obs.install_runtime_spans``):
``jax.trace``, ``jax.lower`` and ``xla.compile`` for every trace, lowering
and XLA compile (``xla.compile`` with ``cache`` "hit", "miss" or "off"), and
``py.gc.<generation>`` for every garbage collection. What the per-layer
readers of set-up and of host stalls share.

A program without them (a commit older than the hooks) reads as nothing:
every function here returns None there, and never raises. The hooks make
their rings when they are installed, so an installed hook that saw nothing
reads 0, not None.
"""

from __future__ import annotations

from benchmarks import program_spans as ps

COMPILE = ("jax.trace", "jax.lower", "xla.compile")
GC = ("py.gc.0", "py.gc.1", "py.gc.2")


def installed(names) -> bool:
    """Whether the program has made the rings of ``names``."""
    try:
        from distributed_tensorflow_tpu.obs import trace
    except ImportError:
        return False
    have = getattr(trace, "names", None)
    return have is not None and set(names) <= set(have())


def union_s(spans, lo: float, hi: float) -> float:
    """Seconds of ``[lo, hi]`` that at least one of ``spans`` covers (nested
    spans count once)."""
    total, end = 0.0, lo
    for t0, t1 in sorted((max(r[0], lo), min(r[1], hi)) for r in spans):
        if t1 <= end:
            continue
        total += t1 - max(t0, end)
        end = t1
    return total


def before_open(c: dict, names):
    """The records of ``names`` that ended before the window opened: the
    set-up's. None where the program has none of them."""
    if not installed(names):
        return None
    out = []
    for name in names:
        recs = ps.records(name, float("-inf"), c["t_open"])
        if recs is None:
            return None
        out += [r for r in recs if r[1] <= c["t_open"]]
    return out or None


def setup_union_s(c: dict, names):
    recs = before_open(c, names)
    return None if recs is None else union_s(
        recs, min(r[0] for r in recs), c["t_open"])


def in_window(c: dict, names):
    """The records of ``names`` that overlap the window; None where the
    program does not record them."""
    if not installed(names):
        return None
    out = []
    for name in names:
        recs = ps.in_window(c, name)
        if recs is None:
            return None
        out += recs
    return out
