"""The readers of the program's own spans, fed hand-made rings: each number
checked, ``engine.host_gap_p50_ms`` across a round boundary above all.

    python -m pytest benchmarks/tests -q
"""

from __future__ import annotations

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import manifest, program_spans  # noqa: E402

NEW = ("sched.between_rounds_p50_ms", "sched.deliver_p50_ms",
       "sched.metrics_sync_p50_ms", "engine.host_gap_p50_ms",
       "engine.dispatch_p50_ms", "sched.queue_wait_p95_ms",
       "engine.warmup_s")


def _round(rings, t, *, active=4, completed=0, chunks=0, admit=0.001,
           sync=0.0005, dispatch=0.002, wait=0.030, readback=0.003,
           deliver=0.004, complete=0.0002):
    """One scheduler step starting at ``t`` as the program would record
    it; returns the time the step ends. The round's own overhead around
    its children is 0.1 ms at each end."""
    t0 = t
    rings.record("sched.admit", t, t + admit, {"admitted": 0})
    t += admit
    rings.record("sched.metrics_sync", t, t + sync)
    t += sync
    r0 = t
    t += 0.0001
    if chunks:
        rings.record("engine.prefill_chunk", t, t + 0.1,
                     {"offset": 0, "width": 8, "final": False})
        t += 0.1
    rings.record("engine.dispatch", t, t + dispatch)
    t += dispatch
    rings.record("engine.wait", t, t + wait)
    t += wait
    rings.record("engine.readback", t, t + readback)
    t += readback + 0.0001
    rings.record("engine.round", r0, t, {"active": active, "live_tokens": 9,
                                         "chunks_run": chunks})
    rings.record("sched.deliver", t, t + deliver, {"produced": active})
    t += deliver
    rings.record("sched.complete", t, t + complete)
    t += complete
    rings.record("sched.step", t0, t, {"completed": completed})
    return t


@pytest.fixture()
def rings(monkeypatch):
    from distributed_tensorflow_tpu.obs import trace

    fresh = trace.SpanRings()
    monkeypatch.setattr(trace, "_rings", fresh)
    return fresh


def _read(name, c):
    return manifest.load_reader(name).read(c)


def test_readers_on_hand_made_rings(rings):
    """Seven plain rounds, one round with a prefill chunk, one that drains
    every slot before an idle second, and one cut by the window's edge."""
    rings.record("engine.warmup", 80.0, 92.5, {"programs": 6})
    t = 100.0
    for _ in range(4):
        t = _round(rings, t) + 0.00005  # the loop's own 50 us
    t = _round(rings, t, chunks=1) + 0.00005
    for _ in range(2):
        t = _round(rings, t) + 0.00005
    t = _round(rings, t, active=1, completed=1) + 1.0  # then idle for 1 s
    t = _round(rings, t, dispatch=0.004) + 0.00005
    t_close = t + 0.01
    _round(rings, t)  # ends beyond the close: cut
    for i, w in enumerate((0.010, 0.020, 0.030, 0.040, 2.0)):
        rings.record("sched.queue_wait", 100.0 + 0.1 * i - w,
                     100.0 + 0.1 * i, {"lane": 1, "prompt_len": 64})
    rings.record("sched.queue_wait", 90.0, 99.0, {"lane": 1})  # before
    c = {"t_open": 100.0, "t_close": t_close}
    assert program_spans.same_clock()

    rs = program_spans.rounds(c)
    assert len(rs) == 9 and [r["left"] for r in rs] == [4] * 7 + [0, 4]
    assert [bool(r["chunks_run"]) for r in rs] == [False] * 4 + [True] + [
        False] * 4
    # Between rounds: deliver + complete + the loop + admit + sync; the
    # idle second is no host time.
    between = 0.004 + 0.0002 + 0.00005 + 0.001 + 0.0005
    assert len(program_spans.between_rounds_s(rs)) == 7
    assert _read("sched.between_rounds_p50_ms", c) == pytest.approx(
        1e3 * between)
    # The host gap crosses the round boundary: the readback of round n, the
    # scheduler between the rounds, the dispatch of round n+1 (and the
    # round's own 0.1 ms at either end); the two pairs
    # around the chunk and the pair across the idle second are left out.
    gap = 0.003 + 0.0001 + between + 0.0001 + 0.002
    gaps = program_spans.host_gaps_s(rs)
    assert len(gaps) == 5 and gaps == pytest.approx([gap] * 5)
    assert _read("engine.host_gap_p50_ms", c) == pytest.approx(1e3 * gap)
    with_chunks = program_spans.host_gaps_s(rs, chunks=True)
    assert len(with_chunks) == 7 and max(with_chunks) == pytest.approx(
        gap + 0.1)
    assert _read("engine.dispatch_p50_ms", c) == pytest.approx(2.0)
    assert _read("sched.deliver_p50_ms", c) == pytest.approx(4.0)
    assert _read("sched.metrics_sync_p50_ms", c) == pytest.approx(0.5)
    # Readback + between + dispatch make the gap up, but for the round's
    # own 0.2 ms: the sum the acceptance asks for, within its 10%.
    parts = (3.0 + _read("sched.between_rounds_p50_ms", c)
             + _read("engine.dispatch_p50_ms", c))
    assert parts == pytest.approx(1e3 * gap, rel=0.1)
    # Five waits ended in the window, the one before it is left out.
    assert _read("sched.queue_wait_p95_ms", c) == pytest.approx(
        1e3 * (0.040 + 0.8 * (2.0 - 0.040)))
    assert _read("engine.warmup_s", c) == pytest.approx(12.5)


def test_a_program_without_rings_reads_as_nothing(monkeypatch, rings):
    """On a commit older than the spans every new reader returns None and
    none raises: the line leaves the metric out."""
    from distributed_tensorflow_tpu.obs import trace

    c = {"t_open": 0.0, "t_close": 10.0}
    assert all(_read(n, c) is None for n in NEW)  # rings there, but empty
    monkeypatch.delattr(trace, "closed")
    assert all(_read(n, c) is None for n in NEW)
    assert program_spans.rounds(c) is None


def test_another_clock_reads_as_nothing(monkeypatch, rings):
    rings.record("sched.deliver", 1.0, 2.0)
    c = {"t_open": 0.0, "t_close": 10.0}
    assert _read("sched.deliver_p50_ms", c) == pytest.approx(1000.0)
    monkeypatch.setattr(program_spans, "same_clock", lambda: False)
    assert _read("sched.deliver_p50_ms", c) is None


def test_the_new_entries_are_appended_with_their_cells():
    m = manifest.load_manifest()
    tail = m["per_layer"][-len(NEW):]
    assert [p["name"] for p in tail] == list(NEW)
    both = ["sc2-3b.decode-closed", "sc2-3b.complete-open"]
    for p in tail:
        assert p["source"] == "program_span" and p["better"] == "lower"
        want = both[1:] if p["name"] == "sched.queue_wait_p95_ms" else both
        assert p["workloads"] == want
