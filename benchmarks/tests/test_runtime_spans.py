"""The readers of the spans the process records by itself (compiles and
garbage collections, ``benchmarks/runtime_spans.py``), fed hand-made rings.

    python -m pytest benchmarks/tests -q
"""

from __future__ import annotations

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import manifest, runtime_spans  # noqa: E402

NEW = ("model.setup_trace_lower_s", "model.setup_compile_s",
       "model.setup_cache_misses", "sched.gc_share", "engine.window_compiles")


@pytest.fixture()
def rings(monkeypatch):
    from distributed_tensorflow_tpu.obs import trace

    fresh = trace.SpanRings()
    monkeypatch.setattr(trace, "_rings", fresh)
    return fresh


def _install(rings):
    for name in runtime_spans.COMPILE + runtime_spans.GC:
        rings.ring(name)


def _read(name, c):
    return manifest.load_reader(name).read(c)


C = {"t_open": 100.0, "t_close": 110.0, "window_s": 10.0}


def test_union_counts_nested_and_overlapping_spans_once():
    u = runtime_spans.union_s
    assert u([], 0.0, 9.0) == 0.0
    assert u([(1.0, 5.0), (2.0, 3.0), (4.0, 6.0), (8.0, 12.0)], 0.0, 9.0) \
        == pytest.approx(5.0 + 1.0)  # 1-6, then 8-9 clipped
    assert u([(-3.0, 1.0)], 0.0, 9.0) == pytest.approx(1.0)


def test_setup_readers_on_hand_made_rings(rings):
    _install(rings)
    rec = rings.record
    # a jit that traces an inner jit: the inner trace nests in the outer
    rec("jax.trace", 10.0, 14.0, {"fun": "step"})
    rec("jax.trace", 11.0, 12.0, {"fun": "inner"})
    rec("jax.lower", 14.0, 15.5, {"fun": "jit(step)"})
    rec("xla.compile", 15.5, 45.5, {"fun": "jit(step)", "cache": "miss"})
    rec("jax.trace", 50.0, 50.5, {"fun": "add"})
    rec("xla.compile", 51.0, 51.25, {"fun": "jit(add)", "cache": "hit"})
    rec("xla.compile", 60.0, 60.5, {"fun": "jit(x)", "cache": "off"})
    # after the window opened: the check's reference compiles
    rec("jax.trace", 120.0, 121.0, {"fun": "ref"})
    rec("xla.compile", 121.0, 130.0, {"fun": "jit(ref)", "cache": "miss"})
    assert _read("model.setup_trace_lower_s", C) == pytest.approx(
        5.5 + 0.5)
    assert _read("model.setup_compile_s", C) == pytest.approx(
        30.0 + 0.25 + 0.5)
    assert _read("model.setup_cache_misses", C) == 1
    assert _read("engine.window_compiles", C) == 0


def test_window_readers_on_hand_made_rings(rings):
    _install(rings)
    rec = rings.record
    rec("py.gc.0", 99.5, 100.5, {"collected": 3, "inside": ""})  # half in
    rec("py.gc.0", 101.0, 101.25, {"collected": 0, "inside": "sched.step"})
    rec("py.gc.2", 105.0, 106.0, {"collected": 90, "inside": ""})
    rec("py.gc.1", 105.5, 105.75, {"collected": 1, "inside": ""})  # inside
    rec("py.gc.0", 111.0, 112.0, {"collected": 1, "inside": ""})  # after
    rec("xla.compile", 109.5, 111.0, {"fun": "jit(f)", "cache": "off"})
    assert _read("sched.gc_share", C) == pytest.approx(
        100.0 * (0.5 + 0.25 + 1.0) / 10.0)
    assert _read("engine.window_compiles", C) == 1
    assert _read("model.setup_compile_s", C) is None  # none before the open


def test_an_installed_hook_that_saw_nothing_reads_zero(rings):
    _install(rings)
    assert _read("sched.gc_share", C) == 0.0
    assert _read("engine.window_compiles", C) == 0
    assert _read("model.setup_compile_s", C) is None


def test_a_program_without_the_hooks_reads_as_nothing(monkeypatch, rings):
    """On the parent every reader returns None and none raises: the rings
    are there, the hooks' are not; then no rings at all."""
    from distributed_tensorflow_tpu.obs import trace

    rings.record("engine.round", 101.0, 102.0)
    assert all(_read(n, C) is None for n in NEW)
    monkeypatch.delattr(trace, "names")
    monkeypatch.delattr(trace, "closed")
    assert all(_read(n, C) is None for n in NEW)


def test_the_entries_carry_their_cells():
    m = manifest.load_manifest()
    got = {p["name"]: p for p in m["per_layer"] if p["name"] in NEW}
    assert [p["name"] for p in m["per_layer"][-len(NEW):]] == list(NEW)
    every = [w["name"] for w in m["workloads"]]
    serving = [w["name"] for w in m["workloads"]
               if not w["name"].startswith("cgpt-")]
    for name, p in got.items():
        assert p["better"] == "lower"
        if name.startswith("model.setup_"):
            assert p["moves"] == "setup_s" and p["workloads"] == every
            assert p["layer"] == "model_step"
        else:
            assert p["moves"] == "out_tok_s" and p["workloads"] == serving
