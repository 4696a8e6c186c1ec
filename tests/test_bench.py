"""bench.py contract test: the driver runs `python bench.py` and parses its
stdout — exactly ONE compact JSON line (headline metric first, extra metrics
stripped to machine fields), with the FULL record written to BENCH_LAST.json.
The compact/record split exists because the r3-r5 driver records all came
back ``"parsed": null``: the detail-laden single line was long enough to be
truncated mid-JSON.

Runs in a subprocess in smoke mode (tiny shapes, CPU-runnable): XLA:CPU
compiles of the real bench shapes take minutes, and the accuracy suites are
covered by their own tests — this asserts the harness shape, not the perf.
"""

import json
import os
import subprocess
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_BENCH = os.path.join(_REPO, "bench.py")


def test_bench_emits_one_compact_json_line_and_full_record(tmp_path):
    env = dict(os.environ)
    env.update(
        # Pin the subprocess to CPU so this smoke test never takes a chip.
        JAX_PLATFORMS="cpu",
        BENCH_SMOKE="1",
        BENCH_WARMUP_STEPS="1",
        BENCH_TIMED_STEPS="4",
        BENCH_STEPS_PER_CALL="2",
        BENCH_ACC_STEPS="60",
        JAX_ENABLE_COMPILATION_CACHE="false",
        XLA_FLAGS="--xla_force_host_platform_device_count=2",
    )
    proc = subprocess.run(
        [sys.executable, _BENCH],
        cwd=str(tmp_path),  # BENCH_LAST.json lands here, not in the repo
        env=env,
        capture_output=True,
        text=True,
        # The smoke suite measures ~9.5 min on this box (the PR 14
        # kv-diet phase added four small-engine warmups); the cap is a
        # hang guard, not a perf gate.
        timeout=700,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [l for l in proc.stdout.strip().splitlines() if l.strip()]
    assert len(lines) == 1, f"expected ONE stdout line, got {len(lines)}: {lines[:3]}"
    # The driver contract: the LAST stdout line round-trips through
    # json.loads (the whole point of the compact-line fix).
    rec = json.loads(lines[-1])
    assert json.loads(json.dumps(rec)) == rec
    # Compact means parseable-under-truncation: no detail prose on stdout.
    assert len(lines[-1]) < 4096, len(lines[-1])
    assert not any("detail" in m for m in rec["extra_metrics"])
    # Smoke mode shrinks the batch to 16 and the metric name says so (the
    # real driver run on TPU reports ..._batch100).
    assert rec["metric"] == "mnist_train_steps_per_sec_per_chip_batch16"
    assert rec["unit"] == "steps/s/chip"
    assert rec["value"] > 0
    assert rec["vs_baseline_estimated"] is True
    extra = {m["metric"]: m for m in rec["extra_metrics"]}
    # Every extra bench ran without an `_error` record.
    assert not [k for k in extra if k.endswith("_error")], extra
    assert extra["lm_train_tokens_per_sec_per_chip"]["value"] > 0
    assert extra["mnist_synthetic_test_accuracy"]["value"] >= 0.5
    # ViT on the bundled REAL t10k digits; 60 smoke steps just needs to beat
    # 10-class chance convincingly (the TPU run trains 2000 and is floored
    # at 0.90 by bench.FLOORS).
    assert extra["vit_real_test_accuracy"]["value"] >= 0.3
    # The zero-stall checkpoint pipeline runs in smoke mode too: the async
    # autosave's main-thread stall is measured and must be a small fraction
    # of the blocking save (the TPU run enforces <= 0.25 via FRAC_CEILS).
    assert extra["ckpt_save_seconds_smoke"]["value"] > 0
    assert extra["ckpt_stall_seconds_smoke"]["frac"] is not None
    # The FULL record (with detail prose) lives in BENCH_LAST.json.
    full = json.loads((tmp_path / "BENCH_LAST.json").read_text())
    assert full["metric"] == rec["metric"]
    full_extra = {m["metric"]: m for m in full["extra_metrics"]}
    assert set(full_extra) == set(extra)
    assert "detail" in full_extra["ckpt_stall_seconds_smoke"]
    # CPU backend: no MFU (unknown peak) and no Mosaic kernel timings.


def test_floor_gate_flags_regressions_and_missing_metrics():
    """bench.FLOORS is a gate: a below-floor value or a MISSING floored
    metric must be reported (VERDICT r3 #1 — r3's retrain miss at 0.6481
    sat silently in the record)."""
    sys.path.insert(0, _REPO)
    import bench

    good = [{"metric": k, "value": v + 0.05} for k, v in bench.FLOORS.items()]
    good += [
        {"metric": k, "value": 1.0, "frac": v + 0.05}
        for k, v in bench.FRAC_FLOORS.items()
    ]
    good += [
        {"metric": k, "value": 1.0, "frac": v - 0.05}
        for k, v in bench.FRAC_CEILS.items()
    ]
    assert bench.enforce_floors(good) == []
    injected = [dict(m) for m in good]
    injected[0]["value"] = bench.FLOORS[injected[0]["metric"]] - 0.01
    problems = bench.enforce_floors(injected)
    assert len(problems) == 1 and injected[0]["metric"] in problems[0]
    # A floored metric that never made it into the record is a violation
    # too — a crashed accuracy bench must not read as a pass.
    assert len(bench.enforce_floors(good[1:])) == 1
    # frac floors (r5): a below-floor efficiency fraction trips even when
    # the raw value looks healthy, and a record missing the frac field
    # (e.g. a kernel timing discarded for jitter) is a violation, not a pass.
    n_ceils = len(bench.FRAC_CEILS)
    frac_bad = [dict(m) for m in good]
    frac_bad[-1 - n_ceils]["frac"] = min(bench.FRAC_FLOORS.values()) - 0.01
    assert len(bench.enforce_floors(frac_bad)) == 1
    frac_missing = [dict(m) for m in good]
    del frac_missing[-1 - n_ceils]["frac"]
    problems = bench.enforce_floors(frac_missing)
    assert len(problems) == 1 and "MISSING frac" in problems[0]
    # frac CEILINGS (the async-autosave stall ratchet): an over-ceiling
    # stall fraction trips, and a missing one is a violation, not a pass.
    ceil_bad = [dict(m) for m in good]
    ceil_bad[-1]["frac"] = max(bench.FRAC_CEILS.values()) + 0.01
    problems = bench.enforce_floors(ceil_bad)
    assert len(problems) == 1 and "ceiling" in problems[0]
    assert len(bench.enforce_floors(good[:-1])) == 1


def test_floor_gate_exits_nonzero_end_to_end(tmp_path):
    """`python bench.py` itself must exit nonzero when floors are enforced
    and violated. The headline suite records no accuracy metrics, so every
    floored metric is missing — the cheapest end-to-end injected failure."""
    env = dict(os.environ)
    env.update(
        JAX_PLATFORMS="cpu",
        BENCH_SMOKE="1",
        BENCH_SUITE="headline",
        BENCH_ENFORCE_FLOORS="1",
        BENCH_WARMUP_STEPS="1",
        BENCH_TIMED_STEPS="4",
        BENCH_STEPS_PER_CALL="2",
        JAX_ENABLE_COMPILATION_CACHE="false",
        XLA_FLAGS="--xla_force_host_platform_device_count=2",
    )
    proc = subprocess.run(
        [sys.executable, _BENCH],
        cwd=str(tmp_path), env=env, capture_output=True, text=True, timeout=560,
    )
    assert proc.returncode != 0
    assert "FLOOR VIOLATION" in proc.stderr
    # The record still prints (the driver parses stdout before rc).
    assert json.loads(proc.stdout.strip().splitlines()[-1])["metric"]
