"""CPU tests of the benchmark itself. Nothing here touches a TPU: every run
is a child process held to the CPU through the harness's rehearsal flag,
which skips the look for a chip and drives the rest of a run at toy width.

    python -m pytest benchmarks/tests -q
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
sys.path.insert(0, ROOT)

from benchmarks import manifest, stats, trace_reduce, traffic  # noqa: E402


def _run(*argv, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_ENABLE_COMPILATION_CACHE="false")
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", *argv], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=timeout)
    return proc


def _result(proc):
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


# ---- the names rule (what refused PR 22) ---------------------------------


def test_check_passes():
    assert manifest.check() == []


def test_check_cli_exit_code():
    assert _run("--check").returncode == 0


@pytest.mark.parametrize("key,value,needle", [
    ("layer", "engine round", "layer"),
    ("layer", "engine_rounds", "is not one of"),
    ("unit", "tokens per second", "unit"),
    ("moves", "no_such_metric", "moves"),
    ("name", "a name", "name"),
])
def test_check_names_the_offending_key(tmp_path, key, value, needle):
    shutil.copytree(os.path.join(ROOT, "benchmarks"),
                    tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    m = manifest.load_manifest()
    m["per_layer"][0][key] = value
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(m))
    problems = manifest.check(str(tmp_path))
    assert problems and any(needle in p for p in problems)


def test_check_refuses_two_four_chip_cells(tmp_path):
    shutil.copytree(os.path.join(ROOT, "benchmarks"),
                    tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    m = manifest.load_manifest()
    for w in m["workloads"]:
        w["chips"] = 4
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(m))
    assert any("4 chips" in p for p in manifest.check(str(tmp_path)))


def test_no_tpu_means_no_result():
    """Without --rehearse-cpu a run on a machine with no TPU exits non-zero
    and prints no result line."""
    m = manifest.load_manifest()
    proc = _run("--workload", m["workloads"][0]["name"], "--seed", "1",
                "--seconds", "1", "--trace", "0")
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout


# ---- the yardstick's arithmetic -------------------------------------------


def test_percentile_and_spread():
    assert stats.percentile([], 95) is None
    assert stats.percentile([3.0], 95) == 3.0
    assert stats.percentile(range(101), 95) == 95.0
    assert stats.spread([1.0, 1.0, 1.0, 1.0, 1.0, 1.0]) == 0.0


def test_every_seed_gets_the_same_sizes():
    spec = manifest.load_json("benchmarks/traffic/complete-open.json")
    spec["rate_per_s"] = 3.0
    a = traffic.serve_plan(spec, 1, 20, 1000)
    b = traffic.serve_plan(spec, 2 ** 31 + 9, 20, 1000)
    sizes = lambda p: sorted((len(r["prompt"]), r["max_new_tokens"])
                             for r in p["window"])
    assert sizes(a) == sizes(b)
    assert [r["prompt"] for r in a["window"]] != [
        r["prompt"] for r in b["window"]]
    assert all(a["lead_s"] <= r["due_s"] < a["lead_s"] + 20
               for r in a["window"])
    gaps = traffic.exp_gaps(100, 4.0)
    assert abs(gaps.sum() - 25.0) < 1e-9


def test_zipf_counts_are_exact():
    c = traffic.zipf_counts(64, 1.0, 180)
    assert c.sum() == 180 and c[0] == max(c) and c[0] > 5 * c[-1]


# ---- what out_tok_s counts, on made-up timelines (no chip, no child) -------
# (t_ref, asked, token_times); the window is [10, 20).

LEAD = (8.0, 6, [9.0, 9.5, 10.0, 10.5, 11.0, 11.5])  # 4 tokens carried in
OWN = (12.0, 5, [12.5, 13.0, 13.5, 14.0, 14.5])
LATE = (19.0, 6, [19.4, 19.8, 20.0, 20.4, 20.8, 21.2])  # 4 cut at the close


def test_closed_loop_counts_every_token_in_the_window():
    got = stats.window_tokens([LEAD, OWN, LATE], 10.0, 20.0, "closed")
    assert got["out_tokens"] == got["delivered_tokens"] == 4 + 5 + 2
    assert got["carried_in_tokens"] == 4
    assert got["cut_at_close_tokens"] == 4
    assert got["offered_tok_s"] is None  # a closed loop offers nothing


def test_open_loop_counts_the_windows_own_requests():
    got = stats.window_tokens([LEAD, OWN, LATE], 10.0, 20.0, "open")
    assert got["out_tokens"] == 5 + 2  # not the lead's 4, not LATE's last 4
    assert got["delivered_tokens"] == 11
    assert got["carried_in_tokens"] == 4
    assert got["cut_at_close_tokens"] == 4
    assert got["offered_tok_s"] == pytest.approx((5 + 6) / 10.0)
    # a request due at the close, or never sent, is nobody's
    more = [(20.0, 3, [20.1]), (None, 3, [15.0])]
    assert stats.window_tokens([OWN] + more, 10.0, 20.0, "open") == dict(
        got, out_tokens=5, delivered_tokens=6, carried_in_tokens=0,
        cut_at_close_tokens=0, offered_tok_s=0.5)


def test_window_tokens_refuses_an_unknown_loop():
    with pytest.raises(ValueError):
        stats.window_tokens([OWN], 10.0, 20.0, "half-open")


# The cell's real schedule (the same for every seed) under a server of a
# given speed: first token ``first_s`` after the due time, then one every
# ``gap_s``. Slowest first.
TIMELINES = [(0.3, 0.077), (0.2, 0.044), (0.1, 0.015), (0.0, 0.0)]
WINDOW_S = 45.0


def _complete_open_timeline(first_s, gap_s):
    spec = manifest.load_json("benchmarks/traffic/complete-open.json")
    plan = traffic.serve_plan(spec, 1, WINDOW_S, 1000)
    reqs = [(r["due_s"], r["max_new_tokens"],
             [r["due_s"] + first_s + i * gap_s
              for i in range(r["max_new_tokens"])])
            for r in plan["lead"] + plan["window"]]
    t_open = plan["lead_s"]
    return stats.window_tokens(reqs, t_open, t_open + WINDOW_S, plan["loop"])


def test_a_faster_server_never_reads_fewer_tokens_in_the_open_cell():
    """What refused PR 27: on this cell's schedule the count of every token
    in the window (the lead's leftovers too) FALLS as the server gets
    faster; the count of the window's own requests rises to what they ask
    for, 3878 tokens or 86.18 a second, and never passes it."""
    got = [_complete_open_timeline(*t) for t in TIMELINES]
    own = [g["out_tokens"] for g in got]
    old = [g["delivered_tokens"] for g in got]
    assert own == sorted(own) and own[0] < own[-1] == 3878
    assert old == sorted(old, reverse=True) and old[0] > old[-1] == 3878
    assert all(g["offered_tok_s"] * WINDOW_S == pytest.approx(3878)
               for g in got)
    assert got[-1]["carried_in_tokens"] == got[-1]["cut_at_close_tokens"] == 0


@pytest.mark.parametrize("first_s,gap_s", TIMELINES)
def test_offered_is_counted_plus_cut_at_the_close(first_s, gap_s):
    g = _complete_open_timeline(first_s, gap_s)
    assert g["offered_tok_s"] * WINDOW_S == pytest.approx(
        g["out_tokens"] + g["cut_at_close_tokens"])
    # and the old reading above what is asked for is the lead's backlog
    # less what the close cut
    assert g["delivered_tokens"] - 3878 == (
        g["carried_in_tokens"] - g["cut_at_close_tokens"])


# ---- the trace reduction, on a small recorded trace ----------------------


def test_trace_reduction_on_recorded_trace():
    trace = trace_reduce.load_recorded(
        os.path.join(DATA, "trace_small.json.gz"))
    expect = json.load(open(os.path.join(DATA, "trace_small.expect.json")))
    red = trace_reduce.reduce(trace)
    assert red["devices"] == expect["devices"]
    assert red["busy_s"] == pytest.approx(expect["busy_s"], rel=1e-9)
    assert red["window_s"] == pytest.approx(expect["window_s"], rel=1e-9)
    assert 0.0 < red["busy_s"] <= red["window_s"]
    for name, calls in expect["module_calls"].items():
        assert red["module_calls"][name] == calls
    assert len(red["breakdown"]["device_ops"]) <= 10
    assert len(red["breakdown"]["idle_gaps"]) <= 10
    owners = {k for k, _ in red["breakdown"]["idle_gaps"]}
    assert owners & set(expect["gap_owners"])


def test_trace_reduction_synthetic():
    """Two devices, overlapping ops, a host span over the one idle gap."""
    ev = lambda n, s, d: [n, s, d]
    trace = {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [
                ev("%fusion.1 = f32[8] fusion(...)", 0, 400),
                ev("%all-reduce.2 = f32[8] all-reduce(...)", 300, 300),
                ev("%fusion.3 = f32[8] fusion(...)", 800, 200)]},
            {"name": "XLA Modules", "events": [ev("jit_step_fn(1)", 0, 1000)]},
        ]},
        {"name": "/device:TPU:1", "lines": [
            {"name": "XLA Ops", "events": [
                ev("%fusion.1 = f32[8] fusion(...)", 0, 1000)]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python", "events": [ev("bench:engine.step", 550, 300)]},
        ]},
    ]}
    red = trace_reduce.reduce(trace)
    assert red["window_s"] == pytest.approx(1000e-9)
    assert red["busy_s"] == pytest.approx((800 + 1000) / 2 * 1e-9)
    assert red["breakdown"]["idle_gaps"][0] == ["engine.step",
                                                pytest.approx(200e-9)]
    assert red["module_calls"]["jit_step_fn"] == 1


# ---- one cell of each kind end to end, and the faults --------------------


def _cells(kind):
    m = manifest.load_manifest()
    kinds = {c["name"]: manifest.load_json(c["file"])["kind"]
             for c in m["configs"]}
    return [w["name"] for w in m["workloads"] if kinds[w["config"]] == kind]


def test_rehearsal_runs_a_serving_cell_end_to_end():
    for cell in _cells("serve")[:1]:
        out = _result(_run("--workload", cell, "--seed", str(2 ** 31 + 77),
                           "--seconds", "3", "--trace", "1",
                           "--rehearse-cpu"))
        assert set(out) >= {"correct", "attempted", "failed", "metrics",
                            "device", "compared"}
        assert list(out)[-1] == "compared"
        assert out["correct"] is False  # a rehearsal is never a result
        assert out["device"]["platform"] == "cpu"
        assert out["attempted"] > 0 and out["failed"] == 0
        assert out["compared"]["served_gap_max"]["ok"]
        assert out["compared"]["compiles_in_window"]["value"] == 0
        # every metric of this cell that needs no device trace is read
        want = {p["name"] for p in manifest.load_manifest()["per_layer"]
                if cell in p["workloads"] and p["source"] != "device_trace"}
        assert want and want <= set(out["metrics"]), want - set(out["metrics"])


def test_rehearsal_reports_the_end_to_end_metrics():
    for cell in _cells("serve")[-1:]:
        seconds = 3
        proc = _run("--workload", cell, "--seed", "5", "--seconds",
                    str(seconds), "--trace", "0", "--rehearse-cpu")
        out = _result(proc)
        assert "setup_s" in out["metrics"] and "out_tok_s" in out["metrics"]
        assert all(v["value"] > 0 for v in out["metrics"].values())
        # the open-loop cell says what out_tok_s counted and what it left out
        extra = json.loads(proc.stdout.strip().splitlines()[-2])["extra"]
        assert extra["offered_tok_s"] >= out["metrics"]["out_tok_s"]["value"]
        assert extra["out_tokens"] == pytest.approx(
            seconds * out["metrics"]["out_tok_s"]["value"])
        assert extra["carried_in_tokens"] >= 0
        assert extra["cut_at_close_tokens"] == pytest.approx(
            seconds * extra["offered_tok_s"] - extra["out_tokens"])
        assert extra["delivered_tokens"] == (
            extra["out_tokens"] + extra["carried_in_tokens"])


def test_fault_token_altered_is_not_correct():
    for cell in _cells("serve")[:1]:
        out = _result(_run("--workload", cell, "--seed", "6", "--seconds",
                           "2", "--trace", "0", "--rehearse-cpu",
                           "--fault", "token_altered"))
        assert not out["compared"]["served_gap_max"]["ok"]
        assert out["correct"] is False


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_training_faults_are_not_correct(fault):
    cells = _cells("train")
    if not cells:
        pytest.skip("no training cell in the manifest")
    out = _result(_run("--workload", cells[0], "--seed", "7", "--seconds",
                       "1", "--trace", "0", "--rehearse-cpu",
                       "--fault", fault))
    bad = [k for k, v in out["compared"].items() if not v["ok"]]
    assert bad, out["compared"]
    if fault == "state_unchanged":
        assert out["compared"]["delta_norm_gap"]["value"] == pytest.approx(1)


def test_training_rehearsal_sound_run_passes_its_numbers():
    cells = _cells("train")
    if not cells:
        pytest.skip("no training cell in the manifest")
    out = _result(_run("--workload", cells[0], "--seed", "8", "--seconds",
                       "1", "--trace", "0", "--rehearse-cpu"))
    assert all(v["ok"] for v in out["compared"].values()), out["compared"]
    assert out["correct"] is False


# ---- the controls, through the harness's own comparison --------------------
# The rehearsal's size (the configuration file's ``toy``) is one a test run
# can hold and at which the control still separates at the CELL's limit.


@pytest.mark.parametrize("seed", [1, 2, 5])
def test_serving_control_int8_is_not_correct(seed):
    """``--control int8`` puts the reference in int8 in the program's place:
    the cell's own number at the cell's own limit fails it, and the same
    line holds the program's reading, which that limit passes."""
    cell = _cells("serve")[0]
    limit = manifest.load_json(f"benchmarks/cells/{cell}.json")["limits"][
        "served_noise_scale"]
    proc = _run("--workload", cell, "--seed", str(seed), "--seconds", "3",
                "--trace", "0", "--rehearse-cpu", "--control", "int8")
    out = _result(proc)
    held = out["compared"]["served_noise_scale"]
    assert held["limit"] == limit and not held["ok"], held
    assert out["correct"] is False
    extra = json.loads(proc.stdout.strip().splitlines()[-2])["extra"]
    assert extra["control_gaps"]["noise_scale"] == held["value"]
    assert extra["served_gaps"]["noise_scale"] <= limit, extra["served_gaps"]


def test_training_control_bf16_state_is_not_correct():
    cells = _cells("train")
    if not cells:
        pytest.skip("no training cell in the manifest")
    proc = _run("--workload", cells[0], "--seed", "9", "--seconds", "1",
                "--trace", "0", "--rehearse-cpu", "--control", "bf16")
    out = _result(proc)
    held = out["compared"]["delta_norm_gap"]
    assert not held["ok"] and held["value"] > held["limit"], held
    extra = json.loads(proc.stdout.strip().splitlines()[-2])["extra"]
    assert extra["program"]["delta_norm_gap"] <= held["limit"]


# ---- data-driven: a new cell edits no file that is there -------------------


def test_a_new_cell_needs_no_edit_of_a_reader(tmp_path):
    """A later PR's cell that reports metrics the benchmark already has is
    one manifest entry plus its own traffic and cell files: the readers
    state nothing about which cells report them."""
    shutil.copytree(os.path.join(ROOT, "benchmarks"),
                    tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    m = manifest.load_manifest()
    old = m["workloads"][0]
    new = dict(old, name=old["name"] + ".b", traffic=old["traffic"] + "-b")
    m["workloads"].append(new)
    for e in m["end_to_end"] + m["per_layer"]:
        if old["name"] in e.get("workloads", []):
            e["workloads"].append(new["name"])
    t = manifest.load_json(f"benchmarks/traffic/{old['traffic']}.json")
    t["name"] = new["traffic"]
    (tmp_path / "benchmarks" / "traffic" / (new["traffic"] + ".json")
     ).write_text(json.dumps(t))
    shutil.copy(os.path.join(ROOT, "benchmarks", "cells",
                             old["name"] + ".json"),
                tmp_path / "benchmarks" / "cells" / (new["name"] + ".json"))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(m))
    assert manifest.check(str(tmp_path)) == []


def test_readers_are_a_docstring_and_read():
    m = manifest.load_manifest()
    for p in m["per_layer"]:
        mod = manifest.load_reader(p["name"])
        assert mod.__doc__ and callable(mod.read)
        assert not {"LAYER", "UNIT", "SOURCE", "MOVES", "BETTER",
                    "WORKLOADS"} & set(vars(mod))


# ---- the check's padded shapes do not move its numbers ----------------------

TEST_SIZE = {"vocab_size": 512, "d_model": 64, "num_heads": 4,
             "num_kv_heads": 2, "num_layers": 2, "d_ff": 128,
             "max_seq_len": 256, "attention_window": 256, "position": "rope",
             "rope_theta": 999999.44, "use_bias": True}


def test_gap_rows_do_not_depend_on_the_padded_shape():
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import serve_cell, weights

    params = weights.make_params(TEST_SIZE, 3, jnp.bfloat16)
    rng = np.random.default_rng(3)
    reqs = []
    for n_prompt, n_out in ((20, 9), (100, 17), (200, 30)):
        r = serve_cell.Req({"prompt": tuple(
            int(x) for x in rng.integers(0, 512, n_prompt))})
        r.tokens = [int(x) for x in rng.integers(0, 512, n_out)]
        reqs.append(r)
    a = serve_cell._gap_rows(params, TEST_SIZE, reqs, 384)  # 128, 128, 256
    b = serve_cell._gap_rows(params, TEST_SIZE, reqs, 768)  # 256 for all
    for key in ("margin", "served"):
        assert [len(x) for x in a[key]] == [9, 17, 30]
        for x, y in zip(a[key], b[key]):
            np.testing.assert_allclose(x, y, atol=2e-5)
    assert max(float(x.max()) for x in a["served"]) > 1.0  # random tokens
