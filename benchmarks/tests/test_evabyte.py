"""CPU tests of what PR 31 adds to the benchmark: EvaByte's counts against
hand-worked numbers, the plain reference's windows and chunks against a loop
over positions, the traffic file's order, the manifest's new entries, and a
rehearsal of the new cell (toy width, ``correct: false``).

    python -m pytest benchmarks/tests/test_evabyte.py -q
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import counts_evabyte as counts  # noqa: E402
from benchmarks import manifest, traffic  # noqa: E402

CELL = "evabyte.sessions-closed"
TOY = {"vocab_size": 320, "d_model": 8, "num_heads": 2, "num_layers": 3,
       "d_ff": 12, "num_pred_heads": 8, "eva_window": 8, "eva_chunk": 2,
       "rope_theta": 100000.0, "norm_eps": 1e-5, "norm_unit_offset": True}


# ---- counts, by hand ------------------------------------------------------


def test_matmul_parameters_by_hand():
    # a layer: q, k, v, o 4 x 8 x 8 = 256; gate, up, down 3 x 8 x 12 = 288
    body, head = counts.matmul_params(TOY)
    assert body == 3 * (256 + 288) == 1632
    assert head == 8 * 320 * 8 == 20480  # eight vocabularies wide
    assert counts.weight_bytes(TOY) == 2 * (1632 + 20480)
    # + the embedding 320 x 8, two norms and two pooling vectors (2 x 8
    # each: 2 heads of 4) a layer, the final norm
    assert counts.param_count(TOY) == 1632 + 20480 + 2560 + 3 * 32 + 8


def test_attended_rows_by_hand():
    # window 8, chunk 2: 4 summaries stand for a finished window
    assert [counts.attended(TOY, i) for i in (0, 7, 8, 9, 16, 23)] == [
        1, 8, 4 + 1, 4 + 2, 8 + 1, 8 + 8]
    full = {"eva_window": 2048, "eva_chunk": 16}
    assert counts.attended(full, 32767) == 2048 + 1920  # ISSUE 31's 3968
    assert counts.attended(full, 20000) == 20000 % 2048 + 1 + 128 * 9


def test_round_bytes_and_flops_by_hand():
    # a row: K and V, 8 values each, 2 B, 3 layers
    assert counts.row_bytes(TOY) == 3 * 8 * 2 * 2 == 96
    assert counts.row_bytes(TOY, layers=1) == 32
    assert counts.decode_round_bytes(TOY, 10) == 44224 + 960
    # positions 6..9 attend 7, 8, 5, 6 rows: 26 x 4 x d 8 x 3 layers
    assert counts.attn_flops_span(TOY, 6, 10) == 4 * 26 * 8 * 3
    # one request computed from 6 to 10, then 2 decode tokens over 11 rows
    want = (2 * 1632 * (4 + 2) + 2 * 20480 * (1 + 2) + 4 * 26 * 8 * 3
            + 4 * 11 * 8 * 3)
    assert counts.serve_flops(TOY, [(6, 10)], 11, 2) == want


def test_counts_at_the_published_widths_match_the_issue():
    cfg = manifest.load_json(
        "benchmarks/configs/evabyte-6.5b.json")["transformer_config"]
    body, head = counts.matmul_params(cfg)
    assert body == 8 * 202_375_168 and head == 4096 * 2560
    assert counts.row_bytes(cfg, layers=1) == 16 * 1024
    assert counts.row_bytes(cfg) == 128 * 1024
    assert round(counts.weight_bytes(cfg) / 1e9, 2) == 3.26


# ---- the reference's index arithmetic -------------------------------------


def test_reference_attention_is_the_per_position_set():
    """``reference_evabyte.attention`` (blocks of queries, masks by index
    arithmetic) against the definition, position by position: the set S_i
    written out as a Python list, one softmax over it."""
    import jax
    import jax.numpy as jnp

    from benchmarks import reference_evabyte as ref

    cfg = dict(TOY, eva_window=8, eva_chunk=2)
    t, heads, dh = 24, 2, 4
    rng = np.random.default_rng(0)
    q, k, v = (rng.normal(size=(t, heads, dh)).astype(np.float32)
               for _ in range(3))
    phi, mu = (rng.normal(size=(heads, dh)).astype(np.float32)
               for _ in range(2))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(ref.attention(*map(jnp.asarray, (q, k, v, phi, mu)),
                                       cfg))
    w, c, s = 8, 2, dh ** -0.5

    def softmax(x):
        e = np.exp(x - x.max())
        return e / e.sum()

    want = np.zeros_like(got)
    for h in range(heads):
        summaries = []
        for n in range(t // c):
            rows = slice(n * c, n * c + c)
            a = softmax(s * k[rows, h] @ phi[h])
            summaries.append((a @ k[rows, h] + mu[h], a @ v[rows, h]))
        for i in range(t):
            keys = [(k[j, h], v[j, h]) for j in range(i // w * w, i + 1)]
            keys += [summaries[n] for n in range(t // c)
                     if (n * c) // w < i // w]
            p = softmax(np.array([s * q[i, h] @ kk for kk, _ in keys]))
            want[i, h] = sum(pj * vv for pj, (_, vv) in zip(p, keys))
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_reference_blocks_divide_the_window():
    from benchmarks import reference_evabyte as ref

    assert ref.block_rows({"eva_window": 2048}) == 512
    assert ref.block_rows({"eva_window": 32}) == 32
    assert ref.block_rows({"eva_window": 1536}) == 512


# ---- the traffic file and the manifest ------------------------------------


def test_every_document_is_first_asked_by_the_first_sixteen():
    t = manifest.load_json("benchmarks/traffic/sessions-closed.json")
    plan = traffic.serve_plan(t, 1, 45.0, 320)
    pool = plan["pool"]
    assert plan["clients"] == 16 and len(pool) == 192
    assert {r["group"] for r in pool[:16]} == set(range(8))
    asked = np.bincount([r["group"] for r in pool])
    assert asked.tolist() == [24] * 8
    docs = sorted({len(r["prompt"]) for r in pool})
    assert 12288 + 128 <= docs[0] and docs[-1] <= 28672 + 512
    total = max(len(r["prompt"]) + r["max_new_tokens"] for r in pool)
    assert total <= 32768
    assert min(r["max_new_tokens"] for r in pool[16:]) >= 256


def test_the_manifest_gains_two_cells_and_five_readers_at_the_end():
    m = manifest.load_manifest()
    assert [w["name"] for w in m["workloads"]][-2:] == [
        "cgpt-590m.pretrain-dp4", CELL]
    assert m["configs"][-1]["name"] == "evabyte-6.5b"
    tail = [p["name"] for p in m["per_layer"]][-5:]
    assert tail == ["model.decode_roofline.eva", "model.serve_mfu.eva",
                    "kernels.paged_decode_roofline.eva",
                    "kv.eva_summary_row_share", "engine.window_roll_p50_ms"]
    for p in m["per_layer"][-5:]:
        assert p["workloads"] == [CELL]
    # The GPT block's counts know nothing of this model: its cell stays
    # out of their lists.
    for p in m["per_layer"]:
        if p["name"] in ("model.decode_roofline", "model.serve_mfu",
                         "model.prefill_mfu", "kv.decode_read_amplification"):
            assert CELL not in p["workloads"]
    assert manifest.check() == []


def test_the_config_file_holds_the_catalog_rows_numbers():
    """Every number of the catalog row's ``config`` under the same key;
    only ``num_hidden_layers`` differs, and ``reduced`` says so."""
    row = None
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog on this machine")
    with open(path) as fh:
        for line in fh:
            if json.loads(line)["name"] == "EvaByte":
                row = json.loads(line)
    f = manifest.load_json("benchmarks/configs/evabyte-6.5b.json")
    assert f["source"] == row["source_url"]
    differs = [k for k, v in row["config"].items() if f.get(k) != v]
    assert differs == ["num_hidden_layers"] == f["reduced"]
    assert f["published"] == {"num_hidden_layers": 32}
    tc = f["transformer_config"]
    assert (tc["d_model"], tc["num_heads"], tc["d_ff"], tc["vocab_size"],
            tc["eva_window"], tc["eva_chunk"], tc["max_seq_len"],
            tc["num_pred_heads"]) == (4096, 32, 11008, 320, 2048, 16,
                                      32768, 8)


# ---- the rehearsal --------------------------------------------------------


def _run(*argv, timeout=900):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_ENABLE_COMPILATION_CACHE="false")
    return subprocess.run(
        [sys.executable, "-m", "benchmarks.run", *argv], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=timeout)


def test_rehearsal_of_the_new_cell_is_never_correct():
    proc = _run("--workload", CELL, "--seed", "2147487901", "--seconds", "3",
                "--trace", "1", "--rehearse-cpu")
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = proc.stdout.strip().splitlines()
    line = json.loads(lines[-1])
    assert line["correct"] is False and line["device"]["platform"] == "cpu"
    assert line["compared"]["served_gap_max"]["ok"]
    assert line["compared"]["compiles_in_window"]["value"] == 0
    got = line["metrics"]
    assert got["kv.eva_summary_row_share"]["value"] > 20.0
    assert got["engine.window_roll_p50_ms"]["value"] > 0.0
    assert 0.0 < got["model.serve_mfu.eva"]["value"] < 105.0
    extra = json.loads(lines[-2])["extra"]
    assert extra["eva_window"]["rolls"] > 0
    assert extra["cold_prefill"]["chunks_in_window"] == 0
    assert extra["unmatched_prompt_tokens"]["max"] < 16  # tails alone


def test_rehearsal_token_altered_fails_its_limit():
    proc = _run("--workload", CELL, "--seed", "7", "--seconds", "2",
                "--trace", "0", "--rehearse-cpu", "--fault", "token_altered")
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert not line["compared"]["served_gap_max"]["ok"]
