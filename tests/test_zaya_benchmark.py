"""The benchmark's own code for the ZAYA1 cell, checked on the CPU: the
counts against the issue's arithmetic, the reference's block-wise logits
against its whole ones, its routing-fault controls, the token law of
``benchmarks/zaya_cell.py``, the kernel reader this cell adds, and the data
files against the manifest's check, their own readings and the tests' toy
size."""

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import counts_zaya, manifest, reference_zaya as ref
from benchmarks import traffic, weights_zaya, zaya_cell
from tests.test_zaya_model import TOY, tokens

ROOT = os.path.join(os.path.dirname(__file__), "..")


def _config():
    with open(os.path.join(ROOT, "benchmarks/configs/zaya1-8b.json")) as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def params():
    return weights_zaya.make_params(TOY, 7, jnp.float32)


def test_the_manifest_checks_and_holds_the_cell():
    assert manifest.check() == []
    man = manifest.load_manifest()
    cell, config, mix, cell_file = manifest.cell_files(
        man, "zaya1-8b.reason-closed")
    assert cell["chips"] == 1 and config["runner"] == "benchmarks.zaya_cell"
    assert config["reduced"] == ["num_hidden_layers"]
    assert (mix["clients"], mix["pool_requests"]) == (32, 128)
    assert config["serve_config"]["prefix_cache"] is False
    assert set(cell_file["limits"]) == {
        "served_noise_scale", "served_gap_max", "checked_tokens_min",
        "compiles_in_window"}
    for name in ("itl_p95_ms", "out_tok_s"):
        e = next(e for e in man["end_to_end"] if e["name"] == name)
        assert "zaya1-8b.reason-closed" in e["workloads"]


def test_each_limit_lies_between_the_readings_it_was_set_from():
    _, _, _, cell_file = manifest.cell_files(
        manifest.load_manifest(), "zaya1-8b.reason-closed")
    limits, read = cell_file["limits"], cell_file["readings"]
    noise = limits["served_noise_scale"]
    # Thin (PERF.md §2): 1.24 over the program's largest, 1.18 under int8's
    # smallest, since the readings of seeds 2147491051 and -52.
    assert max(read["program"]["served_noise_scale"]) * 1.15 < noise
    assert noise * 1.15 < min(read["control_int8"]["served_noise_scale"])
    assert noise < min(read["control_wrong_expert"]["served_noise_scale"])
    gap = limits["served_gap_max"]
    assert max(read["program"]["served_gap_max"]) * 2 < gap
    assert gap * 2 < min(read["control_wrong_expert"]["served_gap_max"])
    assert noise < min(read["control_later_router_zero"]["served_noise_scale"])
    assert len(read["control_wrong_expert"]["served_noise_scale"]) >= 3


def test_the_file_keeps_every_published_number_but_the_depth():
    row = next(json.loads(l) for l in open(
        "/opt/skills/guides/model-configs/architectures.jsonl")
        if l.startswith('{"name": "ZAYA1-8B"')) if os.path.exists(
        "/opt/skills/guides/model-configs/architectures.jsonl") else None
    if row is None:
        pytest.skip("no catalog on this machine")
    cfg = _config()
    assert cfg["source"] == row["source_url"]
    differs = [k for k, v in row["config"].items() if cfg.get(k) != v]
    assert differs == ["num_hidden_layers"] and cfg["num_hidden_layers"] == 20


def test_the_toy_in_the_file_is_the_tests_toy():
    cfg = _config()
    toy = dict(cfg["transformer_config"], **cfg["toy"]["transformer_config"])
    assert {k: toy[k] for k in TOY if k != "max_seq_len"} == {
        k: v for k, v in TOY.items() if k != "max_seq_len"}


def test_counts_are_the_issues_arithmetic():
    cfg = _config()["transformer_config"]
    layer = counts_zaya.shared_params(cfg) + 16 * counts_zaya.expert_params(cfg)
    assert 207.4e6 < layer < 207.7e6  # ISSUE 35: 207.5M a layer
    assert counts_zaya.expert_bytes(cfg) == 3 * 2048 * 2048 * 2
    assert counts_zaya.row_bytes(cfg, layers=1) == 1024  # 1 KiB a layer
    weights = 20 * layer * 2 + 262272 * 2048 * 2
    assert 9.36e9 < weights < 9.39e9  # ISSUE 35: 9.37 GB
    # A round: 14.0 experts a layer touched, 32 slots at a mean of 1.8k.
    b = counts_zaya.decode_round_bytes(cfg, 32 * 1800, 14.0 * 20)
    assert 9.3e9 < b < 9.7e9
    body, head = counts_zaya.matmul_params(cfg)
    assert body == 20 * (counts_zaya.shared_params(cfg) + 3 * 2048 * 2048)
    # One decode token at a context of 1000: the head is over half of it.
    f = counts_zaya.serve_flops(cfg, [], 1001, 1)
    assert 2 * head < f < 2 * (body + head) * 1.1


def test_blockwise_logits_are_the_whole_ones(params, monkeypatch):
    monkeypatch.setattr(ref.Rows, "BLOCK", 16)
    toks = tokens(50, seed=3)
    with jax.default_matmul_precision("highest"):
        whole = ref.logits(params, toks, TOY)
        rows = zaya_cell._rows_reference().logits(params, toks, TOY)
        served = jnp.asarray(tokens(50, seed=4))
        np.testing.assert_allclose(
            np.asarray(ref.gap_rows(rows, served)),
            np.asarray(ref.gap_rows(whole, served)), atol=1e-5)
        np.testing.assert_allclose(
            np.asarray(ref.margin_rows(rows)),
            np.asarray(ref.margin_rows(whole)), atol=1e-5)
    np.testing.assert_array_equal(np.asarray(rows.argmax(-1)),
                                  np.asarray(whole.argmax(-1)))


@pytest.mark.parametrize("mode", ref.ROUTING_FAULTS)
def test_a_routing_fault_moves_the_logits_and_nothing_else_is_rounded(
        params, mode):
    toks = tokens(40, seed=5)
    whole = np.asarray(ref.logits(params, toks, TOY))
    fault = np.asarray(ref.logits(params, toks, TOY, mode=mode))
    assert fault.shape == whole.shape and np.isfinite(fault).all()
    moved = np.abs(fault - whole).max(-1) > 1e-4
    assert moved.any()
    if mode == "wrong_expert":
        assert moved.all()  # every token, from the first layer on
    # One layer has no layer before: the later layers' faults are not in it.
    one = dict(TOY, num_layers=1)
    same = np.asarray(ref.logits(params, toks, one, mode=mode))
    exact = np.asarray(ref.logits(params, toks, one))
    assert (np.abs(same - exact).max() < 1e-6) == (mode != "wrong_expert")


def test_the_runner_refuses_a_file_that_is_not_top_1():
    with pytest.raises(ValueError, match="top-1"):
        zaya_cell.run({"config": {"num_experts_per_tok": 2}})


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_"),
        os.path.join(ROOT, "benchmarks/layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@pytest.mark.parametrize("trace,share", [
    ({"op_time_s": {"paged_decode_attention.3": 0.5, "fusion.1": 9.0},
      "module_calls": {"jit_step_fn": 100}}, 100.0 * 0.8 / 0.5),
    ({"op_time_s": {"fusion.1": 9.0}, "module_calls": {"jit_step_fn": 100}},
     None),
    (None, None),
], ids=["kernel-timed", "no-such-kernel", "untraced"])
def test_the_paged_kernels_share_is_its_rows_bytes_over_its_time(
        monkeypatch, trace, share):
    cfg = _config()["transformer_config"]
    row = counts_zaya.row_bytes(cfg)  # 20 KiB over the 20 layers
    rounds = [{"experts_touched": 270, "active": 32, "live_tokens": 968}] * 4
    monkeypatch.setattr(zaya_cell, "moe_rounds", lambda c, lo, hi: rounds)
    c = {"trace": trace, "t_open": 0.0, "trace_s": 1.0, "model_cfg": cfg,
         # 100 rounds of 1,000 rows take 0.8 s at this rate.
         "peaks": {"hbm_bytes_per_s": row * 1000 * 100 / 0.8}}
    got = _reader("kernels.paged_decode_roofline.zaya")(c)
    assert got is None if share is None else abs(got - share) < 1e-9


@pytest.mark.parametrize("trace,share", [
    ({"op_time_s": {"grouped_matmul f32[32,4096] custom-call": 0.6,
                    "grouped_matmul f32[32,2048] custom-call": 0.3,
                    # a prefill chunk's product: not a round's
                    "grouped_matmul f32[1024,4096] custom-call": 5.0},
      "module_calls": {"jit_step_fn": 100}}, 100.0 * 0.8 / 0.9),
    ({"op_time_s": {"ragged-dot-none f32[32,4096] custom-call": 0.6},
      "module_calls": {"jit_step_fn": 100}}, None),
    (None, None),
], ids=["kernel-timed", "a-tree-without-the-kernel", "untraced"])
def test_the_grouped_products_share_is_the_touched_experts_bytes_over_their_time(
        monkeypatch, trace, share):
    cfg = _config()["transformer_config"]
    rounds = [{"experts_touched": 270, "active": 32, "live_tokens": 968}] * 4
    monkeypatch.setattr(zaya_cell, "moe_rounds", lambda c, lo, hi: rounds)
    c = {"trace": trace, "t_open": 0.0, "trace_s": 1.0, "model_cfg": cfg,
         # 100 rounds of 270 touched experts take 0.8 s at this rate.
         "peaks": {"hbm_bytes_per_s":
                   counts_zaya.expert_bytes(cfg) * 270 * 100 / 0.8,
                   "bf16_flops": 1e30}}
    got = _reader("kernels.grouped_matmul_roofline.zaya")(c)
    assert got is None if share is None else abs(got - share) < 1e-9


def test_prompt_ids_follow_the_files_law():
    with open(os.path.join(ROOT, "benchmarks/traffic/reason-closed.json")) as fh:
        mix = json.load(fh)
    shim = zaya_cell._zipf_traffic()
    vocab = 4096
    plan = shim.serve_plan(mix, 11, 45.0, vocab)
    flat = traffic.serve_plan(mix, 11, 45.0, vocab)
    assert [len(r["prompt"]) for r in plan["pool"]] == [
        len(r["prompt"]) for r in flat["pool"]]
    assert [r["max_new_tokens"] for r in plan["pool"]] == [
        r["max_new_tokens"] for r in flat["pool"]]
    ids = np.concatenate([r["prompt"] for r in plan["pool"]])
    assert 0 <= ids.min() and ids.max() < vocab
    top = np.bincount(ids, minlength=vocab).max() / len(ids)
    harmonic = (1.0 / np.arange(1, vocab + 1)).sum()
    assert abs(top - 1.0 / harmonic) < 0.03  # Zipf(1): the first rank's share
    uniform = np.concatenate([r["prompt"] for r in flat["pool"]])
    assert np.bincount(uniform, minlength=vocab).max() / len(uniform) < 0.01
    # Lengths by the file: 128 requests, prompts 128-512, outputs 1024-3072
    # past the first round's cut.
    lens = [len(r["prompt"]) for r in plan["pool"]]
    assert len(lens) == 128 and 128 <= min(lens) and max(lens) <= 512
    outs = [r["max_new_tokens"] for r in plan["pool"][32:]]
    assert 1024 <= min(outs) and max(outs) <= 3072
    assert 1750 < np.mean(quantiles := traffic.quantile_set(
        mix["output_len"], 128)) < 1950 and len(quantiles) == 128
