"""The DeepSeek-V2-Lite cell's data files, counts and readers, without a
chip: the configuration's cut arithmetic, ``counts_dsv2`` against a count
by hand, the traffic file's order, and each new reader on a synthetic
context (the rounds' counters and a reduced trace as the runner hands
them)."""

import json
import os

import numpy as np
import pytest

from benchmarks import counts_dsv2, manifest, traffic, zaya_cell

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "dsv2-lite.docs-closed"


def _config():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "deepseek-v2-lite.json")) as fh:
        return json.load(fh)


FULL = _config()["transformer_config"]


def test_the_cut_holds_5_68_gb_of_weights_and_6_71_gb_of_rows():
    assert counts_dsv2.attention_params(FULL) == (
        2048 * 3072 + 2048 * 576 + 512 * 4096 + 2048 * 2048) == 13_762_560
    assert round(counts_dsv2.weight_bytes(FULL) / 1e9, 2) == 5.68
    serve = _config()["serve_config"]
    pool = counts_dsv2.pool_bytes(FULL, serve["slots"], serve["serve_max_len"],
                                  serve["page_size"], 640)
    assert round(pool / 1e9, 2) == 6.71
    assert counts_dsv2.latent_row_bytes(FULL) == 1152
    assert counts_dsv2.latent_pair_flops(FULL) == 34_816
    # The whole published model: 15.7B parameters.
    whole = dict(FULL, num_layers=27)
    assert round(counts_dsv2.weight_bytes(whole) / 2 / 1e9, 1) == 15.7


def test_counts_by_hand_at_a_toy_size():
    cfg = dict(d_model=8, num_heads=2, kv_lora_rank=4, qk_nope_head_dim=3,
               qk_rope_head_dim=2, v_head_dim=3, num_layers=3, dense_layers=1,
               d_ff=5, num_experts=4, expert_width=6, experts_per_token=2,
               shared_expert_width=7, vocab_size=11)
    attn = 8 * 2 * 5 + 8 * 6 + 4 * 2 * 6 + 2 * 3 * 8  # q, kv_a, kv_b, o
    assert counts_dsv2.attention_params(cfg) == attn == 224
    body, head = counts_dsv2.matmul_params(cfg)
    assert head == 88
    assert body == 3 * attn + 3 * 8 * 5 + 2 * (8 * 4 + 3 * 8 * 7
                                               + 2 * 3 * 8 * 6)
    assert counts_dsv2.pair_flops(cfg) == 2 * 2 * (3 + 2 + 3) * 3
    assert counts_dsv2.latent_pair_flops(cfg) == 2 * 2 * 6 + 2 * 2 * 4
    # One round: shared weights, 5 touched experts, 10 positions, 2 lanes.
    shared = (3 * attn + 3 * 8 * 5 + 2 * (8 * 4 + 3 * 8 * 7) + 88) * 2
    assert counts_dsv2.decode_round_bytes(cfg, 10, 5, 2) == (
        shared + 5 * 3 * 8 * 6 * 2 + 12 * 3 * 10 + 2 * 10 * 2 * 3 * 2)
    # A prompt of positions 0..3 and 2 decode tokens over 9 rows.
    assert counts_dsv2.serve_flops(cfg, [(0, 4)], 9, 2) == (
        2 * body * 6 + 2 * 88 * 3 + counts_dsv2.pair_flops(cfg) * (10 + 9))


def test_every_document_is_first_asked_by_the_first_thirty_two():
    with open(os.path.join(ROOT, "benchmarks", "traffic",
                           "docs-closed.json")) as fh:
        mix = json.load(fh)
    plan = traffic.serve_plan(mix, 7, 45, 102400)
    groups = [r["group"] for r in plan["pool"]]
    assert len(groups) == 64 and sorted(set(groups)) == list(range(16))
    assert all(groups.count(g) == 4 for g in range(16))
    assert set(groups[:32]) == set(range(16))


@pytest.mark.parametrize("toy", [True, False])
def test_later_laps_ask_the_documents_with_fresh_tails(toy):
    """The runner's closed loop: the first lap is the pool as it stands;
    each later lap asks the same documents with tails and outputs of the
    same lengths, its tail tokens new and the same for the same seed."""
    from benchmarks import dsv2_cell

    with open(os.path.join(ROOT, "benchmarks", "traffic",
                           "docs-closed.json")) as fh:
        mix = json.load(fh)
    vocab = 512 if toy else 102400
    plan = zaya_cell._zipf_traffic().serve_plan(mix, 7, 45, vocab, toy=toy)
    pool = plan["pool"]
    t = traffic.apply_toy(mix, toy)
    g = int(t["shared_prefix"]["groups"])
    docs = traffic.quantile_set(t["shared_prefix"]["prefix_len"], g)[
        traffic._mix(g, 29)]

    def asked(seed):
        got = []

        class Record(dsv2_cell.fresh_tails(mix, seed, vocab, toy)):
            def _submit_and_follow(self, req):
                got.append(req.spec)
                if len(got) == 3 * len(pool):
                    self.stop.set()

        Record(None, None, plan, 0.0, 1.0)._client(pool, [0])
        return got

    got = asked(7)
    assert got[: len(pool)] == pool
    for lap in (1, 2):
        for j, want in enumerate(pool):
            spec = got[lap * len(pool) + j]
            n = int(docs[want["group"]])
            assert (spec["group"], spec["max_new_tokens"], len(spec["prompt"])
                    ) == (want["group"], want["max_new_tokens"],
                          len(want["prompt"]))
            assert spec["prompt"][:n] == want["prompt"][:n]
            if not toy:  # 64 or more tokens of 102,400: never all the same
                assert spec["prompt"][n:] != want["prompt"][n:]
                assert spec["prompt"][n:] != got[(3 - lap) * len(pool) + j][
                    "prompt"][n:]
            assert all(0 <= x < vocab for x in spec["prompt"][n:])
    assert asked(7) == got


def test_the_manifest_holds_the_cell_and_its_five_readers():
    """Found by name, not by place: a later PR appends after them."""
    assert manifest.check() == []
    man = manifest.load_manifest()
    assert CELL in [w["name"] for w in man["workloads"]]
    assert "deepseek-v2-lite" in [c["name"] for c in man["configs"]]
    names = [p["name"] for p in man["per_layer"]]
    new = ["kernels.mla_decode_roofline.dsv2", "model.decode_roofline.dsv2",
           "model.serve_mfu.dsv2", "kernels.moe_grouped_roofline.dsv2",
           "kv.latent_bytes_share"]
    i = names.index(new[0])
    assert names[i:i + 5] == new
    assert all(man["per_layer"][i + j]["workloads"] == [CELL]
               for j in range(5))
    for e in man["end_to_end"]:
        assert (CELL in (e.get("workloads") or [])) == (
            e["name"] in ("itl_p95_ms", "out_tok_s"))


def _context(monkeypatch, rounds):
    monkeypatch.setattr(zaya_cell, "moe_rounds", lambda c, lo=None, hi=None:
                        rounds)
    cfg = dict(FULL)
    return {
        "model_cfg": cfg, "t_open": 0.0, "t_close": 45.0, "trace_s": 3.0,
        "window_s": 45.0, "chips": 1,
        "peaks": {"hbm_bytes_per_s": 8.19e11, "bf16_flops": 1.97e14},
        "counters": {"prompt_spans": [(16384, 16700)]},
        "trace": {
            "module_time_s": {"jit_step_fn": 0.25},
            "module_calls": {"jit_step_fn": 20},
            "op_time_s": {"mla_decode_attention": 0.11,
                          "grouped_matmul f32[256,2816]": 0.03,
                          "grouped_matmul f32[768,2816]": 0.02},
        },
    }


ROUNDS = [{"active": 32, "live_tokens": 32 * 22900,
           "latent_rows": 5 * (32 * 22900 + 32), "experts_touched": 240}]


@pytest.mark.parametrize("name", [
    "kernels.mla_decode_roofline.dsv2", "model.decode_roofline.dsv2",
    "model.serve_mfu.dsv2", "kernels.moe_grouped_roofline.dsv2",
    "kv.latent_bytes_share"])
def test_each_reader_reads_a_synthetic_window(monkeypatch, name):
    c = _context(monkeypatch, ROUNDS)
    value = manifest.load_reader(name).read(c)
    rows = 5 * (32 * 22900 + 32)
    if name == "kernels.mla_decode_roofline.dsv2":
        least = (rows * 1152 + 32 * 5 * 16 * 1088 * 2) / 8.19e11 * 20
        assert value == pytest.approx(100 * least / 0.11)
    elif name == "kv.latent_bytes_share":
        # ~45% of the round's bytes are latent rows at the cell's shape.
        assert 40 < value < 50
    elif name == "model.decode_roofline.dsv2":
        per = counts_dsv2.decode_round_bytes(FULL, rows / 5, 240, 32)
        assert value == pytest.approx(100 * per * 20 / 8.19e11 / 0.25)
    elif name == "kernels.moe_grouped_roofline.dsv2":
        # Only the decode round's 256-row products are its time.
        least = counts_dsv2.expert_bytes(FULL) * 240 * 20 / 8.19e11
        assert value == pytest.approx(100 * least / 0.03)
    else:
        assert 0 < value < 100
    # A program whose rounds carry no latent_rows gives nothing.
    c = _context(monkeypatch, [dict(ROUNDS[0], latent_rows=None)]
                 if name == "kv.latent_bytes_share" else
                 [{k: v for k, v in ROUNDS[0].items() if k != "latent_rows"}])
    assert manifest.load_reader(name).read(c) is None


def test_the_reference_blocks_its_queries_and_rows():
    from benchmarks import reference_dsv2

    assert reference_dsv2.QUERY_BLOCK * 16 * 30000 * 4 < 1e9
    assert set(reference_dsv2.FAULTS) == {
        "wrong_expert", "no_shared", "plain_rope", "no_mscale",
        "no_rope_key"}
    assert 64 % reference_dsv2.EXPERT_BLOCK == 0
    assert np.isclose(reference_dsv2._logit_scale(FULL, "f32") * 192 ** 0.5,
                      (0.1 * 0.707 * np.log(40) + 1) ** 2)
