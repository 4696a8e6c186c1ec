"""The Nemotron-H stage (layers of one kind each: Mamba-2 mixers, a top-k
sigmoid-routed expert layer with a shared expert, GQA attention with no
position signal; an untied head) held to the plain float32 reference
(``benchmarks/reference_nemotron.py``) at the toy size of
``benchmarks/configs/nemotron3-nano-30b.json`` on the CPU: pattern
``MEMEM*EME``, hidden 256, 8 Mamba heads of 32 with state 16 in 2 groups, 4
taps, SSD blocks of 8; 4 query / 2 kv heads of 64; 16 experts of width 128,
top-3, a shared expert of 256; vocabulary 512; seeded random weights.
LOGITS are compared, never tokens.

The tolerance and its reason: program and reference both run in float32
with matrix products at ``highest``; they differ in the FORM of the scan (the
program's SSD blocks against the reference's recurrence), in summation order
(fused projections, the sorted groups of the expert product) and in XLA's
own reassociation, which reads 6e-6 on logits of size 4 over 9 layers.
TOL = 2e-4 leaves that a factor of 30 and is under what the faults read on
70 positions (``test_lower_precision_and_faults_fail``): a bfloat16 router
0.0015 where no route flips (1.07 on 120 positions, where one does), a
bfloat16 state 0.0026 (0.0050 on 120 positions: the rounding accumulates),
bfloat16 linear layers 0.64, int8 1.15, a dropped shared expert 2.2, a wrong
route 3.5. The weights are drawn steady under rounding
(``benchmarks/weights_nemotron.py``), which is why the two smallest are
small: 7 and 13 times TOL all the same.
"""

import json
import os

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import counts_nemotron
from benchmarks import reference_nemotron as ref
from benchmarks import weights_nemotron
from distributed_tensorflow_tpu.models.decoding import init_cache
from distributed_tensorflow_tpu.models.mamba import ssd_scan, ssm_step
from distributed_tensorflow_tpu.models.moe import routed_experts
from distributed_tensorflow_tpu.ops.grouped_matmul import (
    grouped_matmul,
    grouped_matmul_fits,
)
from distributed_tensorflow_tpu.models.transformer import (
    CcaUnsupported,
    SlotStateUnsupported,
    TransformerConfig,
    TransformerLM,
)

TOL = 2e-4
with open(os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                       "configs", "nemotron3-nano-30b.json")) as _fh:
    _FILE = json.load(_fh)
TOY = dict(_FILE["transformer_config"], **_FILE["toy"]["transformer_config"])
E_LAYER = "block_1"


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def params():
    return weights_nemotron.make_params(TOY, 7, jnp.float32)


def toy_cfg(**over):
    return TransformerConfig(**dict(TOY, **over), compute_dtype=jnp.float32)


def tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, 512, n, dtype=np.int32)


# -- the benchmark's files -------------------------------------------------------

CELL = "nemotron3-nano.reason-closed-64"


def test_the_manifest_checks_and_holds_the_cell():
    from benchmarks import manifest

    assert manifest.check() == []
    man = manifest.load_manifest()
    cell, config, mix, cell_file = manifest.cell_files(man, CELL)
    assert cell["chips"] == 1 and cell["config"] == "nemotron3-nano-30b"
    assert config["runner"] == "benchmarks.nemotron_cell"
    assert config["reduced"] == ["num_hidden_layers"]
    assert (mix["clients"], mix["pool_requests"]) == (64, 256)
    assert (mix["prompt_len"]["min"], mix["prompt_len"]["max"],
            mix["output_len"]["min"], mix["output_len"]["max"]) == (
        128, 512, 1024, 3072)
    serve = config["serve_config"]
    assert (serve["slots"], serve["serve_max_len"], serve["prefill_len"],
            serve["page_size"], serve["prefix_cache"], serve["spec_k"]) == (
        64, 4096, 1024, 16, False, 0)
    for name in ("itl_p95_ms", "out_tok_s"):
        e = next(e for e in man["end_to_end"] if e["name"] == name)
        assert e["workloads"][-1] == CELL
    mine = {p["name"] for p in man["per_layer"] if CELL in p["workloads"]}
    assert {"model.decode_roofline.nemotron", "model.serve_mfu.nemotron",
            "kernels.moe_grouped_roofline.nemotron",
            "kv.ssm_state_bytes_share", "model.moe_experts_touched_share",
            "kv.decode_read_amplification",
            "device.idle_share.serve"} <= mine
    assert not any(n.endswith((".zaya", ".eva")) for n in mine)


def test_each_limit_lies_between_the_readings_it_was_set_from():
    from benchmarks import manifest

    _, _, _, cell_file = manifest.cell_files(manifest.load_manifest(), CELL)
    limits, read = cell_file["limits"], cell_file["readings"]
    noise, gap = limits["served_noise_scale"], limits["served_gap_max"]
    assert max(read["program"]["served_noise_scale"]) * 1.5 < noise
    assert noise * 1.5 < min(read["control_int8"]["served_noise_scale"])
    assert max(read["program"]["served_gap_max"]) * 1.5 < gap
    for fault in ("control_wrong_expert", "control_no_shared"):
        assert noise < min(read[fault]["served_noise_scale"])
        assert gap * 1.5 < min(read[fault]["served_gap_max"])
    # What the tokens do not show is written down as such, and the bytes
    # the state is held in are compared instead (nemotron_cell.run).
    assert "PASSES" in read["control_state_bf16"]["verdict"]
    assert "PASSES" in read["control_router_bf16"]["verdict"]
    held = _FILE["serve_config"]["slots"] * counts_nemotron.state_bytes_a_lane(
        _FILE["transformer_config"])
    assert f"{held:,} B" in cell_file["what_is_compared"]["ssm_state_bytes"]


def test_the_file_keeps_every_published_number_but_the_depth():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog on this machine")
    row = next(r for r in map(json.loads, open(path))
               if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")
    assert _FILE["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key == "num_hidden_layers":
            assert (_FILE[key], _FILE["published"][key]) == (9, value)
        else:
            assert _FILE[key] == value, key
    tc = _FILE["transformer_config"]
    assert tc["layer_pattern"] == row["config"]["hybrid_override_pattern"][:9]
    assert (tc["d_model"], tc["ssm_heads"], tc["ssm_head_dim"],
            tc["ssm_state"], tc["ssm_groups"], tc["ssm_conv"],
            tc["ssm_block"]) == tuple(row["config"][k] for k in (
        "hidden_size", "mamba_num_heads", "mamba_head_dim", "ssm_state_size",
        "n_groups", "conv_kernel", "chunk_size"))
    assert (tc["num_experts"], tc["experts_per_token"], tc["expert_width"],
            tc["shared_expert_width"], tc["router_scale"], tc["num_heads"],
            tc["num_kv_heads"], tc["head_dim"], tc["vocab_size"]) == tuple(
        row["config"][k] for k in (
            "n_routed_experts", "num_experts_per_tok",
            "moe_intermediate_size", "moe_shared_expert_intermediate_size",
            "routed_scaling_factor", "num_attention_heads",
            "num_key_value_heads", "head_dim", "vocab_size"))


# -- the uncached forward ------------------------------------------------------


def test_the_toy_is_the_file_s(params):
    assert TOY["layer_pattern"] == "MEMEM*EME" and TOY["d_model"] == 256
    assert (TOY["ssm_heads"], TOY["ssm_head_dim"], TOY["ssm_state"],
            TOY["ssm_groups"], TOY["ssm_conv"], TOY["ssm_block"]) == (
        8, 32, 16, 2, 4, 8)
    assert (TOY["num_experts"], TOY["experts_per_token"],
            TOY["shared_expert_width"], TOY["vocab_size"]) == (16, 3, 256, 512)


def test_uncached_forward_matches_reference(params):
    toks = tokens(70)
    got = TransformerLM(toy_cfg()).apply({"params": params}, toks[None])[0]
    want = ref.logits(params, toks, TOY)
    assert got.shape == (70, 512)
    assert float(jnp.abs(got - want).max()) < TOL


@pytest.mark.parametrize("mode,least", [
    ("bf16", 1000), ("int8", 1000), ("wrong_expert", 1000),
    ("no_shared", 1000), ("state_bf16", 10), ("router_bf16", 5)])
def test_lower_precision_and_faults_fail(params, mode, least):
    toks = tokens(70)
    want = ref.logits(params, toks, TOY)
    low = ref.logits(params, toks, TOY, mode=mode)
    assert float(jnp.abs(low - want).max()) > least * TOL


def test_the_program_s_tree_is_the_weights_file_s(params):
    cfg = toy_cfg()
    tree = TransformerLM(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))["params"]
    assert "pos_embed" not in tree and "lm_head" in tree
    assert set(tree["block_0"]) == {
        "ln1", "in_proj", "conv_w", "conv_b", "A_log", "D", "dt_bias",
        "ssm_norm", "out_proj"}
    assert set(tree["block_1"]) == {
        "ln1", "router", "router_bias", "moe_up", "moe_out", "shared_in",
        "shared_out"}
    assert set(tree["block_5"]) == {"ln1", "qkv", "proj"}
    # Two matrices an expert (relu^2 has no gate), both (width, d_model);
    # a 10,304-like in_proj.
    assert tree["block_1"]["moe_up"].shape == (16, 128, 256)
    assert tree["block_1"]["moe_out"].shape == (16, 128, 256)
    assert tree["block_0"]["in_proj"]["kernel"].shape == (
        256, 256 + (256 + 2 * 2 * 16) + 8)
    assert (jax.tree_util.tree_map(lambda a: a.shape, tree)
            == jax.tree_util.tree_map(lambda a: a.shape, dict(params)))


@pytest.mark.parametrize("layer,name", [
    ("block_0", "conv_b"), ("block_0", "D"), ("block_0", "dt_bias"),
    ("block_2", "conv_w"), ("block_1", "router_bias"),
    ("block_1", "shared_out"), ("block_5", "proj")])
def test_every_term_is_in_the_logits(params, layer, name):
    """Zeroing any one of these moves the logits far over TOL (the bias in
    the choice: by a hundred times its size, so that it changes routes)."""
    toks = tokens(40)
    model = TransformerLM(toy_cfg())
    base = model.apply({"params": params}, toks[None])[0]
    leaf = params[layer][name]
    new = (jax.tree_util.tree_map(jnp.zeros_like, leaf)
           if name != "router_bias" else leaf * 100.0)
    less = dict(params, **{layer: dict(params[layer], **{name: new})})
    got = model.apply({"params": less}, toks[None])[0]
    assert float(jnp.abs(got - base).max()) > 50 * TOL


# -- the scan --------------------------------------------------------------------


def _scan_inputs(l, seed=0, b=2, h=8, p=32, g=2, n=16):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    dt = jax.nn.softplus(f(b, l, h) - 2.0)
    a = -jnp.asarray(rng.uniform(1.0, 16.0, h), jnp.float32)
    return f(b, l, h, p), dt, a, f(b, l, g, n), f(b, l, g, n), f(b, h, p, n)


def _by_steps(x, dt, a, bm, cm, s):
    ys = []
    for t in range(x.shape[1]):
        y, s = ssm_step(x[:, t], dt[:, t], a, bm[:, t], cm[:, t], s)
        ys.append(y)
    return jnp.stack(ys, 1), s


@pytest.mark.parametrize("l,block", [(29, 8), (8, 8), (5, 8), (64, 16)],
                         ids=["ragged", "one-block", "under-a-block",
                              "whole-blocks"])
def test_the_ssd_form_is_the_recurrence(l, block):
    """The block form (matmuls inside a block, a scan over blocks) against
    the recurrence step by step, from a non-zero state, on a segment that is
    not a whole number of blocks: y and the state behind it. 1e-4 of values
    of size 10: summation order alone."""
    x, dt, a, bm, cm, s0 = _scan_inputs(l)
    y, s = ssd_scan(x, dt, a, bm, cm, s0, block)
    y_want, s_want = _by_steps(x, dt, a, bm, cm, s0)
    assert y.shape == (2, l, 8, 32) and s.shape == (2, 8, 32, 16)
    assert float(jnp.abs(y - y_want).max()) < 1e-4 * float(
        jnp.abs(y_want).max())
    assert float(jnp.abs(s - s_want).max()) < 1e-4 * float(
        jnp.abs(s_want).max())


def test_padding_behind_the_last_real_token_leaves_the_state():
    """dt 0 neither decays nor feeds: the state behind 19 real rows and 13
    rows of padding is the state behind the 19."""
    x, dt, a, bm, cm, s0 = _scan_inputs(32, seed=1)
    real = jnp.arange(32)[None, :, None] < 19
    _, s = ssd_scan(x, jnp.where(real, dt, 0.0), a, bm, cm, s0, 8)
    _, s_want = ssd_scan(x[:, :19], dt[:, :19], a, bm[:, :19], cm[:, :19],
                         s0, 8)
    assert float(jnp.abs(s - s_want).max()) < 1e-5 * float(
        jnp.abs(s_want).max())


def test_a_masked_lane_keeps_its_state_bit_for_bit():
    x, dt, a, bm, cm, s0 = _scan_inputs(1, seed=2, b=4)
    live = jnp.asarray([True, False, True, False])
    _, s = ssm_step(x[:, 0], jnp.where(live[:, None], dt[:, 0], 0.0), a,
                    bm[:, 0], cm[:, 0], s0)
    np.testing.assert_array_equal(np.asarray(s[1]), np.asarray(s0[1]))
    np.testing.assert_array_equal(np.asarray(s[3]), np.asarray(s0[3]))
    assert float(jnp.abs(s[0] - s0[0]).max()) > 1e-3


def test_a_bfloat16_state_is_seen(params):
    """What the state's float32 is worth at this size: the reference with
    the state rounded to bfloat16 at every position is 0.005 off on the
    logits of 120 positions, 25 times TOL, and twice what it is on 70."""
    toks = tokens(120, seed=3)
    want = ref.logits(params, toks, TOY)
    low = ref.logits(params, toks, TOY, mode="state_bf16")
    assert float(jnp.abs(low - want).max()) > 15 * TOL


# -- the expert layer -----------------------------------------------------------


class _Experts(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, h, mask=None):
        return routed_experts(self, self.cfg, h, None, mask)


def _layer_params(params, held=None, **over):
    p = {k: v for k, v in params[E_LAYER].items() if k != "ln1"}
    if held is not None:
        idx = np.asarray(held)
        p["moe_up"], p["moe_out"] = p["moe_up"][idx], p["moe_out"][idx]
    return dict(p, **over)


def _reference_experts(params, h, held=tuple(range(16)), mode="f32", **over):
    p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                               _layer_params(params, held, **over))
    return ref.experts(h, p, TOY, mode, held)


def test_the_layer_matches_the_reference_and_counts_its_pairs(params):
    h = jnp.asarray(np.random.default_rng(1).normal(size=(2, 9, 256)),
                    jnp.float32)
    y, r, counts = _Experts(toy_cfg()).apply(
        {"params": _layer_params(params)}, h)
    want = _reference_experts(params, h.reshape(18, 256))
    assert r is None and counts.shape == (16,)
    assert int(counts.sum()) == 18 * 3  # pairs, three a token
    assert float(jnp.abs(y.reshape(18, 256) - want).max()) < 1e-5
    # The weights are renormalised and scaled: the routed part is not the
    # unweighted sum.
    expert, weight = ref.route(h.reshape(18, 256), jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32), _layer_params(params)), TOY)
    np.testing.assert_allclose(np.asarray(weight.sum(-1)), 2.5, rtol=1e-6)
    assert all(len(set(row)) == 3 for row in np.asarray(expert).tolist())


@pytest.mark.parametrize("chosen", [(0, 1, 2), (3, 9, 15)])
def test_dropless_with_every_pair_on_the_same_experts(params, chosen):
    """A balancing bias that sends all 64 tokens to the same three experts:
    192 pairs on 3 of 16, nothing dropped whatever a capacity would have
    been, and the whole model's logits are the reference's under that
    bias."""
    bias = jnp.zeros(16).at[jnp.asarray(chosen)].set(100.0)
    h = jnp.asarray(np.random.default_rng(2).normal(size=(1, 64, 256)),
                    jnp.float32)
    y, _, counts = _Experts(toy_cfg()).apply(
        {"params": _layer_params(params, router_bias=bias)}, h)
    want = _reference_experts(params, h[0], router_bias=bias)
    assert counts.tolist() == [64 if e in chosen else 0 for e in range(16)]
    assert float(jnp.abs(y[0] - want).max()) < 1e-5
    forced = {k: (dict(v, router_bias=bias) if "router_bias" in v else v)
              for k, v in params.items()}
    toks = tokens(64, seed=3)
    got = TransformerLM(toy_cfg()).apply({"params": forced}, toks[None])[0]
    assert float(jnp.abs(got - ref.logits(forced, toks, TOY)).max()) < TOL


def test_two_shares_and_the_shared_expert_once_are_the_whole_layer(params):
    """The share test of the model-configs guide, section 4: a chip holding
    experts 0-7 and a chip holding experts 8-15 each route over all 16 and
    each compute the shared expert; their sum less ONE shared expert is the
    uncut reference's layer."""
    h = jnp.asarray(np.random.default_rng(4).normal(size=(1, 48, 256)),
                    jnp.float32)
    parts, pairs = [], 0
    for held in (tuple(range(8)), tuple(range(8, 16))):
        cfg = toy_cfg(experts_held=list(held))
        y, _, counts = _Experts(cfg).apply(
            {"params": _layer_params(params, held)}, h)
        assert counts.shape == (8,)
        parts.append(y[0])
        pairs += int(counts.sum())
        # The reference, given the same share, gives the same part.
        part = _reference_experts(params, h[0], held=held)
        assert float(jnp.abs(y[0] - part).max()) < 1e-5
    whole = _reference_experts(params, h[0])
    shared = whole - _reference_experts(params, h[0], mode="no_shared")
    assert pairs == 48 * 3 and float(jnp.abs(shared).max()) > 0.1
    assert float(jnp.abs(parts[0] + parts[1] - shared - whole).max()) < 1e-5


def test_masked_tokens_reach_no_routed_expert(params):
    h = jnp.asarray(np.random.default_rng(5).normal(size=(4, 1, 256)),
                    jnp.float32)
    mask = jnp.asarray([[True], [False], [True], [False]])
    apply = lambda *a: _Experts(toy_cfg()).apply(
        {"params": _layer_params(params)}, *a)
    y, _, counts = apply(h, mask)
    full, _, _ = apply(h)
    assert int(counts.sum()) == 2 * 3
    # A masked token gets the shared expert's part and nothing routed.
    shared = full[:, 0] - _reference_experts(params, h[:, 0], mode="no_shared")
    assert float(jnp.abs(y[1, 0] - shared[1]).max()) < 1e-5
    assert float(jnp.abs(y[0] - full[0]).max()) < 1e-6
    assert float(jnp.abs(y[1] - full[1]).max()) > 1e-2


def test_gated_experts_take_the_linear_router_too(params):
    """The activation and the router are independent keys: top-3 over gated
    SiLU experts, against the reference."""
    over = dict(expert_act="swiglu")
    cfg = toy_cfg(**over)
    h = jnp.asarray(np.random.default_rng(6).normal(size=(1, 20, 256)),
                    jnp.float32)
    mod = _Experts(cfg)
    p = mod.init(jax.random.PRNGKey(3), h)["params"]
    assert p["moe_in"].shape == (16, 256, 256)
    y, _, counts = mod.apply({"params": p}, h)
    want = ref.experts(h[0], p, dict(TOY, **over), "f32", tuple(range(16)))
    assert int(counts.sum()) == 60
    assert float(jnp.abs(y[0] - want).max()) < 1e-5


# -- the grouped product's kernel -------------------------------------------------


@pytest.mark.parametrize("m,k,n,g,transposed,rows", [
    (54, 256, 128, 16, True, "all"), (54, 128, 256, 16, False, "some"),
    (300, 256, 384, 8, False, "all"), (300, 256, 384, 8, True, "none"),
    (640, 128, 128, 5, False, "one-group"),
], ids=["up-as-the-toy", "down-short-of-m", "three-row-tiles", "no-row",
        "a-group-over-four-tiles"])
def test_the_grouped_matmul_kernel_is_ragged_dot(m, k, n, g, transposed,
                                                 rows):
    """``ops/grouped_matmul.py`` (interpret mode here) against
    ``jax.lax.ragged_dot`` on the rows that belong to a group: both
    orientations of the matrices, empty groups, rows behind the last group,
    a group that spans row tiles, tiles shared by many groups. 1e-4 of values
    of size 16: summation order."""
    rng = np.random.default_rng(m + k)
    total = {"all": m, "some": 40, "none": 0, "one-group": 600}[rows]
    e = rng.integers(0, g, total)
    if rows == "one-group":
        e[:500] = 2
    counts = jnp.asarray(np.bincount(e, minlength=g), jnp.int32)
    x = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(g, n, k) if transposed else (g, k, n)),
                    jnp.float32)
    assert grouped_matmul_fits(w, transposed)
    got = grouped_matmul(x, w, counts, transpose_rhs=transposed)
    want = jax.lax.ragged_dot(x, w.swapaxes(1, 2) if transposed else w,
                              counts, preferred_element_type=jnp.float32)
    assert got.shape == (m, n) and got.dtype == jnp.float32
    if total:
        assert float(jnp.abs(got[:total] - want[:total]).max()) < 1e-4 * 16


def test_matrices_off_the_tile_are_refused_by_name():
    """The rule on shapes: an expert width that is no whole number of lanes
    where it is the matrices' last axis does not fit (the published 1856 as
    ``(d, width)``), and does as ``(width, d)``, which is how ``moe_up``
    lies; the kernel and the config refuse what does not fit by name, and
    no second product stands behind them."""
    z = lambda *s: jnp.zeros(s, jnp.bfloat16)
    assert not grouped_matmul_fits(z(2, 256, 1856))
    assert grouped_matmul_fits(z(2, 1856, 256), True)
    assert grouped_matmul_fits(z(2, 1856, 256))
    assert not grouped_matmul_fits(z(2, 24, 128)) and grouped_matmul_fits(
        jnp.zeros((2, 24, 128), jnp.float32))
    with pytest.raises(ValueError, match="off the tile"):
        grouped_matmul(z(8, 256), z(2, 256, 1856), jnp.zeros(2, jnp.int32))
    # Gated experts hold gate | up as (d, 2 * width): 192 is a lane and a half.
    with pytest.raises(ValueError, match="off the tiles"):
        toy_cfg(expert_act="swiglu", expert_width=96)
    assert toy_cfg(expert_width=96).expert_width == 96


# -- validation -----------------------------------------------------------------


@pytest.mark.parametrize("over,exc,match", [
    ({"layer_pattern": "MEMEM*EM"}, ValueError, "layer_pattern"),
    ({"layer_pattern": "MEMEMAEME"}, ValueError, "layer_pattern"),
    ({"ssm_groups": 3}, ValueError, "ssm_groups"),
    ({"ssm_state": 0}, ValueError, "ssm_state"),
    ({"ssm_conv": 1}, ValueError, "ssm_conv"),
    ({"experts_per_token": 17}, ValueError, "experts_per_token"),
    ({"experts_per_token": 0}, ValueError, "experts_per_token"),
    ({"router_hidden": 32}, ValueError, "picks one expert"),
    ({"router_hidden": -1}, ValueError, "router_hidden"),
    ({"expert_width": 100}, ValueError, "off the tiles"),
    ({"expert_act": "gelu"}, ValueError, "expert_act"),
    ({"shared_expert_width": -1}, ValueError, "shared_expert_width"),
    ({"num_experts": 0, "router_hidden": 0, "layer_pattern": "M" * 9,
      "experts_per_token": 1, "shared_expert_width": 64}, ValueError,
     "need num_experts"),
    ({"num_experts": 0, "experts_per_token": 1, "shared_expert_width": 0},
     ValueError, "'E' layer"),
    ({"position": "alibi"}, ValueError, "position"),
    ({"kv_cache_dtype": "int8"}, SlotStateUnsupported, "kv_cache_dtype"),
    ({"weight_dtype": "int8"}, SlotStateUnsupported, "weight-only quant"),
    ({"cca_time0": 2, "cca_time1": 2}, SlotStateUnsupported, "cca_time0"),
    ({"eva_window": 32, "eva_chunk": 4, "num_kv_heads": 4},
     SlotStateUnsupported, "eva_window"),
    ({"residual_dtype": "float32"}, SlotStateUnsupported, "residual_dtype"),
    ({"num_pred_heads": 2}, SlotStateUnsupported, "prediction heads"),
], ids=lambda v: None if not isinstance(v, dict) else "-".join(v))
def test_the_config_refuses_by_name(over, exc, match):
    with pytest.raises(exc, match=match):
        toy_cfg(**over)
    assert CcaUnsupported is SlotStateUnsupported
    assert issubclass(SlotStateUnsupported, ValueError)


def test_a_cache_of_rows_alone_refuses_by_name(params):
    """``init_cache`` gives a layer_pattern config K and V for its attention
    layer alone; the Mamba layers' state lives in the serving pool, and the
    monolithic cached branch says so."""
    cfg = toy_cfg()
    cache = init_cache(cfg, 1, 32)
    assert [sorted(l) for l in cache["layers"]] == [
        ["k", "v"] if kind == "*" else [] for kind in "MEMEM*EME"]
    with pytest.raises(SlotStateUnsupported, match="no recurrent state"):
        TransformerLM(cfg).apply({"params": params},
                                 jnp.asarray(tokens(5))[None], cache=cache)


def test_no_position_signal_on_the_plain_block():
    """``position='none'`` on the attention-then-MLP block: no table, no
    rotation, and the cached branch agrees with the uncached forward."""
    cfg = TransformerConfig(
        vocab_size=64, d_model=96, num_heads=4, num_kv_heads=2, num_layers=2,
        d_ff=128, max_seq_len=32, position="none", use_bias=False,
        compute_dtype=jnp.float32)
    model = TransformerLM(cfg)
    toks = jnp.asarray(tokens(11, seed=6) % 64)[None]
    p = model.init(jax.random.PRNGKey(1), toks)["params"]
    assert "pos_embed" not in p
    want = model.apply({"params": p}, toks)
    got, _ = model.apply({"params": p}, toks, cache=init_cache(cfg, 1, 32))
    assert float(jnp.abs(got - want).max()) < 1e-5
