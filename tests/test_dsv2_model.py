"""DeepSeek-V2-Lite's layers in the program (``models/transformer.py``'s
latent attention and dense leading layers, ``ops/rope.py``'s YaRN,
``models/moe.py``'s softmax router) held to the plain float32 reference
(``benchmarks/reference_dsv2.py``) at the toy size of the configuration
file, on seeded random weights (``benchmarks/weights_dsv2.py``).

TOL = 2e-4 on logits of magnitude ~5: float32 against float32, the program
attending a block of rows at a time with an online softmax and the
reference in one softmax, and summing its products in another order; the
toy reads 2e-6 to 6e-6. The bfloat16 reference reads over 30 times TOL,
and every fault of ``reference_dsv2.FAULTS`` more than 10 times it, so the
comparison sees each.
"""

import json
import math
import os

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_dsv2 as ref
from benchmarks import weights_dsv2
from distributed_tensorflow_tpu.models import moe
from distributed_tensorflow_tpu.models.transformer import (
    SlotStateUnsupported,
    TransformerConfig,
    TransformerLM,
    _mla_attend_rows,
    mla_prefill_form,
    mla_prefill_widths,
)
from distributed_tensorflow_tpu.ops import rope as rope_ops

_FILE = json.load(open(os.path.join(
    os.path.dirname(__file__), "..", "benchmarks", "configs",
    "deepseek-v2-lite.json")))
FULL = _FILE["transformer_config"]
TOY = dict(FULL, **_FILE["toy"]["transformer_config"])
TOL = 2e-4


def toy_cfg(**over):
    return TransformerConfig(**dict(TOY, **over), compute_dtype=jnp.float32)


def tokens(n, seed=0):
    return np.random.default_rng(seed).integers(
        0, TOY["vocab_size"], n).astype(np.int32)


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def params():
    return weights_dsv2.make_params(TOY, 7, jnp.float32)


def program_logits(params, toks, cfg=None):
    model = TransformerLM(cfg or toy_cfg())
    return np.asarray(model.apply({"params": params},
                                  jnp.asarray(toks)[None])[0])


# -- the forward pass ------------------------------------------------------------


@pytest.mark.parametrize("n", [5, 47, 130], ids=["short", "mid", "past-yarn"])
def test_forward_matches_reference(params, n):
    """The whole forward (cache None: the expanded form) against the
    reference; 130 positions lie far past YaRN's original 32."""
    toks = tokens(n, seed=n)
    got = program_logits(params, toks)
    want = np.asarray(ref.logits(params, toks, TOY))
    assert np.abs(got - want).max() < TOL


def test_lower_precision_and_faults_fail_the_tolerance(params):
    toks = tokens(60, seed=3)
    want = np.asarray(ref.logits(params, toks, TOY))
    bf16 = np.asarray(ref.logits(params, toks, TOY, mode="bf16"))
    assert np.abs(bf16 - want).max() > 30 * TOL
    for fault in ref.FAULTS:
        got = np.asarray(ref.logits(params, toks, TOY, mode=fault))
        assert np.abs(got - want).max() > 10 * TOL, fault


def test_the_parameters_are_the_published_layout(params):
    """Layer 0 carries the dense SwiGLU of d_ff, the others a router and
    64 experts' matrices; the attention's matrices have the latent's widths."""
    b0, b1 = params["block_0"], params["block_1"]
    assert {"mlp_gate", "mlp_up", "mlp_out"} <= set(b0)
    assert "router" not in b0 and "moe_in" not in b0
    assert b0["mlp_gate"]["kernel"].shape == (256, TOY["d_ff"])
    assert b1["router"]["kernel"].shape == (256, 8)
    assert "router_bias" not in b1  # the softmax router has no bias
    assert b1["moe_in"].shape == (8, 256, 2 * 128)
    assert b1["kv_a"].shape == (256, 112 + 16)
    assert b1["kv_b"].shape == (112, 4, 32 + 32)
    assert b1["q_proj"].shape == (256, 4 * (32 + 16))
    cfg = toy_cfg()
    assert cfg.expert_layers == 2 and cfg.mla_row == 128
    assert TransformerConfig(**FULL).mla_row == 640


def test_the_dense_layer_is_not_routed(params):
    """Layer 0's output is its dense MLP's: zeroing that matrix moves the
    logits far past the tolerance."""
    toks = tokens(20, seed=4)
    base = program_logits(params, toks)
    p2 = jax.tree_util.tree_map(lambda a: a, params)
    p2["block_0"] = dict(p2["block_0"], mlp_out={
        "kernel": 0 * p2["block_0"]["mlp_out"]["kernel"]})
    assert np.abs(program_logits(p2, toks) - base).max() > 10 * TOL


# -- the two forms of attention ------------------------------------------------


@pytest.mark.parametrize("s,start", [(1, 40), (8, 0), (16, 90)])
def test_absorbed_and_expanded_attention_agree(s, start):
    """On the same latent rows, queries folded into the latent give what
    per-head K and V expanded from it give."""
    cfg = toy_cfg()
    rng = np.random.default_rng(s)
    rows = 128
    r, nope, rope, v, h = 112, 32, 16, 32, 4
    lat = np.zeros((1, rows, cfg.mla_row), np.float32)
    lat[..., :r + rope] = rng.normal(size=(1, rows, r + rope))
    q_nope = jnp.asarray(rng.normal(size=(1, s, h, nope)), jnp.float32)
    q_pe = jnp.asarray(rng.normal(size=(1, s, h, rope)), jnp.float32)
    w_kb = jnp.asarray(rng.normal(size=(r, h, nope + v)) / np.sqrt(r),
                       jnp.float32)
    q_pos = start + jnp.arange(s)
    out = [np.asarray(_mla_attend_rows(cfg, q_nope, q_pe, jnp.asarray(lat),
                                       q_pos, start + s, w_kb, form))
           for form in ("expanded", "absorbed")]
    assert np.abs(out[0] - out[1]).max() < 1e-5
    # Both against one softmax over the expanded rows.
    kv = np.einsum("tc,chd->thd", lat[0, :, :r], np.asarray(w_kb))
    sc = (np.einsum("qhd,thd->hqt", np.asarray(q_nope[0]), kv[..., :nope])
          + np.einsum("qhd,td->hqt", np.asarray(q_pe[0]),
                      lat[0, :, r:r + rope])) * cfg.mla_scale
    keep = np.arange(rows)[None] <= np.asarray(q_pos)[:, None]
    sc = np.where(keep[None], sc, -np.inf)
    p = np.exp(sc - sc.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    want = np.einsum("hqt,thd->qhd", p, kv[..., nope:])
    assert np.abs(out[1][0] - want).max() < 1e-5


@pytest.mark.parametrize("form", ["expanded", "absorbed"])
def test_the_chunk_kernel_gives_what_the_blocks_give(form):
    """A prefill chunk of 16 rows at position 40 of a row of 128 through
    ``chunk_flash_attention`` (interpret mode here) in either form, against
    the blockwise ``jax.numpy`` loop in the same form."""
    from distributed_tensorflow_tpu.models.transformer import _mla_flash

    cfg = toy_cfg()
    rng = np.random.default_rng(5)
    r, nope, rope, v, h, s, start = 112, 32, 16, 32, 4, 16, 40
    lat = np.zeros((1, 128, cfg.mla_row), np.float32)
    lat[..., :r + rope] = rng.normal(size=(1, 128, r + rope))
    q_nope = jnp.asarray(rng.normal(size=(1, s, h, nope)), jnp.float32)
    q_pe = jnp.asarray(rng.normal(size=(1, s, h, rope)), jnp.float32)
    w_kb = jnp.asarray(rng.normal(size=(r, h, nope + v)) / np.sqrt(r),
                       jnp.float32)
    got = np.asarray(_mla_flash(cfg, q_nope, q_pe, jnp.asarray(lat),
                                jnp.int32(start), w_kb, form))
    want = np.asarray(_mla_attend_rows(
        cfg, q_nope, q_pe, jnp.asarray(lat), start + jnp.arange(s),
        start + s, w_kb, form))
    assert got.shape == (1, s, h, v)
    assert np.abs(got - want).max() < 1e-5


def test_the_prefill_form_follows_the_flop_counts():
    """At DeepSeek-V2's widths over a row of 32768 the absorbed form wins
    up to 169 queries (170.7 as the rows grow without end); at the toy's,
    up to 38 over 256 rows."""
    full = TransformerConfig(**FULL)
    assert [mla_prefill_form(full, w, 32768) for w in (1, 128, 169)] == [
        "absorbed"] * 3
    assert [mla_prefill_form(full, w, 32768) for w in (170, 256, 1024)] == [
        "expanded"] * 3
    toy = toy_cfg()
    assert mla_prefill_form(toy, 38, 256) == "absorbed"
    assert mla_prefill_form(toy, 39, 256) == "expanded"


def test_the_prefill_widths_follow_the_forms():
    """The widths an engine compiles below its chunk: the widest power of
    two the absorbed form takes, then its doublings under the chunk."""
    full = TransformerConfig(**FULL)
    assert mla_prefill_widths(full, 32768, 1024) == (128, 256, 512)
    assert mla_prefill_widths(full, 32768, 128) == (64,)
    assert mla_prefill_widths(full, 32768, 129) == (128,)
    assert mla_prefill_widths(toy_cfg(), 256, 64) == (32,)
    assert mla_prefill_widths(toy_cfg(), 128, 32) == (16,)


# -- YaRN -------------------------------------------------------------------------


def test_yarn_frequencies_follow_the_formula():
    """DeepSeek-V2's rope part: 32 pairs, base 10000, factor 40 over 4096:
    the ramp runs from pair 10 to pair 23; below it the plain frequency,
    above it the plain one over 40, between them a linear mix."""
    inv = rope_ops.yarn_inv_freq(64, 10000.0, 40.0, 4096, 32.0, 1.0)
    extra = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    low = math.floor(64 * math.log(4096 / (32 * 2 * math.pi))
                     / (2 * math.log(10000)))
    high = math.ceil(64 * math.log(4096 / (2 * math.pi))
                     / (2 * math.log(10000)))
    assert (low, high) == (10, 23)
    ramp = np.clip((np.arange(32) - low) / (high - low), 0, 1)
    want = extra / 40 * ramp + extra * (1 - ramp)
    np.testing.assert_allclose(inv, want, rtol=1e-12)
    np.testing.assert_allclose(inv[:11], extra[:11], rtol=1e-12)
    np.testing.assert_allclose(inv[23:], extra[23:] / 40, rtol=1e-12)
    np.testing.assert_allclose(inv, ref.yarn_inv_freq(FULL), rtol=1e-12)


def test_yarn_scales():
    """m = 0.1 x 0.707 x ln 40 + 1 = 1.2608; the logits take m^2 / sqrt(192)
    and the tables m / m = 1."""
    m = rope_ops.yarn_mscale(40.0, 0.707)
    assert abs(m - 1.26081) < 1e-5 and rope_ops.yarn_mscale(1.0, 0.707) == 1
    cfg = TransformerConfig(**FULL)
    assert abs(cfg.mla_scale - m * m / math.sqrt(192)) < 1e-12
    cos, sin = rope_ops.yarn_tables(jnp.arange(5), np.ones(3), scale=1.0)
    np.testing.assert_allclose(np.asarray(cos[:, 0]), np.cos(np.arange(5)),
                               rtol=1e-6)


# -- the router -------------------------------------------------------------------


class _Route(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, h):
        return moe.route_linear(self, self.cfg, h)


def test_softmax_router_takes_the_top_six_without_renormalising():
    """A hand-made score row over 64 experts: the six largest softmax
    scores, greedy, their weights the softmax values themselves (times 1.0),
    summing to less than one."""
    cfg = TransformerConfig(**FULL)
    logits = np.linspace(-3.0, 3.0, 64)[np.random.default_rng(0).permutation(
        64)].astype(np.float32)
    kernel = np.zeros((2048, 64), np.float32)
    kernel[0] = logits
    h = np.zeros((1, 2048), np.float32)
    h[0, 0] = 1.0
    expert, weight = _Route(cfg).apply(
        {"params": {"router": {"kernel": kernel}}}, jnp.asarray(h))
    s = np.exp(logits - logits.max())
    s /= s.sum()
    top = np.argsort(-s)[:6]
    np.testing.assert_array_equal(np.asarray(expert[0]), top)
    np.testing.assert_allclose(np.asarray(weight[0]), s[top], rtol=1e-6)
    assert float(weight.sum()) < 1.0


def test_the_sigmoid_router_is_unchanged():
    """Cells 6 and 7's router (sigmoid, the choice-only bias, renormalised
    and scaled): the weights are, bit for bit, the formula it had."""
    nem = json.load(open(os.path.join(
        os.path.dirname(__file__), "..", "benchmarks", "configs",
        "nemotron3-nano-30b.json")))["transformer_config"]
    cfg = TransformerConfig(**nem, compute_dtype=jnp.float32)
    rng = np.random.default_rng(1)
    h = jnp.asarray(rng.normal(size=(5, nem["d_model"])), jnp.float32)
    p = {"router": {"kernel": jnp.asarray(
        rng.normal(size=(nem["d_model"], 128)) * 0.02, jnp.float32)},
         "router_bias": jnp.asarray(rng.normal(size=128) * 0.01, jnp.float32)}
    expert, weight = _Route(cfg).apply({"params": p}, h)
    s = jax.nn.sigmoid(jnp.matmul(h, p["router"]["kernel"],
                                  precision="highest"))
    _, want = jax.lax.top_k(s + p["router_bias"], 6)
    picked = jnp.take_along_axis(s, want, -1)
    np.testing.assert_array_equal(np.asarray(expert), np.asarray(want))
    np.testing.assert_array_equal(
        np.asarray(weight),
        np.asarray(2.5 * picked / (picked.sum(-1, keepdims=True) + 1e-20)))


# -- what latent attention is not extended to ---------------------------------------


@pytest.mark.parametrize("over,match", [
    ({"q_lora_rank": 1536}, "q-LoRA"),
    ({"kv_cache_dtype": "int8"}, "int8"),
    ({"weight_dtype": "int8"}, "quantisation"),
    ({"num_kv_heads": 4}, "grouped kv heads"),
    ({"attention_window": 64}, "sliding window"),
    ({"dropout_rate": 0.1}, "dropout"),
    ({"remat": True}, "served, not trained"),
    ({"attention": "flash"}, "served, not trained"),
], ids=["q-lora", "int8-kv", "int8-weights", "gqa", "window", "dropout",
        "remat", "flash"])
def test_the_config_refuses_by_name(over, match):
    with pytest.raises(SlotStateUnsupported, match=match):
        toy_cfg(**over)


@pytest.mark.parametrize("over,match", [
    ({"kv_lora_rank": 0}, "need kv_lora_rank"),
    ({"position": "learned"}, "position must be 'rope'"),
    ({"num_experts": 0, "dense_layers": 0, "experts_per_token": 1,
      "shared_expert_width": 0, "router_score": "sigmoid",
      "router_renorm": True, "router_scale": 1.0, "expert_width": 0},
     None),
    ({"dense_layers": 3}, "dense_layers"),
    ({"router_score": "cosine"}, "router_score"),
], ids=["yarn-without-mla", "learned-positions", "dense-mla",
        "all-dense", "unknown-score"])
def test_the_config_checks_its_keys(over, match):
    if match is None:  # latent attention over dense MLPs alone is fine
        assert toy_cfg(**over).mla
        return
    with pytest.raises(ValueError, match=match):
        toy_cfg(**over)


def test_the_router_options_need_the_linear_router():
    with pytest.raises(ValueError, match="linear router"):
        TransformerConfig(num_experts=0, router_score="softmax")
