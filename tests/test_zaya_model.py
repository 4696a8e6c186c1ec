"""ZAYA1's block (CCA attention and a dropless top-1 expert layer behind an
MLP router, tied head) held to the plain float32 reference
(``benchmarks/reference_zaya.py``) at toy size on the CPU: 3 layers, hidden
256, 4 query / 2 kv heads of 64 (a latent of 256 halved for k and v), 8
experts of width 256, router 32, vocabulary 512, seeded random weights with
every vector non-zero. LOGITS are compared, never tokens.

The tolerance and its reason: program and reference both run in float32
with matrix products at ``highest``; they differ in summation order alone
(the fused projection's slices, the sorted groups of the expert product,
XLA's own reassociation), which reads 5e-6 on logits of size 4 over 3
layers. TOL = 2e-4 leaves that a factor of 40, is 150 times under what
bfloat16 linear layers read (0.03-0.04: ``test_lower_precision_fails``) and
thousands of times under int8 or a wrong route.
"""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_zaya as ref
from benchmarks import weights_zaya
from distributed_tensorflow_tpu.models.decoding import init_cache
from distributed_tensorflow_tpu.models.moe import routed_experts
from distributed_tensorflow_tpu.models.transformer import (
    CcaUnsupported,
    EvaUnsupported,
    TransformerConfig,
    TransformerLM,
    apply_rope,
)
from distributed_tensorflow_tpu.ops.rope import rope_tables

TOL = 2e-4
TOY = dict(
    vocab_size=512, d_model=256, num_heads=4, num_kv_heads=2, head_dim=64,
    num_layers=3, d_ff=256, max_seq_len=256, position="rope",
    rope_theta=5000000.0, rope_fraction=0.5, use_bias=False,
    attention="dense", norm="rms", norm_eps=1e-5, mlp="swiglu",
    tie_embeddings=True, cca_time0=2, cca_time1=2, num_experts=8,
    router_hidden=32, expert_width=256,
)


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def params():
    return weights_zaya.make_params(TOY, 7, jnp.float32)


def toy_cfg(**over):
    return TransformerConfig(**dict(TOY, **over), compute_dtype=jnp.float32)


def tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, 512, n, dtype=np.int32)


# -- the uncached forward ------------------------------------------------------


def test_uncached_forward_matches_reference(params):
    toks = tokens(70)
    got = TransformerLM(toy_cfg()).apply({"params": params}, toks[None])[0]
    want = ref.logits(params, toks, TOY)
    assert got.shape == (70, 512)
    assert float(jnp.abs(got - want).max()) < TOL


@pytest.mark.parametrize("mode,least", [("bf16", 50), ("int8", 1000),
                                        ("wrong_expert", 1000)])
def test_lower_precision_fails(params, mode, least):
    toks = tokens(70)
    want = ref.logits(params, toks, TOY)
    low = ref.logits(params, toks, TOY, mode=mode)
    assert float(jnp.abs(low - want).max()) > least * TOL


def test_the_head_is_the_embedding(params):
    assert "lm_head" not in params
    cfg = toy_cfg()
    tree = TransformerLM(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))["params"]
    assert "lm_head" not in tree and "router_gamma" not in tree["block_0"]
    assert "router_gamma" in tree["block_1"]
    # The parameter tree of the program is the tree the weights file makes.
    assert (jax.tree_util.tree_map(lambda a: a.shape, tree)
            == jax.tree_util.tree_map(lambda a: a.shape, dict(params)))


def test_every_cca_term_is_in_the_logits(params):
    """Zeroing any one CCA parameter moves the logits far over TOL: the
    convolutions, the temperature, the shifted half of the value."""
    toks = tokens(40)
    model = TransformerLM(toy_cfg())
    base = model.apply({"params": params}, toks[None])[0]
    for name in ("cca_conv0", "cca_conv1", "cca_temp"):
        less = jax.tree_util.tree_map(lambda a: a, dict(params))
        less["block_1"] = dict(less["block_1"], **{
            name: jnp.zeros_like(less["block_1"][name])})
        got = model.apply({"params": less}, toks[None])[0]
        assert float(jnp.abs(got - base).max()) > 50 * TOL, name
    # The shifted half of the value (the projection's last head_dim columns).
    kernel = params["block_0"]["cca_in"]["kernel"]
    less = dict(params, block_0=dict(params["block_0"], cca_in={
        "kernel": kernel.at[:, -64:].set(0.0)}))
    got = model.apply({"params": less}, toks[None])[0]
    assert float(jnp.abs(got - base).max()) > 50 * TOL


# -- the expert layer -----------------------------------------------------------


class _Experts(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, h, r_prev=None, mask=None):
        return routed_experts(self, self.cfg, h, r_prev, mask)


def _layer_params(params, held=None):
    p = {k: v for k, v in params["block_1"].items()
         if k.startswith(("router_", "moe_"))}
    if held is not None:
        idx = np.asarray(held)
        p["moe_in"], p["moe_out"] = p["moe_in"][idx], p["moe_out"][idx]
    return p


def _reference_moe(params, h, r_prev, held=tuple(range(8)), bias=None):
    p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                               _layer_params(params, held))
    if bias is not None:
        p["router_bias"] = bias
    return ref.moe(h, r_prev, p, TOY, "f32", held)


def test_the_layer_matches_the_reference_and_counts_its_tokens(params):
    rng = np.random.default_rng(1)
    h = jnp.asarray(rng.normal(size=(2, 9, 256)), jnp.float32)
    r_prev = jnp.asarray(rng.normal(size=(2, 9, 32)), jnp.float32)
    y, r, counts = _Experts(toy_cfg()).apply(
        {"params": _layer_params(params)}, h, r_prev)
    want, r_want = _reference_moe(
        params, h.reshape(18, 256), r_prev.reshape(18, 32))
    assert float(jnp.abs(y.reshape(18, 256) - want).max()) < 1e-5
    assert float(jnp.abs(r.reshape(18, 32) - r_want).max()) < 1e-5
    assert int(counts.sum()) == 18 and counts.shape == (8,)


@pytest.mark.parametrize("expert", [0, 5])
def test_dropless_with_every_token_on_one_expert(params, expert):
    """A balancing bias that sends all 64 tokens to one expert: nothing is
    dropped, whatever a capacity would have been, and the logits of the
    whole model are the reference's under the same bias."""
    bias = jnp.zeros(8).at[expert].set(100.0)
    h = jnp.asarray(np.random.default_rng(2).normal(size=(1, 64, 256)),
                    jnp.float32)
    lp = dict(_layer_params(params), router_bias=bias)
    y, _, counts = _Experts(toy_cfg()).apply({"params": lp}, h, None)
    want, _ = _reference_moe(params, h[0], None, bias=bias)
    assert counts.tolist() == [64 if e == expert else 0 for e in range(8)]
    assert float(jnp.abs(y[0] - want).max()) < 1e-5
    assert float(jnp.abs(y[0]).sum(-1).min()) > 0  # every token got a result
    forced = {k: (dict(v, router_bias=bias) if k.startswith("block_") else v)
              for k, v in params.items()}
    toks = tokens(64, seed=3)
    got = TransformerLM(toy_cfg()).apply({"params": forced}, toks[None])[0]
    assert float(jnp.abs(got - ref.logits(forced, toks, TOY)).max()) < TOL


def test_two_chips_shares_add_up_to_the_whole_layer(params):
    """The share test of the model-configs guide, section 4: what a chip
    holding experts 0-3 and a chip holding experts 4-7 compute, each routing
    over all 8, adds up to the uncut reference's result for the layer."""
    h = jnp.asarray(np.random.default_rng(4).normal(size=(1, 48, 256)),
                    jnp.float32)
    parts, routed = [], 0
    for held in ((0, 1, 2, 3), (4, 5, 6, 7)):
        cfg = toy_cfg(experts_held=list(held))
        assert cfg.experts_held == held  # a JSON list becomes a tuple
        y, _, counts = _Experts(cfg).apply(
            {"params": _layer_params(params, held)}, h, None)
        assert counts.shape == (4,)
        parts.append(y[0])
        routed += int(counts.sum())
        # The reference, given the same share, gives the same part.
        part, _ = _reference_moe(params, h[0], None, held=held)
        assert float(jnp.abs(y[0] - part).max()) < 1e-5
    whole, _ = _reference_moe(params, h[0], None)
    assert routed == 48
    assert float(jnp.abs(parts[0] + parts[1] - whole).max()) < 1e-5
    # A token's result comes from exactly one share.
    assert bool(((jnp.abs(parts[0]).sum(-1) > 0)
                 ^ (jnp.abs(parts[1]).sum(-1) > 0)).all())


def test_masked_tokens_reach_no_expert(params):
    h = jnp.asarray(np.random.default_rng(5).normal(size=(4, 1, 256)),
                    jnp.float32)
    mask = jnp.asarray([[True], [False], [True], [False]])
    y, _, counts = _Experts(toy_cfg()).apply(
        {"params": _layer_params(params)}, h, None, mask)
    full, _, _ = _Experts(toy_cfg()).apply(
        {"params": _layer_params(params)}, h, None)
    assert int(counts.sum()) == 2
    assert float(jnp.abs(y[1]).max()) == 0 and float(jnp.abs(y[3]).max()) == 0
    assert float(jnp.abs(y[0] - full[0]).max()) < 1e-6


# -- what the config gained, on the block that is not CCA -----------------------


PLAIN = dict(vocab_size=64, d_model=96, num_heads=4, num_kv_heads=2,
             num_layers=2, d_ff=128, max_seq_len=32, position="rope",
             use_bias=False)


@pytest.mark.parametrize("over", [
    {}, {"head_dim": 32}, {"head_dim": 32, "rope_fraction": 0.5},
    {"tie_embeddings": True},
], ids=["as-before", "latent-128", "half-rotated", "tied"])
def test_cached_prefill_gives_the_uncached_logits(over):
    """``head_dim``, ``rope_fraction`` and ``tie_embeddings`` on the plain
    attention block: the dense cached branch agrees with the uncached
    forward, and ``logit_rows`` hands back exactly the row it names."""
    cfg = TransformerConfig(**dict(PLAIN, **over), compute_dtype=jnp.float32)
    model = TransformerLM(cfg)
    toks = jnp.asarray(tokens(11, seed=6) % 64)[None]
    p = model.init(jax.random.PRNGKey(1), toks)["params"]
    assert ("lm_head" in p) != cfg.tie_embeddings
    assert p["block_0"]["qkv"]["kernel"].shape == (96, (4 + 2 * 2) * cfg.dh)
    want = model.apply({"params": p}, toks)
    got, cache = model.apply({"params": p}, toks,
                             cache=init_cache(cfg, 1, 32))
    assert cache["layers"][0]["k"].shape == (1, 2, 32, cfg.dh)
    assert float(jnp.abs(got - want).max()) < 1e-5
    one, _ = model.apply({"params": p}, toks, cache=init_cache(cfg, 1, 32),
                         logit_rows=jnp.asarray([7]))
    assert one.shape == (1, 1, 64)
    assert float(jnp.abs(one[0, 0] - want[0, 7]).max()) < 1e-5
    with pytest.raises(ValueError, match="cached branches"):
        model.apply({"params": p}, toks, logit_rows=jnp.asarray([7]))


def test_partial_rope_rotates_the_leading_dimensions_only():
    x = jnp.asarray(np.random.default_rng(7).normal(size=(1, 5, 2, 16)),
                    jnp.float32)
    cos, sin = rope_tables(8, 5, 10000.0)
    y = apply_rope(x, cos, sin)
    np.testing.assert_array_equal(np.asarray(y[..., 8:]),
                                  np.asarray(x[..., 8:]))
    whole_cos, whole_sin = rope_tables(8, 5, 10000.0)
    np.testing.assert_allclose(
        np.asarray(y[..., :8]),
        np.asarray(apply_rope(x[..., :8], whole_cos, whole_sin)), atol=1e-6)
    assert float(jnp.abs(y[:, 1:, :, :8] - x[:, 1:, :, :8]).max()) > 1e-3


# -- validation -----------------------------------------------------------------


@pytest.mark.parametrize("over,exc,match", [
    ({"head_dim": 0}, ValueError, "head_dim"),
    ({"experts_held": [0, 8]}, ValueError, "experts_held"),
    ({"experts_held": [3, 1]}, ValueError, "experts_held"),
    ({"experts_held": []}, ValueError, "experts_held"),
    ({"num_experts": 0, "experts_held": [0]}, ValueError, "num_experts"),
    ({"experts_per_token": 2}, ValueError, "picks one expert"),
    ({"expert_width": 0}, ValueError, "expert_width"),
    ({"cca_time1": None}, ValueError, "go together"),
    ({"cca_time0": 0}, ValueError, "cca_time0"),
    ({"num_kv_heads": 1}, ValueError, "even"),
    ({"rope_fraction": 0.0}, ValueError, "rope_fraction"),
    ({"rope_fraction": 0.3}, ValueError, "rope_fraction"),
    ({"attention_window": 64}, CcaUnsupported, "attention_window"),
    ({"kv_cache_dtype": "int8"}, CcaUnsupported, "kv_cache_dtype"),
    ({"weight_dtype": "int8"}, CcaUnsupported, "weight_dtype"),
    ({"attention": "flash"}, CcaUnsupported, "dense"),
    ({"eva_window": 32, "eva_chunk": 4, "num_kv_heads": 4}, CcaUnsupported,
     "eva_window"),
    ({"cca_time0": None, "cca_time1": None, "eva_window": 32, "eva_chunk": 4,
      "num_kv_heads": 4, "num_experts": 0, "head_dim": 32}, ValueError,
     "latent"),
], ids=lambda v: None if not isinstance(v, dict) else "-".join(v))
def test_the_config_refuses_by_name(over, exc, match):
    with pytest.raises(exc, match=match):
        toy_cfg(**over)
    assert issubclass(CcaUnsupported, ValueError)
    assert not issubclass(CcaUnsupported, EvaUnsupported)
