"""Weight-only quantized serving + rejection-sampling speculation.

Two contracts from ISSUE 11, tested separately because they are lossy in
different senses:

* **Quantized weights change VALUES, never plumbing.** The quantized
  engine's fast path (paged+prefix+spec churn) must serve the tokens of
  the sequential oracle on the SAME int trees (the ``test_paged_kv.py``
  anchor re-run on them), load from a
  ``tools/quantize_lm.py`` bundle bit-exactly, and stay within an
  ACCURACY floor of the native model (argmax agreement + eval-loss
  delta) — never bit-parity with it, since rounding is the whole point.

* **Rejection-sampling verify changes LATENCY, never the distribution.**
  The emitted-token marginal of the RS verify step must match plain
  filtered sampling on a small vocab (chi-square), whatever the drafts.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_tpu.models.quant import (
    QUANT_KERNEL_RE,
    dequantize_int4,
    dequantize_int8,
    dequantize_lm_params,
    pack_int4,
    quantize_int4_groupwise,
    quantize_int8_channelwise,
    quantize_lm_params,
    tree_bytes,
    unpack_int4,
)
from distributed_tensorflow_tpu.models.transformer import (
    TransformerConfig,
    TransformerLM,
)
from distributed_tensorflow_tpu.serve.engine import SlotEngine

pytestmark = [pytest.mark.serve, pytest.mark.quant]

CFG = TransformerConfig(
    vocab_size=64,
    d_model=32,
    num_heads=4,
    num_layers=2,
    d_ff=64,
    max_seq_len=48,
    compute_dtype=jnp.float32,
)


def _qcfg(mode, gs=0):
    from dataclasses import replace

    return replace(CFG, weight_dtype=mode, quant_group_size=gs)


@pytest.fixture(scope="module")
def params():
    return TransformerLM(CFG).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]


# -- pack / scale units ------------------------------------------------------


def test_int4_pack_unpack_roundtrip():
    rng = np.random.default_rng(0)
    q = rng.integers(-8, 8, size=(16, 6)).astype(np.int32)
    packed = pack_int4(jnp.asarray(q))
    assert packed.shape == (8, 6) and packed.dtype == jnp.uint8
    assert np.array_equal(np.asarray(unpack_int4(packed)), q)
    # A tree-wide float cast (generate's cast_params) must round-trip:
    # every packed byte is exact in f32/bf16 and unpack re-casts.
    assert np.array_equal(
        np.asarray(unpack_int4(packed.astype(jnp.float32))), q)


def test_int4_pack_rejects_odd_input_dim():
    with pytest.raises(ValueError, match="even"):
        pack_int4(jnp.zeros((3, 2), jnp.int32))


def test_int8_channelwise_error_bound():
    rng = np.random.default_rng(1)
    w = jnp.asarray(rng.normal(size=(32, 12)) * rng.uniform(0.1, 3.0, 12))
    q, scale = quantize_int8_channelwise(w)
    assert q.dtype == jnp.int8 and scale.shape == (12,)
    # Symmetric rounding: per-element error is at most half a step.
    err = np.abs(np.asarray(dequantize_int8(q, scale)) - np.asarray(w))
    assert np.all(err <= np.asarray(scale)[None, :] * 0.5 + 1e-7)


def test_int4_groupwise_error_bound():
    rng = np.random.default_rng(2)
    w = jnp.asarray(rng.normal(size=(32, 12)))
    q, gscale = quantize_int4_groupwise(w, 8)
    assert q.shape == (16, 12) and gscale.shape == (4, 12)
    err = np.abs(np.asarray(dequantize_int4(q, gscale, 8)) - np.asarray(w))
    step = np.repeat(np.asarray(gscale), 8, axis=0)
    assert np.all(err <= step * 0.5 + 1e-7)


def test_int8_scale_factors_out_of_matmul():
    """The §18 exactness argument, numerically: running the contraction on
    the raw int8 values and scaling the RESULT equals the matmul against
    the dequantized weight (same floating op count per addend — any
    difference is epsilon-level reassociation, not quantization)."""
    rng = np.random.default_rng(3)
    w = jnp.asarray(rng.normal(size=(24, 10)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(5, 24)), jnp.float32)
    q, scale = quantize_int8_channelwise(w)
    fused = (x @ q.astype(jnp.float32)) * scale
    reference = x @ dequantize_int8(q, scale)
    np.testing.assert_allclose(
        np.asarray(fused), np.asarray(reference), rtol=1e-5, atol=1e-5)


# -- param-tree transform ----------------------------------------------------


@pytest.mark.parametrize("mode,gs", [("int8", 0), ("int4", 16)])
def test_quantize_lm_params_structure_and_template(params, mode, gs):
    """Quantized trees must load into the quantized model's OWN init
    template (the bundle-restore path is structural), and only the four
    matmul kernels change representation."""
    qparams = quantize_lm_params(params, mode, group_size=gs, hp_dtype=None)
    template = TransformerLM(_qcfg(mode, gs)).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    t_q = jax.tree_util.tree_structure(qparams)
    t_t = jax.tree_util.tree_structure(template)
    assert t_q == t_t
    for got, want in zip(
        jax.tree_util.tree_leaves(qparams), jax.tree_util.tree_leaves(template)
    ):
        assert got.shape == want.shape and got.dtype == want.dtype
    # High-precision leaves survive untouched with hp_dtype=None...
    assert qparams["tok_embed"]["embedding"].dtype == jnp.float32
    assert qparams["lm_head"]["kernel"].dtype == jnp.float32
    # ...and cast with the default bf16 storage dtype.
    qbf = quantize_lm_params(params, mode, group_size=gs)
    assert qbf["tok_embed"]["embedding"].dtype == jnp.bfloat16
    assert tree_bytes(qbf) < tree_bytes(params)


@pytest.mark.parametrize("mode,gs", [("int8", 0), ("int4", 16)])
def test_dequantize_lm_params_round_trip(params, mode, gs):
    """dequantize(quantize(params)) loads back into the UNQUANTIZED model
    and its logits sit near the quantized forward's (the quality-eval
    reference path)."""
    qparams = quantize_lm_params(params, mode, group_size=gs, hp_dtype=None)
    deq = dequantize_lm_params(qparams, mode, group_size=gs)
    assert jax.tree_util.tree_structure(deq) == (
        jax.tree_util.tree_structure(params))
    x = jnp.arange(8, dtype=jnp.int32)[None, :] % CFG.vocab_size
    ref = TransformerLM(CFG).apply({"params": deq}, x)
    got = TransformerLM(_qcfg(mode, gs)).apply({"params": qparams}, x)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), rtol=1e-4, atol=1e-4)


def test_quant_kernel_pattern_scope(params):
    """Exactly the per-block matmuls match — embeddings, norms, lm_head
    and biases must never quantize."""
    from flax import traverse_util

    names = {"/".join(p) for p in traverse_util.flatten_dict(params)}
    hit = {n for n in names if QUANT_KERNEL_RE.search(n)}
    assert hit == {
        f"block_{b}/{m}/kernel"
        for b in range(CFG.num_layers)
        for m in ("qkv", "proj", "mlp_in", "mlp_out")
    }


# -- model-level accuracy floors --------------------------------------------


@pytest.mark.parametrize(
    "mode,gs,min_agree,max_xent_delta",
    [("int8", 0, 0.95, 0.02), ("int4", 8, 0.70, 0.40)],
)
def test_quantized_model_accuracy_floor(params, mode, gs, min_agree,
                                        max_xent_delta):
    """ACCURACY floor, not bit-parity: int8 must track the native model's
    argmax and eval loss closely, int4 more loosely (16 levels per group).
    These are the CPU-sized analogs of the bench's eval-loss-delta quality
    ceilings."""
    qparams = quantize_lm_params(params, mode, group_size=gs, hp_dtype=None)
    rng = np.random.default_rng(5)
    batch = jnp.asarray(
        rng.integers(1, CFG.vocab_size, size=(8, 32)), jnp.int32)
    ref = TransformerLM(CFG).apply({"params": params}, batch)
    got = TransformerLM(_qcfg(mode, gs)).apply({"params": qparams}, batch)
    agree = float(jnp.mean(
        (jnp.argmax(ref, -1) == jnp.argmax(got, -1)).astype(jnp.float32)))
    assert agree >= min_agree, (
        f"{mode}: argmax agreement {agree:.3f} under floor {min_agree}")

    def xent(logits):
        logp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), -1)
        tgt = batch[:, 1:]
        return float(-jnp.mean(
            jnp.take_along_axis(logp, tgt[..., None], -1)))

    delta = abs(xent(got) - xent(ref))
    assert delta <= max_xent_delta, (
        f"{mode}: eval-loss delta {delta:.4f} over ceiling {max_xent_delta}")


# -- engine churn parity on quantized trees ----------------------------------


def _drive(engine, requests):
    engine.warmup()
    base = engine.compile_count()
    outs = {i: [] for i in range(len(requests))}
    pending = list(range(len(requests)))
    slot2req = {}
    while pending or slot2req:
        while pending:
            slot = engine.acquire_slot()
            if slot is None:
                break
            i = pending.pop(0)
            prompt, kwargs = requests[i]
            first, finished = engine.start(slot, prompt, **kwargs)
            if first is not None:
                outs[i].append(first)
            if first is not None and finished:
                engine.release(slot)
            else:
                slot2req[slot] = i
        if not slot2req:
            continue
        toks, valid, done = engine.step()
        for k in range(toks.shape[0]):
            for slot, i in slot2req.items():
                if valid[k, slot]:
                    outs[i].append(int(toks[k, slot]))
        for slot in list(slot2req):
            if done[slot]:
                engine.release(slot)
                del slot2req[slot]
    assert engine.compile_count() == base, (
        f"recompiled after warmup: {engine.compile_count()} != {base}")
    return [tuple(outs[i]) for i in range(len(requests))]


def _churn_requests():
    rng = np.random.default_rng(7)
    fam_a = rng.integers(1, 64, 20).tolist()
    fam_b = rng.integers(1, 64, 12).tolist()
    prompts = (
        [fam_a + rng.integers(1, 64, int(t)).tolist() for t in (2, 4, 3)]
        + [fam_b + rng.integers(1, 64, int(t)).tolist() for t in (5, 2)]
        + [rng.integers(1, 64, int(n)).tolist() for n in (3, 9, 17, 23, 6)]
    )
    budgets = [6, 9, 12, 5, 8, 14, 4, 7, 10, 3]
    return [(p, {"max_new_tokens": b}) for p, b in zip(prompts, budgets)]


@pytest.mark.spec
@pytest.mark.parametrize("mode,gs", [("int8", 0), ("int4", 16)])
def test_churn_parity_across_layouts_quantized(params, mode, gs):
    """The ``test_paged_kv.py`` churn anchor on quantized trees: given the
    SAME quantized weights, the decode fast path (paged + prefix + spec)
    must be byte-identical to ``build_generate_fn`` serving each request
    alone — quantization changes the model, never the engine's
    losslessness."""
    from tests.test_paged_kv import _sequential

    qparams = quantize_lm_params(params, mode, group_size=gs, hp_dtype=None)
    cfg = _qcfg(mode, gs)
    requests = _churn_requests()
    fast = SlotEngine(cfg, qparams, slots=4, max_len=48, prefill_len=26,
                      page_size=8, prefix_cache=True, spec_k=4)
    baseline = _sequential(cfg, qparams, requests)
    got = _drive(fast, requests)
    for i in range(len(requests)):
        assert list(got[i]) == baseline[i], (
            f"{mode} paged+prefix+spec diverged from build_generate_fn on "
            f"request {i}: {got[i]} != {baseline[i]}")


@pytest.mark.spec
def test_quantized_engine_sampled_spec_rounds(params):
    """Sampled lanes on a quantized engine run the rejection-sampling
    verify variant (no plain-decode fallback) with zero recompiles."""
    qparams = quantize_lm_params(params, "int8", hp_dtype=None)
    engine = SlotEngine(_qcfg("int8"), qparams, slots=2, max_len=48,
                        prefill_len=24, page_size=8, spec_k=3)
    rng = np.random.default_rng(9)
    requests = [
        (rng.integers(1, 64, 6).tolist(),
         {"max_new_tokens": 8, "temperature": 0.9, "top_k": 16, "seed": 3}),
        (rng.integers(1, 64, 9).tolist(),
         {"max_new_tokens": 6, "temperature": 1.2, "top_p": 0.9, "seed": 4}),
    ]
    outs = _drive(engine, requests)
    assert [len(o) for o in outs] == [8, 6]
    assert all(0 <= t < CFG.vocab_size for o in outs for t in o)
    assert engine.stats["spec_rounds_sampled"] > 0, (
        "sampled lanes must take the rejection-sampling verify path")


# -- rejection-sampling distribution parity ----------------------------------


def _rs_first_token_counts(filtered, drafts, n, base_seed):
    """Marginal of the FIRST emitted token over ``n`` independent RS
    verify calls (vmapped over seed)."""
    from distributed_tensorflow_tpu.models.decoding import (
        rejection_verify_row,
    )

    def one(seed):
        emitted, _ = rejection_verify_row(filtered, drafts, seed, 0)
        return emitted[0]

    toks = jax.vmap(one)(base_seed + jnp.arange(n))
    return np.bincount(np.asarray(toks), minlength=filtered.shape[-1])


@pytest.mark.spec
@pytest.mark.parametrize("draft_kind", ["greedy", "adversarial"])
def test_rejection_sampling_matches_plain_sampled_marginal(draft_kind):
    """Losslessness of the RS verify step (Leviathan/Chen 2023): whatever
    the drafts propose — the target's own argmax or the LEAST likely
    tokens — the emitted marginal must equal plain filtered sampling.
    Chi-square on a small vocab over the shared ``filter_logits_batched``
    distribution; the filter being shared is what makes spec and plain
    sampled lanes identical by construction."""
    from distributed_tensorflow_tpu.models.decoding import (
        filter_logits_batched,
    )

    vocab, k, n = 12, 3, 20000
    rng = np.random.default_rng(13)
    logits = jnp.asarray(rng.normal(size=(k + 1, vocab)) * 1.5, jnp.float32)
    filtered = filter_logits_batched(
        logits,
        jnp.full((k + 1,), 0.9, jnp.float32),
        jnp.full((k + 1,), 8, jnp.int32),
        jnp.full((k + 1,), 0.95, jnp.float32),
    )
    if draft_kind == "greedy":
        drafts = jnp.argmax(filtered[:k], -1).astype(jnp.int32)
    else:
        drafts = jnp.argmin(filtered[:k], -1).astype(jnp.int32)
    counts = _rs_first_token_counts(filtered, drafts, n, base_seed=1000)
    p = np.asarray(jax.nn.softmax(filtered[0]))
    expected = p * n
    mask = expected > 5  # chi-square validity; filtered-out bins are ~0
    assert counts[~mask].sum() <= n * 0.01
    chi2 = float(((counts[mask] - expected[mask]) ** 2
                  / expected[mask]).sum())
    # df = mask.sum() - 1 ≈ 7; p=0.001 critical value for df=10 is 29.6 —
    # generous headroom against binomial noise, tight against any real
    # distribution shift (a 10% skew on one bin alone adds ~40).
    assert chi2 < 35.0, f"{draft_kind}: chi-square {chi2:.1f} (df≈{mask.sum() - 1})"


def test_rejection_sampling_accepts_good_drafts():
    """Greedy drafts from a peaked target mostly accept (the speedup
    exists); adversarial drafts mostly reject (the correctness exists)."""
    from distributed_tensorflow_tpu.models.decoding import (
        rejection_verify_row,
    )

    vocab, k = 12, 3
    peaked = jnp.full((k + 1, vocab), -8.0, jnp.float32)
    peaked = peaked.at[jnp.arange(k + 1), jnp.arange(k + 1)].set(8.0)
    good = jnp.arange(k, dtype=jnp.int32)
    bad = jnp.arange(k, dtype=jnp.int32) + 5

    def accepts(drafts, seed):
        _, a = rejection_verify_row(peaked, drafts, seed, 0)
        return a

    n = 200
    seeds = jnp.arange(n, dtype=jnp.int32)
    acc_good = np.asarray(jax.vmap(lambda s: accepts(good, s))(seeds))
    acc_bad = np.asarray(jax.vmap(lambda s: accepts(bad, s))(seeds))
    assert float(acc_good.mean()) > 2.9  # near-deterministic target: all k
    assert float(acc_bad.mean()) < 0.1


# -- bundle round-trip -------------------------------------------------------


@pytest.mark.parametrize("mode,gs", [("int8", 0), ("int4", 16)])
def test_quantized_bundle_round_trip(params, tmp_path, mode, gs):
    """tools/quantize_lm.py bundles restore bit-exactly: same cfg quant
    fields, same int values and scales, and the loaded tree serves."""
    from distributed_tensorflow_tpu.train.checkpoint import (
        export_inference_bundle,
        load_lm_bundle,
    )
    from tools.quantize_lm import quantize_bundle

    src = str(tmp_path / "lm.msgpack")
    export_inference_bundle(src, params, metadata={"config": {
        "vocab_size": CFG.vocab_size, "d_model": CFG.d_model,
        "num_heads": CFG.num_heads, "num_layers": CFG.num_layers,
        "d_ff": CFG.d_ff, "max_seq_len": CFG.max_seq_len,
    }})
    dst = str(tmp_path / f"lm.{mode}.msgpack")
    orig_bytes, new_bytes = quantize_bundle(src, dst, mode, gs,
                                            hp_dtype_name="float32")
    assert new_bytes < orig_bytes
    cfg2, params2, meta = load_lm_bundle(dst)
    assert cfg2.weight_dtype == mode
    assert cfg2.quant_group_size == gs
    assert meta["quantized_from"] == "lm.msgpack"
    want = quantize_lm_params(params, mode, group_size=gs, hp_dtype=None)
    for a, b in zip(jax.tree_util.tree_leaves(params2),
                    jax.tree_util.tree_leaves(want)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    # Double-quantization is refused with a pointer at the real source.
    with pytest.raises(SystemExit, match="already quantized"):
        quantize_bundle(dst, str(tmp_path / "x.msgpack"), "int4", 16)
