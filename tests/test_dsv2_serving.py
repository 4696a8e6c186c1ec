"""DeepSeek-V2-Lite on the normal serving path (``SlotEngine``, its paged
pool and prefix cache, the scheduler behind ``tools/serve_lm.build_stack``),
held to the plain float32 reference's FULL forward
(``benchmarks/reference_dsv2.py``, the expanded form) at the toy size of
``tests/test_dsv2_model.py``: prefill (absorbed at the narrow buckets,
expanded at whole chunks), prefix adoption of latent pages and decode by the
absorbed kernel through the page table give, on LOGITS, what the reference
gives for the whole sequence; a pool row is one latent row a position; what
latent rows are not extended to refuses by name.

TOL and its reason are ``tests/test_dsv2_model.py``'s.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_dsv2 as ref
from benchmarks import weights_dsv2
from distributed_tensorflow_tpu.models.transformer import (
    SlotStateUnsupported,
    TransformerConfig,
)
from distributed_tensorflow_tpu.obs import trace
from distributed_tensorflow_tpu.ops.attention import (
    paged_decode_attention,
    paged_row_write,
)
from distributed_tensorflow_tpu.serve.engine import (
    ShardedSlotEngine,
    SlotEngine,
)
from tests.test_dsv2_model import _FILE, TOL, TOY, tokens, toy_cfg
from tests.test_zaya_serving import LogitSpy, serve_logits

pytestmark = [pytest.mark.serve, pytest.mark.paged]
SERVE = _FILE["toy"]["serve_config"]


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def params():
    return weights_dsv2.make_params(TOY, 7, jnp.float32)


def make_engine(params, **kw):
    kw = dict(dict(slots=3, max_len=SERVE["serve_max_len"],
                   prefill_len=SERVE["prefill_len"], page_size=8,
                   prefill_buckets=(8, 16)), **kw)
    return SlotEngine(toy_cfg(), params, **kw)


def reference_rows(params, prompt, toks):
    seq = np.concatenate([prompt, np.asarray(toks[:-1], np.int32)])
    return np.asarray(ref.logits(params, seq, TOY))[len(prompt) - 1:]


def _spy(monkeypatch):
    return LogitSpy(monkeypatch, vocab=TOY["vocab_size"])


# -- the pool ----------------------------------------------------------------------


def test_a_pool_row_is_one_latent_row(params):
    eng = make_engine(params)
    pool = eng.pool
    assert all(set(layer) == {"lat"} for layer in pool.layers)
    assert pool.layers[0]["lat"].shape == (pool.num_pages, 1, 8, 128)
    # c (112) | k_pe (16): 128 lanes a position a layer, three layers.
    assert pool.bytes_per_token == 3 * 128 * 4
    assert eng.decode_path == "table" and eng.prefill_path == "latent"
    assert eng.decode_kernel_form == "group"
    assert eng.prefix is not None


# -- prefill, chunked prefill, paged decode ---------------------------------------


@pytest.mark.parametrize("p,new,ps", [(3, 12, 8), (16, 6, 8), (45, 9, 8),
                                      (150, 7, 8), (45, 9, 4)],
                         ids=["bucket-8", "bucket-16", "one-shot-64",
                              "chunked", "off-tile-pages"])
def test_prefill_then_paged_decode_matches_reference(params, monkeypatch, p,
                                                     new, ps):
    spy = _spy(monkeypatch)
    eng = make_engine(params, page_size=ps)
    prompt = tokens(p, seed=p)
    slot = eng.acquire_slot()
    toks, got = serve_logits(eng, spy, slot, prompt, new)
    assert len(toks) == new
    assert np.abs(got - reference_rows(params, prompt, toks)).max() < TOL
    assert eng.stats["prefill_chunks"] == (3 if p == 150 else 0)
    eng.release(slot)


def test_a_document_asked_again_adopts_its_latent_pages(params, monkeypatch):
    """The second ask of a document prefills only its tail over the pages
    the first left in the prefix cache, and its logits are the reference's
    (and the first ask's, where the two prompts agree)."""
    spy = _spy(monkeypatch)
    eng = make_engine(params)
    doc = tokens(120, seed=21)
    rows = []
    for ask, tail in enumerate((tokens(9, seed=22), tokens(5, seed=23))):
        prompt = np.concatenate([doc, tail])
        slot = eng.acquire_slot()
        matched0 = eng.stats["prefix_tokens_matched"]
        toks, got = serve_logits(eng, spy, slot, prompt, 6)
        assert np.abs(got - reference_rows(params, prompt, toks)).max() < TOL
        matched = eng.stats["prefix_tokens_matched"] - matched0
        assert matched == (0 if ask == 0 else 120)
        rows.append(got)
        eng.release(slot)
    # The first ask chunked its 129 tokens (64, 64, then the last 8), the
    # second prefilled its tail alone, in one call.
    assert eng.stats["prefill_chunks"] == 3


def test_both_prefill_forms_are_taken_and_said(params):
    """Chunks of 64 expand, the buckets of 8 and 16 absorb, and the spans say
    which, and how many latent rows a layer each expanded: those below the
    chunk's end rounded up to the kernel's key block (the whole row of 256
    f32 rows is one block at the toy's size), none where absorbed, and
    ``stats["mla_rows_expanded"]`` their sum; a decode round notes the
    latent positions its kernel read."""
    eng = make_engine(params)
    expanded0 = eng.stats["mla_rows_expanded"]
    t_lo = max([r[1] for name in ("engine.round", "engine.start",
                                  "engine.prefill_chunk")
                for r in trace.closed(name) or []], default=0.0)
    slot = eng.acquire_slot()
    eng.start(slot, tokens(140, seed=5), max_new_tokens=4)
    while eng.active[slot] or eng.prefilling[slot]:
        eng.step()
    eng.release(slot)
    slot = eng.acquire_slot()
    eng.start(slot, tokens(11, seed=6), max_new_tokens=3)
    while eng.active[slot]:
        eng.step()
    chunks = [r[2] for r in trace.closed("engine.prefill_chunk", t_lo,
                                         float("inf")) if r[0] > t_lo]
    assert [c["mla_form"] for c in chunks] == ["expanded", "expanded",
                                               "absorbed"]
    # 140 positions: two whole chunks, then the last 16 (from 124).
    assert [c["latent_rows_attended"] for c in chunks] == [64, 128, 140]
    starts = [r[2] for r in trace.closed("engine.start", t_lo, float("inf"))
              if r[0] > t_lo and r[2].get("mla_form")]
    assert [s["mla_form"] for s in starts] == ["absorbed"]
    assert starts[0]["latent_rows_attended"] == 16
    assert [c["latent_rows_expanded"] for c in chunks] == [256, 256, 0]
    assert starts[0]["latent_rows_expanded"] == 0
    assert eng.stats["mla_rows_expanded"] - expanded0 == 512
    rounds = [r[2] for r in trace.closed("engine.round", t_lo, float("inf"))
              if r[0] > t_lo and r[2].get("active")]
    assert rounds and all(
        r["latent_rows"] == 3 * (r["live_tokens"] + r["active"])
        for r in rounds)
    assert all(r["experts_total"] == 2 * 8 for r in rounds)


# -- the kernels over one leaf -------------------------------------------------------


def test_the_paged_kernel_reads_a_latent_row_as_key_and_value():
    """``paged_decode_attention`` with no value leaf: 4 query rows of a slot
    against its rows, each row its own value, scaled as asked."""
    rng = np.random.default_rng(0)
    pages, ps, width, slots, pps = 9, 8, 128, 2, 4
    leaf = jnp.asarray(rng.normal(size=(pages, 1, ps, width)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(slots, 1, 4, width)), jnp.float32)
    tables = jnp.asarray([[3, 4, 5, 6], [8, 1, 2, 7]], jnp.int32)
    lens = jnp.asarray([27, 9], jnp.int32)
    out = np.asarray(paged_decode_attention(q, leaf, None, tables, lens,
                                            scale=0.3))
    for b in range(slots):
        rows = np.asarray(leaf)[np.asarray(tables[b])].reshape(-1, width)
        rows = rows[: int(lens[b])]
        s = np.asarray(q[b, 0]) @ rows.T * 0.3
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        np.testing.assert_allclose(out[b, 0], p @ rows, atol=2e-5)


def test_the_row_write_writes_one_leaf_and_skips_masked_lanes():
    rng = np.random.default_rng(1)
    leaf = jnp.asarray(rng.normal(size=(6, 1, 8, 128)), jnp.float32)
    rows = jnp.asarray(rng.normal(size=(3, 1, 128)), jnp.float32)
    out = np.asarray(paged_row_write(
        leaf, None, rows, None, jnp.asarray([2, 0, 5]), jnp.asarray([7, 3, 0]),
        jnp.asarray([True, False, True])))
    want = np.array(leaf)
    want[2, 0, 7] = rows[0, 0]
    want[5, 0, 0] = rows[2, 0]
    np.testing.assert_array_equal(out, want)


# -- what latent rows are not extended to -------------------------------------------


@pytest.mark.parametrize("kw,match", [
    ({"spec_k": 2}, "speculation.*latent attention"),
    ({"spec_k": 2, "spec_branches": 2}, "speculation.*latent attention"),
], ids=["speculation", "tree-speculation"])
def test_the_engine_refuses_by_name(params, kw, match):
    with pytest.raises(SlotStateUnsupported, match=match):
        make_engine(params, **kw)


def test_the_sharded_engine_refuses(params):
    with pytest.raises(SlotStateUnsupported, match="ShardedSlotEngine"):
        ShardedSlotEngine(toy_cfg(), params, tp=2, slots=2, max_len=64,
                          prefill_len=16, page_size=8)


def test_int8_pages_refuse_through_the_stack(params):
    """``--kv_dtype int8`` reaches the config, which refuses it by name."""
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
    try:
        from serve_lm import build_stack
    finally:
        sys.path.pop(0)
    from distributed_tensorflow_tpu.config import ServeConfig

    cfg = ServeConfig(slots=2, serve_max_len=64, prefill_len=16, page_size=8,
                      kv_dtype="int8", port=0, slo="off")
    with pytest.raises(SlotStateUnsupported, match="int8"):
        build_stack(cfg, toy_cfg(), params)


@pytest.mark.parametrize("call", ["export_slot", "export_slot_meta",
                                  "import_slot", "adopt_imported_slot"])
def test_handoff_refuses_by_name(params, call):
    eng = make_engine(params)
    slot = eng.acquire_slot()
    eng.start(slot, tokens(5), max_new_tokens=4)
    args = {"export_slot": (slot,), "export_slot_meta": (slot,),
            "import_slot": (slot, {}),
            "adopt_imported_slot": (slot, {}, [])}[call]
    with pytest.raises(SlotStateUnsupported, match="latent attention"):
        getattr(eng, call)(*args)


# -- the whole stack -------------------------------------------------------------------


def test_build_stack_serves_it_through_the_scheduler(params):
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
    try:
        from serve_lm import build_stack
    finally:
        sys.path.pop(0)
    from distributed_tensorflow_tpu.config import ServeConfig
    from distributed_tensorflow_tpu.serve.scheduler import Completion, Request

    serve_cfg = ServeConfig(slots=2, serve_max_len=256, prefill_len=64,
                            page_size=8, spec_k=0, port=0, slo="off")
    jax.config.update("jax_default_matmul_precision", "highest")
    engine, scheduler, _, server = build_stack(serve_cfg, toy_cfg(), params)
    try:
        assert type(engine) is SlotEngine and engine.decode_path == "table"
        # The engine's own widths: 32, the widest power of two absorbed
        # over 256 rows (models/transformer.mla_prefill_widths), and the chunk.
        assert engine.prefill_buckets == (32, 64)
        warm = engine.compile_count()
        scheduler.start()
        prompt = tokens(23, seed=40)
        out = scheduler.submit(Request(
            prompt=tuple(int(t) for t in prompt), max_new_tokens=10,
            temperature=0.0)).result(timeout=120)
        assert isinstance(out, Completion) and len(out.tokens) == 10
        assert engine.compile_count() == warm
    finally:
        scheduler.stop()
        server.server_close()
        jax.config.update("jax_default_matmul_precision", None)
    want = reference_rows(params, prompt, list(out.tokens))
    assert list(want.argmax(-1)) == list(out.tokens)


def test_the_configuration_file_states_the_cut():
    """Every published width, all 64 experts, top-6, both shared experts
    (one MLP of 2 x 1408) and the whole vocabulary; 5 of 27 layers."""
    tc = _FILE["transformer_config"]
    assert (tc["d_model"], tc["num_heads"], tc["kv_lora_rank"],
            tc["qk_nope_head_dim"], tc["qk_rope_head_dim"],
            tc["v_head_dim"]) == (2048, 16, 512, 128, 64, 128)
    assert (tc["num_experts"], tc["experts_per_token"], tc["expert_width"],
            tc["shared_expert_width"], tc["d_ff"], tc["vocab_size"]) == (
        64, 6, 1408, 2 * 1408, 10944, 102400)
    assert _FILE["n_shared_experts"] == 2 and _FILE["num_hidden_layers"] == 5
    assert _FILE["published"]["num_hidden_layers"] == 27
    assert set(_FILE["reduced"]) == {"num_hidden_layers",
                                     "max_position_embeddings"}
    assert TransformerConfig(**tc).mla


def test_each_limit_lies_between_the_readings_it_was_set_from():
    """The cell's limits stand over every program reading and under the
    int8 control's (the noise scale), under every fault's and the fp8
    control's (the widest gap), each with room."""
    import json

    path = os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                        "cells", "dsv2-lite.docs-closed.json")
    with open(path) as fh:
        cell = json.load(fh)
    lim, rd = cell["limits"], cell["readings"]
    ns = rd["served_noise_scale"]
    assert max(ns["program"]) * 1.3 < lim["served_noise_scale"]
    assert lim["served_noise_scale"] * 1.3 < min(
        ns["controls_seed_2147490036"].values())
    gap = rd["served_gap_max"]
    assert max(gap["program"]) * 2 < lim["served_gap_max"]
    assert all(v > lim["served_gap_max"]
               for k, v in gap["controls_seed_2147490036"].items()
               if k != "int8")
    assert lim["checked_tokens_min"] < min(
        rd["checked_tokens"]["program_45s"])
