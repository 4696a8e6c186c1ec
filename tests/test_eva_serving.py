"""EVA attention (EvaByte) on the normal serving path, held to the plain
float32 reference (``benchmarks/reference_evabyte.py``) at toy size on the
CPU: 4 layers, d 256, 4 heads of 64, window 32, chunk 4, page 4, seeded
random weights with non-zero phi, mu and norm offsets. LOGITS are compared,
never tokens: with random weights the best token changes on rounding.

The tolerance and its reason: program and reference both run in float32
with matrix products at ``highest``; they differ in summation order alone
(fused qkv slices, the page-wise softmax, XLA's own reassociation), which
reads 1e-5 on logits of size 5 over 4 layers. TOL = 2e-4 leaves that a
factor of 20 and is 250 times under what bfloat16 linear layers read (0.05:
``test_bf16_in_place_of_f32_fails``), and a term left out reads thousands
of times over it (``test_a_term_left_out_fails``).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_evabyte as ref
from benchmarks import weights_evabyte
from distributed_tensorflow_tpu.models.transformer import (
    EvaUnsupported,
    TransformerConfig,
    TransformerLM,
)
from distributed_tensorflow_tpu.obs import trace
from distributed_tensorflow_tpu.serve.engine import SlotEngine
from distributed_tensorflow_tpu.serve.kv_pool import (
    TRASH_PAGE,
    InsufficientPages,
    PagedKVPool,
    PrefixCache,
)
from tests.test_serve_engine import SyncEngine

pytestmark = [pytest.mark.serve, pytest.mark.paged]

TOL = 2e-4
W, C = 32, 4
TOY = dict(
    vocab_size=320, d_model=256, num_heads=4, num_layers=4, d_ff=512,
    max_seq_len=256, position="rope", rope_theta=100000.0, use_bias=False,
    attention="dense", norm="rms", norm_eps=1e-5, norm_unit_offset=True,
    mlp="swiglu", residual_dtype="float32", fp32_logits=True,
    num_pred_heads=8, eva_window=W, eva_chunk=C,
)


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def params():
    return weights_evabyte.make_params(TOY, 7, jnp.float32)


def toy_cfg(**over):
    return TransformerConfig(**dict(TOY, **over), compute_dtype=jnp.float32)


def tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, 320, n, dtype=np.int32)


# -- (a), (e): the uncached forward -----------------------------------------


def test_uncached_forward_matches_reference_over_four_windows(params):
    toks = tokens(3 * W + 15)
    got = TransformerLM(toy_cfg()).apply(
        {"params": params}, toks[None], pred_heads=True)[0]
    want = ref.logits(params, toks, TOY, heads=True)
    assert got.shape == (len(toks), 8, 320)  # all eight heads' logits
    assert float(jnp.abs(got - want).max()) < TOL
    # Head 0 is what every caller of the model is served.
    served = TransformerLM(toy_cfg()).apply({"params": params}, toks[None])[0]
    np.testing.assert_array_equal(np.asarray(served), np.asarray(got[:, 0]))


def test_bf16_in_place_of_f32_fails(params):
    toks = tokens(3 * W + 15)
    want = ref.logits(params, toks, TOY, heads=True)
    low = ref.logits(params, toks, TOY, heads=True, mode="bf16")
    assert float(jnp.abs(low - want).max()) > 50 * TOL


@pytest.mark.parametrize("kept", [("summaries",), ("mu",)],
                         ids=["mu-left-out", "summaries-left-out"])
def test_a_term_left_out_fails(params, kept):
    toks = tokens(3 * W + 15)
    got = TransformerLM(toy_cfg()).apply(
        {"params": params}, toks[None], pred_heads=True)[0]
    less = ref.logits(params, toks, TOY, heads=True, parts=kept)
    assert float(jnp.abs(got - less).max()) > 10 * TOL


# -- (b): chunked prefill, then decode through the table --------------------


class LogitSpy:
    """The logits the engine's programs pick their tokens from. The engine
    returns tokens alone, so ``jnp.argmax`` is wrapped, while the programs
    are traced, by a host callback that hands over its operand: (V,) from a
    prefill segment, (slots, V) from a decode round."""

    def __init__(self, monkeypatch):
        self.seen = []
        real = jnp.argmax

        def spy(x, *args, **kwargs):
            jax.debug.callback(
                lambda a: self.seen.append(np.array(a)), x, ordered=True)
            return real(x, *args, **kwargs)

        monkeypatch.setattr(jnp, "argmax", spy)

    def take(self):
        jax.effects_barrier()
        out, self.seen = self.seen, []
        return out


def make_engine(params, cls=SlotEngine, **kw):
    kw = dict(dict(slots=3, max_len=4 * W, prefill_len=16, page_size=C), **kw)
    return cls(toy_cfg(), params, **kw)


def serve_logits(eng, spy, slot, prompt, max_new):
    """Run one request alone to its end; (tokens, the logits each token
    was picked from)."""
    spy.take()
    first, _ = eng.start(slot, prompt, max_new_tokens=max_new)
    toks = [] if first is None else [first]
    while eng.active[slot] or eng.prefilling[slot]:
        t, v, _ = eng.step()
        toks += [int(x) for x in t[v[:, slot], slot]]
    rows = [a if a.ndim == 1 else a[slot] for a in spy.take()]
    # Segments before the last pick a token nobody is served.
    return toks, np.stack(rows[-len(toks):])


@pytest.mark.parametrize("p,new", [(3, 75), (70, 50), (2 * W, 40)],
                         ids=["decode-across-two-rolls",
                              "prefill-across-two-windows",
                              "prompt-ends-at-a-window"])
def test_prefill_in_chunks_then_paged_decode_matches_reference(
        params, monkeypatch, p, new):
    spy = LogitSpy(monkeypatch)
    eng = make_engine(params)
    assert eng.decode_path == "table"
    prompt = tokens(p, seed=p)
    slot = eng.acquire_slot()
    toks, got = serve_logits(eng, spy, slot, prompt, new)
    assert len(toks) == new
    seq = np.concatenate([prompt, np.asarray(toks[:-1], np.int32)])
    want = np.asarray(ref.logits(params, seq, TOY))[p - 1:]
    assert np.abs(got - want).max() < TOL
    assert eng.stats["eva_windows_rolled"] == (p + new - 1) // W
    eng.release(slot)
    assert eng.pool.pages_free == eng.pool.pages_allocatable - len(eng.prefix)


def test_the_paged_kernel_reads_the_composed_table(params, monkeypatch):
    """Heads of 128 and pages of 8 f32 rows: the pool's leaves fit
    ``paged_decode_attention`` (interpret mode here), which then walks
    summary pages and window pages as one run of pages."""
    small = dict(TOY, d_model=256, num_heads=2, num_layers=1, eva_window=64,
                 eva_chunk=8)
    p1 = weights_evabyte.make_params(small, 3, jnp.float32)
    spy = LogitSpy(monkeypatch)
    eng = SlotEngine(
        TransformerConfig(**small, compute_dtype=jnp.float32), p1, slots=2,
        max_len=192, prefill_len=32, page_size=8)
    from distributed_tensorflow_tpu.ops.attention import paged_decode_fits

    assert paged_decode_fits(eng.pool.layers[0]["k"])
    prompt = tokens(61, seed=5)
    slot = eng.acquire_slot()
    toks, got = serve_logits(eng, spy, slot, prompt, 12)
    seq = np.concatenate([prompt, np.asarray(toks[:-1], np.int32)])
    want = np.asarray(ref.logits(p1, seq, small))[60:]
    assert np.abs(got - want).max() < TOL
    assert eng.stats["eva_windows_rolled"] == 1


# -- (c): prefix adoption ---------------------------------------------------


def test_adopting_a_prefix_that_ends_mid_window_gives_the_cold_logits(
        params, monkeypatch):
    spy = LogitSpy(monkeypatch)
    doc = tokens(2 * W + 13, seed=11)  # two windows and three pages more
    ask_a = np.concatenate([doc, tokens(7, seed=12)])
    ask_b = np.concatenate([doc, tokens(9, seed=13)])
    cold = make_engine(params)
    s = cold.acquire_slot()
    toks_cold, lg_cold = serve_logits(cold, spy, s, ask_b, 30)

    eng = make_engine(params)
    pool, spw = eng.pool, eng.pool.sum_pages
    a = eng.acquire_slot()
    eng.start(a, ask_a, max_new_tokens=2)
    while eng.prefilling[a]:
        eng.step()
    # Cached of A: both finished windows' summary pages, and the full pages
    # of the window its prompt ends in.
    full = (len(ask_a) - 2 * W) // C
    assert len(eng.prefix) == 2 * spw + full
    b = eng.acquire_slot()
    spy.take()
    first, _ = eng.start(b, ask_b, max_new_tokens=30)
    assert first is not None  # the tail is one segment
    shared_full = (len(doc) - 2 * W) // C
    assert eng.stats["prefix_tokens_matched"] == 2 * W + shared_full * C
    assert eng.stats["eva_summary_pages_adopted"] == 2 * spw
    row_a, row_b = pool.page_tables[a], pool.page_tables[b]
    adopted = 2 * spw + shared_full
    np.testing.assert_array_equal(row_a[:adopted], row_b[:adopted])
    # A, B and the cache hold the adopted pages; B's own tail page and its
    # forming pages are B's alone (its full tail pages, the cache's too).
    assert (pool.refcount[row_b[:adopted]] == 3).all()
    assert pool.refcount[row_b[adopted + 2]] == 1
    assert (pool.refcount[pool.forming_row(b)] == 1).all()
    eng.release(a)
    assert (pool.refcount[row_b[:adopted]] == 2).all()
    toks = [first]
    while eng.active[b]:
        t, v, _ = eng.step()
        toks += [int(x) for x in t[v[:, b], b]]
    rows = [x if x.ndim == 1 else x[b] for x in spy.take()]
    got = np.stack(rows[-len(toks):])
    assert np.abs(got - lg_cold).max() < TOL
    assert toks == toks_cold
    eng.release(b)
    # Only the cache's references are left: every page is free or indexed.
    assert pool.pages_free == pool.pages_allocatable - len(eng.prefix)
    assert (pool.refcount[1:] <= 1).all()


# -- (d): the roll ----------------------------------------------------------


def test_a_roll_precedes_the_round_it_opens_and_the_tokens_stay(params):
    """A request that decodes across two rolls, run ahead: the round that
    would carry a slot into a new window is not queued before the round
    that fills the old one is read (the roll comes between them, on the
    host), every other round is, and the tokens and the rolls are those of
    the engine that never runs ahead."""
    p, new = 3, 75
    prompt = tokens(p, seed=p)

    def serve(cls):
        eng = make_engine(params, cls=cls)
        slot = eng.acquire_slot()
        t_lo = trace.closed("engine.round")[-1][1] if trace.closed(
            "engine.round") else 0.0
        first, _ = eng.start(slot, prompt, max_new_tokens=new)
        toks = [first]
        while eng.active[slot]:
            t, v, _ = eng.step()
            toks += [int(x) for x in t[v[:, slot], slot]]
        rounds = [r[2] for r in sorted(trace.closed("engine.round"))
                  if r[0] > t_lo]
        eng.release(slot)
        return eng, toks, rounds

    sync, want, sync_rounds = serve(SyncEngine)
    assert sync.stats["rounds_ahead"] == 0
    assert not any(r["ahead"] for r in sync_rounds)
    eng, got, rounds = serve(SlotEngine)
    assert got == want and len(got) == new
    assert eng.stats["eva_windows_rolled"] == 2 == (
        sync.stats["eva_windows_rolled"])
    assert eng.stats["eva_window_pages_released"] == (
        sync.stats["eva_window_pages_released"])
    # One slot: live_tokens is the length its round ran with. The first
    # round and the first round of each window come from the host.
    assert [r["live_tokens"] for r in rounds] == list(range(p, p + new - 1))
    assert [r["ahead"] for r in rounds] == [
        n != p and n % W != 0 for n in range(p, p + new - 1)]
    assert eng.stats["rounds_ahead"] == len(rounds) - 3
    for a, b in zip(rounds, sync_rounds):  # what a round read is unchanged
        assert {k: v for k, v in a.items() if k != "ahead"} == {
            k: v for k, v in b.items() if k != "ahead"}


def test_a_roll_frees_the_window_and_attend_counts_both_kinds(params):
    eng = make_engine(params, prefix_cache=False)
    pool = eng.pool
    slot = eng.acquire_slot()
    p, new = W - 6, W - 4
    eng.start(slot, tokens(p), max_new_tokens=new)
    held = pool.pages_allocatable - pool.pages_free
    assert held == W // C + pool.sum_pages  # one window and its forming pages
    while eng.lengths[slot] < W:
        eng.step()
    # Rolled: the window's pages went back, the next window is bound as far
    # as the request goes (it ends in it: no forming pages).
    rest = -(-(p + new - W) // C)
    assert pool.pages_allocatable - pool.pages_free == pool.sum_pages + rest
    assert pool.windows_done[slot] == 1
    assert (pool.forming_row(slot) == TRASH_PAGE).all()
    t0 = trace.closed("engine.round")[-1][0]
    eng.step()
    rec = [r for r in trace.closed("engine.round") if r[0] > t0][-1][2]
    length = W  # what the noted round's token attends from
    assert rec["summary_rows_read"] == (W // C) * (length // W)
    assert rec["window_rows_read"] == length % W + 1
    attend = (W // C) * (length // W) + length % W + 1
    assert rec["kv_rows_read"] == -(-attend // C) * C
    rolls = trace.closed("engine.window_roll")
    assert rolls[-1][2]["pages_released"] == W // C
    assert eng.stats["eva_window_pages_released"] == W // C


def test_round_counts_the_rows_and_summaries_it_writes(params):
    """``engine.round`` notes ``kv_row_writes`` (the active lanes) and
    ``eva_summary_writes`` (the active lanes whose token fills its chunk:
    (len + 1) % page_size == 0), from the registers the round ran with;
    ``engine.stats`` sums both. Two slots two positions apart: a round's
    lengths follow from its ``live_tokens``."""
    import time

    eng = make_engine(params, prefix_cache=False)
    t0 = time.monotonic()
    for p in (5, 7):
        eng.start(eng.acquire_slot(), tokens(p, seed=p), max_new_tokens=20)
    for _ in range(12):
        eng.step()
    recs = [a for _, _, a in trace.closed("engine.round", t0, float("inf"))]
    both = [a for a in recs if a["active"] == 2]
    assert len(both) >= 10
    for a in recs:
        assert a["kv_row_writes"] == a["active"]
    for a in both:
        first = (a["live_tokens"] - 2) // 2
        assert a["eva_summary_writes"] == sum(
            (n + 1) % C == 0 for n in (first, first + 2))
    assert {a["eva_summary_writes"] for a in both} == {0, 1}
    assert eng.stats["kv_row_writes"] == sum(a["kv_row_writes"] for a in recs)
    assert eng.stats["eva_summary_writes"] == sum(
        a["eva_summary_writes"] for a in recs)


def test_a_page_the_prefix_cache_holds_is_not_freed_by_the_roll(params):
    eng = make_engine(params)
    pool = eng.pool
    slot = eng.acquire_slot()
    p = W - 6  # five full pages and a half
    eng.start(slot, tokens(p), max_new_tokens=W)
    while eng.prefilling[slot]:
        eng.step()
    cached = list(pool.window_row(slot)[: p // C])
    assert len(eng.prefix) == p // C
    while eng.lengths[slot] < W:
        eng.step()
    assert (pool.refcount[cached] == 1).all()  # the cache's, not the slot's
    assert not pool._page_free[list(cached)].any()
    assert eng.stats["eva_window_pages_released"] == W // C


def test_pool_reserves_the_most_a_request_holds():
    pool = PagedKVPool(toy_cfg(), 2, 4 * W, C)
    spw, wp = pool.sum_pages, pool.window_pages
    assert (spw, wp, pool.pages_per_slot) == (2, 8, 3 * 2 + 8)
    assert pool.pages_needed(10, 5) == 4  # inside one window: as plain
    assert pool.pages_needed(W, 1) == spw + wp  # one window behind
    assert pool.pages_needed(3 * W, W) == 3 * spw + wp
    assert pool.reserve(0, pool.pages_allocatable)
    assert not pool.reserve(1, 1)
    pool.reserved[0] = 0
    assert pool.reserve(1, 1)


def test_roll_without_forming_pages_is_an_error():
    pool = PagedKVPool(toy_cfg(), 1, 4 * W, C)
    slot = pool.alloc()
    pool.bind_eva(slot, [], pool.alloc_pages(8), [])
    with pytest.raises(RuntimeError, match="forming"):
        pool.roll_window(slot, 1, forming=False)


def test_roll_asks_the_cache_to_give_up_pages():
    pool = PagedKVPool(toy_cfg(), 1, 4 * W, C, num_pages=8 + 2 + 8 + 1)
    cache = PrefixCache(pool)
    slot = pool.alloc()
    pool.bind_eva(slot, [], pool.alloc_pages(8), pool.alloc_pages(2))
    prompt = tokens(W)
    cache.insert(prompt, pool.window_row(slot))  # the cache holds all 8
    spare = pool.alloc_pages(8)  # another holder of the rest of the pool
    with pytest.raises(InsufficientPages):
        pool.roll_window(slot, 8, forming=False)
    # (the failed roll let go of the window: the cache alone holds it now)
    pool.bind_eva(slot, [], [], pool.forming_row(slot).copy())
    assert pool.roll_window(slot, 8, forming=False,
                            evict=cache.evict_for) == 0
    assert pool.windows_done[slot] == 1 and len(cache) == 0
    pool.free_pages(spare)


# -- (f): what refuses an EVA config ----------------------------------------


def test_export_and_import_refuse_an_eva_config(params):
    eng = make_engine(params)
    slot = eng.acquire_slot()
    eng.start(slot, tokens(5), max_new_tokens=4)
    for call in (lambda: eng.export_slot(slot),
                 lambda: eng.export_slot_meta(slot),
                 lambda: eng.import_slot(slot, {}),
                 lambda: eng.adopt_imported_slot(slot, {}, [])):
        with pytest.raises(EvaUnsupported, match="export/import"):
            call()


@pytest.mark.parametrize("over,match", [
    (dict(kv_cache_dtype="int8"), "int8"),
    (dict(attention_window=16), "attention_window"),
    (dict(num_kv_heads=2), "grouped kv"),
    (dict(weight_dtype="int8"), "quantisation"),
], ids=["int8-kv", "sliding-window", "gqa", "weight-quant"])
def test_config_refuses_what_eva_does_not_extend(over, match):
    with pytest.raises(EvaUnsupported, match=match):
        toy_cfg(**over)


@pytest.mark.parametrize("kw,match", [
    (dict(spec_k=2), "speculation"),
    (dict(spec_k=2, spec_branches=2), "speculation"),
    (dict(prefill_chunk_tokens=-1), "chunked prefill"),
    (dict(prefill_len=24), "divides eva_window"),
], ids=["linear-spec", "tree-spec", "chunking-off",
        "chunk-across-a-window"])
def test_engine_refuses_what_eva_does_not_extend(params, kw, match):
    with pytest.raises(EvaUnsupported, match=match):
        make_engine(params, **kw)


def test_monolithic_cache_refuses_an_eva_config(params):
    from distributed_tensorflow_tpu.models.decoding import init_cache

    cfg = toy_cfg(kv_cache_dtype=None)
    with pytest.raises(EvaUnsupported, match="monolithic"):
        TransformerLM(cfg).apply(
            {"params": params}, tokens(4)[None], cache=init_cache(cfg, 1, 64))


@pytest.mark.parametrize("over,match", [
    (dict(norm="Rms"), "norm must be"),
    (dict(mlp="geglu"), "mlp must be"),
    (dict(residual_dtype="bfloat16"), "residual_dtype"),
    (dict(norm="layer"), "norm_unit_offset needs"),
    (dict(eva_chunk=None), "go together"),
    (dict(eva_chunk=5), "whole number"),
    (dict(num_pred_heads=0), "num_pred_heads"),
], ids=["norm", "mlp", "residual", "offset-needs-rms", "half-eva",
        "chunk-off-window", "no-head"])
def test_config_strings_are_validated(over, match):
    with pytest.raises(ValueError, match=match):
        toy_cfg(**over)


# -- the plain layout keeps its counts --------------------------------------


def test_kv_rows_read_of_a_plain_config_is_what_it_was():
    """``kv.decode_read_amplification`` divides the round's
    ``kv_rows_read`` by the live tokens: on a plain (StarCoder2-shaped)
    config the field still counts, for every active slot, the pages up to
    its live length less those a sliding window skips. Hand-worked: page 8,
    window 24, lengths 5, 30 and 41 attend 6, 31 and 42 positions: pages
    [0,1) = 8 rows, [0,4) = 32 rows (31 - 24 = 7: page 0 still holds a
    live row), [2,6) = 32 rows (42 - 24 = 18: pages 0 and 1 are skipped)."""
    cfg = TransformerConfig(
        vocab_size=64, d_model=256, num_heads=2, num_kv_heads=1,
        num_layers=1, d_ff=64, max_seq_len=64, position="rope",
        attention_window=24, compute_dtype=jnp.float32)
    p = TransformerLM(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))["params"]
    eng = SlotEngine(cfg, p, slots=4, max_len=64, prefill_len=16, page_size=8)
    assert eng.decode_path == "table"
    eng.lengths[:] = (5, 30, 41, 63)
    act = np.array([True, True, True, False])
    assert eng._kv_rows_read(act) == 8 + 32 + 32
    assert eng.pool.pages_needed(20, 13) == 5  # ceil(33 / 8), all up front
    assert "eva_windows_rolled" in eng.stats
