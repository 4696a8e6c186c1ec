"""ZAYA1 on the normal serving path (``SlotEngine``, ``PagedKVPool``, the
scheduler behind ``tools/serve_lm.build_stack``), held to the plain float32
reference's FULL forward (``benchmarks/reference_zaya.py``) at the toy size
of ``tests/test_zaya_model.py`` with pages of 4 and chunks of 16: prefill,
chunked prefill and decode through the page table give, on LOGITS, what the
reference gives for the whole sequence; the convolution state crosses chunk
boundaries, survives the rounds run between a slot's chunks, and starts from
zeros in a reused slot; what the state is not extended to refuses by name.

TOL and its reason are ``tests/test_zaya_model.py``'s (float32 against
float32, summation order alone: 5e-6 read, 2e-4 allowed, 0.03 under bf16).
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_zaya as ref
from benchmarks import weights_zaya
from distributed_tensorflow_tpu.models.transformer import (
    CcaUnsupported,
    TransformerConfig,
    TransformerLM,
)
from distributed_tensorflow_tpu.obs import trace
from distributed_tensorflow_tpu.serve.engine import (
    ShardedSlotEngine,
    SlotEngine,
)
from tests.test_serve_engine import SyncEngine
from tests.test_zaya_model import TOL, TOY, tokens, toy_cfg

pytestmark = [pytest.mark.serve, pytest.mark.paged]


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def params():
    return weights_zaya.make_params(TOY, 7, jnp.float32)


class LogitSpy:
    """The logits the engine's programs pick their tokens from (the engine
    returns tokens alone): ``jnp.argmax`` is wrapped, while the programs are
    traced, by a host callback that hands over its operand where that is
    one vocabulary wide ((V,) from a prefill segment, (slots, V) from a
    decode round; the router's argmax over experts is let through)."""

    def __init__(self, monkeypatch, vocab=TOY["vocab_size"]):
        self.seen = []
        real = jnp.argmax

        def spy(x, *args, **kwargs):
            if x.shape[-1] == vocab:
                jax.debug.callback(
                    lambda a: self.seen.append(np.array(a)), x, ordered=True)
            return real(x, *args, **kwargs)

        monkeypatch.setattr(jnp, "argmax", spy)

    def take(self):
        jax.effects_barrier()
        out, self.seen = self.seen, []
        return out


def make_engine(params, cls=SlotEngine, cfg=None, **kw):
    kw = dict(dict(slots=3, max_len=128, prefill_len=16, page_size=4,
                   prefix_cache=False), **kw)
    return cls(cfg or toy_cfg(), params, **kw)


def serve_logits(eng, spy, slot, prompt, max_new):
    """Run one request alone to its end; (tokens, the logits each token was
    picked from)."""
    spy.take()
    first, _ = eng.start(slot, prompt, max_new_tokens=max_new)
    toks = [] if first is None else [first]
    while eng.active[slot] or eng.prefilling[slot]:
        t, v, _ = eng.step()
        toks += [int(x) for x in t[v[:, slot], slot]]
    rows = [a if a.ndim == 1 else a[slot] for a in spy.take()]
    # Segments before the last pick a token nobody is served.
    return toks, np.stack(rows[-len(toks):])


def reference_rows(params, prompt, toks):
    seq = np.concatenate([prompt, np.asarray(toks[:-1], np.int32)])
    return np.asarray(ref.logits(params, seq, TOY))[len(prompt) - 1:]


# -- prefill, chunked prefill, paged decode --------------------------------------


@pytest.mark.parametrize("p,new", [(3, 24), (16, 6), (37, 15), (70, 9)],
                         ids=["one-padded-segment", "one-whole-chunk",
                              "three-segments", "five-segments"])
def test_prefill_in_chunks_then_paged_decode_matches_reference(
        params, monkeypatch, p, new):
    spy = LogitSpy(monkeypatch)
    eng = make_engine(params)
    assert eng.decode_path == "table" and eng.prefix is None
    prompt = tokens(p, seed=p)
    slot = eng.acquire_slot()
    toks, got = serve_logits(eng, spy, slot, prompt, new)
    assert len(toks) == new
    assert np.abs(got - reference_rows(params, prompt, toks)).max() < TOL
    assert eng.stats["prefill_chunks"] == (-(-p // 16) if p > 16 else 0)
    eng.release(slot)
    assert eng.pool.pages_free == eng.pool.pages_allocatable


def test_the_state_crosses_a_chunk_boundary(params, monkeypatch):
    """A prompt prefilled in chunks of 16 gives the first-token logits of
    the same prompt prefilled whole, and both are the reference's."""
    spy = LogitSpy(monkeypatch)
    prompt = tokens(45, seed=9)
    rows = []
    for width in (64, 16):
        eng = make_engine(params, prefill_len=width)
        slot = eng.acquire_slot()
        toks, got = serve_logits(eng, spy, slot, prompt, 1)
        assert eng.stats["prefill_chunks"] == (0 if width == 64 else 3)
        rows.append(got[0])
    want = np.asarray(ref.logits(params, prompt, TOY))[-1]
    assert np.abs(rows[0] - rows[1]).max() < TOL
    assert np.abs(rows[1] - want).max() < TOL
    # Without the state the second chunk's first positions would see zeros
    # where the first chunk's last latents belong: far over TOL.
    eng = make_engine(params)
    slot = eng.acquire_slot()
    eng.start(slot, prompt, max_new_tokens=1)
    eng.step()  # the first chunk
    for layer in eng.pool.layers:
        layer["cca"] = jnp.zeros_like(layer["cca"])
    spy.take()
    while eng.prefilling[slot]:
        eng.step()
    lost = spy.take()[-1]
    assert np.abs(lost - want).max() > 50 * TOL


def test_a_reused_slot_starts_from_zeros(params, monkeypatch):
    spy = LogitSpy(monkeypatch)
    eng = make_engine(params, slots=1)
    slot = eng.acquire_slot()
    serve_logits(eng, spy, slot, tokens(21, seed=1), 7)
    eng.release(slot)
    # What the last owner left is still there: zeroing is the next prefill's.
    assert float(jnp.abs(eng.pool.layers[0]["cca"][slot]).max()) > 0
    assert eng.acquire_slot() == slot
    prompt = tokens(10, seed=2)
    toks, got = serve_logits(eng, spy, slot, prompt, 9)
    assert np.abs(got - reference_rows(params, prompt, toks)).max() < TOL


def test_slots_at_mixed_phases_each_match_the_reference(params, monkeypatch):
    """Three requests together: one decoding, one admitted while it decodes
    with a prompt of four segments (rounds run BETWEEN its chunks must leave
    its state alone), one short. Every slot's logits are the reference's,
    on the engine that runs ahead and on the one that does not."""
    spy = LogitSpy(monkeypatch)
    prompts = [tokens(5, seed=11), tokens(50, seed=12), tokens(9, seed=13)]
    news = [40, 12, 20]

    def scenario(cls):
        eng = make_engine(params, cls=cls)
        toks, rows, chunks, rounds, reads = {}, {}, [], [], []
        finish = eng._finish_round
        eng._finish_round = lambda *a: (reads.append(1), finish(*a))[1]

        def drain():
            # Callbacks are ordered: a prefill segment leaves a (V,) row, a
            # decode round a (slots, V) one, in the order the device ran.
            for x in spy.take():
                (chunks if x.ndim == 1 else rounds).append(x)

        def admit(i):
            slot = eng.acquire_slot()
            first, _ = eng.start(slot, prompts[i], max_new_tokens=news[i])
            drain()
            toks[slot] = [] if first is None else [first]
            rows[slot] = [] if first is None else [chunks[-1]]
            return slot

        def step():
            n0 = len(reads)
            t, v, _ = eng.step()
            drain()
            # The call read one decode round (the oldest not yet read) or
            # none; rows before it are final chunks' first tokens.
            decoded = len(reads) > n0
            cur = rounds.pop(0) if decoded else None
            for s in toks:
                for k in np.nonzero(v[:, s])[0]:
                    toks[s].append(int(t[k, s]))
                    rows[s].append(
                        cur[s] if k >= t.shape[0] - int(decoded)
                        else chunks[-1])

        spy.take()
        a = admit(0)
        for _ in range(4):
            step()
        b = admit(1)
        assert not toks[b]
        step()
        assert eng.prefilling[b] and eng.active[a]
        admit(2)
        while eng.active.any() or eng.prefilling.any():
            step()
        return toks, rows

    for cls in (SyncEngine, SlotEngine):
        toks, rows = scenario(cls)
        for s, prompt, new in zip(toks, prompts, news):
            assert len(toks[s]) == new
            want = reference_rows(params, prompt, toks[s])
            assert np.abs(np.stack(rows[s]) - want).max() < TOL, (cls, s)


def test_rounds_run_ahead_and_the_tokens_stay(params):
    prompt = tokens(7, seed=21)

    def serve(cls):
        eng = make_engine(params, cls=cls)
        slot = eng.acquire_slot()
        first, _ = eng.start(slot, prompt, max_new_tokens=30)
        out = [first]
        while eng.active[slot]:
            t, v, _ = eng.step()
            out += [int(x) for x in t[v[:, slot], slot]]
        return out, eng.stats

    ahead, stats = serve(SlotEngine)
    sync, stats_sync = serve(SyncEngine)
    assert ahead == sync and len(ahead) == 30
    assert stats["rounds_ahead"] >= 25 and stats_sync["rounds_ahead"] == 0


def test_the_paged_kernel_reads_the_latent_at_four_rows_a_kv_head(
        params, monkeypatch):
    """Heads of 128, 8 query heads over 2 kv heads, pages of 8 f32 rows:
    the pool's leaves fit ``paged_decode_attention`` (interpret mode here),
    on the GROUP form that the full-size model's group of 4 takes."""
    small = dict(TOY, d_model=128, num_heads=8, num_kv_heads=2, head_dim=128,
                 num_layers=1, num_experts=4, expert_width=128)
    p1 = weights_zaya.make_params(small, 3, jnp.float32)
    spy = LogitSpy(monkeypatch)
    eng = SlotEngine(
        TransformerConfig(**small, compute_dtype=jnp.float32), p1, slots=2,
        max_len=64, prefill_len=16, page_size=8, prefix_cache=False)
    assert eng.decode_kernel_form == "group"
    prompt = tokens(19, seed=5)
    slot = eng.acquire_slot()
    toks, got = serve_logits(eng, spy, slot, prompt, 6)
    seq = np.concatenate([prompt, np.asarray(toks[:-1], np.int32)])
    want = np.asarray(ref.logits(p1, seq, small))[18:]
    assert np.abs(got - want).max() < TOL


# -- tracing -----------------------------------------------------------------------


def test_a_round_says_which_experts_it_touched(params):
    eng = make_engine(params)
    t_lo = (trace.closed("engine.round") or [(0, 0, None)])[-1][1]
    slots = [eng.acquire_slot() for _ in range(2)]
    for i, s in enumerate(slots):
        eng.start(s, tokens(6 + i, seed=30 + i), max_new_tokens=8)
    rounds = 0
    while eng.active.any():
        eng.step()
        rounds += 1
    recs = [r[2] for r in trace.closed("engine.round", t_lo, float("inf"))
            if r[0] > t_lo and r[2].get("active")]
    assert recs and all(r["experts_total"] == 3 * 8 for r in recs)
    # Two tokens a round, three layers: 3 to 6 (layer, expert) pairs.
    assert all(3 <= r["experts_touched"] <= 6 for r in recs)
    assert all(1 <= r["expert_tokens_max"] <= 2 for r in recs)
    assert eng.stats["moe_tokens_routed"] == sum(
        3 * r["active"] for r in recs)
    assert eng.stats["moe_experts_touched"] == sum(
        r["experts_touched"] for r in recs)


# -- what the state is not extended to ----------------------------------------------


@pytest.mark.parametrize("kw,match", [
    ({"spec_k": 2}, "speculation"),
    ({"spec_k": 2, "spec_branches": 2}, "speculation"),
    ({"prefix_cache": True}, "prefix cache"),
], ids=["speculation", "tree-speculation", "prefix-adoption"])
def test_the_engine_refuses_by_name(params, kw, match):
    with pytest.raises(CcaUnsupported, match=match):
        make_engine(params, **kw)


def test_routed_experts_without_cca_are_served(params):
    """The same expert layer behind plain attention (no CCA): the engine
    serves it, in segments and through the table as it serves CCA; the
    tokens are the uncached forward's, and an idle lane reaches no expert
    (a round routes one token a layer for each ACTIVE slot, of three)."""
    cfg = toy_cfg(cca_time0=None, cca_time1=None)
    model = TransformerLM(cfg)
    prompt = tokens(21, seed=50)
    plain = model.init(jax.random.PRNGKey(5), prompt[None])["params"]
    eng = make_engine(plain, cfg=cfg)
    assert eng.decode_path == "table" and not eng.pool.state_leaves
    t_lo = (trace.closed("engine.round") or [(0, 0, None)])[-1][1]
    slot = eng.acquire_slot()
    first, _ = eng.start(slot, prompt, max_new_tokens=8)
    toks = [] if first is None else [first]
    while eng.active[slot] or eng.prefilling[slot]:
        t, v, _ = eng.step()
        toks += [int(x) for x in t[v[:, slot], slot]]
    seq = np.concatenate([prompt, np.asarray(toks[:-1], np.int32)])
    want = model.apply({"params": plain}, seq[None])[0, len(prompt) - 1:]
    assert toks == [int(x) for x in want.argmax(-1)]
    recs = [r[2] for r in trace.closed("engine.round", t_lo, float("inf"))
            if r[0] > t_lo and r[2].get("active")]
    assert recs and all(r["experts_touched"] == 3 for r in recs)
    assert eng.stats["moe_tokens_routed"] == 3 * len(recs)
    with pytest.raises(CcaUnsupported, match="prefix cache.*routed experts"):
        make_engine(plain, cfg=cfg, prefix_cache=True)


def test_the_sharded_engine_refuses(params):
    with pytest.raises(CcaUnsupported, match="ShardedSlotEngine"):
        ShardedSlotEngine(toy_cfg(), params, tp=2, slots=2, max_len=64,
                          prefill_len=16, page_size=4, prefix_cache=False)


@pytest.mark.parametrize("call", ["export_slot", "export_slot_meta",
                                  "import_slot", "adopt_imported_slot"])
def test_handoff_refuses_by_name(params, call):
    eng = make_engine(params)
    slot = eng.acquire_slot()
    eng.start(slot, tokens(5), max_new_tokens=4)
    args = {"export_slot": (slot,), "export_slot_meta": (slot,),
            "import_slot": (slot, {}),
            "adopt_imported_slot": (slot, {}, [])}[call]
    with pytest.raises(CcaUnsupported, match="convolution state"):
        getattr(eng, call)(*args)
    with pytest.raises(ValueError, match="page payload"):
        eng.pool.export_pages(slot)


# -- the whole stack -------------------------------------------------------------------


def test_build_stack_serves_it_through_the_scheduler(params):
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
    try:
        from serve_lm import build_stack
    finally:
        sys.path.pop(0)
    from distributed_tensorflow_tpu.config import ServeConfig
    from distributed_tensorflow_tpu.serve.scheduler import Completion, Request

    serve_cfg = ServeConfig(slots=2, serve_max_len=64, prefill_len=16,
                            page_size=4, prefix_cache=False, spec_k=0,
                            port=0, slo="off")
    # The scheduler's thread is outside the fixture's (thread-local)
    # precision: set it for the process, or its first round is a new program.
    # (Read here, inside the fixture's block, the setting is the fixture's
    # and not the process's: the process goes back to its default, None.)
    jax.config.update("jax_default_matmul_precision", "highest")
    engine, scheduler, _, server = build_stack(serve_cfg, toy_cfg(), params)
    try:
        assert type(engine) is SlotEngine and engine.decode_path == "table"
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
    alone = make_engine(params, slots=1, max_len=64)
    slot = alone.acquire_slot()
    first, _ = alone.start(slot, prompt, max_new_tokens=10)
    toks = [] if first is None else [first]
    while alone.active[slot] or alone.prefilling[slot]:
        t, v, _ = alone.step()
        toks += [int(x) for x in t[v[:, slot], slot]]
    assert list(out.tokens) == toks
