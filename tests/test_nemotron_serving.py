"""The Nemotron-H stage on the normal serving path (``SlotEngine``,
``PagedKVPool``, the scheduler behind ``tools/serve_lm.build_stack``), held
to the plain float32 reference's FULL forward
(``benchmarks/reference_nemotron.py``: the Mamba layers as the recurrence) at
the toy size of ``tests/test_nemotron_model.py`` with pages of 4, chunks of
16 and SSD blocks of 8: prefill, chunked prefill and decode through the page
table give, on LOGITS, what the reference gives for the whole sequence; the
recurrent and convolution state cross chunk boundaries, survive the rounds
run between a slot's chunks, stand untouched in a masked lane and start from
zeros in a reused slot; only the attention layer holds pages; what the state
is not extended to refuses by name.

TOL and its reason are ``tests/test_nemotron_model.py``'s (float32 against
float32: the form of the scan and summation order; 6e-6 read, 2e-4 allowed,
0.0026 under a bfloat16 state).
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_nemotron as ref
from benchmarks import weights_nemotron
from distributed_tensorflow_tpu.models.transformer import (
    SlotStateUnsupported,
)
from distributed_tensorflow_tpu.obs import trace
from distributed_tensorflow_tpu.serve.engine import (
    ShardedSlotEngine,
    SlotEngine,
)
from tests.test_nemotron_model import TOL, TOY, tokens, toy_cfg
from tests.test_serve_engine import SyncEngine
from tests.test_zaya_serving import LogitSpy, serve_logits

pytestmark = [pytest.mark.serve, pytest.mark.paged]
M_LAYERS, E_LAYERS, A_LAYERS = (0, 2, 4, 7), (1, 3, 6, 8), (5,)


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def params():
    return weights_nemotron.make_params(TOY, 7, jnp.float32)


def make_engine(params, cls=SlotEngine, cfg=None, **kw):
    kw = dict(dict(slots=3, max_len=128, prefill_len=16, page_size=4,
                   prefix_cache=False), **kw)
    return cls(cfg or toy_cfg(), params, **kw)


def reference_rows(params, prompt, toks):
    seq = np.concatenate([prompt, np.asarray(toks[:-1], np.int32)])
    return np.asarray(ref.logits(params, seq, TOY))[len(prompt) - 1:]


def state_of(eng, slot):
    return [np.asarray(eng.pool.layers[i][name][slot])
            for i in M_LAYERS for name in ("ssm", "conv")]


# -- the pool --------------------------------------------------------------------


def test_layers_hold_the_leaves_of_their_kind(params):
    eng = make_engine(params)
    pool = eng.pool
    for i, layer in enumerate(pool.layers):
        want = ({"ssm", "conv"} if i in M_LAYERS
                else {"k", "v"} if i in A_LAYERS else set())
        assert set(layer) == want, i
    m = pool.layers[0]
    assert m["ssm"].shape == (3, 8, 32, 16) and m["ssm"].dtype == jnp.float32
    assert m["conv"].shape == (3, 3, 256 + 2 * 2 * 16)
    assert pool.state_leaves == ("ssm", "conv")
    # Four layers of state, one layer of pages: the pool reports both, and
    # a cached token costs ONE layer's K and V.
    state = 4 * 3 * (8 * 32 * 16 * 4 + 3 * 320 * 4)
    assert pool.state_bytes == state == eng.stats["ssm_state_bytes"]
    pages = pool.num_pages * 2 * 2 * 4 * 64 * 4
    assert pool.hbm_bytes == state + pages
    assert pool.bytes_per_token == 2 * 2 * 64 * 4
    assert eng.decode_path == "table" and eng.prefix is None


# -- prefill, chunked prefill, paged decode --------------------------------------


@pytest.mark.parametrize("p,new", [(3, 24), (16, 6), (37, 15), (70, 9)],
                         ids=["one-padded-segment", "one-whole-chunk",
                              "three-segments", "five-segments"])
def test_prefill_in_chunks_then_paged_decode_matches_reference(
        params, monkeypatch, p, new):
    spy = LogitSpy(monkeypatch)
    eng = make_engine(params)
    prompt = tokens(p, seed=p)
    slot = eng.acquire_slot()
    toks, got = serve_logits(eng, spy, slot, prompt, new)
    assert len(toks) == new
    assert np.abs(got - reference_rows(params, prompt, toks)).max() < TOL
    assert eng.stats["prefill_chunks"] == (-(-p // 16) if p > 16 else 0)
    assert eng.stats["ssm_tokens_scanned"] == p
    eng.release(slot)
    assert eng.pool.pages_free == eng.pool.pages_allocatable


def test_the_state_crosses_a_chunk_boundary(params, monkeypatch):
    """A prompt prefilled in chunks of 16 gives the first-token logits of
    the same prompt prefilled whole, and both are the reference's; with the
    state zeroed between two chunks they are not."""
    spy = LogitSpy(monkeypatch)
    prompt = tokens(45, seed=9)
    rows = []
    for width in (64, 16):
        eng = make_engine(params, prefill_len=width)
        slot = eng.acquire_slot()
        _, got = serve_logits(eng, spy, slot, prompt, 1)
        assert eng.stats["prefill_chunks"] == (0 if width == 64 else 3)
        rows.append(got[0])
    want = np.asarray(ref.logits(params, prompt, TOY))[-1]
    assert np.abs(rows[0] - rows[1]).max() < TOL
    assert np.abs(rows[1] - want).max() < TOL
    for name in ("ssm", "conv"):
        eng = make_engine(params)
        slot = eng.acquire_slot()
        eng.start(slot, prompt, max_new_tokens=1)
        eng.step()  # the first chunk
        for i in M_LAYERS:
            layer = eng.pool.layers[i]
            layer[name] = jnp.zeros_like(layer[name])
        spy.take()
        while eng.prefilling[slot]:
            eng.step()
        lost = spy.take()[-1]
        assert np.abs(lost - want).max() > 50 * TOL, name


def test_a_reused_slot_starts_from_zeros(params, monkeypatch):
    spy = LogitSpy(monkeypatch)
    eng = make_engine(params, slots=1)
    slot = eng.acquire_slot()
    serve_logits(eng, spy, slot, tokens(21, seed=1), 7)
    eng.release(slot)
    # What the last owner left is still there: zeroing is the next prefill's.
    assert all(np.abs(a).max() > 0 for a in state_of(eng, slot))
    assert eng.acquire_slot() == slot
    prompt = tokens(10, seed=2)
    toks, got = serve_logits(eng, spy, slot, prompt, 9)
    assert np.abs(got - reference_rows(params, prompt, toks)).max() < TOL


def test_a_masked_lane_leaves_its_state_alone(params):
    """A slot that has finished (and one never used) rides every round as a
    masked lane: its recurrent and convolution state stand bit for bit while
    the live slot's advance."""
    eng = make_engine(params)
    done, live = eng.acquire_slot(), eng.acquire_slot()
    eng.start(done, tokens(9, seed=3), max_new_tokens=3)
    eng.start(live, tokens(6, seed=4), max_new_tokens=20)
    while eng.active[done]:
        eng.step()
    for _ in range(2):  # a round queued ahead of the finish is read out
        eng.step()
    idle = [s for s in range(3) if s not in (done, live)][0]
    before = {s: state_of(eng, s) for s in (done, idle, live)}
    for _ in range(5):
        eng.step()
    for s in (done, idle):
        for a, b in zip(before[s], state_of(eng, s)):
            np.testing.assert_array_equal(a, b)
    assert all(np.abs(b).max() == 0 for b in before[idle])
    assert any(np.abs(a - b).max() > 0
               for a, b in zip(before[live], state_of(eng, live)))


def test_slots_at_mixed_phases_each_give_their_own_tokens(params):
    """Three requests together (one decoding, one admitted while it decodes
    with a prompt of four segments, so that rounds run BETWEEN its chunks,
    one short) give each the tokens it gives alone, on the engine that runs
    ahead and on the one that does not; alone is held to the reference's
    logits above."""
    prompts = [tokens(5, seed=11), tokens(50, seed=12), tokens(9, seed=13)]
    news = [30, 12, 16]

    def alone(i):
        eng = make_engine(params, slots=1)
        slot = eng.acquire_slot()
        first, _ = eng.start(slot, prompts[i], max_new_tokens=news[i])
        out = [] if first is None else [first]
        while eng.active[slot] or eng.prefilling[slot]:
            t, v, _ = eng.step()
            out += [int(x) for x in t[v[:, slot], slot]]
        return out

    want = [alone(i) for i in range(3)]
    for cls in (SyncEngine, SlotEngine):
        eng = make_engine(params, cls=cls)
        out = {}

        def admit(i):
            slot = eng.acquire_slot()
            first, _ = eng.start(slot, prompts[i], max_new_tokens=news[i])
            out[slot] = [] if first is None else [first]
            return slot

        def step():
            t, v, _ = eng.step()
            for s in out:
                out[s] += [int(x) for x in t[v[:, s], s]]

        a = admit(0)
        for _ in range(4):
            step()
        b = admit(1)
        step()
        assert eng.prefilling[b] and eng.active[a]
        admit(2)
        while eng.active.any() or eng.prefilling.any():
            step()
        assert [out[s] for s in sorted(out)] == want, cls
    # The one that runs ahead queued rounds from the state of the round
    # before, on the device.
    assert eng.stats["rounds_ahead"] >= 20


def test_the_paged_kernel_reads_the_one_attention_layer(params, monkeypatch):
    """Heads of 128, 8 query heads over 2 kv heads, pages of 8 f32 rows: the
    attention layer's leaves fit ``paged_decode_attention`` (interpret mode
    here) on the GROUP form, which the full-size model's 16 rows a kv head
    take too; the Mamba and expert layers around it are as they were."""
    small = dict(TOY, d_model=128, num_heads=8, num_kv_heads=2, head_dim=128,
                 num_layers=3, layer_pattern="M*E", num_experts=4,
                 experts_per_token=2, expert_width=64,
                 shared_expert_width=64, ssm_heads=4)
    p1 = weights_nemotron.make_params(small, 3, jnp.float32)
    spy = LogitSpy(monkeypatch)
    from distributed_tensorflow_tpu.models.transformer import (
        TransformerConfig,
    )
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


def test_the_spans_say_what_the_state_and_the_experts_did(params):
    eng = make_engine(params)
    t_lo = (trace.closed("engine.round") or [(0, 0, None)])[-1][1]
    slots = [eng.acquire_slot() for _ in range(2)]
    eng.start(slots[0], tokens(6, seed=30), max_new_tokens=8)
    eng.start(slots[1], tokens(39, seed=31), max_new_tokens=8)
    while eng.active.any() or eng.prefilling.any():
        eng.step()
    recs = [r[2] for r in trace.closed("engine.round", t_lo, float("inf"))
            if r[0] > t_lo and r[2].get("active")]
    # Four expert layers of 16; a token's three pairs touch three experts.
    assert recs and all(r["experts_total"] == 4 * 16 for r in recs)
    assert all(r["ssm_lanes"] == r["active"] for r in recs)
    assert all(4 * 3 <= r["experts_touched"] <= 4 * 3 * r["active"]
               for r in recs)
    assert all(1 <= r["expert_tokens_max"] <= r["active"] for r in recs)
    assert eng.stats["moe_tokens_routed"] == sum(
        4 * 3 * r["active"] for r in recs)
    assert eng.stats["moe_experts_touched"] == sum(
        r["experts_touched"] for r in recs)
    chunks = [r[2] for r in trace.closed(
        "engine.prefill_chunk", t_lo, float("inf")) if r[0] > t_lo]
    # Buckets of 16 in SSD blocks of 8, four Mamba layers: 8 blocks a chunk.
    assert len(chunks) == 1 + 3 and all(c["ssm_blocks"] == 8 for c in chunks)
    assert eng.stats["ssm_tokens_scanned"] == 6 + 39


# -- what the state is not extended to ----------------------------------------------


@pytest.mark.parametrize("kw,match", [
    ({"spec_k": 2}, "speculation.*recurrent state"),
    ({"spec_k": 2, "spec_branches": 2}, "speculation.*recurrent state"),
    ({"prefix_cache": True}, "prefix cache.*recurrent state.*snapshot"),
], ids=["speculation", "tree-speculation", "prefix-adoption"])
def test_the_engine_refuses_by_name(params, kw, match):
    with pytest.raises(SlotStateUnsupported, match=match):
        make_engine(params, **kw)


def test_the_sharded_engine_refuses(params):
    with pytest.raises(SlotStateUnsupported, match="ShardedSlotEngine"):
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
    with pytest.raises(SlotStateUnsupported,
                       match="recurrent and convolution state"):
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
