"""Serving engine tests: the three contracts everything else builds on.

1. **Parity** — a request decoded through the slot engine must reproduce
   ``build_generate_fn`` token-for-token (greedy exactly; sampled via the
   same fold_in PRNG discipline), whatever slot it lands in and whatever
   else shares the batch.
2. **Zero recompiles** — the ISSUE 4 acceptance criterion: >= 32 requests
   with heterogeneous prompt/output lengths churn through a 4-slot engine
   and the compiled-program count never moves after warmup.
3. **Slot isolation/reuse** — freed slots are NOT zeroed, so a new tenant
   must never read its predecessor's K/V (the write-before-attend
   invariant in serve/engine.py's module docstring).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_tpu.models import decoding
from distributed_tensorflow_tpu.models.transformer import (
    TransformerConfig,
    TransformerLM,
)
from distributed_tensorflow_tpu.serve import SlotEngine

pytestmark = pytest.mark.serve

CFG = TransformerConfig(
    vocab_size=64,
    d_model=32,
    num_heads=4,
    num_layers=2,
    d_ff=64,
    max_seq_len=48,
    compute_dtype=jnp.float32,
)


@pytest.fixture(scope="module")
def params():
    model = TransformerLM(CFG)
    return model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))[
        "params"
    ]


def _drive(engine, requests):
    """Closed-loop driver; returns {request index: generated tokens}."""
    pending = list(range(len(requests)))
    busy: dict[int, int] = {}
    acc: dict[int, list[int]] = {}
    results: dict[int, list[int]] = {}
    while pending or busy:
        while pending:
            slot = engine.acquire_slot()
            if slot is None:
                break
            i = pending.pop(0)
            prompt, kwargs = requests[i]
            first, finished = engine.start(slot, prompt, **kwargs)
            acc[i] = [first]
            if finished:
                results[i] = acc[i]
                engine.release(slot)
            else:
                busy[slot] = i
        if busy:
            toks, valid, done = engine.step()
            for k in range(toks.shape[0]):
                for slot, i in busy.items():
                    if valid[k, slot]:
                        acc[i].append(int(toks[k, slot]))
            for slot in list(busy):
                if done[slot]:
                    i = busy.pop(slot)
                    results[i] = acc[i]
                    engine.release(slot)
    return results


def _reference_greedy(params, prompt, n_new, cfg=CFG, cache_len=None):
    """The oracle that is no engine: one request alone through
    ``build_generate_fn`` on the model's own B=1 cache."""
    gen = decoding.build_generate_fn(cfg, n_new, temperature=0.0,
                                     cache_len=cache_len)
    out = gen(
        params, jnp.asarray([prompt], jnp.int32), jax.random.PRNGKey(0)
    )
    return np.asarray(out)[0, len(prompt):].tolist()


def test_greedy_parity_with_build_generate_fn(params):
    """Every request through the engine == the sequential decode path,
    token for token, across heterogeneous prompt/output lengths and
    whatever slot each request happens to get."""
    engine = SlotEngine(CFG, params, slots=3, max_len=32, prefill_len=12)
    rng = np.random.default_rng(0)
    requests = []
    for _ in range(7):
        p = rng.integers(0, CFG.vocab_size, rng.integers(1, 12)).tolist()
        requests.append((p, {"max_new_tokens": int(rng.integers(2, 8))}))
    results = _drive(engine, requests)
    for i, (prompt, kwargs) in enumerate(requests):
        ref = _reference_greedy(params, prompt, kwargs["max_new_tokens"])
        assert results[i] == ref, f"request {i} diverged from sequential"


def test_zero_recompiles_under_heterogeneous_churn(params):
    """ISSUE 4 acceptance: >= 32 heterogeneous requests through a 4-slot
    engine, compiled-program count frozen after warmup."""
    engine = SlotEngine(CFG, params, slots=4, max_len=48, prefill_len=16)
    compiled = engine.warmup()
    assert compiled == engine.compile_count()
    rng = np.random.default_rng(1)
    requests = []
    for i in range(32):
        p = rng.integers(0, CFG.vocab_size, rng.integers(1, 17)).tolist()
        kwargs = {"max_new_tokens": int(rng.integers(1, 9))}
        if i % 3 == 1:  # mix sampling configs in — still no new programs
            kwargs.update(temperature=0.8, top_k=int(rng.integers(2, 10)),
                          top_p=0.9, seed=i)
        if i % 5 == 2:
            kwargs.update(eos_id=int(rng.integers(0, CFG.vocab_size)))
        requests.append((p, kwargs))
    results = _drive(engine, requests)
    assert len(results) == 32
    for i, (_, kwargs) in enumerate(requests):
        assert 1 <= len(results[i]) <= kwargs["max_new_tokens"]
    assert engine.compile_count() == compiled, (
        "engine recompiled under churn — a shape or dtype leaked into a "
        "jitted signature"
    )


def test_slot_reuse_isolation(params):
    """A slot's previous tenant must not influence its next one: the same
    request gives identical tokens on a fresh engine and on a slot that
    just hosted a DIFFERENT longer request (stale K/V above the new
    filled length is never attended)."""
    probe = [5, 9, 2]
    fresh = SlotEngine(CFG, params, slots=1, max_len=32, prefill_len=12)
    want = _drive(fresh, [(probe, {"max_new_tokens": 5})])[0]

    reused = SlotEngine(CFG, params, slots=1, max_len=32, prefill_len=12)
    noise = np.random.default_rng(2).integers(0, CFG.vocab_size, 11).tolist()
    _drive(reused, [(noise, {"max_new_tokens": 12})])  # fill slot 0 long
    got = _drive(reused, [(probe, {"max_new_tokens": 5})])[0]
    assert got == want


def test_per_slot_sampling_params_are_independent(params):
    """Slots decode with THEIR OWN temperature/top_k/top_p/seed: a greedy
    request sharing the batch with hot-temperature requests returns the
    greedy reference exactly."""
    engine = SlotEngine(CFG, params, slots=4, max_len=32, prefill_len=8)
    prompt = [3, 1, 4]
    requests = [(prompt, {"max_new_tokens": 6})]
    for s in range(3):
        requests.append(
            (prompt, {"max_new_tokens": 6, "temperature": 1.5, "top_k": 8,
                      "top_p": 0.95, "seed": s + 10})
        )
    results = _drive(engine, requests)
    assert results[0] == _reference_greedy(params, prompt, 6)


def test_sampled_decode_is_seed_deterministic(params):
    """Same request + same seed => same tokens, regardless of batch
    composition (per-slot fold_in streams, not a shared engine key)."""
    kwargs = {"max_new_tokens": 6, "temperature": 1.0, "top_k": 12,
              "top_p": 0.9, "seed": 7}
    alone = SlotEngine(CFG, params, slots=2, max_len=32, prefill_len=8)
    a = _drive(alone, [([2, 4, 6], dict(kwargs))])[0]
    crowded = SlotEngine(CFG, params, slots=2, max_len=32, prefill_len=8)
    b = _drive(
        crowded,
        [([2, 4, 6], dict(kwargs)),
         ([1, 1, 1, 1], {"max_new_tokens": 8, "temperature": 2.0,
                         "seed": 99})],
    )[0]
    assert a == b


def test_eos_stops_early_and_budget_caps(params):
    """eos_id ends a request the step it is sampled; budget caps at
    max_new_tokens; both release the slot for the next wave."""
    engine = SlotEngine(CFG, params, slots=1, max_len=32, prefill_len=8)
    # Use a greedy token that first appears MID-generation as eos, so the
    # stop provably happens in the decode loop, not at prefill. The tiny
    # random-init model often fixates on one token, so scan prompts (one
    # compiled generate fn — fixed prompt length) for a varied output.
    gen = decoding.build_generate_fn(CFG, 8, temperature=0.0)
    for a in range(CFG.vocab_size):
        ref = np.asarray(
            gen(params, jnp.asarray([[a, 7]], jnp.int32),
                jax.random.PRNGKey(0))
        )[0, 2:].tolist()
        j = next((i for i, t in enumerate(ref) if t != ref[0]), None)
        if j is not None:
            break
    assert j is not None, "no prompt produced a varied greedy output"
    results = _drive(engine, [([a, 7], {"max_new_tokens": 8,
                                        "eos_id": ref[j]})])
    assert results[0] == ref[:j + 1]  # stopped at eos, eos included
    assert engine.free_slots == 1
    results = _drive(engine, [([7, 7], {"max_new_tokens": 3})])
    assert len(results[0]) == 3  # budget cap


def test_start_validates_limits(params):
    # Chunking off: this test pins the strict single-shot prompt cap.
    engine = SlotEngine(CFG, params, slots=1, max_len=16, prefill_len=8,
                        prefill_chunk_tokens=-1)
    slot = engine.acquire_slot()
    with pytest.raises(ValueError, match="at least one token"):
        engine.start(slot, [], max_new_tokens=2)
    with pytest.raises(ValueError, match="prefill_len"):
        engine.start(slot, list(range(9)), max_new_tokens=2)
    with pytest.raises(ValueError, match="max_new_tokens"):
        engine.start(slot, [1], max_new_tokens=0)
    with pytest.raises(ValueError, match="max_len"):
        engine.start(slot, list(range(8)), max_new_tokens=9)
    engine.release(slot)
    with pytest.raises(RuntimeError, match="no active slots"):
        engine.step()


def test_page_size_zero_is_refused_by_name(params):
    """There is one KV layout. ``page_size=0`` comes from outside the
    program (``--page_size``, a ServeConfig file): it is refused where the
    value is resolved, with the sizes the engine takes, before a pool is
    built."""
    built = []
    build = SlotEngine._build_pool

    class Spy(SlotEngine):
        def _build_pool(self, *a):
            built.append(a)
            return build(self, *a)

    for bad in (0, -1):
        with pytest.raises(ValueError, match="positive divisor of max_len 32"):
            Spy(CFG, params, slots=2, max_len=32, page_size=bad)
    assert not built
    assert Spy(CFG, params, slots=2, max_len=32, page_size=None).page_size == 16
    assert len(built) == 1


def test_sample_logits_batched_matches_static_sampler():
    """Per-row traced sampling == the static sample_logits filter-for-
    filter: same key, same temper/top-k/top-p => same token; disabled
    filters and greedy rows match too."""
    rng = np.random.default_rng(3)
    logits = jnp.asarray(rng.standard_normal((5, 32)), jnp.float32)
    keys = jnp.stack([jax.random.PRNGKey(i) for i in range(5)])
    cases = [  # (temperature, top_k, top_p) per row; 0 = disabled
        (0.0, 0, 0.0),     # greedy
        (1.0, 0, 0.0),     # plain categorical
        (0.7, 5, 0.0),     # top-k only
        (1.3, 0, 0.8),     # nucleus only
        (1.0, 7, 0.6),     # both
    ]
    temp = jnp.asarray([c[0] for c in cases], jnp.float32)
    top_k = jnp.asarray([c[1] for c in cases], jnp.int32)
    top_p = jnp.asarray([c[2] for c in cases], jnp.float32)
    batched = decoding.sample_logits_batched(logits, keys, temp, top_k, top_p)
    for i, (t, k, p) in enumerate(cases):
        ref = decoding.sample_logits(
            logits[i:i + 1], keys[i], temperature=t,
            top_k=k or None, top_p=p or None,
        )
        assert int(batched[i]) == int(ref[0]), f"row {i} ({t}, {k}, {p})"


# -- run-ahead of depth one: round n+1 queued before round n is read --------

# dh 128: the head size whose plain decode goes through the page table.
CFG_TABLE = TransformerConfig(
    vocab_size=64, d_model=256, num_heads=2, num_layers=2, d_ff=64,
    max_seq_len=48, compute_dtype=jnp.float32,
)
# Quantize-on-write pages: the pool carries scale leaves beside its rows,
# and they are donated and run ahead like the rows.
CFG_INT8 = dataclasses.replace(CFG, kv_cache_dtype="int8")
_AHEAD_LAYOUTS = {
    # name: (config, engine keywords, the decode path it must take)
    "table": (CFG_TABLE, dict(page_size=8, prefill_chunk_tokens=8), "table"),
    "gather": (CFG, dict(page_size=8, prefill_chunk_tokens=8), "gather"),
    "int8-kv": (CFG_INT8, dict(page_size=8, prefill_chunk_tokens=8), "gather"),
}
_SAMPLING = {
    "greedy": {},
    "sampled": {"temperature": 0.9, "top_k": 12, "top_p": 0.9},
}


class SyncEngine(SlotEngine):
    """The engine with run-ahead off: every round is queued from the
    host's registers after the round before it was read, as before."""

    def _host_silent(self):
        return False


@pytest.fixture(scope="module")
def layout_params():
    cache = {}

    def get(cfg):
        if id(cfg) not in cache:
            cache[id(cfg)] = TransformerLM(cfg).init(
                jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
        return cache[id(cfg)]

    return get


def _alone(cfg, params, kw, prompt, kwargs):
    """One request alone on a synchronous engine: the oracle for a sampled
    request (greedy ones are also held to ``build_generate_fn``)."""
    eng = SyncEngine(cfg, params, slots=1, max_len=48, prefill_len=12, **kw)
    out = _drive_chunked(eng, [(prompt, kwargs)])[0]
    assert eng.stats["rounds_ahead"] == 0
    return out


def _drive_chunked(engine, requests, cancel=None, log=None):
    """``_drive`` for engines that chunk a long prompt (``start`` returns
    ``(None, False)`` and the first token comes from a later round), with
    ``cancel``: {request index: tokens after which the driver releases the
    slot while the request is still decoding}."""
    cancel = cancel or {}
    pending = list(range(len(requests)))
    busy: dict[int, int] = {}
    acc = {i: [] for i in range(len(requests))}
    results = {}

    def finish(slot):
        i = busy.pop(slot)
        results[i] = acc[i]
        engine.release(slot)

    while pending or busy:
        while pending:
            slot = engine.acquire_slot()
            if slot is None:
                break
            i = pending.pop(0)
            prompt, kwargs = requests[i]
            first, finished = engine.start(slot, prompt, **kwargs)
            busy[slot] = i
            if first is not None:
                acc[i].append(first)
            if finished:
                finish(slot)
        if busy:
            toks, valid, done = engine.step()
            if log is not None:
                log.append((valid.copy(), done.copy()))
            for k in range(toks.shape[0]):
                for slot, i in busy.items():
                    if valid[k, slot]:
                        acc[i].append(int(toks[k, slot]))
            for slot in list(busy):
                i = busy[slot]
                if done[slot]:
                    finish(slot)
                elif i in cancel and len(acc[i]) >= cancel[i]:
                    assert engine.active[slot]
                    finish(slot)  # a cancel: the device still carries it
    return results


def _ahead_requests(cfg, params, kw, sampling):
    """Seven requests on three slots: admissions all along, a budget's end,
    an eos met mid-decode, and two prompts longer than the chunk, whose
    final chunk lands while a round of the others is in flight."""
    rng = np.random.default_rng(5)
    lens = [5, 20, 3, 9, 26, 2, 7]
    news = [9, 6, 12, 4, 7, 10, 8]
    requests = []
    for i, (p, n) in enumerate(zip(lens, news)):
        kwargs = {"max_new_tokens": n, **sampling}
        if sampling:
            kwargs["seed"] = 100 + i
        requests.append((rng.integers(0, cfg.vocab_size, p).tolist(), kwargs))
    want = [_alone(cfg, params, kw, p, k) for p, k in requests]
    # An eos that request 2 meets in mid-decode: the first token of its
    # stream that did not occur before it.
    j = next(j for j in range(1, len(want[2]))
             if want[2][j] not in want[2][:j])
    requests[2][1]["eos_id"] = want[2][j]
    want[2] = want[2][:j + 1]
    return requests, want


@pytest.mark.parametrize("sampling", sorted(_SAMPLING))
@pytest.mark.parametrize("layout", sorted(_AHEAD_LAYOUTS))
def test_run_ahead_serves_the_oracles_tokens(layout_params, layout, sampling):
    """Run ahead or not, the tokens are the same: same program, same
    inputs, another moment of dispatch. With a cancel of a slot that the
    round in flight still carries, and the slot taken again at once."""
    cfg, kw, path = _AHEAD_LAYOUTS[layout]
    params = layout_params(cfg)
    requests, want = _ahead_requests(cfg, params, kw, _SAMPLING[sampling])
    engine = SlotEngine(cfg, params, slots=3, max_len=48, prefill_len=12, **kw)
    assert engine.decode_path == path
    compiled = engine.warmup()
    got = _drive_chunked(engine, requests, cancel={0: 4, 5: 3})
    for i, w in enumerate(want):
        if i in (0, 5):  # cancelled: what it was served is the oracle's head
            assert 3 <= len(got[i]) < len(w) and got[i] == w[:len(got[i])]
        else:
            assert got[i] == w, f"request {i} diverged"
    if not sampling:
        for i, (prompt, kwargs) in enumerate(requests):
            if i == 2 or cfg is not CFG:
                continue
            assert want[i] == _reference_greedy(
                params, prompt, kwargs["max_new_tokens"])
    assert engine.stats["rounds_ahead"] > 0
    assert engine.stats["rounds_ahead"] < engine.stats["plain_rounds"]
    assert engine.compile_count() == compiled
    assert engine.free_slots == 3


def test_a_slot_admitted_under_a_round_in_flight_keeps_the_hosts_word(params):
    """The merge. Fifteen slots decode and a round is in flight when the
    sixteenth is admitted: the next call reads that round, the fifteen take
    the device's registers, the sixteenth keeps the host's (its prompt's
    length, its own first token) and yields nothing from a round it was not
    in; the next round goes out from the merged registers in the same call,
    uploaded once, and the quiet rounds before and after upload nothing."""
    engine = SlotEngine(CFG, params, slots=16, max_len=48, prefill_len=12)
    uploads = []
    put = engine._put
    engine._put = lambda host: uploads.append(len(host)) or put(host)
    rng = np.random.default_rng(9)
    prompts = [rng.integers(0, CFG.vocab_size, 3 + i % 5).tolist()
               for i in range(16)]
    want = [_reference_greedy(params, p, 10) for p in prompts]
    got = {}
    for s in range(15):
        slot = engine.acquire_slot()
        assert slot == s
        got[s] = [engine.start(slot, prompts[s], max_new_tokens=10)[0]]

    def step():
        toks, valid, done = engine.step()
        for s in got:
            got[s] += [int(t) for t in toks[valid[:, s], s]]
        return valid, done

    step()
    assert uploads == [11]  # the first round: ten registers and the table
    step(), step()
    assert uploads == [11] and engine.stats["rounds_ahead"] == 3
    assert engine._flight is not None and engine._flight.ahead
    lengths = engine.lengths.copy()
    slot = engine.acquire_slot()
    assert slot == 15
    first, _ = engine.start(slot, prompts[15], max_new_tokens=10)
    got[15] = [first]
    valid, _ = step()
    assert not valid[:, 15].any() and valid[:, :15].all()
    # One upload, in the same call; nothing queued ahead of this reading.
    assert uploads == [11, 11] and engine.stats["rounds_ahead"] == 3
    assert engine._flight is not None and not engine._flight.ahead
    assert not engine._touched.any()
    assert (engine.lengths[:15] == lengths[:15] + 1).all()
    assert engine.lengths[15] == len(prompts[15])
    assert engine.made[15] == 1 and engine.cur_tok[15] == first
    while engine.active.any():
        step()
    assert uploads == [11, 11]
    assert [got[s] for s in range(16)] == want
