"""Paged-KV / prefix-cache / speculative-decoding correctness.

The contract under test is ISSUE 8's: the decode fast path may change how
fast tokens arrive, NEVER which tokens arrive. The anchor test churns
mixed-length, shared- and disjoint-prefix greedy requests through a
4-slot engine in three configurations of the pool — {paged, paged+prefix,
paged+prefix+speculative} — and requires each to serve the tokens of the
oracle that is not an engine: ``decoding.build_generate_fn``, one request
at a time on the model's own B=1 cache.
Around it: page refcount hygiene (everything free after drain),
double-free / stale-page-table units, prefix-adoption accounting, and
pages-exhausted admission requeue through the scheduler.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_tpu.models.transformer import (
    TransformerConfig,
    TransformerLM,
)
from distributed_tensorflow_tpu.serve.engine import SlotEngine
from distributed_tensorflow_tpu.serve.kv_pool import (
    TRASH_PAGE,
    InsufficientPages,
    PagedKVPool,
    PrefixCache,
)
from distributed_tensorflow_tpu.serve.scheduler import (
    Completion,
    Request,
    Scheduler,
)

pytestmark = [pytest.mark.serve, pytest.mark.paged]

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
    return TransformerLM(CFG).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]


def _drive(engine, requests, warm=True):
    """Closed-loop driver: feed ``requests`` (prompt, kwargs) through the
    engine keeping every slot busy; returns per-request token lists and
    asserts the compile count never moves after warmup. ``warm=False``
    skips the warmup call (second pass on an already-warm engine — warmup
    clears the prefix cache, which cache-reuse tests must keep)."""
    if warm:
        engine.warmup()
    base = engine.compile_count()
    outs = {}
    pending = list(range(len(requests)))
    slot2req = {}
    while pending or slot2req:
        while pending:
            slot = engine.acquire_slot()
            if slot is None:
                break
            i = pending[0]
            prompt, kwargs = requests[i]
            first, finished = engine.start(slot, prompt, **kwargs)
            pending.pop(0)
            # Chunked prefill returns first=None (the first token arrives
            # from a later step()).
            outs[i] = [] if first is None else [first]
            if finished:
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
        f"recompiled after warmup: {engine.compile_count()} != {base}"
    )
    return outs


def _churn_requests():
    """Mixed prompt/output lengths; two shared-prefix families plus
    disjoint prompts — the workload shape the tentpole optimizes."""
    rng = np.random.default_rng(7)
    fam_a = rng.integers(1, 64, 20).tolist()
    fam_b = rng.integers(1, 64, 12).tolist()
    prompts = (
        [fam_a + rng.integers(1, 64, int(t)).tolist() for t in (2, 4, 3)]
        + [fam_b + rng.integers(1, 64, int(t)).tolist() for t in (5, 2)]
        + [rng.integers(1, 64, int(n)).tolist() for n in (3, 9, 17, 23, 6)]
    )
    budgets = [6, 9, 12, 5, 8, 14, 4, 7, 10, 3]
    return [
        (p, {"max_new_tokens": b}) for p, b in zip(prompts, budgets)
    ]


def _sequential(cfg, params, requests):
    """The oracle: each greedy request alone through ``build_generate_fn``
    (the model's B=1 logical cache at the engines' length; no pool, no
    page, no slot)."""
    from tests.test_serve_engine import _reference_greedy

    return {
        i: _reference_greedy(params, prompt, kwargs["max_new_tokens"],
                             cfg=cfg, cache_len=48)
        for i, (prompt, kwargs) in enumerate(requests)
    }


_LAYOUTS = {
    "paged": dict(page_size=8, prefix_cache=False),
    "paged+prefix": dict(page_size=8, prefix_cache=True),
    "paged+prefix+spec": dict(page_size=8, prefix_cache=True, spec_k=4),
}


@pytest.mark.spec
def test_churn_parity_across_kv_layouts(params):
    """ISSUE 8 anchor: greedy tokens byte-identical to the sequential
    oracle in every configuration of the pool under 4-slot churn, zero
    recompiles in each."""
    requests = _churn_requests()
    baseline = _sequential(CFG, params, requests)
    for name, kw in _LAYOUTS.items():
        engine = SlotEngine(
            CFG, params, slots=4, max_len=48, prefill_len=26, **kw
        )
        got = _drive(engine, requests)
        if engine.prefix is not None:
            engine.prefix.clear()
        assert engine.pool.pages_free == engine.pool.num_pages - 1, (
            f"{name}: leaked pages after drain"
        )
        for i in range(len(requests)):
            assert got[i] == baseline[i], (
                f"{name} diverged from build_generate_fn on request {i}: "
                f"{got[i]} != {baseline[i]}"
            )


@pytest.mark.spec
def test_spec_parity_with_eos_and_budget_truncation(params):
    """Speculative rounds must truncate identically to plain decoding at
    eos and budget boundaries (the verify step's n_final logic)."""
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, 64, int(n)).tolist() for n in (5, 11, 19)]
    plain = SlotEngine(CFG, params, slots=2, max_len=48, prefill_len=24,
                       page_size=8, spec_k=0)
    # First pass (no eos) to discover each request's greedy stream, so we
    # can plant an eos id that genuinely fires mid-stream.
    ref = _drive(plain, [(p, {"max_new_tokens": 12}) for p in prompts])
    requests = []
    for i, p in enumerate(prompts):
        stream = ref[i]
        eos = stream[len(stream) // 2] if len(stream) > 2 else None
        requests.append(
            (p, {"max_new_tokens": 12,
                 **({"eos_id": eos} if eos is not None else {})})
        )
    plain2 = SlotEngine(CFG, params, slots=2, max_len=48, prefill_len=24,
                        page_size=8, spec_k=0)
    spec = SlotEngine(CFG, params, slots=2, max_len=48, prefill_len=24,
                      page_size=8, spec_k=4)
    out_plain = _drive(plain2, requests)
    out_spec = _drive(spec, requests)
    for i in range(len(requests)):
        assert out_spec[i] == out_plain[i], (
            f"spec diverged on eos/budget truncation, request {i}"
        )
    assert spec.stats["spec_rounds"] > 0


def test_prefix_adoption_accounting_and_reuse(params):
    """A repeated prompt adopts its full pages: hit counters advance,
    output is identical, and the adopted pages are SHARED (refcount > 1
    while both the cache and the new slot hold them)."""
    rng = np.random.default_rng(11)
    prompt = rng.integers(1, 64, 26).tolist()  # 3 full pages @ page_size 8
    engine = SlotEngine(CFG, params, slots=2, max_len=48, prefill_len=26,
                        page_size=8, prefix_cache=True)
    engine.warmup()
    slot = engine.acquire_slot()
    engine.start(slot, prompt, max_new_tokens=4)
    first_tables = engine.pool.page_tables[slot].copy()
    while engine.active[slot]:
        engine.step()
    engine.release(slot)
    assert engine.prefix.tokens_matched == 0  # cold
    slot2 = engine.acquire_slot()
    engine.start(slot2, prompt, max_new_tokens=4)
    # cap = (26-1)//8 = 3 pages, but only pages below max_len - prefill_len
    # = 22 -> 2 pages are adoptable; both must come from the first run.
    assert engine.prefix.tokens_matched == 16
    adopted = engine.pool.page_tables[slot2][:2]
    assert list(adopted) == list(first_tables[:2])
    for pid in adopted:
        assert engine.pool.refcount[pid] >= 2  # cache + this slot
    while engine.active[slot2]:
        engine.step()
    engine.release(slot2)
    engine.prefix.clear()
    assert engine.pool.pages_free == engine.pool.num_pages - 1


def test_paged_pool_double_free_and_stale_table():
    pool = PagedKVPool(CFG, slots=2, max_len=32, page_size=8)
    slot = pool.alloc()
    pages = pool.alloc_pages(3)
    pool.bind(slot, pages)
    assert list(pool.page_tables[slot][:3]) == pages
    assert pool.page_tables[slot][3] == TRASH_PAGE
    free_before = pool.pages_free
    pool.free(slot)
    # Stale-page-table hazard: the freed slot's row must point at trash so
    # a masked lane write can never land in a reassigned page.
    assert all(pid == TRASH_PAGE for pid in pool.page_tables[slot])
    assert pool.pages_free == free_before + 3
    with pytest.raises(ValueError, match="double free"):
        pool.free(slot)
    pid = pool.alloc_pages(1)[0]
    pool.decref(pid)
    with pytest.raises(ValueError, match="double free"):
        pool.decref(pid)
    with pytest.raises(ValueError):
        pool.incref(TRASH_PAGE)


def test_alloc_pages_hands_out_runs_of_neighbours_after_churn():
    """Pages come as neighbours in ascending order, whatever was freed in
    between: the smallest run of free ids that holds the request whole,
    else the largest runs first. Rows of neighbours are what the paged
    decode kernel copies a step at a time."""
    pool = PagedKVPool(CFG, slots=4, max_len=64, page_size=8)
    rows = {}
    for n in (5, 8, 3, 6):
        slot = pool.alloc()
        rows[slot] = pool.alloc_pages(n)
        pool.bind(slot, rows[slot])
    for pages in rows.values():
        assert np.array_equal(np.diff(pages), np.ones(len(pages) - 1))
    (a, five), (b, eight), (c, three), _ = rows.items()
    tail = pool.pages_free  # the untouched run behind the four rows
    pool.free(c)
    pool.free(a)
    # Holes of 5 and 3 (apart: the row of 8 lies between) and the tail:
    # a request goes into the smallest hole that holds it whole ...
    assert pool.alloc_pages(3) == three
    assert pool.alloc_pages(4) == five[:4]
    # ... and, where none does, takes the largest runs first, sorted. The
    # freed row of 8 and the page left of the row of 5 are one run of 9.
    pool.free(b)
    assert pool.pages_free == tail + 1 + 8
    got = pool.alloc_pages(tail + 8)
    assert got == five[4:] + eight[:7] + list(
        range(pool.num_pages - tail, pool.num_pages))
    assert pool.alloc_pages(2) is None and pool.alloc_pages(1) == eight[7:]
    assert pool.alloc_pages(0) == []


def test_paged_pool_refcount_sharing():
    pool = PagedKVPool(CFG, slots=2, max_len=32, page_size=8)
    cache = PrefixCache(pool)
    prompt = np.arange(1, 20, dtype=np.int32)  # 2 full pages
    pages = pool.alloc_pages(3)
    cache.insert(prompt, pages)
    assert len(cache) == 2
    assert pool.refcount[pages[0]] == 2  # owner + cache
    matched = cache.match(prompt, 2)
    assert matched == pages[:2]
    assert pool.refcount[pages[0]] == 3
    # Mismatched prompt shares page 1 only.
    other = prompt.copy()
    other[10] = 63
    assert cache.match(other, 2) == pages[:1]
    # Eviction drops only the cache's reference.
    for pid in matched:
        pool.decref(pid)
    pool.decref(pages[0])  # extra match above
    cache.evict_for(pool.num_pages)  # force full eviction
    assert len(cache) == 0
    assert pool.refcount[pages[0]] == 1  # original owner survives
    for pid in pages:
        pool.decref(pid)
    assert pool.pages_free == pool.num_pages - 1


def test_prefix_cache_keys_are_exact_prefixes():
    """A page is adopted only behind the exact prefix it was indexed
    behind: the same tokens after another first page match nothing past
    it."""
    pool = PagedKVPool(CFG, slots=2, max_len=32, page_size=8)
    cache = PrefixCache(pool)
    prompt = np.arange(1, 25, dtype=np.int32)  # 3 full pages
    pages = pool.alloc_pages(3)
    cache.insert(prompt, pages)
    other = prompt.copy()
    other[:8] = 99  # page 0 differs, pages 1 and 2 are the same tokens
    assert cache.match(other, 3) == []
    assert cache.match(prompt, 3) == pages
    assert cache.match(prompt[:16], 2) == pages[:2]
    assert len(cache) == 3


def _orphans(cache):
    """Entries whose parent entry is not in the cache."""
    nodes = {PrefixCache.ROOT} | {n for _, n in cache._entries.values()}
    return [k for k in cache._entries
            if (k[1] if k[0] == "S" else k[0]) not in nodes]


def test_prefix_cache_evicts_leaves_first():
    """Eviction takes a chain's last page before the pages it hangs from,
    also after a lookup of a shorter prefix or an insert of a branch
    touched the chain's head last: no entry is ever left behind that no
    lookup could reach."""
    pool = PagedKVPool(CFG, slots=2, max_len=32, page_size=8)
    cache = PrefixCache(pool)
    prompt = np.arange(1, 25, dtype=np.int32)  # 3 full pages
    pages = pool.alloc_pages(3)
    cache.insert(prompt, pages)
    branch = prompt[:16].copy()
    branch[8:] = 77  # shares page 0, then its own page 1
    own = pool.alloc_pages(1)
    cache.insert(branch, [pages[0], own[0]])
    for pid in pages + own:
        pool.decref(pid)  # the cache's references alone hold them now
    for pid in cache.match(prompt[:8], 1):  # touches the head alone
        pool.decref(pid)
    order = []
    while len(cache):
        before = dict(cache._entries)
        assert cache.evict_for(pool.pages_free + 1) == 1
        (gone,) = set(before) - set(cache._entries)
        order.append(before[gone][0])
        assert not _orphans(cache)
    assert order[-1] == pages[0]  # the shared head goes last
    assert order.index(pages[2]) < order.index(pages[1])
    assert pool.pages_free == pool.num_pages - 1


def test_paged_pool_slot_bookkeeping():
    """The slot free list: every slot once, ``None`` at exhaustion, the
    slot freed last is the next one handed out, and the companion set that
    the double-free check reads mirrors the list under churn."""
    pool = PagedKVPool(CFG, slots=4, max_len=16, page_size=8)
    assert pool.num_free == 4
    assert pool._free_slot_set == set(pool._free_slots)
    slots = [pool.alloc() for _ in range(4)]
    assert sorted(slots) == [0, 1, 2, 3] and pool.alloc() is None
    assert pool.num_free == 0 and pool._free_slot_set == set()
    for s in slots[::-1]:
        pool.free(s)
        assert pool._free_slot_set == set(pool._free_slots)
    with pytest.raises(ValueError, match="double free"):
        pool.free(slots[0])
    with pytest.raises(ValueError, match="outside"):
        pool.free(99)
    assert pool.alloc() == slots[0]  # LIFO
    pool.free(slots[0])
    a, b = pool.alloc(), pool.alloc()
    pool.free(a)
    assert pool.alloc() == a and pool.num_free == 2
    assert pool._free_slot_set == set(pool._free_slots) == set(slots) - {a, b}


def test_insufficient_pages_requeues_instead_of_rejecting(params):
    """Admission under page pressure: a pool sized for ~one worst-case
    request at a time must still complete every submitted request (requeue
    at the head of the lane, never a rejection)."""
    pps = 48 // 8
    engine = SlotEngine(
        CFG, params, slots=4, max_len=48, prefill_len=24,
        page_size=8, kv_pages=pps + 1, prefix_cache=True, spec_k=0,
    )
    engine.warmup()
    sched = Scheduler(engine)
    rng = np.random.default_rng(5)
    handles = [
        sched.submit(Request(
            prompt=tuple(int(t) for t in rng.integers(1, 64, 20)),
            max_new_tokens=20,
        ))
        for _ in range(3)
    ]
    sched.run_until_idle(max_steps=500)
    for h in handles:
        outcome = h.result(timeout=5)
        assert isinstance(outcome, Completion), outcome
        assert len(outcome.tokens) == 20
    if engine.prefix is not None:
        engine.prefix.clear()
    assert engine.pool.pages_free == engine.pool.num_pages - 1


def test_engine_start_raises_insufficient_pages_directly(params):
    engine = SlotEngine(
        CFG, params, slots=2, max_len=48, prefill_len=24,
        page_size=8, kv_pages=(48 // 8) + 1, prefix_cache=False,
    )
    engine.warmup()
    s1 = engine.acquire_slot()
    engine.start(s1, [1, 2, 3], max_new_tokens=40)  # claims all 6 pages
    s2 = engine.acquire_slot()
    assert s2 is not None  # slots are free; PAGES are the gate
    with pytest.raises(InsufficientPages):
        engine.start(s2, [4, 5, 6], max_new_tokens=40)
    # The failed start must not leak: same slot starts fine after drain.
    while engine.active[s1]:
        engine.step()
    engine.release(s1)
    engine.start(s2, [4, 5, 6], max_new_tokens=40)
    while engine.active[s2]:
        engine.step()
    engine.release(s2)
    assert engine.pool.pages_free == engine.pool.num_pages - 1


# int8-KV rows of the churn matrix (ISSUE 14 satellite): same contract as
# the bf16 matrix above, baselined against the sequential oracle on the
# int8 config (int8 changes numerics vs bf16 by design; it must not change
# them between the model's own cache and the pool's pages).
_INT8_LAYOUTS = {
    "paged+prefix": dict(page_size=8, prefix_cache=True),
    "paged+prefix+spec": dict(page_size=8, prefix_cache=True, spec_k=4),
    "paged+prefix+tree": dict(page_size=8, prefix_cache=True, spec_k=4,
                              spec_branches=2),
    "paged+prefix+chunked": dict(page_size=8, prefix_cache=True,
                                 prefill_chunk_tokens=8),
}


@pytest.mark.spec
@pytest.mark.kvquant
def test_churn_parity_int8_kv_layouts(params):
    """Quantize-on-write int8 KV as the LIVE decode format: greedy tokens
    those of the sequential oracle on the int8 config in {paged+prefix,
    +spec, +tree, +chunked}, zero recompiles in each."""
    from dataclasses import replace

    cfg8 = replace(CFG, kv_cache_dtype="int8")
    requests = _churn_requests()
    baseline = _sequential(cfg8, params, requests)
    for name, kw in _INT8_LAYOUTS.items():
        engine = SlotEngine(
            cfg8, params, slots=4, max_len=48, prefill_len=26, **kw
        )
        assert engine.kv_dtype == "int8"
        got = _drive(engine, requests)
        if engine.prefix is not None:
            engine.prefix.clear()
        assert engine.pool.pages_free == engine.pool.num_pages - 1, (
            f"{name}: leaked pages after drain"
        )
        for i in range(len(requests)):
            assert got[i] == baseline[i], (
                f"int8 {name} diverged from int8 build_generate_fn on "
                f"request {i}: {got[i]} != {baseline[i]}"
            )


@pytest.mark.kvquant
def test_prefix_adoption_int8_token_identical(params):
    """Adopted int8 pages decode token-identically to fresh-prefill int8
    pages: a second pass of the same workload (warm prefix cache, pages
    adopted) must reproduce the cold pass exactly."""
    from dataclasses import replace

    cfg8 = replace(CFG, kv_cache_dtype="int8")
    requests = _churn_requests()
    engine = SlotEngine(cfg8, params, slots=4, max_len=48, prefill_len=26,
                        page_size=8, prefix_cache=True, spec_k=3)
    cold = _drive(engine, requests)
    matched_cold = engine.prefix.tokens_matched
    warm = _drive(engine, requests, warm=False)
    assert engine.prefix.tokens_matched > matched_cold  # pages adopted
    for i in range(len(requests)):
        assert warm[i] == cold[i], (
            f"adopted int8 pages diverged on request {i}"
        )
    engine.prefix.clear()
    assert engine.pool.pages_free == engine.pool.num_pages - 1


@pytest.mark.kvquant
def test_kv_bytes_per_token_accounting(params):
    """The pool's measured bytes/token equals the analytic helper in both
    formats, and int8 lands under the 0.55x byte-diet ceiling."""
    from dataclasses import replace

    from distributed_tensorflow_tpu.models.quant import (
        kv_cache_bytes_per_token,
    )

    cfg8 = replace(CFG, kv_cache_dtype="int8")
    kw = dict(slots=2, max_len=48, prefill_len=24)
    for page_size in (8, 48):
        hi = SlotEngine(CFG, params, page_size=page_size, **kw)
        lo = SlotEngine(cfg8, params, page_size=page_size, **kw)
        assert hi.kv_dtype == "bf16" and lo.kv_dtype == "int8"
        assert hi.kv_bytes_per_token == kv_cache_bytes_per_token(CFG)
        assert lo.kv_bytes_per_token == kv_cache_bytes_per_token(cfg8)
        assert lo.kv_bytes_per_token / hi.kv_bytes_per_token <= 0.55


@pytest.mark.spec
def test_paged_int8_kv_parity(params):
    """int8 KV rows + f32 scales page through gather/scatter untouched
    (no requantization), so quantized paged/spec output must equal
    the sequential oracle's on the quantized B=1 cache."""
    from dataclasses import replace

    cfg8 = replace(CFG, kv_cache_dtype="int8")
    rng = np.random.default_rng(9)
    requests = [
        (rng.integers(1, 64, int(n)).tolist(), {"max_new_tokens": b})
        for n, b in ((7, 6), (15, 9), (21, 5))
    ]
    fast = SlotEngine(cfg8, params, slots=2, max_len=48, prefill_len=24,
                      page_size=8, prefix_cache=True, spec_k=3)
    want = _sequential(cfg8, params, requests)
    out_fast = _drive(fast, requests)
    for i in range(len(requests)):
        assert out_fast[i] == want[i]
