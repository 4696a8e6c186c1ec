"""Decode through the page table: the kernel, and the engine path it serves.

The contract is the paged-KV one (``test_paged_kv.py``): how K and V are
reached may change how fast tokens arrive, never which. So the kernel
(``ops.attention.paged_decode_attention``) is held against dense f32
attention over the gathered rows, and an engine whose plain decode round
reads the pages in place (``decode_path == "table"``) against the same
engine made to gather (a subclass that overrides the ``_decode_path``
hook, as ``ShardedSlotEngine`` does), on the churn matrices of
``test_paged_kv.py`` at a head size and page size the table path takes.
Shapes are small: off the TPU the kernel runs in interpret mode.
"""

import time
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_tpu.models.transformer import (
    TransformerConfig,
    TransformerLM,
)
from distributed_tensorflow_tpu.obs import trace
from distributed_tensorflow_tpu.ops.attention import (
    paged_decode_attention,
    paged_decode_chain,
    paged_decode_copies,
    paged_decode_form,
    paged_row_write,
)
from distributed_tensorflow_tpu.serve.engine import (
    ShardedSlotEngine,
    SlotEngine,
)
from distributed_tensorflow_tpu.serve.kv_pool import TRASH_PAGE
from tests.test_paged_kv import _churn_requests, _drive

pytestmark = [pytest.mark.serve, pytest.mark.paged]

# dh 128: the head size the table path takes (and the benchmark's).
CFG = TransformerConfig(
    vocab_size=64,
    d_model=256,
    num_heads=2,
    num_layers=2,
    d_ff=64,
    max_seq_len=48,
    compute_dtype=jnp.float32,
)


class GatherEngine(SlotEngine):
    """The same engine on the gather path, whatever its shapes."""

    def _decode_path(self):
        return "gather"


@pytest.fixture(scope="module")
def params():
    return TransformerLM(CFG).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]


# -- the kernel ------------------------------------------------------------


def _dense(q, k_pages, v_pages, tables, lens, window):
    """Dense f32 attention over each slot's gathered rows, masked as the
    cached branch of ``attention_sublayer`` masks them."""
    slots, kv, _, dh = q.shape
    ps = k_pages.shape[2]
    out = np.zeros(q.shape, np.float32)
    for b in range(slots):
        n = int(lens[b])
        if not n:
            continue
        rows = lambda pages: np.concatenate(
            [np.asarray(pages[p], np.float32) for p in tables[b]], axis=1
        )  # (kv, pps * ps, dh)
        k, v = rows(k_pages), rows(v_pages)
        lo = max(0, n - window) if window else 0
        for h in range(kv):
            s = np.asarray(q[b, h], np.float32) @ k[h, lo:n].T / np.sqrt(dh)
            p = np.exp(s - s.max(-1, keepdims=True))
            out[b, h] = (p / p.sum(-1, keepdims=True)) @ v[h, lo:n]
    return out


def _pool(rng, dtype, kv, ps, pps, slots, dh=128):
    pages = slots * pps + 3
    k = jnp.asarray(rng.standard_normal((pages, kv, ps, dh)), dtype)
    v = jnp.asarray(rng.standard_normal((pages, kv, ps, dh)), dtype)
    # Out of order, and slot 1 shares slot 0's first two pages.
    tables = rng.permutation(np.arange(1, pages))[: slots * pps]
    tables = tables.reshape(slots, pps).astype(np.int32)
    tables[1, :2] = tables[0, :2]
    return k, v, tables


def _kernel_call(fn, *args):
    """The ``pallas_call`` equation ``fn(*args)`` traces to."""
    def find(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                return eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                if (hit := find(sub)) is not None:
                    return hit
        return None

    return find(jax.make_jaxpr(fn)(*args).jaxpr)


# The two forms of the kernel's products (``paged_decode_form``): the row
# form up to two query rows a kv head, the group form beyond.
_FORMS = [(2, 1, "row"), (3, 2, "row"), (2, 12, "group"), (1, 3, "group")]
_FORM_IDS = [f"kv{kv}-group{g}-{form}" for kv, g, form in _FORMS]

# The copy chain's cases, at 8 pages a chunk and therefore 8 pages a copy
# step (``paged_decode_chain``; pages of 8 rows, rows of 24 pages): each a
# list of slots ``(live pages, layout of the slot's table row)`` and a
# window. A slot's length ends 3 rows short of its last page's end.
# Layouts: "scatter" ids in no order; "up" / "down" one run of neighbours,
# ascending / descending; "up@4" a run of 16 neighbours from logical page 4
# (it crosses the chunk's edges at 8 and 16 and fills the step between
# them) among scattered pages.
_CHAINS = {
    # The last chunk's page count, by what the wait handles: one page, a
    # half, a half and one, the whole chunk less one.
    "last-1": ([(9, "up"), (17, "scatter"), (1, "up")], None),
    "last-half": ([(12, "scatter"), (20, "up")], None),
    "last-half-and-1": ([(13, "up"), (21, "scatter")], None),
    "last-whole-less-1": ([(15, "scatter"), (23, "up"), (7, "up")], None),
    "ends-on-a-chunk": ([(8, "up"), (16, "scatter"), (24, "up")], None),
    # One slot's last chunk hands over to the next LIVE slot's first.
    "handover-over-dead-slots": (
        [(13, "up"), (0, "up"), (0, "scatter"), (3, "scatter"), (0, "up"),
         (17, "up")], None),
    # 43 positions: whole pages below the window are skipped, and a slot's
    # first live page is no multiple of a step.
    "window-skips-pages": ([(9, "up"), (17, "scatter"), (24, "up")], 43),
    "runs-ascending": ([(24, "up"), (16, "up"), (19, "up")], None),
    "runs-descending": ([(24, "down"), (16, "down"), (11, "down")], None),
    "scattered": ([(24, "scatter"), (16, "scatter"), (11, "scatter")], None),
    "run-crosses-a-chunk": ([(24, "up@4"), (21, "up@4"), (18, "up@4")], None),
}
_CHAIN_PS, _CHAIN_PPS, _CHAIN_CHUNK = 8, 24, 8


def _chain_pool(rng, kv, slots_spec):
    """Pool leaves, table rows laid out as ``slots_spec`` says, lengths."""
    ps, pps = _CHAIN_PS, _CHAIN_PPS
    slots = len(slots_spec)
    pages = 2 * slots * pps + 1
    k = jnp.asarray(rng.standard_normal((pages, kv, ps, 128)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((pages, kv, ps, 128)), jnp.float32)
    # Odd ids for the scattered rows (no two of them neighbours), a block of
    # even-and-odd neighbours a slot for the runs.
    odd = rng.permutation(np.arange(1, slots * pps, 2))
    tables = np.zeros((slots, pps), np.int32)
    lens = np.zeros(slots, np.int32)
    for b, (live, layout) in enumerate(slots_spec):
        block = slots * pps + 1 + b * pps + np.arange(pps)
        scatter = odd[(b * pps // 2 + np.arange(pps)) % odd.size]
        tables[b] = {"scatter": scatter, "up": block, "down": block[::-1],
                     "up@4": np.concatenate(
                         [scatter[:4], block[:16], scatter[4:8]])}[layout]
        lens[b] = max(0, live * ps - 3)
    return k, v, tables, lens


_RAGGED = [pytest.param(kv, g, form, window, None,
                        id=f"kv{kv}-group{g}-{form}-{wid}")
           for kv, g, form in _FORMS
           for window, wid in ((None, "full"), (11, "window11"))]
_CHAINED = [pytest.param(kv, g, form, None, name,
                         id=f"kv{kv}-group{g}-{form}-{name}")
            for kv, g, form in (_FORMS[0], _FORMS[2]) for name in _CHAINS]


@pytest.mark.parametrize("kv,group,form,window,chain", _RAGGED + _CHAINED)
def test_kernel_matches_dense_over_ragged_lengths(kv, group, form, window,
                                                  chain):
    assert paged_decode_form(group) == form
    rng = np.random.default_rng(group)
    if chain is None:
        ps, pps, chunk = 8, 6, 2
        max_len = ps * pps
        # Among them a slot of length 0, lengths that end mid-page, and one
        # (2 * ps) that ends exactly where a chunk of two pages does. Two
        # pages a chunk: the longest slots take three chunks, so the copy
        # chain crosses chunks and slots.
        lens = np.array(
            [1, ps - 1, ps, ps + 1, 2 * ps, max_len - 1, 0, max_len],
            np.int32)
        k, v, tables = _pool(rng, jnp.float32, kv, ps, pps, lens.size)
    else:
        slots_spec, window = _CHAINS[chain]
        chunk = _CHAIN_CHUNK
        k, v, tables, lens = _chain_pool(rng, kv, slots_spec)
    q = jnp.asarray(rng.standard_normal((lens.size, kv, group, 128)),
                    jnp.float32)
    got = paged_decode_attention(
        q, k, v, jnp.asarray(tables), jnp.asarray(lens), window=window,
        pages_per_chunk=chunk,
    )
    want = _dense(q, k, v, tables, lens, window)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-6, rtol=2e-6)
    # lens 0: nothing read, zeros
    assert not np.asarray(got)[lens == 0].any()


@pytest.mark.parametrize("group,form,slack", [
    (12, "group", 2.0), (2, "row", 1.05), (1, "row", 1.05),
], ids=["group12", "row-group2", "row-group1"])
def test_kernel_bf16_pool_keeps_f32_probabilities(group, form, slack):
    """A bf16 pool at the benchmark's page size: the value product keeps
    the probabilities' f32 precision, so what is left is the rounding of
    the output itself; on the row form, where every probability enters the
    product as f32, hardly more than that one rounding."""
    ps, pps, kv = 16, 4, 2
    lens = np.array([ps * pps, 3, 0, ps + 5], np.int32)
    assert paged_decode_form(group) == form
    rng = np.random.default_rng(5)
    k, v, tables = _pool(rng, jnp.bfloat16, kv, ps, pps, lens.size)
    q = jnp.asarray(rng.standard_normal((lens.size, kv, group, 128)),
                    jnp.bfloat16)
    got = paged_decode_attention(
        q, k, v, jnp.asarray(tables), jnp.asarray(lens)
    )
    assert got.dtype == jnp.bfloat16
    want = _dense(q, k, v, tables, lens, None)
    rounding = np.abs(
        np.asarray(jnp.asarray(want, jnp.bfloat16), np.float32) - want
    ).max()
    err = np.abs(np.asarray(got, np.float32) - want).max()
    assert err <= slack * rounding + 1e-6, (err, rounding)


def test_kernel_form_follows_from_the_shapes_alone():
    """No argument chooses the form: the group size does, and
    ``starcoder2-3b``'s shape (2 kv heads, group 12, bf16) lowers to the
    call it always had: q padded to a tile of rows a head, the output in
    q's dtype, no compiler parameter."""
    assert [paged_decode_form(g) for g in (1, 2, 3, 4, 12)] == [
        "row", "row", "group", "group", "group"]

    def call(kv, group):
        shape = jax.ShapeDtypeStruct
        return _kernel_call(
            lambda *a: paged_decode_attention(*a, interpret=True),
            shape((4, kv, group, 128), jnp.bfloat16),
            shape((9, kv, 16, 128), jnp.bfloat16),
            shape((9, kv, 16, 128), jnp.bfloat16),
            shape((4, 2), jnp.int32), shape((4,), jnp.int32),
        )

    old, new = call(2, 12), call(32, 1)
    assert [v.aval.shape for v in old.invars[2:3]] == [(4, 2, 16, 128)]
    assert old.outvars[0].aval.dtype == jnp.bfloat16
    assert not old.params["compiler_params"]
    assert [v.aval.shape for v in new.invars[2:3]] == [(4, 32, 128)]
    assert new.outvars[0].aval.dtype == jnp.float32


@pytest.mark.parametrize("chain", [None, *_CHAINS])
def test_kernel_reads_only_the_live_pages(chain):
    """Every page the lengths (and the window) do not reach is poisoned: a
    kernel that copied a dead page of a row, a neighbour beyond a run's
    live end, or a page of no row, would read NaN."""
    rng = np.random.default_rng(3)
    if chain is None:
        ps, pps, kv, chunk, window = 8, 6, 1, 2, None
        lens = np.array([ps + 1, 0, 3 * ps], np.int32)
        k, v, tables = _pool(rng, jnp.float32, kv, ps, pps, lens.size)
    else:
        (slots_spec, window), kv, ps = _CHAINS[chain], 1, _CHAIN_PS
        chunk = _CHAIN_CHUNK
        k, v, tables, lens = _chain_pool(rng, kv, slots_spec)
    live = {int(p) for b, n in enumerate(lens)
            for p in tables[b, (max(0, int(n) - window) // ps
                                if window else 0): -(-int(n) // ps)]}
    dead = np.array([p for p in range(k.shape[0]) if p not in live])
    k, v = k.at[dead].set(jnp.nan), v.at[dead].set(jnp.nan)
    q = jnp.asarray(rng.standard_normal((lens.size, kv, 4, 128)),
                    jnp.float32)
    got = np.asarray(paged_decode_attention(
        q, k, v, jnp.asarray(tables), jnp.asarray(lens), window=window,
        pages_per_chunk=chunk,
    ))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(
        got, _dense(q, np.nan_to_num(np.asarray(k)),
                    np.nan_to_num(np.asarray(v)), tables, lens, window),
        atol=2e-6, rtol=2e-6,
    )


def _copies_one_by_one(tables, lens, chunk, run, ps, window):
    """The kernel's chain walked in Python: a chunk at a time, a step of
    ``run`` pages at a time, what is left of a chunk page by page."""
    copies = pages = 0
    for row, n in zip(tables, lens):
        p0 = max(0, int(n) - window) // ps if window else 0
        live = -(-int(n) // ps) - p0
        pages += live
        for c0 in range(0, live, chunk):
            ids = row[p0 + c0: p0 + min(live, c0 + chunk)]
            for s0 in range(0, len(ids) - run + 1, run):
                step = ids[s0: s0 + run]
                adjacent = run > 1 and (np.diff(step) == 1).all()
                copies += 1 if adjacent else run
            copies += len(ids) % run
    return copies, pages


@pytest.mark.parametrize("chain", list(_CHAINS))
def test_copies_counted_on_the_host_follow_the_kernels_rule(chain):
    """``paged_decode_copies`` (what ``engine.round`` notes as
    ``kv_copies`` / ``kv_pages``) against the chain walked page by page, at
    the sizes the chain takes for this leaf: rows of 24 pages of 4 KiB make
    one chunk of 24 pages and steps of 16."""
    slots_spec, window = _CHAINS[chain]
    k, _, tables, lens = _chain_pool(np.random.default_rng(1), 1, slots_spec)
    chunk, run = paged_decode_chain(k, _CHAIN_PPS)
    assert (chunk, run) == (24, 16)
    got = paged_decode_copies(tables, lens, k, window=window)
    assert got == _copies_one_by_one(tables, lens, chunk, run, _CHAIN_PS,
                                     window)
    if chain == "runs-ascending":
        # 24, 16 and 19 pages: one step of 16 neighbours each, the rest
        # page by page.
        assert got == (3 + 8 + 0 + 3, 24 + 16 + 19)
    if chain in ("runs-descending", "scattered"):
        assert got[0] == got[1]  # a copy a page


@pytest.mark.parametrize("kv,ps,dtype,pps,want", [
    (2, 16, jnp.bfloat16, 256, (128, 16)),  # the 2-kv-head cells: 8 KiB
    (32, 16, jnp.bfloat16, 248, (8, 1)),  # evabyte-6.5b: 128 KiB a page
    (2, 8, jnp.float32, 6, (6, 4)),  # a row shorter than a chunk
    (1, 16, jnp.bfloat16, 4096, (256, 32)),
], ids=["2kv-bf16", "evabyte", "short-row", "1kv"])
def test_chain_sizes_follow_from_the_pages_bytes(kv, ps, dtype, pps, want):
    """A chunk of 1 MiB a buffer and a copy step of 128 KiB, a power of two
    of pages, whatever the model: no argument chooses."""
    leaf = jax.ShapeDtypeStruct((9, kv, ps, 128), dtype)
    assert paged_decode_chain(leaf, pps) == want


def test_kernel_refuses_shapes_that_do_not_fit():
    k = jnp.zeros((4, 2, 8, 128))
    with pytest.raises(ValueError, match="does not fit pages"):
        paged_decode_attention(jnp.zeros((2, 1, 4, 128)), k, k,
                               jnp.zeros((2, 3), jnp.int32),
                               jnp.zeros((2,), jnp.int32))
    with pytest.raises(ValueError, match="slots"):
        paged_decode_attention(jnp.zeros((2, 2, 4, 128)), k, k,
                               jnp.zeros((3, 3), jnp.int32),
                               jnp.zeros((2,), jnp.int32))


# -- the row write ------------------------------------------------------------


def _scatter_rows(leaf, new, pages, offsets):
    """The scatter the page-copy kernel stands in for: every lane's row into
    the leaf seen as (pages * kv * page_size, head_dim)."""
    n, kv, ps, dh = leaf.shape
    rows = ((pages[:, None] * kv + np.arange(kv)[None, :]) * ps
            + offsets[:, None]).reshape(-1)
    return leaf.reshape(n * kv * ps, dh).at[rows].set(
        new.reshape(-1, dh).astype(leaf.dtype)).reshape(leaf.shape)


def _write_case(kv, slots=6, ps=16, seed=0):
    """A bf16 pool of 3 pages a lane and new f32 rows: the lanes' pages
    scattered over it, offsets 0, 7 and 15 among them, lane 2 masked with
    its page at a real page of its own."""
    rng = np.random.default_rng(seed)
    n = 3 * slots + 1
    k = jnp.asarray(rng.standard_normal((n, kv, ps, 128)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((n, kv, ps, 128)), jnp.bfloat16)
    rows = [jnp.asarray(rng.standard_normal((slots, kv, 128)), jnp.float32)
            for _ in range(2)]
    pages = rng.permutation(np.arange(1, n))[:slots].astype(np.int32)
    offsets = np.array([0, 7, 15, 3, 15, 0], np.int32)[:slots]
    live = np.arange(slots) != 2
    return k, v, rows, pages, offsets, live


@pytest.mark.parametrize("kv", [2, 32], ids=["2kv", "32kv-evabyte"])
def test_row_write_matches_the_scatter(kv):
    """Every page but the trash page is bitwise what ``.at[rows].set`` gives
    with the masked lane sent to the trash page, the kernel's input leaves
    are left as they were (a copy where nothing is donated), and the masked
    lane's own page is untouched."""
    k, v, (kr, vr), pages, offsets, live = _write_case(kv)
    got_k, got_v = paged_row_write(k, v, kr, vr, jnp.asarray(pages),
                                   jnp.asarray(offsets), jnp.asarray(live))
    trash = np.where(live, pages, TRASH_PAGE)
    for got, leaf, new in ((got_k, k, kr), (got_v, v, vr)):
        want = _scatter_rows(leaf, new, trash, offsets)
        assert got.dtype == leaf.dtype
        np.testing.assert_array_equal(np.asarray(got[1:]),
                                      np.asarray(want[1:]))
        np.testing.assert_array_equal(np.asarray(got[TRASH_PAGE]),
                                      np.asarray(leaf[TRASH_PAGE]))
        np.testing.assert_array_equal(np.asarray(got[pages[2]]),
                                      np.asarray(leaf[pages[2]]))


def test_row_write_with_no_lane_live_changes_nothing():
    k, v, (kr, vr), pages, offsets, _ = _write_case(2)
    got = paged_row_write(k, v, kr, vr, jnp.asarray(pages),
                          jnp.asarray(offsets), jnp.zeros(6, bool))
    for g, leaf in zip(got, (k, v)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(leaf))


@pytest.mark.parametrize("kv", [2, 32], ids=["2kv", "32kv-evabyte"])
def test_summary_write_touches_only_the_lanes_that_fill_a_chunk(kv):
    """EVA's second call: ``live`` = the lanes whose token fills its chunk
    ((len + 1) % page_size == 0); their summary row at (len % window) //
    chunk lands in their forming page, and nothing else of the pool moves."""
    k, v, (sk, sv), pages, _, _ = _write_case(kv, seed=1)
    window, ps = 256, 16
    lengths = np.array([15, 30, 47, 63, 200, 255], np.int32)
    fills = (lengths + 1) % ps == 0
    assert fills.tolist() == [True, False, True, True, False, True]
    row = (lengths % window) // ps % ps
    got_k, got_v = paged_row_write(k, v, sk, sv, jnp.asarray(pages),
                                   jnp.asarray(row), jnp.asarray(fills))
    for got, leaf, new in ((got_k, k, sk), (got_v, v, sv)):
        got, leaf = np.asarray(got), np.asarray(leaf)
        moved = (got != leaf).any(axis=(1, 3))  # (pages, rows)
        want = np.zeros_like(moved)
        want[pages[fills], row[fills]] = True
        np.testing.assert_array_equal(moved, want)
        np.testing.assert_array_equal(
            got[pages[fills], :, row[fills]],
            np.asarray(new.astype(leaf.dtype))[fills])


def test_row_write_refuses_shapes_that_do_not_fit():
    k = jnp.zeros((4, 2, 8, 128), jnp.bfloat16)  # a page of 8 bf16 rows
    lanes = jnp.zeros((2,), jnp.int32)
    with pytest.raises(ValueError, match="off the tile"):
        paged_row_write(k, k, jnp.zeros((2, 2, 128)), jnp.zeros((2, 2, 128)),
                        lanes, lanes, lanes)
    with pytest.raises(ValueError, match="do not fit"):
        paged_row_write(k, k, jnp.zeros((2, 4, 128)), jnp.zeros((2, 4, 128)),
                        lanes, lanes, lanes)


# -- which engines take which path -----------------------------------------


def _engine(cfg=CFG, cls=SlotEngine, **kw):
    p = TransformerLM(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    kw = {"slots": 2, "max_len": 32, "prefill_len": 16, **kw}
    return cls(cfg, p, **kw)


_SMALL = replace(CFG, d_model=32, num_heads=4, max_seq_len=32)  # dh 8
_BF16 = replace(CFG, compute_dtype=jnp.bfloat16, max_seq_len=32)
_GQA = replace(CFG, d_model=512, num_heads=4, num_kv_heads=1, max_seq_len=32)


@pytest.mark.parametrize("want,form,make", [
    ("table", "row", lambda: _engine(page_size=8)),
    ("table", "row", lambda: _engine(_BF16, page_size=16)),
    ("table", "row", lambda: _engine(page_size=8, spec_k=2)),
    ("table", "group", lambda: _engine(_GQA, page_size=8)),
    # The configurations no cell of the benchmark runs: all gather.
    ("gather", None, lambda: _engine(_BF16, page_size=8)),
    ("gather", None, lambda: _engine(_SMALL, page_size=8)),
    ("gather", None, lambda: _engine(replace(CFG, kv_cache_dtype="int8"),
                                     page_size=8)),
    ("gather", None, lambda: _engine(replace(CFG, max_seq_len=32),
                                     cls=ShardedSlotEngine, tp=2,
                                     page_size=8)),
], ids=["f32-page8", "bf16-page16", "spec-plain-step", "gqa-group4",
        "bf16-page8", "head8", "int8-kv", "sharded"])
def test_decode_path_is_fixed_by_what_the_engine_sees(want, form, make):
    """... and with it the form the paged kernel's products take in the
    decode program, which the engine reports beside its counters."""
    engine = make()
    assert engine.decode_path == want
    assert engine.decode_kernel_form == form
    assert engine.stats["decode_kernel_form"] == form


class DenseEngine(SlotEngine):
    """The same engine with the dense prefill lines, whatever its shapes."""

    def _prefill_path(self):
        return "dense"


@pytest.mark.parametrize("want,make", [
    ("flash", lambda: _engine(page_size=8)),
    ("flash", lambda: _engine(_BF16, page_size=16)),
    ("flash", lambda: _engine(_GQA, page_size=8, prefill_buckets=(8,))),
    # What the kernel does not take, and what no cell runs: all dense.
    ("dense", lambda: _engine(_SMALL, page_size=8)),
    ("dense", lambda: _engine(replace(CFG, kv_cache_dtype="int8"),
                              page_size=8)),
    ("dense", lambda: _engine(_BF16, page_size=16, prefill_buckets=(8,))),
    ("dense", lambda: _engine(page_size=4, max_len=36, prefill_len=12)),
    ("dense", lambda: _engine(replace(CFG, max_seq_len=32),
                              cls=ShardedSlotEngine, tp=2, page_size=8)),
], ids=["f32", "bf16", "gqa-bucket8", "head32", "int8-kv",
        "bf16-bucket8", "rows-off-tile", "sharded"])
def test_prefill_path_is_fixed_by_what_the_engine_sees(want, make):
    """``prefill_path`` beside ``decode_path``: from the pool's leaves, the
    head size and the chunk widths, by no option; reported beside the
    counters and on the spans of an admission."""
    engine = make()
    assert engine.prefill_path == want
    assert engine.stats["prefill_path"] == want
    t0 = time.monotonic()
    engine.start(engine.acquire_slot(), [1, 2, 3], max_new_tokens=1)
    ((_, _, attrs),) = trace.closed("engine.start", t0)
    assert attrs["path"] == want


def _prefix_chunk_requests():
    """A long prompt (two whole chunks and a final chunk that starts at
    the odd offset ``p - w``), a second that adopts its first three pages
    and is chunked behind them, and short prompts padded to each bucket."""
    rng = np.random.default_rng(17)
    a = rng.integers(1, 64, 45).tolist()
    b = a[:24] + rng.integers(1, 64, 30).tolist()
    return [(a, {"max_new_tokens": 6}), (b, {"max_new_tokens": 5}),
            (a[:5], {"max_new_tokens": 4}), (b[:27], {"max_new_tokens": 4}),
            (rng.integers(1, 64, 11).tolist(), {"max_new_tokens": 7})]


@pytest.mark.parametrize("variant", ["learned-mha", "rope-gqa-window"])
def test_flash_prefill_serves_the_dense_prefills_tokens(variant):
    """Chunked prefill, prefix adoption and a padded final chunk through
    the kernel: greedy tokens identical to the same engine on the dense
    lines, no recompile in either (``_drive`` asserts it)."""
    from tests.test_serve_chunked import _drive as drive_chunked

    cfg = replace(CFG, max_seq_len=96)
    if variant == "rope-gqa-window":
        cfg = replace(cfg, position="rope", num_kv_heads=1,
                      attention_window=40)
    params = TransformerLM(cfg).init(
        jax.random.PRNGKey(1), jnp.zeros((1, 8), jnp.int32))["params"]
    kw = dict(slots=2, max_len=96, prefill_len=16, page_size=8,
              prefill_buckets=(8,), prefix_cache=True)
    flash, dense = SlotEngine(cfg, params, **kw), DenseEngine(cfg, params, **kw)
    assert (flash.prefill_path, dense.prefill_path) == ("flash", "dense")
    t0 = time.monotonic()
    got = drive_chunked(flash, _prefix_chunk_requests())
    chunks = [a for _, _, a in trace.closed("engine.prefill_chunk", t0)]
    assert chunks and {a["path"] for a in chunks} == {"flash"}
    # The final chunk of the 45-token prompt starts at 45 - 16.
    assert 29 in {a["offset"] for a in chunks}
    assert flash.stats["prefix_tokens_matched"] >= 24
    assert got == drive_chunked(dense, _prefix_chunk_requests())


def test_kv_rows_read_counts_live_pages_on_the_table_path(params):
    """``engine.round`` notes the positions a micro-step reads: the live
    pages of the active slots here, every slot's whole row on the gather
    path; and the count never costs a compile."""
    import time

    engine = SlotEngine(CFG, params, slots=3, max_len=48, prefill_len=24,
                        page_size=8)
    engine.warmup()
    base = engine.compile_count()
    for n in (9, 17):
        engine.start(engine.acquire_slot(), list(range(1, n + 1)),
                     max_new_tokens=4)
    t0, writes0 = time.monotonic(), engine.stats["kv_row_writes"]
    engine.step()
    ((_, _, attrs),) = trace.closed("engine.round", t0, float("inf"))
    assert attrs["active"] == 2 and attrs["live_tokens"] == 9 + 17
    assert attrs["kv_row_writes"] == 2
    assert engine.stats["kv_row_writes"] - writes0 == 2
    # Lengths 9 and 17 attend 10 and 18 positions: 2 and 3 pages of 8.
    assert attrs["kv_rows_read"] == (2 + 3) * 8
    assert engine.compile_count() == base


def test_kv_copies_counts_a_step_of_neighbours_as_one(params):
    """``engine.round`` notes the descriptors a layer its paged kernel's
    chain starts and the live pages they carry, ``engine.stats`` sums both:
    rows of 6 pages of 8 KiB make steps of 4 pages, a fresh pool hands out
    neighbours, so the slot of 4 live pages costs one copy and the slot of
    2 two. A program that gathers takes every page of every row, a copy
    each."""
    engine = SlotEngine(CFG, params, slots=3, max_len=48, prefill_len=32,
                        page_size=8, prefix_cache=False)
    for n in (9, 30):
        engine.start(engine.acquire_slot(), list(range(1, n + 1)),
                     max_new_tokens=4)
    t0 = time.monotonic()
    engine.step()
    ((_, _, attrs),) = trace.closed("engine.round", t0, float("inf"))
    assert (attrs["kv_copies"], attrs["kv_pages"]) == (2 + 1, 2 + 4)
    assert attrs["kv_pages"] * 8 == attrs["kv_rows_read"]
    assert (engine.stats["kv_copies"], engine.stats["kv_pages_copied"]) == (
        3, 6)
    gather = GatherEngine(CFG, params, slots=3, max_len=48, prefill_len=32,
                          page_size=8)
    gather.start(gather.acquire_slot(), list(range(1, 31)), max_new_tokens=4)
    t0 = time.monotonic()
    gather.step()
    ((_, _, attrs),) = trace.closed("engine.round", t0, float("inf"))
    assert (attrs["kv_copies"], attrs["kv_pages"]) == (3 * 6, 3 * 6)
    assert attrs["kv_pages"] * 8 == attrs["kv_rows_read"]


def _registers(engine, lengths):
    """Slots 0.. active at ``lengths``, set on the host registers alone:
    ``_kv_rows_read`` reads nothing else, so no program is compiled."""
    act = np.zeros(engine.slots, bool)
    act[: len(lengths)] = True
    engine.lengths[: len(lengths)] = lengths
    return act


@pytest.mark.parametrize("want,kw,cfg", [
    # Gather path: every slot's whole row, however little is live.
    (3 * 48, dict(cls=GatherEngine), CFG),
    # A verify round gathers on a table-path engine too ...
    (3 * 48, dict(spec_k=2), CFG),
    # ... and a window skips the pages wholly below it: length 29 attends
    # positions 20..29, pages 2 and 3; length 9 attends 0..9, pages 0 and 1.
    (4 * 8, dict(), replace(CFG, attention_window=10)),
], ids=["gather", "verify-round", "window"])
def test_kv_rows_read_from_the_registers(want, kw, cfg):
    engine = _engine(cfg, slots=3, max_len=48, prefill_len=24, page_size=8,
                     **kw)
    assert engine._kv_rows_read(_registers(engine, [29, 9])) == want
    assert engine._kv_rows_read(np.zeros(3, bool)) == 0


# -- the engine on the table path ------------------------------------------


def test_inactive_lane_writes_only_the_trash_page(params):
    """A round writes the active slot's row into its own page and nothing
    else: at these shapes the page-copy kernel takes the writes, and a
    masked lane issues none, the trash page included."""
    engine = SlotEngine(CFG, params, slots=3, max_len=48, prefill_len=24,
                        page_size=8, prefix_cache=False)
    engine.warmup()
    slot = engine.acquire_slot()
    engine.start(slot, list(range(1, 12)), max_new_tokens=8)  # length 11
    before = jax.device_get(engine.pool.layers)
    rounds0 = engine.stats["plain_rounds"]
    engine.step()
    after = jax.device_get(engine.pool.layers)
    # step() returns one round and has queued the next behind it.
    queued = engine.stats["plain_rounds"] - rounds0
    assert queued == 2 and engine.stats["rounds_ahead"] == 1
    wrote = int(engine.pool.page_tables[slot, 11 // 8])
    for b, a in zip(before, after):
        for leaf in ("k", "v"):
            changed = {int(p) for p in np.nonzero(
                (b[leaf] != a[leaf]).any(axis=(1, 2, 3)))[0]}
            assert changed == {wrote}
            # One new row a round, and nothing else of that page.
            rows = (b[leaf][wrote] != a[leaf][wrote]).any(axis=(0, 2))
            assert list(np.nonzero(rows)[0]) == [11 % 8, 12 % 8]


_LAYOUTS = {
    "paged": dict(prefix_cache=False),
    "paged+prefix": dict(prefix_cache=True),
    "paged+prefix+spec": dict(prefix_cache=True, spec_k=4),
    "paged+prefix+chunked": dict(prefix_cache=True, prefill_chunk_tokens=8),
    "paged+prefix+tree": dict(prefix_cache=True, spec_k=4, spec_branches=2),
}


@pytest.fixture(scope="module")
def gather_tokens(params):
    """The churn requests through the SAME engine made to gather. Greedy
    tokens do not depend on the layout (``test_paged_kv.py`` holds the
    gather path to that), so one baseline serves every layout below."""
    engine = GatherEngine(CFG, params, slots=4, max_len=48, prefill_len=26,
                          page_size=8, prefix_cache=False)
    assert engine.decode_path == "gather"
    return _drive(engine, _churn_requests())


@pytest.mark.parametrize("layout", list(_LAYOUTS))
def test_table_path_serves_the_gather_paths_tokens(params, gather_tokens,
                                                   layout):
    """The churn matrix of ``test_paged_kv.py``: greedy tokens identical
    between the two paths in every layout, zero recompiles in each
    (``_drive`` asserts the compile count after ``warmup()``)."""
    engine = SlotEngine(CFG, params, slots=4, max_len=48, prefill_len=26,
                        page_size=8, **_LAYOUTS[layout])
    assert engine.decode_path == "table"
    got = _drive(engine, _churn_requests())
    if engine.prefix is not None:
        engine.prefix.clear()
    assert engine.pool.pages_free == engine.pool.num_pages - 1
    assert got == gather_tokens


@pytest.mark.parametrize("cls", [SlotEngine, GatherEngine],
                         ids=["table", "gather"])
def test_a_plain_round_is_one_micro_step(params, cls):
    """One dispatch, one micro-step: a plain round returns exactly one row
    of tokens and every active slot advances by one position, on either
    decode path (a verify round and a final prefill chunk are what yield
    more rows)."""
    engine = cls(CFG, params, slots=3, max_len=48, prefill_len=24,
                 page_size=8)
    engine.warmup()
    for n in (5, 11):
        engine.start(engine.acquire_slot(), list(range(1, n + 1)),
                     max_new_tokens=6)
    lengths = engine.lengths.copy()
    for i in range(1, 4):
        toks, valid, done = engine.step()
        assert toks.shape == valid.shape == (1, 3)
        assert valid[0].tolist() == [True, True, False] and not done.any()
        assert (engine.lengths[:2] == lengths[:2] + i).all()
        assert (engine.made[:2] == 1 + i).all()


@pytest.mark.parametrize("variant", ["rope-gqa-window", "sampled"])
def test_table_path_matches_whole_row_attention(variant):
    """Per-slot rotation and a window inside the kernel against the gather
    path's attention over each slot's whole logical row; and the sampled
    twin of the program against its own (same seeds, same draws)."""
    cfg, extra = CFG, {}
    if variant == "rope-gqa-window":
        cfg = replace(CFG, position="rope", num_kv_heads=1,
                      attention_window=12)
    else:
        extra = {"temperature": 0.8, "top_k": 8, "seed": 5}
    p = TransformerLM(cfg).init(
        jax.random.PRNGKey(1), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    requests = [(prompt, {**kw, **extra})
                for prompt, kw in _churn_requests()[:6]]
    got = {}
    for cls, path in ((GatherEngine, "gather"), (SlotEngine, "table")):
        engine = cls(cfg, p, slots=3, max_len=48, prefill_len=26,
                     page_size=8)
        assert engine.decode_path == path
        got[path] = _drive(engine, requests)
    assert got["table"] == got["gather"]


# -- the benchmark's readers of the kernel and its chain --------------------


def _reader(name):
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                        "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@pytest.mark.parametrize("trace,share", [
    ({"op_time_s": {"paged_decode_attention.3": 0.5, "fusion.1": 9.0},
      "module_calls": {"jit_step_fn": 100}}, 100.0 * 0.8 / 0.5),
    ({"op_time_s": {"fusion.1": 9.0}, "module_calls": {"jit_step_fn": 100}},
     None),
    (None, None),
], ids=["kernel-timed", "no-such-kernel", "untraced"])
def test_the_kernels_share_in_starcoder2s_cells(trace, share):
    """``kernels.paged_decode_roofline``: the attended rows' bytes (30 KiB a
    row over the 30 layers) at the HBM rate over the custom calls' time;
    nothing where no such call was timed."""
    import json
    import os

    from benchmarks import counts

    with open(os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                           "configs", "starcoder2-3b.json")) as fh:
        cfg = json.load(fh)["transformer_config"]
    row = counts.kv_bytes_per_token(cfg)
    assert row == 30 * 1024
    # (start, end, active slots, their live positions): the fifth round
    # starts behind the traced second and is not counted.
    rounds = [(0.2 * i, 0.2 * i + 0.1, 16, 984) for i in range(4)]
    rounds.append((1.5, 1.6, 16, 50000))
    c = {"trace": trace, "t_open": 0.0, "trace_s": 1.0, "model_cfg": cfg,
         "counters": {"decode_rounds": rounds},
         # 100 rounds of 1,000 rows take 0.8 s at this rate.
         "peaks": {"hbm_bytes_per_s": row * 1000 * 100 / 0.8}}
    got = _reader("kernels.paged_decode_roofline")(c)
    assert got is None if share is None else abs(got - share) < 1e-9


@pytest.mark.parametrize("rounds,want", [
    ([{"kv_copies": 10, "kv_pages": 64}, {"kv_copies": 6, "kv_pages": 64},
      {"active": 0}], 8.0),
    ([{"active": 3, "kv_rows_read": 40}], None),  # a program with no count
    (None, None),  # a program with no rings
], ids=["counted", "no-count", "no-rings"])
def test_pages_per_copy_reads_the_rounds_counts(monkeypatch, rounds, want):
    from benchmarks import program_spans

    monkeypatch.setattr(program_spans, "rounds", lambda c: rounds)
    assert _reader("kv.decode_pages_per_copy")({}) == want


@pytest.mark.parametrize("trace,want", [
    ({"op_time_s": {"paged_row_write.2": 0.03, "paged_row_write.7": 0.01,
                    "fusion.1": 9.0},
      "module_calls": {"jit_step_fn": 150, "jit_step_fn_sampled": 50,
                       "jit_prefill_fn": 9}}, 1000.0 * 0.04 / 200),
    ({"op_time_s": {"fusion bf16[2097664,128]": 0.25},
      "module_calls": {"jit_step_fn": 200}}, None),  # the scatter's program
    (None, None),
], ids=["kernel-timed", "scatter", "untraced"])
def test_decode_write_ms_reads_the_kernels_time_a_round(trace, want):
    """``kv.decode_write_ms``: the ``paged_row_write`` custom calls' device
    time over the decode program's calls, in ms; nothing where no such call
    was timed."""
    got = _reader("kv.decode_write_ms")({"trace": trace})
    assert got is None if want is None else abs(got - want) < 1e-12
