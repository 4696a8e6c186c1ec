"""The serving round, timed from inside: which spans a scheduler round
closes and how they nest, the queue-wait and between-rounds instruments on
a fake clock, the named scopes of the engine's programs (and that they
change no token), and what ``sync_engine`` no longer does every round."""

import dataclasses
import os
import re
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_tpu import obs
from distributed_tensorflow_tpu.config import ServeConfig
from distributed_tensorflow_tpu.models.transformer import (
    TransformerConfig,
    TransformerLM,
)
from distributed_tensorflow_tpu.obs import trace
from distributed_tensorflow_tpu.obs.export import prometheus_text
from distributed_tensorflow_tpu.serve import (
    Request,
    Scheduler,
    ServingMetrics,
    SlotEngine,
)
from distributed_tensorflow_tpu.serve.deploy import WeightSwapper

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
import serve_lm  # noqa: E402

pytestmark = pytest.mark.serve

CFG = TransformerConfig(
    vocab_size=64, d_model=32, num_heads=4, num_kv_heads=2, num_layers=2,
    d_ff=64, max_seq_len=48, position="rope", compute_dtype=jnp.float32,
)
PROMPTS = (
    (60, 40, 43, 57, 37),
    (49, 53, 14, 3, 19, 18, 55, 58, 0, 31, 52, 8, 51, 7, 29, 52, 19, 21, 17,
     46),  # longer than prefill_len: chunked
)
# Greedy tokens of PROMPTS on the tree BEFORE the named scopes (parent
# commit 08f58d2, this CFG, PRNGKey(0), CPU f32).
GOLDEN = ((47, 4, 4, 29, 29, 4, 29, 4), (17, 19, 5, 4, 4, 4, 4, 4))
SCOPES = ("kv.gather", "attn", "mlp", "lm_head", "sample", "kv.scatter")
ROUND_SPANS = (
    "sched.step", "sched.admit", "sched.queue_wait", "engine.start",
    "sched.metrics_sync", "engine.round", "engine.prefill_chunk",
    "engine.dispatch", "engine.wait", "engine.readback", "sched.deliver",
    "sched.complete",
)


@pytest.fixture(scope="module")
def params():
    return TransformerLM(CFG).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]


def _engine(params, cfg=CFG, **kw):
    return SlotEngine(cfg, params, slots=2, max_len=48, prefill_len=8, **kw)


def _since(t_lo):
    """{span name: records} of everything closed since ``t_lo``."""
    out = {n: trace.closed(n, t_lo) for n in trace.names()}
    return {n: rs for n, rs in out.items() if rs}


def _inside(inner, outer):
    return outer[0] <= inner[0] and inner[1] <= outer[1]


def test_one_round_closes_each_span_once_nested_at_most_twelve(params):
    """Admitting one (chunked) request beside one decoding slot closes every
    span of the table, nested as the table says, and nothing else: the
    call that admits it reads the round in flight and queues nothing (the
    chunk plan waits one call: no chunk runs ahead of the host), the next
    spends the first chunk and is the synchronous round it always was:
    chunk, dispatch, wait, readback. Twelve records at the most a call."""
    engine = _engine(params)
    engine.warmup()
    sched = Scheduler(engine, metrics=ServingMetrics())
    sched.submit(Request(prompt=PROMPTS[0], max_new_tokens=8))
    sched.step()  # slot 0 decodes from here on, a round queued ahead
    sched.submit(Request(prompt=PROMPTS[1], max_new_tokens=8))
    t_lo = time.monotonic()
    sched.step()
    admit = _since(t_lo)
    t_mid = time.monotonic()
    sched.step()
    chunk = _since(t_mid)
    quiet = {"sched.step", "sched.admit", "sched.metrics_sync",
             "engine.round", "engine.wait", "engine.readback",
             "sched.deliver", "sched.complete"}
    assert set(admit) == quiet | {"sched.queue_wait", "engine.start"}
    assert set(chunk) == quiet | {"engine.prefill_chunk", "engine.dispatch"}
    assert set(admit) | set(chunk) == set(ROUND_SPANS)
    for got in (admit, chunk):
        assert all(len(v) == 1 for v in got.values()), {
            k: len(v) for k, v in got.items()}
        assert sum(len(v) for v in got.values()) <= 12
    a = {k: v[0] for k, v in admit.items()}
    c = {k: v[0] for k, v in chunk.items()}
    for r, pairs in ((a, (("engine.start", "sched.admit"),)),
                     (c, (("engine.prefill_chunk", "engine.round"),
                          ("engine.dispatch", "engine.round")))):
        for child, parent in pairs + (
            ("sched.admit", "sched.step"), ("sched.metrics_sync", "sched.step"),
            ("engine.round", "sched.step"), ("engine.wait", "engine.round"),
            ("engine.readback", "engine.round"),
            ("sched.deliver", "sched.step"), ("sched.complete", "sched.step"),
        ):
            assert _inside(r[child], r[parent]), (child, parent)
    for r, in_order in (
        (a, ["sched.admit", "sched.metrics_sync", "engine.wait",
             "engine.readback", "sched.deliver", "sched.complete"]),
        (c, ["sched.admit", "sched.metrics_sync", "engine.prefill_chunk",
             "engine.dispatch", "engine.wait", "engine.readback",
             "sched.deliver", "sched.complete"]),
    ):
        for x, y in zip(in_order, in_order[1:]):
            assert r[x][1] <= r[y][0], (x, y)
    assert a["sched.queue_wait"][1] <= a["engine.start"][0]
    # What each records.
    assert a["sched.step"][2] == c["sched.step"][2] == {"completed": 0}
    assert a["sched.admit"][2] == {"admitted": 1}
    assert c["sched.admit"][2] == {"admitted": 0}
    assert a["sched.queue_wait"][2] == {"lane": 1, "prompt_len": 20}
    # dh 8 is no head size the chunk's kernel takes either: dense prefill.
    assert a["engine.start"][2] == {"prompt_len": 20, "matched": 0,
                                    "chunks": 3, "path": "dense"}
    assert c["engine.prefill_chunk"][2] == {"offset": 0, "width": 8,
                                            "final": False, "path": "dense"}
    # dh 8 is no head size the table path takes: the gather path reads
    # every slot's whole row, 2 slots of 48. The round the admitting call
    # read was queued by the call before it, ahead of its reading: it ran
    # with slot 0 alone, one token on from its prompt of 5; the chunk
    # call's round went out from the host behind the chunk. A gather takes
    # every page by its own index: as many copies as pages. Each round wrote
    # the one active lane's rows.
    pages = 2 * 48 // engine.page_size
    assert a["engine.round"][2] == {"active": 1, "live_tokens": 6,
                                    "chunks_run": 0, "kv_rows_read": 2 * 48,
                                    "kv_copies": pages, "kv_pages": pages,
                                    "kv_row_writes": 1, "ahead": True}
    assert c["engine.round"][2] == {"active": 1, "live_tokens": 7,
                                    "chunks_run": 1, "kv_rows_read": 2 * 48,
                                    "kv_copies": pages, "kv_pages": pages,
                                    "kv_row_writes": 1, "ahead": False}
    assert a["sched.deliver"][2] == c["sched.deliver"][2] == {"produced": 1}
    assert c["engine.dispatch"][2] is None
    assert engine._flight is None and engine.prefilling.any()


def _round_parts(t_lo):
    """[(attrs of engine.round, its dispatch, wait and readback records)]
    of every round closed since ``t_lo``, oldest first."""
    parts = {n: sorted(trace.closed("engine." + n, t_lo))
             for n in ("dispatch", "wait", "readback")}
    out = []
    for t0, t1, attrs in sorted(trace.closed("engine.round", t_lo)):
        inside = {n: [r for r in rs if t0 <= r[0] and r[1] <= t1]
                  for n, rs in parts.items()}
        out.append((attrs, inside))
    return out


@pytest.mark.parametrize("kv", [None, "int8"], ids=["native-kv", "int8-kv"])
def test_quiet_rounds_are_queued_before_the_round_before_is_read(params, kv):
    """N rounds with nothing for the host to say: the first goes out from
    the host's registers, every other one is queued (engine.dispatch)
    before the round before it is waited for and read, so ``rounds_ahead``
    is N - 1 and each ``engine.round`` says which kind its round was. The
    last call queues nothing: the budget ends in the round it reads. An
    int8 pool's scale leaves are queued ahead with its rows."""
    engine = _engine(params, dataclasses.replace(CFG, kv_cache_dtype=kv))
    engine.warmup()
    ahead0 = engine.stats["rounds_ahead"]
    slot = engine.acquire_slot()
    t_lo = time.monotonic()
    engine.start(slot, PROMPTS[0], max_new_tokens=7)
    n = 0
    while engine.active[slot]:
        toks, _, _ = engine.step()
        assert toks.shape[0] == 1  # one micro-step a dispatch
        n += 1
    assert n == 6 and engine.stats["rounds_ahead"] - ahead0 == n - 1
    rounds = _round_parts(t_lo)
    assert [a["ahead"] for a, _ in rounds] == [False] + [True] * (n - 1)
    assert [a["live_tokens"] for a, _ in rounds] == [
        5 + i for i in range(n)]
    # First call: this round from the host, the next ahead, then the read.
    # Middle calls: one dispatch, before the wait. Last call: the read alone.
    assert [len(p["dispatch"]) for _, p in rounds] == [2] + [1] * (n - 2) + [0]
    for _, p in rounds:
        assert len(p["wait"]) == len(p["readback"]) == 1
        assert all(d[1] <= p["wait"][0][0] for d in p["dispatch"])
        assert p["wait"][0][1] <= p["readback"][0][0]
    assert engine._flight is None
    engine.release(slot)


def test_a_verify_round_is_never_queued_early(params):
    """Drafts are made on the host from the tokens of the round before: an
    engine that speculates reads every round before it queues the next."""
    engine = _engine(params, spec_k=2)
    engine.warmup()
    slot = engine.acquire_slot()
    t_lo = time.monotonic()
    first, _ = engine.start(slot, PROMPTS[0], max_new_tokens=8)
    toks = [first]
    while engine.active[slot]:
        t, v, _ = engine.step()
        toks += [int(x) for x in t[v[:, slot], slot]]
        assert engine._flight is None
    assert tuple(toks) == GOLDEN[0]
    assert engine.stats["spec_rounds"] > 0
    assert engine.stats["rounds_ahead"] == 0
    rounds = _round_parts(t_lo)
    assert rounds and not any(a["ahead"] for a, _ in rounds)
    for _, p in rounds:  # dispatch, wait, readback: once each, in that order
        assert [len(p[n]) for n in ("dispatch", "wait", "readback")] == [1] * 3
        assert p["dispatch"][0][1] <= p["wait"][0][0]
    engine.release(slot)


def test_queue_wait_and_between_rounds_on_a_fake_clock(params):
    """``sched.queue_wait`` and ``serve_queue_wait_seconds`` are one
    reading of the scheduler's clock; ``serve_between_rounds_seconds``
    observes a gap only while slots stayed in flight."""
    ticks = [1000.0]

    def clock():
        ticks[0] += 0.5
        return ticks[0]

    metrics = ServingMetrics()
    sched = Scheduler(_engine(params), metrics=metrics, clock=clock)
    lo = ticks[0]
    for i, prompt in enumerate(PROMPTS + (PROMPTS[0],)):  # 3 on 2 slots
        sched.submit(Request(prompt=prompt, max_new_tokens=4, priority=i))
    t_real = time.monotonic()
    sched.run_until_idle(max_steps=100)
    waits = [r for r in trace.closed("sched.queue_wait", lo, ticks[0])]
    assert [r[2]["lane"] for r in waits] == [0, 1, 2]
    assert [r[2]["prompt_len"] for r in waits] == [5, 20, 5]
    np.testing.assert_allclose(
        sorted(metrics.queue_wait.values()),
        sorted(r[1] - r[0] for r in waits))
    assert waits[2][1] - waits[2][0] > waits[0][1] - waits[0][0]
    rounds = [r for r in trace.closed("engine.round", t_real)]
    gaps = metrics.between_rounds.values()
    assert len(gaps) == len(rounds) - 1 and (gaps > 0).all()
    # After an idle stretch the first round observes nothing.
    sched.submit(Request(prompt=PROMPTS[0], max_new_tokens=3))
    t_real = time.monotonic()
    sched.run_until_idle(max_steps=100)
    more = len(trace.closed("engine.round", t_real))
    assert more >= 2
    assert len(metrics.between_rounds.values()) == len(gaps) + more - 1
    text = prometheus_text(metrics.registry)
    assert "serve_queue_wait_seconds_count 4" in text
    assert "serve_between_rounds_seconds_bucket" in text


def _lowered_scopes(jitted, *args):
    text = jitted.lower(*args).as_text(debug_info=True)
    names = set(re.findall(r'loc\("([^"]*)"', text))
    return {s for s in SCOPES
            if any(re.search(r"(^|[/(])%s([/)]|$)" % re.escape(s), n)
                   for n in names)}


@pytest.mark.parametrize("sampled", [False, True])
def test_programs_carry_every_scope_name(params, sampled):
    e = _engine(params)
    step = e._step_sampled if sampled else e._step_greedy
    prefill = e._prefill_sampled if sampled else e._prefill_greedy
    step_args = (e.pool.layers, e.params, e.pool.page_tables, e.active,
                 e.lengths, e.cur_tok, e.temp, e.top_k, e.top_p, e.seed,
                 e.made, e.budget, e.eos)
    assert _lowered_scopes(step, *step_args) == set(SCOPES)
    prefill_args = (e.pool.layers, e.params, np.zeros((1, 8), np.int32),
                    np.int32(5), np.int32(0),
                    np.array(e.pool.page_tables[0]), np.float32(0.0),
                    np.int32(0), np.float32(0.0), np.uint32(0))
    assert _lowered_scopes(prefill, *prefill_args) == set(SCOPES)


def test_training_path_carries_no_scope(params):
    """The scopes are on the cached branch alone: the plain forward (what
    a trainer compiles) lowers to the text it lowered to before."""
    fwd = jax.jit(lambda p, t: TransformerLM(CFG).apply({"params": p}, t))
    text = fwd.lower(params, jnp.zeros((1, 8), jnp.int32)).as_text(
        debug_info=True)
    names = set(re.findall(r'loc\("([^"]*)"', text))
    assert not [n for n in names for s in ("attn", "mlp")
                if re.search(r"(^|/)%s(/|$)" % s, n)]


def test_greedy_tokens_are_what_they_were_before_the_scopes(params):
    engine = _engine(params)
    engine.warmup()
    sched = Scheduler(engine)
    handles = [sched.submit(Request(prompt=p, max_new_tokens=8))
               for p in PROMPTS]
    sched.run_until_idle(max_steps=200)
    assert tuple(h.result(timeout=5).tokens for h in handles) == GOLDEN


def test_warmup_is_one_span_with_a_child_per_program(params):
    t_lo = time.monotonic()
    engine = _engine(params)
    n = engine.warmup()
    (warm,) = trace.closed("engine.warmup", t_lo)
    assert warm[2] == {"programs": n}
    kids = trace.closed("engine.warmup_program", t_lo)
    assert [k[2]["program"] for k in kids] == [
        "step.greedy", "step.sampled", "chunked.greedy", "chunked.sampled"]
    assert all(_inside(k, warm) for k in kids)
    # The step passes compile their prefill and step programs; the chunked
    # prompts reuse them (the zero-recompile contract).
    assert [k[2]["compiled"] for k in kids] == [True, True, False, False]
    assert sum(k[1] - k[0] for k in kids) <= warm[1] - warm[0]


@pytest.fixture()
def weight_walks(monkeypatch):
    """Counts reads of ``SlotEngine.weight_bytes_per_device`` (a walk over
    every parameter leaf)."""
    walks = []
    inner = SlotEngine.weight_bytes_per_device.fget
    monkeypatch.setattr(
        SlotEngine, "weight_bytes_per_device",
        property(lambda self: walks.append(1) or inner(self)))
    return walks


FIXED = {  # what the parent's sync_engine set every round, for this stack
    "serve_mesh_tp": 1.0, "serve_hbm_bytes_per_device": 28672.0,
    "serve_hbm_bytes_per_slot": 14336.0, "serve_kv_bytes_per_token": 256.0,
    'serve_kv_dtype{dtype="bf16"}': 1.0, "serve_prefill_tokens_budget": 8.0,
    "serve_weight_bytes_per_device": 76800.0,
    'serve_spec_accept_rate_by_drafter{drafter="ngram"}': 0.0,
    'serve_spec_accept_rate_by_drafter{drafter="model"}': 0.0,
}
FAMILIES = {  # every family /metrics showed at the parent commit
    "fleet_handoff_bytes_total", "fleet_handoff_chunk_ms",
    "fleet_handoff_throughput_bytes_per_s", "recompile_events_total",
    "serve_completed_total", "serve_handoff_stall_events_total",
    "serve_handoff_stall_max_seconds", "serve_handoff_stall_seconds_total",
    "serve_handoff_total", "serve_hbm_bytes_per_device",
    "serve_hbm_bytes_per_slot", "serve_kv_bytes_per_token", "serve_kv_dtype",
    "serve_kv_page_occupancy_current", "serve_kv_pages_free_current",
    "serve_lane_depth_current", "serve_mesh_tp", "serve_per_token_seconds",
    "serve_prefill_chunks_total", "serve_prefill_tokens_budget",
    "serve_prefill_tokens_last_iter", "serve_prefix_hit_rate",
    "serve_prefix_tokens_matched_total", "serve_prefix_tokens_total",
    "serve_queue_depth", "serve_queue_depth_current",
    "serve_queue_depth_peak", "serve_shed_total", "serve_slot_occupancy",
    "serve_slot_occupancy_current", "serve_spec_accept_per_verify",
    "serve_spec_accept_rate", "serve_spec_accept_rate_by_drafter",
    "serve_spec_accepted_per_verify_p50",
    "serve_spec_accepted_per_verify_p99", "serve_spec_drafts_accepted_total",
    "serve_spec_drafts_proposed_total", "serve_swap_total",
    "serve_tokens_out_total", "serve_ttft_seconds",
    "serve_variant_requests_total", "serve_weight_bytes_per_device",
    "serve_weight_version", "xla_compile_events_total",
}


def _samples(text):
    return {k: float(v) for k, v in (
        l.rsplit(" ", 1) for l in text.splitlines() if not l.startswith("#"))}


def test_build_stack_refuses_page_size_zero_before_any_pool(
        params, monkeypatch):
    """``--page_size 0`` reaches the engine as it was given and is refused
    there, with the sizes it takes, before a pool is allocated."""
    from distributed_tensorflow_tpu.serve import kv_pool

    def no_pool(*a, **kw):
        raise AssertionError("a pool was built")

    monkeypatch.setattr(kv_pool.PagedKVPool, "__init__", no_pool)
    serve_cfg = ServeConfig(slots=2, serve_max_len=48, prefill_len=8, port=0,
                            slo="off", page_size=0)
    with pytest.raises(ValueError, match="positive divisor of max_len 48"):
        serve_lm.build_stack(serve_cfg, CFG, params)


def test_build_stack_binds_once_and_rounds_walk_no_params(
        params, weight_walks):
    """After ``build_stack`` the gauges the build fixes are on /metrics
    before any traffic, with the values the parent showed; rounds never
    read ``weight_bytes_per_device``; a hot swap reads it again."""
    t_lo = time.monotonic()
    serve_cfg = ServeConfig(slots=2, serve_max_len=48, prefill_len=8, port=0,
                            slo="off")
    engine, sched, metrics, server = serve_lm.build_stack(
        serve_cfg, CFG, params)
    try:
        assert len(weight_walks) == 1
        before = _samples(prometheus_text(metrics.registry))
        assert {k: before[k] for k in FIXED} == FIXED
        for p in PROMPTS:
            sched.submit(Request(prompt=p, max_new_tokens=4))
        sched.run_until_idle(max_steps=100)
        assert len(weight_walks) == 1
        text = prometheus_text(metrics.registry)
        families = {l.split()[2] for l in text.splitlines()
                    if l.startswith("# TYPE")}
        assert FAMILIES <= families
        assert families - FAMILIES == {"serve_queue_wait_seconds",
                                       "serve_between_rounds_seconds"}
        after = _samples(text)
        assert {k: after[k] for k in FIXED} == FIXED
        assert after["serve_prefill_chunks_total"] == float(
            engine.stats["prefill_chunks"]) >= 3.0
        assert after["serve_kv_pages_free_current"] == 4.0
        snap = metrics.snapshot()
        assert (snap["weight_dtype"], snap["kv_dtype"]) == ("native", "bf16")
        # adopt_weights (through the swapper, at a boundary) refreshes them.
        other = TransformerLM(CFG).init(
            jax.random.PRNGKey(1), jnp.zeros((1, 8), jnp.int32))["params"]
        swapper = WeightSwapper(engine, sched, metrics=metrics,
                                probe_prompts=[PROMPTS[0]])
        swapper.submit(7, other)
        sched.submit(Request(prompt=PROMPTS[0], max_new_tokens=2))
        sched.run_until_idle(max_steps=100)
        assert swapper.last.outcome == "ok" and engine.weight_version == 7
        assert len(weight_walks) == 2
        # The build's spans: serve.build around the constructor (weights
        # placed inside it) and the warm-up.
        (build,) = trace.closed("serve.build", t_lo)
        (ctor,) = trace.closed("serve.build_engine", t_lo)
        (place,) = trace.closed("engine.place_weights", t_lo)
        (warm,) = trace.closed("engine.warmup", t_lo)
        assert _inside(place, ctor) and _inside(ctor, build)
        assert _inside(warm, build) and ctor[1] <= warm[0]
        # What the build fixed of the decode program rides the span.
        assert ctor[2]["decode_path"] == engine.decode_path
        assert ctor[2]["decode_kernel_form"] == engine.decode_kernel_form
    finally:
        server.server_close()


def test_hand_built_scheduler_binds_its_engine_on_the_first_round(
        params, weight_walks):
    metrics = ServingMetrics()
    sched = Scheduler(_engine(params), metrics=metrics)
    sched.submit(Request(prompt=PROMPTS[0], max_new_tokens=4))
    sched.run_until_idle(max_steps=100)
    assert len(weight_walks) == 1
    got = _samples(prometheus_text(metrics.registry))
    assert got["serve_weight_bytes_per_device"] == 76800.0
    assert got["serve_mesh_tp"] == 1.0


def test_per_token_help_says_what_it_observes():
    text = prometheus_text(ServingMetrics().registry)
    (line,) = [l for l in text.splitlines()
               if l.startswith("# HELP serve_per_token_seconds")]
    assert "Inter-token gap:" not in line and "over all slots" in line
