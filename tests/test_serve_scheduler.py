"""Scheduler tests: FCFS order, iteration-level refill, and — the load-shed
contract — every submitted request terminates with a TYPED outcome
(Completion, or Rejection{queue_full, deadline, invalid, shutting_down}),
never a hang."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_tpu.models.transformer import (
    TransformerConfig,
    TransformerLM,
)
from distributed_tensorflow_tpu.serve import (
    Completion,
    Rejection,
    Request,
    Scheduler,
    ServingMetrics,
    SlotEngine,
)

pytestmark = pytest.mark.serve

CFG = TransformerConfig(
    vocab_size=64,
    d_model=32,
    num_heads=4,
    num_layers=2,
    d_ff=64,
    max_seq_len=32,
    compute_dtype=jnp.float32,
)


@pytest.fixture(scope="module")
def params():
    model = TransformerLM(CFG)
    return model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))[
        "params"
    ]


@pytest.fixture(scope="module")
def layout_params():
    cache = {}

    def get(cfg):
        if id(cfg) not in cache:
            cache[id(cfg)] = TransformerLM(cfg).init(
                jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
        return cache[id(cfg)]

    return get


def _engine(params, slots=2):
    return SlotEngine(CFG, params, slots=slots, max_len=32, prefill_len=12)


def test_fcfs_completion_and_accounting(params):
    """All submitted requests complete in run_until_idle; with one slot
    the service order is strictly submission order (TTFTs increase)."""
    metrics = ServingMetrics()
    sched = Scheduler(_engine(params, slots=1), max_queue_depth=8,
                      metrics=metrics)
    handles = [
        sched.submit(Request(prompt=(i + 1, 2, 3), max_new_tokens=3,
                             request_id=f"r{i}"))
        for i in range(4)
    ]
    assert sched.run_until_idle(max_steps=200) == 4
    outcomes = [h.result(timeout=1) for h in handles]
    assert all(isinstance(o, Completion) for o in outcomes)
    assert [o.request_id for o in outcomes] == [f"r{i}" for i in range(4)]
    assert all(len(o.tokens) == 3 for o in outcomes)
    ttfts = [o.ttft_s for o in outcomes]
    assert ttfts == sorted(ttfts)  # one slot => strictly FCFS service
    snap = metrics.snapshot()
    assert snap["completed"] == 4 and snap["shed"] == 0
    assert snap["tokens_out"] >= 4 * 2  # decode tokens (first comes from prefill)
    assert snap["ttft_ms"]["count"] == 4


def test_iteration_level_refill(params):
    """A short request finishing frees its slot for the queue WHILE a long
    request keeps decoding — continuous batching, not run-to-completion
    batches: with 2 slots and a 12-token straggler, 5 two-token requests
    all finish before the straggler."""
    sched = Scheduler(_engine(params, slots=2), max_queue_depth=16)
    long_h = sched.submit(Request(prompt=(1, 2), max_new_tokens=12))
    short_hs = [
        sched.submit(Request(prompt=(3 + i,), max_new_tokens=2))
        for i in range(5)
    ]
    order = []
    steps = 0
    while not (long_h.done() and all(h.done() for h in short_hs)):
        sched.step()
        steps += 1
        assert steps < 100
        for h in short_hs + [long_h]:
            if h.done() and h not in order:
                order.append(h)
    assert order.index(long_h) == len(order) - 1  # straggler finished last
    assert all(isinstance(h.result(0), Completion) for h in short_hs)


def test_queue_full_is_typed_and_immediate(params):
    sched = Scheduler(_engine(params), max_queue_depth=2)
    keep = [sched.submit(Request(prompt=(1,), max_new_tokens=2))
            for _ in range(2)]
    over = sched.submit(Request(prompt=(1,), max_new_tokens=2))
    assert over.done()  # rejected synchronously at submit, no waiting
    out = over.result(timeout=0)
    assert isinstance(out, Rejection) and out.reason == "queue_full"
    sched.run_until_idle(max_steps=100)
    assert all(isinstance(h.result(0), Completion) for h in keep)


def test_deadline_shed_is_typed(params):
    """A request whose deadline lapses while QUEUED is shed with reason
    'deadline'; one admitted in time runs to completion even if the clock
    later passes its deadline (deadlines bound queue wait, not decode)."""
    t = [0.0]
    sched = Scheduler(_engine(params, slots=1), max_queue_depth=8,
                      clock=lambda: t[0])
    admitted = sched.submit(Request(prompt=(1,), max_new_tokens=6,
                                    deadline_s=1.0))
    queued = sched.submit(Request(prompt=(2,), max_new_tokens=2,
                                  deadline_s=1.0))
    sched.step()  # admits `admitted` into the single slot at t=0
    t[0] = 5.0  # both deadlines lapse; only the queued one sheds
    while not (admitted.done() and queued.done()):
        sched.step()
    out = queued.result(0)
    assert isinstance(out, Rejection) and out.reason == "deadline"
    assert "5.000s" in out.detail and "1.0" in out.detail
    assert isinstance(admitted.result(0), Completion)


def test_invalid_requests_are_typed(params):
    sched = Scheduler(_engine(params), max_queue_depth=8)
    cases = [
        Request(prompt=(), max_new_tokens=2),
        Request(prompt=tuple(range(32)), max_new_tokens=2),  # > prompt cap
        Request(prompt=(1,), max_new_tokens=0),
        Request(prompt=(1,), max_new_tokens=64),  # > max_len
        Request(prompt=(1,), max_new_tokens=2, deadline_s=-1.0),
    ]
    for r in cases:
        h = sched.submit(r)
        assert h.done()
        out = h.result(0)
        assert isinstance(out, Rejection) and out.reason == "invalid", r


def test_stop_sheds_leftovers_typed(params):
    """stop() must leave NO hanging waiters: queued and in-flight requests
    get a 'shutting_down' rejection, later submits are refused."""
    sched = Scheduler(_engine(params, slots=1), max_queue_depth=8)
    running = sched.submit(Request(prompt=(1,), max_new_tokens=10))
    queued = sched.submit(Request(prompt=(2,), max_new_tokens=2))
    sched.step()  # `running` occupies the slot; `queued` still waiting
    sched.stop()
    for h in (running, queued):
        out = h.result(timeout=1)
        assert isinstance(out, Rejection) and out.reason == "shutting_down"
    late = sched.submit(Request(prompt=(3,), max_new_tokens=2))
    assert late.result(0).reason == "shutting_down"


def test_background_loop_drives_to_completion(params):
    """start()/stop(): submits complete without the caller ever touching
    step() — the serve_lm wiring."""
    sched = Scheduler(_engine(params), max_queue_depth=16)
    sched.start(poll_s=0.001)
    try:
        handles = [
            sched.submit(Request(prompt=(i + 1,), max_new_tokens=3))
            for i in range(6)
        ]
        outs = [h.result(timeout=30) for h in handles]
        assert all(isinstance(o, Completion) for o in outs)
    finally:
        sched.stop()


def test_result_timeout_raises_not_hangs(params):
    sched = Scheduler(_engine(params), max_queue_depth=8)
    h = sched.submit(Request(prompt=(1,), max_new_tokens=2))
    with pytest.raises(TimeoutError):
        h.result(timeout=0.01)  # nothing is driving the scheduler
    sched.run_until_idle(max_steps=50)
    assert isinstance(h.result(0), Completion)


# -- run-ahead of depth one, through the scheduler ---------------------------


@pytest.mark.parametrize("sampling", ["greedy", "sampled"])
@pytest.mark.parametrize("layout", ["gather", "int8-kv", "table"])
def test_scheduler_over_run_ahead_serves_the_oracles_tokens(
        layout_params, layout, sampling):
    """Seven requests queue for three slots: every completion is followed
    by an admission while a round is in flight, one request stops at an eos
    and the others at their budgets, and two prompts are chunked in
    beside the decoding slots. Each request's tokens are those it is served
    alone on an engine that never runs ahead."""
    from tests.test_serve_engine import (
        _AHEAD_LAYOUTS,
        _SAMPLING,
        _ahead_requests,
    )

    cfg, kw, path = _AHEAD_LAYOUTS[layout]
    params = layout_params(cfg)
    requests, want = _ahead_requests(cfg, params, kw, _SAMPLING[sampling])
    engine = SlotEngine(cfg, params, slots=3, max_len=48, prefill_len=12, **kw)
    assert engine.decode_path == path
    compiled = engine.warmup()
    metrics = ServingMetrics()
    sched = Scheduler(engine, max_queue_depth=16, metrics=metrics)
    handles = []
    for prompt, kwargs in requests:
        kwargs = dict(kwargs)
        handles.append(sched.submit(Request(
            prompt=tuple(prompt), max_new_tokens=kwargs.pop("max_new_tokens"),
            **kwargs)))
    assert sched.run_until_idle(max_steps=400) == len(requests)
    outs = [h.result(timeout=1) for h in handles]
    assert [list(o.tokens) for o in outs] == want
    assert [o.finish_reason for o in outs] == [
        "eos" if i == 2 else "length" for i in range(len(outs))]
    assert 0 < engine.stats["rounds_ahead"] < engine.stats["plain_rounds"]
    assert engine.compile_count() == compiled
    assert metrics.snapshot()["completed"] == len(requests)
