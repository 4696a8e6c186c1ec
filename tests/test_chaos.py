"""Chaos-hardened serving plane (ISSUE 16): DTT_FAULT grammar units,
circuit-breaker FSM, deadline propagation router -> replica, hedging
first-winner/cancel, corrupt-handoff typed fallback, and a 2-replica
kill+hang e2e with zero silent drops — the injection layer and every
defense it exists to exercise."""

import json
import os
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
from struct import error as struct_error
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np
import pytest

from distributed_tensorflow_tpu.obs.registry import MetricsRegistry
from distributed_tensorflow_tpu.serve.fleet import (
    CircuitBreaker,
    FleetRouter,
    HandoffOutbox,
    ProbeResult,
    ReplicaRegistry,
    encode_bundle,
    make_router_server,
)
from distributed_tensorflow_tpu.serve.fleet.handoff import decode_bundle
from distributed_tensorflow_tpu.utils import faults
from distributed_tensorflow_tpu.utils.retry import Budget, deadline_retry_call

pytestmark = [pytest.mark.chaos, pytest.mark.fleet]

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_TOOLS = os.path.join(_REPO, "tools")


@pytest.fixture(autouse=True)
def _quiet_faults():
    """Every test starts and ends with NO armed faults (configure("")
    overrides any DTT_FAULT inherited from the environment)."""
    faults.configure("")
    yield
    faults.configure(None)


# -- shared stubs ----------------------------------------------------------


class ChaosStub:
    """A scripted /generate endpoint whose behavior (``mode``) can change
    mid-test: ok | 503 | hang (accept, never answer, close after hang_s)
    — plus optional pre-answer delay and request header/body capture."""

    def __init__(self, mode="ok", delay_s=0.0, hang_s=1.0):
        self.mode = mode
        self.delay_s = delay_s
        self.hang_s = hang_s
        self.hits = 0
        self.headers_seen = []
        self.bodies = []
        outer = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, fmt, *args):
                pass

            def do_POST(self):
                outer.hits += 1
                outer.headers_seen.append(dict(self.headers))
                n = int(self.headers.get("Content-Length", 0))
                outer.bodies.append(json.loads(self.rfile.read(n) or b"{}"))
                mode, delay = outer.mode, outer.delay_s
                if delay:
                    time.sleep(delay)
                if mode == "hang":
                    # Accepted-then-silent: the stuck-socket failure the
                    # router's read watchdog must turn into breaker
                    # evidence. Bounded hold; handler threads are daemons.
                    time.sleep(outer.hang_s)
                    self.close_connection = True
                    return
                if mode == "503":
                    data = json.dumps({"error": "shutting_down",
                                       "detail": "stub"}).encode()
                    self.send_response(503)
                else:
                    data = json.dumps({
                        "request_id": "stub", "tokens": [1, 2, 3],
                        "ttft_ms": 1.0, "latency_ms": 2.0,
                        "finish_reason": "length",
                    }).encode()
                    self.send_response(200)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(data)))
                self.end_headers()
                self.wfile.write(data)

        self.server = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
        self.thread = threading.Thread(
            target=self.server.serve_forever, daemon=True)
        self.thread.start()
        host, port = self.server.server_address
        self.url = f"http://{host}:{port}"

    def close(self):
        self.server.shutdown()
        self.server.server_close()
        self.thread.join(timeout=5)


def _make_fleet(named_urls, registry_kw=None, **router_kw):
    registry = ReplicaRegistry(
        registry=MetricsRegistry(),
        probe=lambda url: ProbeResult(ok=True, accepting=True, slots=2),
        up_after=1,
        **(registry_kw or {}),
    )
    for rid, url in named_urls.items():
        registry.add(url, replica_id=rid)
    registry.probe_once()
    return registry, FleetRouter(registry, **router_kw)


def _counter(registry, name, **labels):
    for fam in registry.collect():
        if fam.name != name:
            continue
        total = 0.0
        for values, inst in fam.children():
            if labels and values != tuple(
                    str(labels[n]) for n in fam.label_names):
                continue
            total += inst.count if fam.kind == "histogram" else inst.value
        return total
    return 0.0


def _post(base, payload, timeout=15):
    req = urllib.request.Request(
        base + "/generate", data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, dict(resp.headers), json.loads(resp.read())
    except urllib.error.HTTPError as err:
        return err.code, dict(err.headers), json.loads(err.read())


@pytest.fixture()
def serve_router():
    cleanup = []

    def build(named_urls, registry_kw=None, **router_kw):
        registry, router = _make_fleet(
            named_urls, registry_kw=registry_kw, **router_kw)
        server = make_router_server(router, port=0)
        thread = threading.Thread(target=server.serve_forever, daemon=True)
        thread.start()
        cleanup.append((server, thread))
        host, port = server.server_address
        return f"http://{host}:{port}", registry, router

    yield build
    for server, thread in cleanup:
        server.shutdown()
        server.server_close()
        thread.join(timeout=5)


# -- DTT_FAULT grammar -----------------------------------------------------


def test_grammar_parses_probability_after_and_ms():
    sites = faults.parse_spec(  # dttlint: disable=fault-registry -- grammar unit test: dummy site names exercise the parser, not injection
        "a:p=0.5,a:ms=100,b:after=2,b:after=5,c:3,d:ms=250")
    assert sites["a"].p == 0.5 and sites["a"].ms == 100.0
    assert sites["b"].afters == {2, 5}
    assert sites["c"].remaining == 3
    assert sites["d"].ms == 250.0 and sites["d"].remaining == 0


@pytest.mark.parametrize("bad", ["a:p=1.5", "a:p=-0.1", "a:ms=-1", "a:x=3"])
def test_grammar_rejects_malformed(bad):
    with pytest.raises(ValueError):
        faults.parse_spec(bad)


def test_after_fires_once_past_the_crossing():
    faults.configure("s:after=2")  # dttlint: disable=fault-registry -- registry unit test: dummy site fired via faults.fire directly below, no wired call site needed
    assert [faults.fire("s") for _ in range(5)] == [
        False, False, True, False, False]


def test_probability_arm_is_seeded_and_replayable(monkeypatch):
    monkeypatch.setenv(faults.SEED_ENV_VAR, "7")
    faults.configure("s:p=0.5")
    first = [faults.fire("s") for _ in range(32)]
    faults.configure("s:p=0.5")
    second = [faults.fire("s") for _ in range(32)]
    assert first == second        # same seed -> same storm
    assert any(first) and not all(first)  # actually probabilistic


def test_ms_only_site_delays_every_traversal_but_never_errors():
    faults.configure("s:ms=250")
    assert [faults.delay_s("s") for _ in range(3)] == [0.25, 0.25, 0.25]
    assert faults.fire("s") is False


def test_count_plus_ms_delays_only_when_the_arm_fires():
    faults.configure("s:1,s:ms=100")
    assert faults.delay_s("s") == 0.1
    assert faults.delay_s("s") == 0.0  # count consumed
    assert faults.site_ms("s", 5.0) == 100.0  # non-consuming duration read
    faults.configure("")
    assert faults.site_ms("s", 5.0) == 5.0


# -- circuit breaker FSM ---------------------------------------------------


def test_breaker_needs_min_samples_before_tripping():
    b = CircuitBreaker(window=8, fail_threshold=0.5, min_samples=4)
    for _ in range(3):
        b.record(False, now=0.0)
    assert b.state == "closed"
    b.record(False, now=0.0)
    assert b.state == "open" and b.open_total == 1


def test_breaker_open_halfopen_close_cycle():
    b = CircuitBreaker(window=4, fail_threshold=0.5, min_samples=2,
                       open_s=2.0, half_open_max=1)
    b.record(False, now=0.0)
    b.record(False, now=0.0)
    assert b.state == "open"
    assert not b.admissible(1.0)      # still cooling
    assert b.admissible(2.5)          # cooled: one trial may go
    b.on_pick(2.5)
    assert b.state == "half_open"
    assert not b.admissible(2.5)      # trial slot taken
    b.record(True, now=2.6)
    assert b.state == "closed"


def test_breaker_halfopen_failure_reopens():
    b = CircuitBreaker(min_samples=2, fail_threshold=0.5, open_s=1.0)
    b.record(False, now=0.0)
    b.record(False, now=0.0)
    b.on_pick(1.5)
    b.record(False, now=1.5)
    assert b.state == "open" and b.open_total == 2
    assert not b.admissible(2.0)      # cooldown restarted at the re-trip
    b.reset()
    assert b.state == "closed" and b.admissible(0.0)


def test_registry_breaker_fences_pick_then_reopens_via_trial():
    now = [0.0]
    registry = ReplicaRegistry(
        registry=MetricsRegistry(),
        probe=lambda url: ProbeResult(ok=True, accepting=True, slots=2),
        up_after=1, down_after=10,
        breaker_min_samples=2, breaker_open_s=1.0,
        clock=lambda: now[0],
    )
    a = registry.add("http://x", replica_id="a")
    registry.add("http://y", replica_id="b")
    registry.probe_once()
    registry.note_result(a, False)
    registry.note_result(a, False)
    assert a.breaker.state == "open"
    assert not registry.breakers_closed()
    assert _counter(registry.metrics_registry,
                    "fleet_breaker_open_total", replica="a") == 1
    assert registry.pick().replica_id == "b"  # hard filter, not preference
    now[0] = 1.5
    trial = registry.pick()                   # cooled: half-open trial
    assert trial.replica_id == "a" and a.breaker.state == "half_open"
    registry.note_result(a, True)
    assert a.breaker.state == "closed" and registry.breakers_closed()
    assert registry.snapshot()["replicas"]["a"]["breaker_open_total"] == 1


def test_probe_down_resets_breaker():
    """Health state takes over: a replica the probe FSM takes down
    restarts with a clean breaker when it returns."""
    flap = {"ok": True}
    registry = ReplicaRegistry(
        registry=MetricsRegistry(),
        probe=lambda url: ProbeResult(
            ok=flap["ok"], accepting=True, slots=2),
        up_after=1, down_after=1, breaker_min_samples=2,
    )
    a = registry.add("http://x", replica_id="a")
    registry.probe_once()
    registry.note_result(a, False)
    registry.note_result(a, False)
    assert a.breaker.state == "open"
    flap["ok"] = False
    registry.probe_once()
    assert a.state == "down" and a.breaker.state == "closed"


def test_probe_fault_sites_flap_and_slow():
    registry = ReplicaRegistry(
        registry=MetricsRegistry(),
        probe=lambda url: ProbeResult(ok=True, accepting=True, slots=2),
        up_after=1, down_after=1,
    )
    a = registry.add("http://x", replica_id="a")
    registry.probe_once()
    assert a.state == "up"
    faults.configure("probe_flap:1")
    registry.probe_once()
    assert a.state == "down"          # injected unreachable, not the stub
    registry.probe_once()
    assert a.state == "up"            # flap consumed, FSM recovers
    faults.configure("probe_slow:ms=120")
    t0 = time.monotonic()
    registry.probe_once()
    assert time.monotonic() - t0 >= 0.12


# -- router: injection sites + defenses ------------------------------------


def test_route_dispatch_fault_fails_over_with_trail(serve_router):
    a, b = ChaosStub(), ChaosStub()
    try:
        base, registry, _ = serve_router({"a": a.url, "b": b.url})
        faults.configure("route_dispatch:1")
        status, headers, body = _post(base, {"prompt": [1]})
        assert status == 200 and body["tokens"] == [1, 2, 3]
        assert headers["X-Attempts"] == "2"
        assert headers["X-Attempt-Trail"] == "a:connect_error,b:200"
        assert a.hits == 0            # the fault fired before any bytes
        assert registry.get("a").error_total == 1
    finally:
        a.close()
        b.close()


def test_expired_budget_answers_typed_deadline(serve_router):
    base, registry, _ = serve_router({})
    status, headers, body = _post(base, {"prompt": [1], "deadline_s": 0.0})
    assert (status, body["error"]) == (503, "deadline")
    assert "X-Attempt-Trail" in headers
    reg = registry.metrics_registry
    assert _counter(reg, "fleet_deadline_shed_total") == 1
    assert _counter(reg, "fleet_shed_total") == 1


def test_budget_header_propagates_to_the_replica(serve_router):
    stub = ChaosStub()
    try:
        base, _, _ = serve_router({"a": stub.url})
        status, _, _ = _post(base, {"prompt": [1], "deadline_s": 5.0})
        assert status == 200
        budget_ms = int(stub.headers_seen[0]["X-Budget-Ms"])
        assert 0 < budget_ms <= 5000
        # No deadline -> no budget header (unbounded requests stay so).
        _post(base, {"prompt": [1]})
        assert "X-Budget-Ms" not in stub.headers_seen[1]
    finally:
        stub.close()


def test_deadline_expiring_mid_dispatch_sheds_typed(serve_router):
    """The upstream read timeout is capped at the remaining budget, and
    once it trips with the budget gone the answer is the typed deadline
    503 — not an exhaustion relay, not a parked handler."""
    stub = ChaosStub(delay_s=1.0)
    try:
        base, registry, _ = serve_router(
            {"a": stub.url}, max_attempts=3)
        t0 = time.monotonic()
        status, headers, body = _post(
            base, {"prompt": [1], "deadline_s": 0.3})
        assert (status, body["error"]) == (503, "deadline")
        assert time.monotonic() - t0 < 0.9  # did not wait out the stub
        assert headers["X-Attempt-Trail"].startswith("a:")
        assert _counter(registry.metrics_registry,
                        "fleet_deadline_shed_total") == 1
    finally:
        stub.close()


def test_hang_watchdog_trips_breaker_then_halfopen_recovers(serve_router):
    """A replica that accepts and never answers (healthz would still be
    fine) is caught by the per-attempt read watchdog; repeated hangs trip
    its breaker (pick stops offering it), and once the fault clears the
    half-open trial re-closes the breaker."""
    hang, live = ChaosStub(mode="hang", hang_s=1.0), ChaosStub()
    try:
        base, registry, _ = serve_router(
            {"a-hang": hang.url, "b-live": live.url},
            registry_kw=dict(down_after=10, breaker_min_samples=2,
                             breaker_open_s=0.4),
            max_attempts=2, read_timeout_s=0.2)
        for _ in range(2):
            status, headers, _ = _post(base, {"prompt": [1]})
            assert status == 200 and headers["X-Replica"] == "b-live"
            assert headers["X-Attempts"] == "2"
        snap = registry.snapshot()["replicas"]["a-hang"]
        assert snap["breaker"] == "open"
        assert snap["state"] == "up"  # health never saw it: breaker did
        assert not registry.breakers_closed()
        # Fenced: the next request never touches the hung replica.
        status, headers, _ = _post(base, {"prompt": [1]})
        assert status == 200 and headers["X-Attempts"] == "1"
        assert hang.hits == 2
        # Fault clears; after open_s one half-open trial re-closes it.
        hang.mode = "ok"
        time.sleep(0.45)
        status, headers, _ = _post(base, {"prompt": [1]})
        assert status == 200 and headers["X-Replica"] == "a-hang"
        assert registry.breakers_closed()
    finally:
        hang.close()
        live.close()


def test_hedge_first_winner_cancels_loser(serve_router):
    slow, fast = ChaosStub(delay_s=0.8), ChaosStub()
    try:
        base, registry, _ = serve_router(
            {"a-slow": slow.url, "b-fast": fast.url},
            hedge_after_s=0.15)
        t0 = time.monotonic()
        status, headers, body = _post(base, {"prompt": [1]})
        assert status == 200 and body["tokens"] == [1, 2, 3]
        assert headers["X-Replica"] == "b-fast"
        assert time.monotonic() - t0 < 0.7  # did not wait for the primary
        assert "b-fast:200" in headers["X-Attempt-Trail"]
        reg = registry.metrics_registry
        assert _counter(reg, "fleet_hedge_total", outcome="launched") == 1
        assert _counter(reg, "fleet_hedge_total",
                        outcome="winner_hedge") == 1
        # A hedge is not a failover, and the cancelled loser feeds no
        # error streaks or breaker evidence.
        assert _counter(reg, "fleet_failover_total") == 0
        time.sleep(1.0)  # let the loser finish its (cancelled) attempt
        assert registry.get("a-slow").error_total == 0
        assert registry.get("a-slow").breaker.state == "closed"
    finally:
        slow.close()
        fast.close()


def test_hedge_delay_policy():
    registry, router = _make_fleet({})
    assert router._hedge_delay() is None  # default: hedging disabled
    _, adaptive = _make_fleet({}, hedge_after_s=0.0, hedge_min_s=0.05)
    assert adaptive._hedge_delay() is None  # cold window: never hedge
    for _ in range(8):
        adaptive._note_latency(0.4)
    assert adaptive._hedge_delay() == pytest.approx(0.4)
    _, fixed = _make_fleet({}, hedge_after_s=1.5)
    assert fixed._hedge_delay() == 1.5


def test_exhaustion_relay_keeps_attempt_trail(serve_router):
    """The bugfix: when the failover budget exhausts, the relayed answer
    still carries per-attempt attribution instead of dropping it."""
    a, b = ChaosStub(mode="503"), ChaosStub(mode="503")
    try:
        base, _, _ = serve_router({"a": a.url, "b": b.url}, max_attempts=2)
        status, headers, body = _post(base, {"prompt": [1]})
        assert (status, body["error"]) == (503, "shutting_down")
        assert headers["X-Attempt-Trail"] == "a:503,b:503"
        assert headers["X-Attempts"] == "2"
    finally:
        a.close()
        b.close()


def test_injected_5xx_and_stall_sites_answer_typed():
    """The server-side sites, exercised at the faults layer the server
    consumes them through: replica_5xx fires exactly N times, and
    replica_stall yields a bounded delay."""
    faults.configure("replica_5xx:2,replica_stall:ms=50")
    assert [faults.fire("replica_5xx") for _ in range(4)] == [
        True, True, False, False]
    assert faults.delay_s("replica_stall") == 0.05


# -- server-side deadline min ----------------------------------------------


def test_parse_request_mins_budget_into_deadline():
    from distributed_tensorflow_tpu.serve.server import _parse_request

    req = _parse_request({"prompt": [1, 2], "deadline_s": 5.0}, None,
                         budget_s=1.0)
    assert req.deadline_s == 1.0   # propagated budget tightens
    req = _parse_request({"prompt": [1, 2], "deadline_s": 0.5}, None,
                         budget_s=2.0)
    assert req.deadline_s == 0.5   # client's own deadline stays tighter
    req = _parse_request({"prompt": [1, 2]}, None, budget_s=3.0)
    assert req.deadline_s == 3.0   # budget alone is enough
    req = _parse_request({"prompt": [1, 2]}, None)
    assert req.deadline_s is None


# -- deadline-aware retry helper -------------------------------------------


def test_budget_none_is_unbounded():
    budget = Budget(None)
    assert budget.remaining() == float("inf") and not budget.expired()


def test_deadline_retry_call_stops_when_budget_cannot_fit_backoff():
    now = [0.0]
    calls = []

    def fn():
        calls.append(1)
        raise OSError("transient")

    budget = Budget(1.0, clock=lambda: now[0])
    with pytest.raises(OSError):
        deadline_retry_call(
            fn, budget=budget, attempts=5, base_delay=0.4, jitter=0.0,
            sleep=lambda s: now.__setitem__(0, now[0] + s),
            rng=__import__("random").Random(0))
    # attempt 1 (sleep 0.4) + attempt 2, then the 0.8s backoff no longer
    # fits the 0.6s remaining -> the REAL error re-raises, not a 5th try.
    assert len(calls) == 2


def test_deadline_retry_call_succeeds_within_budget():
    state = {"n": 0}

    def fn():
        state["n"] += 1
        if state["n"] < 2:
            raise OSError("transient")
        return "ok"

    assert deadline_retry_call(
        fn, budget=Budget(10.0), attempts=3, base_delay=0.01) == "ok"
    assert state["n"] == 2


# -- corrupt handoff: typed rejection both directions ----------------------


class HandoffPeerStub:
    """A decode-peer /handoff endpoint running the REAL wire codec: a
    corrupt bundle gets the typed 400 the real replica answers, a valid
    one streams accept + done."""

    def __init__(self):
        self.hits = 0
        self.rejections = 0
        outer = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, fmt, *args):
                pass

            def do_POST(self):
                outer.hits += 1
                n = int(self.headers.get("Content-Length", 0))
                body = self.rfile.read(n)
                try:
                    decode_bundle(body)
                except (ValueError, KeyError, struct_error):
                    outer.rejections += 1
                    data = json.dumps({"error": "invalid",
                                       "detail": "bad bundle"}).encode()
                    self.send_response(400)
                    self.send_header("Content-Type", "application/json")
                    self.send_header("Content-Length", str(len(data)))
                    self.end_headers()
                    self.wfile.write(data)
                    return
                self.send_response(200)
                self.send_header("Content-Type", "text/event-stream")
                self.end_headers()
                self.wfile.write(
                    b'event: token\ndata: {"tokens": [5]}\n\n'
                    b'event: done\ndata: {"tokens": [5], '
                    b'"finish_reason": "length"}\n\n')

        self.server = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
        threading.Thread(
            target=self.server.serve_forever, daemon=True).start()
        host, port = self.server.server_address
        self.url = f"http://{host}:{port}"

    def close(self):
        self.server.shutdown()
        self.server.server_close()


class _HandoffEvents:
    def __init__(self):
        self.accepted = []
        self.done = []
        self.failed = []
        self.terminal = threading.Event()

    def on_accepted(self, peer):
        self.accepted.append(peer)

    def on_tokens(self, tokens):
        pass

    def on_done(self, payload):
        self.done.append(payload)
        self.terminal.set()

    def on_failed(self, detail, accepted):
        self.failed.append((detail, accepted))
        self.terminal.set()


def _bundle_bytes():
    return encode_bundle({
        "length": 3, "cur_tok": 7, "made": 1,
        "pages": {"n_pages": 1, "page_size": 4, "layers": [
            {"k": np.zeros((1, 4), np.float32),
             "v": np.ones((1, 4), np.float32)},
        ]},
    }, request_id="chaos")


def test_corrupt_handoff_rejected_typed_then_retry_recovers():
    peer = HandoffPeerStub()
    outbox = HandoffOutbox([peer.url], max_attempts=3, backoff_s=0.01)
    try:
        faults.configure("handoff_corrupt:1")
        events = _HandoffEvents()
        outbox.submit(_bundle_bytes(), "req-1", events)
        assert events.terminal.wait(10.0)
        # Attempt 1 corrupt -> typed 400 at the peer (garbage pages never
        # imported); attempt 2 clean -> accepted + done. Nothing lost.
        assert peer.rejections == 1 and peer.hits == 2
        assert len(events.accepted) == 1 and len(events.done) == 1
        assert events.failed == []
    finally:
        outbox.stop()
        peer.close()


def test_corrupt_handoff_exhaustion_fails_typed_pre_accept():
    peer = HandoffPeerStub()
    outbox = HandoffOutbox([peer.url], max_attempts=2, backoff_s=0.01)
    try:
        faults.configure("handoff_corrupt:10")
        events = _HandoffEvents()
        outbox.submit(_bundle_bytes(), "req-2", events)
        assert events.terminal.wait(10.0)
        # Every push corrupted -> typed failure with accepted=False: the
        # exporter still owns the slot and decodes locally (fallback).
        assert events.accepted == [] and events.done == []
        assert len(events.failed) == 1
        detail, accepted = events.failed[0]
        assert accepted is False and "400" in detail
    finally:
        outbox.stop()
        peer.close()


def test_handoff_send_timeout_retries_then_lands():
    peer = HandoffPeerStub()
    outbox = HandoffOutbox([peer.url], max_attempts=3, backoff_s=0.01)
    try:
        faults.configure("handoff_send_timeout:1")
        events = _HandoffEvents()
        outbox.submit(_bundle_bytes(), "req-3", events)
        assert events.terminal.wait(10.0)
        assert len(events.done) == 1 and events.failed == []
        assert peer.hits == 1  # the injected timeout died before the wire
    finally:
        outbox.stop()
        peer.close()


# -- loadgen: typed outcome classes ----------------------------------------


class StreamCutStub:
    """SSE /generate that completes odd hits and cuts even hits after one
    token frame — the truncation loadgen must type as stream_aborted."""

    def __init__(self):
        self.hits = 0
        self.bodies = []
        outer = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, fmt, *args):
                pass

            def do_POST(self):
                outer.hits += 1
                n = int(self.headers.get("Content-Length", 0))
                outer.bodies.append(json.loads(self.rfile.read(n) or b"{}"))
                cut = outer.hits % 2 == 0
                self.send_response(200)
                self.send_header("Content-Type", "text/event-stream")
                self.end_headers()
                self.wfile.write(b'event: token\ndata: {"tokens": [1]}\n\n')
                self.wfile.flush()
                if cut:
                    self.close_connection = True
                    return
                self.wfile.write(
                    b'event: done\ndata: {"request_id": "s", '
                    b'"tokens": [1], "ttft_ms": 1.0, "latency_ms": 2.0, '
                    b'"finish_reason": "length"}\n\n')

        self.server = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
        threading.Thread(
            target=self.server.serve_forever, daemon=True).start()
        host, port = self.server.server_address
        self.url = f"http://{host}:{port}"

    def close(self):
        self.server.shutdown()
        self.server.server_close()


def test_loadgen_types_stream_cuts_and_carries_deadline_ms(tmp_path):
    stub = StreamCutStub()
    report_file = tmp_path / "report.jsonl"
    try:
        env = {**os.environ, "JAX_PLATFORMS": "cpu"}
        proc = subprocess.run(
            [sys.executable, os.path.join(_TOOLS, "loadgen.py"),
             "--targets", stub.url, "--num_requests", "4",
             "--concurrency", "1", "--stream", "--smoke",
             "--deadline_ms", "250", "--prompt_len", "4",
             "--max_new_tokens", "4", "--timeout_s", "30", "--seed", "0",
             "--report_file", str(report_file)],
            capture_output=True, text=True, timeout=120, env=env)
        # Truncated-after-tokens streams are a TYPED outcome, so --smoke
        # passes: visible and accounted is not dropped.
        assert proc.returncode == 0, proc.stderr[-1500:]
        report = json.loads(report_file.read_text().splitlines()[-1])
        assert report["outcomes"] == {
            "ok": 2, "deadline": 0, "failover_exhausted": 0,
            "capacity_shed": 0, "shed_unknown": 0,
            "stream_aborted": 2, "errored": 0}
        assert report["stream_aborted"] == 2
        assert sum(report["outcomes"].values()) == report["num_requests"]
        assert report["dropped_without_shed"] == 0
        # --deadline_ms rode every request as the deadline_s the router
        # would turn into an X-Budget-Ms hop budget.
        assert all(b.get("deadline_s") == 0.25 for b in stub.bodies)
    finally:
        stub.close()


# -- e2e: kill + hang against real replicas --------------------------------


def test_e2e_kill_and_hang_zero_silent_drops():
    """Two real serve_lm replicas — one chaos-armed with a hang via
    DTT_FAULT alone — behind the real router: the hang becomes a
    watchdog failover, the SIGKILL becomes connect-error failovers, and
    every request gets a typed answer while the fleet re-settles."""
    sys.path.insert(0, _TOOLS)
    from serve_fleet import launch_fleet

    shape = ["--demo", "--vocab_size", "256", "--d_model", "32",
             "--num_heads", "4", "--num_layers", "2", "--d_ff", "64",
             "--seq_len", "32", "--slots", "2"]
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)
    env.pop("DTT_FAULT", None)
    chaos_env = dict(env)
    chaos_env["DTT_FAULT"] = "replica_hang:1,replica_hang:ms=4000"

    replicas = []
    registry = server = None
    try:
        # Overlap the two jax boots: spawn both, then wait both.
        replicas += launch_fleet(1, shape, env=env)
        replicas += launch_fleet(1, shape, env=chaos_env)
        registry = ReplicaRegistry(
            registry=MetricsRegistry(), up_after=1, down_after=2,
            breaker_min_samples=2, breaker_open_s=0.5)
        registry.add(replicas[0].url, replica_id="b-clean")
        registry.add(replicas[1].url, replica_id="a-chaos")
        router = FleetRouter(registry, max_attempts=3, read_timeout_s=1.0)
        server = make_router_server(router, port=0)
        threading.Thread(target=server.serve_forever, daemon=True).start()
        registry.start(interval_s=0.2)
        deadline = time.monotonic() + 30
        while registry.up_count() < 2 and time.monotonic() < deadline:
            time.sleep(0.1)
        assert registry.up_count() == 2
        host, port = server.server_address
        base = f"http://{host}:{port}"

        outcomes = []
        for i in range(4):
            status, headers, body = _post(
                base, {"prompt": [3, 4, 5], "max_new_tokens": 4,
                       "deadline_s": 30.0}, timeout=30)
            outcomes.append((status, body.get("error")))
            assert status == 200, (status, body)  # hang -> failover -> ok
        # The armed hang really fired somewhere in the wave: the chaos
        # replica took at least one watchdog failure.
        assert registry.get("a-chaos").error_total >= 1

        replicas[1].proc.kill()  # now the hard failure: no FIN, no drain
        for i in range(4):
            status, headers, body = _post(
                base, {"prompt": [3, 4, 5], "max_new_tokens": 4,
                       "deadline_s": 30.0}, timeout=30)
            outcomes.append((status, body.get("error")))
            assert status == 200, (status, body)
        # Every request in the soak got a typed answer — zero silent
        # drops — and once probes declare the corpse down its breaker is
        # reset: the fleet ends settled.
        assert all(s == 200 for s, _ in outcomes)
        deadline = time.monotonic() + 10
        while ((registry.up_count() != 1 or not registry.breakers_closed())
               and time.monotonic() < deadline):
            time.sleep(0.1)
        assert registry.up_count() == 1
        assert registry.breakers_closed()
    finally:
        if server is not None:
            server.shutdown()
            server.server_close()
        if registry is not None:
            registry.stop()
        for replica in replicas:
            replica.terminate(grace_s=2.0)


@pytest.mark.slow
def test_bench_fleet_chaos_smoke_meets_gates():
    """ISSUE 16's bench phase end-to-end on the smoke shape: the scripted
    storm terminates with every request typed, breakers re-closed,
    survivors recompile-free, and the storm p99 under its inflation
    ceiling — all hard-asserted inside bench_fleet_chaos, so a clean
    return IS the pass. Excluded from the whole-suite smoke run
    (3 subprocess jax boots + 3 loadgen waves), like the elastic bench."""
    env = {**os.environ, "BENCH_SMOKE": "1", "JAX_PLATFORMS": "cpu",
           "JAX_ENABLE_COMPILATION_CACHE": "false"}
    env.pop("XLA_FLAGS", None)
    env.pop("DTT_FAULT", None)
    out = subprocess.run(
        [sys.executable, "-c",
         "import json, bench; "
         "print(json.dumps(bench.bench_fleet_chaos()))"],
        cwd=_REPO, capture_output=True, text=True, timeout=560, env=env,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    recs = {r["metric"]: r for r in json.loads(out.stdout.splitlines()[-1])}
    import bench
    for gate in ("fleet_chaos_zero_drops", "fleet_chaos_breakers_closed",
                 "fleet_chaos_zero_recompiles"):
        assert recs[gate]["value"] >= bench.FLOORS[gate], recs[gate]
    inflation = recs["fleet_chaos_p99_inflation"]
    assert inflation["frac"] <= bench.FRAC_CEILS[inflation["metric"]]
    assert inflation["value"] > 0
