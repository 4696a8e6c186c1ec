"""Elastic fleet supervision (ISSUE 13): FleetSupervisor policy units —
sustained-watermark scale-up/down, SLO-breach override, cooldown and
flap hysteresis, min/max bounds, drain-then-stop scale-down, dead-
replica replacement — with fake spawns and a fake clock (no threads, no
sockets); the registry's died-mid-probe accounting against a REAL HTTP
server; and the supervised-replacement e2e: SIGKILL a live subprocess
replica and watch the supervisor put a working replacement in its
place."""

import json
import os
import subprocess
import sys
import threading
import time
import types
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

from distributed_tensorflow_tpu.obs.export import (
    parse_prometheus_text,
    prometheus_text,
)
from distributed_tensorflow_tpu.obs.registry import MetricsRegistry
from distributed_tensorflow_tpu.serve.fleet import (
    FleetSupervisor,
    ProbeResult,
    ReplicaRegistry,
)
from distributed_tensorflow_tpu.serve.fleet.registry import http_probe

pytestmark = [pytest.mark.serve, pytest.mark.fleet, pytest.mark.elastic]

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_TOOLS = os.path.join(_REPO, "tools")


# -- policy units (fake spawn, fake clock) ---------------------------------


class _Handle:
    def __init__(self, url):
        self.url = url
        self._alive = True
        self.terminations = []  # grace_s per terminate() call

    def alive(self):
        return self._alive

    def terminate(self, grace_s=0.0):
        self.terminations.append(grace_s)
        self._alive = False

    def kill(self):  # simulate an unsupervised death
        self._alive = False


class _Spawner:
    def __init__(self):
        self.count = 0
        self.handles = []
        self.roles = []
        self.fail = False

    def __call__(self, role):
        if self.fail:
            raise RuntimeError("boot failed")
        self.count += 1
        handle = _Handle(f"http://r{self.count}:1")
        self.handles.append(handle)
        self.roles.append(role)
        return handle


def _make(clock, pressure, **kw):
    registry = ReplicaRegistry(
        [], probe=lambda url: ProbeResult(ok=True, accepting=True, slots=2),
        registry=MetricsRegistry(), up_after=1, clock=lambda: clock[0])
    registry.fleet_pressure = lambda: pressure[0]
    spawner = _Spawner()
    sup = FleetSupervisor(
        registry, spawner, clock=lambda: clock[0],
        min_replicas=1, max_replicas=3, high_watermark=0.85,
        low_watermark=0.25, scale_up_sustain_s=1.0,
        scale_down_sustain_s=4.0, cooldown_s=2.0, drain_grace_s=7.5, **kw)
    return sup, spawner, registry


def _events(registry):
    return {
        (s["labels"]["direction"], s["labels"]["reason"]): s["value"]
        for s in parse_prometheus_text(
            prometheus_text(registry.metrics_registry))
        if s["name"] == "fleet_scale_events_total"
    }


def _gauge(registry, name):
    for s in parse_prometheus_text(
            prometheus_text(registry.metrics_registry)):
        if s["name"] == name:
            return s["value"]
    return None


def test_scale_up_needs_sustained_pressure_not_a_blip():
    clock, pressure = [100.0], [0.9]
    sup, spawner, registry = _make(clock, pressure)
    sup._spawn_one("mixed")
    assert sup.tick() is None  # crossing just started
    clock[0] += 0.5
    pressure[0] = 0.1  # blip down: the sustain window resets
    assert sup.tick() is None
    pressure[0] = 0.9
    assert sup.tick() is None
    clock[0] += 0.9
    assert sup.tick() is None  # 0.9s < 1.0s sustain
    clock[0] += 0.2
    assert sup.tick() == "up"
    assert sup.member_count() == 2
    assert _events(registry)[("up", "pressure_high")] == 1.0
    assert _gauge(registry, "fleet_target_replicas") == 2.0


def test_cooldown_gates_back_to_back_decisions_and_max_bounds():
    clock, pressure = [0.0], [0.95]
    sup, spawner, registry = _make(clock, pressure)
    sup._spawn_one("mixed")
    sup.tick()  # starts the sustain window
    clock[0] += 1.5
    assert sup.tick() == "up"  # cooldown runs until t=3.5
    # Pressure stays high, sustain re-elapses — but the cooldown holds.
    clock[0] += 1.5
    assert sup.tick() is None
    clock[0] += 1.0  # t=4.0: past cooldown, 1.0s re-sustained
    assert sup.tick() == "up"
    assert sup.member_count() == 3
    # At max_replicas: sustained pressure no longer scales.
    clock[0] += 5.0
    sup.tick()
    clock[0] += 1.5
    assert sup.tick() is None
    assert sup.member_count() == 3
    assert _events(registry)[("up", "pressure_high")] == 2.0


def test_slo_breach_forces_scale_up_without_sustain():
    clock, pressure = [0.0], [0.1]  # pressure looks tame
    sup, spawner, registry = _make(clock, pressure)
    sup._spawn_one("mixed")
    sup.notice_slo(True)
    assert sup.tick() == "up"
    assert _events(registry)[("up", "slo_breach")] == 1.0
    # The breach flag is consumed by the decision, not sticky.
    clock[0] += 10.0
    assert sup.tick() != "up"


def test_attach_slo_only_reacts_to_named_fleet_rules():
    clock, pressure = [0.0], [0.1]
    sup, _, _ = _make(clock, pressure)
    callbacks = []
    monitor = types.SimpleNamespace(add_callback=callbacks.append)
    sup.attach_slo(monitor)
    (cb,) = callbacks
    cb(types.SimpleNamespace(name="train_loss"), "breach", 9.0)
    assert sup._slo_breach is False
    cb(types.SimpleNamespace(name="fleet_ttft_p99"), "breach", 2.0)
    assert sup._slo_breach is True
    cb(types.SimpleNamespace(name="fleet_ttft_p99"), "ok", 0.1)
    assert sup._slo_breach is False


def test_scale_down_drains_least_loaded_and_respects_min():
    clock, pressure = [0.0], [0.9]
    sup, spawner, registry = _make(clock, pressure)
    for _ in range(3):
        sup._spawn_one("mixed")
    # Make r2 the busy one; r1/r3 idle — victim must not be r2.
    registry.get("r2:1").inflight = 5
    pressure[0] = 0.1
    sup.tick()
    clock[0] += 4.5
    assert sup.tick() == "down"
    # The drain runs on a worker thread; wait for it to finish.
    deadline = time.monotonic() + 5.0
    while sup.member_count() > 2 and time.monotonic() < deadline:
        time.sleep(0.01)
    assert sup.member_count() == 2
    drained = [h for h in spawner.handles if h.terminations]
    assert len(drained) == 1
    assert drained[0].url != "http://r2:1", "drained the BUSY replica"
    # Drained with the grace window — never a bare SIGKILL.
    assert drained[0].terminations == [7.5]
    assert registry.get(drained[0].url.split("//")[1]) is None
    assert _events(registry)[("down", "pressure_low")] == 1.0
    # At min_replicas=1... scale down to 1 then stop.
    clock[0] += 10.0
    sup.tick()
    clock[0] += 4.5
    assert sup.tick() == "down"
    deadline = time.monotonic() + 5.0
    while sup.member_count() > 1 and time.monotonic() < deadline:
        time.sleep(0.01)
    clock[0] += 10.0
    sup.tick()
    clock[0] += 4.5
    assert sup.tick() is None, "scaled below min_replicas"
    assert sup.member_count() == 1


def test_dead_replica_is_replaced_with_same_role():
    clock, pressure = [0.0], [0.5]
    sup, spawner, registry = _make(clock, pressure)
    changes = []
    sup.on_change = lambda members: changes.append(
        sorted(m.handle.url for m in members))
    sup._spawn_one("prefill")
    sup._spawn_one("decode")
    spawner.handles[0].kill()
    assert sup.tick() == "replace"
    assert sup.member_count() == 2
    assert spawner.roles == ["prefill", "decode", "prefill"]
    assert registry.get("r1:1") is None
    assert registry.get("r3:1") is not None
    assert _events(registry)[("replace", "replica_died")] == 1.0
    # Membership observers saw both the removal and the replacement.
    assert any("http://r3:1" in urls for urls in changes)


def test_spawn_failure_is_retried_next_tick_not_fatal():
    clock, pressure = [0.0], [0.5]
    sup, spawner, registry = _make(clock, pressure)
    sup._spawn_one("mixed")
    spawner.handles[0].kill()
    spawner.fail = True
    assert sup.tick() is None  # replacement boot failed; no crash
    assert sup.member_count() == 0
    spawner.fail = False
    assert sup.tick() == "replace"
    assert sup.member_count() == 1


@pytest.mark.fault
def test_injected_spawn_fault_is_absorbed_like_a_real_boot_failure():
    """The ``spawn_fail`` chaos site (DTT_FAULT) takes the same non-fatal
    path as a spawner that raises: no member, no crash, next attempt
    clean once the arm exhausts."""
    from distributed_tensorflow_tpu.utils import faults

    clock, pressure = [0.0], [0.5]
    sup, spawner, registry = _make(clock, pressure)
    faults.configure("spawn_fail:1")
    try:
        assert sup._spawn_one("mixed") is None
        assert sup.member_count() == 0
        assert spawner.count == 0  # the fault fired before the real spawn
        assert sup._spawn_one("mixed") is not None
        assert sup.member_count() == 1
    finally:
        faults.reset()


def test_supervisor_bounds_are_validated():
    registry = ReplicaRegistry([], registry=MetricsRegistry())
    with pytest.raises(ValueError, match="min_replicas"):
        FleetSupervisor(registry, lambda role: None, min_replicas=3,
                        max_replicas=2)
    with pytest.raises(ValueError, match="watermark"):
        FleetSupervisor(registry, lambda role: None, low_watermark=0.9,
                        high_watermark=0.5)


# -- registry: replica dying between /healthz and /metrics -----------------


class _MidDeathHandler(BaseHTTPRequestHandler):
    def do_GET(self):
        if self.path == "/healthz":
            body = json.dumps({
                "accepting": True, "draining": False, "slots": 2,
                "free_slots": 2, "queue_depth": 0, "role": "mixed",
            }).encode()
            self.send_response(200)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)
        elif self.server.metrics_mode == "ok":  # noqa: SLF001
            body = b"# empty\n"
            self.send_response(200)
            self.send_header("Content-Type", "text/plain")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)
        else:
            # Die mid-probe: healthz answered, /metrics cuts the socket.
            self.connection.close()

    def log_message(self, *args):
        pass


def test_probe_counts_mid_probe_death_once_per_cycle():
    """A replica that dies between the /healthz poll and the /metrics
    scrape of ONE probe cycle must cost exactly one fail-streak advance —
    ok=False from the probe itself, not a bogus ok=True that lets the
    dispatch path double-count the corpse."""
    server = ThreadingHTTPServer(("127.0.0.1", 0), _MidDeathHandler)
    server.metrics_mode = "ok"
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    url = f"http://127.0.0.1:{server.server_address[1]}"
    try:
        registry = ReplicaRegistry(
            [url], probe=http_probe, registry=MetricsRegistry(),
            up_after=1, down_after=2)
        registry.probe_once()
        replica = registry.replicas[0]
        assert replica.state == "up"
        server.metrics_mode = "die"
        result = http_probe(url)
        assert result.ok is False
        assert "died mid-probe" in result.detail
        # One poisoned cycle: hysteresis holds (fail_streak advanced ONCE,
        # down_after=2 not yet reached) — the old double-count took the
        # replica down here.
        registry.probe_once()
        assert replica.state == "up"
        registry.probe_once()
        assert replica.state == "down"
    finally:
        server.shutdown()
        server.server_close()
        thread.join(timeout=5)


# -- supervised replacement e2e (subprocess replicas) ----------------------

_REPLICA_ARGV = [
    "--demo", "--vocab_size", "64", "--d_model", "32", "--num_heads", "4",
    "--num_layers", "2", "--d_ff", "64", "--seq_len", "32",
    "--slots", "2", "--prefill_len", "12", "--serve_max_len", "32",
    "--drain_deadline_s", "10",
]


def _fleet_env():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)  # replicas don't need 8 virtual devices
    env["JAX_PLATFORMS"] = "cpu"
    return env


def test_supervisor_replaces_sigkilled_replica_e2e():
    """ISSUE 13 acceptance (supervision half): SIGKILL a supervised
    subprocess replica; the supervisor spawns a working same-role
    replacement on a fresh URL, the registry converges on it, and the
    replacement serves traffic."""
    sys.path.insert(0, _TOOLS)
    from serve_fleet import ReplicaProc

    def spawn(role):
        proc = subprocess.Popen(
            [sys.executable, os.path.join(_TOOLS, "serve_lm.py"),
             "--port", "0", *_REPLICA_ARGV],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=_fleet_env())
        replica = ReplicaProc(proc)
        replica.wait_url(120.0)
        replica.role = role
        return replica

    registry = ReplicaRegistry([], up_after=1, down_after=2)
    sup = FleetSupervisor(
        registry, spawn, min_replicas=1, max_replicas=2,
        scale_up_sustain_s=30.0, scale_down_sustain_s=600.0,
        cooldown_s=0.1, drain_grace_s=10.0)
    try:
        sup.start(1, interval_s=0.2)
        registry.start(interval_s=0.1)
        assert sup.member_count() == 1
        victim = sup.members[0]
        deadline = time.monotonic() + 20
        while registry.up_count() < 1 and time.monotonic() < deadline:
            time.sleep(0.1)
        assert registry.up_count() == 1
        victim.handle.proc.kill()  # SIGKILL: crash, not drain
        deadline = time.monotonic() + 30
        replacement = None
        while time.monotonic() < deadline:
            members = [m for m in sup.members if not m.draining]
            if members and members[0].replica_id != victim.replica_id:
                replacement = members[0]
                break
            time.sleep(0.1)
        assert replacement is not None, "no replacement appeared"
        assert replacement.handle.url != victim.handle.url
        assert _events(registry).get(("replace", "replica_died")) == 1.0
        # The replacement actually serves.
        req = urllib.request.Request(
            replacement.handle.url + "/generate",
            data=json.dumps({"prompt": [1, 2, 3],
                             "max_new_tokens": 4}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=30) as resp:
            body = json.loads(resp.read())
        assert resp.status == 200 and len(body["tokens"]) == 4
        deadline = time.monotonic() + 20
        while registry.up_count() < 1 and time.monotonic() < deadline:
            time.sleep(0.1)
        assert registry.up_count() == 1
    finally:
        registry.stop()
        sup.stop(drain=False)


@pytest.mark.slow
def test_bench_fleet_elastic_smoke_meets_gates():
    """ISSUE 13's bench phase end-to-end on the smoke shape: the diurnal
    run terminates with zero drops while the supervisor scales 1 -> 2
    within budget, the routed p99 TTFT lands under its FRAC ceiling, and
    the prefill->decode handoff parity gate holds with accepted (never
    fallback) handoffs — all hard-asserted inside bench_fleet_elastic,
    so a clean return IS the pass. Excluded from the whole-suite smoke
    run (5 subprocess jax boots), like the quant bench."""
    env = {**os.environ, "BENCH_SMOKE": "1", "JAX_PLATFORMS": "cpu",
           "JAX_ENABLE_COMPILATION_CACHE": "false"}
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c",
         "import json, bench; "
         "print(json.dumps(bench.bench_fleet_elastic()))"],
        cwd=_REPO, capture_output=True, text=True, timeout=560, env=env,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    recs = {r["metric"]: r for r in json.loads(out.stdout.splitlines()[-1])}
    import bench
    for gate in ("fleet_elastic_zero_drops", "fleet_elastic_scaleup",
                 "fleet_handoff_token_parity"):
        assert recs[gate]["value"] >= bench.FLOORS[gate], recs[gate]
    ttft = recs["fleet_elastic_ttft_p99_ms"]
    assert ttft["frac"] <= bench.FRAC_CEILS[ttft["metric"]], ttft
    assert ttft["value"] > 0
