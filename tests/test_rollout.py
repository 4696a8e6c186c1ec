"""Fleet-coordinated rollout tests: the chief for serving weights.

RolloutController walk units over real in-process replicas (clean walk
commits fleet-wide one replica at a time; a NaN-poisoned step halts at
the first replica-local canary rollback and rolls the fleet back; a
dead push is a typed halt; an uncommitted prior is reported, not
papered over), the SLO-gated canary-percent ramp (widen on sustained-ok,
narrow-to-first-rung on any breach edge — real SloMonitor wiring and
the ``rollout_slo_flap`` chaos site), the ``POST /admin/deploy``
control surface, cross-structure sibling-engine variants behind ONE
scheduler with exact ``(variant, weight_version)`` attribution, the
drafter's ``--publish_dir`` committed-step publish, and the 3-replica
subprocess e2e: a clean walk converges under load with zero silent
drops and zero recompiles, then a ``DTT_FAULT=deploy_nan``-poisoned
step halts fleet-wide and every replica is restored.
"""

import itertools
import json
import os
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import pytest

from distributed_tensorflow_tpu.models.transformer import (
    TransformerConfig,
    TransformerLM,
)
from distributed_tensorflow_tpu.obs.slo import SloMonitor, SloRule
from distributed_tensorflow_tpu.serve import (
    Request,
    Scheduler,
    ServingMetrics,
    SlotEngine,
)
from distributed_tensorflow_tpu.serve import metric_names as mn
from distributed_tensorflow_tpu.serve.deploy import (
    VariantTable,
    variant_lane,
)
from distributed_tensorflow_tpu.serve.fleet import (
    CanaryRamp,
    ReplicaRegistry,
    RolloutController,
    RolloutResult,
)
from distributed_tensorflow_tpu.serve.scheduler import Completion, Rejection
from distributed_tensorflow_tpu.train.checkpoint import (
    list_committed_steps,
    read_step,
    write_committed_step,
)
from distributed_tensorflow_tpu.utils import faults

pytestmark = [pytest.mark.rollout, pytest.mark.serve, pytest.mark.fleet]

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_TOOLS = os.path.join(_REPO, "tools")

CFG = TransformerConfig(
    vocab_size=64,
    d_model=32,
    num_heads=4,
    num_layers=2,
    d_ff=64,
    max_seq_len=32,
    compute_dtype=jnp.float32,
)

# A genuinely DIFFERENT treedef (one block, not two) — the retrained-head
# scenario the buffer flip hard-rejects and the sibling engine serves.
SIB_CFG = TransformerConfig(
    vocab_size=64,
    d_model=32,
    num_heads=4,
    num_layers=1,
    d_ff=64,
    max_seq_len=32,
    compute_dtype=jnp.float32,
)

# Committed-step numbers stay monotonic across tests sharing the module
# fleet: every test establishes its own baseline walk in its own dir.
_STEP = itertools.count(1)


@pytest.fixture(scope="module")
def params_pair():
    model = TransformerLM(CFG)
    zeros = jnp.zeros((1, 8), jnp.int32)
    return (
        model.init(jax.random.PRNGKey(0), zeros)["params"],
        model.init(jax.random.PRNGKey(1), zeros)["params"],
    )


@pytest.fixture(scope="module")
def serve_lm():
    import importlib.util

    for p in (_REPO, _TOOLS):
        if p not in sys.path:
            sys.path.insert(0, p)
    spec = importlib.util.spec_from_file_location(
        "serve_lm", os.path.join(_TOOLS, "serve_lm.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class _Replica:
    """One full in-process serving stack (engine + scheduler + swapper +
    HTTP server) — the real thing the controller pushes to, minus the
    subprocess boundary."""

    def __init__(self, serve_lm, params):
        from distributed_tensorflow_tpu.config import (
            DeployConfig,
            ServeConfig,
        )

        serve_cfg = ServeConfig(port=0, slots=2, serve_max_len=32,
                                prefill_len=12, max_queue_depth=32)
        # canary_percent > 0 builds the VariantTable, so both admin
        # planes (step push + canary percent) exist on every replica.
        deploy_cfg = DeployConfig(canary_rows=2, canary_len=12,
                                  canary_probes=1, canary_percent=1.0)
        self.engine, self.sched, self.metrics, self.server = (
            serve_lm.build_stack(serve_cfg, CFG, params,
                                 deploy_cfg=deploy_cfg))
        self.thread = threading.Thread(target=self.server.serve_forever,
                                       daemon=True)
        self.thread.start()
        self.sched.start(poll_s=0.001)
        host, port = self.server.server_address
        self.base = f"http://{host}:{port}"

    def close(self):
        self.server.shutdown()
        self.server.server_close()
        self.thread.join(timeout=5)
        self.sched.stop()


@pytest.fixture(scope="module")
def fleet(serve_lm, params_pair):
    reps = [_Replica(serve_lm, params_pair[0]) for _ in range(3)]
    yield reps
    for rep in reps:
        rep.close()


def _registry_for(reps):
    reg = ReplicaRegistry(up_after=1, down_after=2, probe_timeout_s=10.0)
    for i, rep in enumerate(reps):
        reg.add(rep.base, replica_id=f"r{i:02d}")
    reg.probe_once()
    assert reg.up_count() == len(reps)
    return reg


def _controller(reg, d):
    # start_after=0: deliver steps already committed before construction
    # (each test publishes, then builds its controller).
    return RolloutController(reg, d, settle_timeout_s=120.0,
                             settle_poll_s=0.01, push_timeout_s=30.0,
                             start_after=0)


def _baseline(fleet, reg, d, params):
    """Publish + walk a baseline step so every replica sits on a version
    that IS a committed step of ``d`` (replicas boot on version 0, which
    no rollback can restore by re-push)."""
    step = next(_STEP)
    write_committed_step(d, step, {"params": params})
    ctrl = _controller(reg, d)
    assert ctrl.poll_once() == step
    assert ctrl.last.outcome == "committed"
    reg.probe_once()  # refresh weight_version -> the next walk's priors
    return step, ctrl


def _post(url, payload, timeout=30):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, dict(resp.headers), json.loads(resp.read())
    except urllib.error.HTTPError as err:
        return err.code, dict(err.headers), json.loads(err.read())


def _healthz(base, timeout=10):
    try:
        with urllib.request.urlopen(base + "/healthz",
                                    timeout=timeout) as resp:
            return json.loads(resp.read())
    except urllib.error.HTTPError as err:
        return json.loads(err.read())


# ---------------------------------------------------------------------------
# RolloutResult + controller walk
# ---------------------------------------------------------------------------


def test_rollout_result_typed_shape():
    res = RolloutResult(7, "rolled_back", updated=("a", "b"),
                        rolled_back=("a", "b"), halted_at="c",
                        detail="canary rollback: nan")
    d = res.to_dict()
    assert d == {"step": 7, "outcome": "rolled_back",
                 "updated": ["a", "b"], "rolled_back": ["a", "b"],
                 "halted_at": "c", "detail": "canary rollback: nan"}


def test_clean_walk_commits_fleet_wide_one_at_a_time(
        fleet, tmp_path, params_pair):
    """The tentpole's happy path: one committed step walks the fleet in
    replica-id order, each replica settles LIVE before the next one is
    touched, and the walk lands as a typed committed result with the
    progress gauge and outcome counter moving."""
    d = str(tmp_path / "ck")
    reg = _registry_for(fleet)
    step = next(_STEP)
    write_committed_step(d, step, {"params": params_pair[1]})
    ctrl = _controller(reg, d)

    order = []
    orig = ctrl._push_and_settle

    def spy(replica, s):
        idx = int(replica.replica_id[1:])
        for j, rep in enumerate(fleet):
            if j > idx:  # later replicas must not have moved yet
                assert rep.engine.weight_version != s
        order.append(replica.replica_id)
        return orig(replica, s)

    ctrl._push_and_settle = spy
    assert ctrl.poll_once() == step  # the watcher contract, reused
    res = ctrl.last
    assert res is not None and res.outcome == "committed"
    assert res.updated == ("r00", "r01", "r02") == tuple(order)
    assert res.step == step and res.halted_at == ""
    for rep in fleet:
        assert rep.engine.weight_version == step
        assert _healthz(rep.base)["deploy"]["weight_version"] == step
    assert ctrl._c_rollout.labels(outcome="committed").value == 1.0
    assert ctrl._g_current.value == 3.0
    assert ctrl.history[-1] is res


@pytest.mark.fault
def test_poisoned_step_halts_walk_and_rolls_fleet_back(
        fleet, tmp_path, params_pair):
    """ISSUE acceptance: a ``deploy_nan``-poisoned step burns exactly ONE
    replica's canary — the walk halts there, and the already-updated
    replicas are re-pushed back to their prior committed step."""
    d = str(tmp_path / "ck")
    reg = _registry_for(fleet)
    base_step, ctrl = _baseline(fleet, reg, d, params_pair[0])

    bad = next(_STEP)
    write_committed_step(d, bad, {"params": params_pair[1]})
    # after=3: the controller's own watcher delivery traverses the site
    # once (and discards the poisoned tree), then the r00/r01 pushes
    # pass, then the r02 push poisons its canary.
    faults.configure("deploy_nan:after=3")
    try:
        assert ctrl.poll_once() == bad
    finally:
        faults.reset()
    res = ctrl.last
    assert res.outcome == "rolled_back"
    assert res.halted_at == "r02"
    assert res.updated == ("r00", "r01")
    assert res.rolled_back == ("r00", "r01")
    assert "canary rollback" in res.detail
    for rep in fleet:  # nobody is left on the poisoned step
        assert rep.engine.weight_version == base_step
    assert ctrl._c_rollout.labels(outcome="rolled_back").value == 1.0
    assert ctrl._g_current.value == 0.0


@pytest.mark.fault
def test_rollout_push_fault_is_a_typed_halt_with_rollback(
        fleet, tmp_path, params_pair):
    """``rollout_push`` chaos site: a delivery that dies mid-walk halts
    at that replica with the push error in the detail, and the replicas
    already on the new step are rolled back — never a half-updated
    fleet left behind."""
    d = str(tmp_path / "ck")
    reg = _registry_for(fleet)
    base_step, ctrl = _baseline(fleet, reg, d, params_pair[0])

    step = next(_STEP)
    write_committed_step(d, step, {"params": params_pair[1]})
    # after=1: the r00 push passes, the r01 push dies.
    faults.configure("rollout_push:after=1")
    try:
        assert ctrl.poll_once() == step
    finally:
        faults.reset()
    res = ctrl.last
    assert res.outcome == "rolled_back"
    assert res.halted_at == "r01"
    assert res.updated == ("r00",) == res.rolled_back
    assert res.detail.startswith("push failed: InjectedFault")
    for rep in fleet:
        assert rep.engine.weight_version == base_step


@pytest.mark.fault
def test_rollback_without_committed_prior_reports_halted(
        fleet, tmp_path, params_pair):
    """A replica whose prior version is NOT a committed step of the
    watch dir (fresh dir, nothing published before the halt) cannot be
    restored by re-push — the result says so (outcome ``halted``)
    instead of faking a clean rollback."""
    d = str(tmp_path / "ck")
    reg = _registry_for(fleet)
    step = next(_STEP)
    write_committed_step(d, step, {"params": params_pair[1]})
    ctrl = _controller(reg, d)
    faults.configure("rollout_push:after=1")
    try:
        assert ctrl.poll_once() == step
    finally:
        faults.reset()
    res = ctrl.last
    assert res.outcome == "halted"
    assert res.halted_at == "r01"
    assert res.updated == ("r00",) and res.rolled_back == ()
    assert "not a committed step" in res.detail
    assert ctrl._c_rollout.labels(outcome="halted").value == 1.0


# ---------------------------------------------------------------------------
# CanaryRamp: SLO-gated percent schedule
# ---------------------------------------------------------------------------


def test_ramp_schedule_validation():
    reg = ReplicaRegistry()
    for bad in ((), (0.0,), (50.0, 5.0), (5.0, 101.0)):
        with pytest.raises(ValueError, match="schedule"):
            CanaryRamp(reg, schedule=bad)


def test_ramp_widens_on_hold_and_narrows_to_first_rung_on_breach(fleet):
    """The ramp's whole contract: open at the first rung, widen one rung
    per ``hold_s`` of clean signal, and one breach edge forfeits ALL
    earned exposure — straight back to the first rung, with every change
    pushed to every replica's variant table."""
    clk = [0.0]
    reg = _registry_for(fleet)
    ramp = CanaryRamp(reg, None, variant="canary",
                      schedule=(5.0, 25.0, 100.0), hold_s=10.0,
                      clock=lambda: clk[0])
    assert ramp.percent == 0.0 and not ramp.done
    try:
        assert ramp.begin() == 5.0
        for rep in fleet:
            assert rep.sched.variants.canary_percent == 5.0
            assert rep.sched.variants.canary_variant == "canary"
        clk[0] = 5.0
        assert ramp.tick() == 5.0  # hold not met yet
        clk[0] = 11.0
        assert ramp.tick() == 25.0 and ramp.widened_total == 1
        for rep in fleet:
            assert rep.sched.variants.canary_percent == 25.0
        ramp._on_slo("ttft_p99", "breach", 2.0)  # the monitor's edge
        assert ramp.tick() == 5.0 and ramp.narrowed_total == 1
        assert not ramp.done
        for rep in fleet:
            assert rep.sched.variants.canary_percent == 5.0
        clk[0] = 22.0
        assert ramp.tick() == 25.0
        clk[0] = 33.0
        assert ramp.tick() == 100.0 and ramp.done
        for rep in fleet:
            assert rep.sched.variants.canary_percent == 100.0
        assert _healthz(fleet[0].base)["deploy"]["canary_percent"] == 100.0
    finally:
        for rep in fleet:  # leave the shared fleet as it was built
            rep.sched.variants.set_canary(1.0, "canary")


def test_ramp_narrows_on_real_slo_monitor_breach():
    """End-to-end SLO wiring: a real SloMonitor rule over a real metrics
    registry breaches, its ok->breach callback reaches the ramp, and the
    next tick narrows — no fleet needed (the registry has no replicas,
    pushes are a no-op)."""
    reg = ReplicaRegistry()
    clk = [0.0]
    g = reg.metrics_registry.gauge("rollout_test_latency",
                                   "ramp-test latency signal")
    mon = SloMonitor(reg.metrics_registry,
                     [SloRule("lat", "rollout_test_latency", 1.0)],
                     clock=lambda: clk[0])
    ramp = CanaryRamp(reg, mon, schedule=(5.0, 50.0), hold_s=0.0,
                      clock=lambda: clk[0])
    ramp.begin()
    clk[0] = 1.0
    assert ramp.tick() == 50.0 and ramp.done  # hold_s=0: instant widen
    g.set(9.0)
    clk[0] = 2.0
    mon.evaluate()  # ok -> breach edge fires the callback
    assert ramp.tick() == 5.0
    assert ramp.narrowed_total == 1 and ramp.rung == 0


@pytest.mark.fault
def test_rollout_slo_flap_fault_narrows_never_widens_through_noise():
    """``rollout_slo_flap`` chaos site: an injected breach signal narrows
    exactly like a real one, and the very next clean tick does NOT widen
    (the hold clock restarted at the flap)."""
    reg = ReplicaRegistry()
    clk = [0.0]
    ramp = CanaryRamp(reg, None, schedule=(5.0, 50.0), hold_s=10.0,
                      clock=lambda: clk[0])
    ramp.begin()
    clk[0] = 11.0
    assert ramp.tick() == 50.0
    faults.configure("rollout_slo_flap:1")
    try:
        assert ramp.tick() == 5.0
    finally:
        faults.reset()
    assert ramp.narrowed_total == 1 and ramp.rung == 0
    clk[0] = 12.0
    assert ramp.tick() == 5.0  # one second after the flap: still held
    clk[0] = 22.0
    assert ramp.tick() == 50.0  # exposure re-earned over a full hold


# ---------------------------------------------------------------------------
# POST /admin/deploy control surface
# ---------------------------------------------------------------------------


def test_admin_deploy_canary_and_step_planes(fleet, tmp_path, params_pair):
    rep = fleet[0]
    admin = rep.base + "/admin/deploy"

    status, _, body = _post(admin, {"canary_percent": 37.5,
                                    "canary_variant": "canary"})
    assert status == 200 and body["canary_percent"] == 37.5
    assert _healthz(rep.base)["deploy"]["canary_percent"] == 37.5
    rep.sched.variants.set_canary(1.0, "canary")

    status, _, body = _post(admin, {"canary_percent": 150.0})
    assert status == 400 and body["error"] == "invalid"

    d = str(tmp_path / "ck")
    step = next(_STEP)
    write_committed_step(d, step, {"params": params_pair[1]})

    # Uncommitted step / missing watch_dir: typed 400s, no swap.
    status, _, body = _post(admin, {"watch_dir": d, "step": step + 999})
    assert status == 400 and body["error"] == "invalid"
    status, _, body = _post(admin, {"step": step})
    assert status == 400 and body["error"] == "invalid"

    # The real push, answered inline via wait_s.
    status, _, body = _post(admin, {"watch_dir": d, "step": step,
                                    "wait_s": 60})
    assert status == 200 and body["ok"] and body["applied"]
    assert body["swap"]["outcome"] == "ok" and body["swap"]["step"] == step
    deploy = _healthz(rep.base)["deploy"]
    assert deploy["weight_version"] == step
    assert deploy["last_swap"]["step"] == step


def test_admin_deploy_without_deploy_plane_is_typed_400(
        serve_lm, params_pair):
    """A replica built with no deploy plane (deploy_cfg=None) answers
    /admin/deploy with typed 400s, not a crash."""
    from distributed_tensorflow_tpu.config import ServeConfig

    serve_cfg = ServeConfig(port=0, slots=2, serve_max_len=32,
                            prefill_len=12)
    _, sched, _, server = serve_lm.build_stack(
        serve_cfg, CFG, params_pair[0], deploy_cfg=None)
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    sched.start(poll_s=0.001)
    host, port = server.server_address
    admin = f"http://{host}:{port}/admin/deploy"
    try:
        status, _, body = _post(admin, {"step": 1, "watch_dir": "/tmp"})
        assert status == 400 and "swapper" in body["detail"]
        status, _, body = _post(admin, {"canary_percent": 5.0})
        assert status == 400 and "variant table" in body["detail"]
        status, _, body = _post(admin, [])  # non-object body
        assert status == 400
    finally:
        server.shutdown()
        server.server_close()
        thread.join(timeout=5)
        sched.stop()


# ---------------------------------------------------------------------------
# Cross-structure sibling-engine variants
# ---------------------------------------------------------------------------


def _client_in_lane(below, percent):
    for i in range(1000):
        cid = f"client-{i}"
        if (variant_lane(cid) < percent) == below:
            return cid
    raise AssertionError("no client id found for the requested lane side")


def test_sibling_engine_variant_serves_behind_one_scheduler(params_pair):
    """ISSUE acceptance: a variant whose param treedef DIFFERS from the
    live engine (the buffer flip hard-rejects it) runs as a sibling
    engine behind the SAME scheduler — lane routing, explicit pins,
    ``(variant, weight_version)`` attribution, and typed rejection of
    unknown variants all unchanged, with zero recompiles on either
    engine."""
    engine = SlotEngine(CFG, params_pair[0], slots=2, max_len=32,
                        prefill_len=12)
    base_compiled = engine.warmup()
    sib_params = TransformerLM(SIB_CFG).init(
        jax.random.PRNGKey(2), jnp.zeros((1, 8), jnp.int32))["params"]
    # The motivation: the flip path cannot take this tree.
    with pytest.raises(ValueError):
        engine.stage_weights(sib_params)

    sib_engine = SlotEngine(SIB_CFG, sib_params, slots=2, max_len=32,
                            prefill_len=12)
    sib_compiled = sib_engine.warmup()
    table = VariantTable(engine, canary_percent=40.0,
                         canary_variant="exp")
    with pytest.raises(ValueError, match="default"):
        table.set_engine("main", sib_engine)
    table.set_engine("exp", sib_engine, step=7)
    assert table.engine_for("exp") is sib_engine
    assert table.engine_for("main") is engine
    assert table.snapshot()["variants"]["exp"]["engine"] == "sibling"
    assert table.snapshot()["variants"]["main"]["engine"] == "base"

    metrics = ServingMetrics()
    sched = Scheduler(engine, max_queue_depth=32, metrics=metrics,
                      variants=table)
    exp_cid = _client_in_lane(True, 40.0)
    main_cid = _client_in_lane(False, 40.0)
    assert table.resolve(exp_cid) == "exp"
    assert table.resolve(main_cid) == "main"

    unknown = sched.submit(Request(prompt=(1,), max_new_tokens=2,
                                   variant="nope"))
    out = unknown.result(timeout=1)
    assert isinstance(out, Rejection) and out.reason == "invalid"

    lane_exp = sched.submit(Request(prompt=(3, 1, 4), max_new_tokens=4,
                                    client_id=exp_cid))
    lane_main = sched.submit(Request(prompt=(3, 1, 4), max_new_tokens=4,
                                     client_id=main_cid))
    pinned = sched.submit(Request(prompt=(9, 9), max_new_tokens=4,
                                  variant="exp"))
    sched.run_until_idle(max_steps=500)

    got_exp = lane_exp.result(timeout=10)
    got_main = lane_main.result(timeout=10)
    got_pin = pinned.result(timeout=10)
    for got in (got_exp, got_main, got_pin):
        assert isinstance(got, Completion), got
    assert got_exp.variant == "exp" and got_exp.weight_version == 7
    assert got_pin.variant == "exp" and got_pin.weight_version == 7
    assert got_main.variant == "main" and got_main.weight_version == 0
    assert engine.compile_count() == base_compiled
    assert sib_engine.compile_count() == sib_compiled
    counts = metrics.variant_requests()
    assert counts["exp"] == 2 and counts["main"] == 1

    # The scheduler keeps flipping cleanly after the sibling served.
    again = sched.submit(Request(prompt=(5, 2), max_new_tokens=3,
                                 client_id=main_cid))
    sched.run_until_idle(max_steps=200)
    assert again.result(timeout=10).variant == "main"


# ---------------------------------------------------------------------------
# tools/train_draft.py --publish_dir (the self-refreshing drafter)
# ---------------------------------------------------------------------------


def test_train_draft_publishes_committed_steps(tmp_path):
    """``--publish_dir`` lands the distilled drafter as a COMMITTED
    checkpoint step (auto-numbered after the newest, or pinned via
    ``--publish_step``) so the rollout controller can walk it."""
    import importlib.util

    for p in (_REPO, _TOOLS):
        if p not in sys.path:
            sys.path.insert(0, p)
    spec = importlib.util.spec_from_file_location(
        "train_draft", os.path.join(_TOOLS, "train_draft.py"))
    train_draft = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(train_draft)

    pub = str(tmp_path / "pub")
    argv = [
        "--demo", "--vocab_size", "32", "--d_model", "16",
        "--num_heads", "2", "--num_layers", "1", "--d_ff", "32",
        "--seq_len", "16", "--draft_layers", "1", "--steps", "1",
        "--batch", "2", "--window", "4", "--rollouts", "2",
        "--rollout_prompt", "2", "--log_every", "1",
        "--output", str(tmp_path / "draft.msgpack"),
        "--publish_dir", pub,
    ]
    train_draft.main(argv)
    assert list_committed_steps(pub) == [1]  # auto: empty dir -> step 1
    tree = read_step(pub, 1)
    assert "params" in tree

    train_draft.main(argv + ["--publish_step", "10"])
    assert list_committed_steps(pub) == [1, 10]


# ---------------------------------------------------------------------------
# 3-replica subprocess e2e: clean walk + poisoned halt, under load
# ---------------------------------------------------------------------------


@pytest.mark.fault
def test_fleet_rollout_e2e_clean_then_poisoned_under_load(tmp_path):
    """ISSUE acceptance, over real processes: a committed step walks 3
    subprocess replicas one at a time under open traffic (zero silent
    drops, zero post-warmup recompiles, every replica converges), then a
    ``DTT_FAULT=deploy_nan``-poisoned step halts at the armed replica
    and the fleet is rolled back — no replica left on the bad step."""
    for p in (_REPO, _TOOLS):
        if p not in sys.path:
            sys.path.insert(0, p)
    from serve_fleet import launch_fleet

    from distributed_tensorflow_tpu.serve.fleet import (
        FleetRouter,
        make_router_server,
    )

    argv = ["--demo", "--vocab_size", "64", "--d_model", "32",
            "--num_heads", "4", "--num_layers", "2", "--d_ff", "64",
            "--seq_len", "32", "--slots", "2", "--prefill_len", "12",
            "--serve_max_len", "32", "--drain_deadline_s", "10"]
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    poisoned_env = dict(env)
    # after=1: the baseline push passes, the next pushed step poisons.
    poisoned_env["DTT_FAULT"] = "deploy_nan:after=1"

    ckpt = str(tmp_path / "ck")
    model = TransformerLM(CFG)
    zeros = jnp.zeros((1, 8), jnp.int32)
    good = model.init(jax.random.PRNGKey(1), zeros)["params"]
    newer = model.init(jax.random.PRNGKey(2), zeros)["params"]

    replicas = launch_fleet(2, argv, env=env)
    rserver = rthread = None
    stop = threading.Event()
    clients = []
    try:
        replicas += launch_fleet(1, argv, env=poisoned_env)
        reg = ReplicaRegistry(up_after=1, down_after=3,
                              probe_timeout_s=10.0)
        for i, rp in enumerate(replicas):
            reg.add(rp.url, replica_id=f"r{i:02d}")
        reg.probe_once()
        assert reg.up_count() == 3
        router = FleetRouter(reg, read_timeout_s=60.0)
        rserver = make_router_server(router, port=0)
        rthread = threading.Thread(target=rserver.serve_forever,
                                   daemon=True)
        rthread.start()
        rhost, rport = rserver.server_address
        base = f"http://{rhost}:{rport}"

        transport_drops = []
        statuses = []
        lock = threading.Lock()

        def pound(i):
            n = 0
            while not stop.is_set():
                n += 1
                try:
                    status, _, _ = _post(base + "/generate", {
                        "prompt": [1 + (n % 7), 2, 3],
                        "max_new_tokens": 6,
                        "request_id": f"load-{i}-{n}",
                    }, timeout=60)
                    with lock:
                        statuses.append(status)
                except OSError as exc:  # a silent drop, the one sin
                    with lock:
                        transport_drops.append(repr(exc))

        clients = [threading.Thread(target=pound, args=(i,), daemon=True)
                   for i in range(3)]
        for th in clients:
            th.start()

        write_committed_step(ckpt, 1, {"params": good})
        ctrl = RolloutController(reg, ckpt, settle_timeout_s=120.0,
                                 settle_poll_s=0.05, push_timeout_s=60.0,
                                 start_after=0)
        assert ctrl.poll_once() == 1
        res = ctrl.last
        assert res.outcome == "committed", res.to_dict()
        assert res.updated == ("r00", "r01", "r02")
        for rp in replicas:
            assert _healthz(rp.url)["deploy"]["weight_version"] == 1

        reg.probe_once()  # pin the rollback priors at step 1
        write_committed_step(ckpt, 2, {"params": newer})
        assert ctrl.poll_once() == 2
        res = ctrl.last
        assert res.outcome == "rolled_back", res.to_dict()
        assert res.halted_at == "r02"
        assert res.rolled_back == ("r00", "r01")
        assert "canary rollback" in res.detail
        for rp in replicas:  # every replica restored, none on step 2
            assert _healthz(rp.url)["deploy"]["weight_version"] == 1

        stop.set()
        for th in clients:
            th.join(timeout=60)
        assert transport_drops == []  # zero silent drops
        assert statuses and all(s == 200 for s in statuses), (
            sorted(set(statuses)))
        for rp in replicas:  # zero post-warmup recompiles anywhere
            with urllib.request.urlopen(rp.url + "/metrics",
                                        timeout=10) as resp:
                text = resp.read().decode()
            for line in text.splitlines():
                if line.startswith(mn.RECOMPILE_EVENTS_TOTAL + " "):
                    assert float(line.split()[-1]) == 0.0, line
    finally:
        stop.set()
        for th in clients:
            th.join(timeout=10)
        if rserver is not None:
            rserver.shutdown()
            rserver.server_close()
        if rthread is not None:
            rthread.join(timeout=5)
        for rp in replicas:
            rp.terminate()


# -- bench gate ------------------------------------------------------------


@pytest.mark.slow
def test_bench_fleet_rollout_smoke_meets_gates():
    """Run the fleet-rollout bench in smoke shape and hold it to the
    same FLOORS bench_diff enforces: zero silent drops under load while
    both walks cross the fleet, zero post-warmup recompiles on any
    replica, the poisoned step halted AND rolled back fleet-wide, and
    the SLO-gated ramp narrowed on the injected breach before full
    promotion."""
    env = dict(os.environ)
    env.update(BENCH_SMOKE="1", JAX_PLATFORMS="cpu",
               JAX_ENABLE_COMPILATION_CACHE="false")
    env.pop("XLA_FLAGS", None)  # subprocesses don't need 8 virtual devices
    out = subprocess.run(
        [sys.executable, "-c",
         "import json, bench; "
         "print(json.dumps(bench.bench_fleet_rollout()))"],
        cwd=_REPO, env=env, capture_output=True, text=True, timeout=560)
    assert out.returncode == 0, out.stderr[-4000:]
    rows = json.loads(out.stdout.strip().splitlines()[-1])
    import bench
    by_name = {r["metric"]: r for r in rows}
    for name, floor in bench.FLOORS.items():
        if name in by_name:
            assert by_name[name]["value"] >= floor, by_name[name]
    assert "fleet_rollout_zero_drops" in by_name
    assert "fleet_rollout_zero_recompiles" in by_name
    assert "fleet_rollout_halt_rollback" in by_name
    assert "fleet_rollout_ramp_narrowed" in by_name
    assert "fleet_rollout_walk_s" in by_name
