"""Fault-tolerance subsystem tests: retry/backoff timing, the DTT_FAULT
injection registry, corrupt-checkpoint walk-back, the non-finite-step guard
(skip + metric + rollback), preemption emergency-save/resume, and the
kill-and-resume multiprocess case (marked slow).

The deterministic fault-injection cases carry the ``fault`` marker and run in
tier-1; the multiprocess kill-and-resume case is ``slow``.
"""

import os
import random
import signal
import socket
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_tpu.utils import faults
from distributed_tensorflow_tpu.utils.retry import backoff_delays, retry_call

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_fault_registry():
    faults.reset()
    yield
    faults.reset()


# ---------------------------------------------------------------------------
# retry/backoff
# ---------------------------------------------------------------------------


def test_retry_backoff_timing_envelope():
    """Delays follow base*2^(n-1), capped, jittered within ±jitter."""
    sleeps = []
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 4:
            raise OSError("transient")
        return "ok"

    out = retry_call(
        flaky,
        attempts=4,
        base_delay=0.1,
        max_delay=10.0,
        jitter=0.25,
        sleep=sleeps.append,
        rng=random.Random(0),
    )
    assert out == "ok"
    assert calls["n"] == 4
    assert len(sleeps) == 3
    for d, nominal in zip(sleeps, (0.1, 0.2, 0.4)):
        assert nominal * 0.75 <= d <= nominal * 1.25, (d, nominal)


def test_retry_respects_max_delay_cap():
    delays = backoff_delays(
        6, base_delay=1.0, max_delay=3.0, jitter=0.0, rng=random.Random(0)
    )
    assert delays == [1.0, 2.0, 3.0, 3.0, 3.0]


def test_retry_exhaustion_reraises():
    sleeps = []
    with pytest.raises(OSError, match="always"):
        retry_call(
            lambda: (_ for _ in ()).throw(OSError("always")),
            attempts=3,
            base_delay=0.01,
            sleep=sleeps.append,
        )
    assert len(sleeps) == 2  # no sleep after the final attempt


def test_retry_non_retryable_raises_immediately():
    calls = {"n": 0}

    def bad():
        calls["n"] += 1
        raise ValueError("deterministic")

    with pytest.raises(ValueError):
        retry_call(bad, attempts=5, base_delay=0.01, sleep=lambda _: None)
    assert calls["n"] == 1


# ---------------------------------------------------------------------------
# fault-injection registry
# ---------------------------------------------------------------------------


def test_fault_spec_grammar():
    sites = faults.parse_spec("download:2,ckpt_save:1,nonfinite_grad:step=7,ckpt_restore")
    assert sites["download"].remaining == 2
    assert sites["ckpt_save"].remaining == 1
    assert sites["ckpt_restore"].remaining == 1
    assert sites["nonfinite_grad"].steps == {7}
    merged = faults.parse_spec("x:step=3,x:step=9,x:2")  # dttlint: disable=fault-registry -- grammar unit test: dummy site exercises entry merging, not injection
    assert merged["x"].steps == {3, 9} and merged["x"].remaining == 2


def test_fault_spec_rejects_typos():
    with pytest.raises(ValueError):
        faults.parse_spec("download:twice")
    with pytest.raises(ValueError):
        faults.parse_spec(":3")


def test_fault_counts_decrement_and_exhaust():
    faults.configure("site_a:2")  # dttlint: disable=fault-registry -- registry unit test: dummy site fired via faults.fire directly below, no wired call site needed
    assert faults.fire("site_a")
    assert faults.fire("site_a")
    assert not faults.fire("site_a")
    assert not faults.fire("never_armed")


def test_fault_steps_consumed_by_range():
    faults.configure("g:step=5,g:step=11")  # dttlint: disable=fault-registry -- registry unit test: dummy site fired via faults.fire_step directly below, no wired call site needed
    assert not faults.fire_step("g", range(0, 4))
    assert faults.fire_step("g", range(4, 8))  # consumes 5
    assert not faults.fire_step("g", range(4, 8))
    assert faults.fire_step("g", [11])


def test_injected_fault_is_oserror_subclass():
    faults.configure("s:1")
    with pytest.raises(OSError):
        faults.maybe_fail("s")
    faults.maybe_fail("s")  # disarmed: no raise


def test_registry_loads_from_env(monkeypatch):
    monkeypatch.setenv(faults.ENV_VAR, "envsite:1")
    faults.reset()
    assert faults.fire("envsite")
    assert not faults.fire("envsite")


# ---------------------------------------------------------------------------
# download: retry, stale .part sweep, stderr progress
# ---------------------------------------------------------------------------


@pytest.mark.fault
def test_download_recovers_from_injected_failures(tmp_path):
    from distributed_tensorflow_tpu.data import download as dl

    src = tmp_path / "payload.bin"
    src.write_bytes(b"y" * 4096)
    dest = tmp_path / "out" / "payload.bin"
    faults.configure("download:2")
    assert dl.download_file(
        src.as_uri(), str(dest), progress=False, retries=3, retry_base_delay=0.01
    )
    assert dest.read_bytes() == b"y" * 4096
    # Both injected shots consumed, none left to poison later downloads.
    assert not faults.fire("download")


def test_download_retries_exhausted_leaves_no_partial(tmp_path):
    from distributed_tensorflow_tpu.data import download as dl

    src = tmp_path / "payload.bin"
    src.write_bytes(b"z" * 128)
    dest = tmp_path / "out" / "payload.bin"
    faults.configure("download:5")
    with pytest.raises(OSError):
        dl.download_file(
            src.as_uri(), str(dest), progress=False, retries=2, retry_base_delay=0.01
        )
    assert not dest.exists()
    leftovers = [f for f in os.listdir(tmp_path / "out") if f.endswith(".part")]
    assert leftovers == []


def test_stale_part_sweep(tmp_path):
    from distributed_tensorflow_tpu.data import download as dl

    src = tmp_path / "f.bin"
    src.write_bytes(b"data")
    out = tmp_path / "out"
    out.mkdir()
    stale = out / "f.bin.deadbeef.part"
    stale.write_text("junk")
    old = time.time() - 7200
    os.utime(stale, (old, old))
    fresh = out / "f.bin.cafe.part"  # a live concurrent download's temp file
    fresh.write_text("inflight")
    other = out / "g.bin.dead.part"  # someone else's download
    other.write_text("x")
    os.utime(other, (old, old))
    dl.download_file(src.as_uri(), str(out / "f.bin"), progress=False)
    assert not stale.exists()
    assert fresh.exists()  # age-gated: live temp files survive
    assert other.exists()  # name-scoped: other destinations untouched


def test_progress_goes_to_stderr_not_stdout(tmp_path, capsys):
    from distributed_tensorflow_tpu.data import download as dl

    src = tmp_path / "p.bin"
    src.write_bytes(b"q" * (1 << 17))
    dl.download_file(src.as_uri(), str(tmp_path / "out" / "p.bin"), progress=True)
    captured = capsys.readouterr()
    assert ">> Downloading p.bin" in captured.err
    assert ">> Downloading" not in captured.out


def test_progress_byte_count_without_content_length(tmp_path, capsys, monkeypatch):
    """No Content-Length → byte-count progress instead of silence."""
    import urllib.request

    from distributed_tensorflow_tpu.data import download as dl

    class _Resp:
        headers = {}

        def __init__(self):
            self._left = 1 << 17

        def read(self, n):
            take = min(n, self._left)
            self._left -= take
            return b"a" * take

        def __enter__(self):
            return self

        def __exit__(self, *a):
            return False

    monkeypatch.setattr(urllib.request, "urlopen", lambda *a, **k: _Resp())
    dl.download_file("http://unused", str(tmp_path / "o" / "b.bin"), progress=True)
    captured = capsys.readouterr()
    assert "MB" in captured.err
    assert (tmp_path / "o" / "b.bin").stat().st_size == 1 << 17


# ---------------------------------------------------------------------------
# checkpoint: save retry + corrupt-checkpoint walk-back
# ---------------------------------------------------------------------------


def _truncate_step_dir(root: str, step: int) -> None:
    """Simulate a writer killed mid-checkpoint: empty every file of the step
    dir but leave the directory structure (so Orbax still lists the step)."""
    step_dir = os.path.join(root, str(step))
    assert os.path.isdir(step_dir), step_dir
    for dirpath, _dirs, files in os.walk(step_dir):
        for f in files:
            os.remove(os.path.join(dirpath, f))


@pytest.mark.fault
def test_ckpt_save_recovers_from_injected_io_failure(tmp_path):
    from distributed_tensorflow_tpu.train.checkpoint import CheckpointManager

    mngr = CheckpointManager(str(tmp_path / "ck"), save_interval_secs=0)
    faults.configure("ckpt_save:2")
    mngr.save(3, {"w": np.arange(4.0, dtype=np.float32)}, wait=True)
    assert mngr.latest_step() == 3
    mngr.close()


def test_restore_walks_back_over_truncated_latest(tmp_path):
    from distributed_tensorflow_tpu.train.checkpoint import CheckpointManager

    root = str(tmp_path / "ck")
    mngr = CheckpointManager(root, save_interval_secs=0)
    state1 = {"w": np.arange(8.0, dtype=np.float32)}
    state2 = {"w": np.arange(8.0, dtype=np.float32) * 2}
    mngr.save(1, state1, wait=True)
    mngr.save(2, state2, wait=True)
    _truncate_step_dir(root, 2)
    step, restored = mngr.restore_latest(state1)
    assert step == 1
    np.testing.assert_array_equal(restored["w"], state1["w"])
    # The template-free path walks back identically.
    step_raw, _ = mngr.restore_latest_raw()
    assert step_raw == 1
    mngr.close()


def test_restore_returns_none_when_every_step_corrupt(tmp_path):
    from distributed_tensorflow_tpu.train.checkpoint import CheckpointManager

    root = str(tmp_path / "ck")
    mngr = CheckpointManager(root, save_interval_secs=0)
    state = {"w": np.zeros(4, np.float32)}
    mngr.save(1, state, wait=True)
    _truncate_step_dir(root, 1)
    assert mngr.restore_latest(state) is None
    mngr.close()


@pytest.mark.fault
def test_async_save_retries_injected_fault_on_background_thread(tmp_path):
    """DTT_FAULT=ckpt_save:1 must still be recovered when the write happens
    on the snapshot worker thread (the async path), not just the blocking
    one."""
    from distributed_tensorflow_tpu.train.checkpoint import CheckpointManager

    mngr = CheckpointManager(str(tmp_path / "ck"), save_interval_secs=0)
    faults.configure("ckpt_save:1")
    state = {"w": np.arange(4.0, dtype=np.float32)}
    assert mngr.save(7, state)  # async: accepted without blocking
    mngr.wait_until_finished()
    assert mngr.latest_step() == 7
    assert not faults.fire("ckpt_save")  # the one shot was consumed + retried
    step, restored = mngr.restore_latest(state)
    assert step == 7
    np.testing.assert_array_equal(restored["w"], state["w"])
    mngr.close()


def test_timed_gate_skips_without_blocking_when_save_in_flight(tmp_path):
    """The head-of-line fix: a timed gate firing while the previous save is
    still in flight skips with a warning instead of stalling the caller for
    the previous write (old behavior: unconditional wait_until_finished)."""
    import time as _time

    from distributed_tensorflow_tpu.train.checkpoint import CheckpointManager

    mngr = CheckpointManager(str(tmp_path / "ck"), save_interval_secs=0)
    mngr._hold_next_snapshot = True  # park save 1 in flight
    state = {"w": np.arange(8.0, dtype=np.float32)}
    assert mngr.maybe_save(1, state)
    t0 = _time.perf_counter()
    assert not mngr.maybe_save(2, state)  # gate fires again: skip, don't block
    assert _time.perf_counter() - t0 < 2.0
    for j in mngr._jobs:  # release the parked snapshot
        j.held = False
    mngr.wait_until_finished()
    assert mngr.latest_step() == 1  # save 1 completed; save 2 was skipped
    mngr.close()


def test_max_to_keep_plumbed_from_config(tmp_path, monkeypatch):
    """MnistTrainConfig.max_to_keep reaches the CheckpointManager."""
    from distributed_tensorflow_tpu.config import MnistTrainConfig, RetrainConfig
    from distributed_tensorflow_tpu.train import checkpoint as ckpt_mod
    from distributed_tensorflow_tpu.train.loop import MnistTrainer

    assert MnistTrainConfig().max_to_keep == 5
    assert RetrainConfig().max_to_keep == 5
    seen = {}
    real = ckpt_mod.CheckpointManager

    class Spy(real):
        def __init__(self, directory, save_interval_secs=600.0, max_to_keep=5, **kw):
            seen["max_to_keep"] = max_to_keep
            super().__init__(directory, save_interval_secs, max_to_keep, **kw)

    import distributed_tensorflow_tpu.train.loop as loop_mod

    monkeypatch.setattr(loop_mod, "CheckpointManager", Spy)
    from distributed_tensorflow_tpu.data.mnist import read_data_sets

    ds = read_data_sets(
        "unused", synthetic=True, num_synthetic_train=64, num_synthetic_test=32
    )
    cfg = MnistTrainConfig(
        data_dir="x", log_dir=str(tmp_path / "logs"), model_dir=str(tmp_path / "m"),
        training_steps=1, synthetic_data=True, max_to_keep=7,
    )
    from distributed_tensorflow_tpu.parallel.mesh import make_mesh

    MnistTrainer(cfg, mesh=make_mesh(num_devices=1), datasets=ds)
    assert seen["max_to_keep"] == 7


# ---------------------------------------------------------------------------
# non-finite guard (step builders)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def guard_fixture():
    import optax

    from distributed_tensorflow_tpu.models.mnist_cnn import MnistCNN
    from distributed_tensorflow_tpu.parallel import data_parallel as dp
    from distributed_tensorflow_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(num_devices=1)
    model = MnistCNN(compute_dtype=jnp.float32)
    tx = optax.adam(1e-3)
    params = jax.device_get(
        model.init(jax.random.PRNGKey(0), jnp.zeros((1, 784)), train=False)["params"]
    )
    rng = np.random.default_rng(0)
    xs = rng.normal(size=(16, 784)).astype(np.float32)
    ys = np.eye(10, dtype=np.float32)[np.arange(16) % 10]
    return mesh, model, tx, params, xs, ys


def _fresh_state(dp, mesh, tx, params):
    p = dp.replicate(params, mesh)
    o = dp.replicate(jax.device_get(tx.init(params)), mesh)
    g = dp.replicate(jnp.zeros((), jnp.int32), mesh)
    return p, o, g


def _trees_equal(a, b) -> bool:
    return all(
        np.array_equal(np.asarray(jax.device_get(x)), np.asarray(jax.device_get(y)))
        for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b))
    )


def test_nonfinite_guard_skips_update_keeps_step(guard_fixture):
    from distributed_tensorflow_tpu.parallel import data_parallel as dp

    mesh, model, tx, params, xs, ys = guard_fixture
    p, o, g = _fresh_state(dp, mesh, tx, params)
    step = dp.build_train_step(model.apply, tx, mesh, donate=False)
    good = dp.shard_batch({"image": xs, "label": ys}, mesh)
    bad = dp.shard_batch({"image": xs * np.nan, "label": ys}, mesh)

    p1, o1, g1, m1 = step(p, o, g, good, jax.random.PRNGKey(0))
    assert float(jax.device_get(m1["skipped_nonfinite"])) == 0.0
    assert not _trees_equal(p, p1)  # finite step really updated

    p2, o2, g2, m2 = step(p1, o1, g1, bad, jax.random.PRNGKey(0))
    assert float(jax.device_get(m2["skipped_nonfinite"])) == 1.0
    assert int(jax.device_get(g2)) == 2  # step count stays honest
    assert _trees_equal(p1, p2)  # params untouched
    assert _trees_equal(o1, o2)  # optimizer moments untouched too


def test_nonfinite_guard_multi_step_counts_per_step(guard_fixture):
    from distributed_tensorflow_tpu.parallel import data_parallel as dp

    mesh, model, tx, params, xs, ys = guard_fixture
    p, o, g = _fresh_state(dp, mesh, tx, params)
    multi = dp.build_multi_step(model.apply, tx, mesh, donate=False)
    stacked = {
        "image": np.stack([xs, xs * np.nan, xs]),
        "label": np.stack([ys, ys, ys]),
    }
    from jax.sharding import NamedSharding, PartitionSpec as P

    batch = jax.device_put(
        stacked, NamedSharding(mesh, P(None, ("data", "model")))
    )
    p1, o1, g1, m = multi(p, o, g, batch, jax.random.PRNGKey(0))
    skipped = np.asarray(jax.device_get(m["skipped_nonfinite"]))
    np.testing.assert_array_equal(skipped, [0.0, 1.0, 0.0])
    assert int(jax.device_get(g1)) == 3


def test_nonfinite_guard_accum_step(guard_fixture):
    from distributed_tensorflow_tpu.parallel import data_parallel as dp

    mesh, model, tx, params, xs, ys = guard_fixture
    p, o, g = _fresh_state(dp, mesh, tx, params)
    accum = dp.build_accum_train_step(model.apply, tx, mesh, donate=False)
    from jax.sharding import NamedSharding, PartitionSpec as P

    sharding = NamedSharding(mesh, P(None, ("data", "model")))
    bad = jax.device_put(
        {"image": np.stack([xs, xs * np.nan]), "label": np.stack([ys, ys])}, sharding
    )
    p1, o1, g1, m = accum(p, o, g, bad, jax.random.PRNGKey(0))
    # One NaN microbatch poisons the accumulated gradient -> ONE skipped update.
    assert float(jax.device_get(m["skipped_nonfinite"])) == 1.0
    assert _trees_equal(p, p1)
    assert int(jax.device_get(g1)) == 1


def test_guard_can_be_disabled(guard_fixture):
    from distributed_tensorflow_tpu.parallel import data_parallel as dp

    mesh, model, tx, params, xs, ys = guard_fixture
    p, o, g = _fresh_state(dp, mesh, tx, params)
    step = dp.build_train_step(model.apply, tx, mesh, donate=False, guard_nonfinite=False)
    good = dp.shard_batch({"image": xs, "label": ys}, mesh)
    _, _, _, m = step(p, o, g, good, jax.random.PRNGKey(0))
    assert "skipped_nonfinite" not in m


# ---------------------------------------------------------------------------
# trainer end-to-end: guard + rollback + preemption + injected faults
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def resil_data():
    from distributed_tensorflow_tpu.data.mnist import read_data_sets

    return read_data_sets(
        "/nonexistent", synthetic=True, num_synthetic_train=512, num_synthetic_test=128
    )


def _trainer_cfg(tmp_path, **kw):
    from distributed_tensorflow_tpu.config import MnistTrainConfig

    defaults = dict(
        data_dir=str(tmp_path / "none"),
        log_dir=str(tmp_path / "logs"),
        model_dir=str(tmp_path / "model"),
        batch_size=32,
        learning_rate=1e-3,
        synthetic_data=True,
        save_model_secs=3600,  # no timed autosaves; boundary/forced only
        seed=0,
    )
    defaults.update(kw)
    return MnistTrainConfig(**defaults)


def _make_trainer(cfg, datasets):
    from distributed_tensorflow_tpu.models.mnist_cnn import MnistCNN
    from distributed_tensorflow_tpu.parallel.mesh import make_mesh
    from distributed_tensorflow_tpu.train.loop import MnistTrainer

    return MnistTrainer(
        cfg,
        mesh=make_mesh(num_devices=1),
        datasets=datasets,
        model=MnistCNN(compute_dtype=jnp.float32, dropout_rate=0.1),
    )


@pytest.mark.fault
def test_injected_faults_recover_end_to_end(tmp_path, resil_data):
    """The acceptance scenario: one download failure, one ckpt-save failure,
    and one non-finite grad step — the run completes, skips exactly one
    update, and lands within noise of the no-fault run."""
    from distributed_tensorflow_tpu.data import download as dl

    clean = _make_trainer(
        _trainer_cfg(tmp_path / "clean", training_steps=24, eval_step_interval=8),
        resil_data,
    )
    clean.train()
    acc_clean, _ = clean.evaluate(resil_data.test)
    assert clean.total_skipped == 0

    faults.configure("download:1,ckpt_save:1,nonfinite_grad:step=3")
    src = tmp_path / "asset.bin"
    src.write_bytes(b"model-asset" * 100)
    assert dl.download_file(
        src.as_uri(), str(tmp_path / "fetched" / "asset.bin"),
        progress=False, retries=3, retry_base_delay=0.01,
    )
    faulted = _make_trainer(
        _trainer_cfg(tmp_path / "faulted", training_steps=24, eval_step_interval=8),
        resil_data,
    )
    stats = faulted.train()
    acc_fault, _ = faulted.evaluate(resil_data.test)
    assert stats["steps"] == 24
    assert faulted.total_skipped == 1  # exactly the injected NaN step
    assert faulted.ckpt.latest_step() == 24  # ckpt_save fault was retried away
    assert abs(acc_fault - acc_clean) < 0.2, (acc_fault, acc_clean)


@pytest.mark.fault
def test_rollback_to_last_good_checkpoint(tmp_path, resil_data):
    """Two consecutive bad eval windows trigger a rollback to the last good
    checkpoint, after which training completes normally."""
    kw = dict(eval_step_interval=3, rollback_bad_windows=2)
    # Phase A: 3 clean steps; the forced final save is the good checkpoint.
    a = _make_trainer(_trainer_cfg(tmp_path, training_steps=3, **kw), resil_data)
    a.train()
    assert a.ckpt.latest_step() == 3
    # Phase B: resume; NaN at steps 4 and 7 -> bad windows ending at 6 and 9.
    faults.configure("nonfinite_grad:step=4,nonfinite_grad:step=7")
    b = _make_trainer(_trainer_cfg(tmp_path, training_steps=12, **kw), resil_data)
    stats = b.train()
    assert stats["steps"] == 12
    assert b._rollbacks == 1
    assert b.total_skipped == 2
    # Bad windows never advanced the checkpoint chain past the good step.
    assert b.ckpt.latest_step() == 12  # final forced save after recovery


@pytest.mark.fault
def test_preemption_emergency_save_and_resume(tmp_path, resil_data):
    """A preemption request (same flag a SIGTERM sets) stops the run at the
    next step boundary with an emergency checkpoint; a restarted trainer
    resumes from it and completes."""
    faults.configure("preempt:step=5")
    t1 = _make_trainer(
        _trainer_cfg(tmp_path, training_steps=10, eval_step_interval=5), resil_data
    )
    stats = t1.train()
    assert stats["steps"] == 5  # stopped at the boundary after the request
    assert t1.ckpt.latest_step() == 5  # the emergency save
    faults.reset()
    t2 = _make_trainer(
        _trainer_cfg(tmp_path, training_steps=10, eval_step_interval=5), resil_data
    )
    assert int(jax.device_get(t2.global_step)) == 5  # resumed, not restarted
    stats2 = t2.train()
    assert stats2["steps"] == 10


@pytest.mark.fault
def test_rollback_vetoes_queued_snapshot(tmp_path, resil_data):
    """A snapshot queued by a timed save INSIDE a diverging window must not
    advance the checkpoint chain: the bad-window veto cancels it, and the
    rollback restores the pre-divergence step."""
    kw = dict(eval_step_interval=3, rollback_bad_windows=2)
    a = _make_trainer(_trainer_cfg(tmp_path, training_steps=3, **kw), resil_data)
    a.train()
    assert a.ckpt.latest_step() == 3  # the good checkpoint
    faults.configure("nonfinite_grad:step=4,nonfinite_grad:step=7")
    b = _make_trainer(_trainer_cfg(tmp_path, training_steps=12, **kw), resil_data)
    b.ckpt._hold_next_snapshot = True  # keep the queued snapshot cancellable
    b.ckpt._last_save = 0.0  # the timed gate fires at step 4 — mid bad window
    stats = b.train()
    assert stats["steps"] == 12
    assert b._rollbacks == 1
    # The held step-4 snapshot was vetoed at the bad boundary: the chain
    # never advanced past the good step, so rollback restored step 3 and
    # only the final forced save added a step.
    assert b.ckpt.all_steps() == [3, 12]


@pytest.mark.fault
def test_preemption_drains_inflight_snapshot_single_durable(tmp_path, resil_data):
    """Preemption while async autosaves are in flight: the emergency save
    drains the background snapshot and leaves exactly one durable, readable
    latest checkpoint at the stop step."""
    faults.configure("preempt:step=5")
    cfg = _trainer_cfg(
        tmp_path, training_steps=10, eval_step_interval=5,
        save_model_secs=0,  # timed gate fires every step: async saves in flight
    )
    t1 = _make_trainer(cfg, resil_data)
    stats = t1.train()
    assert stats["steps"] == 5
    assert t1.ckpt.latest_step() == 5  # the emergency save, durable
    step, restored = t1.ckpt.restore_latest(t1._state_dict())
    assert step == 5
    assert int(np.asarray(restored["global_step"])) == 5
    assert stats["ckpt_stall_seconds"] >= 0.0  # stall accounting is plumbed


def test_sigterm_sets_preemption_flag():
    from distributed_tensorflow_tpu.train.resilience import PreemptionGuard

    prev = signal.getsignal(signal.SIGTERM)
    with PreemptionGuard() as guard:
        assert not guard.requested
        os.kill(os.getpid(), signal.SIGTERM)
        deadline = time.time() + 5
        while not guard.requested and time.time() < deadline:
            time.sleep(0.01)
        assert guard.requested
        assert guard.should_exit(at_boundary=False)  # single process: any boundary
    assert signal.getsignal(signal.SIGTERM) is prev  # handlers restored


def test_initialization_timeout_config_default():
    from distributed_tensorflow_tpu.config import ClusterConfig

    assert ClusterConfig().initialization_timeout == 120


def test_compilation_cache_dir_from_env_is_left_to_jax(monkeypatch):
    """Cache contract, placed from outside: with JAX_COMPILATION_CACHE_DIR
    set, JAX reads it itself and no code path here touches the config."""
    import jax

    from distributed_tensorflow_tpu.utils import compile_cache as cc

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/x")
    assert cc.enable_compilation_cache() == "/x"
    assert jax.config.jax_compilation_cache_dir == before


def test_compilation_cache_dir_default_is_fixed_in_checkout(tmp_path, monkeypatch):
    """Unset, the cache sits at <repo>/.jax_cache whatever the cwd — the
    path is part of the cache key, so a directory that moves never hits."""
    import jax

    from distributed_tensorflow_tpu.utils import compile_cache as cc

    want = os.path.join(_REPO, ".jax_cache")
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        for cwd in (tmp_path, _REPO):
            monkeypatch.chdir(cwd)
            jax.config.update("jax_compilation_cache_dir", None)
            assert cc.enable_compilation_cache() == want
            assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_vmem_budget_warns_when_jax_private_probe_is_gone(monkeypatch):
    """The scoped-VMEM raise rides jax._src.xla_bridge.backends_are_initialized
    (no public probe exists). If a future jax moves it, the budget write is
    skipped conservatively — but LOUDLY, because silently losing the raise
    costs MFU on TPU and the operator should learn it from a warning, not a
    perf regression."""
    import sys
    import types

    from distributed_tensorflow_tpu.utils import compile_cache as cc

    monkeypatch.delenv("DTF_SCOPED_VMEM_KIB", raising=False)
    monkeypatch.delenv("LIBTPU_INIT_ARGS", raising=False)
    # A module object without the symbol: the from-import raises ImportError.
    monkeypatch.setitem(
        sys.modules, "jax._src.xla_bridge",
        types.ModuleType("jax._src.xla_bridge"),
    )
    with pytest.warns(UserWarning, match="backends_are_initialized"):
        cc._configure_tpu_vmem_budget()
    assert "LIBTPU_INIT_ARGS" not in os.environ  # write skipped


# ---------------------------------------------------------------------------
# kill-and-resume, 2 real processes (slow)
# ---------------------------------------------------------------------------


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def _spawn_resil_workers(log_dir: str, per_worker_env: list[dict]) -> list[str]:
    port = _free_port()
    base_env = {
        k: v
        for k, v in os.environ.items()
        if k not in ("XLA_FLAGS", "JAX_PLATFORMS", faults.ENV_VAR)
    }
    worker = os.path.join(_REPO, "tests", "mp_resilience_worker.py")
    procs = [
        subprocess.Popen(
            [sys.executable, worker, str(i), str(port), log_dir],
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
            env={**base_env, **extra},
            cwd=_REPO,
        )
        for i, extra in enumerate(per_worker_env)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=240)
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"resilience worker {i} failed:\n{out}"
    return outs


@pytest.mark.slow
@pytest.mark.fault
def test_kill_and_resume_two_process(tmp_path):
    """Worker 0 is 'killed' (preemption fault = the SIGTERM flag) mid-run:
    both processes must agree at the next eval boundary, emergency-save
    together, and exit cleanly; a relaunch resumes from the checkpoint and
    reaches the full step count."""
    log_dir = str(tmp_path / "logs")
    # Phase 1: only worker 0 gets the preemption; coordination must stop BOTH
    # at the boundary after step 6 (eval interval 4 -> boundary 8).
    outs = _spawn_resil_workers(
        log_dir,
        [
            {faults.ENV_VAR: "preempt:step=6", "DTT_RESIL_EXPECT_STEPS": "8"},
            {"DTT_RESIL_EXPECT_STEPS": "8"},
        ],
    )
    for i in range(2):
        assert f"RESIL_WORKER_{i}_OK steps=8" in outs[i], outs[i]
    # Phase 2: clean relaunch resumes at 8 and completes 12.
    outs2 = _spawn_resil_workers(
        log_dir,
        [{"DTT_RESIL_EXPECT_STEPS": "12"}, {"DTT_RESIL_EXPECT_STEPS": "12"}],
    )
    for i in range(2):
        assert f"RESIL_WORKER_{i}_OK steps=12" in outs2[i], outs2[i]
        assert "restored checkpoint at step 8" in outs2[i], outs2[i]
