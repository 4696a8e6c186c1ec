"""Checkpoint/export tests (reference C14 parity: Saver ckpts, Supervisor
timed autosave + restore, frozen export → inference bundle)."""

import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from distributed_tensorflow_tpu.models.mnist_cnn import MnistCNN
from distributed_tensorflow_tpu.train import checkpoint as ckpt


@pytest.fixture
def params():
    model = MnistCNN(compute_dtype=jnp.float32)
    return model.init(jax.random.PRNGKey(0), jnp.zeros((1, 784)))["params"]


def _state(params):
    tx = optax.adam(1e-4)
    return {
        "params": params,
        "opt_state": tx.init(params),
        "global_step": jnp.asarray(17, jnp.int32),
    }


def test_save_restore_roundtrip(tmp_path, params):
    mngr = ckpt.CheckpointManager(str(tmp_path / "ck"), save_interval_secs=0)
    state = _state(params)
    mngr.save(17, state)
    assert mngr.latest_step() == 17
    step, restored = mngr.restore_latest(state)
    assert step == 17
    assert int(restored["global_step"]) == 17
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
        jax.device_get(state["params"]),
        restored["params"],
    )
    mngr.close()


def test_timed_autosave_gate(tmp_path, params):
    mngr = ckpt.CheckpointManager(str(tmp_path / "ck"), save_interval_secs=3600)
    state = _state(params)
    assert not mngr.maybe_save(1, state)  # interval not yet elapsed
    assert mngr.maybe_save(2, state, force=True)
    mngr._last_save = time.time() - 7200
    assert mngr.maybe_save(3, state)  # interval elapsed
    assert mngr.latest_step() == 3
    mngr.close()


def test_keep_n(tmp_path, params):
    mngr = ckpt.CheckpointManager(str(tmp_path / "ck"), save_interval_secs=0, max_to_keep=2)
    state = _state(params)
    for s in (1, 2, 3, 4):
        mngr.save(s, state)
    assert mngr.latest_step() == 4
    assert len(mngr._mngr.all_steps()) <= 2
    mngr.close()


def test_inference_bundle_roundtrip(tmp_path, params):
    path = str(tmp_path / "model.msgpack")
    labels_path = str(tmp_path / "labels.txt")
    ckpt.export_inference_bundle(
        path, params, labels=["cat", "dog"], labels_path=labels_path, metadata={"model": "M"}
    )
    restored, meta = ckpt.load_inference_bundle(path, template=params)
    assert meta["model"] == "M"
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
        jax.device_get(params),
        restored,
    )
    assert ckpt.load_labels(labels_path) == ["cat", "dog"]


def test_inference_bundle_splits_over_part_limit(tmp_path, params, monkeypatch):
    """No bundle file grows past BUNDLE_PART_BYTES (a machine may cap the
    size of one file): a larger blob lands in part files, loads back
    bit-identical, and a re-export leaves no stale part behind."""
    path = str(tmp_path / "model.msgpack")
    ckpt.export_inference_bundle(path, params, metadata={"model": "M"})
    whole = os.path.getsize(path)
    assert os.listdir(tmp_path) == ["model.msgpack"]

    monkeypatch.setattr(ckpt, "BUNDLE_PART_BYTES", whole // 3)
    ckpt.export_inference_bundle(path, params, metadata={"model": "M"})
    files = sorted(os.listdir(tmp_path))
    assert len(files) >= 4 and files[0] == "model.msgpack"
    assert all(os.path.getsize(tmp_path / f) <= whole // 3 for f in files)
    restored, meta = ckpt.load_inference_bundle(path, template=params)
    assert meta == {"format": "dtf_tpu.params.v1", "model": "M"}
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
        jax.device_get(params),
        restored,
    )

    monkeypatch.setattr(ckpt, "BUNDLE_PART_BYTES", whole)
    ckpt.export_inference_bundle(path, params, metadata={"model": "M"})
    assert os.listdir(tmp_path) == ["model.msgpack"]
    ckpt.load_inference_bundle(path, template=params)


def test_async_autosave_durable_after_next_access(tmp_path):
    """Timed autosaves are async (the loop is not stalled by the disk
    write); any subsequent latest_step/restore/save drains the in-flight
    write first, and forced (final) saves are synchronous."""
    from distributed_tensorflow_tpu.train.checkpoint import CheckpointManager

    mngr = CheckpointManager(str(tmp_path / "ck"), save_interval_secs=0.0)
    state = {"w": np.arange(8.0, dtype=np.float32)}
    assert mngr.maybe_save(1, state)  # async
    # Forced re-save of the SAME step while its async write may still be in
    # flight: the drain-before-guard ordering must make this a no-op, not a
    # StepAlreadyExistsError (the job-restart / final-save-at-timed-step
    # race).
    mngr.save(1, state, wait=True)
    # Reading through the manager must see the completed step-1 save.
    assert mngr.latest_step() == 1
    state2 = {"w": np.arange(8.0, dtype=np.float32) * 2}
    assert mngr.maybe_save(2, state2, force=True)  # waits
    step, restored = mngr.restore_latest(state)
    assert step == 2
    np.testing.assert_array_equal(restored["w"], state2["w"])
    mngr.close()


def test_async_snapshot_chunked_fetch_roundtrip_and_stall_accounting(tmp_path):
    """Async save with device leaves and a 1 MB chunk plan (several chunks):
    the on-device snapshot copy + chunked double-buffered fetch round-trips
    bit-exactly, and the manager accounts the main-thread stall."""
    mngr = ckpt.CheckpointManager(
        str(tmp_path / "ck"), save_interval_secs=0, snapshot_chunk_mb=1
    )
    state = {
        "a": jnp.arange(512 * 1024, dtype=jnp.float32).reshape(512, 1024),  # 2 MB
        "b": jnp.ones((256, 1024), jnp.float32) * 3,  # 1 MB
        "step": jnp.asarray(11, jnp.int32),
    }
    assert mngr.save(11, state)  # async: accepted without blocking
    mngr.wait_until_finished()
    assert mngr.latest_step() == 11
    assert mngr.stall_seconds > 0.0
    step, restored = mngr.restore_latest(state)
    assert step == 11
    np.testing.assert_array_equal(restored["a"], np.asarray(state["a"]))
    np.testing.assert_array_equal(restored["b"], np.asarray(state["b"]))
    mngr.close()


def test_single_process_reader_reassembles_sharded_checkpoint(tmp_path):
    """A multi-process (sharded-format) save must be readable by a plain
    single-process CheckpointManager — demo2/test.py restores the latest
    autosave of a distributed run without joining a process group. Shard
    files are crafted on disk exactly as two writer processes would leave
    them: per-process npz + manifest, chief-only full entries, replica-0
    index entries, and the chief's COMMIT marker."""
    import json as _json

    root = tmp_path / "ck"
    d = root / "7"
    d.mkdir(parents=True)
    full = np.arange(6, dtype=np.float32).reshape(2, 3)
    sharded = np.arange(8, dtype=np.float32).reshape(4, 2) * 10
    # "process 0": the full (replicated) leaf + the first half of the shard.
    np.savez(
        str(d / "shard_p0.npz"),
        a0=np.ascontiguousarray(full).reshape(-1).view(np.uint8),
        a1=np.ascontiguousarray(sharded[:2]).reshape(-1).view(np.uint8),
    )
    (d / "manifest_p0.json").write_text(_json.dumps({
        "format": "dtt.sharded.v1", "process": 0, "process_count": 2,
        "entries": [
            {"key": "a0", "path": "['params']['w']",
             "tokens": [{"k": "params"}, {"k": "w"}],
             "shape": [2, 3], "dtype": "float32", "index": None},
            {"key": "a1", "path": "['params']['emb']",
             "tokens": [{"k": "params"}, {"k": "emb"}],
             "shape": [2, 2], "dtype": "float32", "index": [[0, 2], [0, 2]]},
        ],
    }))
    # "process 1": the second half of the sharded leaf.
    np.savez(
        str(d / "shard_p1.npz"),
        a0=np.ascontiguousarray(sharded[2:]).reshape(-1).view(np.uint8),
    )
    (d / "manifest_p1.json").write_text(_json.dumps({
        "format": "dtt.sharded.v1", "process": 1, "process_count": 2,
        "entries": [
            {"key": "a0", "path": "['params']['emb']",
             "tokens": [{"k": "params"}, {"k": "emb"}],
             "shape": [2, 2], "dtype": "float32", "index": [[2, 4], [0, 2]]},
        ],
    }))
    (d / "COMMIT.json").write_text(_json.dumps({"step": 7, "process_count": 2}))

    mngr = ckpt.CheckpointManager(str(root), save_interval_secs=0)
    assert mngr.latest_step() == 7
    step, state = mngr.restore_latest_raw()
    assert step == 7
    np.testing.assert_array_equal(state["params"]["w"], full)
    np.testing.assert_array_equal(state["params"]["emb"], sharded)
    # Template-driven restore takes the same full/shard entries (all leaves
    # land as numpy in a single-process reader).
    template = {"params": {"w": np.zeros((2, 3), np.float32),
                           "emb": np.zeros((4, 2), np.float32)}}
    step, state = mngr.restore_latest(template)
    assert step == 7
    np.testing.assert_array_equal(state["params"]["emb"], sharded)
    mngr.close()
