"""Checkpointing + model export.

Replaces the reference's three formats (SURVEY §5.4):
  (a) explicit final ``tf.train.Saver`` ckpt (``demo1/train.py:144,165``)
      → Orbax save at the end of training;
  (b) ``Supervisor`` timed autosave every 600 s to ``logdir`` with
      auto-restore-on-restart (``demo2/train.py:166-176``)
      → :class:`CheckpointManager` with a wall-clock save gate,
      ``restore_latest``, and a zero-stall snapshot→write→finalize save
      pipeline (background device→host fetch, per-process sharded writes,
      deferred multi-process commit — DESIGN.md §9);
  (c) frozen-GraphDef + labels export
      (``retrain1/retrain.py:470-475``)
      → :func:`export_inference_bundle`: a msgpack params pytree + labels
      file. "Freezing" is meaningless under JAX — params are already data
      and the apply fn is retraced/jitted at load time.
"""

from __future__ import annotations

import glob
import json
import os
import queue
import shutil
import threading
import time
import zipfile
from typing import Any

import jax
import numpy as np
import orbax.checkpoint as ocp
from flax import serialization

from distributed_tensorflow_tpu import obs
from distributed_tensorflow_tpu.utils import faults
from distributed_tensorflow_tpu.utils.logging import get_logger
from distributed_tensorflow_tpu.utils.retry import retry_call

log = get_logger(__name__)

# Orbax I/O retry envelope: transient filesystem/NFS hiccups get a couple of
# quick retries; deterministic failures (corrupt step, template mismatch)
# raise OSError subclasses rarely and fall through to the walk-back loop.
_IO_ATTEMPTS = 3
_IO_BASE_DELAY = 0.1
_IO_MAX_DELAY = 2.0


def _cross_process_sharded(x) -> bool:
    """A leaf that no single process can fetch: sharded (not replicated)
    across a multi-process mesh. ``device_get`` on such arrays raises;
    Orbax saves/restores them natively (each process handles its shards)."""
    return (
        isinstance(x, jax.Array)
        and not x.is_fully_addressable
        and not x.is_fully_replicated
    )


def _savable(state: Any) -> Any:
    """numpy for fetchable leaves (replicated / single-process — the fast,
    simple case); cross-process-sharded jax.Arrays pass through for Orbax's
    distributed array handler. Only the synchronous (``ckpt_async=0``)
    single-process path still uses this — the async pipeline fetches through
    :class:`_SnapshotJob` units instead."""
    return jax.tree_util.tree_map(
        lambda x: x if _cross_process_sharded(x) else np.asarray(jax.device_get(x)),
        state,
    )


def _np_dtype(name: str) -> np.dtype:
    """np.dtype by name, including the ml_dtypes extension types (bfloat16
    et al.) that plain numpy only knows once ml_dtypes is imported."""
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes

        return np.dtype(getattr(ml_dtypes, name))


def _path_tokens(path) -> list[dict]:
    """JSON-serializable form of a tree_flatten_with_path key path: dict keys
    as {"k": name}, sequence/index keys as {"i": idx} — enough to rebuild a
    plain dict/list nesting for template-free restores."""
    toks: list[dict] = []
    for k in path:
        if hasattr(k, "key"):
            toks.append({"k": str(k.key)})
        elif hasattr(k, "idx"):
            toks.append({"i": int(k.idx)})
        elif hasattr(k, "name"):
            toks.append({"k": str(k.name)})
        else:
            toks.append({"k": str(k)})
    return toks


def _index_bounds(index, shape) -> list[list[int]]:
    """A shard's index (tuple of slices) as [[start, stop], ...] per dim."""
    out = []
    for sl, dim in zip(index, shape):
        start = 0 if sl.start is None else int(sl.start)
        stop = int(dim) if sl.stop is None else int(sl.stop)
        out.append([start, stop])
    return out


# ---------------------------------------------------------------------------
# Snapshot pipeline — zero-stall autosave.
#
# Three stages (DESIGN.md §9):
#   snapshot  — an on-device defensive copy of the state tree (fresh buffers,
#       so later DONATING train dispatches can never invalidate what the
#       background thread reads), then a chunked, double-buffered device→host
#       fetch on the snapshot worker thread (chunk i+1's transfer is started
#       before chunk i is materialized);
#   write     — single-process: the Orbax save (itself async). Multi-process:
#       each process writes ONLY the bytes it owns (replica-0 addressable
#       shards; replicated/host leaves are the chief's alone) into a
#       per-process npz + manifest under the step dir — NO collectives ever
#       run on this thread;
#   finalize  — multi-process durability is deferred to an explicit drain
#       point on the MAIN thread (the next eval boundary, or a forced save):
#       processes allgather their local write status and the chief then
#       writes the COMMIT marker. Restores ignore uncommitted steps. Keeping
#       every collective on the main thread is what makes async multi-process
#       saves deadlock-free against ``broadcast_one_to_all`` (the hazard that
#       previously forced multi-process saves fully synchronous).
# ---------------------------------------------------------------------------

_JOB_PENDING, _JOB_DONE, _JOB_FAILED, _JOB_CANCELLED = 0, 1, 2, 3


class _Unit:
    """One fetchable piece of a snapshot: a whole leaf, or one addressable
    shard of a cross-process-sharded leaf."""

    __slots__ = ("data", "host", "nbytes", "keystr", "tokens", "shape", "dtype", "index")

    def __init__(self, data, keystr, tokens, shape, dtype, index):
        self.data = data          # device array / shard data / numpy
        self.host: np.ndarray | None = None
        self.shape = tuple(int(d) for d in shape)
        self.dtype = str(dtype)
        self.nbytes = int(np.prod(self.shape or (1,))) * _np_dtype(self.dtype).itemsize
        self.keystr = keystr
        self.tokens = tokens
        self.index = index        # None = full leaf; else [[lo, hi], ...]


class _SnapshotJob:
    def __init__(self, step: int, units: list[_Unit], treedef, multi: bool):
        self.step = step
        self.units = units
        self.treedef = treedef    # single-process: rebuild the Orbax tree
        self.multi = multi
        self.done = threading.Event()
        self.status = _JOB_PENDING
        self.error: Exception | None = None
        self.cancelled = False
        self.writing = False      # set just before the write stage (veto point)
        self.warned = False       # skip-with-warning rate limit
        self.held = False         # test seam: park the job until released/vetoed


def _assemble_full(elist, load) -> np.ndarray:
    """Reassemble a full array from its covering replica-0 shard entries.
    Entries store BLOCK shapes; the global extent per dim is the max stop
    over the covering shards."""
    _, e0 = elist[0]
    global_shape = [
        max(e["index"][d][1] for _, e in elist) for d in range(len(e0["index"]))
    ]
    value = np.empty(global_shape, _np_dtype(e0["dtype"]))
    for p, e in elist:
        sl = tuple(slice(lo, hi) for lo, hi in e["index"])
        value[sl] = load(p, e)
    return value


class _ShardStore:
    """Per-process sharded checkpoint files + commit markers (the
    multi-process backend). Layout under ``directory/<step>/``:

      shard_p<K>.npz     process K's bytes (uint8-viewed leaf/shard blocks)
      manifest_p<K>.json what lives in K's npz (path, shape, dtype, index)
      COMMIT.json        written by the CHIEF at finalize — only committed
                         steps exist as far as restores are concerned

    Readable from any process count (a single-process tool can reassemble a
    multi-process save — ``demo2/test.py``'s restore-latest fallback)."""

    COMMIT = "COMMIT.json"

    def __init__(self, directory: str):
        self.directory = directory

    def step_dir(self, step: int) -> str:
        return os.path.join(self.directory, str(step))

    @staticmethod
    def is_sharded_dir(step_dir: str) -> bool:
        return bool(
            os.path.exists(os.path.join(step_dir, _ShardStore.COMMIT))
            or glob.glob(os.path.join(step_dir, "manifest_p*.json"))
        )

    @staticmethod
    def is_committed(step_dir: str) -> bool:
        return os.path.exists(os.path.join(step_dir, _ShardStore.COMMIT))

    def write_local(self, step: int, units: list[_Unit]) -> None:
        """Write THIS process's shard file + manifest (atomic renames, no
        coordination — the commit marker is finalize's job)."""
        p = jax.process_index()
        d = self.step_dir(step)
        os.makedirs(d, exist_ok=True)
        arrays: dict[str, np.ndarray] = {}
        entries = []
        for i, u in enumerate(units):
            key = f"a{i}"
            arrays[key] = np.ascontiguousarray(u.host).reshape(-1).view(np.uint8)
            entries.append(
                {
                    "key": key,
                    "path": u.keystr,
                    "tokens": u.tokens,
                    "shape": list(u.shape),
                    "dtype": u.dtype,
                    "index": u.index,
                }
            )
        shard_path = os.path.join(d, f"shard_p{p}.npz")
        tmp = shard_path + ".tmp"
        with open(tmp, "wb") as fh:
            np.savez(fh, **arrays)
        os.replace(tmp, shard_path)
        man = {
            "format": "dtt.sharded.v1",
            "process": p,
            "process_count": jax.process_count(),
            "entries": entries,
        }
        man_path = os.path.join(d, f"manifest_p{p}.json")
        tmp = man_path + ".tmp"
        with open(tmp, "w") as fh:
            json.dump(man, fh)
        os.replace(tmp, man_path)

    def commit(self, step: int) -> None:
        d = self.step_dir(step)
        tmp = os.path.join(d, self.COMMIT + ".tmp")
        with open(tmp, "w") as fh:
            json.dump({"step": step, "process_count": jax.process_count()}, fh)
        os.replace(tmp, os.path.join(d, self.COMMIT))

    def committed_steps(self) -> list[int]:
        out = []
        try:
            names = os.listdir(self.directory)
        except OSError:
            return out
        for n in names:
            d = os.path.join(self.directory, n)
            if n.isdigit() and self.is_sharded_dir(d) and self.is_committed(d):
                out.append(int(n))
        return sorted(out)

    def retain(self, max_to_keep: int) -> None:
        """Chief-only retention over committed sharded steps (Orbax-format
        steps keep Orbax's own retention)."""
        if max_to_keep is None or max_to_keep <= 0:
            return
        for step in self.committed_steps()[:-max_to_keep]:
            shutil.rmtree(self.step_dir(step), ignore_errors=True)

    def abandon(self, step: int) -> None:
        d = self.step_dir(step)
        if os.path.isdir(d) and not self.is_committed(d):
            shutil.rmtree(d, ignore_errors=True)

    # -- read ---------------------------------------------------------------

    def _load_entries(self, step: int):
        """Returns (entries_by_path, load_fn, closer): every manifest entry of
        the committed save, keyed by leaf keystr."""
        d = self.step_dir(step)
        with open(os.path.join(d, self.COMMIT)) as fh:
            commit = json.load(fh)
        nproc = int(commit["process_count"])
        by_path: dict[str, list] = {}
        for p in range(nproc):
            with open(os.path.join(d, f"manifest_p{p}.json")) as fh:
                man = json.load(fh)
            for e in man["entries"]:
                by_path.setdefault(e["path"], []).append((p, e))
        npz_cache: dict[int, Any] = {}

        def load(p: int, entry: dict) -> np.ndarray:
            npz = npz_cache.get(p)
            if npz is None:
                npz = npz_cache[p] = np.load(os.path.join(d, f"shard_p{p}.npz"))
            raw = npz[entry["key"]]
            return raw.view(_np_dtype(entry["dtype"])).reshape(entry["shape"])

        def close() -> None:
            for npz in npz_cache.values():
                npz.close()

        return by_path, load, close

    def read(self, step: int, template: Any | None):
        """Template-driven restore (cross-process-sharded template leaves come
        back as sharded jax.Arrays, everything else numpy), or template-free
        reassembly into plain dicts/lists when ``template`` is None."""
        by_path, load, close = self._load_entries(step)
        try:
            if template is None:
                return self._assemble_raw(by_path, load)

            def restore_leaf(path, leaf):
                ks = jax.tree_util.keystr(path)
                elist = by_path.get(ks)
                if not elist:
                    raise OSError(f"checkpoint step {step} is missing leaf {ks}")
                if _cross_process_sharded(leaf):
                    shape = tuple(leaf.shape)
                    sharding = leaf.sharding
                    idx_map = sharding.devices_indices_map(shape)
                    by_bounds = {
                        tuple(map(tuple, e["index"])): (p, e)
                        for p, e in elist
                        if e["index"] is not None
                    }
                    arrays = []
                    for dev in sharding.addressable_devices:
                        bounds = tuple(
                            map(tuple, _index_bounds(idx_map[dev], shape))
                        )
                        if bounds not in by_bounds:
                            raise OSError(
                                f"checkpoint step {step}: no shard covering "
                                f"{bounds} of {ks} (saved with a different "
                                "mesh/process layout?)"
                            )
                        p, e = by_bounds[bounds]
                        arrays.append(jax.device_put(load(p, e), dev))
                    return jax.make_array_from_single_device_arrays(
                        shape, sharding, arrays
                    )
                full = [pe for pe in elist if pe[1]["index"] is None]
                if full:
                    value = load(*full[0])
                else:
                    # A host/replicated template leaf reading a save whose
                    # leaf was cross-process sharded (e.g. a single-process
                    # tool restoring a distributed run): reassemble the full
                    # array from the covering replica-0 shards.
                    value = _assemble_full(elist, load)
                if hasattr(leaf, "shape") and tuple(np.shape(leaf)) != tuple(value.shape):
                    raise OSError(
                        f"checkpoint step {step}: shape mismatch for {ks}: "
                        f"saved {value.shape}, template {np.shape(leaf)}"
                    )
                return value

            return jax.tree_util.tree_map_with_path(restore_leaf, template)
        finally:
            close()

    def _assemble_raw(self, by_path, load):
        out: Any = {}
        for ks, elist in by_path.items():
            full = [pe for pe in elist if pe[1]["index"] is None]
            value = load(*full[0]) if full else _assemble_full(elist, load)
            node = out
            toks = elist[0][1]["tokens"]
            for i, t in enumerate(toks):
                last = i == len(toks) - 1
                if "k" in t:
                    key = t["k"]
                    if last:
                        node[key] = value
                    else:
                        node = node.setdefault(
                            key, [] if "i" in toks[i + 1] else {}
                        )
                else:
                    idx = t["i"]
                    while len(node) <= idx:
                        node.append(None)
                    if last:
                        node[idx] = value
                    else:
                        if node[idx] is None:
                            node[idx] = [] if "i" in toks[i + 1] else {}
                        node = node[idx]
        return out


# ---------------------------------------------------------------------------
# Public sharded-format surface (serve/deploy/ and tools).
#
# The deploy watcher consumes checkpoints through these three functions
# instead of re-parsing ``manifest_p*.json`` privately: the manifest walk,
# the commit-marker rule (only COMMIT.json makes a step visible) and the
# shard reassembly live in ONE place — :class:`_ShardStore` — no matter
# whether the reader is a restore, a watcher, or a CLI.
# ---------------------------------------------------------------------------


def list_committed_steps(directory: str) -> list:
    """Committed sharded-format steps under ``directory``, ascending.

    A step counts only once its ``COMMIT.json`` marker exists (written by
    the chief via atomic rename at finalize) — torn or uncommitted step
    dirs (process killed mid-write, finalize never ran) are invisible,
    exactly like restores treat them. Orbax-format steps are NOT listed:
    this is the watch surface for the per-process shard+manifest format.
    """
    return _ShardStore(directory).committed_steps()


def read_step(directory: str, step: int, template: Any | None = None):
    """Read one COMMITTED sharded-format step.

    ``template=None`` reassembles plain dicts/lists with numpy leaves
    (cross-process-sharded leaves are stitched back to full arrays);
    with a template, leaves restore against it like ``restore_latest``.
    Raises ``OSError`` for an uncommitted/missing step or a committed dir
    whose shard/manifest files are missing or torn (the caller — e.g. the
    deploy watcher — skips and walks on, like restores walk back).
    """
    store = _ShardStore(directory)
    d = store.step_dir(step)
    if not store.is_committed(d):
        raise OSError(
            f"checkpoint step {step} in {directory} is not committed "
            f"(no {_ShardStore.COMMIT})"
        )
    try:
        return store.read(step, template)
    except (OSError, ValueError, KeyError, json.JSONDecodeError,
            zipfile.BadZipFile) as e:
        raise OSError(
            f"checkpoint step {step} in {directory} is committed but "
            f"unreadable: {type(e).__name__}: {e}"
        ) from e


def write_committed_step(directory: str, step: int, tree: Any) -> str:
    """Publish ``tree`` as ONE committed sharded-format step from this
    process (shard_p<K>.npz + manifest_p<K>.json + COMMIT.json, all via
    atomic renames). This is the single-process producer half of the
    watch surface: trainers publish a weight tree for serving without a
    multi-process finalize (whose commit is collective), and tests/bench
    drop checkpoints the deploy watcher can adopt. Returns the step dir.

    Host-fetchable leaves only (replicated or single-process); a
    cross-process-sharded leaf cannot be published from one process.
    """
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    units = []
    for path, leaf in flat:
        arr = np.asarray(jax.device_get(leaf))
        unit = _Unit(
            None, jax.tree_util.keystr(path), _path_tokens(path),
            arr.shape, arr.dtype, None,
        )
        unit.host = arr
        units.append(unit)
    store = _ShardStore(directory)
    store.write_local(step, units)
    faults.maybe_fail("ckpt_publish", f"step {step}")
    store.commit(step)
    return store.step_dir(step)


class CheckpointManager:
    """Supervisor-parity manager (timed autosave, keep-N, restore-latest)
    with a zero-stall save pipeline: timed autosaves cost the training
    thread only an on-device copy dispatch + job enqueue (``stall_seconds``
    measures exactly that blocked time); the device→host fetch and the disk
    write run on a background snapshot thread. Single-process saves land in
    Orbax format; multi-process saves are per-process sharded files whose
    collective finalize is deferred to :meth:`finalize_pending` (called by
    ``coordinated_maybe_save`` at eval boundaries). Forced saves
    (final/emergency) remain fully synchronous and durable on return."""

    def __init__(
        self,
        directory: str,
        save_interval_secs: float = 600.0,
        max_to_keep: int = 5,
        async_snapshot: bool = True,
        snapshot_chunk_mb: int = 64,
    ):
        self.directory = os.path.abspath(directory)
        os.makedirs(self.directory, exist_ok=True)
        self._mngr = ocp.CheckpointManager(
            self.directory,
            options=ocp.CheckpointManagerOptions(max_to_keep=max_to_keep, create=True),
        )
        self.save_interval_secs = save_interval_secs
        self.max_to_keep = max_to_keep
        self.async_snapshot = async_snapshot
        self.snapshot_chunk_mb = max(1, int(snapshot_chunk_mb))
        self._last_save = time.time()
        self.stall_seconds = 0.0  # main-thread time blocked inside save paths
        self._store = _ShardStore(self.directory)
        self._lock = threading.Lock()
        self._worker: threading.Thread | None = None
        self._queue: "queue.Queue[_SnapshotJob | None]" = queue.Queue()
        self._jobs: list[_SnapshotJob] = []  # issued, not yet retired/finalized
        self._issued: set[int] = set()
        self._hold_next_snapshot = False  # test seam: park the next job

    # -- gate ----------------------------------------------------------------

    def should_save(self, force: bool = False) -> bool:
        """The timed-autosave gate, side-effect free (multi-process callers
        broadcast the chief's answer so every process enters the save
        together)."""
        return force or time.time() - self._last_save >= self.save_interval_secs

    def mark_saved(self) -> None:
        self._last_save = time.time()

    def maybe_save(self, step: int, state: Any, force: bool = False) -> bool:
        """Save if ``save_interval_secs`` elapsed since the last save (the
        Supervisor's timed-autosave behavior) or if forced (final save —
        which also WAITS, so the artifact exists before the process exits).
        A timed gate firing while the previous save is still in flight skips
        with a warning instead of blocking the training thread."""
        if not self.should_save(force):
            return False
        if self.save(step, state, wait=force, skip_if_busy=not force):
            self.mark_saved()
            return True
        return False

    # -- save ----------------------------------------------------------------

    def save(
        self,
        step: int,
        state: Any,
        wait: bool = False,
        skip_if_busy: bool = False,
    ) -> bool:
        """Issue a save of ``state`` at ``step``. Returns True when the save
        is satisfied (issued, or the step already exists on disk); False only
        on the ``skip_if_busy`` path — the timed-gate caller's non-blocking
        skip while the previous save is still in flight.

        Async (default): the training thread pays an on-device snapshot copy
        dispatch + enqueue; fetch/write happen on the snapshot thread.
        ``wait=True`` (final/emergency saves) drains everything — the
        artifact is durable (and in multi-process runs committed) on return.
        """
        t0 = time.perf_counter()
        try:
            with obs.span("checkpoint_save", step=int(step), wait=bool(wait)):
                multi = jax.process_count() > 1
                busy = self._busy()
                if busy and skip_if_busy:
                    self._warn_busy(step)
                    obs.trace_event("ckpt_skip_busy", step=int(step))
                    return False
                # Duplicate-step guard WITHOUT draining (the old
                # unconditional wait_until_finished here head-of-line-blocked
                # the caller for the whole previous write even when this
                # guard made the call a no-op): hit when a finished job
                # restarts (restore to step N, zero-iteration loop, forced
                # re-save of N) or when the timed gate fires on the very last
                # step before the final save.
                if step in self._issued or step in self._all_steps():
                    if wait:
                        self._drain_jobs()
                        if multi:
                            self.finalize_pending(block=True)
                        else:
                            self._mngr.wait_until_finished()
                    return True
                if busy:
                    # Direct (non-gate) callers keep strict ordering: drain
                    # the previous save before issuing the next.
                    self._drain_jobs()
                    if multi:
                        self.finalize_pending(block=True)
                self._issued.add(step)
                if not multi and not self.async_snapshot and not wait:
                    # ckpt_async=0: the pre-pipeline behavior — synchronous
                    # device→host fetch on this thread, Orbax's own
                    # background write overlapping training.
                    self._orbax_write(step, _savable(state))
                    return True
                job = self._make_job(step, state, multi)
                self._enqueue(job)
                if wait or not self.async_snapshot:
                    self._drain_jobs()
                    if job.error is not None:
                        raise job.error
                    if multi:
                        self.finalize_pending(block=True)
                    else:
                        self._mngr.wait_until_finished()
                return True
        finally:
            self.stall_seconds += time.perf_counter() - t0

    def _make_job(self, step: int, state: Any, multi: bool) -> _SnapshotJob:
        """Snapshot stage, main-thread half: an on-device defensive copy of
        every device leaf (fresh buffers — a later dispatch that DONATES the
        originals cannot invalidate them), then the fetch plan: which pieces
        THIS process owns. All of it is asynchronous dispatch + bookkeeping;
        no device→host bytes move here."""
        from distributed_tensorflow_tpu.parallel import data_parallel as dp

        flat, treedef = jax.tree_util.tree_flatten_with_path(state)
        leaves = [leaf for _, leaf in flat]
        dev_idx = [i for i, x in enumerate(leaves) if isinstance(x, jax.Array)]
        if dev_idx:
            copies = dp.device_copy([leaves[i] for i in dev_idx])
            for i, c in zip(dev_idx, copies):
                leaves[i] = c
        chief = (not multi) or jax.process_index() == 0
        units: list[_Unit] = []
        for (path, _), leaf in zip(flat, leaves):
            ks = jax.tree_util.keystr(path)
            toks = _path_tokens(path)
            if _cross_process_sharded(leaf):
                global_shape = tuple(leaf.shape)
                for s in leaf.addressable_shards:
                    if s.replica_id != 0:
                        continue  # exactly one process writes each shard
                    # Unit shape = the BLOCK's shape (that is what gets
                    # written); index records its place in the global array.
                    units.append(
                        _Unit(
                            s.data, ks, toks, tuple(s.data.shape), leaf.dtype,
                            _index_bounds(s.index, global_shape),
                        )
                    )
            elif chief:
                # Replicated / host leaves: the chief alone writes them —
                # non-chief processes move zero bytes for these.
                data = leaf if isinstance(leaf, jax.Array) else np.array(leaf, copy=True)
                units.append(
                    _Unit(data, ks, toks, np.shape(data), np.asarray(data).dtype
                          if not isinstance(data, jax.Array) else data.dtype, None)
                )
        return _SnapshotJob(step, units, treedef, multi)

    def _enqueue(self, job: _SnapshotJob) -> None:
        with self._lock:
            if self._worker is None:
                self._worker = threading.Thread(
                    target=self._worker_loop, name="ckpt-snapshot", daemon=True
                )
                self._worker.start()
            if self._hold_next_snapshot:
                job.held = True
                self._hold_next_snapshot = False
            self._jobs.append(job)
        self._queue.put(job)

    # -- snapshot worker -----------------------------------------------------

    def _worker_loop(self) -> None:
        while True:
            job = self._queue.get()
            if job is None:
                return
            try:
                self._run_job(job)
            except Exception as e:  # noqa: BLE001 — surfaced via job.error
                job.error = e
                job.status = _JOB_FAILED
                log.error(
                    "background checkpoint save of step %d failed: %s: %s",
                    job.step, type(e).__name__, e,
                )
            finally:
                job.done.set()

    def _run_job(self, job: _SnapshotJob) -> None:
        deadline = time.monotonic() + 60.0
        while job.held and not job.cancelled and time.monotonic() < deadline:
            time.sleep(0.005)
        if job.cancelled:
            job.status = _JOB_CANCELLED
            log.warning("checkpoint snapshot of step %d cancelled (vetoed)", job.step)
            return
        if not self._fetch(job):
            job.status = _JOB_CANCELLED
            log.warning(
                "checkpoint snapshot of step %d cancelled mid-fetch (vetoed)",
                job.step,
            )
            return
        job.writing = True

        def _write() -> None:
            # Fault site ``ckpt_save`` fires BEFORE the write — models a
            # transient I/O error the backoff retry recovers from, now on
            # the background path.
            faults.maybe_fail("ckpt_save", f"step {job.step}")
            if job.multi:
                self._store.write_local(job.step, job.units)
            else:
                # Serialize against Orbax's own async machinery: this wait is
                # on the WORKER thread, so the training thread never pays it.
                self._mngr.wait_until_finished()
                host_leaves = [u.host for u in job.units]
                self._mngr.save(
                    job.step,
                    args=ocp.args.StandardSave(job.treedef.unflatten(host_leaves)),
                )

        retry_call(
            _write,
            attempts=_IO_ATTEMPTS,
            base_delay=_IO_BASE_DELAY,
            max_delay=_IO_MAX_DELAY,
            description=f"checkpoint save step {job.step}",
        )
        job.status = _JOB_DONE

    def _fetch(self, job: _SnapshotJob) -> bool:
        """Chunked, double-buffered device→host copy: units are grouped into
        ~``snapshot_chunk_mb`` chunks; chunk i+1's async transfer is started
        before chunk i is materialized, so transfer overlaps materialization.
        Returns False when the job is vetoed between chunks."""
        chunk_bytes = self.snapshot_chunk_mb * (1 << 20)
        groups: list[list[_Unit]] = []
        cur: list[_Unit] = []
        cur_bytes = 0
        for u in job.units:
            if cur and cur_bytes + u.nbytes > chunk_bytes:
                groups.append(cur)
                cur, cur_bytes = [], 0
            cur.append(u)
            cur_bytes += u.nbytes
        if cur:
            groups.append(cur)

        def start(group: list[_Unit]) -> None:
            for u in group:
                if isinstance(u.data, jax.Array):
                    try:
                        u.data.copy_to_host_async()
                    except Exception:  # noqa: BLE001 — best-effort prefetch
                        pass

        if groups:
            start(groups[0])
        for gi, group in enumerate(groups):
            if job.cancelled:
                return False
            if gi + 1 < len(groups):
                start(groups[gi + 1])
            for u in group:
                u.host = np.asarray(u.data)  # waits on the in-flight transfer
                u.data = None  # release the device buffer reference early
        return True

    # -- bookkeeping ---------------------------------------------------------

    def _busy(self) -> bool:
        with self._lock:
            if jax.process_count() > 1:
                # Pending = unfinalized — identical across processes (save
                # decisions are broadcast), so the skip decision is symmetric.
                return bool(self._jobs)
            self._jobs = [j for j in self._jobs if not j.done.is_set()]
            return bool(self._jobs)

    def _warn_busy(self, step: int) -> None:
        with self._lock:
            job = self._jobs[0] if self._jobs else None
        if job is not None and not job.warned:
            job.warned = True
            log.warning(
                "skipping timed checkpoint of step %d: save of step %d still "
                "in flight (will retry at the next gate)", step, job.step,
            )

    def _drain_jobs(self) -> None:
        """Join every issued snapshot job (worker-side work only — NO
        collectives, safe from any caller/thread)."""
        for j in list(self._jobs):
            j.done.wait()
        if jax.process_count() == 1:
            with self._lock:
                self._jobs = [j for j in self._jobs if not j.done.is_set()]

    def veto_pending(self) -> int:
        """Cancel snapshot jobs that have not reached the write stage — the
        bad-eval-window suppression and rollback paths use this so a queued
        snapshot from inside a diverging window never advances the
        checkpoint chain. Jobs already writing are left alone (their data was
        captured at enqueue time). Returns the number cancelled."""
        n = 0
        with self._lock:
            for j in self._jobs:
                if not j.done.is_set() and not j.writing:
                    j.cancelled = True
                    n += 1
        if n:
            log.warning("vetoed %d queued checkpoint snapshot(s)", n)
            obs.trace_event("ckpt_veto", cancelled=n)
        return n

    def finalize_pending(self, block: bool = False) -> None:
        """Deferred multi-process finalize — the ONLY collective piece of the
        async save, and it runs on the caller's (main) thread at explicit
        drain points: eval boundaries, forced saves, restores. Processes
        allgather their local write status; when all are done the chief
        writes the COMMIT marker (then a named barrier makes the commit
        visible to everyone before any process may act on it). A failed or
        vetoed shard write on ANY process abandons the step everywhere.
        Single-process: no-op."""
        if jax.process_count() == 1:
            return
        from jax.experimental import multihost_utils

        while True:
            with self._lock:
                job = self._jobs[0] if self._jobs else None
            if job is None:
                return
            if block:
                job.done.wait()
            status = job.status if job.done.is_set() else _JOB_PENDING
            code = {_JOB_PENDING: 0, _JOB_DONE: 1}.get(status, 2)
            gathered = multihost_utils.process_allgather(
                np.asarray([code], np.int32)
            )
            codes = set(int(x) for x in np.ravel(gathered))
            if 0 in codes:
                if not block:
                    return  # not everyone is done — try again next boundary
                time.sleep(0.2)
                continue
            with self._lock:
                self._jobs.remove(job)
            if 2 in codes:
                log.warning(
                    "abandoning uncommitted checkpoint step %d (a process "
                    "failed or vetoed its shard write)", job.step,
                )
                if jax.process_index() == 0:
                    self._store.abandon(job.step)
                self._issued.discard(job.step)
            else:
                if jax.process_index() == 0:
                    self._store.commit(job.step)
                    self._store.retain(self.max_to_keep)
                multihost_utils.sync_global_devices(f"dtt_ckpt_commit_{job.step}")
                log.info("finalized checkpoint step %d (deferred commit)", job.step)

    def _orbax_write(self, step: int, data: Any) -> None:
        def _write() -> None:
            faults.maybe_fail("ckpt_save", f"step {step}")
            self._mngr.save(step, args=ocp.args.StandardSave(data))

        retry_call(
            _write,
            attempts=_IO_ATTEMPTS,
            base_delay=_IO_BASE_DELAY,
            max_delay=_IO_MAX_DELAY,
            description=f"checkpoint save step {step}",
        )

    # -- introspection -------------------------------------------------------

    def wait_until_finished(self) -> None:
        """Drain the snapshot worker and Orbax's background write. NO
        collectives — committing multi-process saves is
        :meth:`finalize_pending`'s job."""
        self._drain_jobs()
        self._mngr.wait_until_finished()

    def _all_steps(self) -> list[int]:
        """Steps visible on disk: Orbax-format step dirs plus COMMITTED
        sharded-format step dirs (an uncommitted sharded dir is an in-flight
        or abandoned save, never a restorable step)."""
        steps = set()
        try:
            names = os.listdir(self.directory)
        except OSError:
            return []
        for n in names:
            if not n.isdigit():
                continue
            d = os.path.join(self.directory, n)
            if not os.path.isdir(d):
                continue
            if _ShardStore.is_sharded_dir(d) and not _ShardStore.is_committed(d):
                continue
            steps.add(int(n))
        return sorted(steps)

    def all_steps(self) -> list[int]:
        self.wait_until_finished()
        return self._all_steps()

    def latest_step(self) -> int | None:
        self.wait_until_finished()  # include any in-flight async save
        steps = self._all_steps()
        return steps[-1] if steps else None

    # -- restore -------------------------------------------------------------

    def _read_step(self, step: int, template: Any | None, raw: bool = False):
        """Format-probing per-step reader: sharded-format steps go through
        the shard store (works from any process count); Orbax-format steps
        through Orbax."""
        d = os.path.join(self.directory, str(step))
        if _ShardStore.is_sharded_dir(d):
            return self._store.read(step, None if raw else template)
        if raw:
            # Explicit StandardRestore: a FRESH manager (demo2/test.py's
            # restore-latest fallback) has no handler registry from a prior
            # save in this process, and a bare restore() then raises instead
            # of inferring — with args it reads the tree as numpy directly.
            return self._mngr.restore(step, args=ocp.args.StandardRestore())
        abstract = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)
            if _cross_process_sharded(x)
            else np.asarray(jax.device_get(x)),
            template,
        )
        return self._mngr.restore(step, args=ocp.args.StandardRestore(abstract))

    def _walk_back_restore(self, restore_fn):
        """Restore the newest READABLE step, newest→oldest: a truncated or
        corrupt latest checkpoint (process killed mid-write, bad disk) is
        skipped with a warning instead of blocking every restart while older
        good checkpoints sit on disk. Returns (step, state) or None (no
        steps, or none readable — init fresh beats crash-looping). Drains
        the snapshot worker first, and in multi-process runs finalizes any
        pending save (all processes restore at the same program point, so
        the collective is symmetric — rollback's drain-or-finalize)."""
        self.wait_until_finished()
        self.finalize_pending(block=True)
        steps = sorted(self._all_steps(), reverse=True)
        skipped: list[int] = []
        for step in steps:
            def _read(step=step):
                faults.maybe_fail("ckpt_restore", f"step {step}")
                return restore_fn(step)

            try:
                state = retry_call(
                    _read,
                    attempts=2,
                    base_delay=_IO_BASE_DELAY,
                    max_delay=_IO_MAX_DELAY,
                    description=f"checkpoint restore step {step}",
                )
            except Exception as e:
                log.warning(
                    "checkpoint step %d unreadable (%s: %s) — walking back",
                    step, type(e).__name__, e,
                )
                skipped.append(step)
                continue
            if skipped:
                log.warning(
                    "restored step %d after skipping corrupt/partial "
                    "checkpoint step(s) %s", step, skipped,
                )
            return step, state
        if skipped:
            log.error("no readable checkpoint (skipped %s) — starting fresh", skipped)
        return None

    def restore_latest_raw(self):
        """Restore the newest readable ckpt without a structure template
        (numpy leaves, dict/list nesting); returns (step, state) or None."""
        return self._walk_back_restore(
            lambda step: self._read_step(step, None, raw=True)
        )

    def restore_latest(self, template: Any):
        """Returns (step, state) restored from the newest readable ckpt, or
        None — mirrors Supervisor init-or-restore (``demo2/train.py:176``),
        plus the corrupt-checkpoint walk-back (see
        :meth:`_walk_back_restore`). Cross-process-sharded template leaves
        restore as sharded jax.Arrays (each process reads its own shards);
        everything else as numpy."""
        return self._walk_back_restore(lambda step: self._read_step(step, template))

    def close(self) -> None:
        self._drain_jobs()
        with self._lock:
            worker, self._worker = self._worker, None
        if worker is not None:
            self._queue.put(None)
            worker.join(timeout=30)
        self._mngr.close()


def restore_replicated(mngr: CheckpointManager, template: Any, mesh):
    """Restore the newest checkpoint and place it mesh-replicated, leaf
    dtypes taken from ``template`` (the live train state). Returns
    (step, state) or None. Shared by the MNIST and retrain trainers."""
    import jax.numpy as jnp

    from distributed_tensorflow_tpu.parallel import data_parallel as dp

    restored = mngr.restore_latest(template)
    if restored is None:
        return None
    step, state = restored
    placed = jax.tree_util.tree_map(
        lambda a, b: dp.replicate(jnp.asarray(b, a.dtype), mesh)
        if hasattr(a, "dtype")
        else b,
        template,
        state,
    )
    return step, placed


def coordinated_maybe_save(
    mngr: CheckpointManager,
    step: int,
    state: Any,
    is_chief: bool,
    force: bool = False,
    at_boundary: bool = True,
) -> bool:
    """Timed autosave, multi-process safe — the one save gate both trainers
    use. Saves are group-wide when ``jax.process_count() > 1`` (each process
    writes its own shards, and the chief's timed-gate decision is broadcast
    at eval boundaries so every process issues the save together), but the
    save itself is ASYNC: the per-process shard writes run on background
    threads with zero collectives, and the collective finalize is DEFERRED
    to this function's next boundary call (``finalize_pending`` — main
    thread, so it can never deadlock against the gate broadcast the way a
    background finalize barrier did). Forced saves (final/emergency) stay
    synchronous and committed on return. Single process keeps exact
    Supervisor semantics (chief-only, per-call gate)."""
    if jax.process_count() == 1:
        return mngr.maybe_save(step, state, force=force) if is_chief else False
    if not (at_boundary or force):
        return False
    # Deferred-finalize drain point: commit (or abandon) any async save whose
    # shard writes have finished, BEFORE possibly issuing the next one.
    mngr.finalize_pending(block=force)
    from jax.experimental import multihost_utils

    want = mngr.should_save(force)
    if not bool(multihost_utils.broadcast_one_to_all(np.asarray(want))):
        return False
    # skip_if_busy is symmetric across processes: "busy" means an
    # unfinalized pending save, and the pending set is identical everywhere
    # (save decisions are broadcast), so either every process saves or every
    # process skips. wait=force: forced saves drain + finalize inline.
    if mngr.save(step, state, wait=force, skip_if_busy=not force):
        mngr.mark_saved()
        return True
    return False


# ---------------------------------------------------------------------------
# Inference bundle (frozen-graph export parity).
# ---------------------------------------------------------------------------


# No bundle file grows past this. The 403M flagship's f32 state-dict is
# 1.6 GB, and a machine may cap the size of one file (RLIMIT_FSIZE; the chip
# check's did — EFBIG at export, PR 21): a larger blob goes into numbered
# part files next to ``path``.
BUNDLE_PART_BYTES = 16 << 20


def _bundle_part_paths(path: str, n: int) -> list[str]:
    return [f"{path}.part-{i:05d}-of-{n:05d}" for i in range(n)]


def export_inference_bundle(
    path: str,
    params: Any,
    labels: list[str] | None = None,
    labels_path: str | None = None,
    metadata: dict | None = None,
) -> None:
    """Write params as a msgpack state-dict (+ optional labels txt, one class
    per line — ``retrain1/retrain.py:474-475`` parity) and a small JSON header.

    A blob over ``BUNDLE_PART_BYTES`` is cut into ``<path>.part-i-of-n``
    files and ``path`` keeps only the header (``"parts": n``); it is written
    last, so a reader that finds it finds every part."""
    os.makedirs(os.path.dirname(os.path.abspath(path)) or ".", exist_ok=True)
    state = serialization.to_state_dict(jax.device_get(params))
    blob = memoryview(serialization.msgpack_serialize(state))
    meta = {"format": "dtf_tpu.params.v1", **(metadata or {})}
    for stale in glob.glob(glob.escape(path) + ".part-*"):
        os.remove(stale)
    if len(blob) > BUNDLE_PART_BYTES:
        n = -(-len(blob) // BUNDLE_PART_BYTES)
        meta["parts"] = n
        for i, part in enumerate(_bundle_part_paths(path, n)):
            with open(part, "wb") as fh:
                fh.write(blob[i * BUNDLE_PART_BYTES:(i + 1) * BUNDLE_PART_BYTES])
        blob = b""
    header = json.dumps(meta).encode()
    with open(path, "wb") as fh:
        fh.write(len(header).to_bytes(8, "little"))
        fh.write(header)
        fh.write(blob)
    if labels is not None and labels_path is not None:
        with open(labels_path, "w") as fh:
            fh.write("\n".join(labels) + "\n")


def load_inference_bundle(path: str, template: Any | None = None):
    """Returns (params_state_dict_or_restored_pytree, metadata)."""
    with open(path, "rb") as fh:
        hlen = int.from_bytes(fh.read(8), "little")
        metadata = json.loads(fh.read(hlen).decode())
        blob = fh.read()
    parts = metadata.pop("parts", 0)
    if parts:
        chunks = []
        for part in _bundle_part_paths(path, parts):
            with open(part, "rb") as fh:
                chunks.append(fh.read())
        blob = b"".join(chunks)
        del chunks
    state = serialization.msgpack_restore(blob)
    if template is not None:
        state = serialization.from_state_dict(template, state)
    return state, metadata


def load_lm_bundle(path: str, fallback_shapes: dict | None = None):
    """Restore a TransformerLM bundle: (cfg, params, metadata).

    One loader for every LM CLI (generate/eval): prefers the config embedded
    in the bundle metadata, falls back to ``fallback_shapes`` (CLI flags) for
    pre-metadata bundles; unstacks pp bundles; rejects tp/ep bundles (their
    param factorizations — separate q/k/v, expert-stacked MLPs — don't load
    into the plain decoder). Raises ValueError on tp/ep.
    """
    import jax
    import jax.numpy as jnp

    from distributed_tensorflow_tpu.models.transformer import (
        TransformerConfig,
        TransformerLM,
        default_compute_dtype,
    )

    state, meta = load_inference_bundle(path)
    if meta.get("parallelism") in ("tp", "ep", "3d", "sp_tp"):
        raise ValueError(
            f"{meta['parallelism']} bundles use a different param "
            "factorization (separate q/k/v for tp/3d/sp_tp, expert-stacked "
            "MoE MLPs for ep) that the plain decoder cannot load — retrain "
            "with dp/fsdp/sp/pp"
        )
    if "stages" in state:
        from distributed_tensorflow_tpu.parallel.pipeline_parallel import (
            unstack_stage_params,
        )

        state = unstack_stage_params(state)
    fb = fallback_shapes or {}
    shape_meta = meta.get("config") or {}

    def dim(name, default):
        return int(shape_meta.get(name, fb.get(name, default)))

    cfg = TransformerConfig(
        vocab_size=dim("vocab_size", 256),
        d_model=dim("d_model", 128),
        num_heads=dim("num_heads", 4),
        # 0/absent = MHA (pre-GQA bundles carry no num_kv_heads key).
        num_kv_heads=dim("num_kv_heads", 0) or None,
        attention_window=dim("attention_window", 0) or None,
        # 1/absent = biased Dense layers (pre-r5 bundles carry no use_bias
        # key and were always trained with biases on the CLI path).
        use_bias=bool(dim("use_bias", 1)),
        # 0/absent = learned position table (pre-RoPE bundles). theta is a
        # FLOAT (dim() would truncate it) — a non-default rotation base must
        # survive the round trip or inference silently rotates q/k by the
        # wrong angles.
        position="rope" if dim("rope", 0) else "learned",
        rope_theta=float(shape_meta.get("rope_theta", fb.get("rope_theta", 10000.0))),
        num_layers=dim("num_layers", 4),
        d_ff=dim("d_ff", 512),
        max_seq_len=dim("max_seq_len", 128),
        # Quantized bundles (tools/quantize_lm.py): the mode must ride the
        # metadata so the init template below grows the matching
        # kernel_q/scale leaf structure — from_state_dict restores by
        # structure, and int leaves cannot load into a float-kernel tree.
        weight_dtype=(shape_meta.get("weight_dtype")
                      or fb.get("weight_dtype") or None),
        quant_group_size=dim("quant_group_size", 0),
        # KV ACTIVATION format (orthogonal to weight_dtype): a bundle
        # exported with tools/quantize_lm.py --kv_dtype int8 serves
        # quantize-on-write int8 KV pages by default; --kv_dtype/
        # --kv_cache_dtype at serve time still override.
        kv_cache_dtype=(shape_meta.get("kv_cache_dtype")
                        or fb.get("kv_cache_dtype") or None),
        compute_dtype=default_compute_dtype(),
    )
    template = TransformerLM(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    params = serialization.from_state_dict(template, state)
    return cfg, params, meta


def load_vit_bundle(path: str):
    """Restore a ViT classifier bundle from ``tools/train_image_classifier``:
    (cfg, params, metadata). Shape config, class labels, and the TRAINING
    compute dtype all come from the embedded metadata (so a CPU-trained f32
    bundle classifies in f32 even on a TPU host, and vice versa)."""
    import jax
    import jax.numpy as jnp

    from distributed_tensorflow_tpu.models.vit import ViT, ViTConfig

    state, meta = load_inference_bundle(path)
    shape_meta = meta.get("config")
    if not shape_meta or not meta.get("labels"):
        raise ValueError(
            f"{path} lacks embedded config/labels — train it with "
            "tools/train_image_classifier.py"
        )
    dtype_name = meta.get("compute_dtype", "float32")
    cfg = ViTConfig(
        **{k: int(v) for k, v in shape_meta.items()},
        compute_dtype=jnp.bfloat16 if dtype_name == "bfloat16" else jnp.float32,
    )
    template = ViT(cfg).init(
        jax.random.PRNGKey(0),
        jnp.zeros((1, cfg.image_size, cfg.image_size, cfg.channels), jnp.float32),
    )["params"]
    params = serialization.from_state_dict(template, state)
    return cfg, params, meta


def load_labels(path: str) -> list[str]:
    with open(path) as fh:
        return [ln.rstrip("\n") for ln in fh if ln.strip()]


# ---------------------------------------------------------------------------
# Frozen StableHLO export — the closest TPU-native analog of the reference's
# ``graph_util.convert_variables_to_constants`` (`retrain1/retrain.py:470-473`):
# one self-contained compiled-program artifact with the weights baked in as
# constants, loadable and runnable without the model's Python code.
# ---------------------------------------------------------------------------


def export_frozen_stablehlo(
    path: str,
    fn,
    example_args: tuple,
    metadata: dict | None = None,
    platforms: tuple[str, ...] = ("cpu", "tpu"),
    polymorphic_batch: bool = True,
) -> None:
    """Serialize ``jit(fn)`` (params already closed over / baked in) traced at
    ``example_args``'s shapes to a portable StableHLO artifact via
    ``jax.export``. Multi-platform by default so an artifact exported on TPU
    still runs on CPU (and vice versa). With ``polymorphic_batch`` the leading
    axis of every non-scalar arg becomes one shared symbolic dim, so the
    loaded program accepts any batch size (the frozen .pb took any batch too)."""
    from jax import export as jax_export

    batch_dim = jax_export.symbolic_shape("b")[0] if polymorphic_batch else None

    def spec(a):
        shape = np.shape(a)
        if batch_dim is not None and len(shape) >= 1:
            shape = (batch_dim,) + tuple(shape[1:])
        return jax.ShapeDtypeStruct(shape, np.asarray(a).dtype)

    specs = jax.tree_util.tree_map(spec, example_args)
    exported = jax_export.export(jax.jit(fn), platforms=list(platforms))(*specs)
    blob = exported.serialize()
    header = json.dumps(
        {"format": "dtf_tpu.stablehlo.v1", "platforms": list(platforms), **(metadata or {})}
    ).encode()
    os.makedirs(os.path.dirname(os.path.abspath(path)) or ".", exist_ok=True)
    with open(path, "wb") as fh:
        fh.write(len(header).to_bytes(8, "little"))
        fh.write(header)
        fh.write(bytes(blob))


def export_frozen_classifier(
    path: str,
    apply_fn,
    params: Any,
    input_shape: tuple[int, ...],
    metadata: dict | None = None,
) -> None:
    """The one frozen-classifier export shape every CLI shares: bake
    ``softmax(apply_fn({'params': params}, x))`` into a polymorphic-batch
    StableHLO artifact, traced at ``(1, *input_shape)`` float32 input."""
    params = jax.device_get(params)

    def frozen_probs(x):
        return jax.nn.softmax(apply_fn({"params": params}, x), -1)

    export_frozen_stablehlo(
        path,
        frozen_probs,
        (np.zeros((1, *input_shape), np.float32),),
        metadata=metadata,
    )


def load_frozen_stablehlo(path: str):
    """Returns (callable, metadata): the deserialized exported program. The
    callable jit-executes on the current default backend — no model code or
    params needed, exactly like loading the reference's frozen ``.pb``."""
    from jax import export as jax_export

    with open(path, "rb") as fh:
        hlen = int.from_bytes(fh.read(8), "little")
        metadata = json.loads(fh.read(hlen).decode())
        blob = fh.read()
    exported = jax_export.deserialize(bytearray(blob))
    return exported.call, metadata
