"""MNIST trainer — the demo1/demo2 training loop, TPU-native.

One loop serves both the single-device (``demo1/train.py:149-165``) and
distributed (``demo2/train.py:176-193``) workloads: the only difference is the
mesh it runs over. Structure parity with the reference:

  * ``training_steps`` steps of batch-``batch_size`` Adam updates
  * full test-set + train-set accuracy eval every ``eval_step_interval``
    (reference evals *inside* the hot loop at ``demo1/train.py:158-163`` with
    full-dataset feed_dict runs — here eval is a separate jitted sharded
    program and the hot loop stays free of host transfers)
  * scalar/histogram summaries per eval (not per step: a per-step host sync
    would stall the TPU pipeline; divergence documented)
  * timed checkpoint autosave + restore-on-start (Supervisor parity)
  * wall-clock ``Training time`` print (``demo1/train.py:164``)
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from distributed_tensorflow_tpu import obs
from distributed_tensorflow_tpu.config import MnistTrainConfig
from distributed_tensorflow_tpu.data.mnist import DataSet, read_data_sets
from distributed_tensorflow_tpu.data.prefetch import (
    bounded_device_batches,
    stacked_device_batches,
)
from distributed_tensorflow_tpu.models.mnist_cnn import MnistCNN
from distributed_tensorflow_tpu.parallel import data_parallel as dp
from distributed_tensorflow_tpu.parallel.mesh import make_mesh
from distributed_tensorflow_tpu.train import resilience
from distributed_tensorflow_tpu.train.checkpoint import CheckpointManager
from distributed_tensorflow_tpu.utils import faults
from distributed_tensorflow_tpu.utils.logging import get_logger
from distributed_tensorflow_tpu.utils.profiler import Profiler
from distributed_tensorflow_tpu.utils.summary import SummaryWriter, variable_summaries
from distributed_tensorflow_tpu.utils.timer import StepTimer, WallClock

log = get_logger(__name__)


def build_model(cfg: MnistTrainConfig):
    """cfg.model selects the MNIST classifier family: the reference convnet
    (``demo1/train.py:49-123`` shape) or the ViT (``models/vit.py``) — same
    (B, 784) apply convention, same trainer/ckpt/export machinery."""
    from distributed_tensorflow_tpu.models import digit_classifier

    kwargs = {"dropout_rate": cfg.dropout_rate}
    if cfg.model in ("vit", "ViT"):
        kwargs["remat"] = cfg.remat
    return digit_classifier(cfg.model, **kwargs)


class MnistTrainer:
    @staticmethod
    def _resolve_data_dir(cfg: MnistTrainConfig) -> str:
        """Real-data convenience (C19 spirit): with ``--t10k_split`` and
        ``--data_dir`` left at its parser default, fall back to the repo's
        bundled genuine t10k files so the demo runs bare from any cwd. An
        explicitly passed data_dir is never redirected."""
        if cfg.t10k_split:
            import os

            from distributed_tensorflow_tpu.data.mnist import (
                TEST_IMAGES,
                bundled_mnist_dir,
            )
            from distributed_tensorflow_tpu.utils.assets import dataclass_default

            if (
                not os.path.exists(os.path.join(cfg.data_dir, TEST_IMAGES))
                and cfg.data_dir == dataclass_default(MnistTrainConfig, "data_dir")
                and bundled_mnist_dir()
            ):
                log.info(
                    "%s has no t10k files; using bundled real MNIST %s",
                    cfg.data_dir, bundled_mnist_dir(),
                )
                return bundled_mnist_dir()
        return cfg.data_dir

    def __init__(
        self,
        cfg: MnistTrainConfig,
        mesh=None,
        datasets=None,
        model: MnistCNN | None = None,
        is_chief: bool = True,
        eval_chunk: int = 2000,
        scale_batch_by_mesh: bool = True,
    ):
        self.cfg = cfg
        self.mesh = mesh if mesh is not None else make_mesh(num_devices=1)
        self.model = model if model is not None else build_model(cfg)
        self.datasets = datasets or read_data_sets(
            self._resolve_data_dir(cfg),
            one_hot=True,
            seed=cfg.seed,
            synthetic=cfg.synthetic_data,
            download=cfg.download_data,
            t10k_split=cfg.t10k_split,
        )
        self.is_chief = is_chief
        self.eval_chunk = eval_chunk
        self.mesh_size = self.mesh.devices.size
        # Reference demo2 semantics: each of n async workers consumed
        # batch_size examples per step; the sync-SPMD equivalent is a global
        # batch of batch_size × mesh_size (each device computes one
        # batch_size shard). With a 1-device mesh this is exactly demo1.
        if scale_batch_by_mesh:
            self.global_batch = cfg.batch_size * self.mesh_size
        else:
            if cfg.batch_size % self.mesh_size:
                raise ValueError(
                    f"batch_size {cfg.batch_size} not divisible by mesh size {self.mesh_size}"
                )
            self.global_batch = cfg.batch_size
        # Multi-process: each worker samples its own share of the global batch
        # independently (reference demo2 parity — independent per-worker
        # shuffles), so the host pipeline assembles feed_batch examples here
        # and each process gets a decorrelated shuffle stream over the same
        # dataset copy.
        self.feed_batch = self.global_batch // jax.process_count()
        if jax.process_count() > 1:
            self.datasets.train.reseed_shuffle(cfg.seed + 1000003 * jax.process_index())

        # Default adam/constant == demo1/train.py:132 parity.
        from distributed_tensorflow_tpu.train.optimizers import make_optimizer

        self.tx = make_optimizer(
            cfg.optimizer,
            cfg.learning_rate,
            total_steps=cfg.training_steps,
            schedule=cfg.lr_schedule,
            warmup_steps=cfg.warmup_steps,
            grad_clip_norm=cfg.grad_clip_norm,
        )
        self.rng = jax.random.PRNGKey(cfg.seed)

        params = self.model.init(
            jax.random.PRNGKey(cfg.seed), jnp.zeros((1, 784), jnp.float32), train=False
        )["params"]
        opt_state = self.tx.init(params)
        self.params = dp.replicate(params, self.mesh)
        self.opt_state = dp.replicate(opt_state, self.mesh)
        self.global_step = dp.replicate(jnp.zeros((), jnp.int32), self.mesh)

        self._guard = bool(getattr(cfg, "guard_nonfinite", 1))
        self.train_step = dp.build_train_step(
            self.model.apply, self.tx, self.mesh, guard_nonfinite=self._guard
        )
        if cfg.accum_steps > 1 and (cfg.steps_per_call > 1 or cfg.device_data):
            raise ValueError(
                "accum_steps>1 is exclusive with steps_per_call>1 / device_data "
                "(accumulation trades dispatches for memory; fusion trades the "
                "other way)"
            )
        self.multi_step = (
            dp.build_multi_step(self.model.apply, self.tx, self.mesh, guard_nonfinite=self._guard)
            if cfg.steps_per_call > 1
            else None
        )
        self.accum_step = (
            dp.build_accum_train_step(self.model.apply, self.tx, self.mesh, guard_nonfinite=self._guard)
            if cfg.accum_steps > 1
            else None
        )
        self.eval_step = dp.build_eval_step(self.model.apply, self.mesh)

        self.ckpt = CheckpointManager(
            cfg.log_dir,
            save_interval_secs=cfg.save_model_secs,
            max_to_keep=getattr(cfg, "max_to_keep", 5),
            async_snapshot=bool(getattr(cfg, "ckpt_async", 1)),
            snapshot_chunk_mb=getattr(cfg, "snapshot_chunk_mb", 64),
        )
        self.writer = SummaryWriter(cfg.log_dir) if is_chief else None

        # Resilience state: lazily-accumulated per-window skipped-step
        # scalars (device arrays — summed/fetched only at eval boundaries so
        # the hot loop stays sync-free), the consecutive-bad-window counter
        # driving rollback, and the preemption guard (installed for the
        # duration of train()).
        self._window_skips: list = []
        self._bad_windows = 0
        self._rollbacks = 0
        self.total_skipped = 0
        self._preempt: resilience.PreemptionGuard | None = None

        # Observability: crash dumps go to cfg.obs_dir when set, and the
        # step-time decomposition is published into the process registry at
        # eval boundaries (counters are window DELTAS of the shared
        # data-wait counter and the checkpoint stall accumulator — the
        # compute slice is what's left of the window wall time).
        if getattr(cfg, "obs_dir", ""):
            obs.set_dump_dir(cfg.obs_dir)
        reg = obs.get_registry()
        self._obs_wait = reg.counter(
            "data_wait_seconds_total",
            "Seconds the training thread blocked waiting for input batches.")
        self._obs_compute = reg.counter(
            "train_compute_seconds_total",
            "Window wall time minus data-wait and checkpoint stall.")
        self._obs_stall = reg.counter(
            "train_ckpt_stall_seconds_total",
            "Main-thread seconds blocked inside checkpoint save paths.")
        self._obs_steps = reg.counter(
            "train_steps_total", "Optimizer steps completed.")
        self._obs_skipped = reg.counter(
            "train_skipped_nonfinite_total",
            "Steps skipped by the non-finite guard.")
        self._obs_examples_rate = reg.gauge(
            "train_examples_per_sec",
            "Global examples/s over the last drained training window.")
        self._obs_wait_frac = reg.gauge(
            "train_data_wait_frac",
            "Data-wait share of the last window's wall time (the "
            "input-bound alarm the default training SLO watches).")
        self._perf = obs.PerfGauges(reg)
        slo_rules = obs.parse_slo_flag(
            getattr(cfg, "slo", ""),
            defaults=obs.default_training_rules)
        # Evaluated at eval boundaries (no ticker thread: the train loop
        # already has a natural heartbeat, and a wall-clock ticker would
        # race the window bookkeeping for no fresher data).
        self._slo = obs.SloMonitor(reg, slo_rules) if slo_rules else None
        self._win_t0 = 0.0
        self._win_wait_base = 0.0
        self._win_stall_base = 0.0

        # Supervisor parity: init-or-restore from logdir (demo2/train.py:166-176).
        from distributed_tensorflow_tpu.train.checkpoint import restore_replicated

        restored = restore_replicated(self.ckpt, self._state_dict(), self.mesh)
        if restored is not None:
            step, state = restored
            self.params = state["params"]
            self.opt_state = state["opt_state"]
            self.global_step = state["global_step"]
            log.info("restored checkpoint at step %d from %s", step, cfg.log_dir)

    # -- state (de)serialization ------------------------------------------------

    def _state_dict(self):
        return {
            "params": self.params,
            "opt_state": self.opt_state,
            "global_step": self.global_step,
        }

    # (restore in __init__ goes through checkpoint.restore_replicated;
    # saves go through checkpoint.coordinated_maybe_save below.)

    # -- eval ------------------------------------------------------------------

    def evaluate(self, dataset: DataSet, max_examples: int | None = None):
        """Exact full-dataset accuracy/loss via chunked sharded eval."""
        images, labels = dataset.images, dataset.labels
        if max_examples is not None:
            images, labels = images[:max_examples], labels[:max_examples]
        total_correct = total_loss = 0.0
        n = images.shape[0]
        for lo in range(0, n, self.eval_chunk):
            chunk = {"image": images[lo : lo + self.eval_chunk], "label": labels[lo : lo + self.eval_chunk]}
            padded, real = dp.pad_to_multiple(chunk, self.mesh_size)
            # Every process holds the same dataset copy — identical-data path.
            batch = dp.shard_global_batch(padded, self.mesh)
            correct, loss_sum = self.eval_step(self.params, batch)
            total_correct += float(correct)
            total_loss += float(loss_sum)
        return total_correct / n, total_loss / n

    # -- train -----------------------------------------------------------------

    def train(self, num_steps: int | None = None):
        cfg = self.cfg
        num_steps = num_steps if num_steps is not None else cfg.training_steps
        clock = WallClock()
        # Boundary-drained timing: the timer ticks ONLY in _post_step at
        # eval boundaries, right after the metrics device_get forces every
        # queued dispatch to complete — dispatch is asynchronous, so a
        # per-dispatch tick measures issue time, not compute — and
        # warmup=2 drops the first measured window (it contains the jit
        # compile).
        timer = StepTimer(warmup_steps=2)
        step = start_step = int(jax.device_get(self.global_step))
        timer.start(step)
        self._bad_windows = 0
        self._window_skips = []
        guard = resilience.PreemptionGuard() if getattr(cfg, "preempt_save", 1) else None
        if guard is not None:
            self._preempt = guard.install()
        self._reset_window_obs(step)
        preempted = False
        try:
            while step < num_steps:
                try:
                    self._run_training(step, num_steps, timer)
                except resilience.Preempted as p:
                    # Fall through to the forced save below: that IS the
                    # coordinated emergency checkpoint, after which we return
                    # cleanly so a restart resumes via restore_replicated.
                    log.warning(
                        "preemption at step %d — emergency checkpoint, then "
                        "clean exit", p.step,
                    )
                    preempted = True
                    break
                except resilience.RollbackRequested as rb:
                    self._rollbacks += 1
                    if self._rollbacks > getattr(cfg, "max_rollbacks", 3):
                        raise RuntimeError(
                            f"giving up after {self._rollbacks - 1} rollbacks: "
                            f"{rb}"
                        ) from rb
                    if not self._rollback(rb, timer):
                        log.error(
                            "rollback requested but no checkpoint to restore "
                            "— continuing from current state"
                        )
                step = int(jax.device_get(self.global_step))
        finally:
            if guard is not None:
                guard.uninstall()
            self._preempt = None
        step = int(jax.device_get(self.global_step))
        if preempted:
            # The emergency-shutdown span wraps the coordinated forced save
            # so the flight record a preemption ships shows both: the
            # shutdown envelope and the checkpoint_save span nested in it.
            with obs.span("emergency_shutdown", step=step, reason="preempt"):
                self._maybe_save(step, force=True)
            resilience.dump_flight_record("preempt")
        else:
            self._maybe_save(step, force=True)
        if self.is_chief and self.writer:
            self.writer.flush()
        train_time = clock.elapsed
        rate = timer.steps_per_sec
        if rate <= 0 and train_time > 0:
            # Run too short for a post-compile drained window (single eval
            # boundary): fall back to whole-run wall-clock — an honest
            # LOWER bound since it includes compile and evals.
            rate = (step - start_step) / train_time
            basis = "whole run incl. compile/eval — run longer for a clean rate"
        else:
            basis = "drained training windows; wall-clock includes eval/compile"
        log.info("Training time: %.2fs (%.1f steps/s, %s)", train_time, rate, basis)
        return {
            "steps": step,
            "seconds": train_time,
            "steps_per_sec": rate,
            # Main-thread time blocked inside save paths (the zero-stall
            # pipeline's own measure of what autosave cost the loop).
            "ckpt_stall_seconds": round(self.ckpt.stall_seconds, 4),
        }

    def _run_training(self, step: int, num_steps: int, timer: StepTimer) -> None:
        """One attempt at running [step, num_steps): builds the input
        pipeline and drives the hot loop. Preemption/rollback propagate as
        exceptions (input pipeline and profiler are closed on the way out);
        ``train()`` owns the recovery policy."""
        cfg = self.cfg
        if cfg.device_data:
            self._train_loop(None, num_steps, step, timer)
            return
        # Background input pipeline: batch assembly + HBM transfer
        # overlap the device step (replaces the reference's serial
        # feed_dict upload, demo1/train.py:153-155).
        if self.multi_step is not None:
            chunks = self._chunk_sizes(step, num_steps)
            prefetch = stacked_device_batches(
                self.datasets.train, self.feed_batch, self.mesh, chunks
            )
        elif self.accum_step is not None:
            # k microbatches per optimizer step, stacked on a leading
            # dim (the accum step scans over them).
            prefetch = stacked_device_batches(
                self.datasets.train,
                self.feed_batch,
                self.mesh,
                [self.cfg.accum_steps] * (num_steps - step),
            )
        else:
            prefetch = bounded_device_batches(
                self.datasets.train, self.feed_batch, self.mesh, num_steps - step
            )
        try:
            self._train_loop(prefetch, num_steps, step, timer)
        finally:
            prefetch.close()

    def _rollback(self, rb: "resilience.RollbackRequested", timer: StepTimer) -> bool:
        """Restore the last good checkpoint after a rollback request; returns
        False when there is nothing to restore."""
        from distributed_tensorflow_tpu.train.checkpoint import restore_replicated

        self._bad_windows = 0
        self._window_skips = []
        # A snapshot queued during the diverging window must not complete
        # into the step we are rolling away from (restore itself drains
        # whatever already reached the write stage).
        self.ckpt.veto_pending()
        restored = restore_replicated(self.ckpt, self._state_dict(), self.mesh)
        if restored is None:
            return False
        step, state = restored
        self.params = state["params"]
        self.opt_state = state["opt_state"]
        self.global_step = state["global_step"]
        timer.mark(int(step))
        self._reset_window_obs(int(step))
        log.warning("rolled back to checkpoint step %d (%s)", step, rb)
        obs.trace_event("rollback", from_step=rb.step, to_step=int(step),
                        bad_windows=rb.bad_windows)
        resilience.dump_flight_record("rollback")
        return True

    # -- window observability ---------------------------------------------

    def _reset_window_obs(self, step: int) -> None:
        self._win_t0 = time.perf_counter()
        self._win_step_base = step
        self._win_wait_base = self._obs_wait.value
        self._win_stall_base = self.ckpt.stall_seconds

    def _publish_window_obs(self, step: int, steps_per_sec: float,
                            window_skipped: int) -> None:
        """Decompose the window just drained: wall = data-wait + checkpoint
        stall + (residual) device compute. The wait/stall slices are deltas
        of their process accumulators, so they are measured, not inferred."""
        wall = time.perf_counter() - self._win_t0
        wait_d = max(self._obs_wait.value - self._win_wait_base, 0.0)
        stall_d = max(self.ckpt.stall_seconds - self._win_stall_base, 0.0)
        compute = max(wall - wait_d - stall_d, 0.0)
        self._obs_compute.inc(compute)
        self._obs_stall.inc(stall_d)
        self._obs_steps.inc(max(step - self._win_step_base, 0))
        if window_skipped:
            self._obs_skipped.inc(window_skipped)
        if steps_per_sec > 0:
            self._obs_examples_rate.set(steps_per_sec * self.global_batch)
            self._perf.update_window(
                steps_per_sec=steps_per_sec,
                examples_per_step=self.global_batch,
            )
        if wall > 0:
            self._obs_wait_frac.set(wait_d / wall)
        obs.update_memory_gauges()  # no-op readings on CPU (graceful null)
        if self._slo is not None:
            self._slo.evaluate()
        obs_dir = getattr(self.cfg, "obs_dir", "")
        if obs_dir:
            # Fleet plane: every process drops its snapshot; the chief
            # merges whatever snapshots exist so far into the fleet view.
            try:
                obs.write_process_snapshot(obs_dir)
                if self.is_chief:
                    agg = obs.FleetAggregator()
                    if agg.load_dir(obs_dir):
                        agg.export(obs_dir)
            except OSError:
                pass  # observability must never kill the train step
        self._reset_window_obs(step)

    def _train_loop(self, prefetch, num_steps: int, step: int, timer: StepTimer) -> None:
        cfg = self.cfg
        # Chief-only trace (SURVEY §5.1): replaces the reference's wall-clock
        # prints with a real per-op device timeline when --profile_dir is set.
        # The window is relative to THIS run's first step (``step`` may be a
        # checkpoint-resumed global step); the sync callback flushes the
        # async-dispatched device queue so the XPlane isn't truncated.
        prof = Profiler(
            cfg.profile_dir if self.is_chief else None,
            start_step=step + cfg.profile_start_step,
            num_steps=cfg.profile_num_steps,
            # The same barrier the timing windows use: a host transfer of a
            # value that depends on every queued dispatch, so the trace is
            # not truncated. (block_until_ready waits too on the current
            # installation — checked on the chip, PERF.md PR 21.)
            sync=lambda: jax.device_get(self.global_step),
        )
        try:
            self._train_steps(prefetch, num_steps, step, timer, prof)
        finally:
            prof.close()

    def _chunk_sizes(self, step: int, num_steps: int) -> list[int]:
        """Fused-dispatch sizes: ``steps_per_call`` steps per call, clipped so
        no call crosses an eval boundary or the end of training (eval needs
        up-to-date params on the host side of a call)."""
        interval = self.cfg.eval_step_interval
        chunks, s = [], step
        while s < num_steps:
            boundary = min(num_steps, ((s // interval) + 1) * interval)
            k = min(self.cfg.steps_per_call, boundary - s)
            chunks.append(k)
            s += k
        return chunks

    def _train_steps(self, prefetch, num_steps: int, step: int, timer: StepTimer, prof) -> None:
        if prefetch is None:
            self._train_steps_device_data(num_steps, step, timer, prof)
            return
        while step < num_steps:
            batch = next(prefetch)
            # Fused dispatches advance `span` steps per call; the profiler
            # window intersects [step, step+span), not just [step, step+1).
            k = (
                next(iter(batch.values())).shape[0]
                if self.multi_step is not None
                else 1  # accum: k microbatches but ONE optimizer step
            )
            # Fault site ``nonfinite_grad:step=N``: NaN the dispatch covering
            # step N so the grads go non-finite and the guard path (skip +
            # metric + rollback policy) is exercised for real.
            if faults.fire_step("nonfinite_grad", range(step, step + k)):
                batch = {**batch, "image": batch["image"] * jnp.float32(jnp.nan)}
            # Base key only: the step fold happens on-device inside the jitted
            # program (keyed on global_step), so the hot loop does zero
            # per-step host dispatches besides the train step itself.
            with prof.step(step, span=k):
                if self.multi_step is not None:
                    self.params, self.opt_state, self.global_step, metrics = self.multi_step(
                        self.params, self.opt_state, self.global_step, batch, self.rng
                    )
                    self._note_skips(metrics)
                    # Stacked (k,) metrics → report the final step's values,
                    # matching what a per-step loop would log at this point.
                    metrics = {name: v[-1] for name, v in metrics.items()}
                elif self.accum_step is not None:
                    self.params, self.opt_state, self.global_step, metrics = self.accum_step(
                        self.params, self.opt_state, self.global_step, batch, self.rng
                    )
                    self._note_skips(metrics)
                else:
                    self.params, self.opt_state, self.global_step, metrics = self.train_step(
                        self.params, self.opt_state, self.global_step, batch, self.rng
                    )
                    self._note_skips(metrics)
            step += k
            self._post_step(step, num_steps, metrics, timer)

    def _train_steps_device_data(self, num_steps: int, step: int, timer: StepTimer, prof) -> None:
        """Hot loop with the training set resident in HBM: one pool upload,
        then per-dispatch fused steps whose batches are gathered on device
        (``dp.build_pool_train_fn``) — no host input work at all."""
        train = self.datasets.train
        pool = dp.shard_pool(train.images, train.labels, self.mesh)
        batch_per_shard = self.global_batch // self.mesh_size
        fns: dict[int, object] = {}  # one compiled program per distinct k
        for k in set(self._chunk_sizes(step, num_steps)):
            fns[k] = dp.build_pool_train_fn(
                self.model.apply, self.tx, self.mesh, batch_per_shard, k,
                guard_nonfinite=self._guard,
            )
        for k in self._chunk_sizes(step, num_steps):
            with prof.step(step, span=k):
                self.params, self.opt_state, self.global_step, metrics = fns[k](
                    self.params, self.opt_state, self.global_step, pool, self.rng
                )
            self._note_skips(metrics)
            # Lazy on-device slice — no host sync in the hot loop; _post_step
            # device_gets at eval cadence only.
            metrics = {name: v[-1] for name, v in metrics.items()}
            step += k
            self._post_step(step, num_steps, metrics, timer)

    def _note_skips(self, metrics) -> None:
        """Queue this dispatch's skipped-step count (scalar or stacked) for
        the window aggregate — a device-side sum, NO host sync here."""
        s = metrics.get("skipped_nonfinite")
        if s is not None:
            self._window_skips.append(jnp.sum(s))

    def _drain_window_skips(self) -> int:
        """Total non-finite-skipped steps since the last eval boundary
        (fetches the queued device scalars — call at boundaries only)."""
        parts, self._window_skips = self._window_skips, []
        if not parts:
            return 0
        return int(round(sum(float(jax.device_get(x)) for x in parts)))

    def _post_step(self, step: int, num_steps: int, metrics, timer: StepTimer) -> None:
        cfg = self.cfg
        at_boundary = step % cfg.eval_step_interval == 0 or step == num_steps
        # Preemption first: a pending SIGTERM means save-and-exit beats one
        # more eval. Fault site ``preempt:step=N`` feeds the same flag a real
        # signal sets.
        if self._preempt is not None:
            if faults.fire_step("preempt", [step]):
                self._preempt.request()
            if self._preempt.should_exit(at_boundary):
                obs.trace_event("preempt_exit", step=step)
                raise resilience.Preempted(step)
        window_skipped = 0
        if at_boundary:
            m = jax.device_get(metrics)  # completion barrier for the window
            timer.tick_to(step)
            window_skipped = self._drain_window_skips()
            self.total_skipped += window_skipped
            if window_skipped:
                self._bad_windows += 1
                log.warning(
                    "eval window ending at step %d skipped %d non-finite "
                    "step(s) (%d consecutive bad window(s))",
                    step, window_skipped, self._bad_windows,
                )
            else:
                self._bad_windows = 0
            rate = timer.steps_per_sec  # 0.0 until the compile window passes
            # Decompose the drained window BEFORE eval/summary work so the
            # compute slice covers training dispatches only.
            self._publish_window_obs(step, rate, window_skipped)
            test_acc, test_loss = self.evaluate(self.datasets.test)
            train_acc, _ = self.evaluate(self.datasets.train, max_examples=10000)
            log.info(
                "step %d: batch loss %.4f, test acc %.4f, train acc %.4f (%s)",
                step, float(m["loss"]), test_acc, train_acc,
                f"{rate:.1f} steps/s" if rate > 0 else "steps/s pending",
            )
            if self.writer:
                self.writer.add_scalars(
                    {
                        "cross_entropy": float(m["loss"]),
                        "batch_accuracy": float(m["accuracy"]),
                        "test_accuracy": test_acc,
                        "test_loss": test_loss,
                        "train_accuracy": train_acc,
                        "skipped_nonfinite": float(window_skipped),
                        **({"steps_per_sec": rate} if rate > 0 else {}),
                    },
                    step,
                )
                # variable_summaries parity (demo1/train.py:15-24) at eval
                # cadence, for the classifier-head weights (fc2 on the
                # convnet; the ViT's head otherwise).
                p = jax.device_get(self.params)
                head_name = "fc2" if "fc2" in p else "head"
                if head_name in p and "kernel" in p[head_name]:
                    variable_summaries(
                        self.writer, f"{head_name}/weights",
                        p[head_name]["kernel"], step,
                    )
        if (
            at_boundary
            and window_skipped
            and getattr(cfg, "rollback_bad_windows", 0) > 0
            and self._bad_windows >= cfg.rollback_bad_windows
            and self.ckpt.latest_step() is not None
        ):
            # K consecutive windows of skipped updates = a diverged run the
            # guard alone can't rescue; train() restores the last good
            # checkpoint. (The bad-window save suppression below keeps the
            # latest checkpoint pre-divergence.)
            raise resilience.RollbackRequested(step, self._bad_windows)
        if at_boundary and window_skipped:
            # Don't advance the checkpoint chain on a window that skipped
            # updates: rollback must land BEFORE the divergence started.
            # That veto extends to any snapshot still queued from a timed
            # save INSIDE this window (async saves capture state at enqueue
            # time, but a bad window disqualifies the whole window).
            self.ckpt.veto_pending()
            saved = False
        else:
            saved = self._maybe_save(step, at_eval_boundary=at_boundary)
        if at_boundary or saved:
            # Exclude the eval/summary/save work above from the next
            # training window (the boundary tick_to already closed this
            # window at the completion barrier; a mid-window timed save
            # drops the partial window — steps AND time — so the next
            # boundary doesn't attribute full-window steps to partial time).
            timer.mark(step)
            self._reset_window_obs(step)

    def _maybe_save(self, step: int, force: bool = False, at_eval_boundary: bool = True) -> bool:
        from distributed_tensorflow_tpu.train.checkpoint import coordinated_maybe_save

        return coordinated_maybe_save(
            self.ckpt, step, self._state_dict(), self.is_chief,
            force=force, at_boundary=at_eval_boundary,
        )
