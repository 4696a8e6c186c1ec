"""Double-buffered weight swap with a canary gate and automatic rollback.

State machine per candidate (DESIGN.md §19)::

    submitted ──stage (validate + device_put, off-thread OK)──▶ staged
    staged ──scheduler iteration boundary──▶ canary
    canary ──all checks pass──▶ live        (outcome "ok")
    canary ──any check fails──▶ rolled back (outcome "rollback",
                                             flight-recorder dump)

**Staging** happens where the candidate arrives (the watcher thread):
``engine.stage_weights`` validates the zero-recompile precondition
(identical treedef/shapes/dtypes) and device_puts fresh buffers — the
same donation-safe defensive-copy trick as the checkpoint D2H snapshot
path, but pointed up. In-flight slots keep decoding against the old
buffers the whole time; nothing is dropped, nothing recompiles.

**Canary** and **flip** run on the scheduler's driver thread at an
iteration boundary (``Scheduler.at_boundary``) so no jitted program is
mid-flight when the reference moves. The canary never touches a serving
slot — the candidate runs OUTSIDE the engine's compiled program set
(eager forwards, invisible to the RecompileSentinel's cache counts), so
a poisoned checkpoint is rejected without serving a single token from
it:

1. **non-finite scan** — any NaN/Inf in a floating leaf;
2. **held-out eval loss** — next-token cross-entropy on a small fixed
   batch, candidate vs live; regression beyond ``max_loss_ratio`` fails;
3. **probe prompts** — K greedy forwards; non-finite logits fail, and
   the probe continuations land in the SwapResult for offline diffing.

Rollback is the cheap direction: the live reference never moved, so
"rolling back" is dropping the staged buffers, dumping the flight
recorder (``flight_swap_rollback_*``), and counting
``serve_swap_total{outcome="rollback"}``.
"""

from __future__ import annotations

import sys
import threading
import time

import numpy as np

from distributed_tensorflow_tpu.obs import recorder as obs_recorder
from distributed_tensorflow_tpu.serve.deploy.variants import VariantTable
from distributed_tensorflow_tpu.utils.logging import get_logger

__all__ = ["SwapResult", "WeightSwapper", "make_canary_batch"]

# stderr: a serving CLI's stdout carries data (bench compact line,
# loadgen JSONL) and must stay log-free.
log = get_logger(__name__, stream=sys.stderr)


class SwapResult:
    """Outcome of one swap attempt (``history`` keeps the last N)."""

    __slots__ = ("step", "variant", "outcome", "reason", "canary_loss",
                 "baseline_loss", "stall_s", "probe_tokens")

    def __init__(self, step, variant, outcome, reason="", canary_loss=None,
                 baseline_loss=None, stall_s=0.0, probe_tokens=()):
        self.step = int(step)
        self.variant = str(variant)
        self.outcome = str(outcome)  # "ok" | "rollback"
        self.reason = str(reason)
        self.canary_loss = canary_loss
        self.baseline_loss = baseline_loss
        self.stall_s = float(stall_s)
        self.probe_tokens = tuple(probe_tokens)

    def to_dict(self) -> dict:
        return {
            "step": self.step,
            "variant": self.variant,
            "outcome": self.outcome,
            "reason": self.reason,
            "canary_loss": self.canary_loss,
            "baseline_loss": self.baseline_loss,
            "stall_ms": round(self.stall_s * 1e3, 3),
        }


def make_canary_batch(vocab_size: int, *, rows: int = 4, length: int = 16,
                      seed: int = 0) -> np.ndarray:
    """The held-out canary batch: fixed random tokens. Deterministic per
    (vocab, shape, seed) so baseline and candidate always score the same
    data, across swaps and across replicas."""
    rng = np.random.default_rng(seed)
    return rng.integers(0, int(vocab_size),
                        size=(int(rows), int(length))).astype(np.int32)


class WeightSwapper:
    """Stages checkpoint candidates into a serving engine through the
    canary gate. One instance per (engine, scheduler) pair; ``submit`` is
    thread-safe (the watcher calls it), the canary+flip runs on the
    scheduler's driver thread via ``Scheduler.at_boundary``.

    ``variants``: optional :class:`VariantTable`. Candidates deploy INTO
    a named variant (``submit(..., variant=...)``, default the table's
    default variant); a candidate for the live variant flips the engine,
    one for another variant just updates the table (the scheduler
    activates it when that variant's traffic arrives). Without a table
    the engine's single param slot is the only target.
    """

    def __init__(
        self,
        engine,
        scheduler=None,
        *,
        metrics=None,
        variants: VariantTable | None = None,
        canary_batch=None,
        probe_prompts=(),
        probe_tokens: int = 4,
        max_loss_ratio: float = 1.5,
        keep_history: int = 32,
        clock=time.perf_counter,
    ):
        if max_loss_ratio <= 0:
            raise ValueError(
                f"max_loss_ratio must be > 0, got {max_loss_ratio}"
            )
        self.engine = engine
        self.scheduler = scheduler
        self.metrics = metrics
        self.variants = variants
        if canary_batch is None:
            canary_batch = make_canary_batch(
                engine.cfg.vocab_size,
                length=min(16, int(engine.cfg.max_seq_len)),
            )
        self.canary_batch = np.asarray(canary_batch, np.int32)
        self.probe_prompts = tuple(
            tuple(int(t) for t in p) for p in probe_prompts
        )
        self.probe_tokens = int(probe_tokens)
        self.max_loss_ratio = float(max_loss_ratio)
        self.keep_history = int(keep_history)
        self.clock = clock
        self._lock = threading.Lock()
        self._staged: tuple | None = None  # (step, staged_params, variant)
        self.history: list[SwapResult] = []
        self.last: SwapResult | None = None
        self._applied = threading.Event()

    # -- submit side (watcher thread) --------------------------------------

    def submit(self, step: int, params, *, variant: str | None = None):
        """Stage a candidate and schedule its canary+flip at the next
        scheduler iteration boundary (or run it inline when no scheduler
        is attached — unit tests, offline tools). A newer candidate
        submitted before the boundary supersedes an older staged one.
        Raises ValueError when the candidate cannot ever swap in
        (structure/shape/dtype mismatch) — that is a deploy bug, not a
        canary matter."""
        if variant is None:
            variant = self.variants.default if self.variants else ""
        staged = self.engine.stage_weights(params)  # validate + device_put
        obs_recorder.get_recorder().record(
            kind="deploy_staged", step=int(step), variant=str(variant))
        with self._lock:
            schedule = self._staged is None
            self._staged = (int(step), staged, str(variant))
        self._applied.clear()
        if self.scheduler is None:
            return self.apply_staged()
        if schedule:
            self.scheduler.at_boundary(self.apply_staged)
        return None

    def wait_applied(self, timeout: float | None = None) -> bool:
        """Block until the most recently submitted candidate has been
        canaried (either outcome). Tests and ``--swap_mid_run`` use this
        to sequence assertions after the flip."""
        return self._applied.wait(timeout)

    def prewarm(self) -> None:
        """Run the canary gate once against the LIVE params and discard
        the result. The canary is eager, and first-time eager executables
        are process-wide XLA compile events — run this BEFORE the
        sentinel's ``mark_warm`` so the first real swap reuses the cached
        executables instead of breaching the zero-recompile SLO (every
        candidate shares the live tree's shapes/dtypes by the swap
        precondition, so one pass covers all future canaries)."""
        self._canary(self.engine.params)

    # -- driver-thread side ------------------------------------------------

    def apply_staged(self) -> SwapResult | None:
        """Canary the staged candidate and flip or roll back. Called at a
        scheduler iteration boundary (driver thread); returns the result
        or None when nothing was staged."""
        with self._lock:
            if self._staged is None:
                return None
            step, staged, variant = self._staged
            self._staged = None
        t0 = self.clock()
        reason, canary_loss, base_loss, probes = self._canary(staged)
        if reason is None:
            live = (self.variants is None
                    or variant == self.engine.serving_variant)
            if live:
                self.engine.adopt_weights(
                    staged, version=step, variant=variant or None)
            if self.variants is not None:
                self.variants.set_staged(variant, staged, step=step)
            result = SwapResult(
                step, variant, "ok",
                reason="live" if live else "staged into variant table",
                canary_loss=canary_loss, baseline_loss=base_loss,
                stall_s=self.clock() - t0, probe_tokens=probes,
            )
            log.info(
                "deploy swap ok: step %d -> variant %r (%s, stall %.1f ms)",
                step, variant or "<engine>", result.reason,
                result.stall_s * 1e3,
            )
        else:
            result = SwapResult(
                step, variant, "rollback", reason=reason,
                canary_loss=canary_loss, baseline_loss=base_loss,
                stall_s=self.clock() - t0,
            )
            log.error(
                "deploy swap ROLLBACK: step %d variant %r — %s",
                step, variant or "<engine>", reason,
            )
        obs_recorder.get_recorder().record(
            kind="deploy_swap", **result.to_dict())
        if result.outcome == "rollback":
            obs_recorder.dump_to_dir("swap_rollback")
        if self.metrics is not None:
            self.metrics.record_swap(result.outcome)
            if result.outcome == "ok":
                self.metrics.record_weight_version(
                    self.engine.weight_version)
                # The gauges the engine's build fixes (weight bytes per
                # device, dtype labels) are re-read where they can change,
                # here, and not every round.
                self.metrics.bind_engine(self.engine)
        self.history.append(result)
        del self.history[:-self.keep_history]
        self.last = result
        self._applied.set()
        return result

    # -- the canary gate ---------------------------------------------------

    def _canary(self, staged):
        """Run the three checks against the staged candidate. Returns
        (fail_reason | None, canary_loss, baseline_loss, probe_tokens).
        Eager forwards only — nothing here touches the engine's compiled
        program set. The eager executables themselves still compile the
        FIRST time each shape runs, which is why :meth:`prewarm` exists."""
        import jax

        for path, leaf in jax.tree_util.tree_flatten_with_path(staged)[0]:
            arr = np.asarray(leaf)
            if (np.issubdtype(arr.dtype, np.floating)
                    and not np.all(np.isfinite(arr))):
                return (
                    f"non-finite leaf {jax.tree_util.keystr(path)}",
                    None, None, (),
                )
        base_loss = self._eval_loss(self.engine.params)
        canary_loss = self._eval_loss(staged)
        if not np.isfinite(canary_loss):
            return (
                f"non-finite canary eval loss {canary_loss}",
                float(canary_loss), float(base_loss), (),
            )
        # Ratio gate with a small absolute slack so a near-zero baseline
        # does not turn float noise into rollbacks.
        if canary_loss > base_loss * self.max_loss_ratio + 1e-3:
            return (
                f"eval-loss regression: candidate {canary_loss:.4f} vs "
                f"live {base_loss:.4f} (x{self.max_loss_ratio} gate)",
                float(canary_loss), float(base_loss), (),
            )
        probes = []
        for i, prompt in enumerate(self.probe_prompts):
            toks = self._probe(staged, prompt)
            if toks is None:
                return (
                    f"non-finite logits on probe prompt {i}",
                    float(canary_loss), float(base_loss), (),
                )
            probes.append(tuple(toks))
        return None, float(canary_loss), float(base_loss), tuple(probes)

    def _eval_loss(self, params) -> float:
        """Next-token cross-entropy of ``params`` on the held-out canary
        batch (eager, float32 log-softmax)."""
        import jax
        import jax.numpy as jnp

        toks = jnp.asarray(self.canary_batch)
        logits = self.engine.model.apply({"params": params}, toks)
        logp = jax.nn.log_softmax(
            logits[:, :-1].astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(
            logp, toks[:, 1:, None], axis=-1)
        return float(jnp.mean(nll))

    def _probe(self, params, prompt):
        """Greedy-continue one probe prompt for ``probe_tokens`` steps
        (eager, no KV cache — probes are tiny). None on non-finite
        logits, else the continuation tokens."""
        import jax.numpy as jnp

        toks = list(prompt)
        out = []
        limit = int(self.engine.cfg.max_seq_len)
        for _ in range(self.probe_tokens):
            window = toks[-limit:]
            logits = self.engine.model.apply(
                {"params": params}, jnp.asarray([window], jnp.int32))
            last = np.asarray(logits[0, -1].astype(jnp.float32))
            if not np.all(np.isfinite(last)):
                return None
            nxt = int(np.argmax(last))
            out.append(nxt)
            toks.append(nxt)
        return out
