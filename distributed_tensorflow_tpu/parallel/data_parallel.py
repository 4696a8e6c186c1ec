"""Synchronous SPMD data-parallel training over a device mesh.

This is the TPU-native replacement for the reference's **asynchronous
parameter-server** data parallelism (``demo2/train.py:18-29,149,166-193``):
workers there pull stale variables from ps hosts over gRPC, compute gradients
locally, and push un-synchronized updates back (HogWild). On TPU the idiomatic
equivalent is synchronous SPMD: the batch is sharded over the mesh's ``data``
axis, every device computes gradients on its shard, and a single
``lax.psum``-mean over ICI replaces the two gRPC crossings per step.
Documented divergence (SURVEY §2.2): sync DP ≥ async PS in convergence per
step; async PS semantics are an anti-pattern on TPU.

Implementation: ``jax.shard_map`` with explicit collectives (not relying on
sharding propagation) so the communication pattern is visible and auditable;
the whole step (fwd + bwd + psum + optimizer) is one jitted XLA program —
parameters never leave HBM.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distributed_tensorflow_tpu.ops.losses import (
    accuracy,
    correct_mask,
    per_example_cross_entropy,
    softmax_cross_entropy,
)

Batch = dict[str, jnp.ndarray]


def fence_grads(grads: Any) -> Any:
    """``lax.optimization_barrier`` between the gradient tree and the
    optimizer update — identity on values, but XLA may not fuse across it.

    Without the fence XLA folds the Adam elementwise chain into the
    weight-gradient matmuls' epilogues, and the fused dW ops run measurably
    over the matmul roofline: the r4 XPlane budget attributed ~16 ms/step
    of epilogue overhead at the flagship LM shape, and fencing recovered
    10-12 ms/step — **72.6% → 74.7% MFU**, reproduced in reversed A/B order
    (tools/adam_fusion_probe.py, r5). Applied by every train-step builder
    right before ``tx.update``; numerics and collective structure are
    untouched (the barrier is not a collective)."""
    return lax.optimization_barrier(grads)


def _to_global(tree: Any, sharding: NamedSharding) -> Any:
    """Place host data onto a (possibly multi-process) sharding. Single
    process: plain device_put. Multi-process: every process contributes the
    block for its own devices via ``make_array_from_process_local_data`` —
    ``device_put`` cannot address other hosts' devices."""
    if jax.process_count() == 1:
        return jax.device_put(tree, sharding)
    return jax.tree_util.tree_map(
        lambda x: jax.make_array_from_process_local_data(sharding, np.asarray(x)), tree
    )


def place_by_specs(tree: Any, mesh: Mesh, specs: Any) -> Any:
    """Place a host tree leaf-by-leaf per a matching PartitionSpec tree.
    Every process passes the same full GLOBAL values; the multi-process path
    uses ``make_array_from_callback`` (each process serves exactly its
    addressable shards' slices — correct even when a sharded axis spans
    processes). Used by the TP and PP param placements."""

    def place(x, s):
        x = np.asarray(x)
        sharding = NamedSharding(mesh, s)
        if jax.process_count() == 1:
            return jax.device_put(x, sharding)
        return jax.make_array_from_callback(x.shape, sharding, lambda idx: x[idx])

    return jax.tree_util.tree_map(place, tree, specs)


@jax.jit
def _copy_leaves(leaves):
    return [jnp.copy(x) for x in leaves]


def device_copy(leaves: list) -> list:
    """Fresh on-device buffers for a list of ``jax.Array`` leaves — the
    checkpoint snapshot stage's defensive copy. The copies are owned by the
    snapshot alone, so a later train dispatch that DONATES the originals
    (every MNIST-path step builder donates by default) can never invalidate
    what the background device→host fetch reads. One asynchronous dispatch;
    the cost is one transient extra copy of the tree in device memory — the
    device half of the snapshot double buffer. Sharded inputs keep their
    shardings (the copy is collective-free), so every process must call this
    at the same program point in multi-process runs, like any jit."""
    return _copy_leaves(leaves)


def replicate(tree: Any, mesh: Mesh) -> Any:
    """Place a pytree fully-replicated over the mesh (params/opt state live in
    HBM once per device — the reference instead kept one copy on ps hosts and
    shipped it over the network every step). Multi-process: every process must
    pass the same host values (chief-seeded init or a restored checkpoint).

    Caveat: when a leaf is already a device array with a compatible sharding,
    ``device_put`` may return it as-is (no copy). Donating the result to a
    train step then invalidates the caller's original array. Keep initial
    params host-side (numpy) if you need them after training starts."""
    return _to_global(tree, NamedSharding(mesh, P()))


def shard_batch(batch: Batch, mesh: Mesh) -> Batch:
    """Split dim 0 of every array over the 'data' axis.

    Multi-process: ``batch`` is this process's LOCAL portion (global dim 0 =
    local dim 0 × process_count) — each worker feeds its own independently
    sampled examples, the SPMD analog of the reference's per-worker
    independent shuffles (``demo2/train.py:182``). For identical-on-all-hosts
    data (eval sweeps) use :func:`shard_global_batch`."""
    sharding = NamedSharding(mesh, P(("data", "model")))
    return _to_global(batch, sharding)


def shard_global_batch(batch: Batch, mesh: Mesh, spec: P | None = None) -> Batch:
    """Shard a batch that every process holds IDENTICALLY (deterministic eval
    chunks / step-keyed LM batches): the global array equals the logical
    batch exactly once, each process contributing its own devices' slices.
    ``spec`` defaults to the 2-axis batch sharding; pass e.g.
    ``P('data', 'pipe')`` on a ('data','pipe','model') mesh.

    Multi-process placement goes through ``make_array_from_callback`` (each
    process serves exactly its addressable shards' index slices of the full
    global value) — correct for ANY spec, including ones where the leading
    batch axis does NOT span the processes (a batch-dim slice-by-process
    would hand devices garbage there)."""
    resolved = spec if spec is not None else P(("data", "model"))
    if jax.process_count() == 1:
        return _to_global(batch, NamedSharding(mesh, resolved))
    return place_by_specs(
        batch, mesh, jax.tree_util.tree_map(lambda _: resolved, batch)
    )


def _global_grad_norm(grads: Any) -> jnp.ndarray:
    """Global L2 norm of a gradient tree, accumulated in f32 (the same
    quantity optax's clip_by_global_norm gates on)."""
    leaves = jax.tree_util.tree_leaves(grads)
    total = sum(jnp.sum(jnp.square(g.astype(jnp.float32))) for g in leaves)
    return jnp.sqrt(total)


def guarded_apply(tx, params, opt_state, grads):
    """Non-finite-step guard: apply the optimizer update only when the global
    gradient norm is finite; otherwise keep params AND opt state untouched
    (a NaN step must not advance Adam's moments either — one poisoned moment
    buffer corrupts every later step). Returns
    ``(params, opt_state, skipped)`` with ``skipped`` a 0/1 f32 scalar the
    loops aggregate into the ``skipped_nonfinite`` metric.

    ``lax.cond`` keeps the gate jit/scan-compatible: the predicate is
    replicated across the mesh (grads are post-pmean), so every device takes
    the same branch."""
    finite = jnp.isfinite(_global_grad_norm(grads))

    def _apply(operands):
        p, o, g = operands
        g = fence_grads(g)
        updates, o = tx.update(g, o, p)
        p = jax.tree_util.tree_map(lambda a, u: a + u, p, updates)
        return p, o

    def _skip(operands):
        p, o, _ = operands
        return p, o

    params, opt_state = lax.cond(finite, _apply, _skip, (params, opt_state, grads))
    return params, opt_state, 1.0 - finite.astype(jnp.float32)


def _shard_index(data_axes: tuple[str, str]):
    """Flat per-device index over the (data, model) axes — the one identity
    used by both the dropout stream and the pool-sampling stream."""
    return lax.axis_index(data_axes[0]) * lax.axis_size(data_axes[1]) + lax.axis_index(
        data_axes[1]
    )


def _grad_and_metrics(apply_fn: Callable, loss_fn: Callable, params, batch, rng):
    """One forward+backward on a local batch shard: the single source of
    truth for the train-step loss body (plain, fused, pool and accumulation
    paths all call this)."""

    def compute_loss(p):
        logits = apply_fn(
            {"params": p}, batch["image"], train=True, rngs={"dropout": rng}
        )
        return loss_fn(logits, batch["label"]), logits

    (loss, logits), grads = jax.value_and_grad(compute_loss, has_aux=True)(params)
    return grads, loss, accuracy(logits, batch["label"])


def _make_shard_step(
    apply_fn: Callable,
    tx,
    loss_fn: Callable,
    data_axes: tuple[str, str] = ("data", "model"),
    guard_nonfinite: bool = True,
):
    """The per-step SPMD body shared by :func:`build_train_step` (one step per
    dispatch) and :func:`build_multi_step` (k steps per dispatch)."""

    def _shard_step(params, opt_state, global_step, batch, rng):
        # Distinct dropout noise per step (fold in the on-device global step —
        # no per-step host-side key derivation/dispatch) and per shard.
        shard_id = _shard_index(data_axes)
        rng = jax.random.fold_in(jax.random.fold_in(rng, global_step), shard_id)
        grads, loss, acc = _grad_and_metrics(apply_fn, loss_fn, params, batch, rng)
        # THE collective: gradient mean over ICI (replaces worker->ps gRPC push).
        grads = lax.pmean(grads, data_axes)
        loss = lax.pmean(loss, data_axes)
        acc = lax.pmean(acc, data_axes)
        metrics = {"loss": loss, "accuracy": acc}
        if guard_nonfinite:
            params, opt_state, skipped = guarded_apply(tx, params, opt_state, grads)
            metrics["skipped_nonfinite"] = skipped
        else:
            grads = fence_grads(grads)
            updates, opt_state = tx.update(grads, opt_state, params)
            params = jax.tree_util.tree_map(lambda p, u: p + u, params, updates)
        # global_step advances either way — a skipped update must not shift
        # the data/RNG alignment of every later step.
        return params, opt_state, global_step + 1, metrics

    return _shard_step


def build_train_step(
    apply_fn: Callable,
    tx,
    mesh: Mesh,
    loss_fn: Callable = softmax_cross_entropy,
    donate: bool = True,
    guard_nonfinite: bool = True,
):
    """Build a jitted SPMD train step.

    step(params, opt_state, global_step, batch, rng)
        -> (params, opt_state, global_step, metrics)

    ``global_step`` is the reference's chief-maintained global step
    (``demo2/train.py:146-149``) — here every device holds the same
    replicated counter, incremented exactly once per synchronous step.
    With ``guard_nonfinite`` (default) a non-finite global grad norm skips
    the update (see :func:`guarded_apply`) and metrics carry a 0/1
    ``skipped_nonfinite`` scalar.
    """
    shard_fn = jax.shard_map(
        _make_shard_step(apply_fn, tx, loss_fn, guard_nonfinite=guard_nonfinite),
        mesh=mesh,
        in_specs=(P(), P(), P(), P(("data", "model")), P()),
        out_specs=(P(), P(), P(), P()),
        check_vma=False,
    )
    donate_args = (0, 1, 2) if donate else ()
    return jax.jit(shard_fn, donate_argnums=donate_args)


def build_multi_step(
    apply_fn: Callable,
    tx,
    mesh: Mesh,
    loss_fn: Callable = softmax_cross_entropy,
    donate: bool = True,
    guard_nonfinite: bool = True,
):
    """k fused train steps per dispatch: ``lax.scan`` over a stacked batch.

    multi_step(params, opt_state, global_step, batches, rng)
        -> (params, opt_state, global_step, metrics)   # metrics stacked (k,)

    ``batches`` arrays carry a leading steps dim: ``image (k, B, ...)``. One
    XLA program runs k optimizer steps back-to-back on device, so the
    per-dispatch Python/runtime overhead — what dominates small-model steps
    like the reference's MNIST convnet — is paid once per k steps instead of
    every step. Semantics are identical to k calls of :func:`build_train_step`
    (same per-step RNG folding via the carried global_step).
    """
    step = _make_shard_step(apply_fn, tx, loss_fn, guard_nonfinite=guard_nonfinite)

    def _shard_multi(params, opt_state, global_step, batches, rng):
        def body(carry, batch):
            p, o, g = carry
            p, o, g, metrics = step(p, o, g, batch, rng)
            return (p, o, g), metrics

        (params, opt_state, global_step), metrics = lax.scan(
            body, (params, opt_state, global_step), batches
        )
        return params, opt_state, global_step, metrics

    shard_fn = jax.shard_map(
        _shard_multi,
        mesh=mesh,
        in_specs=(P(), P(), P(), P(None, ("data", "model")), P()),
        out_specs=(P(), P(), P(), P()),
        check_vma=False,
    )
    donate_args = (0, 1, 2) if donate else ()
    return jax.jit(shard_fn, donate_argnums=donate_args)


def build_accum_train_step(
    apply_fn: Callable,
    tx,
    mesh: Mesh,
    loss_fn: Callable = softmax_cross_entropy,
    donate: bool = True,
    guard_nonfinite: bool = True,
):
    """Gradient accumulation: ONE optimizer step from k microbatch gradient
    means — the way to train at an effective batch size whose activations
    don't fit HBM (each microbatch's activations are freed before the next;
    only the gradient accumulator persists).

    accum_step(params, opt_state, global_step, batches, rng)
        -> (params, opt_state, global_step, metrics)

    ``batches`` arrays carry a leading microbatch dim: ``image
    (k, B_micro, ...)`` (shard with :func:`stack_shard_batches`); k is taken
    from that dim, so the same compiled step serves any microbatch count of
    the same shape. With equal microbatch sizes, the mean-of-means equals
    the full-batch gradient mean, so semantics match one
    :func:`build_train_step` call on the concatenated batch (exact up to
    float summation order). Unlike :func:`build_multi_step` — k *optimizer*
    steps per dispatch — this runs k *gradient* passes and one update;
    ``global_step`` advances by 1. Dropout noise is folded per microbatch
    (distinct masks, as k separate forward passes would get).
    """
    data_axes = ("data", "model")

    def _shard_accum(params, opt_state, global_step, batches, rng):
        k = jax.tree_util.tree_leaves(batches)[0].shape[0]
        shard_id = _shard_index(data_axes)
        base = jax.random.fold_in(jax.random.fold_in(rng, global_step), shard_id)

        def body(carry, inp):
            acc, i = carry
            grads, loss, acc_metric = _grad_and_metrics(
                apply_fn, loss_fn, params, inp, jax.random.fold_in(base, i)
            )
            acc = jax.tree_util.tree_map(lambda a, g_: a + g_, acc, grads)
            return (acc, i + 1), (loss, acc_metric)

        zero = jax.tree_util.tree_map(jnp.zeros_like, params)
        (grad_sum, _), (losses, accs) = lax.scan(
            body, (zero, jnp.zeros((), jnp.int32)), batches
        )
        grads = jax.tree_util.tree_map(lambda g_: g_ / k, grad_sum)
        grads = lax.pmean(grads, data_axes)
        loss = lax.pmean(jnp.mean(losses), data_axes)
        acc = lax.pmean(jnp.mean(accs), data_axes)
        metrics = {"loss": loss, "accuracy": acc}
        if guard_nonfinite:
            params, opt_state, skipped = guarded_apply(tx, params, opt_state, grads)
            metrics["skipped_nonfinite"] = skipped
        else:
            grads = fence_grads(grads)
            updates, opt_state = tx.update(grads, opt_state, params)
            params = jax.tree_util.tree_map(lambda p, u: p + u, params, updates)
        return params, opt_state, global_step + 1, metrics

    shard_fn = jax.shard_map(
        _shard_accum,
        mesh=mesh,
        in_specs=(P(), P(), P(), P(None, ("data", "model")), P()),
        out_specs=(P(), P(), P(), P()),
        check_vma=False,
    )
    donate_args = (0, 1, 2) if donate else ()
    return jax.jit(shard_fn, donate_argnums=donate_args)


def build_pool_train_fn(
    apply_fn: Callable,
    tx,
    mesh: Mesh,
    batch_per_shard: int,
    steps_per_call: int,
    loss_fn: Callable = softmax_cross_entropy,
    donate: bool = True,
    guard_nonfinite: bool = True,
):
    """Device-resident-dataset training: k steps per dispatch, batches
    gathered on device from an HBM-resident example pool.

    pool_fn(params, opt_state, global_step, pool, rng)
        -> (params, opt_state, global_step, metrics)   # metrics stacked (k,)

    ``pool`` is the full (sharded) training set placed once with
    :func:`shard_batch`; each device samples ``batch_per_shard`` examples per
    step from its local shard (uniform with replacement, keyed on the carried
    global step). The hot loop involves the host ONLY to dispatch — no batch
    assembly, no HBM transfer. This is the logical endpoint of the prefetch
    story: the reference re-uploaded every batch via feed_dict
    (``demo1/train.py:153-155``); per-shard independent sampling mirrors the
    reference's per-worker independent shuffles (``demo2/train.py:182``).
    """
    data_axes = ("data", "model")
    step = _make_shard_step(apply_fn, tx, loss_fn, data_axes, guard_nonfinite=guard_nonfinite)

    def _shard_pool_train(params, opt_state, global_step, pool, rng):
        n_local = pool["image"].shape[0]
        shard_id = _shard_index(data_axes)

        def body(carry, _):
            p, o, g = carry
            # Separate index stream from the dropout stream (extra fold tag).
            idx_key = jax.random.fold_in(
                jax.random.fold_in(jax.random.fold_in(rng, 0x5A11), g), shard_id
            )
            idx = jax.random.randint(idx_key, (batch_per_shard,), 0, n_local)
            batch = {k: jnp.take(v, idx, axis=0) for k, v in pool.items()}
            p, o, g, metrics = step(p, o, g, batch, rng)
            return (p, o, g), metrics

        (params, opt_state, global_step), metrics = lax.scan(
            body, (params, opt_state, global_step), None, length=steps_per_call
        )
        return params, opt_state, global_step, metrics

    shard_fn = jax.shard_map(
        _shard_pool_train,
        mesh=mesh,
        in_specs=(P(), P(), P(), P(("data", "model")), P()),
        out_specs=(P(), P(), P(), P()),
        check_vma=False,
    )
    donate_args = (0, 1, 2) if donate else ()
    return jax.jit(shard_fn, donate_argnums=donate_args)


def shard_pool(images, labels, mesh: Mesh) -> Batch:
    """Place a whole training set in HBM for :func:`build_pool_train_fn`,
    truncated to a multiple of the mesh size (shards must be even; the loss
    is <mesh_size examples). Multi-process: every process holds the same full
    dataset on the host (each downloads/loads its own copy, as the reference's
    workers did) and contributes its devices' slice."""
    n = np.asarray(images).shape[0]
    n -= n % mesh.devices.size
    return shard_global_batch(
        {"image": np.asarray(images)[:n], "label": np.asarray(labels)[:n]}, mesh
    )


def stack_shard_batches(batches: list[Batch], mesh: Mesh) -> Batch:
    """Stack k host batches into one ``(k, B, ...)`` pytree sharded for
    :func:`build_multi_step` (steps dim replicated, batch dim sharded).
    Multi-process: like :func:`shard_batch`, each process passes its LOCAL
    k batches (global batch dim = local × process_count)."""
    stacked = {
        k: np.stack([np.asarray(b[k]) for b in batches]) for k in batches[0]
    }
    return _to_global(stacked, NamedSharding(mesh, P(None, ("data", "model"))))


def build_lm_train_step(cfg, tx, mesh: Mesh, donate: bool = False):
    """Data-parallel LM train step: tokens ``(B, S)`` sharded over the mesh,
    replicated params, ``lax.pmean`` gradient sync — the LM counterpart of
    :func:`build_train_step`, shared by ``tools/train_lm.py`` (``dp`` mode)
    and the bench harness.

    step(params, opt_state, global_step, tokens, rng)
        -> (params, opt_state, global_step, {"loss"})
    """
    from distributed_tensorflow_tpu.models.transformer import (
        TransformerLM,
        next_token_loss,
    )
    from distributed_tensorflow_tpu.obs import install_runtime_spans

    install_runtime_spans()  # the step's trace, lowering and compile
    model = TransformerLM(cfg)

    def _shard_step(p, o, g, tokens, key):
        del key  # no dropout in the LM pretraining path

        def compute(pp_):
            logits = model.apply({"params": pp_}, tokens)
            return next_token_loss(logits, tokens)

        loss, grads = jax.value_and_grad(compute)(p)
        grads = lax.pmean(grads, ("data", "model"))
        loss = lax.pmean(loss, ("data", "model"))
        grads = fence_grads(grads)
        updates, o = tx.update(grads, o, p)
        p = jax.tree_util.tree_map(lambda a, u: a + u, p, updates)
        return p, o, g + 1, {"loss": loss}

    shard_fn = jax.shard_map(
        _shard_step,
        mesh=mesh,
        in_specs=(P(), P(), P(), P(("data", "model"), None), P()),
        out_specs=(P(), P(), P(), P()),
        check_vma=False,
    )
    donate_args = (0, 1) if donate else ()
    return jax.jit(shard_fn, donate_argnums=donate_args)


def build_lm_multi_step(cfg, tx, mesh: Mesh, donate: bool = False):
    """k fused LM train steps per dispatch: ``lax.scan`` over stacked tokens
    ``(k, B, S)`` (steps dim replicated, batch dim sharded) — the LM
    counterpart of :func:`build_multi_step`, used by ``tools/train_lm.py
    --steps_per_call``. Semantics identical to k calls of
    :func:`build_lm_train_step`; returns stacked ``(k,)`` losses."""
    from distributed_tensorflow_tpu.models.transformer import (
        TransformerLM,
        next_token_loss,
    )

    model = TransformerLM(cfg)

    def _shard_multi(p, o, g, tokens_k, key):
        del key  # no dropout in the LM pretraining path

        def body(carry, tokens):
            p_, o_, g_ = carry

            def compute(pp_):
                logits = model.apply({"params": pp_}, tokens)
                return next_token_loss(logits, tokens)

            loss, grads = jax.value_and_grad(compute)(p_)
            grads = lax.pmean(grads, ("data", "model"))
            loss = lax.pmean(loss, ("data", "model"))
            grads = fence_grads(grads)
            updates, o_ = tx.update(grads, o_, p_)
            p_ = jax.tree_util.tree_map(lambda a, u: a + u, p_, updates)
            return (p_, o_, g_ + 1), loss

        (p, o, g), losses = lax.scan(body, (p, o, g), tokens_k)
        return p, o, g, {"loss": losses}

    shard_fn = jax.shard_map(
        _shard_multi,
        mesh=mesh,
        in_specs=(P(), P(), P(), P(None, ("data", "model"), None), P()),
        out_specs=(P(), P(), P(), P()),
        check_vma=False,
    )
    donate_args = (0, 1) if donate else ()
    return jax.jit(shard_fn, donate_argnums=donate_args)


def build_eval_step(apply_fn: Callable, mesh: Mesh):
    """Jitted SPMD eval step: returns summed correct-count and summed
    per-example cross-entropy over the global (sharded) batch so the host can
    aggregate exact full-dataset accuracy across uneven batch loops."""

    def _shard_eval(params, batch):
        logits = apply_fn({"params": params}, batch["image"], train=False)
        # ``weight`` masks padding rows (see ``pad_to_multiple``).
        w = batch.get("weight", jnp.ones((batch["image"].shape[0],), jnp.float32))
        correct = lax.psum(jnp.sum(correct_mask(logits, batch["label"]) * w), ("data", "model"))
        loss_sum = lax.psum(
            jnp.sum(per_example_cross_entropy(logits, batch["label"]) * w), ("data", "model")
        )
        return correct, loss_sum

    shard_fn = jax.shard_map(
        _shard_eval,
        mesh=mesh,
        in_specs=(P(), P(("data", "model"))),
        out_specs=(P(), P()),
        check_vma=False,
    )
    return jax.jit(shard_fn)


def build_apply_fn(apply_fn: Callable, mesh: Mesh):
    """Jitted sharded inference: logits for a (possibly large) batch."""

    def _shard_apply(params, images):
        return apply_fn({"params": params}, images, train=False)

    shard_fn = jax.shard_map(
        _shard_apply,
        mesh=mesh,
        in_specs=(P(), P(("data", "model"))),
        out_specs=P(("data", "model")),
        check_vma=False,
    )
    return jax.jit(shard_fn)


def pad_to_multiple(batch: Batch, multiple: int) -> tuple[Batch, int]:
    """Pad dim 0 up to a multiple of the mesh size (XLA needs static, evenly
    divisible shard shapes) and attach a ``weight`` mask (1=real, 0=padding).
    Returns (padded batch, original size)."""
    n = next(iter(batch.values())).shape[0]
    rem = (-n) % multiple
    weight = np.concatenate([np.ones(n, np.float32), np.zeros(rem, np.float32)])
    padded = {
        k: np.concatenate([np.asarray(v), np.zeros((rem,) + v.shape[1:], v.dtype)])
        if rem
        else np.asarray(v)
        for k, v in batch.items()
    }
    padded["weight"] = weight
    return padded, n
