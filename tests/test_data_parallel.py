"""SPMD data-parallel tests on the 8-device virtual CPU mesh (reference C6
parity: this is the multi-worker training story, minus parameter servers)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from distributed_tensorflow_tpu.models.mnist_cnn import MnistCNN
from distributed_tensorflow_tpu.parallel import data_parallel as dp
from distributed_tensorflow_tpu.parallel.mesh import make_mesh
from jax.sharding import PartitionSpec as P


@pytest.fixture(scope="module")
def setup():
    model = MnistCNN(compute_dtype=jnp.float32, dropout_rate=0.0)
    tx = optax.adam(1e-3)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 784)))["params"]
    return model, tx, params


def _fake_batch(n, seed=0):
    rng = np.random.default_rng(seed)
    images = rng.random((n, 784)).astype(np.float32)
    labels = np.eye(10, dtype=np.float32)[rng.integers(0, 10, n)]
    return {"image": images, "label": labels}


def test_mesh_shapes():
    assert jax.device_count() == 8
    mesh = make_mesh()
    assert dict(mesh.shape) == {"data": 8, "model": 1}
    mesh2 = make_mesh(model_parallel=2)
    assert dict(mesh2.shape) == {"data": 4, "model": 2}
    mesh1 = make_mesh(num_devices=1)
    assert mesh1.devices.size == 1


def test_train_step_runs_and_counts(setup):
    model, tx, params = setup
    mesh = make_mesh()
    step_fn = dp.build_train_step(model.apply, tx, mesh, donate=False)
    p = dp.replicate(params, mesh)
    o = dp.replicate(tx.init(params), mesh)
    g = dp.replicate(jnp.zeros((), jnp.int32), mesh)
    batch = dp.shard_batch(_fake_batch(64), mesh)
    p, o, g, metrics = step_fn(p, o, g, batch, jax.random.PRNGKey(0))
    assert int(jax.device_get(g)) == 1
    assert np.isfinite(float(metrics["loss"]))


def test_dp_equals_single_device(setup):
    """8-way sharded gradient step == single-device step on the same global
    batch: the psum-mean must be exactly a big-batch gradient. Uses SGD so the
    update is linear in the gradient (an Adam step would amplify float noise
    through g/(|g|+eps))."""
    model, _, params = setup
    tx = optax.sgd(0.1)
    batch = _fake_batch(64)

    results = {}
    for ndev in (1, 8):
        mesh = make_mesh(num_devices=ndev)
        step_fn = dp.build_train_step(model.apply, tx, mesh, donate=False)
        p = dp.replicate(params, mesh)
        o = dp.replicate(tx.init(params), mesh)
        g = dp.replicate(jnp.zeros((), jnp.int32), mesh)
        sharded = dp.shard_batch(batch, mesh)
        p, o, g, m = step_fn(p, o, g, sharded, jax.random.PRNGKey(7))
        results[ndev] = (jax.device_get(p), float(m["loss"]))

    np.testing.assert_allclose(results[1][1], results[8][1], rtol=1e-5)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, atol=1e-5),
        results[1][0],
        results[8][0],
    )


def test_eval_step_exact_counts(setup):
    model, tx, params = setup
    mesh = make_mesh()
    eval_fn = dp.build_eval_step(model.apply, mesh)
    batch = _fake_batch(40)  # not divisible by 8 -> exercises padding/mask
    padded, n = dp.pad_to_multiple(batch, 8)
    assert padded["image"].shape[0] == 40  # 40 % 8 == 0 already
    batch27 = _fake_batch(27)
    padded27, n27 = dp.pad_to_multiple(batch27, 8)
    assert padded27["image"].shape[0] == 32 and n27 == 27
    p = dp.replicate(params, mesh)
    correct, loss_sum = eval_fn(p, dp.shard_batch(padded27, mesh))
    # Reference computation on host:
    logits = model.apply({"params": params}, jnp.asarray(batch27["image"]))
    host_correct = float(
        np.sum(np.argmax(np.asarray(logits), -1) == np.argmax(batch27["label"], -1))
    )
    np.testing.assert_allclose(float(correct), host_correct)
    assert 0 <= float(correct) <= 27


def test_model_parallel_mesh_train_step(setup):
    """The ('data','model') 2-D mesh path compiles and matches 1-device
    results (model axis currently replicates compute; reserved for TP)."""
    model, tx, params = setup
    batch = _fake_batch(32)
    mesh = make_mesh(model_parallel=2)  # 4x2
    step_fn = dp.build_train_step(model.apply, tx, mesh, donate=False)
    p = dp.replicate(params, mesh)
    o = dp.replicate(tx.init(params), mesh)
    g = dp.replicate(jnp.zeros((), jnp.int32), mesh)
    p, o, g, m = step_fn(p, o, g, dp.shard_batch(batch, mesh), jax.random.PRNGKey(3))
    assert np.isfinite(float(m["loss"]))
    assert int(jax.device_get(g)) == 1


def test_multi_step_equals_k_single_steps(setup):
    """build_multi_step(k) must be semantically identical to k sequential
    build_train_step calls (same RNG folding via carried global_step)."""
    model, tx, params = setup
    mesh = make_mesh()
    k, per_batch = 4, 16

    single = dp.build_train_step(model.apply, tx, mesh, donate=False)
    multi = dp.build_multi_step(model.apply, tx, mesh, donate=False)
    rng = jax.random.PRNGKey(7)

    batches = [_fake_batch(per_batch, seed=s) for s in range(k)]

    p1 = dp.replicate(params, mesh)
    o1 = dp.replicate(tx.init(params), mesh)
    g1 = dp.replicate(jnp.zeros((), jnp.int32), mesh)
    losses = []
    for b in batches:
        p1, o1, g1, m = single(p1, o1, g1, dp.shard_batch(b, mesh), rng)
        losses.append(float(m["loss"]))

    p2 = dp.replicate(params, mesh)
    o2 = dp.replicate(tx.init(params), mesh)
    g2 = dp.replicate(jnp.zeros((), jnp.int32), mesh)
    stacked = dp.stack_shard_batches(batches, mesh)
    p2, o2, g2, metrics = multi(p2, o2, g2, stacked, rng)

    assert int(jax.device_get(g2)) == k
    np.testing.assert_allclose(
        np.asarray(jax.device_get(metrics["loss"])), np.asarray(losses), rtol=1e-5
    )
    # scan vs unrolled compile to differently-fused programs — float noise
    # only (measured max |diff| ~5e-6 across leaves)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(jax.device_get(a)), np.asarray(jax.device_get(b)), rtol=1e-4, atol=1e-5
        ),
        p1,
        p2,
    )


def test_multi_step_dropout_rng_advances(setup):
    """With dropout active, each scanned step must get distinct noise (the
    on-device global_step fold): two fused steps on the SAME batch produce
    different losses."""
    _, tx, _ = setup
    model = MnistCNN(compute_dtype=jnp.float32, dropout_rate=0.5)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 784)), train=False)["params"]
    mesh = make_mesh()
    multi = dp.build_multi_step(model.apply, optax.sgd(0.0), mesh, donate=False)
    b = _fake_batch(16, seed=1)
    stacked = dp.stack_shard_batches([b, b], mesh)
    p = dp.replicate(params, mesh)
    o = dp.replicate(optax.sgd(0.0).init(params), mesh)
    g = dp.replicate(jnp.zeros((), jnp.int32), mesh)
    _, _, _, metrics = multi(p, o, g, stacked, jax.random.PRNGKey(3))
    losses = np.asarray(jax.device_get(metrics["loss"]))
    assert losses[0] != losses[1]  # lr=0: only the dropout mask differs


def test_pool_train_fn_learns_and_counts(setup):
    """Device-resident-pool training: correct step accounting, distinct
    batches per step, and loss decreases on a separable pool."""
    model, tx, params = setup
    mesh = make_mesh()
    k = 8
    rng = np.random.default_rng(0)
    n = 256
    labels_idx = rng.integers(0, 10, n)
    # Make the pool trivially separable: image = one-hot-ish signal per class.
    images = np.zeros((n, 784), np.float32)
    images[np.arange(n), labels_idx * 7] = 1.0
    pool_host = {
        "image": images,
        "label": np.eye(10, dtype=np.float32)[labels_idx],
    }
    pool = dp.shard_batch(pool_host, mesh)
    tx2 = optax.adam(3e-3)
    fn = dp.build_pool_train_fn(model.apply, tx2, mesh, batch_per_shard=8, steps_per_call=k, donate=False)
    p = dp.replicate(params, mesh)
    o = dp.replicate(tx2.init(params), mesh)
    g = dp.replicate(jnp.zeros((), jnp.int32), mesh)
    first = None
    for _ in range(12):
        p, o, g, metrics = fn(p, o, g, pool, jax.random.PRNGKey(5))
        losses = np.asarray(jax.device_get(metrics["loss"]))
        assert losses.shape == (k,)
        if first is None:
            first = losses[0]
            # Distinct on-device batches per scanned step (index stream keyed
            # on global_step): consecutive losses must not all be identical.
            assert not np.allclose(losses, losses[0])
    assert int(jax.device_get(g)) == 12 * k
    assert losses[-1] < first


def test_pool_train_fn_deterministic(setup):
    model, tx, params = setup
    mesh = make_mesh()
    rng = np.random.default_rng(1)
    pool_host = _fake_batch(128, seed=9)
    pool = dp.shard_batch(pool_host, mesh)
    fn = dp.build_pool_train_fn(model.apply, tx, mesh, batch_per_shard=4, steps_per_call=3, donate=False)

    def run():
        p = dp.replicate(params, mesh)
        o = dp.replicate(tx.init(params), mesh)
        g = dp.replicate(jnp.zeros((), jnp.int32), mesh)
        p, o, g, m = fn(p, o, g, pool, jax.random.PRNGKey(2))
        return np.asarray(jax.device_get(m["loss"]))

    np.testing.assert_array_equal(run(), run())


def test_shard_pool_truncates_to_mesh_multiple(setup):
    _, _, _ = setup
    mesh = make_mesh()  # 8 devices
    images = np.zeros((29, 784), np.float32)
    labels = np.eye(10, dtype=np.float32)[np.zeros(29, np.int64)]
    pool = dp.shard_pool(images, labels, mesh)
    assert pool["image"].shape == (24, 784)
    assert pool["label"].shape == (24, 10)


def test_accum_step_matches_full_batch_step():
    """One accumulated step over k microbatches == one plain step over the
    concatenated batch (mean of equal-size microbatch grads == full-batch
    grad mean). Dropout off — the full-batch step draws one mask where
    accumulation correctly draws one per microbatch."""
    import optax

    from distributed_tensorflow_tpu.models.mnist_cnn import MnistCNN

    mesh = make_mesh()
    model = MnistCNN(dropout_rate=0.0, compute_dtype=jnp.float32)
    host = jax.device_get(
        model.init(jax.random.PRNGKey(0), jnp.zeros((1, 784), jnp.float32))["params"]
    )
    tx = optax.adam(1e-3)
    rng = np.random.default_rng(0)
    k, bsz = 4, 16
    micros = [
        {
            "image": rng.random((bsz, 784), np.float32),
            "label": np.eye(10, dtype=np.float32)[rng.integers(0, 10, bsz)],
        }
        for _ in range(k)
    ]
    full = {kk: np.concatenate([m[kk] for m in micros]) for kk in micros[0]}
    key = jax.random.PRNGKey(5)

    p = dp.replicate(host, mesh)
    o = dp.replicate(jax.device_get(tx.init(host)), mesh)
    g = dp.replicate(jnp.zeros((), jnp.int32), mesh)
    plain = dp.build_train_step(model.apply, tx, mesh, donate=False)
    p1, o1, g1, m1 = plain(p, o, g, dp.shard_batch(full, mesh), key)

    pa = dp.replicate(host, mesh)
    oa = dp.replicate(jax.device_get(tx.init(host)), mesh)
    ga = dp.replicate(jnp.zeros((), jnp.int32), mesh)
    accum = dp.build_accum_train_step(model.apply, tx, mesh, donate=False)
    stacked = dp.stack_shard_batches(micros, mesh)
    pa1, oa1, ga1, ma1 = accum(pa, oa, ga, stacked, key)

    assert int(jax.device_get(ga1)) == 1  # one optimizer step, not k
    np.testing.assert_allclose(
        float(jax.device_get(ma1["loss"])), float(jax.device_get(m1["loss"])), rtol=1e-6
    )
    for a, b in zip(
        jax.tree_util.tree_leaves(jax.device_get(pa1)),
        jax.tree_util.tree_leaves(jax.device_get(p1)),
    ):
        # mean-of-means vs full-batch mean differ in float summation order;
        # Adam's rsqrt amplifies near-zero second moments slightly.
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


def test_accum_step_distinct_dropout_per_microbatch():
    """With dropout on, microbatches of identical data must produce
    different losses within the scan (distinct masks per microbatch)."""
    import optax

    from distributed_tensorflow_tpu.models.mnist_cnn import MnistCNN

    mesh = make_mesh()
    model = MnistCNN(dropout_rate=0.5, compute_dtype=jnp.float32)
    host = jax.device_get(
        model.init(jax.random.PRNGKey(0), jnp.zeros((1, 784), jnp.float32))["params"]
    )
    tx = optax.sgd(0.0)  # no update — we only probe the per-micro losses
    rng = np.random.default_rng(1)
    one = {
        "image": rng.random((16, 784), np.float32),
        "label": np.eye(10, dtype=np.float32)[rng.integers(0, 10, 16)],
    }
    micros = [one, one]  # identical data

    # Re-build with metrics per micro: reuse the public step and compare the
    # MEAN loss against a single-micro run — identical masks would make the
    # 2-micro mean equal the 1-micro loss exactly.
    key = jax.random.PRNGKey(2)
    p = dp.replicate(host, mesh)
    o = dp.replicate(jax.device_get(tx.init(host)), mesh)
    g = dp.replicate(jnp.zeros((), jnp.int32), mesh)
    accum2 = dp.build_accum_train_step(model.apply, tx, mesh, donate=False)
    _, _, _, m2 = accum2(p, o, g, dp.stack_shard_batches(micros, mesh), key)

    p = dp.replicate(host, mesh)
    o = dp.replicate(jax.device_get(tx.init(host)), mesh)
    g = dp.replicate(jnp.zeros((), jnp.int32), mesh)
    accum1 = dp.build_accum_train_step(model.apply, tx, mesh, donate=False)
    _, _, _, m1 = accum1(p, o, g, dp.stack_shard_batches(micros[:1], mesh), key)

    assert float(jax.device_get(m2["loss"])) != float(jax.device_get(m1["loss"]))


def test_lm_multi_step_matches_single_steps():
    """k fused LM steps (one lax.scan dispatch) == k single steps, bitwise
    (same contract build_multi_step has for the classifier path)."""
    from distributed_tensorflow_tpu.models.transformer import (
        TransformerConfig,
        TransformerLM,
    )

    mesh = make_mesh()
    cfg = TransformerConfig(
        vocab_size=32, d_model=16, num_heads=2, num_layers=2, d_ff=32,
        max_seq_len=8, compute_dtype=jnp.float32,
    )
    # SGD, not Adam: the fused scan and the standalone step compile to
    # different XLA programs, and Adam's 1/sqrt(v) at v~=0 amplifies
    # float-epsilon grad differences into visible param noise on the first
    # steps — SGD keeps the contract testable at float tolerance.
    tx = optax.sgd(0.1)
    host = jax.device_get(
        TransformerLM(cfg).init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))[
            "params"
        ]
    )
    k, batch = 3, 2 * mesh.devices.size
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg.vocab_size, (k, batch, 8)).astype(np.int32)
    key = jax.random.PRNGKey(1)

    single = dp.build_lm_train_step(cfg, tx, mesh, donate=False)
    p1 = dp.replicate(host, mesh)
    o1 = dp.replicate(jax.device_get(tx.init(host)), mesh)
    g1 = dp.replicate(jnp.zeros((), jnp.int32), mesh)
    losses1 = []
    for j in range(k):
        t = dp.shard_global_batch({"x": jnp.asarray(toks[j])}, mesh)["x"]
        p1, o1, g1, m1 = single(p1, o1, g1, t, key)
        losses1.append(float(jax.device_get(m1["loss"])))

    multi = dp.build_lm_multi_step(cfg, tx, mesh, donate=False)
    pk = dp.replicate(host, mesh)
    ok = dp.replicate(jax.device_get(tx.init(host)), mesh)
    gk = dp.replicate(jnp.zeros((), jnp.int32), mesh)
    stacked = dp.shard_global_batch(
        {"x": jnp.asarray(toks)}, mesh, spec=P(None, ("data", "model"), None)
    )["x"]
    pk, ok, gk, mk = multi(pk, ok, gk, stacked, key)

    assert int(jax.device_get(gk)) == k
    np.testing.assert_allclose(
        np.asarray(jax.device_get(mk["loss"])), np.asarray(losses1), rtol=1e-6
    )
    # Same math, but the scanned body and the standalone step compile to
    # different XLA programs (fusion/reduction order), so equality is to
    # float tolerance rather than bitwise.
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(jax.device_get(a)),
            np.asarray(jax.device_get(b)),
            rtol=1e-6,
            atol=1e-7,
        ),
        p1,
        pk,
    )
