"""FSDP (ZeRO-3) parameter sharding — exact parity with plain DP.

The reference shards VARIABLES across parameter servers via
``replica_device_setter`` (demo2/train.py:27-29) and has workers read/push
them over gRPC each step; ``parallel/fsdp.py`` is the TPU-native analog
(params + opt state 1/N per device, all_gather on use, psum_scatter for
grads). These tests pin (a) the chunk/place/gather round trip, (b) bitwise
parity of the FSDP step against ``data_parallel.build_train_step`` on the
MNIST convnet (including dropout), and (c) the TransformerLM variant against
the replicated dp-LM step.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from distributed_tensorflow_tpu.models.mnist_cnn import MnistCNN
from distributed_tensorflow_tpu.models.transformer import (
    TransformerConfig,
    TransformerLM,
    next_token_loss,
)
from distributed_tensorflow_tpu.parallel import data_parallel as dp, fsdp
from distributed_tensorflow_tpu.parallel.mesh import make_mesh


@pytest.fixture(scope="module")
def mesh():
    return make_mesh()  # 8 virtual devices, ('data','model') = (8, 1)


def tree_max_diff(a, b):
    return max(
        jax.tree_util.tree_leaves(
            jax.tree_util.tree_map(
                lambda x, y: float(np.max(np.abs(np.asarray(x) - np.asarray(y)))), a, b
            )
        )
    )


def test_chunk_place_gather_round_trip(mesh):
    # Leaf sizes chosen to exercise both the even-split and padding paths
    # (10 and 3 are not divisible by 8).
    tree = {
        "w": np.arange(64, dtype=np.float32).reshape(8, 8),
        "b": np.arange(10, dtype=np.float32),
        "t": np.arange(3, dtype=np.float32),
    }
    sharded = fsdp.shard_fsdp_params(tree, mesh)
    # Every array leaf is (n_devices, chunk), one block per device.
    n = mesh.devices.size
    for leaf in jax.tree_util.tree_leaves(sharded):
        assert leaf.shape[0] == n
        assert len(leaf.sharding.addressable_devices) == n
    back = fsdp.gather_fsdp_params(sharded, tree)
    assert tree_max_diff(back, tree) == 0.0


def test_opt_state_scalars_replicate(mesh):
    tree = {"w": np.zeros((10,), np.float32)}
    opt = fsdp.init_fsdp_opt_state(optax.adam(1e-3), tree, mesh)
    leaves = jax.tree_util.tree_leaves(opt)
    # adam: count scalar + mu/nu chunked leaves
    scalars = [l for l in leaves if l.ndim == 0]
    chunked = [l for l in leaves if l.ndim == 2]
    assert scalars and chunked
    for s in scalars:
        assert s.sharding.is_fully_replicated


# XLA:CPU only: the FSDP step's regathered params drift from the plain-DP
# step by a few f32 ulps (3.4e-6 after 3 Adam steps on jax 0.9.0, re-checked
# in PR 21 with --runxfail) because psum_scatter and psum reduce in a
# different order there. strict=True so a stack on which the match holds
# flips this back to a hard assert instead of rotting as a stale xfail.
_XFAIL_CPU_DRIFT = pytest.mark.xfail(
    jax.default_backend() == "cpu",
    reason="XLA:CPU reduces psum_scatter and psum in different orders; "
           "the FSDP==DP bitwise match drifts by f32 ulps there",
    strict=True,
)


@_XFAIL_CPU_DRIFT
def test_fsdp_step_matches_dp_step_exactly(mesh):
    """k FSDP steps == k plain-DP steps bitwise (params, loss, accuracy),
    dropout active — same per-shard RNG discipline on both paths."""
    model = MnistCNN(compute_dtype=jnp.float32)
    host = jax.device_get(
        model.init(jax.random.PRNGKey(0), jnp.zeros((1, 784), jnp.float32))["params"]
    )
    tx = optax.adam(1e-3)
    rng = np.random.default_rng(0)
    batch = {
        "image": rng.random((16, 784), np.float32),
        "label": np.eye(10, dtype=np.float32)[rng.integers(0, 10, 16)],
    }
    key = jax.random.PRNGKey(7)
    b = dp.shard_batch(batch, mesh)

    p = dp.replicate(host, mesh)
    o = dp.replicate(jax.device_get(tx.init(host)), mesh)
    g = dp.replicate(jnp.zeros((), jnp.int32), mesh)
    step_dp = dp.build_train_step(model.apply, tx, mesh, donate=False)

    pf = fsdp.shard_fsdp_params(host, mesh)
    of = fsdp.init_fsdp_opt_state(tx, host, mesh)
    gf = dp.replicate(jnp.zeros((), jnp.int32), mesh)
    step_f = fsdp.build_fsdp_train_step(model.apply, tx, mesh, host, donate=False)

    for _ in range(3):
        p, o, g, m = step_dp(p, o, g, b, key)
        pf, of, gf, mf = step_f(pf, of, gf, b, key)
        assert float(jax.device_get(m["loss"])) == float(jax.device_get(mf["loss"]))
        assert float(jax.device_get(m["accuracy"])) == float(
            jax.device_get(mf["accuracy"])
        )

    assert int(jax.device_get(gf)) == 3
    full = fsdp.gather_fsdp_params(pf, host)
    assert tree_max_diff(full, jax.device_get(p)) == 0.0


def test_fsdp_lm_step_matches_replicated_lm_step(mesh):
    """FSDP TransformerLM step == replicated dp-LM step bitwise."""
    cfg = TransformerConfig(
        vocab_size=64, d_model=32, num_heads=2, num_layers=2, d_ff=64,
        max_seq_len=16, compute_dtype=jnp.float32,
    )
    model = TransformerLM(cfg)
    host = jax.device_get(
        model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    )
    tx = optax.adam(1e-3)
    tokens = np.random.default_rng(0).integers(0, 64, (16, 16)).astype(np.int32)
    key = jax.random.PRNGKey(3)
    ts = jax.device_put(tokens, NamedSharding(mesh, P(("data", "model"), None)))

    def _shard_step(p, o, g, t, k):
        loss, grads = jax.value_and_grad(
            lambda pp: next_token_loss(model.apply({"params": pp}, t), t)
        )(p)
        grads = lax.pmean(grads, ("data", "model"))
        loss = lax.pmean(loss, ("data", "model"))
        u, o = tx.update(grads, o, p)
        return jax.tree_util.tree_map(lambda a, b_: a + b_, p, u), o, g + 1, loss

    step_dp = jax.jit(
        jax.shard_map(
            _shard_step,
            mesh=mesh,
            in_specs=(P(), P(), P(), P(("data", "model"), None), P()),
            out_specs=(P(), P(), P(), P()),
            check_vma=False,
        )
    )
    p = dp.replicate(host, mesh)
    o = dp.replicate(jax.device_get(tx.init(host)), mesh)
    g = dp.replicate(jnp.zeros((), jnp.int32), mesh)

    pf = fsdp.shard_fsdp_params(host, mesh)
    of = fsdp.init_fsdp_opt_state(tx, host, mesh)
    gf = dp.replicate(jnp.zeros((), jnp.int32), mesh)
    step_f = fsdp.build_fsdp_lm_train_step(cfg, tx, mesh, host, donate=False)

    for _ in range(2):
        p, o, g, loss = step_dp(p, o, g, ts, key)
        pf, of, gf, mf = step_f(pf, of, gf, ts, key)
        assert float(jax.device_get(loss)) == float(jax.device_get(mf["loss"]))

    full = fsdp.gather_fsdp_params(pf, host)
    assert tree_max_diff(full, jax.device_get(p)) == 0.0


def test_fsdp_step_with_scalar_param_leaf(mesh):
    """Scalar param leaves stay replicated through the whole step (a model
    with a learned temperature must not be force-chunked)."""
    host = {"w": np.ones((4, 3), np.float32), "temp": np.float32(2.0)}
    tx = optax.sgd(0.1)

    def loss_and_metrics(full, batch, rng):
        pred = batch["x"] @ full["w"] * full["temp"]
        return jnp.mean((pred - batch["y"]) ** 2), {}

    step = fsdp._build_step(
        loss_and_metrics, tx, mesh, host, P(("data", "model")), donate=False
    )
    p = fsdp.shard_fsdp_params(host, mesh)
    o = fsdp.init_fsdp_opt_state(tx, host, mesh)
    g = dp.replicate(jnp.zeros((), jnp.int32), mesh)
    rng_np = np.random.default_rng(0)
    batch = dp.shard_batch(
        {"x": rng_np.random((16, 4), np.float32), "y": rng_np.random((16, 3), np.float32)},
        mesh,
    )
    p, o, g, m = step(p, o, g, batch, jax.random.PRNGKey(0))
    assert np.isfinite(float(jax.device_get(m["loss"])))
    back = fsdp.gather_fsdp_params(p, host)
    assert back["temp"].shape == ()
    assert back["temp"] != host["temp"]  # the scalar actually trained


def test_fsdp_per_device_memory_is_sharded(mesh):
    """The point of ZeRO-3: per-device bytes ≈ total/N, not total."""
    host = {"w": np.zeros((1024, 64), np.float32)}  # 256 KiB total
    sharded = fsdp.shard_fsdp_params(host, mesh)
    leaf = sharded["w"]
    n = mesh.devices.size
    for shard in leaf.addressable_shards:
        assert shard.data.nbytes == leaf.nbytes // n
