"""Compiled-HLO collective-structure guards (VERDICT r1 #8).

Multi-chip hardware isn't attached in CI, so a regression that silently
doubles communication (an extra all-gather per layer, a psum that stops
being combined, a reduce-scatter that becomes a full all-reduce) would
only show up as a perf cliff on real pods. These tests pin the collective
op COUNTS of the three cheapest programs' optimized HLO so such a change
fails here instead.

Counts are asserted exactly, each derived in a comment. If a JAX/XLA
upgrade legitimately changes a number, re-derive it — don't loosen the
assert to a range (a range is exactly where a silent 2x hides).
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import optax

from distributed_tensorflow_tpu.models.mnist_cnn import MnistCNN
from distributed_tensorflow_tpu.models.transformer import (
    TransformerConfig,
    TransformerLM,
)
from distributed_tensorflow_tpu.parallel import (
    data_parallel as dp,
    fsdp,
    tensor_parallel as tp,
)
from distributed_tensorflow_tpu.parallel.mesh import make_mesh

_COLLECTIVES = (
    "all-reduce",
    "all-gather",
    "reduce-scatter",
    "collective-permute",
    "all-to-all",
)


def collective_counts(compiled) -> dict[str, int]:
    """Instruction-definition counts per collective op in optimized HLO
    (tuple-typed results mean the type can contain spaces, so match the
    op name right before its operand parenthesis; operand mentions like
    ``get-tuple-element(%all-reduce)`` don't match). ``ROOT``-form
    definitions count too — async-wrapped collectives sit as the ROOT of
    their wrapped computation."""
    txt = compiled.as_text()
    return {
        op: len(
            re.findall(rf"^\s*(?:ROOT )?%?\S+ = .*? {op}(?:-start)?\(", txt, re.M)
        )
        for op in _COLLECTIVES
    }


def _lm_cfg() -> TransformerConfig:
    return TransformerConfig(
        vocab_size=64, d_model=32, num_heads=2, num_layers=2, d_ff=64,
        max_seq_len=16, compute_dtype=jnp.float32,
    )


def test_dp_step_is_one_combined_all_reduce():
    mesh = make_mesh()
    model = MnistCNN(compute_dtype=jnp.float32)
    tx = optax.adam(1e-4)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 784), jnp.float32))[
        "params"
    ]
    p = dp.replicate(params, mesh)
    o = dp.replicate(tx.init(params), mesh)
    g = dp.replicate(jnp.zeros((), jnp.int32), mesh)
    batch = dp.shard_batch(
        {
            "image": np.zeros((16, 784), np.float32),
            "label": np.eye(10, dtype=np.float32)[np.zeros(16, int)],
        },
        mesh,
    )
    step = dp.build_train_step(model.apply, tx, mesh, donate=False)
    counts = collective_counts(
        step.lower(p, o, g, batch, jax.random.PRNGKey(0)).compile()
    )
    # The whole step's communication is ONE all-reduce: XLA combines the
    # per-leaf gradient psums plus the loss/accuracy pmeans into a single
    # tuple all-reduce. A second all-reduce = the combiner broke (two
    # latency-bound ICI rounds per step); any gather/scatter = params
    # stopped being replicated.
    assert counts == {
        "all-reduce": 1,
        "all-gather": 0,
        "reduce-scatter": 0,
        "collective-permute": 0,
        "all-to-all": 0,
    }, counts


def test_fsdp_step_gathers_and_scatters_per_param():
    mesh = make_mesh()
    cfg = _lm_cfg()
    host = jax.device_get(
        TransformerLM(cfg).init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))[
            "params"
        ]
    )
    tx = optax.adam(1e-3)
    step = fsdp.build_fsdp_lm_train_step(cfg, tx, mesh, host, donate=False)
    fp = fsdp.shard_fsdp_params(host, mesh)
    fo = fsdp.init_fsdp_opt_state(tx, host, mesh)
    g = dp.replicate(jnp.zeros((), jnp.int32), mesh)
    toks = jax.device_put(
        jnp.zeros((16, 16), jnp.int32),
        jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec(("data", "model"), None)),
    )
    counts = collective_counts(
        step.lower(fp, fo, g, toks, jax.random.PRNGKey(0)).compile()
    )
    # ZeRO-3 structure for this 2-layer LM (15 param leaves: embed, 2 x
    # (ln1 scale/bias..qkv/proj/ffn = 6 kernel+bias pairs -> 6 leaves) + 2
    # final-ln leaves... = 15): each leaf is all-gathered once for the
    # forward and re-gathered once for the backward (no persisted full
    # params — that's the memory contract), and each gradient leaf is
    # reduce-scattered once: 2x15 gathers, 15 scatters... the embed table
    # is additionally re-gathered for the logits matmul's backward.
    # The single all-reduce is the scalar loss pmean.
    assert counts["all-reduce"] == 1, counts
    assert counts["all-gather"] == 30, counts
    assert counts["reduce-scatter"] == 30, counts
    assert counts["collective-permute"] == 0 and counts["all-to-all"] == 0, counts


def test_tp_step_all_reduce_count():
    mesh = make_mesh(model_parallel=2)
    cfg = _lm_cfg()
    host = tp.init_tp_params(cfg, seed=0)
    tx = optax.sgd(0.1)
    step = tp.build_tp_lm_train_step(cfg, tx, mesh, host, donate=False)
    params = tp.shard_params(host, mesh)
    opt = tp.shard_params(jax.device_get(tx.init(host)), mesh)
    g = jax.device_put(
        jnp.zeros((), jnp.int32),
        jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec()),
    )
    toks = jnp.zeros((2 * mesh.shape["data"], 16), jnp.int32)
    counts = collective_counts(
        step.lower(params, opt, g, toks, jax.random.PRNGKey(0)).compile()
    )
    # Megatron structure, 2 layers: per layer the forward psums the
    # attention proj and FFN down-proj partial sums over 'model' (2), and
    # the backward psums the activation grads entering each sharded block
    # (2) = 4 per layer = 8, plus ONE combined tuple all-reduce for the
    # data-axis gradient/loss pmean = 9. More = an activation stopped
    # being kept sharded or the grad combiner broke; any gather/scatter =
    # the head/FFN sharding layout regressed.
    assert counts["all-reduce"] == 9, counts
    assert counts["all-gather"] == 0, counts
    assert counts["reduce-scatter"] == 0, counts
    assert counts["collective-permute"] == 0 and counts["all-to-all"] == 0, counts


def test_ring_attention_uses_collective_permute():
    # The SP ring's defining structure: K/V shards rotate via ppermute
    # (collective-permute), NOT via all-gather — an all-gather would mean
    # the ring degenerated into materializing the full sequence.
    from distributed_tensorflow_tpu.parallel import sequence_parallel as sp

    mesh = make_mesh()
    cfg = TransformerConfig(
        vocab_size=64, d_model=32, num_heads=2, num_layers=1,
        max_seq_len=8 * mesh.shape["data"], d_ff=64, compute_dtype=jnp.float32,
    )
    host = jax.device_get(
        TransformerLM(cfg).init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))[
            "params"
        ]
    )
    tx = optax.sgd(0.1)
    step = sp.build_lm_train_step(
        cfg, tx, mesh, data_axis="model", seq_axis="data", donate=False
    )
    p = dp.replicate(host, mesh)
    o = dp.replicate(jax.device_get(tx.init(host)), mesh)
    g = dp.replicate(jnp.zeros((), jnp.int32), mesh)
    toks = sp.shard_lm_batch(
        jnp.zeros((1, cfg.max_seq_len), jnp.int32),
        mesh,
        data_axis="model",
        seq_axis="data",
    )
    counts = collective_counts(
        step.lower(p, o, g, toks, jax.random.PRNGKey(0)).compile()
    )
    assert counts["collective-permute"] >= 1, counts
    assert counts["all-gather"] == 0, counts


# ---------------------------------------------------------------------------
# Payload BYTES guards (VERDICT r5: "multi-chip asserts count collectives but
# not bytes"). Bytes are summed over result shapes per collective DEFINITION
# (parallel.consistency.hlo_collective_bytes) and are INVARIANT to XLA's op
# combiner — N per-leaf psums and one combined tuple all-reduce move the same
# payload — so these hold even on stacks where the count asserts above drift.
# ---------------------------------------------------------------------------


def test_hlo_collective_bytes_parser():
    from distributed_tensorflow_tpu.parallel.consistency import (
        hlo_collective_bytes,
    )

    hlo = "\n".join(
        [
            "ENTRY main {",
            # plain result with layout annotation: 128*64*4 = 32768 bytes
            "  %ar0 = f32[128,64]{1,0} all-reduce(f32[128,64] %p0), to_apply=%add",
            # tuple result: 10*4 + 4 = 44 bytes
            "  ROOT %ar1 = (f32[10], f32[]) all-reduce(f32[10] %a, f32[] %b)",
            # async -start carries (operands, results): counted ONCE = 1024
            "  %ag = (bf16[256]{0}, bf16[256]{0}) all-gather-start(bf16[256] %x)",
            # operand mentions / done ops must NOT count
            "  %agd = bf16[256]{0} all-gather-done((bf16[256], bf16[256]) %ag)",
            "  %gte = f32[10] get-tuple-element((f32[10], f32[]) %ar1), index=0",
            # scalar collective-permute: 4 bytes
            "  %cp = f32[] collective-permute(f32[] %s), source_target_pairs={{0,1}}",
            "}",
        ]
    )
    bytes_found = hlo_collective_bytes(hlo)
    assert bytes_found["all-reduce"] == 128 * 64 * 4 + 44, bytes_found
    assert bytes_found["all-gather"] == 256 * 2, bytes_found
    assert bytes_found["collective-permute"] == 4, bytes_found
    assert bytes_found["reduce-scatter"] == 0 and bytes_found["all-to-all"] == 0


def test_dp_step_all_reduce_payload_bytes():
    """The DP step's whole communication payload is exactly the f32 gradient
    tree (same shapes as params) plus the two pmean'd metric scalars — a
    silent doubling of gradient traffic trips this even if the op count is
    unchanged (and vice versa)."""
    from distributed_tensorflow_tpu.parallel.consistency import (
        hlo_collective_bytes,
        tree_bytes,
    )

    mesh = make_mesh()
    model = MnistCNN(compute_dtype=jnp.float32)
    tx = optax.adam(1e-4)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 784), jnp.float32))[
        "params"
    ]
    p = dp.replicate(params, mesh)
    o = dp.replicate(tx.init(params), mesh)
    g = dp.replicate(jnp.zeros((), jnp.int32), mesh)
    batch = dp.shard_batch(
        {
            "image": np.zeros((16, 784), np.float32),
            "label": np.eye(10, dtype=np.float32)[np.zeros(16, int)],
        },
        mesh,
    )
    step = dp.build_train_step(model.apply, tx, mesh, donate=False)
    txt = step.lower(p, o, g, batch, jax.random.PRNGKey(0)).compile().as_text()
    found = hlo_collective_bytes(txt)
    assert found["all-reduce"] == tree_bytes(params) + 8, (
        found, tree_bytes(params)
    )
    assert found["all-gather"] == 0 and found["reduce-scatter"] == 0


def test_fsdp_all_gather_payload_bytes():
    """ZeRO-3's param gather happens OUTSIDE value_and_grad (DESIGN §3), so
    each padded leaf's bytes cross the wire exactly once per step: total
    all-gather payload == the sharded param tree's bytes, independent of how
    many ops XLA splits the gathers into. A 2x here means the gather moved
    inside the grad computation and is being recomputed."""
    from distributed_tensorflow_tpu.parallel.consistency import (
        hlo_collective_bytes,
        tree_bytes,
    )

    mesh = make_mesh()
    cfg = _lm_cfg()
    host = jax.device_get(
        TransformerLM(cfg).init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))[
            "params"
        ]
    )
    tx = optax.adam(1e-3)
    step = fsdp.build_fsdp_lm_train_step(cfg, tx, mesh, host, donate=False)
    fp = fsdp.shard_fsdp_params(host, mesh)
    fo = fsdp.init_fsdp_opt_state(tx, host, mesh)
    g = dp.replicate(jnp.zeros((), jnp.int32), mesh)
    toks = jax.device_put(
        jnp.zeros((16, 16), jnp.int32),
        jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec(("data", "model"), None)),
    )
    txt = step.lower(fp, fo, g, toks, jax.random.PRNGKey(0)).compile().as_text()
    found = hlo_collective_bytes(txt)
    assert found["all-gather"] == tree_bytes(fp), (found, tree_bytes(fp))
