"""Attention stack: dense / blockwise / flash / ring parity and gradients.

Runs on the 8-device CPU mesh from conftest (flash in interpret mode).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from distributed_tensorflow_tpu.ops import attention as A
from distributed_tensorflow_tpu.parallel.mesh import make_mesh
from distributed_tensorflow_tpu.parallel.ring_attention import ring_attention


def _qkv(b=2, h=2, s=32, d=8, seed=0, dtype=jnp.float32):
    r = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(r.standard_normal((b, h, s, d)), dtype)
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True])
def test_blockwise_matches_dense(causal):
    q, k, v = _qkv(s=48)
    ref = A.dense_attention(q, k, v, causal=causal)
    for block_kv in (7, 16, 48, 512):  # non-dividing block exercises padding
        out = A.blockwise_attention(q, k, v, causal=causal, block_kv=block_kv)
        np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_dense(causal):
    q, k, v = _qkv(s=64)
    ref = A.dense_attention(q, k, v, causal=causal)
    out = A.flash_attention(q, k, v, causal=causal, block_q=16, block_kv=16)
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


def test_fully_masked_rows_agree_across_tiers():
    """sq > skv causal: leading queries have negative end-aligned positions →
    no attendable keys. All tiers must output exactly 0 for those rows (dense
    would otherwise degrade to uniform-mean softmax)."""
    r = np.random.default_rng(5)
    q = jnp.asarray(r.standard_normal((1, 2, 12, 8)), jnp.float32)
    k = jnp.asarray(r.standard_normal((1, 2, 8, 8)), jnp.float32)
    v = jnp.asarray(r.standard_normal((1, 2, 8, 8)), jnp.float32)
    ref = A.dense_attention(q, k, v, causal=True)
    np.testing.assert_array_equal(np.asarray(ref[:, :, :4]), 0.0)  # rows 0-3 masked
    blk = A.blockwise_attention(q, k, v, causal=True, block_kv=4)
    np.testing.assert_allclose(blk, ref, rtol=2e-5, atol=2e-5)
    fl = A.flash_attention(q, k, v, causal=True, block_q=4, block_kv=4)
    np.testing.assert_allclose(fl, ref, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("sq,skv", [(4, 32), (16, 32), (8, 24)])
def test_causal_cross_length_matches_dense(sq, skv):
    """Sq != Skv (decode with cached keys): all tiers must share dense's
    end-aligned causal semantics — query i attends keys <= i + (Skv - Sq)."""
    r = np.random.default_rng(3)
    mk = lambda s: jnp.asarray(r.standard_normal((2, 2, s, 8)), jnp.float32)
    q, k, v = mk(sq), mk(skv), mk(skv)
    ref = A.dense_attention(q, k, v, causal=True)
    blk = A.blockwise_attention(q, k, v, causal=True, block_kv=8)
    np.testing.assert_allclose(blk, ref, rtol=2e-5, atol=2e-5)
    if sq % 4 == 0 and skv % 8 == 0:
        fl = A.flash_attention(q, k, v, causal=True, block_q=4, block_kv=8)
        np.testing.assert_allclose(fl, ref, rtol=2e-5, atol=2e-5)


def test_flash_autofits_non_divisible_blocks():
    """Requested blocks that don't divide the sequence shrink to the largest
    divisor satisfying Mosaic's sublane rule (multiple of 8), falling back to
    the full sequence for odd lengths."""
    assert A._fit_block(512, 768) == 384
    assert A._fit_block(32, 48) == 24
    assert A._fit_block(512, 509) == 509  # prime -> whole sequence
    # A long prime sequence must NOT silently fall back to one whole-sequence
    # VMEM block on real TPU (it would die deep in Mosaic, or OOM); it fails
    # at the call site with a pad-or-blockwise fix instead. Interpret mode
    # has no VMEM, so the same shape stays usable for CPU debugging.
    with pytest.raises(ValueError, match="blockwise_attention"):
        A._fit_block(512, 8191)
    assert A._fit_block(512, 8191, interpret=True) == 8191
    # An explicitly requested block past the VMEM limit that DOES divide the
    # sequence (divisor-loop path, not the fallback) is clamped with a
    # warning on real TPU — it must not reach Mosaic as a >4096-row block.
    with pytest.warns(UserWarning, match="VMEM-safe limit"):
        assert A._fit_block(8192, 8192) == A._FALLBACK_BLOCK_LIMIT
    assert A._fit_block(8192, 8192, interpret=True) == 8192
    q, k, v = _qkv(s=48)
    ref = A.dense_attention(q, k, v, causal=True)
    out = A.flash_attention(q, k, v, causal=True, block_q=32, block_kv=32)
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


def test_blockwise_gradients_match_dense():
    q, k, v = _qkv(s=24)

    def loss_via(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v, causal=True) ** 2)

    gd = jax.grad(loss_via(A.dense_attention), argnums=(0, 1, 2))(q, k, v)
    gb = jax.grad(loss_via(A.blockwise_attention), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gd, gb):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)


def test_flash_gradients_match_dense():
    q, k, v = _qkv(s=32)

    def loss_via(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v, causal=True) ** 2)

    gd = jax.grad(loss_via(A.dense_attention), argnums=(0, 1, 2))(q, k, v)
    gf = jax.grad(
        lambda q, k, v: jnp.sum(
            A.flash_attention(q, k, v, causal=True, block_q=16, block_kv=16) ** 2
        ),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b in zip(gd, gf):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)


def test_fully_masked_rows_are_zero_not_nan():
    # Query block attending to an empty causal window must produce finite
    # output (NEG_INF guard): kv strictly in the future.
    q, k, v = _qkv(s=8)
    out = A.blockwise_attention(q, k, v, causal=True, q_offset=0, kv_offset=100)
    assert np.all(np.isfinite(np.asarray(out)))
    np.testing.assert_allclose(np.asarray(out), 0.0, atol=1e-6)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_matches_dense_on_mesh(causal):
    devices = jax.devices()
    if len(devices) < 8:
        pytest.skip("needs 8 devices")
    mesh = make_mesh(num_devices=8)  # ('data': 8, 'model': 1) — seq on 'data'
    b, h, s, d = 2, 2, 64, 8
    q, k, v = _qkv(b, h, s, d, seed=3)
    ref = A.dense_attention(q, k, v, causal=causal)

    f = jax.jit(
        jax.shard_map(
            lambda q, k, v: ring_attention(q, k, v, axis_name="data", causal=causal),
            mesh=mesh,
            in_specs=(P(None, None, "data", None),) * 3,
            out_specs=P(None, None, "data", None),
            check_vma=False,
        )
    )
    out = f(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_ring_gradients_match_dense_on_mesh():
    devices = jax.devices()
    if len(devices) < 8:
        pytest.skip("needs 8 devices")
    mesh = make_mesh(num_devices=8)
    q, k, v = _qkv(2, 2, 32, 8, seed=4)

    ring_f = jax.shard_map(
        lambda q, k, v: ring_attention(q, k, v, axis_name="data", causal=True),
        mesh=mesh,
        in_specs=(P(None, None, "data", None),) * 3,
        out_specs=P(None, None, "data", None),
        check_vma=False,
    )
    gd = jax.grad(lambda *a: jnp.sum(A.dense_attention(*a, causal=True) ** 2), (0, 1, 2))(
        q, k, v
    )
    gr = jax.jit(jax.grad(lambda *a: jnp.sum(ring_f(*a) ** 2), (0, 1, 2)))(q, k, v)
    for a, b in zip(gd, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4)


def test_flash_backward_multiblock_noncausal():
    """Pallas backward over several q AND kv tiles, full attention."""
    q, k, v = _qkv(s=64)
    g = jnp.asarray(np.random.default_rng(3).standard_normal(q.shape), q.dtype)

    def loss_via(fn, **kw):
        return lambda q, k, v: jnp.sum(fn(q, k, v, causal=False, **kw) * g)

    gd = jax.grad(loss_via(A.dense_attention), argnums=(0, 1, 2))(q, k, v)
    gf = jax.grad(
        loss_via(A.flash_attention, block_q=16, block_kv=16), argnums=(0, 1, 2)
    )(q, k, v)
    for a, b in zip(gd, gf):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)


def test_flash_backward_cross_length_causal():
    """Backward with Sq < Skv (end-aligned causal, the decode-style shape)."""
    rng = np.random.default_rng(4)
    q = jnp.asarray(rng.standard_normal((2, 2, 16, 8)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, 2, 48, 8)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, 2, 48, 8)), jnp.float32)
    g = jnp.asarray(rng.standard_normal((2, 2, 16, 8)), jnp.float32)

    gd = jax.grad(
        lambda q, k, v: jnp.sum(A.dense_attention(q, k, v, causal=True) * g),
        argnums=(0, 1, 2),
    )(q, k, v)
    gf = jax.grad(
        lambda q, k, v: jnp.sum(
            A.flash_attention(q, k, v, causal=True, block_q=8, block_kv=16) * g
        ),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b in zip(gd, gf):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)


def test_flash_forward_lse_matches_dense_logsumexp():
    """The saved statistic the backward depends on: lse == logsumexp of the
    (scaled, masked) dense logits."""
    q, k, v = _qkv(s=32)
    _, lse = A._flash_forward(
        q, k, v, causal=True, block_q=16, block_kv=16, scale=None,
        interpret=True, with_lse=True,
    )
    s = 1.0 / np.sqrt(q.shape[-1])
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * s
    mask = jnp.tril(jnp.ones((32, 32), bool))
    logits = jnp.where(mask, logits, A.NEG_INF)
    ref = jax.scipy.special.logsumexp(logits, axis=-1)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_flash_backward_mixed_masked_tile():
    """sq > skv end-aligned causal: a q tile holding BOTH fully-masked rows
    (lse == NEG_INF) and live rows must produce dense-matching gradients —
    the masked rows' p must be zeroed explicitly (exp(logits - lse) would be
    exp(0) = 1 since NEG_INF is finite)."""
    rng = np.random.default_rng(4)
    q = jnp.asarray(rng.standard_normal((1, 1, 16, 8)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 1, 8, 8)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 1, 8, 8)), jnp.float32)
    g = jnp.asarray(rng.standard_normal((1, 1, 16, 8)), jnp.float32)
    gd = jax.grad(
        lambda *a: jnp.sum(A.dense_attention(*a, causal=True) * g), argnums=(0, 1, 2)
    )(q, k, v)
    gf = jax.grad(
        lambda *a: jnp.sum(
            A.flash_attention(*a, causal=True, block_q=16, block_kv=8) * g
        ),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b in zip(gd, gf):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_backward_segmented_matches_whole(monkeypatch, causal):
    """q-segmented fused backward (sequence too long for one dq scratch):
    shrinking _FUSED_BWD_SCRATCH_LIMIT forces the segment loop, whose grads
    must match the single-call fused path bit-for-bit in dq (disjoint row
    ranges) and to adds-only reassociation in dk/dv (partial sums)."""
    q, k, v = _qkv(s=64, d=8)
    g = jnp.asarray(np.random.default_rng(7).standard_normal(q.shape), q.dtype)

    def grads():
        return jax.grad(
            lambda q, k, v: jnp.sum(
                A.flash_attention(q, k, v, causal=causal, block_q=16, block_kv=16) * g
            ),
            argnums=(0, 1, 2),
        )(q, k, v)

    whole = grads()
    # d=8 pads to 128 lanes -> 512 B/row of dq scratch + 512 B/row of delta
    # scratch; cap at 16 rows' worth so 64 rows split into four segments.
    monkeypatch.setattr(A, "_FUSED_BWD_SCRATCH_LIMIT", 16 * 1024)
    assert A._fused_segment_rows(64, 8, 16) == 16
    seg = grads()
    np.testing.assert_array_equal(np.asarray(whole[0]), np.asarray(seg[0]))
    for a, b in zip(whole[1:], seg[1:]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5)


def test_fused_segment_rows_choices():
    """Segment chooser: largest block-multiple divisor under the VMEM cap;
    None when the requested block alone exceeds it (two-pass fallback)."""
    # The gate is budget-aware since r5: 4 MB under the raised 32 MiB
    # scoped-VMEM budget (utils/compile_cache applies it), 2 MB under the
    # XLA 16 MiB default, explicit override wins.
    import os

    old_env = os.environ.get("LIBTPU_INIT_ARGS")
    try:
        os.environ["LIBTPU_INIT_ARGS"] = "--xla_tpu_scoped_vmem_limit_kib=32768"
        assert A._fused_bwd_scratch_limit() == 4 * 1024 * 1024
        os.environ["LIBTPU_INIT_ARGS"] = ""
        assert A._fused_bwd_scratch_limit() == 2 * 1024 * 1024
        os.environ["LIBTPU_INIT_ARGS"] = "--xla_tpu_scoped_vmem_limit_kib=32768"
        # 4096 rows at D<=128: 512 B/row lane-padded dq + 512 B/row delta.
        limit_rows = A._fused_bwd_scratch_limit() // (2 * 128 * 4)
        assert limit_rows == 4096
        assert A._fused_segment_rows(4096, 128, 1024) == 4096
        assert A._fused_segment_rows(16384, 128, 1024) == limit_rows
        # D=64 pads to 128 lanes, so its cap matches D=128's, not double it.
        assert A._fused_segment_rows(65536, 64, 1024) == 4096
        assert A._fused_segment_rows(8192, 128, 8192) is None
        # Multi-way split picks the LARGEST valid block-multiple segment.
        assert A._fused_segment_rows(12288, 128, 1024) == 4096
        # No block-multiple divisor at all: the block FITS the cap but no
        # divisor of sq under the cap is a multiple of it (3 divides 3072
        # but not 20480), so the divisor search itself must exhaust -> None
        # — distinct from the block-exceeds-cap early exit above.
        assert A._fused_segment_rows(20480, 128, 3072) is None
    finally:
        if old_env is None:
            os.environ.pop("LIBTPU_INIT_ARGS", None)
        else:
            os.environ["LIBTPU_INIT_ARGS"] = old_env


# ---------------------------------------------------------------------------
# Layout-native entries (r4): BSHD and packed-qkv wrappers share the BHSD
# kernel bodies — only grids/index maps differ — so values and grads must
# match the BHSD path bitwise.
# ---------------------------------------------------------------------------


def _bshd(t):
    return t.transpose(0, 2, 1, 3)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_bshd_matches_bhsd_bitwise(causal):
    q, k, v = _qkv(s=64, d=16)
    qs, ks, vs = (_bshd(t) for t in (q, k, v))

    out1 = A.flash_attention_bshd(qs, ks, vs, causal=causal, block_q=16, block_kv=16)
    out2 = A.flash_attention(q, k, v, causal=causal, block_q=16, block_kv=16)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(_bshd(out2)))

    # Grads under the SAME elementwise cotangent (2·out); a scalar loss like
    # sum(out²) would reduce in layout order and differ by f32 reassociation.
    def loss_bshd(q, k, v):
        return jnp.sum(
            A.flash_attention_bshd(q, k, v, causal=causal, block_q=16, block_kv=16)
            ** 2
        )

    def loss_bhsd(q, k, v):
        return jnp.sum(
            A.flash_attention(q, k, v, causal=causal, block_q=16, block_kv=16) ** 2
        )

    g1 = jax.grad(loss_bshd, argnums=(0, 1, 2))(qs, ks, vs)
    g2 = jax.grad(loss_bhsd, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(_bshd(b)))


def test_flash_bshd_decode_alignment():
    """sq != skv end-aligned causal (the decode convention) holds in BSHD."""
    q, k, v = _qkv(s=48, d=16)
    out = A.flash_attention_bshd(
        _bshd(q)[:, :16], _bshd(k), _bshd(v), causal=True, block_q=8, block_kv=16
    )
    ref = A.dense_attention(q[:, :, :16], k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(_bshd(out)), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


@pytest.mark.parametrize("causal", [False, True])
def test_flash_packed_qkv_matches_bhsd(causal):
    """flash_attention_qkv consumes the fused (B, S, 3·d_model) projection
    output; its packed cotangent must equal the concatenated per-tensor
    grads of the BHSD path."""
    b, h, s, d = 2, 3, 64, 16
    r = np.random.default_rng(3)
    qkv = jnp.asarray(r.standard_normal((b, s, 3 * h * d)), jnp.float32)
    g_out = jnp.asarray(r.standard_normal((b, s, h * d)), jnp.float32)

    def loss_packed(qkv):
        return jnp.sum(
            A.flash_attention_qkv(qkv, h, causal=causal, block_q=16, block_kv=16)
            * g_out
        )

    def loss_ref(qkv):
        q, k, v = (
            t.reshape(b, s, h, d).transpose(0, 2, 1, 3)
            for t in jnp.split(qkv, 3, axis=-1)
        )
        out = A.flash_attention(q, k, v, causal=causal, block_q=16, block_kv=16)
        return jnp.sum(out.transpose(0, 2, 1, 3).reshape(b, s, h * d) * g_out)

    v1, g1 = jax.value_and_grad(loss_packed)(qkv)
    v2, g2 = jax.value_and_grad(loss_ref)(qkv)
    np.testing.assert_array_equal(np.asarray(v1), np.asarray(v2))
    np.testing.assert_array_equal(np.asarray(g1), np.asarray(g2))


@pytest.mark.parametrize("causal", [False, True])
def test_flash_fused_matches_two_pass(monkeypatch, causal):
    """The fused one-pass backward vs the two-pass FlashAttention-2 pair
    (forced by making segmentation unavailable): same grads. Tight allclose,
    not bitwise — the fused kernel computes delta in-kernel while the
    two-pass path sums it in XLA, a benign f32 reassociation."""
    q, k, v = _qkv(s=64, d=8)
    gcot = jnp.asarray(np.random.default_rng(9).standard_normal(q.shape), q.dtype)

    def grads():
        return jax.grad(
            lambda q, k, v: jnp.sum(
                A.flash_attention(q, k, v, causal=causal, block_q=16, block_kv=16)
                * gcot
            ),
            argnums=(0, 1, 2),
        )(q, k, v)

    fused = grads()
    monkeypatch.setattr(A, "_FUSED_BWD_SCRATCH_LIMIT", 0)
    monkeypatch.setattr(A, "_fused_segment_rows", lambda *a: None)
    two_pass = grads()
    for a, b in zip(fused, two_pass):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5
        )


@pytest.mark.parametrize("window", [None, 24])
def test_flash_packed_rope_fallback_grads_match_fused(monkeypatch, window):
    """The packed-qkv backward's FALLBACK branch with in-kernel rope: when
    the fused one-pass kernel doesn't fit (forced via the scratch limit),
    the packed backward unpacks to BSHD with the rope rotation applied and
    must rotate the resulting dq/dk BACK before regrouping — same packed
    cotangent as the fused in-kernel path. GQA (4q/2kv) + causal (+ sliding
    window), per-batch tables: every index-map variant the rotate-back
    touches."""
    from distributed_tensorflow_tpu.ops.rope import rope_cos_sin

    b, s, h, kv, d = 2, 64, 4, 2, 16
    width = (h + 2 * kv) * d
    r = np.random.default_rng(11)
    qkv = jnp.asarray(r.standard_normal((b, s, width)), jnp.float32)
    g_out = jnp.asarray(r.standard_normal((b, s, h * d)), jnp.float32)
    # Distinct per-batch global positions — the (B, S, half) table shape.
    positions = jnp.stack([jnp.arange(s), 37 + jnp.arange(s)])
    cos, sin = rope_cos_sin(positions, d)

    def loss(qkv):
        return jnp.sum(
            A.flash_attention_qkv(
                qkv, h, kv, causal=True, window=window, block_q=16,
                block_kv=16, interpret=True, rope_cos=cos, rope_sin=sin,
            )
            * g_out
        )

    v_fused, g_fused = jax.value_and_grad(loss)(qkv)
    monkeypatch.setattr(A, "_FUSED_BWD_SCRATCH_LIMIT", 0)
    v_fb, g_fb = jax.value_and_grad(loss)(qkv)
    # The forward is identical (the limit only gates the backward).
    np.testing.assert_array_equal(np.asarray(v_fused), np.asarray(v_fb))
    np.testing.assert_allclose(
        np.asarray(g_fb), np.asarray(g_fused), rtol=1e-4, atol=1e-4
    )


# -- the prefill chunk's kernel: a traced query offset over a logical cache --

_CHUNK_S, _CHUNK_MAX = 256, 1024  # on-tile, dh 128: block_kv 512, page 16


def _chunk_dense(q, k, v, offset, window):
    """The dense cached branch's own lines (``models/transformer.py``):
    f32 scores over all S_max positions, masked, a softmax, f32 values."""
    b, h, s, dh = q.shape
    kv = k.shape[1]
    qh = q.reshape(b, kv, h // kv, s, dh)
    scores = jnp.einsum("bkgqd,bkTd->bkgqT", qh, k,
                        preferred_element_type=jnp.float32,
                        precision="highest") / np.sqrt(dh)
    q_pos = offset + jnp.arange(s)
    key_pos = jnp.arange(k.shape[2])
    allowed = key_pos[None, :] <= q_pos[:, None]
    if window is not None:
        allowed &= key_pos[None, :] > q_pos[:, None] - window
    scores = jnp.where(allowed[None, None, None], scores, A.NEG_INF)
    weights = jax.nn.softmax(scores, -1)
    return jnp.einsum("bkgqT,bkTd->bkgqd", weights, v.astype(jnp.float32),
                      precision="highest").reshape(b, h, s, dh)


def _chunk_case(kv, group, dtype, seed, batch=1):
    r = np.random.default_rng(seed)
    mk = lambda heads, rows: jnp.asarray(
        r.standard_normal((batch, heads, rows, 128)), dtype)
    return mk(kv * group, _CHUNK_S), mk(kv, _CHUNK_MAX), mk(kv, _CHUNK_MAX)


@pytest.mark.parametrize("window", [None, 200], ids=["full", "window200"])
@pytest.mark.parametrize("kv,group", [(2, 12), (2, 1)],
                         ids=["kv2-group12", "kv2-group1"])
@pytest.mark.parametrize("offset", [0, 512, 48, 331, _CHUNK_MAX - _CHUNK_S],
                         ids=["zero", "block", "page", "odd", "end"])
def test_chunk_flash_matches_the_dense_branch(offset, kv, group, window):
    """Offsets 0, a key-block boundary, a page multiple off it, an odd one
    (a final chunk starts at ``p - w``) and the cache's end, TRACED: one
    compiled program serves them all."""
    q, k, v = _chunk_case(kv, group, jnp.float32, seed=group)
    fn = jax.jit(lambda q, k, v, off: A.chunk_flash_attention(
        q, k, v, off, window=window))
    got = fn(q, k, v, jnp.int32(offset))
    assert got.shape == q.shape and got.dtype == q.dtype
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(_chunk_dense(q, k, v, offset, window)),
        atol=3e-6, rtol=3e-6)


@pytest.mark.parametrize("group", [12, 1], ids=["group12", "group1"])
def test_chunk_flash_bf16_cache_keeps_16_bits_of_every_probability(group):
    """A bf16 cache (the benchmark's): each probability enters the value
    product as two bf16 halves, so what is left against the f32 dense
    lines is the rounding of the output itself. (A product that rounded
    the probabilities to bf16 reads 1.8 and 1.2 times that here.)"""
    q, k, v = _chunk_case(2, group, jnp.bfloat16, seed=5)
    got = A.chunk_flash_attention(q, k, v, jnp.int32(331), window=None)
    assert got.dtype == jnp.bfloat16
    want = np.asarray(_chunk_dense(q, k, v, 331, None))
    rounding = np.abs(
        np.asarray(jnp.asarray(want, jnp.bfloat16), np.float32) - want).max()
    err = np.abs(np.asarray(got, np.float32) - want).max()
    assert err <= 1.05 * rounding + 1e-6, (err, rounding)


@pytest.mark.parametrize("offset,window", [(48, None), (331, 200), (0, None)],
                         ids=["page", "odd-window", "zero"])
def test_chunk_flash_reads_no_dead_key(offset, window):
    """Every row past the chunk's end holds NaN, those of the chunk's last
    key block among them: nothing dead reaches a product."""
    q, k, v = _chunk_case(2, 3, jnp.float32, seed=7)
    dead = offset + _CHUNK_S
    kn, vn = k.at[:, :, dead:].set(jnp.nan), v.at[:, :, dead:].set(jnp.nan)
    got = np.asarray(A.chunk_flash_attention(
        q, kn, vn, jnp.int32(offset), window=window))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(
        got, np.asarray(_chunk_dense(q, k, v, offset, window)),
        atol=3e-6, rtol=3e-6)


def test_chunk_flash_takes_on_tile_shapes_and_says_so():
    fits = A.chunk_flash_fits
    assert fits(jnp.bfloat16, 128, (4096, 1024))  # starcoder2-3b's cell
    assert fits(jnp.float32, 128, (32, 16, 8))
    assert not fits(jnp.bfloat16, 128, (4096, 1000))  # a bucket off the tile
    assert not fits(jnp.bfloat16, 128, (4096, 1024, 8))  # 8 rows of bf16
    assert not fits(jnp.float32, 32, (64, 16))  # the CPU smoke heads
    q, k, v = _chunk_case(2, 1, jnp.float32, seed=0)
    with pytest.raises(ValueError, match="off the tile"):
        A.chunk_flash_attention(q[:, :, :251], k, v, 0)
    with pytest.raises(ValueError, match="does not fit cache"):
        A.chunk_flash_attention(q, k, v[:, :1], 0)
