"""Attention ops: dense reference, blockwise (memory-efficient, differentiable),
and a Pallas TPU flash-attention forward kernel.

The reference has no sequence models (SURVEY §5.7: its largest "sequence" is a
784-pixel flattened image), but long-context support is first-class in this
framework: these ops are the single-device building blocks under
``parallel.ring_attention`` (sequence parallelism over a mesh axis) and
``models.transformer``.

Three tiers, one semantics (causal or full softmax attention over
``(batch, heads, seq, head_dim)``):

  * :func:`dense_attention` — O(S²) memory jnp reference; ground truth in
    tests, fine for short sequences.
  * :func:`blockwise_attention` — online-softmax ``lax.scan`` over key/value
    blocks; O(S·block) memory, differentiable through the scan (the training
    path for long sequences). Same algorithm as flash attention, expressed at
    the XLA level so autodiff derives the backward pass.
  * :func:`flash_attention` — Pallas kernels both directions. Forward: grid
    over (batch·heads, q-blocks, kv-blocks) with the kv axis innermost —
    sequential on TPU — and the running max/denominator/accumulator carried
    in VMEM scratch, so VMEM holds only (block_q + 2·block_kv)·D rows, never
    the full sequence; f32 accumulation, MXU dots; emits the row logsumexp.
    Backward: by default ONE fused Pallas kernel (kv outer, q inner) that
    recomputes p per tile from the saved logsumexp once and accumulates
    dk/dv per-kv-block and dq in a whole-sequence f32 VMEM scratch — 5 MXU
    dots + 1 softmax recompute per tile pair vs the two-pass
    FlashAttention-2 pair's 7 + 2 (measured v5e-1: flagship-shape kernel
    34 → 55% of bf16 peak, BASELINE.md). Sequences whose dq scratch
    exceeds ``_FUSED_BWD_SCRATCH_LIMIT`` run as fused q-SEGMENTS (partial dk/dv
    summed), and shapes with no clean segmentation fall back to the
    original two-pass pair. Block-sparse causal skipping everywhere.

Causal masking is **end-aligned** in all three tiers: query ``i`` attends to
keys ``<= i + (Skv - Sq)``, so with cached keys (Sq < Skv, decode) the last
query sees the full prefix — matching :func:`dense_attention`'s ground truth.
"""

from __future__ import annotations

import functools
import math
import warnings

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30  # large-negative instead of -inf: keeps masked softmax NaN-free
_LOG2_E = math.log2(math.e)


def _scale(q, scale):
    return (1.0 / math.sqrt(q.shape[-1])) if scale is None else scale


def dense_attention(
    q,
    k,
    v,
    causal: bool = False,
    scale: float | None = None,
    window: int | None = None,
):
    """O(S²)-memory reference: softmax(q·kᵀ/√d [+ causal mask]) · v.

    q: (B, H, Sq, D); k, v: (B, H, Skv, D). Returns (B, H, Sq, D) in q's dtype.
    ``window`` (requires ``causal``): sliding-window attention — query at
    global position p attends keys in [p - window + 1, p] (self always
    included; the Mistral convention).
    """
    s = _scale(q, scale)
    if window is not None and (not causal or window < 1):
        raise ValueError("window requires causal=True and window >= 1")
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32) * s
    if causal:
        sq, skv = q.shape[2], k.shape[2]
        # Align the ends: query i attends to keys ≤ i + (skv - sq).
        mask = jnp.tril(jnp.ones((sq, skv), jnp.bool_), k=skv - sq)
        if window is not None:
            mask &= jnp.triu(jnp.ones((sq, skv), jnp.bool_), k=skv - sq - window + 1)
        logits = jnp.where(mask, logits, NEG_INF)
    weights = jax.nn.softmax(logits, axis=-1)
    if causal:
        # Fully-masked rows (possible when sq > skv) output 0, not the uniform
        # mean softmax degrades to — same semantics as blockwise/flash.
        weights = jnp.where(mask.any(axis=-1)[:, None], weights, 0.0)
    return jnp.einsum(
        "bhqk,bhkd->bhqd", weights.astype(q.dtype), v, preferred_element_type=jnp.float32
    ).astype(q.dtype)


def _online_block_update(carry, q, k_blk, v_blk, mask, s):
    """One online-softmax accumulation step shared by blockwise/ring attention.

    carry = (acc (..., q, d) f32, m (..., q) f32 running max,
             l (..., q) f32 running denominator); mask True = attend.
    """
    acc, m, l = carry
    logits = (
        jnp.einsum("...qd,...kd->...qk", q, k_blk, preferred_element_type=jnp.float32) * s
    )
    logits = jnp.where(mask, logits, NEG_INF)
    m_new = jnp.maximum(m, logits.max(axis=-1))
    # Guard fully-masked rows: keep m finite so exp() stays 0, not NaN.
    m_safe = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
    correction = jnp.exp(m - m_safe)
    p = jnp.exp(logits - m_safe[..., None])
    l_new = l * correction + p.sum(axis=-1)
    acc_new = acc * correction[..., None] + jnp.einsum(
        "...qk,...kd->...qd", p.astype(v_blk.dtype), v_blk, preferred_element_type=jnp.float32
    )
    return acc_new, m_safe + jnp.where(m_new <= NEG_INF / 2, NEG_INF, 0.0), l_new


def _finalize(acc, l, dtype):
    return (acc / jnp.maximum(l, 1e-30)[..., None]).astype(dtype)


def blockwise_attention(
    q,
    k,
    v,
    causal: bool = False,
    block_kv: int = 512,
    scale: float | None = None,
    q_offset: int | jax.Array | None = None,
    kv_offset: int | jax.Array = 0,
    window: int | None = None,
):
    """Memory-efficient attention: ``lax.scan`` over kv blocks with the online
    softmax; never materializes (Sq, Skv). Differentiable (autodiff through
    the scan rematerializes per-block logits — O(S·block) backward memory).

    ``q_offset``/``kv_offset`` are the global positions of q[..., 0, :] and
    k[..., 0, :] — used by ring attention where each device holds a sequence
    shard (may be traced values). ``q_offset=None`` (default) end-aligns the
    sequences (``q_offset = Skv - Sq``), matching :func:`dense_attention`'s
    causal semantics when Sq != Skv.
    """
    b, h, sq, d = q.shape
    skv = k.shape[2]
    if window is not None and (not causal or window < 1):
        raise ValueError("window requires causal=True and window >= 1")
    if q_offset is None:
        q_offset = skv - sq
    s = _scale(q, scale)
    block_kv = min(block_kv, skv)
    num_blocks = -(-skv // block_kv)
    pad = num_blocks * block_kv - skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    kb = k.reshape(b, h, num_blocks, block_kv, d).transpose(2, 0, 1, 3, 4)
    vb = v.reshape(b, h, num_blocks, block_kv, d).transpose(2, 0, 1, 3, 4)

    q_pos = q_offset + lax.broadcasted_iota(jnp.int32, (sq, 1), 0)  # (sq, 1)

    def step(carry, xs):
        blk_idx, k_blk, v_blk = xs
        k_pos = kv_offset + blk_idx * block_kv + lax.broadcasted_iota(
            jnp.int32, (1, block_kv), 1
        )
        valid = (k_pos - kv_offset) < skv  # padding mask
        mask = valid if not causal else (k_pos <= q_pos) & valid
        if causal and window is not None:
            mask &= k_pos > q_pos - window
        carry = _online_block_update(carry, q, k_blk, v_blk, mask, s)
        return carry, None

    init = (
        jnp.zeros((b, h, sq, d), jnp.float32),
        jnp.full((b, h, sq), NEG_INF, jnp.float32),
        jnp.zeros((b, h, sq), jnp.float32),
    )
    (acc, _, l), _ = lax.scan(step, init, (jnp.arange(num_blocks), kb, vb))
    return _finalize(acc, l, q.dtype)


# ---------------------------------------------------------------------------
# Pallas flash-attention forward kernel.
# ---------------------------------------------------------------------------


# Lane width of the m/l scratch buffers: TPU VMEM tiles are (8, 128); a
# 128-wide broadcast column keeps Mosaic on the fast layout path.
_STAT_LANES = 128


def _rot_combine(x, c, s, inverse: bool):
    """Split-half rotation arithmetic shared by both table sources: ``x``
    (rows, d), ``c``/``s`` (rows, d//2) f32. f32 math, cast back to
    ``x.dtype`` — matching `ops.rope.apply_rope`'s rounding, so the
    in-kernel path needs no new numerics story. ``inverse`` applies the
    transpose rotation (angle negated) — the backward's rotate-back for
    dq/dk. The swap is a static-slice concat (interpret-safe; Mosaic
    lowers it to vector moves), VPU-only work that never touches HBM."""
    c = c.astype(jnp.float32)  # tables may arrive bf16 (DMA halving);
    s = s.astype(jnp.float32)  # the rotation arithmetic stays f32
    if inverse:
        s = -s
    cf = jnp.concatenate([c, c], axis=-1)
    sf = jnp.concatenate([-s, s], axis=-1)
    xf = x.astype(jnp.float32)
    half = x.shape[-1] // 2
    swapped = jnp.concatenate([xf[:, half:], xf[:, :half]], axis=-1)
    return (xf * cf + swapped * sf).astype(x.dtype)


def _rot_tile(x, cos_ref, sin_ref, inverse: bool = False):
    """In-VMEM RoPE rotation of one (rows, d) tile from TABLE OPERANDS:
    ``cos_ref``/``sin_ref`` hold (1, rows, d//2) f32 blocks riding the same
    index map as ``x``'s rows. This is the SHIPPED model path
    (models/transformer.py always passes tables): 72.7% flagship MFU vs
    62.1% for the iota mode below. Cost profile: at long S with few heads
    the per-cell kv-table DMA (f32, ~2x the bf16 kv fetch) drags ~6%
    behind even the outside rotation (128k envelope, BASELINE.md r5) —
    a bf16 table variant is the named untried lever."""
    return _rot_combine(x, cos_ref[0], sin_ref[0], inverse)


def _rot_tile_iota(x, row_base, theta: float, inverse: bool = False):
    """In-VMEM RoPE rotation of one (rows, d) tile with the cos/sin tables
    COMPUTED IN-KERNEL from the tile's global row positions (``row_base +
    iota``): zero table operands and zero table DMA, but a MEASURED LOSER
    on v5e — 62.1 vs 72.7% flagship MFU against the table mode (Mosaic's
    per-tile cos/sin transcendentals cost far more than the table DMA
    they save; BASELINE.md r5 negative result). Kept parity-tested as the
    zero-operand option — the right call only if a future core gets cheap
    transcendentals. Positions are ``arange`` by construction (packed
    self-attention), angles f32 like `ops.rope.rope_cos_sin`."""
    rows, d = x.shape
    half = d // 2
    # θ^(-i/half) = exp(-i·ln θ/half), built from an INTEGER lane iota
    # (Mosaic's tpu.iota is integer-only, and Pallas kernels cannot
    # capture array constants). ln θ is a static Python float.
    lane = lax.broadcasted_iota(jnp.int32, (1, half), 1).astype(jnp.float32)
    inv_freq = jnp.exp(lane * (-math.log(theta) / half))
    pos = (
        row_base + lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    ).astype(jnp.float32)
    ang = pos * inv_freq
    return _rot_combine(x, jnp.cos(ang), jnp.sin(ang), inverse)


def _flash_kernel(
    q_ref,
    k_ref,
    v_ref,
    *refs,
    block_kv: int,
    num_kv: int,
    causal: bool,
    s: float,
    q_pos_offset: int,
    window: int | None = None,
    rope: str | None = None,
    rope_theta: float = 10000.0,
):
    """One (batch·head, q-block, kv-block) grid cell.

    The kv axis is the innermost grid dimension — executed sequentially on
    TPU — so the online-softmax state (acc/m/l) lives in VMEM scratch and is
    carried across kv iterations; only one (block_q, D) q tile and one
    (block_kv, D) k/v tile are resident per cell. q_pos_offset end-aligns
    causal masking when Sq != Skv. ``rope`` rotates the q/k tiles in-VMEM
    on load — positions enter the kernel, no rotated copies of q/k ever
    exist in HBM: mode "tables" takes four table operands after v
    (:func:`_rot_tile`; the shipped model path), mode "iota" computes
    cos/sin in-kernel from the tile's row positions
    (:func:`_rot_tile_iota`; zero operands, measured slower on v5e).
    """
    if rope == "tables":
        cos_q_ref, sin_q_ref, cos_kv_ref, sin_kv_ref = refs[:4]
        refs = refs[4:]
    o_ref, lse_ref, acc_ref, m_ref, l_ref = refs
    qi = pl.program_id(1)
    j = pl.program_id(2)
    bq = q_ref.shape[1]

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros(acc_ref.shape, acc_ref.dtype)
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, m_ref.dtype)
        l_ref[...] = jnp.zeros(l_ref.shape, l_ref.dtype)

    def compute():
        # MXU dots take the INPUT dtype operands (bf16 in training) with f32
        # accumulation — kept as standard practice; the measured benefit over
        # upcasting to f32 first is small (~1-3%, BASELINE.md negative
        # results — Mosaic handles the upcast well). Softmax statistics and
        # the accumulator stay f32. The softmax scale is folded into the q
        # TILE (block_q·D multiplies) instead of the logits (block_q·block_kv
        # — 8x more VPU work at 1024-blocks/D=128); bf16 rounding of q·s is
        # the FlashAttention-2 convention and is covered by the kernel-vs-
        # dense parity tests.
        q_raw = q_ref[0]  # (bq, D)
        k_blk = k_ref[0]  # (bkv, D)
        if rope == "tables":
            # Rotation BEFORE the scale fold, rounded to the operand dtype —
            # matching what apply_rope-outside-then-kernel produces.
            q_raw = _rot_tile(q_raw, cos_q_ref, sin_q_ref)
            k_blk = _rot_tile(k_blk, cos_kv_ref, sin_kv_ref)
        elif rope == "iota":
            # Global row positions: this is the self-attention path
            # (q_pos_offset == 0, blk == grid j whenever compute runs —
            # the causal clamps only pin SKIPPED cells' DMAs).
            q_raw = _rot_tile_iota(q_raw, qi * bq, rope_theta)
            k_blk = _rot_tile_iota(k_blk, j * block_kv, rope_theta)
        q = (q_raw.astype(jnp.float32) * s).astype(q_ref.dtype)
        v_blk = v_ref[0]
        logits = jax.lax.dot_general(
            q,
            k_blk,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (bq, bkv)
        if causal:
            q_pos = (
                q_pos_offset
                + qi * bq
                + lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
            )
            k_pos = j * block_kv + lax.broadcasted_iota(jnp.int32, (1, block_kv), 1)
            mask = k_pos <= q_pos
            if window is not None:
                mask &= k_pos > q_pos - window
            logits = jnp.where(mask, logits, NEG_INF)
        m = m_ref[:, :1]
        l = l_ref[:, :1]
        m_new = jnp.maximum(m, logits.max(axis=-1, keepdims=True))
        m_safe = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
        correction = jnp.exp(m - m_safe)
        p = jnp.exp(logits - m_safe)
        l_new = l * correction + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * correction + jax.lax.dot_general(
            p.astype(v_blk.dtype),
            v_blk,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_out = m_safe + jnp.where(m_new <= NEG_INF / 2, NEG_INF, 0.0)
        m_ref[...] = jnp.broadcast_to(m_out, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    if causal:
        # Skip kv blocks entirely beyond the last query position of this
        # tile — and, with a sliding window, entirely before the FIRST
        # query's window start (that lower bound is what turns the cost
        # from O(S²) to O(S·window)).
        last_q = q_pos_offset + (qi + 1) * bq - 1
        needed = j * block_kv <= last_q
        if window is not None:
            first_q = q_pos_offset + qi * bq
            needed &= (j + 1) * block_kv - 1 >= first_q - (window - 1)

        @pl.when(needed)
        def _():
            compute()
    else:
        compute()

    @pl.when(j == num_kv - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[:, :1], 1e-30)).astype(o_ref.dtype)
        # Row logsumexp (m + log l) — the backward's saved statistic; rows
        # that attended to nothing keep lse = NEG_INF (p = 0 in backward).
        lse_ref[0] = m_ref[:, :1] + jnp.log(jnp.maximum(l_ref[:, :1], 1e-30))


# Ceiling on the whole-sequence fallback block below: past this, the kernel
# would try to hold the entire K/V sequence in VMEM and fail deep inside
# Mosaic (or OOM) far from the call site. 4096 rows × D=128 × 3 tensors ×
# f32 ≈ 6 MB — comfortably inside a v5e core's ~16 MB VMEM.
_FALLBACK_BLOCK_LIMIT = 4096


def _fit_block(requested: int, seq: int, interpret: bool = False) -> int:
    """Largest block ≤ requested that divides seq AND satisfies Mosaic's
    sublane rule (multiple of 8, or the whole sequence). On real TPU
    (not interpret mode, which has no VMEM) the search is also capped at
    ``_FALLBACK_BLOCK_LIMIT`` rows — an explicitly requested block past the
    limit (e.g. block_q=8192 on seq 8192) would reach Mosaic and blow VMEM
    far from the call site, so it is clamped down with a warning instead.
    Falls back to the full sequence when no valid divisor exists
    (odd/prime lengths), refusing past the same limit: fail here, at the
    call site, with a fix."""
    cap = min(requested, seq)
    if not interpret and cap > _FALLBACK_BLOCK_LIMIT:
        warnings.warn(
            f"flash_attention: requested block {requested} exceeds the "
            f"VMEM-safe limit ({_FALLBACK_BLOCK_LIMIT} rows); clamping.",
            stacklevel=3,
        )
        cap = _FALLBACK_BLOCK_LIMIT
    for b in range(cap, 7, -1):
        if seq % b == 0 and b % 8 == 0:
            return b
    if seq > _FALLBACK_BLOCK_LIMIT and not interpret:
        raise ValueError(
            f"flash_attention: no block size ≤ {requested} that is a multiple "
            f"of 8 divides sequence length {seq}, and the whole-sequence "
            f"fallback ({seq} rows) exceeds the VMEM-safe limit "
            f"({_FALLBACK_BLOCK_LIMIT}). Pad the sequence to a multiple of 8 "
            "or use blockwise_attention for this shape."
        )
    return seq


def _causal_kv_index(
    q_pos_offset: int,
    block_q: int,
    block_kv: int,
    num_kv: int,
    window: int | None = None,
):
    """Block-sparse kv fetch map shared by the forward and dq kernels:
    clamping the index beyond this q-tile's last needed kv block keeps it
    constant across the skipped tail, so Pallas elides the HBM→VMEM DMA (it
    only re-fetches when the mapped index changes between grid steps). With
    a sliding ``window`` the clamp is two-sided — blocks wholly before the
    tile's earliest window start are elided too, making kv DMA O(S·window)."""

    def kv_index(bh, i, j):
        last_block = jnp.clip(
            (q_pos_offset + (i + 1) * block_q - 1) // block_kv, 0, num_kv - 1
        )
        blk = jnp.minimum(j, last_block)
        if window is not None:
            first_block = jnp.clip(
                (q_pos_offset + i * block_q - (window - 1)) // block_kv,
                0,
                num_kv - 1,
            )
            blk = jnp.maximum(blk, first_block)
        return (bh, blk, 0)

    return kv_index


def _flash_forward(
    q, k, v, causal, block_q, block_kv, scale, interpret,
    with_lse: bool = False, window: int | None = None,
):
    b, h, sq, d = q.shape
    skv = k.shape[2]
    s = _scale(q, scale)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    block_q = _fit_block(block_q, sq, interpret)
    block_kv = _fit_block(block_kv, skv, interpret)
    qf = q.reshape(b * h, sq, d)
    kf = k.reshape(b * h, skv, d)
    vf = v.reshape(b * h, skv, d)
    num_kv = skv // block_kv
    kernel = functools.partial(
        _flash_kernel,
        block_kv=block_kv,
        num_kv=num_kv,
        causal=causal,
        s=s,
        q_pos_offset=skv - sq,  # end-aligned causal, matching dense_attention
        window=window,
    )
    if causal:
        kv_index = _causal_kv_index(skv - sq, block_q, block_kv, num_kv, window)
    else:
        kv_index = lambda bh, i, j: (bh, j, 0)
    out, lse = pl.pallas_call(
        kernel,
        grid=(b * h, sq // block_q, num_kv),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, block_kv, d), kv_index),
            pl.BlockSpec((1, block_kv, d), kv_index),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda bh, i, j: (bh, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, _STAT_LANES), jnp.float32),
            pltpu.VMEM((block_q, _STAT_LANES), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf)
    out = out.reshape(b, h, sq, d)
    if with_lse:
        return out, lse.reshape(b, h, sq)  # (b*h, sq, 1) -> logical (b, h, sq)
    return out


# ---------------------------------------------------------------------------
# Pallas flash-attention backward kernels (FlashAttention-2 style two-pass).
#
# With the forward's saved row logsumexp L, the attention probabilities are
# recomputed per tile as p = exp(q·kᵀ·s − L) — no O(S²) materialization —
# and with delta = rowsum(dO ∘ O) (computed once in XLA):
#     dS = p ∘ (dO·vᵀ − delta)        (softmax Jacobian, rank-1 corrected)
#     dq = s · dS·k        (kv-innermost grid, accumulated in VMEM scratch)
#     dk = s · dSᵀ·q,  dv = pᵀ·dO     (q-innermost grid, one pass for both)
# ---------------------------------------------------------------------------


def _flash_bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_acc,
    *, block_kv: int, num_kv: int, causal: bool, s: float, q_pos_offset: int,
    window: int | None = None,
):
    qi = pl.program_id(1)
    j = pl.program_id(2)
    bq = q_ref.shape[1]

    @pl.when(j == 0)
    def _init():
        dq_acc[...] = jnp.zeros(dq_acc.shape, dq_acc.dtype)

    def compute():
        # Operands stay in the input dtype (bf16 in training) so every dot
        # takes the fast MXU path; p/ds are computed in f32 and cast back to
        # the operand dtype for their dots — the FlashAttention-2 recipe
        # (accumulation is f32 via preferred_element_type throughout).
        # The q tile carries the softmax scale (same fold as the forward
        # kernel, so the recomputed p matches it bitwise).
        q = (q_ref[0].astype(jnp.float32) * s).astype(q_ref.dtype)
        k_blk = k_ref[0]
        v_blk = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0]  # (bq, 1)
        delta = delta_ref[0]
        logits = jax.lax.dot_general(
            q, k_blk, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if causal:
            q_pos = q_pos_offset + qi * bq + lax.broadcasted_iota(
                jnp.int32, (bq, 1), 0
            )
            k_pos = j * block_kv + lax.broadcasted_iota(jnp.int32, (1, block_kv), 1)
            mask = k_pos <= q_pos
            if window is not None:
                mask &= k_pos > q_pos - window
            logits = jnp.where(mask, logits, NEG_INF)
        # Fully-masked rows have lse == NEG_INF (finite), so exp(logits -
        # lse) would be exp(0) = 1, not 0 — zero them explicitly.
        p = jnp.where(lse <= NEG_INF / 2, 0.0, jnp.exp(logits - lse))
        dp = jax.lax.dot_general(
            do, v_blk, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = (p * (dp - delta)).astype(k_blk.dtype)
        dq_acc[...] += s * jax.lax.dot_general(
            ds, k_blk, dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if causal:
        last_q = q_pos_offset + (qi + 1) * bq - 1
        needed = j * block_kv <= last_q
        if window is not None:
            first_q = q_pos_offset + qi * bq
            needed &= (j + 1) * block_kv - 1 >= first_q - (window - 1)

        @pl.when(needed)
        def _():
            compute()
    else:
        compute()

    @pl.when(j == num_kv - 1)
    def _finalize():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    dk_acc, dv_acc,
    *, block_q: int, num_q: int, causal: bool, s: float, q_pos_offset: int,
    window: int | None = None,
):
    kj = pl.program_id(1)
    i = pl.program_id(2)
    bkv = k_ref.shape[1]

    @pl.when(i == 0)
    def _init():
        dk_acc[...] = jnp.zeros(dk_acc.shape, dk_acc.dtype)
        dv_acc[...] = jnp.zeros(dv_acc.shape, dv_acc.dtype)

    def compute():
        # Same dtype discipline as the dq kernel: operand-dtype (bf16) MXU
        # dots, f32 softmax statistics and accumulators, p/ds cast back to
        # the operand dtype before their dots. q carries the softmax scale
        # (matching the forward bitwise); dk's trailing ·s is absorbed by
        # the scaled q: s·dSᵀ·q == dSᵀ·(q·s).
        q = (q_ref[0].astype(jnp.float32) * s).astype(q_ref.dtype)  # (bq, D)
        k_blk = k_ref[0]  # (bkv, D)
        v_blk = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0]  # (bq, 1)
        delta = delta_ref[0]
        bq = q.shape[0]
        logits = jax.lax.dot_general(
            q, k_blk, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (bq, bkv)
        if causal:
            q_pos = q_pos_offset + i * bq + lax.broadcasted_iota(
                jnp.int32, (bq, 1), 0
            )
            k_pos = kj * bkv + lax.broadcasted_iota(jnp.int32, (1, bkv), 1)
            mask = k_pos <= q_pos
            if window is not None:
                mask &= k_pos > q_pos - window
            logits = jnp.where(mask, logits, NEG_INF)
        p = jnp.where(lse <= NEG_INF / 2, 0.0, jnp.exp(logits - lse))
        dv_acc[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # pᵀ·dO: (bkv, D)
        dp = jax.lax.dot_general(
            do, v_blk, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = (p * (dp - delta)).astype(q.dtype)
        dk_acc[...] += jax.lax.dot_general(
            ds, q, dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # dSᵀ·(q·s): (bkv, D)

    if causal:
        # Skip q tiles that end before this kv block starts (no query in the
        # tile can see these keys) — and, windowed, tiles that START after
        # the last query that can still see this block.
        needed = q_pos_offset + (i + 1) * block_q - 1 >= kj * bkv
        if window is not None:
            needed &= (
                q_pos_offset + i * block_q
                <= kj * bkv + bkv - 1 + (window - 1)
            )

        @pl.when(needed)
        def _():
            compute()
    else:
        compute()

    @pl.when(i == num_q - 1)
    def _finalize():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _flash_bwd_fused_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, out_ref,
    *refs,
    num_q: int, num_kv: int, causal: bool, s: float,
    q_pos_offset: int, window: int | None = None, rope: str | None = None,
    rope_theta: float = 10000.0,
):
    """ONE-pass backward: grid (bh, kj, i) — kv outer so dk/dv accumulate in
    per-kj scratch exactly like :func:`_flash_bwd_dkv_kernel`, while dq
    accumulates into a WHOLE-SEQUENCE (sq, D) f32 scratch that persists
    across the entire (kj, i) grid and is written out at the last cell.

    vs the two-pass FlashAttention-2 scheme this computes each (q, kv) tile
    pair ONCE: 5 MXU dots + 1 softmax recompute instead of 7 + 2 (the qk
    logits, exp and do·vᵀ were previously done in BOTH kernels). For fixed i
    the dq contributions arrive in ascending-kj order, the same order the dq
    kernel's inner loop used.

    delta = rowsum(dO ∘ O) is computed IN-KERNEL during the first kv sweep
    (kj == 0 visits every q tile — the causal skip never drops kv block 0)
    into a whole-sequence VMEM scratch read by later cells. The XLA-side
    alternative materializes a (B·H, Sq, 1) array whose trailing-1 tiled
    layout pads 128x — a ~2 ms/step copy plus padded reads at the flagship
    shape (XPlane r4). ``out`` blocks ride the q-side index map, PINNED to
    block 0 after the kj==0 sweep so their DMA is elided where delta is
    already known.

    The sq·D f32 dq scratch plus the sq-row delta scratch are the cost —
    callers gate on them fitting VMEM (``_FUSED_BWD_SCRATCH_LIMIT``) and
    fall back to q-segmentation or the two-pass kernels.

    With ``rope`` the q/k tiles rotate on load (matching the forward), and
    the accumulated dq/dk — gradients w.r.t. the ROTATED q/k — rotate BACK
    in-kernel (inverse rotation; rotation is orthogonal, its transpose is
    the inverse) before they are written: dq per q tile at its LAST
    contributing kv block (the diagonal cell — later kv blocks are
    causally skipped), dk at the per-kj finalize. Mode "iota" computes
    cos/sin in-kernel from row positions; mode "tables" takes four table
    operands after ``out``. No rotated copies or rotate-back passes exist
    in HBM in either direction."""
    if rope == "tables":
        cos_q_ref, sin_q_ref, cos_kv_ref, sin_kv_ref = refs[:4]
        refs = refs[4:]
    dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc, delta_acc = refs
    kj = pl.program_id(1)
    i = pl.program_id(2)
    bkv = k_ref.shape[1]
    bq = q_ref.shape[1]

    @pl.when((kj == 0) & (i == 0))
    def _init_dq():
        dq_acc[...] = jnp.zeros(dq_acc.shape, dq_acc.dtype)

    @pl.when(i == 0)
    def _init_dkv():
        dk_acc[...] = jnp.zeros(dk_acc.shape, dk_acc.dtype)
        dv_acc[...] = jnp.zeros(dv_acc.shape, dv_acc.dtype)

    @pl.when(kj == 0)
    def _compute_delta():
        d_rows = jnp.sum(
            do_ref[0].astype(jnp.float32) * out_ref[0].astype(jnp.float32),
            axis=-1,
            keepdims=True,
        )  # (bq, 1)
        delta_acc[pl.dslice(i * bq, bq), :] = jnp.broadcast_to(
            d_rows, (bq, delta_acc.shape[1])
        )

    def compute():
        # q carries the softmax scale (matching the forward kernel bitwise);
        # dk's trailing ·s is absorbed: s·dSᵀ·q == dSᵀ·(q·s).
        q_raw = q_ref[0]  # (bq, D)
        k_blk = k_ref[0]  # (bkv, D)
        if rope == "tables":
            q_raw = _rot_tile(q_raw, cos_q_ref, sin_q_ref)
            k_blk = _rot_tile(k_blk, cos_kv_ref, sin_kv_ref)
        elif rope == "iota":
            q_raw = _rot_tile_iota(q_raw, i * bq, rope_theta)
            k_blk = _rot_tile_iota(k_blk, kj * bkv, rope_theta)
        q = (q_raw.astype(jnp.float32) * s).astype(q_ref.dtype)
        v_blk = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0]  # (bq, 1)
        delta = delta_acc[pl.dslice(i * bq, bq), :1]  # (bq, 1)
        logits = jax.lax.dot_general(
            q, k_blk, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (bq, bkv)
        if causal:
            q_pos = q_pos_offset + i * bq + lax.broadcasted_iota(
                jnp.int32, (bq, 1), 0
            )
            k_pos = kj * bkv + lax.broadcasted_iota(jnp.int32, (1, bkv), 1)
            mask = k_pos <= q_pos
            if window is not None:
                mask &= k_pos > q_pos - window
            logits = jnp.where(mask, logits, NEG_INF)
        p = jnp.where(lse <= NEG_INF / 2, 0.0, jnp.exp(logits - lse))
        dv_acc[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # pᵀ·dO: (bkv, D)
        dp = jax.lax.dot_general(
            do, v_blk, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = (p * (dp - delta)).astype(q.dtype)
        dk_acc[...] += jax.lax.dot_general(
            ds, q, dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # dSᵀ·(q·s): (bkv, D)
        rows = pl.dslice(i * bq, bq)
        dq_acc[rows, :] += s * jax.lax.dot_general(
            ds, k_blk, dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # dS·k: (bq, D)

    if causal:
        needed = q_pos_offset + (i + 1) * bq - 1 >= kj * bkv
        if window is not None:
            needed &= (
                q_pos_offset + i * bq <= kj * bkv + bkv - 1 + (window - 1)
            )

        @pl.when(needed)
        def _():
            compute()
    else:
        compute()

    if rope:
        # Tile i's dq rows are complete once its LAST contributing kv block
        # has run: causally that is the diagonal block holding the tile's
        # last query (kv blocks past it are skipped; a window only removes
        # EARLIER blocks, so the upper end is unchanged); non-causal, the
        # final kv block. Rotate those rows back IN the f32 scratch — the
        # q-side table block at this cell is exactly tile i's rows.
        if causal:
            last_kj = jnp.clip(
                (q_pos_offset + (i + 1) * bq - 1) // bkv, 0, num_kv - 1
            )
        else:
            last_kj = num_kv - 1

        @pl.when(kj == last_kj)
        def _rotate_back_dq():
            rows = pl.dslice(i * bq, bq)
            if rope == "tables":
                dq_acc[rows, :] = _rot_tile(
                    dq_acc[rows, :], cos_q_ref, sin_q_ref, inverse=True
                )
            else:
                dq_acc[rows, :] = _rot_tile_iota(
                    dq_acc[rows, :], i * bq, rope_theta, inverse=True
                )

    @pl.when(i == num_q - 1)
    def _finalize_kv():
        dk_blk = dk_acc[...]
        if rope == "tables":
            dk_blk = _rot_tile(dk_blk, cos_kv_ref, sin_kv_ref, inverse=True)
        elif rope == "iota":
            dk_blk = _rot_tile_iota(dk_blk, kj * bkv, rope_theta, inverse=True)
        dk_ref[0] = dk_blk.astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)

    @pl.when((kj == num_kv - 1) & (i == num_q - 1))
    def _finalize_q():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


# The fused backward holds TWO whole-sequence VMEM scratches: the (sq, D)
# f32 dq accumulator and the (sq, _STAT_LANES) f32 delta rows; past this
# many TILED bytes for their sum, the q axis is SEGMENTED into fused calls
# that fit (or, if no clean segmentation exists, the two-pass kernels take
# over). 4 MB ≈ sq 4096 at D=128 (1 KB/row: 512 B dq + 512 B delta).
# History: the r4 value was 2 MB, tuned against XLA:TPU's DEFAULT 16 MiB
# scoped-VMEM budget (a 4 MB gate measured 868 KB over it at the 16k D=32
# remat shape). r5 raised the compiler budget to 32 MiB
# (utils/compile_cache.py — measured +3.5 MFU points on the flagship step
# by itself), re-swept this gate under it, and 4 MB / 4096-row segments
# won at 8k_d128 (+2.4% fused-bwd kernel time vs 2 MB; 8 MB whole-seq
# REGRESSED to 58% of peak — deeper segments starve Mosaic's other
# buffers), with the previously-OOM 16k D=32 and 16k D=128 shapes
# compile+run verified at this gate. The limit is tuned JOINTLY with the
# 1024/1024 default blocks: the resident per-tile f32 intermediates
# (logits/p/dp at (block_q, block_kv)) dominate VMEM at several MB each;
# this gate bounds only the part that GROWS with sq, which is what the
# caller controls via segmentation. Sized in TILED bytes: Mosaic pads the
# lane (last) dim to 128, so a D=32 dq scratch occupies 4x its logical
# size (measured: a 16k D=32 whole-sequence call hit 21 MB and failed to
# compile when this gate counted logical bytes).
#
# None = AUTO: resolve per call from the EFFECTIVE compiler budget
# (:func:`_fused_bwd_scratch_limit`) — 4 MB only when the raised scoped-VMEM
# budget is actually in force, else the 16-MiB-default-safe 2 MB (the r4
# value; the 4 MB gate measured 868 KB over that default at 16k D=32).
# Tests (and callers wanting a fixed gate) may set a byte count here.
_FUSED_BWD_SCRATCH_LIMIT: int | None = None


def _scoped_vmem_budget_kib() -> int:
    """The scoped-VMEM budget libtpu will use: parsed from LIBTPU_INIT_ARGS
    (set by utils/compile_cache before backend init), else XLA's default."""
    import os
    import re as _re

    m = _re.search(
        r"--xla_tpu_scoped_vmem_limit_kib=(\d+)",
        os.environ.get("LIBTPU_INIT_ARGS", ""),
    )
    return int(m.group(1)) if m else 16384


def _fused_bwd_scratch_limit() -> int:
    if _FUSED_BWD_SCRATCH_LIMIT is not None:
        return _FUSED_BWD_SCRATCH_LIMIT
    return (
        4 * 1024 * 1024 if _scoped_vmem_budget_kib() >= 32768 else 2 * 1024 * 1024
    )


def _dq_scratch_bytes_per_row(d: int) -> int:
    # f32 dq row (lane dim padded to a multiple of 128) + f32 delta row.
    return -(-d // 128) * 128 * 4 + _STAT_LANES * 4


def _causal_q_index(
    q_pos_offset: int,
    block_q: int,
    block_kv: int,
    num_q: int,
    window: int | None = None,
):
    """q-side twin of :func:`_causal_kv_index` for kv-outer grids: q tiles
    strictly before kv block ``kj`` are skipped, and clamping the mapped
    index over the skipped prefix keeps it constant so Pallas elides the
    HBM→VMEM DMA. With a sliding ``window`` the clamp is two-sided — q
    tiles past the last query that can still see block ``kj`` are elided
    too."""

    def q_index(bh, kj, i):
        first_block = jnp.clip(
            (kj * block_kv - q_pos_offset) // block_q, 0, num_q - 1
        )
        blk = jnp.maximum(i, first_block)
        if window is not None:
            last_block = jnp.clip(
                (kj * block_kv + block_kv - 1 + (window - 1) - q_pos_offset)
                // block_q,
                0,
                num_q - 1,
            )
            blk = jnp.minimum(blk, last_block)
        return (bh, blk, 0)

    return q_index


def _fused_segment_rows(sq: int, d: int, block_q: int) -> int | None:
    """Largest q-segment length whose f32 dq scratch fits
    ``_FUSED_BWD_SCRATCH_LIMIT``: a multiple of ``block_q`` that divides ``sq``
    evenly. None when no such segmentation exists (callers fall back to the
    two-pass kernels)."""
    max_rows = _fused_bwd_scratch_limit() // _dq_scratch_bytes_per_row(d)
    if block_q > max_rows:
        return None
    for n_seg in range(-(-sq // max_rows), sq + 1):  # smallest count first
        if sq % n_seg:
            continue
        seg = sq // n_seg
        if seg <= max_rows and seg % block_q == 0:
            return seg
    return None


def _flash_backward_fused(
    q, k, v, out, lse, g, causal, block_q, block_kv, scale, interpret,
    q_pos_offset: int | None = None, window: int | None = None,
):
    """One fused-kernel call; ``q_pos_offset`` overrides the end-aligned
    default when the q tensor is a SEGMENT of a longer sequence (the
    segmented path below) — its queries' global positions start at
    ``q_pos_offset`` rather than ``skv - sq``."""
    b, h, sq, d = q.shape
    skv = k.shape[2]
    s = _scale(q, scale)
    block_q = _fit_block(block_q, sq, interpret)
    block_kv = _fit_block(block_kv, skv, interpret)
    num_q, num_kv = sq // block_q, skv // block_kv
    if q_pos_offset is None:
        q_pos_offset = skv - sq

    qf = q.reshape(b * h, sq, d)
    kf = k.reshape(b * h, skv, d)
    vf = v.reshape(b * h, skv, d)
    gf = g.reshape(b * h, sq, d)
    outf = out.reshape(b * h, sq, d)
    lsef = lse.reshape(b * h, sq, 1)

    if causal:
        base_q_map = _causal_q_index(q_pos_offset, block_q, block_kv, num_q, window)

        def q_index(bh, kj, i):
            # The kj==0 sweep computes the in-kernel delta for EVERY q tile,
            # so the q/do fetch must be the REAL tile there — the windowed
            # upper clamp (which elides out-of-window tiles at kj > 0) would
            # otherwise feed delta the wrong rows.
            return (bh, jnp.where(kj == 0, i, base_q_map(bh, kj, i)[1]), 0)

        # kv blocks wholly after this call's LAST q position (a q SEGMENT of
        # a longer sequence sees only a prefix of kv) are compute-skipped —
        # clamping their mapped index keeps it constant so the k/v DMAs are
        # elided, not just the math. Windowed, the clamp gains a LOWER end:
        # blocks before the segment's earliest window start are elided too,
        # keeping segmented-backward kv traffic O(S·window).
        last_kv = max(0, min(num_kv - 1, (q_pos_offset + sq - 1) // block_kv))
        first_kv = (
            0 if window is None
            else max(0, min(num_kv - 1, (q_pos_offset - (window - 1)) // block_kv))
        )
        kv_index = lambda bh, kj, i: (
            bh, jnp.maximum(jnp.minimum(kj, last_kv), first_kv), 0
        )
    else:
        q_index = lambda bh, kj, i: (bh, i, 0)
        kv_index = lambda bh, kj, i: (bh, kj, 0)

    # out is only read during the kj==0 sweep (in-kernel delta); pinning the
    # index afterwards elides its DMA for every later cell.
    out_index = lambda bh, kj, i: (bh, jnp.where(kj == 0, i, 0), 0)

    dq, dk, dv = pl.pallas_call(
        functools.partial(
            _flash_bwd_fused_kernel,
            num_q=num_q, num_kv=num_kv, causal=causal, s=s,
            q_pos_offset=q_pos_offset, window=window,
        ),
        grid=(b * h, num_kv, num_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), q_index),
            pl.BlockSpec((1, block_kv, d), kv_index),
            pl.BlockSpec((1, block_kv, d), kv_index),
            pl.BlockSpec((1, block_q, d), q_index),
            pl.BlockSpec((1, block_q, 1), q_index),
            pl.BlockSpec((1, block_q, d), out_index),
        ],
        out_specs=[
            pl.BlockSpec((1, sq, d), lambda bh, kj, i: (bh, 0, 0)),
            pl.BlockSpec((1, block_kv, d), lambda bh, kj, i: (bh, kj, 0)),
            pl.BlockSpec((1, block_kv, d), lambda bh, kj, i: (bh, kj, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, skv, d), k.dtype),
            jax.ShapeDtypeStruct((b * h, skv, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((sq, d), jnp.float32),
            pltpu.VMEM((block_kv, d), jnp.float32),
            pltpu.VMEM((block_kv, d), jnp.float32),
            pltpu.VMEM((sq, _STAT_LANES), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf, gf, lsef, outf)

    return (
        dq.reshape(b, h, sq, d),
        dk.reshape(b, h, skv, d),
        dv.reshape(b, h, skv, d),
    )


def _flash_backward(
    q, k, v, out, lse, g, causal, block_q, block_kv, scale, interpret,
    window: int | None = None,
):
    b, h, sq, d = q.shape
    skv = k.shape[2]
    s = _scale(q, scale)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if sq * _dq_scratch_bytes_per_row(d) <= _fused_bwd_scratch_limit():
        return _flash_backward_fused(
            q, k, v, out, lse, g, causal, block_q, block_kv, scale, interpret,
            window=window,
        )
    # Longer sequences: run the fused kernel per q-SEGMENT (each segment's
    # dq scratch fits VMEM). Segment dqs are disjoint row ranges
    # (concatenated); each segment contributes a partial dk/dv (summed —
    # T extra (skv, D) adds, negligible next to the saved recompute pass).
    # Each segment's call clamps its kv index map past the segment's last
    # q position, so causal segments fetch only the kv prefix they can see
    # — k/v DMA stays proportional to COMPUTED tile pairs, not to
    # segments x num_kv.
    # Fit the block first: an oversize requested block (clamped by
    # _fit_block inside every kernel call anyway) must not forfeit the
    # fused path for want of a block-multiple segment.
    seg = _fused_segment_rows(sq, d, _fit_block(block_q, sq, interpret))
    if seg is not None:
        offset0 = skv - sq
        dqs, dk_tot, dv_tot = [], None, None
        for a in range(0, sq, seg):
            dq_s, dk_s, dv_s = _flash_backward_fused(
                q[:, :, a : a + seg],
                k,
                v,
                out[:, :, a : a + seg],
                lse[:, :, a : a + seg],
                g[:, :, a : a + seg],
                causal,
                block_q,
                block_kv,
                scale,
                interpret,
                q_pos_offset=offset0 + a,
                window=window,
            )
            dqs.append(dq_s)
            dk_tot = dk_s if dk_tot is None else dk_tot + dk_s
            dv_tot = dv_s if dv_tot is None else dv_tot + dv_s
        return jnp.concatenate(dqs, axis=2), dk_tot, dv_tot
    block_q = _fit_block(block_q, sq, interpret)
    block_kv = _fit_block(block_kv, skv, interpret)
    num_q, num_kv = sq // block_q, skv // block_kv
    q_pos_offset = skv - sq

    # delta = rowsum(dO ∘ O): one fused XLA elementwise-reduce, f32.
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)

    qf = q.reshape(b * h, sq, d)
    kf = k.reshape(b * h, skv, d)
    vf = v.reshape(b * h, skv, d)
    gf = g.reshape(b * h, sq, d)
    lsef = lse.reshape(b * h, sq, 1)
    deltaf = delta.reshape(b * h, sq, 1)

    if causal:
        kv_index = _causal_kv_index(q_pos_offset, block_q, block_kv, num_kv, window)
    else:
        kv_index = lambda bh, i, j: (bh, j, 0)

    dq = pl.pallas_call(
        functools.partial(
            _flash_bwd_dq_kernel,
            block_kv=block_kv, num_kv=num_kv, causal=causal, s=s,
            q_pos_offset=q_pos_offset, window=window,
        ),
        grid=(b * h, num_q, num_kv),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, block_kv, d), kv_index),
            pl.BlockSpec((1, block_kv, d), kv_index),
            pl.BlockSpec((1, block_q, d), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda bh, i, j: (bh, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda bh, i, j: (bh, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
    )(qf, kf, vf, gf, lsef, deltaf)

    if causal:
        q_index = _causal_q_index(q_pos_offset, block_q, block_kv, num_q, window)
    else:
        q_index = lambda bh, kj, i: (bh, i, 0)

    dk, dv = pl.pallas_call(
        functools.partial(
            _flash_bwd_dkv_kernel,
            block_q=block_q, num_q=num_q, causal=causal, s=s,
            q_pos_offset=q_pos_offset, window=window,
        ),
        grid=(b * h, num_kv, num_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), q_index),
            pl.BlockSpec((1, block_kv, d), lambda bh, kj, i: (bh, kj, 0)),
            pl.BlockSpec((1, block_kv, d), lambda bh, kj, i: (bh, kj, 0)),
            pl.BlockSpec((1, block_q, d), q_index),
            pl.BlockSpec((1, block_q, 1), q_index),
            pl.BlockSpec((1, block_q, 1), q_index),
        ],
        out_specs=[
            pl.BlockSpec((1, block_kv, d), lambda bh, kj, i: (bh, kj, 0)),
            pl.BlockSpec((1, block_kv, d), lambda bh, kj, i: (bh, kj, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, skv, d), k.dtype),
            jax.ShapeDtypeStruct((b * h, skv, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_kv, d), jnp.float32),
            pltpu.VMEM((block_kv, d), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf, gf, lsef, deltaf)

    return (
        dq.reshape(b, h, sq, d),
        dk.reshape(b, h, skv, d),
        dv.reshape(b, h, skv, d),
    )


# ---------------------------------------------------------------------------
# BSHD (activation-layout-native) wrappers: the SAME kernel bodies, with
# grids/index maps that read and write the (B, S, H·dh) layout directly.
#
# Motivation (measured v5e-1, tools/attn_probe.py): the attention sublayer
# minus the flash call runs at ~99% of bf16 peak — ln1/qkv/proj and even the
# head transposes fuse perfectly — but inserting the Pallas custom call
# forces every (B,S,H,D)<->(B,H,S,D) layout change to MATERIALIZE (XLA
# cannot fuse through a custom call): ~8 extra 100 MB HBM passes per layer
# at the flagship shape, ~40 ms/step of pure boundary cost. These wrappers
# delete ALL of them: q/k/v arrive as a free reshape of the qkv matmul
# output, and each grid cell's (1, block, dh) block is a strided slab the
# DMA engine gathers directly (256 B rows at dh=128 — measured as fast as
# the contiguous BHSD fetch, tools/bshd_probe.py: bitwise-equal output,
# kernel time equal or better).
#
# Constraint: blocks on the lane (last) dim must be 128-aligned, so the
# fast path needs dh % 128 == 0; other head dims transpose-fallback to the
# BHSD path (exactly the pre-existing behavior).
# ---------------------------------------------------------------------------


def _bshd_maps(h: int, base_q=None, base_kv=None):
    """Lift 3D (bh, i, j)->(bh, blk, 0) index maps onto a (B, S, H*dh) array:
    same grid, but dim 0 splits into (batch = bh // h, head-column = bh % h)."""

    def q_index(bh, i, j):
        blk = i if base_q is None else base_q(bh, i, j)[1]
        return (bh // h, blk, bh % h)

    def kv_index(bh, i, j):
        blk = j if base_kv is None else base_kv(bh, i, j)[1]
        return (bh // h, blk, bh % h)

    return q_index, kv_index


def _flash_forward_bshd(
    q, k, v, causal, block_q, block_kv, scale, interpret,
    with_lse: bool = False, window: int | None = None,
):
    """q, k, v: (B, S, H, dh) — the layout the qkv projection produces.
    Returns out in the same layout (and lse as (B*H, Sq, 1) when asked)."""
    b, sq, h, d = q.shape
    skv = k.shape[1]
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if not interpret and d % 128:
        # Mosaic requires lane-dim blocks in 128 multiples when the block is
        # narrower than the array; odd head dims take the transpose path.
        bhsd = lambda t: t.transpose(0, 2, 1, 3)
        res = _flash_forward(
            bhsd(q), bhsd(k), bhsd(v), causal, block_q, block_kv, scale,
            interpret, with_lse=with_lse, window=window,
        )
        if with_lse:
            out, lse = res
            return out.transpose(0, 2, 1, 3), lse.reshape(b * h, sq, 1)
        return res.transpose(0, 2, 1, 3)
    s = _scale(q, scale)
    block_q = _fit_block(block_q, sq, interpret)
    block_kv = _fit_block(block_kv, skv, interpret)
    num_kv = skv // block_kv
    qf = q.reshape(b, sq, h * d)  # free: same memory layout
    kf = k.reshape(b, skv, h * d)
    vf = v.reshape(b, skv, h * d)
    kernel = functools.partial(
        _flash_kernel,
        block_kv=block_kv,
        num_kv=num_kv,
        causal=causal,
        s=s,
        q_pos_offset=skv - sq,
        window=window,
    )
    base_kv = (
        _causal_kv_index(skv - sq, block_q, block_kv, num_kv, window)
        if causal else None
    )
    q_index, kv_index = _bshd_maps(h, base_kv=base_kv)
    out, lse = pl.pallas_call(
        kernel,
        grid=(b * h, sq // block_q, num_kv),
        in_specs=[
            pl.BlockSpec((1, block_q, d), q_index),
            pl.BlockSpec((1, block_kv, d), kv_index),
            pl.BlockSpec((1, block_kv, d), kv_index),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), q_index),
            pl.BlockSpec((1, block_q, 1), lambda bh, i, j: (bh, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, sq, h * d), q.dtype),
            jax.ShapeDtypeStruct((b * h, sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, _STAT_LANES), jnp.float32),
            pltpu.VMEM((block_q, _STAT_LANES), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf)
    out = out.reshape(b, sq, h, d)
    if with_lse:
        return out, lse
    return out


def _flash_backward_fused_bshd(
    q, k, v, out, lse, g, causal, block_q, block_kv, scale, interpret,
    q_pos_offset: int | None = None, window: int | None = None,
):
    """Fused one-pass backward reading/writing (B, S, H, dh) directly.
    ``lse`` is the forward's (B*H, Sq, 1) statistic."""
    b, sq, h, d = q.shape
    skv = k.shape[1]
    s = _scale(q, scale)
    block_q = _fit_block(block_q, sq, interpret)
    block_kv = _fit_block(block_kv, skv, interpret)
    num_q, num_kv = sq // block_q, skv // block_kv
    if q_pos_offset is None:
        q_pos_offset = skv - sq

    qf = q.reshape(b, sq, h * d)
    kf = k.reshape(b, skv, h * d)
    vf = v.reshape(b, skv, h * d)
    gf = g.reshape(b, sq, h * d)
    outf = out.reshape(b, sq, h * d)

    base_q = (
        _causal_q_index(q_pos_offset, block_q, block_kv, num_q, window)
        if causal else None
    )
    if causal:
        last_kv = max(0, min(num_kv - 1, (q_pos_offset + sq - 1) // block_kv))
        first_kv = (
            0 if window is None
            else max(0, min(num_kv - 1, (q_pos_offset - (window - 1)) // block_kv))
        )
        base_kv = lambda bh, kj, i: (
            bh, jnp.maximum(jnp.minimum(kj, last_kv), first_kv), 0
        )
    else:
        base_kv = None
    # Fused grid is (bh, kj, i): q-side blocks key on i (3rd grid axis),
    # kv-side on kj (2nd) — mirror _flash_backward_fused's maps.
    def q_index(bh, kj, i):
        blk = i if base_q is None else base_q(bh, kj, i)[1]
        # kj==0 computes the in-kernel delta for EVERY q tile: fetch the
        # real tile there (the windowed upper clamp applies at kj > 0 only).
        blk = jnp.where(kj == 0, i, blk)
        return (bh // h, blk, bh % h)

    def stat_index(bh, kj, i):
        blk = i if base_q is None else base_q(bh, kj, i)[1]
        return (bh, blk, 0)

    def kv_index(bh, kj, i):
        blk = kj if base_kv is None else base_kv(bh, kj, i)[1]
        return (bh // h, blk, bh % h)

    def out_index(bh, kj, i):
        # Read only during the kj==0 sweep (in-kernel delta); pinned after.
        return (bh // h, jnp.where(kj == 0, i, 0), bh % h)

    dq, dk, dv = pl.pallas_call(
        functools.partial(
            _flash_bwd_fused_kernel,
            num_q=num_q, num_kv=num_kv, causal=causal, s=s,
            q_pos_offset=q_pos_offset, window=window,
        ),
        grid=(b * h, num_kv, num_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), q_index),
            pl.BlockSpec((1, block_kv, d), kv_index),
            pl.BlockSpec((1, block_kv, d), kv_index),
            pl.BlockSpec((1, block_q, d), q_index),
            pl.BlockSpec((1, block_q, 1), stat_index),
            pl.BlockSpec((1, block_q, d), out_index),
        ],
        out_specs=[
            pl.BlockSpec((1, sq, d), lambda bh, kj, i: (bh // h, 0, bh % h)),
            pl.BlockSpec((1, block_kv, d), lambda bh, kj, i: (bh // h, kj, bh % h)),
            pl.BlockSpec((1, block_kv, d), lambda bh, kj, i: (bh // h, kj, bh % h)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, sq, h * d), q.dtype),
            jax.ShapeDtypeStruct((b, skv, h * d), k.dtype),
            jax.ShapeDtypeStruct((b, skv, h * d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((sq, d), jnp.float32),
            pltpu.VMEM((block_kv, d), jnp.float32),
            pltpu.VMEM((block_kv, d), jnp.float32),
            pltpu.VMEM((sq, _STAT_LANES), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf, gf, lse, outf)

    return (
        dq.reshape(b, sq, h, d),
        dk.reshape(b, skv, h, d),
        dv.reshape(b, skv, h, d),
    )


def _flash_backward_bshd(
    q, k, v, out, lse, g, causal, block_q, block_kv, scale, interpret,
    window: int | None = None,
):
    b, sq, h, d = q.shape
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    def via_bhsd():
        # Transpose fallback (the pre-BSHD behavior, bit-identical results):
        # used for odd head dims (lane-alignment gate, same as the forward)
        # and for shapes with no clean q-segmentation.
        bhsd = lambda t: t.transpose(0, 2, 1, 3)
        dq, dk, dv = _flash_backward(
            bhsd(q), bhsd(k), bhsd(v), bhsd(out), lse.reshape(b, h, sq),
            bhsd(g), causal, block_q, block_kv, scale, interpret,
            window=window,
        )
        return bhsd(dq), bhsd(dk), bhsd(dv)

    if not interpret and d % 128:
        return via_bhsd()
    if sq * _dq_scratch_bytes_per_row(d) <= _fused_bwd_scratch_limit():
        return _flash_backward_fused_bshd(
            q, k, v, out, lse, g, causal, block_q, block_kv, scale, interpret,
            window=window,
        )
    seg = _fused_segment_rows(sq, d, _fit_block(block_q, sq, interpret))
    if seg is not None:
        # Same q-segmentation as _flash_backward, sliced on the S axis of the
        # BSHD layout (lse rows are the matching (B*H, seg, 1) slices).
        skv = k.shape[1]
        offset0 = skv - sq
        dqs, dk_tot, dv_tot = [], None, None
        for a in range(0, sq, seg):
            dq_s, dk_s, dv_s = _flash_backward_fused_bshd(
                q[:, a : a + seg],
                k,
                v,
                out[:, a : a + seg],
                lse[:, a : a + seg],
                g[:, a : a + seg],
                causal,
                block_q,
                block_kv,
                scale,
                interpret,
                q_pos_offset=offset0 + a,
                window=window,
            )
            dqs.append(dq_s)
            dk_tot = dk_s if dk_tot is None else dk_tot + dk_s
            dv_tot = dv_s if dv_tot is None else dv_tot + dv_s
        return jnp.concatenate(dqs, axis=1), dk_tot, dv_tot
    # No clean segmentation: two-pass BHSD pair via transposes (rare shapes).
    return via_bhsd()


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def flash_attention_bshd(
    q,
    k,
    v,
    causal: bool = False,
    block_q: int = 1024,
    block_kv: int = 1024,
    scale: float | None = None,
    interpret: bool | None = None,
    window: int | None = None,
):
    """:func:`flash_attention` on the ACTIVATION layout: q, k, v and the
    result are (B, S, H, head_dim) — a free reshape of the qkv projection's
    (B, S, 3·d_model) output — so callers never materialize the
    (B,H,S,D) transposes a custom call would otherwise force (module
    docstring has the measured motivation). Semantics, blocks, causal
    end-alignment, segmentation and fallbacks are identical to
    :func:`flash_attention`; head dims not divisible by 128 transparently
    take the transpose path."""
    if window is not None and (not causal or window < 1):
        raise ValueError("window requires causal=True and window >= 1")
    return _flash_forward_bshd(
        q, k, v, causal, block_q, block_kv, scale, interpret, window=window
    )


def _flash_bshd_fwd(q, k, v, causal, block_q, block_kv, scale, interpret, window):
    out, lse = _flash_forward_bshd(
        q, k, v, causal, block_q, block_kv, scale, interpret, with_lse=True,
        window=window,
    )
    return out, (q, k, v, out, lse)


def _flash_bshd_bwd(
    causal, block_q, block_kv, scale, interpret, window, residuals, g
):
    q, k, v, out, lse = residuals
    return _flash_backward_bshd(
        q, k, v, out, lse, g, causal, block_q, block_kv, scale, interpret,
        window=window,
    )


flash_attention_bshd.defvjp(_flash_bshd_fwd, _flash_bshd_bwd)


# ---------------------------------------------------------------------------
# Packed-qkv self-attention: one step further than BSHD — the kernel's
# operand IS the qkv projection's (B, S, 3·d_model) output, passed three
# times with index maps that pick the q / k / v column sections per head.
# The XLA `split` that otherwise materializes three (B, S, d_model) operand
# copies at the custom-call boundary (measured 6.2 ms/step on the flagship,
# XPlane r4) never exists. Self-attention only (Sq == Skv by construction).
# ---------------------------------------------------------------------------


def _unpack_qkv(qkv, h, kv=None, rope_cos=None, rope_sin=None):
    """Split a packed [q (H·dh) | k (KV·dh) | v (KV·dh)] projection into
    (B, S, heads, dh) tensors, EXPANDING kv heads to H by repeat under GQA
    (the 4D BSHD tiers want equal head counts). When rope tables are given
    (the fallback paths for shapes the in-kernel rotation doesn't cover),
    q/k rotate HERE — once, before the kv expansion — via
    :mod:`ops.rope`, preserving the packed-path semantics exactly."""
    kv = h if kv is None else kv
    b, sq, width = qkv.shape
    dh = width // (h + 2 * kv)
    q, k, v = jnp.split(qkv, [h * dh, (h + kv) * dh], axis=-1)
    q = q.reshape(b, sq, h, dh)
    k = k.reshape(b, sq, kv, dh)
    v = v.reshape(b, sq, kv, dh)
    if rope_cos is not None:
        from distributed_tensorflow_tpu.ops.rope import apply_rope

        q = apply_rope(q, rope_cos, rope_sin)
        k = apply_rope(k, rope_cos, rope_sin)
    if kv != h:
        k = jnp.repeat(k, h // kv, axis=2)
        v = jnp.repeat(v, h // kv, axis=2)
    return q, k, v


def _check_rope_tables(rope_cos, rope_sin, b, sq, d, rope_theta=None):
    """Resolve the packed-path rope mode: ``rope_theta`` (contiguous
    positions, tables computed in-kernel) → "iota"; cos/sin table operands
    ((1|B, S, d//2), f32 OR bf16 — rotation arithmetic is f32 in-kernel
    either way, and bf16 tables halve the per-tile table DMA under bf16
    compute) → "tables"; neither → None. Theta and tables are mutually
    exclusive."""
    if (rope_cos is None) != (rope_sin is None):
        raise ValueError("rope_cos and rope_sin must be passed together")
    if rope_theta is not None:
        if rope_cos is not None:
            raise ValueError(
                "pass either rope_theta (contiguous positions, in-kernel "
                "tables) or rope_cos/rope_sin (explicit positions), not both"
            )
        return "iota"
    if rope_cos is None:
        return None
    expect_tail = (sq, d // 2)
    for name, t in (("rope_cos", rope_cos), ("rope_sin", rope_sin)):
        if t.ndim != 3 or t.shape[0] not in (1, b) or t.shape[1:] != expect_tail:
            raise ValueError(
                f"{name} must be (1|{b}, {sq}, {d // 2}), got {t.shape}"
            )
    return "tables"


def _flash_forward_qkv(
    qkv, h, kv, causal, block_q, block_kv, scale, interpret,
    with_lse: bool = False, window: int | None = None,
    rope_cos=None, rope_sin=None, rope_theta=None,
):
    """qkv: (B, S, (H + 2·KV)·dh), columns [q | k | v], heads contiguous
    within each section (KV == H is plain MHA; under GQA each group of
    H/KV query heads reads its shared kv-head column block — the index
    maps do the sharing, no expansion materializes). Returns out
    (B, S, H·dh) (+ lse (B·H, S, 1)). ``rope_cos``/``rope_sin``
    (1|B, S, dh//2), f32 or bf16 (bf16 halves the per-tile table DMA;
    rotation arithmetic is f32 in-kernel either way), rotate q/k
    IN-KERNEL (:func:`_rot_tile`) — every head rotates by the same
    position angles, so the tables are head-independent and ride the
    row index maps."""
    b, sq, width = qkv.shape
    if kv < 1 or h % kv:
        raise ValueError(
            f"num_heads {h} must be a positive multiple of num_kv_heads {kv}"
        )
    if width % (h + 2 * kv):
        raise ValueError(
            f"packed qkv width {width} is not (num_heads + 2*num_kv_heads) "
            f"= {h + 2 * kv} head columns"
        )
    d = width // (h + 2 * kv)  # head dim
    dm = h * d
    group = h // kv
    rope = _check_rope_tables(rope_cos, rope_sin, b, sq, d, rope_theta)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if not interpret and d % 128:
        if rope == "iota":
            from distributed_tensorflow_tpu.ops.rope import rope_tables

            rope_cos, rope_sin = rope_tables(d, sq, rope_theta)
        q, k, v = _unpack_qkv(qkv, h, kv, rope_cos=rope_cos, rope_sin=rope_sin)
        res = _flash_forward_bshd(
            q, k, v, causal, block_q, block_kv, scale, interpret,
            with_lse=with_lse, window=window,
        )
        if with_lse:
            out, lse = res
            return out.reshape(b, sq, dm), lse
        return res.reshape(b, sq, dm)
    s = (1.0 / math.sqrt(d)) if scale is None else scale
    block_q = _fit_block(block_q, sq, interpret)
    block_kv = _fit_block(block_kv, sq, interpret)
    num_kv = sq // block_kv
    kernel = functools.partial(
        _flash_kernel,
        block_kv=block_kv,
        num_kv=num_kv,
        causal=causal,
        s=s,
        q_pos_offset=0,
        window=window,
        rope=rope,
        rope_theta=rope_theta if rope_theta is not None else 10000.0,
    )
    base_kv = (
        _causal_kv_index(0, block_q, block_kv, num_kv, window) if causal else None
    )

    def q_index(bh, i, j):
        return (bh // h, i, bh % h)

    def k_index(bh, i, j):
        blk = j if base_kv is None else base_kv(bh, i, j)[1]
        return (bh // h, blk, h + (bh % h) // group)

    def v_index(bh, i, j):
        blk = j if base_kv is None else base_kv(bh, i, j)[1]
        return (bh // h, blk, h + kv + (bh % h) // group)

    in_specs = [
        pl.BlockSpec((1, block_q, d), q_index),
        pl.BlockSpec((1, block_kv, d), k_index),
        pl.BlockSpec((1, block_kv, d), v_index),
    ]
    operands = [qkv, qkv, qkv]
    if rope == "tables":
        tb = rope_cos.shape[0]

        def table_q_index(bh, i, j):
            return (0 if tb == 1 else bh // h, i, 0)

        def table_kv_index(bh, i, j):
            blk = j if base_kv is None else base_kv(bh, i, j)[1]
            return (0 if tb == 1 else bh // h, blk, 0)

        half = d // 2
        in_specs += [
            pl.BlockSpec((1, block_q, half), table_q_index),
            pl.BlockSpec((1, block_q, half), table_q_index),
            pl.BlockSpec((1, block_kv, half), table_kv_index),
            pl.BlockSpec((1, block_kv, half), table_kv_index),
        ]
        operands += [rope_cos, rope_sin, rope_cos, rope_sin]

    out, lse = pl.pallas_call(
        kernel,
        grid=(b * h, sq // block_q, num_kv),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, d), q_index),
            pl.BlockSpec((1, block_q, 1), lambda bh, i, j: (bh, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, sq, dm), qkv.dtype),
            jax.ShapeDtypeStruct((b * h, sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, _STAT_LANES), jnp.float32),
            pltpu.VMEM((block_q, _STAT_LANES), jnp.float32),
        ],
        interpret=interpret,
    )(*operands)
    if with_lse:
        return out, lse
    return out


def _flash_backward_qkv(
    qkv, h, kv, out, lse, g, causal, block_q, block_kv, scale, interpret,
    window: int | None = None, rope_cos=None, rope_sin=None, rope_theta=None,
):
    b, sq, width = qkv.shape
    d = width // (h + 2 * kv)
    dm = h * d
    group = h // kv
    rope = _check_rope_tables(rope_cos, rope_sin, b, sq, d, rope_theta)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    fits_fused = sq * _dq_scratch_bytes_per_row(d) <= _fused_bwd_scratch_limit()

    def regroup_kv(dt4):
        """(B, S, H, d) per-q-head kv grads -> (B, S, KV·d): the transpose
        of the GQA head-sharing (repeat's chain rule is the group sum)."""
        if group == 1:
            return dt4.reshape(b, sq, dm)
        return dt4.reshape(b, sq, kv, group, d).sum(axis=3).reshape(b, sq, kv * d)

    if (not interpret and d % 128) or not fits_fused:
        # Odd head dims or segmented/two-pass shapes: unpack once (kv heads
        # expanded) and take the BSHD backward (which handles segmentation
        # and fallbacks); the packed fast path exists for shapes that fit
        # ONE fused call — q-segmenting a packed array would slice k/v
        # along with q. Rope rotates at unpack and the resulting dq/dk —
        # gradients w.r.t. the rotated q/k — rotate back below
        # (apply_rope with negated sin IS the inverse rotation).
        if rope == "iota":
            from distributed_tensorflow_tpu.ops.rope import rope_tables

            rope_cos, rope_sin = rope_tables(d, sq, rope_theta)
            rope = "tables"
        q, k, v = _unpack_qkv(qkv, h, kv, rope_cos=rope_cos, rope_sin=rope_sin)
        dq, dk, dv = _flash_backward_bshd(
            q, k, v, out.reshape(b, sq, h, d), lse, g.reshape(b, sq, h, d),
            causal, block_q, block_kv, scale, interpret, window=window,
        )
        if rope:
            from distributed_tensorflow_tpu.ops.rope import apply_rope

            dq = apply_rope(dq, rope_cos, -rope_sin)
            dk = apply_rope(dk, rope_cos, -rope_sin)
        return jnp.concatenate(
            [dq.reshape(b, sq, dm), regroup_kv(dk), regroup_kv(dv)], axis=-1
        )
    s = (1.0 / math.sqrt(d)) if scale is None else scale
    block_q = _fit_block(block_q, sq, interpret)
    block_kv = _fit_block(block_kv, sq, interpret)
    num_q, num_kv = sq // block_q, sq // block_kv

    base_q = (
        _causal_q_index(0, block_q, block_kv, num_q, window) if causal else None
    )

    def q_index(bh, kj, i):
        blk = i if base_q is None else base_q(bh, kj, i)[1]
        # kj==0 computes the in-kernel delta for EVERY q tile: fetch the
        # real tile there (the windowed upper clamp applies at kj > 0 only).
        blk = jnp.where(kj == 0, i, blk)
        return (bh // h, blk, bh % h)

    def stat_index(bh, kj, i):
        blk = i if base_q is None else base_q(bh, kj, i)[1]
        return (bh, blk, 0)

    def k_index(bh, kj, i):
        return (bh // h, kj, h + (bh % h) // group)

    def v_index(bh, kj, i):
        return (bh // h, kj, h + kv + (bh % h) // group)

    def out_index(bh, kj, i):
        # Read only during the kj==0 sweep (in-kernel delta); pinned after.
        return (bh // h, jnp.where(kj == 0, i, 0), bh % h)

    in_specs = [
        pl.BlockSpec((1, block_q, d), q_index),
        pl.BlockSpec((1, block_kv, d), k_index),
        pl.BlockSpec((1, block_kv, d), v_index),
        pl.BlockSpec((1, block_q, d), q_index),
        pl.BlockSpec((1, block_q, 1), stat_index),
        pl.BlockSpec((1, block_q, d), out_index),
    ]
    operands = [qkv, qkv, qkv, g, lse, out]
    if rope == "tables":
        tb = rope_cos.shape[0]
        half = d // 2

        def table_q_index(bh, kj, i):
            # Same row block as q_index (incl. its clamps/pins) so the table
            # rows always match the q tile's whenever compute or the dq
            # rotate-back reads them; only the leading index differs
            # (tables are head-independent).
            return (0 if tb == 1 else bh // h, q_index(bh, kj, i)[1], 0)

        def table_kv_index(bh, kj, i):
            return (0 if tb == 1 else bh // h, kj, 0)

        in_specs += [
            pl.BlockSpec((1, block_q, half), table_q_index),
            pl.BlockSpec((1, block_q, half), table_q_index),
            pl.BlockSpec((1, block_kv, half), table_kv_index),
            pl.BlockSpec((1, block_kv, half), table_kv_index),
        ]
        operands += [rope_cos, rope_sin, rope_cos, rope_sin]

    # dk/dv are emitted PER Q HEAD (the kernel's per-kj scratch accumulates
    # one q head's contributions; different q heads of a group land in
    # adjacent column blocks) and group-summed in XLA below — writing them
    # directly into shared kv columns would overwrite across the grid's bh
    # axis, where Pallas output blocks cannot accumulate.
    dq, dk_exp, dv_exp = pl.pallas_call(
        functools.partial(
            _flash_bwd_fused_kernel,
            num_q=num_q, num_kv=num_kv, causal=causal, s=s, q_pos_offset=0,
            window=window, rope=rope,
            rope_theta=rope_theta if rope_theta is not None else 10000.0,
        ),
        grid=(b * h, num_kv, num_q),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, sq, d), lambda bh, kj, i: (bh // h, 0, bh % h)),
            pl.BlockSpec((1, block_kv, d), lambda bh, kj, i: (bh // h, kj, bh % h)),
            pl.BlockSpec((1, block_kv, d), lambda bh, kj, i: (bh // h, kj, bh % h)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, sq, dm), qkv.dtype),
            jax.ShapeDtypeStruct((b, sq, dm), qkv.dtype),
            jax.ShapeDtypeStruct((b, sq, dm), qkv.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((sq, d), jnp.float32),
            pltpu.VMEM((block_kv, d), jnp.float32),
            pltpu.VMEM((block_kv, d), jnp.float32),
            pltpu.VMEM((sq, _STAT_LANES), jnp.float32),
        ],
        interpret=interpret,
    )(*operands)
    return jnp.concatenate([dq, regroup_kv(dk_exp.reshape(b, sq, h, d)),
                            regroup_kv(dv_exp.reshape(b, sq, h, d))], axis=-1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5, 6, 7, 8, 11))
def flash_attention_qkv(
    qkv,
    num_heads: int,
    num_kv_heads: int | None = None,
    causal: bool = False,
    block_q: int = 1024,
    block_kv: int = 1024,
    scale: float | None = None,
    interpret: bool | None = None,
    window: int | None = None,
    rope_cos=None,
    rope_sin=None,
    rope_theta: float | None = None,
):
    """Flash SELF-attention on the packed qkv projection output: ``qkv`` is
    (B, S, (H + 2·KV)·head_dim) with columns [q | k | v], heads contiguous
    within each section — exactly what a fused Dense produces (KV == H is
    plain MHA and the classic 3·d_model thirds). Returns (B, S, H·head_dim).
    Under GQA (``num_kv_heads`` < ``num_heads``) the kv column index maps do
    the head sharing — no expanded K/V ever materializes, in either
    direction (the backward emits per-q-head dk/dv and group-sums, the
    transpose of the sharing). Same kernels, blocks, causal semantics and
    fallbacks as :func:`flash_attention`; the gradient arrives as one
    packed cotangent that feeds the qkv matmul backward directly.

    Rotary position embeddings apply IN-KERNEL: q/k tiles rotate in VMEM
    on load and gradients rotate back in VMEM before they are written —
    no rotated copies or boundary passes ever exist in HBM (the outside
    split → `apply_rope` → concat measured ~7 ms/layer at the flagship
    shape; XLA cannot fuse elementwise work into a custom call's
    operands — BASELINE.md r5). Two sources: ``rope_cos``/``rope_sin``
    ((1|B, S, head_dim//2) f32, from `ops.rope.rope_tables`) pass
    position tables as operands — THE SHIPPED MODEL PATH (72.7% flagship
    MFU; also how sequence shards pass per-batch explicit positions);
    ``rope_theta`` (float) instead computes cos/sin in-kernel from
    contiguous row positions — zero operands but measured 10 MFU points
    slower on v5e (transcendental cost; BASELINE.md r5 negative result)."""
    if window is not None and (not causal or window < 1):
        raise ValueError("window requires causal=True and window >= 1")
    kv = num_heads if num_kv_heads is None else num_kv_heads
    return _flash_forward_qkv(
        qkv, num_heads, kv, causal, block_q, block_kv, scale, interpret,
        window=window, rope_cos=rope_cos, rope_sin=rope_sin,
        rope_theta=rope_theta,
    )


def _flash_qkv_fwd(
    qkv, h, num_kv_heads, causal, block_q, block_kv, scale, interpret, window,
    rope_cos=None, rope_sin=None, rope_theta=None,
):
    kv = h if num_kv_heads is None else num_kv_heads
    out, lse = _flash_forward_qkv(
        qkv, h, kv, causal, block_q, block_kv, scale, interpret, with_lse=True,
        window=window, rope_cos=rope_cos, rope_sin=rope_sin,
        rope_theta=rope_theta,
    )
    return out, (qkv, out, lse, rope_cos, rope_sin)


def _flash_qkv_bwd(
    h, num_kv_heads, causal, block_q, block_kv, scale, interpret, window,
    rope_theta, residuals, g,
):
    kv = h if num_kv_heads is None else num_kv_heads
    qkv, out, lse, rope_cos, rope_sin = residuals
    dqkv = _flash_backward_qkv(
        qkv, h, kv, out, lse, g, causal, block_q, block_kv, scale,
        interpret, window=window, rope_cos=rope_cos, rope_sin=rope_sin,
        rope_theta=rope_theta,
    )
    # Position tables are constants (integer positions), not trained
    # parameters — zero cotangents, DCE'd by XLA.
    dcos = None if rope_cos is None else jnp.zeros_like(rope_cos)
    dsin = None if rope_sin is None else jnp.zeros_like(rope_sin)
    return (dqkv, dcos, dsin)


flash_attention_qkv.defvjp(_flash_qkv_fwd, _flash_qkv_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def flash_attention(
    q,
    k,
    v,
    causal: bool = False,
    block_q: int = 1024,
    block_kv: int = 1024,
    scale: float | None = None,
    interpret: bool | None = None,
    window: int | None = None,
):
    """Pallas flash-attention (TPU; interpret-mode elsewhere): forward with
    online softmax in VMEM scratch; backward is the fused one-pass kernel
    (dq in a whole-sequence f32 VMEM scratch, q-segmented past
    ``_FUSED_BWD_SCRATCH_LIMIT``, two-pass FlashAttention-2 fallback) — see
    :func:`_flash_backward`. O(S·block) memory in both directions plus the
    backward's ≤2 MB dq scratch, block-sparse causal skipping throughout.

    Default blocks 1024/1024: best of a measured v5e-1 sweep, re-confirmed
    after the fused backward (BASELINE.md; 512-blocks cost ~3 MFU points on
    the flagship step, 2048-row blocks exceed VMEM). Blocks auto-shrink to
    fit shorter sequences (:func:`_fit_block`). Forward VMEM at D=128 is
    ~2.3 MB of tiles+scratch; the fused backward adds the dq scratch and
    resident (block, block) f32 intermediates, still inside a v5e core's
    ~16 MB."""
    if window is not None and (not causal or window < 1):
        raise ValueError("window requires causal=True and window >= 1")
    return _flash_forward(
        q, k, v, causal, block_q, block_kv, scale, interpret, window=window
    )


def _flash_fwd(q, k, v, causal, block_q, block_kv, scale, interpret, window):
    out, lse = _flash_forward(
        q, k, v, causal, block_q, block_kv, scale, interpret, with_lse=True,
        window=window,
    )
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, block_q, block_kv, scale, interpret, window, residuals, g):
    q, k, v, out, lse = residuals
    return _flash_backward(
        q, k, v, out, lse, g, causal, block_q, block_kv, scale, interpret,
        window=window,
    )


flash_attention.defvjp(_flash_fwd, _flash_bwd)


# ---------------------------------------------------------------------------
# Paged decode attention: one new token per slot attends its K and V pages
# where they lie in the serving engine's page pool.
#
# The pool keeps a leaf as (pages, kv_heads, page_size, head_dim) and a slot
# as one row of physical page ids (serve/kv_pool.py). The kernel takes that
# row and the slot's live length as scalar-prefetched operands, copies the
# live pages of one chunk from HBM into a VMEM buffer while it computes on
# the chunk before, and runs an online softmax over the chunks: no logical
# (slots, kv, max_len, dh) cache exists anywhere, and a slot costs what its
# live length costs. One invocation walks every slot, so the copy chain also
# crosses from one slot's last chunk to the next slot's first.
#
# The copy chain (PR 38) pays by the chunk and the step, not by the page. A
# chunk is 1 MiB a buffer and a step 128 KiB, both in pages by the page's
# bytes (paged_decode_chain: 128 and 16 pages of 2 kv heads, 8 and 1 of 32).
# Starting a chunk reads a step's table entries ahead of its first push and
# copies the step with ONE descriptor a leaf where the ids ascend by one
# (kv_pool.alloc_pages hands out ascending runs, so most steps do), page by
# page elsewhere and for what a chunk leaves under a step. Waiting for a
# chunk is one descriptor a leaf of the whole buffer's shape: a DMA
# semaphore counts bytes, not copies. Only live pages are ever copied.
#
# How a head's chunk (t rows of K and of V) meets its query rows is the
# kernel's TILE, and there are two (paged_decode_form picks by the group
# size). Each is four functions over the same chain: ``init(b)`` -> slot
# b's query rows as the tile holds them, and their softmax state;
# ``live(pos0, n, lo)`` -> the mask of the chunk that starts at position
# pos0; ``attend(q, i, k, v, live, state)`` -> the state of query unit i
# after the chunk; ``finish(b, i, state)`` writes unit i's output. ``units``
# of them belong to one kv head.
# ---------------------------------------------------------------------------


def _group_tile(q_ref, o_ref, *, scale, window, t):
    """The query group as the MXU's streamed rows and K and V as its latched
    operand: one unit a kv head, its group padded to ``gp`` rows (a whole
    sublane tile), scores (gp, t), state (gp, 1) / (gp, 1) / (gp, dh). Four
    latches a head a chunk whatever the group: right for a group that fills
    a good part of the tile."""
    _, kv, gp, dh = q_ref.shape

    def init(b):
        q = [q_ref[b, h] for h in range(kv)]  # (gp, dh) each
        return q, [
            (jnp.full((gp, 1), NEG_INF, jnp.float32),
             jnp.zeros((gp, 1), jnp.float32),
             jnp.zeros((gp, dh), jnp.float32))
            for _ in range(kv)
        ]

    def live(pos0, n, lo):
        pos = pos0 + lax.broadcasted_iota(jnp.int32, (1, t), 1)
        return (pos < n) if window is None else (pos < n) & (pos >= lo)

    def attend(q, h, k, v, live, state):
        m, l, acc = state
        s = lax.dot_general(
            q[h], k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # (gp, t)
        s = jnp.where(live, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l = alpha * l + p.sum(axis=-1, keepdims=True)
        if v.dtype == jnp.bfloat16:
            # p = hi + lo, two bf16 halves as rows of ONE pass over v: 16
            # bits of every probability at the MXU cost of 8 (Mosaic's own
            # f32 product rounds p to bf16: measured).
            hi = p.astype(jnp.bfloat16)
            lo_p = (p - hi.astype(jnp.float32)).astype(jnp.bfloat16)
            both = jnp.dot(jnp.concatenate([hi, lo_p], axis=0), v,
                           preferred_element_type=jnp.float32)
            pv = both[:gp] + both[gp:]
        else:
            pv = jnp.dot(p, v.astype(jnp.float32),
                         preferred_element_type=jnp.float32)
        return m_new, l, alpha * acc + pv

    def finish(b, h, state):
        _, l, acc = state
        o_ref[b, h] = (acc / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)

    return 1, init, live, attend, finish


def _row_tile(q_ref, o_ref, w_buf, *, scale, window, t, group):
    """One query row a unit, ``group`` units a kv head, for a group that
    would leave most of a sublane tile empty: nothing of K or V is latched
    for it. The row, replicated over ``dh`` columns (``w_buf``, written once
    a slot), is the MXU's latched operand and the chunk's K rows stream past
    it, so the scores come out (t, dh) with row r's score on every lane:
    the layout ``p[r] * V[r, :]`` wants. Softmax and the value product run
    on the VPU in f32 (every probability enters the product with all its
    bits), each of the 8 sublanes carrying the online softmax of the chunk
    rows that fall on it: (8, dh) each of m, l and acc a query row where
    the group form pads to a tile; one sublane reduction a slot joins the
    eight. ``q_ref`` and ``o_ref`` are flat f32 rows (slots, units, dh), so
    that one row is read or written without a packed tile around it."""
    _, units, dh = q_ref.shape

    def init(b):
        for j in range(units):
            w_buf[j] = jnp.broadcast_to(
                q_ref[b, pl.ds(j, 1), :], (dh, dh)).astype(w_buf.dtype)
        return None, [
            (jnp.full((8, dh), NEG_INF, jnp.float32),
             jnp.zeros((8, dh), jnp.float32),
             jnp.zeros((8, dh), jnp.float32))
            for _ in range(units)
        ]

    def live(pos0, n, lo):
        pos = pos0 + lax.broadcasted_iota(jnp.int32, (t, dh), 0)
        return (pos < n) if window is None else (pos < n) & (pos >= lo)

    def attend(_, j, k, v, live, state):
        m, l, acc = state
        # Scores in units of log 2, so that the scale and the exponential's
        # change of base are one multiplication.
        s = lax.dot_general(
            k, w_buf[j], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * (scale * _LOG2_E)  # (t, dh), a row's score on every lane
        # A sublane all of whose rows are dead keeps its finite m, and
        # exp2(-inf - m) is exactly zero.
        s = jnp.where(live, s, -jnp.inf).reshape(t // 8, 8, dh)
        m_new = jnp.maximum(m, s.max(axis=0))
        alpha = jnp.exp2(m - m_new)
        p = jnp.exp2(s - m_new)
        l = alpha * l + p.sum(axis=0)
        v = v.astype(jnp.float32).reshape(t // 8, 8, dh)
        return m_new, l, alpha * acc + (p * v).sum(axis=0)

    def finish(b, j, state):
        m, l, acc = state
        w = jnp.exp2(m - m.max(axis=0, keepdims=True))
        l = (w * l).sum(axis=0, keepdims=True)
        o_ref[b, pl.ds(j, 1), :] = (
            (w * acc).sum(axis=0, keepdims=True) / jnp.where(l == 0.0, 1.0, l)
        ).astype(o_ref.dtype)

    return group, init, live, attend, finish


def _paged_decode_kernel(
    tables_ref,
    lens_ref,
    q_ref,
    *refs,
    scale: float,
    window: int | None,
    chunk_pages: int,
    run: int,
    shared: bool,
):
    # ``shared``: one leaf whose rows are keys and values both (latent
    # attention's rows), copied once.
    if shared:
        k_hbm, o_ref, k_buf, sems, *w_buf = refs
        v_hbm, v_buf = k_hbm, k_buf
    else:
        k_hbm, v_hbm, o_ref, k_buf, v_buf, sems, *w_buf = refs
    slots = q_ref.shape[0]
    _, kv, ps, dh = k_hbm.shape
    pps = tables_ref.shape[0] // slots
    t = chunk_pages * ps
    # The row form's call hands over a scratch for its replicated rows.
    if w_buf:
        tile = _row_tile(q_ref, o_ref, *w_buf, scale=scale, window=window,
                         t=t, group=q_ref.shape[1] // kv)
    else:
        tile = _group_tile(q_ref, o_ref, scale=scale, window=window, t=t)
    units, init, live_rows, attend, finish = tile

    # A dead row of a live chunk is multiplied by a probability of exactly
    # zero, which only a finite value survives.
    k_buf[...] = jnp.zeros(k_buf.shape, k_buf.dtype)
    if not shared:
        v_buf[...] = jnp.zeros(v_buf.shape, v_buf.dtype)

    def span(b):
        """(positions attended, first position, first page, pages) of slot b."""
        n = lens_ref[b]
        lo = jnp.maximum(n - window, 0) if window is not None else 0
        p0 = lo // ps
        return n, lo, p0, pl.cdiv(n, ps) - p0

    def next_live(b):
        """The first slot at or after ``b`` with something to attend."""
        return lax.fori_loop(
            b, slots,
            lambda i, r: jnp.where((r == slots) & (lens_ref[i] > 0), i, r),
            slots,
        )

    leaves = ((k_hbm, k_buf, 0),) if shared else (
        (k_hbm, k_buf, 0), (v_hbm, v_buf, 1))

    def start_chunk(b, p0, pages, c, buf):
        """Start the copies of chunk ``c`` of slot ``b``, ``run`` pages a
        step: their table entries read ahead of the first push, and ONE
        descriptor a leaf where the ids ascend by one (the pages lie side
        by side in the pool). What is left under a step goes page by page,
        in the static sizes of its count's bits."""
        first = b * pps + p0 + c * chunk_pages
        n = jnp.minimum(pages - c * chunk_pages, chunk_pages)

        def singles(i0, pids):
            for j, pid in enumerate(pids):
                for hbm, vmem, s in leaves:
                    pltpu.make_async_copy(
                        hbm.at[pid], vmem.at[buf, i0 + j],
                        sems.at[s, buf]).start()

        def step(i0, count):
            pids = [tables_ref[first + i0 + j] for j in range(count)]
            if count < max(run, 2):
                return singles(i0, pids)
            adjacent = pids[1] == pids[0] + 1
            for j in range(2, count):
                adjacent &= pids[j] == pids[0] + j

            @pl.when(adjacent)
            def _():
                for hbm, vmem, s in leaves:
                    pltpu.make_async_copy(
                        hbm.at[pl.ds(pids[0], count)],
                        vmem.at[buf, pl.ds(i0, count)],
                        sems.at[s, buf]).start()

            pl.when(jnp.logical_not(adjacent))(lambda: singles(i0, pids))

        def steps(g, _):
            step(g * run, run)
            return 0

        lax.fori_loop(0, n // run, steps, 0)
        for bit in range(run.bit_length() - 1):
            pl.when((n >> bit) & 1 == 1)(
                lambda: step((n >> (bit + 1)) << (bit + 1), 1 << bit))

    def wait_chunk(pages, c, buf):
        """Wait for chunk ``c``'s copies. A DMA semaphore counts bytes, so a
        whole chunk is awaited by ONE descriptor a leaf of the buffer's
        shape, however many copies filled it, and a slot's last, partial
        chunk by one of each static size among its page count's bits. (A
        wait needs the shapes of its copy alone, not its source.)"""
        n = jnp.minimum(pages - c * chunk_pages, chunk_pages)
        for bit in range(chunk_pages.bit_length()):
            size = 1 << bit

            @pl.when((n >> bit) & 1 == 1)
            def _():
                for hbm, vmem, s in leaves:
                    pltpu.make_async_copy(
                        hbm.at[pl.ds(0, size)], vmem.at[buf, pl.ds(0, size)],
                        sems.at[s, buf]).wait()

    b0 = next_live(0)

    @pl.when(b0 < slots)
    def _():
        _, _, p0, pages = span(b0)
        start_chunk(b0, p0, pages, 0, 0)

    def slot_body(b, g):
        n, lo, p0, pages = span(b)
        chunks = pl.cdiv(pages, chunk_pages)
        nb = next_live(b + 1)
        q, state0 = init(b)

        def chunk_body(c, carry):
            g, state = carry
            buf = g % 2

            # The next chunk to attend: this slot's, or the first of the
            # next live slot.
            more = c + 1 < chunks

            @pl.when(more | (nb < slots))
            def _():
                bn = jnp.where(more, b, nb)
                _, _, p0n, pagesn = span(bn)
                start_chunk(bn, p0n, pagesn, jnp.where(more, c + 1, 0),
                            1 - buf)

            wait_chunk(pages, c, buf)
            live = live_rows((p0 + c * chunk_pages) * ps, n, lo)
            new_state = []
            for h in range(kv):
                k = k_buf[buf, :, h].reshape(t, dh)
                v = v_buf[buf, :, h].reshape(t, dh)
                new_state += [
                    attend(q, i, k, v, live, state[i])
                    for i in range(h * units, (h + 1) * units)
                ]
            return g + 1, new_state

        g, state = lax.fori_loop(0, chunks, chunk_body, (g, state0))
        for i, unit in enumerate(state):
            finish(b, i, unit)
        return g

    lax.fori_loop(0, slots, slot_body, 0)


def _sublane_rows(dtype) -> int:
    """Rows of one sublane tile of ``dtype``: 8 of f32, 16 of bf16."""
    return 32 // jnp.dtype(dtype).itemsize


def paged_decode_form(group: int) -> str:
    """Which way :func:`paged_decode_attention` forms its two products for
    a query group of ``group`` rows a kv head: ``"row"`` or ``"group"`` (the
    two tiles of ``_paged_decode_kernel``). A function of the shape alone:
    no argument or configuration chooses.

    The group form latches a head's K and V tiles into the MXU (four
    latches a head a chunk) whatever the group, the row form passes the VPU
    over the chunk once a query row: they cross between 2 and 4 rows. The
    kernel alone on a v5e, 16 slots attending 1,552-3,409 rows, 32 kv heads
    of 128, bf16, 16 pages a chunk, the live bytes at the HBM rate 0.721 ms
    (PR 34; ms, row / group): group 1 0.833 / 1.192, group 2 0.906 / 1.191,
    group 4 1.571 / 1.194; an f32 pool (pages of 8, floor 0.854): group 1
    0.961 / 0.974, group 2 0.962 / 0.968; 2 kv heads, group 12, window
    4096, 32 pages a chunk (``starcoder2-3b``; floor 0.054): 0.543 / 0.203.
    Since PR 38 (:func:`paged_decode_chain`: 128 pages a chunk, 16 a copy
    step at 2 kv heads; 500-1,900 rows a slot; ms, PR 37's chain / this
    one over table rows of ascending neighbours / over scattered rows):
    group 12, 16 slots, floor 0.021: 0.077 / 0.035 / 0.056; group 4, 32
    slots, floor 0.046: 0.167 / 0.070 / 0.118; group 16, 64 slots, floor
    0.092: 0.341 / 0.146 / 0.246; the row form at 32 kv heads, floor 0.726:
    0.800 / 0.800 / 0.800. At 128 pages a chunk the group tile passes over
    up to 2,047 dead rows of a slot's last chunk and is still faster alone
    (0.038 ms at group 4) than at 32 (0.059): its cost is the chunk's, not
    the row's.
    A group that was not measured stays on the group form."""
    return "row" if group <= 2 else "group"


def paged_decode_fits(pages) -> bool:
    """Whether :func:`paged_decode_attention` takes a pool leaf ``pages``
    (pages, kv_heads, page_size, head_dim) as it lies: a page a whole number
    of the dtype's sublane tiles and a head a whole number of 128 lanes, so
    that a page is copied and viewed as rows of its chunk without a
    relayout. A rule on shapes, the same off the TPU (interpret mode) as on
    it: what the chip was measured with is what every backend runs."""
    return (pages.shape[2] % _sublane_rows(pages.dtype) == 0
            and pages.shape[3] % 128 == 0)


def paged_decode_chain(pages, pages_per_slot: int) -> tuple[int, int]:
    """``(pages a chunk, pages a copy step)`` of the kernel's copy chain over
    a pool leaf ``pages`` (pages, kv_heads, page_size, head_dim) and table
    rows of ``pages_per_slot``: functions of one page's bytes, like the form
    of the group, and no argument or configuration chooses.

    A chunk is 1 MiB a buffer (four of them: K and V, double): 128 pages of
    2 kv heads, 8 of EvaByte's 32. A step is as many pages as make 128 KiB,
    a power of two: 16 pages of 8 KiB, one of EvaByte's, whose single-page
    copies already run at the HBM rate. The kernel alone on a v5e at cell
    6's shape (32 slots of 500-1,900 rows, 2 kv heads, group 4, bf16; the
    rows' bytes at the HBM rate 0.0495 ms; PR 38), ms by pages a chunk:
    a wait a page and a start a page as PR 30 wrote it 0.179 at 32; one wait
    a chunk 0.161; starts unrolled by 8 0.155 at 32, 0.129 at 64, 0.117 at
    128; one descriptor a run of 8 adjacent pages 0.085 at 64 and 0.074 at
    128, of 16 0.082 and **0.071** (70% of the bytes' rate; over 1,500-3,500
    rows 78%); of 2 or 4 none faster than page by page (a copy of 16 or
    32 KiB costs the chain as much as one of 64). EvaByte's shape reads 0.879
    under every one of these and 0.888 at 16 pages a chunk.

    Where a row holds more, a chunk is a power of two of pages: a latent
    row's page of 20 KiB gives 32 (512 rows), not 51."""
    page_bytes = math.prod(pages.shape[1:]) * pages.dtype.itemsize
    fit = max(1, (1 << 20) // page_bytes)
    chunk = min(pages_per_slot, 1 << (fit.bit_length() - 1))
    run = max(1, min(chunk, (128 << 10) // page_bytes))
    return chunk, 1 << (run.bit_length() - 1)


def paged_decode_copies(page_tables, lens, pages, *, window=None):
    """``(copies, live pages)`` a leaf of one call of
    :func:`paged_decode_attention` over these host ``page_tables`` and
    ``lens`` (numpy) and a pool leaf shaped like ``pages``: the kernel's
    rule (``_paged_decode_kernel``'s ``start_chunk``) counted on the host,
    one descriptor for a step whose ids ascend by one and one a page
    elsewhere. (Chunks hold whole steps, so a slot's steps lie ``run``
    apart from its first live page.)"""
    tables, n = np.asarray(page_tables), np.asarray(lens, np.int64)
    _, run = paged_decode_chain(pages, tables.shape[1])
    ps = pages.shape[2]
    steps = tables.shape[1] // run
    ids, live = tables[:, : steps * run], -(-n // ps)
    if window is not None:  # the row from each slot's first live page
        p0 = np.maximum(n - window, 0) // ps
        live = live - p0
        at = np.minimum(p0[:, None] + np.arange(steps * run),
                        tables.shape[1] - 1)
        ids = np.take_along_axis(tables, at, axis=1)
    ids = ids.reshape(-1, steps, run)
    whole = np.arange(steps) < (live // run)[:, None]
    adjacent = (np.diff(ids, axis=2) == 1).all(axis=2)  # all, at one page
    copies = ((whole & adjacent).sum() + run * (whole & ~adjacent).sum()
              + (live % run).sum())
    return int(copies), int(live.sum())


def paged_decode_attention(
    q,
    k_pages,
    v_pages,
    page_tables,
    lens,
    *,
    window: int | None = None,
    scale: float | None = None,
    pages_per_chunk: int | None = None,
    interpret: bool | None = None,
):
    """Attention of one query token per slot over that slot's pages, in place.

    ``q`` (slots, kv_heads, group, head_dim); ``k_pages`` / ``v_pages``
    (pages, kv_heads, page_size, head_dim), the pool's leaves as they lie;
    ``page_tables`` (slots, pages_per_slot) int32 physical page ids, logical
    page ``j`` of a slot holding its positions ``[j*page_size,
    (j+1)*page_size)``; ``lens`` (slots,) int32, the positions ``0..lens-1``
    the slot's token attends (its own included). A slot with ``lens`` 0 reads
    nothing and returns zeros. ``window`` keeps the last ``window`` of those
    positions and skips the pages wholly below them. Returns (slots,
    kv_heads, group, head_dim) in ``q``'s dtype.

    Scores, softmax state and the value product accumulate in f32 over the
    operands' own dtype, as the dense cached branch of
    ``models/transformer.py`` does. Only the pages ``ceil(lens/page_size)``
    (less the window's skip) are copied from HBM, a chunk of them a buffer
    and a step of neighbouring pages a descriptor, both sized by the page's
    bytes (:func:`paged_decode_chain`; ``pages_per_chunk`` is for tests
    that want small chunks: no call site of the models gives it). How the
    two products are formed follows from ``group``
    alone (:func:`paged_decode_form`): up to two query rows a kv head
    stream K past the row and finish on the VPU, where a probability keeps
    all its f32 bits; larger groups stream past latched K and V tiles, where
    it keeps 16.

    ``v_pages`` None: the rows of ``k_pages`` are the values too, copied
    once (latent attention's rows, whose value is the latent: the kernel is
    then named ``mla_decode_attention``). ``scale`` multiplies the scores
    (head_dim^-1/2 by default).
    """
    slots, kv, _, dh = q.shape
    if (v_pages is not None and k_pages.shape != v_pages.shape) or (
            k_pages.shape[1::2] != (kv, dh)):
        raise ValueError(
            f"q {q.shape} does not fit pages k {k_pages.shape} / v "
            f"{None if v_pages is None else v_pages.shape}"
        )
    if page_tables.shape[0] != slots or lens.shape != (slots,):
        raise ValueError(
            f"page_tables {page_tables.shape} / lens {lens.shape} do not fit "
            f"{slots} slots"
        )
    if window is not None and window < 1:
        raise ValueError(f"window must be >= 1, got {window}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    chunk_pages, run = paged_decode_chain(k_pages, page_tables.shape[1])
    if pages_per_chunk is not None:  # the tests' small chunks
        chunk_pages = min(int(pages_per_chunk), page_tables.shape[1])
        run = min(run, 1 << (chunk_pages.bit_length() - 1))
    return _paged_decode_call(
        q, k_pages, v_pages, page_tables, lens,
        scale=_scale(q, scale), window=window, chunk_pages=chunk_pages,
        run=run, interpret=bool(interpret),
    )


# Jitted, so that a model's layers trace and lower the kernel once between
# them (30 separate pallas_calls cost the serving engine seconds of warm-up).
@functools.partial(
    jax.jit,
    static_argnames=("scale", "window", "chunk_pages", "run", "interpret"),
)
def _paged_decode_call(q, k_pages, v_pages, page_tables, lens, *, scale,
                       window, chunk_pages, run, interpret):
    slots, kv, group, dh = q.shape
    ps = k_pages.shape[2]
    dtype = q.dtype
    buf = (2, chunk_pages, kv, ps, dh)
    row_form = paged_decode_form(group) == "row"
    scratch, params = [], {}
    if row_form:
        # Flat rows of f32, so that one row is read and written without a
        # packed tile around it (the kernel rounds q back to its dtype), a
        # scratch for the replicated rows, and the VMEM the shapes need:
        # the chunk buffers, that scratch, q and the output, and 4 MiB for
        # what the heads' straight-line code spills. (The group form's call
        # stays as it was: it fits the default at the sizes it is run at.)
        q = q.reshape(slots, kv * group, dh).astype(jnp.float32)
        scratch.append(pltpu.VMEM((kv * group, dh, dh), dtype))
        params["compiler_params"] = pltpu.CompilerParams(
            vmem_limit_bytes=2 * math.prod(buf) * k_pages.dtype.itemsize
            + kv * group * dh * dh * dtype.itemsize
            + 4 * q.size * 4 + (4 << 20))
    else:
        # The MXU takes whole sublane tiles of query rows.
        rows = _sublane_rows(dtype)
        gp = -(-group // rows) * rows
        q = jnp.pad(q, ((0, 0), (0, 0), (0, gp - group), (0, 0)))
    shared = v_pages is None
    leaves = (k_pages,) if shared else (k_pages, v_pages)
    kernel = functools.partial(
        _paged_decode_kernel, scale=scale, window=window,
        chunk_pages=chunk_pages, run=run, shared=shared,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(1,),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.VMEM),
                *(pl.BlockSpec(memory_space=pl.ANY) for _ in leaves),
            ],
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            scratch_shapes=[
                *(pltpu.VMEM(buf, leaf.dtype) for leaf in leaves),
                pltpu.SemaphoreType.DMA((len(leaves), 2)),
                *scratch,
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
        name="mla_decode_attention" if shared else "paged_decode_attention",
        **params,
    )(page_tables.reshape(-1).astype(jnp.int32), lens.astype(jnp.int32),
      q, *leaves)
    if row_form:
        return out.reshape(slots, kv, group, dh).astype(dtype)
    return out[:, :, :group]


# ---------------------------------------------------------------------------
# The decode round's row writes: one new row a kv head into one page of each
# of a layer's two pool leaves, a page copy a live lane, in place.
# ---------------------------------------------------------------------------


def _paged_row_write_kernel(page_ref, off_ref, live_ref, *refs):
    """Every live lane's page of each leaf is copied into VMEM at once, then,
    lane by lane, row ``off`` of every kv head is set and the page copied
    back. A lane that is not live issues nothing. ``refs`` are, for n leaves
    (two: k and v; one: a latent row), the n row arrays, the n leaves in,
    out and their n VMEM buffers, then ``sems`` (2, n, slots): [in | out,
    leaf, lane]; the copies back are awaited after the last."""
    n = (len(refs) - 1) // 4
    rows_in, ins, outs, bufs = (refs[i * n:(i + 1) * n] for i in range(4))
    sems = refs[-1]
    slots = rows_in[0].shape[0]
    _, kv, ps, dh = ins[0].shape
    leaves = tuple(zip(ins, outs, bufs, rows_in, range(n)))

    def copies(b, way):
        """Lane ``b``'s page copies of both leaves: in (0) or out (1)."""
        out = []
        for src, dst, buf, _, s in leaves:
            a, z = (src.at[page_ref[b]], buf.at[b]) if way == 0 else (
                buf.at[b], dst.at[page_ref[b]])
            out.append(pltpu.make_async_copy(a, z, sems.at[way, s, b]))
        return out

    def each_live(body):
        def step(b, carry):
            pl.when(live_ref[b] != 0)(functools.partial(body, b))
            return carry

        lax.fori_loop(0, slots, step, 0)

    def start_in(b):
        for c in copies(b, 0):
            c.start()

    at_row = lax.broadcasted_iota(jnp.int32, (kv, ps, dh), 1)

    def set_row(b):
        for c in copies(b, 0):
            c.wait()
        for _, _, buf, rows, _ in leaves:
            # f32 for the select: the chip's VPU has no bf16 lanes, and a
            # bf16 value goes through f32 and back unchanged.
            buf[b] = jnp.where(
                at_row == off_ref[b],
                rows[b].astype(jnp.float32)[:, None, :],
                buf[b].astype(jnp.float32)).astype(buf.dtype)
        for c in copies(b, 1):
            c.start()

    def wait_out(b):
        for c in copies(b, 1):
            c.wait()

    each_live(start_in)
    each_live(set_row)
    each_live(wait_out)


def paged_row_write(k_pages, v_pages, k_rows, v_rows, pages, offsets, live, *,
                    interpret: bool | None = None):
    """The decode round's new K and V rows written into a layer's pool
    leaves, in place: for each lane ``b`` with ``live[b]``, row
    ``offsets[b]`` of every kv head of page ``pages[b]`` of ``k_pages`` /
    ``v_pages`` (pages, kv_heads, page_size, head_dim) becomes ``k_rows[b]``
    / ``v_rows[b]`` (slots, kv_heads, head_dim, cast to the leaf's dtype). A
    lane that is not live writes nothing, wherever its page points. The live
    lanes' pages are distinct (each a slot's own page: the engine's pool
    guarantees it), since each lane's page goes back whole. Returns the two
    leaves, aliased to the ones given (inside a program that donates the
    pool, nothing of it is copied).

    XLA's scatter of the same rows into the leaf seen as (pages * kv *
    page_size, head_dim) writes one row of 256 B an update, and pays by the
    row whatever its bytes. Here a live lane costs a copy of its page in and
    out of VMEM, 128 KiB each way at EvaByte's 32 kv heads and 8 KiB at two,
    all the lanes' copies in flight together. Alone on a v5e, bf16, pages
    of 16, 16 lanes unless named (PERF.md §6; us a call, scatter / this
    kernel): 32 kv heads 77.4 / 20.4, one lane of 16 live (EvaByte's
    summaries) 77.8 / 7.6; 2 kv heads 10.1 / 7.5, 32 lanes 14.6 / 10.2, 64
    lanes 23.8 / 15.0. No crossover: the kernel is taken wherever
    :func:`paged_decode_fits` takes the leaf, the read kernel's rule. Mosaic
    refuses a copy of one bf16 row (or two) into the tiled leaf; a copy of
    the 8-row half of the page that holds the row read 13.6 / 8.7 / 7.3 /
    10.3 / 16.4 on the same cases, and leans on XLA keeping 8-row tiles in
    HBM, so the whole page is copied.

    ``v_pages`` and ``v_rows`` None: one leaf (latent attention's row),
    returned alone."""
    slots, kv, dh = k_rows.shape
    one = v_pages is None
    if (not one and (k_pages.shape != v_pages.shape
                     or v_rows.shape != k_rows.shape)
            or k_pages.shape[1::2] != (kv, dh)):
        raise ValueError(
            f"rows k {k_rows.shape} / v "
            f"{None if one else v_rows.shape} do not fit pages k "
            f"{k_pages.shape} / v {None if one else v_pages.shape}")
    if not all(a.shape == (slots,) for a in (pages, offsets, live)):
        raise ValueError(
            f"pages {pages.shape} / offsets {offsets.shape} / live "
            f"{live.shape} do not fit {slots} slots")
    if not paged_decode_fits(k_pages):
        raise ValueError(
            f"pages {k_pages.shape} of {k_pages.dtype} are off the tile: the "
            f"scatter serves them")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    leaves = (k_pages,) if one else (k_pages, v_pages)
    rows = (k_rows,) if one else (k_rows, v_rows)
    out = _paged_row_write_call(
        leaves, tuple(r.astype(l.dtype) for r, l in zip(rows, leaves)),
        pages.astype(jnp.int32), offsets.astype(jnp.int32),
        live.astype(jnp.int32), interpret=bool(interpret))
    return out[0] if one else tuple(out)


# Jitted like _paged_decode_call: a model's layers trace the kernel once.
@functools.partial(jax.jit, static_argnames=("interpret",))
def _paged_row_write_call(leaves, rows, pages, offsets, live, *, interpret):
    slots, n = rows[0].shape[0], len(leaves)
    buf = (slots,) + leaves[0].shape[1:]
    buf_bytes = math.prod(buf) * leaves[0].dtype.itemsize
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        _paged_row_write_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)] * n
            + [any_spec] * n,
            out_specs=[any_spec] * n,
            scratch_shapes=[
                *(pltpu.VMEM(buf, leaf.dtype) for leaf in leaves),
                pltpu.SemaphoreType.DMA((2, n, slots)),
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct(leaf.shape, leaf.dtype)
                   for leaf in leaves],
        # Operands count the three prefetched scalars and the n row arrays:
        # the leaves follow them.
        input_output_aliases={3 + n + i: i for i in range(n)},
        interpret=interpret,
        name="paged_row_write",
        # Every leaf's page of every lane, the rows, and room besides.
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=n * buf_bytes + 2 * n * rows[0].size
            * rows[0].dtype.itemsize + (4 << 20)),
    )(pages, offsets, live, *rows, *leaves)


# ---------------------------------------------------------------------------
# Prefill-chunk attention: a block of query rows at a TRACED offset attends a
# slot's whole logical cache, forward only.
#
# ``_flash_kernel``'s grid cell and ``_causal_kv_index``'s clamp with the
# query offset as a scalar-prefetched operand in the static's place: a
# serving chunk starts at ``cache["len"]``, which only the device knows. The
# cache stays unexpanded, (B, KV, S_max, dh): one grid cell holds the whole
# query group of a kv head, so a K and a V block are fetched once a group
# and no ``expand_kv`` copy exists. Key blocks past the chunk's last row, and
# before its first row's window, are neither copied nor computed.
# ---------------------------------------------------------------------------


def _chunk_flash_kernel(
    off_ref,
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    acc_ref,
    m_ref,
    l_ref,
    *,
    block_kv: int,
    num_kv: int,
    sq: int,
    s: float,
    window: int | None,
):
    """One (batch·kv head, q-block, kv-block) grid cell, kv innermost:
    ``_flash_kernel``'s online softmax for each of the ``group`` query heads
    that share the cell's K and V block, with its tiles TRANSPOSED: scores
    (bkv, bq), so that the reductions over keys run down the sublanes and
    the statistics are (1, bq) rows, two vregs a head where a (bq, 1) column
    is thirty-two; the accumulator (dh, bq), turned back once a q-block. On
    a v5e at ``starcoder2-3b``'s shape, 1024 rows behind 3072: 0.58 ms
    against 1.77 for the untransposed cell, whose time was its statistics'
    (PERF.md, PR 36). Query row ``r`` of q-block ``qi`` stands at position
    ``off + qi·bq + r``. Scores, statistics and the accumulator are f32; the
    scale multiplies the f32 scores (the dense cached branch divides them:
    no rounding of q·s to the operand dtype); over a bf16 V a probability
    enters the value product as two bf16 halves in one pass
    (``_group_tile``: 16 of its bits where Mosaic's own f32 product keeps
    8)."""
    off = off_ref[0]
    qi = pl.program_id(1)
    j = pl.program_id(2)
    _, group, bq, _ = q_ref.shape

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros(acc_ref.shape, acc_ref.dtype)
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, m_ref.dtype)
        l_ref[...] = jnp.zeros(l_ref.shape, l_ref.dtype)

    first_q = off + qi * bq
    last_q = first_q + bq - 1
    needed = j * block_kv <= last_q
    if window is not None:
        needed &= (j + 1) * block_kv - 1 >= first_q - (window - 1)

    @pl.when(needed)
    def _compute():
        k_blk = k_ref[0]  # (bkv, D), shared by the group
        k_pos = j * block_kv + lax.broadcasted_iota(jnp.int32, (block_kv, 1), 0)
        # Rows past the chunk's end lie in its last block too: their
        # probability is exactly zero, which only a finite value survives.
        v_blk = jnp.where(k_pos < off + sq, v_ref[0], 0)
        bf16 = v_blk.dtype == jnp.bfloat16
        if not bf16:
            v_blk = v_blk.astype(jnp.float32)
        q_pos = first_q + lax.broadcasted_iota(jnp.int32, (1, bq), 1)
        mask = k_pos <= q_pos  # (bkv, bq)
        if window is not None:
            mask &= k_pos > q_pos - window
        # Unrolled: the heads' VPU and MXU work interleave (twice as fast
        # as a fori_loop on the chip).
        for g in range(group):
            logits = lax.dot_general(
                k_blk, q_ref[0, g], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * s  # (bkv, bq)
            logits = jnp.where(mask, logits, NEG_INF)
            m = m_ref[g]  # (1, bq)
            m_new = jnp.maximum(m, logits.max(axis=0, keepdims=True))
            m_safe = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
            correction = jnp.exp(m - m_safe)
            p = jnp.exp(logits - m_safe)
            l_ref[g] = l_ref[g] * correction + p.sum(axis=0, keepdims=True)
            if bf16:
                hi = p.astype(jnp.bfloat16)
                lo = (p - hi.astype(jnp.float32)).astype(jnp.bfloat16)
                p = jnp.concatenate([hi, lo], axis=1)  # (bkv, 2 bq)
            pv = lax.dot_general(
                v_blk, p, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # V^T P^T: (D, bq), or the two halves side by side
            if bf16:
                pv = pv[:, :bq] + pv[:, bq:]
            acc_ref[g] = acc_ref[g] * correction + pv
            m_ref[g] = m_safe + jnp.where(m_new <= NEG_INF / 2, NEG_INF, 0.0)

    @pl.when(j == num_kv - 1)
    def _finalize():
        for g in range(group):
            o_ref[0, g] = (
                acc_ref[g] / jnp.maximum(l_ref[g], 1e-30)
            ).T.astype(o_ref.dtype)


def _chunk_kv_index(block_q: int, block_kv: int, num_kv: int,
                    window: int | None):
    """:func:`_causal_kv_index`'s clamp with the prefetched offset in the
    static's place: the mapped block stays constant across a q-tile's
    skipped cells, so their copies are elided."""

    def kv_index(bk, i, j, off_ref):
        off = off_ref[0]
        last_block = jnp.clip(
            (off + (i + 1) * block_q - 1) // block_kv, 0, num_kv - 1)
        blk = jnp.minimum(j, last_block)
        if window is not None:
            first_block = jnp.clip(
                (off + i * block_q - (window - 1)) // block_kv, 0, num_kv - 1)
            blk = jnp.maximum(blk, first_block)
        return (bk, blk, 0)

    return kv_index


def _tile_block(cap: int, seq: int, rows: int) -> int:
    """The largest divisor of ``seq`` up to ``cap`` that is a whole number
    of 128 lanes, or else of ``rows`` (a sublane tile)."""
    for unit in (128, rows):
        for b in range(min(cap, seq) // unit * unit, 0, -unit):
            if seq % b == 0:
                return b
    raise ValueError(f"{seq} rows are no whole number of {rows}-row tiles")


def chunk_flash_fits(dtype, head_dim: int, rows) -> bool:
    """Whether :func:`chunk_flash_attention` takes a logical cache of
    ``dtype`` with heads of ``head_dim`` at the row counts ``rows`` (the
    cache's ``S_max`` and every chunk width): the head a whole number of
    128 lanes and every count a whole number of the dtype's sublane tiles,
    so that every block is cut on a tile. A rule on shapes, the same off
    the TPU as on it (:func:`paged_decode_fits`)."""
    tile = _sublane_rows(dtype)
    return head_dim % 128 == 0 and all(int(n) % tile == 0 for n in rows)


def chunk_flash_attention(
    q,
    k,
    v,
    q_offset,
    *,
    window: int | None = None,
    scale: float | None = None,
    interpret: bool | None = None,
):
    """Causal attention of a block of query rows over a logical cache.

    ``q`` (B, H, s, head_dim), already rotated, its row ``r`` at position
    ``q_offset + r``; ``k`` / ``v`` (B, kv_heads, S_max, head_dim),
    unexpanded, holding the chunk's own rows at ``[q_offset, q_offset + s)``
    already; ``q_offset`` any int32 scalar, traced or not, with ``q_offset +
    s <= S_max``. A row attends the positions up to its own, the last
    ``window`` of them where one is given. Returns (B, H, s, head_dim) in
    ``q``'s dtype. No (s, S_max) score matrix exists: the key blocks a chunk
    cannot see are neither copied nor computed, whatever they hold.

    Scores and softmax statistics are f32 over the operands' own dtype, and
    a probability keeps 16 bits in the value product over a bf16 cache
    (f32 over an f32 one), as in :func:`paged_decode_attention`'s group
    form. ``scale`` multiplies the scores (head_dim^-1/2 by default)."""
    b, h, sq, dh = q.shape
    kv, s_max = k.shape[1], k.shape[2]
    if k.shape != v.shape or k.shape[0] != b or k.shape[3] != dh or h % kv:
        raise ValueError(
            f"q {q.shape} does not fit cache k {k.shape} / v {v.shape}")
    if sq > s_max:
        raise ValueError(f"{sq} query rows exceed the cache's {s_max}")
    if not chunk_flash_fits(k.dtype, dh, (s_max, sq)):
        raise ValueError(
            f"chunk of {sq} rows over a cache {k.shape} of {k.dtype} is off "
            f"the tile: the dense branch serves it")
    if window is not None and window < 1:
        raise ValueError(f"window must be >= 1, got {window}")
    if window is not None and window >= s_max:
        window = None  # it hides no position of this cache
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    rows = _sublane_rows(k.dtype)
    # Blocks of 512 x 512 at up to 12 query heads a kv head (measured:
    # 0.58 / 0.30 / 0.15 ms behind 3072 / 1024 / 0 rows; 256 x 256 0.75 /
    # 0.37 / 0.17; 512 x 1024 0.60 / - / 0.18): a cell holds the group's q
    # rows and its f32 accumulator, about 6k rows between them.
    block_q = _tile_block(max(rows, min(512, 6144 // (h // kv))), sq, rows)
    return _chunk_flash_call(
        q, k, v, jnp.asarray(q_offset, jnp.int32).reshape(1),
        scale=_scale(q, scale), window=window, block_q=block_q,
        block_kv=_tile_block(512, s_max, rows), interpret=bool(interpret))


# Jitted like _paged_decode_call: a model's layers trace the kernel once.
@functools.partial(
    jax.jit,
    static_argnames=("scale", "window", "block_q", "block_kv", "interpret"),
)
def _chunk_flash_call(q, k, v, offset, *, scale, window, block_q, block_kv,
                      interpret):
    b, h, sq, dh = q.shape
    kv, s_max = k.shape[1], k.shape[2]
    group = h // kv
    num_kv = s_max // block_kv
    kernel = functools.partial(
        _chunk_flash_kernel, block_kv=block_kv, num_kv=num_kv, sq=sq,
        s=scale, window=window)
    kv_index = _chunk_kv_index(block_q, block_kv, num_kv, window)
    q_spec = pl.BlockSpec(
        (1, group, block_q, dh), lambda bk, i, j, off: (bk, 0, i, 0))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b * kv, sq // block_q, num_kv),
            in_specs=[
                q_spec,
                pl.BlockSpec((1, block_kv, dh), kv_index),
                pl.BlockSpec((1, block_kv, dh), kv_index),
            ],
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((group, dh, block_q), jnp.float32),
                pltpu.VMEM((group, 1, block_q), jnp.float32),
                pltpu.VMEM((group, 1, block_q), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b * kv, group, sq, dh), q.dtype),
        interpret=interpret,
        name="prefill_chunk_attention",
    )(offset, q.reshape(b * kv, group, sq, dh),
      k.reshape(b * kv, s_max, dh), v.reshape(b * kv, s_max, dh))
    return out.reshape(b, h, sq, dh)
