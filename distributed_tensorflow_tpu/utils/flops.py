"""Model-FLOPs accounting and chip peak throughput — the MFU denominator.

MFU (model FLOPs utilization) = model FLOPs executed per second / chip peak
FLOP/s. "Model FLOPs" counts only the mathematically required matmul work of
the model itself (fwd + bwd), NOT rematerialization recompute, and counts
causal attention at its actual half-triangle cost — the standard accounting
of the PaLM appendix / How-to-Scale-Your-Model, under which a perfectly
fused dense causal transformer tops out below 1.0 by definition.

The reference never measured compute efficiency at all (its README has no
numbers, ``/root/reference/README.md:1-2``); this module is what makes the
framework's per-chip performance story falsifiable and trackable per round.
"""

from __future__ import annotations

# bf16 peak matmul FLOP/s per chip, by jax device_kind substring (checked in
# order). Public spec-sheet numbers: TPU v4 275 T, v5e 197 T, v5p 459 T,
# v6e (Trillium) 918 T.
_PEAK_BF16 = (
    ("v6e", 918e12),
    ("v6", 918e12),
    ("v5p", 459e12),
    ("v5 lite", 197e12),
    ("v5litepod", 197e12),
    ("v5e", 197e12),
    ("v4", 275e12),
)


def _chip_table_lookup(table, what: str, device=None) -> float | None:
    """``table`` entry for ``device`` (default: jax.devices()[0]). None off
    TPU — callers then report the derived number as absent rather than
    invent a denominator. A TPU whose ``device_kind`` the table does not
    know is an ERROR, not a default: a silent None there turns every MFU
    and roofline readout on a new chip into a missing field."""
    import jax

    if device is None:
        device = jax.devices()[0]
    if device.platform != "tpu":
        return None
    kind = device.device_kind.lower()
    for sub, value in table:
        if sub in kind:
            return value
    raise ValueError(
        f"no {what} for TPU device_kind {device.device_kind!r}: add it to "
        "utils/flops.py with its source"
    )


def chip_peak_flops(device=None) -> float | None:
    """Peak bf16 FLOP/s of ``device`` — the MFU denominator
    (:func:`_chip_table_lookup`: None off TPU, error on an unknown TPU)."""
    return _chip_table_lookup(_PEAK_BF16, "bf16 peak FLOP/s", device)


def transformer_train_flops(
    cfg, batch_size: int, seq_len: int | None = None, causal: bool = True
) -> int:
    """Model matmul FLOPs for ONE optimizer step (fwd + bwd) of
    ``TransformerLM(cfg)`` on ``(batch_size, seq_len)`` tokens.

    Accounting (2 FLOPs per MAC, backward = 2x forward, so train = 3x fwd):
      * parameter matmuls: per layer 4·d² (q,k,v,o) + 2·d·d_ff (ffn in/out),
        plus the d·vocab logits projection; fwd cost 2·T·N_matmul.
        Embedding lookup is a gather — 0 matmul FLOPs.
      * attention scores+values: per layer fwd 4·B·S²·d dense, halved for
        causal (the blockwise/flash kernels actually skip the masked half,
        and masked work isn't "model FLOPs" either way). With a sliding
        ``cfg.attention_window`` the causal count is the BANDED area —
        position i attends min(i+1, window) keys — so a windowed run's MFU
        is not credited the full triangle it never computes.
    Remat recompute is deliberately NOT counted — MFU measures useful work.
    """
    s = int(cfg.max_seq_len if seq_len is None else seq_len)
    b = int(batch_size)
    d = int(cfg.d_model)
    tokens = b * s
    # GQA (num_kv_heads < num_heads) shrinks the k/v projections: q and o
    # stay d x d, k/v are d x (kv_heads * head_dim) each.
    kv = int(cfg.kv_heads)
    kv_width = (d // cfg.num_heads) * kv
    n_matmul = (
        cfg.num_layers * (2 * d * d + 2 * d * kv_width + 2 * d * cfg.d_ff)
        + d * cfg.vocab_size
    )
    dense = 2 * tokens * n_matmul
    window = getattr(cfg, "attention_window", None)
    if causal and window is not None and window < s:
        # Exact attended (q, k) pair count of the band: the first `window`
        # rows ramp 1..window, the rest attend `window` keys each.
        pairs = window * (window + 1) // 2 + (s - window) * window
        attn = 4 * b * pairs * d * cfg.num_layers
    else:
        attn = 4 * b * s * s * d * cfg.num_layers
        if causal:
            attn //= 2
    return 3 * (dense + attn)


# HBM bandwidth (bytes/s) per chip, by device_kind substring — the decode
# roofline denominator (each KV-cache decode step re-reads the whole param
# tree, so tokens/s ≤ B · bw / param_bytes). Public spec-sheet numbers:
# v4 1228 GB/s, v5e 819 GB/s, v5p 2765 GB/s, v6e 1640 GB/s.
_HBM_BW = (
    ("v6e", 1640e9),
    ("v6", 1640e9),
    ("v5p", 2765e9),
    ("v5 lite", 819e9),
    ("v5litepod", 819e9),
    ("v5e", 819e9),
    ("v4", 1228e9),
)


def chip_hbm_bandwidth(device=None) -> float | None:
    """Peak HBM bytes/s of ``device`` — the decode roofline denominator
    (:func:`_chip_table_lookup`: None off TPU, error on an unknown TPU)."""
    return _chip_table_lookup(_HBM_BW, "HBM bandwidth", device)
