"""Operations and bytes the ZAYA1 block needs, from shapes alone.

``cfg`` is the configuration file's ``transformer_config`` dict. Beside
``benchmarks/counts.py`` and ``counts_evabyte.py``, for the block whose
attention runs in a latent of ``num_heads x head_dim`` (CCA: one fused
projection in, two small convolutions, one projection out), whose MLP is
ONE of ``num_experts`` gated experts chosen by a small MLP router, and whose
head is the embedding. 2 FLOPs a MAC. The depthwise convolution, the norms
and the softmaxes are a few thousandths of a layer's work and not counted.
"""

from __future__ import annotations


def _dims(cfg: dict):
    return (int(cfg["d_model"]), int(cfg["num_heads"]),
            int(cfg["num_kv_heads"]), int(cfg["head_dim"]),
            int(cfg["num_layers"]))


def shared_params(cfg: dict) -> int:
    """Matmul parameters of one layer that every token passes whatever its
    route: the CCA projections in and out, the per-head convolution, the
    router."""
    d, heads, kv, dh, _ = _dims(cfg)
    rh = int(cfg["router_hidden"])
    return ((heads + 2 * kv) * dh * d + heads * dh * d
            + (heads + kv) * int(cfg["cca_time1"]) * dh * dh
            + d * rh + 2 * rh * rh + rh * int(cfg["num_experts"]))


def expert_params(cfg: dict) -> int:
    """One expert: gate, up and down."""
    return 3 * int(cfg["d_model"]) * int(cfg["expert_width"])


def matmul_params(cfg: dict) -> tuple[int, int]:
    """(per-token matmul parameters of the blocks, with ONE expert a layer;
    of the output head, which is the embedding)."""
    d, _, _, _, layers = _dims(cfg)
    return (layers * (shared_params(cfg) + expert_params(cfg)),
            d * int(cfg["vocab_size"]))


def attn_flops_span(cfg: dict, start: int, stop: int) -> int:
    """Forward attention FLOPs (scores and values, in the latent) of the
    tokens at positions [start, stop) of one sequence, every layer."""
    _, heads, _, dh, layers = _dims(cfg)
    rows = (start + stop + 1) * (stop - start) // 2  # sum of pos + 1
    return 4 * rows * heads * dh * layers


def serve_flops(cfg: dict, prompt_spans, decode_rows_sum: int,
                decode_tokens: int) -> int:
    """Useful forward FLOPs of a serving window. ``prompt_spans``: (start,
    stop) prompt positions actually computed; the head counts once for each
    request's last prompt position and once for each decode token.
    ``decode_rows_sum``: over the decode tokens, the rows each attended."""
    _, heads, _, dh, layers = _dims(cfg)
    body, head = matmul_params(cfg)
    prompt_tokens = sum(b - a for a, b in prompt_spans)
    f = 2 * body * (prompt_tokens + decode_tokens)
    f += 2 * head * (len(prompt_spans) + decode_tokens)
    f += sum(attn_flops_span(cfg, a, b) for a, b in prompt_spans)
    f += 4 * int(decode_rows_sum) * heads * dh * layers
    return f


def shared_weight_bytes(cfg: dict, bytes_per_param: int = 2) -> int:
    """Bytes one decode round reads whatever the routing: every layer's
    attention, convolution and router weights once, and the head (the
    embedding as a matrix; the few rows gathered as an embedding are in
    it)."""
    d, _, _, _, layers = _dims(cfg)
    return (layers * shared_params(cfg)
            + d * int(cfg["vocab_size"])) * bytes_per_param


def expert_bytes(cfg: dict, bytes_per_param: int = 2) -> int:
    return expert_params(cfg) * bytes_per_param


def row_bytes(cfg: dict, bytes_per_value: int = 2, layers=None) -> int:
    """K and V of one attended row over ``layers`` layers (all by default):
    1 KiB a layer at 2 kv heads of 128 in bfloat16."""
    _, _, kv, dh, n = _dims(cfg)
    return (n if layers is None else layers) * 2 * kv * dh * bytes_per_value


def decode_round_bytes(cfg: dict, rows, experts_touched) -> float:
    """Least bytes one decode round moves: the shared weights once, the
    experts that got a token (``experts_touched``: (layer, expert) pairs,
    from the round's counter), and the K and V of every row attended."""
    return (shared_weight_bytes(cfg) + expert_bytes(cfg) * experts_touched
            + row_bytes(cfg) * rows)


def expert_product_flops(cfg: dict, tokens: int) -> int:
    """FLOPs of the expert products of one layer for ``tokens`` routed
    tokens: each passes one expert."""
    return 2 * expert_params(cfg) * int(tokens)
