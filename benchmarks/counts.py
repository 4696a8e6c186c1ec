"""Operations and bytes the algorithm needs, from shapes alone.

``cfg`` is the configuration file's ``transformer_config`` dict. The training
count is a copy of ``utils/flops.transformer_train_flops`` (2 FLOPs a MAC,
backward twice the forward, causal attention at its half triangle, banded
under a sliding window, recomputation not counted); the serving counts are
new. The originals are listed in PERF.md for a later PR to delete.
"""

from __future__ import annotations


def _dims(cfg: dict):
    d = int(cfg["d_model"])
    heads = int(cfg["num_heads"])
    kv = int(cfg.get("num_kv_heads") or heads)
    dh = d // heads
    return d, heads, kv, dh, int(cfg["num_layers"]), int(cfg["d_ff"])


def matmul_params(cfg: dict) -> tuple[int, int]:
    """(per-token matmul parameters of the blocks, of the output head)."""
    d, _, kv, dh, layers, ff = _dims(cfg)
    body = layers * (2 * d * d + 2 * d * kv * dh + 2 * d * ff)
    return body, d * int(cfg["vocab_size"])


def param_count(cfg: dict) -> int:
    """Every parameter held: matmuls, biases, norms, embeddings, head."""
    d, _, kv, dh, layers, ff = _dims(cfg)
    body, head = matmul_params(cfg)
    n = body + head + int(cfg["vocab_size"]) * d
    if cfg.get("position", "learned") == "learned":
        n += int(cfg["max_seq_len"]) * d
    if cfg.get("use_bias", True):
        n += layers * (d + 2 * kv * dh + d + ff + d) + int(cfg["vocab_size"])
    n += layers * 4 * d + 2 * d  # LayerNorm scale and bias
    return n


def _attended(pos: int, window) -> int:
    """Keys position ``pos`` (0-based) attends, itself included."""
    n = pos + 1
    return min(n, int(window)) if window else n


def attn_flops_span(cfg: dict, start: int, stop: int) -> int:
    """Forward attention FLOPs (scores and values) of the tokens at
    positions [start, stop) of one sequence, every layer."""
    d, _, _, _, layers, _ = _dims(cfg)
    w = cfg.get("attention_window")
    if not w or stop <= w:
        pairs = (stop * (stop + 1) - start * (start + 1)) // 2
    else:
        pairs = sum(_attended(i, w) for i in range(start, stop))
    return 4 * pairs * d * layers


def train_flops(cfg: dict, batch: int, seq: int) -> int:
    """Model FLOPs of ONE optimizer step on (batch, seq) tokens."""
    body, head = matmul_params(cfg)
    dense = 2 * batch * seq * (body + head)
    attn = batch * attn_flops_span(cfg, 0, seq)
    return 3 * (dense + attn)


def flash_flops(cfg: dict, batch: int, seq: int) -> int:
    """Forward + backward attention FLOPs of one step: what the flash
    kernels are there to compute (backward counted at twice the forward,
    the kernel's own recomputation of scores not counted)."""
    return 3 * batch * attn_flops_span(cfg, 0, seq)


def serve_flops(cfg: dict, prompt_spans, decode_lengths_sum: int,
                decode_tokens: int) -> int:
    """Useful forward FLOPs of a serving window. ``prompt_spans`` is a list
    of (start, stop) prompt positions actually computed (start > 0 where a
    prefix was adopted); the head is counted once for each request's last
    prompt position and once for each decode token. ``decode_lengths_sum``
    is the sum, over decode tokens, of the cache length each attended."""
    d, _, _, _, layers, _ = _dims(cfg)
    body, head = matmul_params(cfg)
    prompt_tokens = sum(b - a for a, b in prompt_spans)
    f = 2 * body * (prompt_tokens + decode_tokens)
    f += 2 * head * (len(prompt_spans) + decode_tokens)
    f += sum(attn_flops_span(cfg, a, b) for a, b in prompt_spans)
    f += 4 * decode_lengths_sum * d * layers
    return f


def weight_bytes(cfg: dict, bytes_per_param: int = 2) -> int:
    """Bytes one decode round has to read of the weights: every matmul
    parameter once (the token embedding is a gather of a few rows)."""
    body, head = matmul_params(cfg)
    return (body + head) * bytes_per_param


def kv_bytes_per_token(cfg: dict, bytes_per_value: int = 2) -> int:
    _, _, kv, dh, layers, _ = _dims(cfg)
    return layers * kv * dh * 2 * bytes_per_value


def decode_round_bytes(cfg: dict, live_lengths_sum: int) -> int:
    """Least bytes one decode round moves: the weights once and the K and V
    rows of every live position of every active slot."""
    return weight_bytes(cfg) + kv_bytes_per_token(cfg) * int(live_lengths_sum)
