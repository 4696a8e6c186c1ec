"""Operations and bytes the EvaByte block needs, from shapes alone.

``cfg`` is the configuration file's ``transformer_config`` dict. Beside
``benchmarks/counts.py`` (the GPT block: two-matrix MLP, one head, K/V bytes
by live length), for the block with a gated three-matrix MLP, a head
``num_pred_heads`` vocabularies wide, and a cache whose attended rows are a
window's exact rows plus one summary row for every chunk of the windows
before it. 2 FLOPs a MAC. The pooling that forms a summary (a 16-row softmax
a chunk) is a few thousandths of a layer's work and is not counted.
"""

from __future__ import annotations


def _dims(cfg: dict):
    d = int(cfg["d_model"])
    return d, int(cfg["num_heads"]), int(cfg["num_layers"]), int(cfg["d_ff"])


def matmul_params(cfg: dict) -> tuple[int, int]:
    """(per-token matmul parameters of the blocks, of the output head):
    q, k, v and o of d x d, three MLP matrices of d x d_ff, and a head of
    d x (vocab x num_pred_heads), all of which a token passes."""
    d, _, layers, ff = _dims(cfg)
    head = d * int(cfg["vocab_size"]) * int(cfg.get("num_pred_heads", 1))
    return layers * (4 * d * d + 3 * d * ff), head


def param_count(cfg: dict) -> int:
    """Every parameter held: matmuls, the embedding, the norms' vectors and
    the two pooling vectors of every head."""
    d, _, layers, _ = _dims(cfg)
    body, head = matmul_params(cfg)
    return (body + head + int(cfg["vocab_size"]) * d
            + layers * (2 * d + 2 * d) + d)


def attended(cfg: dict, pos: int) -> int:
    """Rows position ``pos`` (0-based) attends: its window up to itself,
    and a summary for every chunk of the windows before."""
    w, c = int(cfg["eva_window"]), int(cfg["eva_chunk"])
    return pos % w + 1 + (w // c) * (pos // w)


def attn_flops_span(cfg: dict, start: int, stop: int) -> int:
    """Forward attention FLOPs (scores and values) of the tokens at
    positions [start, stop) of one sequence, every layer."""
    d, _, layers, _ = _dims(cfg)
    return 4 * sum(attended(cfg, i) for i in range(start, stop)) * d * layers


def serve_flops(cfg: dict, prompt_spans, decode_rows_sum: int,
                decode_tokens: int) -> int:
    """Useful forward FLOPs of a serving window. ``prompt_spans``: (start,
    stop) prompt positions actually computed (start > 0 where a prefix was
    adopted); the head counts once for each request's last prompt position
    and once for each decode token. ``decode_rows_sum``: over the decode
    tokens, the rows (summaries and window rows) each attended."""
    d, _, layers, _ = _dims(cfg)
    body, head = matmul_params(cfg)
    prompt_tokens = sum(b - a for a, b in prompt_spans)
    f = 2 * body * (prompt_tokens + decode_tokens)
    f += 2 * head * (len(prompt_spans) + decode_tokens)
    f += sum(attn_flops_span(cfg, a, b) for a, b in prompt_spans)
    f += 4 * int(decode_rows_sum) * d * layers
    return f


def weight_bytes(cfg: dict, bytes_per_param: int = 2) -> int:
    """Bytes one decode round reads of the weights: every matmul parameter
    once (the embedding is a gather of a few rows)."""
    body, head = matmul_params(cfg)
    return (body + head) * bytes_per_param


def row_bytes(cfg: dict, bytes_per_value: int = 2, layers=None) -> int:
    """K and V of one attended row, window row or summary alike, over
    ``layers`` layers (all of them by default): 16 KiB a layer at 32 heads
    of 128 in bfloat16."""
    d, _, n, _ = _dims(cfg)
    return (n if layers is None else layers) * d * 2 * bytes_per_value


def decode_round_bytes(cfg: dict, rows: int) -> int:
    """Least bytes one decode round moves: the weights once and the K and V
    of every row (summary or window) that an active slot's token attends."""
    return weight_bytes(cfg) + row_bytes(cfg) * int(rows)
