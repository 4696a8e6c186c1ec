"""Operations and bytes the DeepSeek-V2-Lite stage needs, from shapes alone.

``cfg`` is the configuration file's ``transformer_config`` dict. Beside
``counts_nemotron.py``: every layer is multi-head latent attention (q, the
latent and its rope key in, K and V out of the latent, o back), then the
dense SwiGLU of ``d_ff`` in the ``dense_layers`` leading layers and, in the
rest, ``experts_per_token`` of ``num_experts`` SwiGLU experts behind a
linear router plus the shared experts every token passes; an untied head.
A position is cached as one latent row of ``kv_lora_rank`` +
``qk_rope_head_dim`` values (1,152 B a layer in bfloat16; the pool pads it
to 640 lanes, which is not counted: the padding shows as lost roofline
share). 2 FLOPs a MAC. The norms, the softmax and the router's top-k are a
few thousandths of a layer's work and not counted.
"""

from __future__ import annotations


def _widths(cfg: dict):
    return (int(cfg["d_model"]), int(cfg["num_heads"]), int(
        cfg["kv_lora_rank"]), int(cfg["qk_nope_head_dim"]), int(
        cfg["qk_rope_head_dim"]), int(cfg["v_head_dim"]))


def layers(cfg: dict) -> tuple[int, int]:
    """(dense layers, expert layers)."""
    n, dense = int(cfg["num_layers"]), int(cfg.get("dense_layers", 0))
    return dense, n - dense


def attention_params(cfg: dict) -> int:
    """Matmul parameters of one latent attention sublayer: q, kv_a, kv_b
    (whose halves are the absorbed form's W_UK and W_UV: the same numbers
    either way) and o."""
    d, h, r, nope, rope, v = _widths(cfg)
    return d * h * (nope + rope) + d * (r + rope) + r * h * (nope + v) + (
        h * v * d)


def dense_mlp_params(cfg: dict) -> int:
    return 3 * int(cfg["d_model"]) * int(cfg["d_ff"])


def expert_params(cfg: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * int(cfg["d_model"]) * int(cfg["expert_width"])


def shared_params(cfg: dict) -> int:
    """What every token passes of one expert layer whatever its route: the
    router and the shared experts."""
    d = int(cfg["d_model"])
    return d * int(cfg["num_experts"]) + 3 * d * int(
        cfg["shared_expert_width"])


def matmul_params(cfg: dict) -> tuple[int, int]:
    """(per-token matmul parameters of the layers, with
    ``experts_per_token`` experts and the shared ones an expert layer; of
    the output head)."""
    dense, moe = layers(cfg)
    k = int(cfg["experts_per_token"])
    body = (dense + moe) * attention_params(cfg) + dense * dense_mlp_params(
        cfg) + moe * (shared_params(cfg) + k * expert_params(cfg))
    return body, int(cfg["d_model"]) * int(cfg["vocab_size"])


def pair_flops(cfg: dict) -> int:
    """The model's attention FLOPs for one (query, key) pair over the
    layers, whatever form computes it: q.k at nope + rope, p.v at v."""
    _, h, _, nope, rope, v = _widths(cfg)
    return 2 * h * (nope + rope + v) * int(cfg["num_layers"])


def latent_pair_flops(cfg: dict) -> int:
    """FLOPs of the absorbed kernel for one (query, cached position) pair of
    one layer: every head's scores against the row (r + rope) and its value
    product over the latent (r): 34,816 at DeepSeek-V2's widths."""
    _, h, r, _, rope, _ = _widths(cfg)
    return 2 * h * (r + rope) + 2 * h * r


def latent_row_bytes(cfg: dict, bytes_per_value: int = 2) -> int:
    """Bytes of one cached position of one layer that the kernel needs:
    1,152 at DeepSeek-V2's widths in bfloat16."""
    _, _, r, _, rope, _ = _widths(cfg)
    return (r + rope) * bytes_per_value


def latent_query_bytes(cfg: dict, bytes_per_value: int = 2) -> int:
    """One slot's absorbed queries of one layer, in and out of the kernel
    (16 x 576 in, 16 x 512 out in bfloat16)."""
    _, h, r, _, rope, _ = _widths(cfg)
    return h * (2 * r + rope) * bytes_per_value


def serve_flops(cfg: dict, prompt_spans, decode_rows_sum: int,
                decode_tokens: int) -> int:
    """Useful forward FLOPs of a serving window. ``prompt_spans``: (start,
    stop) prompt positions actually computed; the head counts once for each
    request's last prompt position and once for each decode token;
    attention by :func:`pair_flops` over the pairs of every computed token
    (``decode_rows_sum``: over the decode tokens, the rows each attended)."""
    body, head = matmul_params(cfg)
    tokens = sum(b - s for s, b in prompt_spans) + decode_tokens
    pairs = sum((s + b + 1) * (b - s) // 2 for s, b in prompt_spans)
    return (2 * body * tokens + 2 * head * (len(prompt_spans) + decode_tokens)
            + pair_flops(cfg) * (pairs + int(decode_rows_sum)))


def shared_weight_bytes(cfg: dict, bytes_per_param: int = 2) -> int:
    """Bytes one decode round reads whatever the routing: every attention
    sublayer, the dense MLP, the routers and shared experts once, and the
    head (the few embedding rows gathered are not counted)."""
    dense, moe = layers(cfg)
    return ((dense + moe) * attention_params(cfg)
            + dense * dense_mlp_params(cfg) + moe * shared_params(cfg)
            + int(cfg["d_model"]) * int(cfg["vocab_size"])) * bytes_per_param


def expert_bytes(cfg: dict, bytes_per_param: int = 2) -> int:
    return expert_params(cfg) * bytes_per_param


def decode_round_bytes(cfg: dict, rows, experts_touched, lanes=0) -> float:
    """Least bytes one decode round moves: the shared weights once, the
    experts that got a pair (``experts_touched``: (layer, expert) pairs,
    from the round's counter), every attended position's latent row in
    every layer (``rows``: positions attended, summed over the slots), and
    the ``lanes`` live slots' absorbed queries in and out."""
    n = int(cfg["num_layers"])
    return (shared_weight_bytes(cfg) + expert_bytes(cfg) * experts_touched
            + latent_row_bytes(cfg) * n * rows
            + latent_query_bytes(cfg) * n * lanes)


def expert_product_flops(cfg: dict, pairs: int) -> int:
    """FLOPs of the routed products of one layer for ``pairs`` (token,
    expert) pairs: each passes one expert."""
    return 2 * expert_params(cfg) * int(pairs)


def weight_bytes(cfg: dict, bytes_per_param: int = 2) -> int:
    """Every parameter of the stage: 5.68 GB at the cell's cut."""
    dense, moe = layers(cfg)
    d, vocab = int(cfg["d_model"]), int(cfg["vocab_size"])
    return (dense + moe) * attention_params(cfg) * bytes_per_param + (
        dense * dense_mlp_params(cfg) + moe * (
            shared_params(cfg) + int(cfg["num_experts"]) * expert_params(cfg))
        + 2 * d * vocab) * bytes_per_param


def pool_bytes(cfg: dict, slots: int, max_len: int, page_size: int,
               row_lanes: int, bytes_per_value: int = 2) -> int:
    """The latent pool as the engine builds it: every slot's worth of pages
    and the trash page, every layer, ``row_lanes`` a row."""
    pages = slots * (max_len // page_size) + 1
    return pages * int(cfg["num_layers"]) * page_size * row_lanes * (
        bytes_per_value)
