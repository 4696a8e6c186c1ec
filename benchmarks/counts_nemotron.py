"""Operations and bytes the Nemotron-H stage needs, from shapes alone.

``cfg`` is the configuration file's ``transformer_config`` dict. Beside
``benchmarks/counts.py``, ``counts_evabyte.py`` and ``counts_zaya.py``, for
layers of one kind each (``layer_pattern``): ``M`` a Mamba-2 mixer (one
projection in, one out, and a recurrent state read and written at every
token), ``E`` ``experts_per_token`` of ``num_experts`` two-matrix experts
behind a linear router plus a shared expert every token passes, ``*`` GQA
attention; an untied head. 2 FLOPs a MAC. The convolution, the norms, the
softmax and the router's top-k are a few thousandths of a layer's work and
not counted.
"""

from __future__ import annotations


def _kinds(cfg: dict) -> tuple[int, int, int]:
    pat = cfg["layer_pattern"]
    return pat.count("M"), pat.count("E"), pat.count("*")


def _fan(cfg: dict) -> int:
    """Matrices into an expert's hidden layer: gate and up, or up alone."""
    return 2 if cfg.get("expert_act", "swiglu") == "swiglu" else 1


def mamba_params(cfg: dict) -> int:
    """Matmul parameters of one ``M`` layer: ``in_proj`` and ``out_proj``."""
    d, h, p = int(cfg["d_model"]), int(cfg["ssm_heads"]), int(
        cfg["ssm_head_dim"])
    inner = h * p
    conv = inner + 2 * int(cfg["ssm_groups"]) * int(cfg["ssm_state"])
    return d * (inner + conv + h) + inner * d


def attention_params(cfg: dict) -> int:
    d, heads, kv, dh = (int(cfg[k]) for k in (
        "d_model", "num_heads", "num_kv_heads", "head_dim"))
    return d * (heads + 2 * kv) * dh + heads * dh * d


def expert_params(cfg: dict) -> int:
    """One routed expert."""
    return (_fan(cfg) + 1) * int(cfg["d_model"]) * int(cfg["expert_width"])


def shared_params(cfg: dict) -> int:
    """What every token passes of one ``E`` layer whatever its route: the
    router and the shared expert."""
    d = int(cfg["d_model"])
    return d * int(cfg["num_experts"]) + (_fan(cfg) + 1) * d * int(
        cfg.get("shared_expert_width", 0))


def matmul_params(cfg: dict) -> tuple[int, int]:
    """(per-token matmul parameters of the layers, with
    ``experts_per_token`` experts and the shared one an ``E`` layer; of the
    output head)."""
    m, e, a = _kinds(cfg)
    k = int(cfg.get("experts_per_token", 1))
    body = (m * mamba_params(cfg) + a * attention_params(cfg)
            + e * (shared_params(cfg) + k * expert_params(cfg)))
    return body, int(cfg["d_model"]) * int(cfg["vocab_size"])


def scan_flops_a_token(cfg: dict) -> int:
    """FLOPs the recurrence itself takes a token over the ``M`` layers, in
    the form the decode step computes it: the state's decay, its update
    x (x) B and its read-out against C, 2 FLOPs each an element."""
    m, _, _ = _kinds(cfg)
    return m * 6 * int(cfg["ssm_heads"]) * int(cfg["ssm_head_dim"]) * int(
        cfg["ssm_state"])


def attn_flops_span(cfg: dict, start: int, stop: int) -> int:
    """Forward attention FLOPs (scores and values) of the tokens at
    positions [start, stop) of one sequence, over the ``*`` layers."""
    _, _, a = _kinds(cfg)
    rows = (start + stop + 1) * (stop - start) // 2  # sum of pos + 1
    return 4 * rows * int(cfg["num_heads"]) * int(cfg["head_dim"]) * a


def serve_flops(cfg: dict, prompt_spans, decode_rows_sum: int,
                decode_tokens: int) -> int:
    """Useful forward FLOPs of a serving window. ``prompt_spans``: (start,
    stop) prompt positions actually computed; the head counts once for each
    request's last prompt position and once for each decode token.
    ``decode_rows_sum``: over the decode tokens, the rows each attended."""
    _, _, a = _kinds(cfg)
    body, head = matmul_params(cfg)
    tokens = sum(b - s for s, b in prompt_spans) + decode_tokens
    f = (2 * body + scan_flops_a_token(cfg)) * tokens
    f += 2 * head * (len(prompt_spans) + decode_tokens)
    f += sum(attn_flops_span(cfg, s, b) for s, b in prompt_spans)
    f += 4 * int(decode_rows_sum) * int(cfg["num_heads"]) * int(
        cfg["head_dim"]) * a
    return f


def shared_weight_bytes(cfg: dict, bytes_per_param: int = 2) -> int:
    """Bytes one decode round reads whatever the routing: the Mamba,
    attention, router and shared-expert weights once, and the head (the few
    rows gathered of the embedding are not counted)."""
    m, e, a = _kinds(cfg)
    return (m * mamba_params(cfg) + a * attention_params(cfg)
            + e * shared_params(cfg)
            + int(cfg["d_model"]) * int(cfg["vocab_size"])) * bytes_per_param


def expert_bytes(cfg: dict, bytes_per_param: int = 2) -> int:
    return expert_params(cfg) * bytes_per_param


def state_bytes_a_lane(cfg: dict, conv_bytes: int = 2) -> int:
    """The recurrent state (float32) and the convolution's last inputs of
    one slot, over the ``M`` layers: read once and written once a round."""
    m, _, _ = _kinds(cfg)
    h, p, n = (int(cfg[k]) for k in ("ssm_heads", "ssm_head_dim",
                                     "ssm_state"))
    conv = (int(cfg["ssm_conv"]) - 1) * (
        h * p + 2 * int(cfg["ssm_groups"]) * n)
    return m * (4 * h * p * n + conv_bytes * conv)


def row_bytes(cfg: dict, bytes_per_value: int = 2) -> int:
    """K and V of one attended row over the ``*`` layers: 1 KiB a layer at 2
    kv heads of 128 in bfloat16."""
    _, _, a = _kinds(cfg)
    return a * 2 * int(cfg["num_kv_heads"]) * int(
        cfg["head_dim"]) * bytes_per_value


def decode_round_bytes(cfg: dict, rows, experts_touched, lanes) -> float:
    """Least bytes one decode round moves: the shared weights once, the
    experts that got a pair (``experts_touched``: (layer, expert) pairs,
    from the round's counter), the state of every live lane in and out, and
    the K and V of every row attended."""
    return (shared_weight_bytes(cfg) + expert_bytes(cfg) * experts_touched
            + 2 * state_bytes_a_lane(cfg) * lanes + row_bytes(cfg) * rows)


def expert_product_flops(cfg: dict, pairs: int) -> int:
    """FLOPs of the routed products of one layer for ``pairs`` (token,
    expert) pairs: each passes one expert."""
    return 2 * expert_params(cfg) * int(pairs)
