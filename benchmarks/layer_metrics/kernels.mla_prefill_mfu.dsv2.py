"""Useful FLOPs of the expanded latent-attention prefill calls whose program spans (engine.start and engine.prefill_chunk with mla_form "expanded") start in the traced window, over the summed device time of the mla_chunk_attention custom calls (ops/attention.py: K and V made in the kernel from the latent rows), over the bf16 peak. A call counts the expansion of the rows below its end, 2 r H (nope + v) a row a layer (4.2 MFLOP at DeepSeek-V2's widths), and the causal pairs of its real query rows at counts_dsv2.pair_flops (nope + rope + v wide). The key block's rows past the end, the rope tile's padding lanes, the second bf16 half of a probability and a padded tail's rows are the kernel's work and not counted; a final chunk's rows recomputed over the chunk before are real rows and counted. Nothing on a program without the custom call."""

KERNEL = "mla_chunk_attention"


def _flops(cfg, first: int, end: int) -> int:
    """Query rows first .. end-1, each attending the positions up to its
    own, over every layer."""
    from benchmarks import counts_dsv2

    _, h, r, nope, _, v = counts_dsv2._widths(cfg)
    pairs = (first + 1 + end) * (end - first) // 2
    return (2 * r * h * (nope + v) * end * int(cfg["num_layers"])
            + counts_dsv2.pair_flops(cfg) * pairs)


def read(c):
    from benchmarks import program_spans

    tr = c.get("trace") or {}
    t = sum(v for k, v in tr.get("op_time_s", {}).items() if KERNEL in k)
    lo, hi = c["t_open"], c["t_open"] + c["trace_s"]
    flops = 0
    for name in ("engine.start", "engine.prefill_chunk"):
        for t0, _, a in program_spans.records(name, lo, hi) or []:
            if not (a and a.get("mla_form") == "expanded" and lo <= t0 < hi):
                continue
            if name == "engine.start":
                first, end = int(a.get("matched", 0)), int(a["prompt_len"])
            else:
                first, end = int(a["offset"]), int(a["offset"] + a["width"])
            flops += _flops(c["model_cfg"], first, end)
    if not t or not flops:
        return None
    return 100.0 * flops / t / c["peaks"]["bf16_flops"]
