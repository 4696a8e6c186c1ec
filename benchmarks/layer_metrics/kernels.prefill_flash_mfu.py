"""Useful attention FLOPs (scores and values, counts.attn_flops_span: model.prefill_mfu's sum, its attention term alone) of the prompts started in the traced window, adopted prefixes left out, over the summed device time of the prefill_chunk_attention custom calls (ops/attention.py chunk_flash_attention), over the bf16 peak. The second bf16 half of a probability, a padded chunk's rows past the prompt and a final chunk's recomputed overlap are the kernel's work and not counted: at most 67%. Nothing on a program without the custom call."""

KERNEL = "prefill_chunk_attention"


def read(c):
    from benchmarks import counts

    tr = c.get("trace") or {}
    t = sum(v for k, v in tr.get("op_time_s", {}).items() if KERNEL in k)
    lo, hi = c["t_open"], c["t_open"] + c["trace_s"]
    flops = 0
    for rec in c["spans"].within("engine.start", lo, hi):
        req = rec[2]
        if req is not None and lo <= rec[0] < hi:
            flops += counts.attn_flops_span(
                c["model_cfg"], rec[4] - rec[3], len(req.spec["prompt"]))
    if not t or not flops:
        return None
    return 100.0 * flops / t / c["peaks"]["bf16_flops"]
