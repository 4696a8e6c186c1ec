"""Useful FLOPs of the prompts started in the traced window (tokens actually computed, adopted prefixes left out) over the device time of the prefill program events, over the bf16 peak."""

PROGRAM = "prefill_fn"


def read(c):
    from benchmarks import counts

    tr = c.get("trace") or {}
    t = sum(v for k, v in tr.get("module_time_s", {}).items() if PROGRAM in k)
    lo, hi = c["t_open"], c["t_open"] + c["trace_s"]
    spans = []
    for rec in c["spans"].within("engine.start", lo, hi):
        req = rec[2]
        if req is not None and lo <= rec[0] < hi:
            spans.append((rec[4] - rec[3], len(req.spec["prompt"])))
    if not t or not spans:
        return None
    body, _ = counts.matmul_params(c["model_cfg"])
    flops = sum(2 * body * (b - a) + counts.attn_flops_span(
        c["model_cfg"], a, b) for a, b in spans)
    return 100.0 * flops / t / c["peaks"]["bf16_flops"]
