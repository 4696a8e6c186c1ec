"""Useful forward FLOPs of the window (matmul parameters times prompt tokens actually computed and output tokens, attention from the live lengths) per second, over the bf16 peak."""


def read(c):
    from benchmarks import counts

    n = c["counters"]
    rounds = n["decode_rounds"]
    tokens = sum(r[2] for r in rounds)
    lengths = sum(r[3] + r[2] for r in rounds)  # each attends itself too
    flops = counts.serve_flops(c["model_cfg"], n["prompt_spans"], lengths,
                               tokens)
    if not flops:
        return None
    return 100.0 * flops / c["window_s"] / (
        c["peaks"]["bf16_flops"] * c["chips"])
