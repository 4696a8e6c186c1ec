"""Useful forward FLOPs of the whole window (the Nemotron-H stage's matmul parameters with SIX routed experts and the shared one an expert layer, the recurrence's own work a token, times prompt tokens computed and output tokens, attention from the rows each token attended in the one attention layer) per second, over the bf16 peak: the share of the whole step that bounds a later claim on out_tok_s."""


def read(c):
    from benchmarks import counts_nemotron, zaya_cell

    rs = [r for r in zaya_cell.moe_rounds(c) or [] if "ssm_lanes" in r]
    if not rs:
        return None
    spans = [(m, p) for m, p in c["counters"]["prompt_spans"]]
    flops = counts_nemotron.serve_flops(
        c["model_cfg"], spans,
        sum(r["live_tokens"] + r["active"] for r in rs),
        sum(r["active"] for r in rs))
    return 100.0 * flops / c["window_s"] / (
        c["peaks"]["bf16_flops"] * c["chips"])
