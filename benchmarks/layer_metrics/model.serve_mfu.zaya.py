"""Useful forward FLOPs of the whole window (ZAYA1's matmul parameters with ONE expert a layer times prompt tokens computed and output tokens, attention in the latent from the rows each token attended) per second, over the bf16 peak: the share of the whole step that bounds a later claim on out_tok_s."""


def read(c):
    from benchmarks import counts_zaya, zaya_cell

    rs = zaya_cell.moe_rounds(c)
    if not rs:
        return None
    spans = [(m, p) for m, p in c["counters"]["prompt_spans"]]
    flops = counts_zaya.serve_flops(
        c["model_cfg"], spans,
        sum(r["live_tokens"] + r["active"] for r in rs),
        sum(r["active"] for r in rs))
    return 100.0 * flops / c["window_s"] / (
        c["peaks"]["bf16_flops"] * c["chips"])
