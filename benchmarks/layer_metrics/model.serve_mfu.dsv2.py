"""Useful forward FLOPs of the whole window (the DeepSeek-V2-Lite stage's matmul parameters with SIX routed experts and the shared ones an expert layer, times prompt tokens computed and output tokens, and attention at the model's own 2 x 16 x (192 + 128) FLOPs a (query, key) pair a layer, whatever form computed it) per second, over the bf16 peak: the share of the whole step that bounds a later claim on out_tok_s."""


def read(c):
    from benchmarks import counts_dsv2, zaya_cell

    rs = [r for r in zaya_cell.moe_rounds(c) or [] if "latent_rows" in r]
    if not rs:
        return None
    spans = [(m, p) for m, p in c["counters"]["prompt_spans"]]
    flops = counts_dsv2.serve_flops(
        c["model_cfg"], spans,
        sum(r["live_tokens"] + r["active"] for r in rs),
        sum(r["active"] for r in rs))
    return 100.0 * flops / c["window_s"] / (
        c["peaks"]["bf16_flops"] * c["chips"])
