"""Useful forward FLOPs of the whole window (EvaByte's matmul parameters times prompt tokens actually computed and output tokens, attention from the rows each token attended: its window and the summaries before it) per second, over the bf16 peak."""


def read(c):
    from benchmarks import counts_evabyte, evabyte_cell

    rows = evabyte_cell.eva_rows(c)
    if not rows:
        return None
    flops = counts_evabyte.serve_flops(
        c["model_cfg"], c["counters"]["prompt_spans"], rows[0] + rows[1],
        rows[2])
    return 100.0 * flops / c["window_s"] / (
        c["peaks"]["bf16_flops"] * c["chips"])
