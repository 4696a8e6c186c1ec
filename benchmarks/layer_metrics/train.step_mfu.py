"""Model FLOPs of the steps completed in the window (recomputation not counted) per second over chips times the bf16 peak."""


def read(c):
    from benchmarks import counts

    flops = counts.train_flops(c["model_cfg"], c["global_batch"], c["seq"])
    return 100.0 * flops * c["steps"] / c["window_s"] / (
        c["peaks"]["bf16_flops"] * c["chips"])
