"""Forward and backward attention FLOPs from shapes over the bf16 peak, against the summed device time of the flash kernels events (the custom calls of ops/attention.py). Bound by compute."""

import re

KERNEL = re.compile(r"custom-call")


def read(c):
    from benchmarks import counts

    tr = c.get("trace") or {}
    t = sum(v for k, v in tr.get("op_time_s", {}).items() if KERNEL.search(k))
    steps = c.get("traced_steps")
    if not t or not steps:
        return None
    flops = counts.flash_flops(c["model_cfg"], c["global_batch"] // c["chips"],
                               c["seq"]) * steps
    return 100.0 * flops / t / c["peaks"]["bf16_flops"]
