"""The routed products of the traced window's decode rounds (the Pallas kernel ops/grouped_matmul.py: device ops named grouped_matmul whose result has one row a (slot, expert) pair, 6 a slot, 192 at 32 slots padded to the kernel's 256) against the larger of their bytes (the experts that got a pair, from the rounds' counter experts_touched, all three matrices) over the HBM bandwidth and their FLOPs (the pairs routed, one expert each) over the bf16 peak. Bound by bytes at a decode batch."""

KERNEL = "grouped_matmul"
# Fewer rows than this: a decode round's product (256 at 32 slots); a tail
# prefill's at the 128-wide bucket has 768 and a chunk's 6,144.
ROWS_A_ROUND = 512


def read(c):
    import re

    from benchmarks import counts_dsv2, zaya_cell

    tr = c.get("trace") or {}
    rs = [r for r in zaya_cell.moe_rounds(
        c, c["t_open"], c["t_open"] + c["trace_s"]) or []
        if "latent_rows" in r]
    calls = sum(v for k, v in tr.get("module_calls", {}).items()
                if "step_fn" in k)
    if not rs or not calls:
        return None
    rows = re.compile(r"\[(\d+),")
    t = sum(v for k, v in tr.get("op_time_s", {}).items()
            if KERNEL in k
            and (m := rows.search(k)) and int(m.group(1)) < ROWS_A_ROUND)
    if not t:
        return None
    cfg = c["model_cfg"]
    touched = sum(r["experts_touched"] for r in rs) / len(rs) * calls
    pairs = sum(r["active"] for r in rs) / len(rs) * calls * int(
        cfg["experts_per_token"]) * counts_dsv2.layers(cfg)[1]
    least = max(
        counts_dsv2.expert_bytes(cfg) * touched
        / c["peaks"]["hbm_bytes_per_s"],
        counts_dsv2.expert_product_flops(cfg, pairs)
        / c["peaks"]["bf16_flops"])
    return 100.0 * least / t
