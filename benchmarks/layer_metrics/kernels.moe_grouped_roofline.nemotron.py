"""The routed products of the traced window's decode rounds (the Pallas kernel ops/grouped_matmul.py: device ops named grouped_matmul whose result has one row a (slot, expert) pair, 6 a slot) against the larger of their bytes (the experts that got a pair, from the rounds' counter experts_touched, both matrices) over the HBM bandwidth and their FLOPs (the pairs routed, one expert each) over the bf16 peak. Bound by bytes at a decode batch."""

KERNEL = "grouped_matmul"
ROWS_A_CHUNK = 1024  # fewer rows than this: a decode round's product


def read(c):
    import re

    from benchmarks import counts_nemotron, zaya_cell

    tr = c.get("trace") or {}
    rs = [r for r in zaya_cell.moe_rounds(
        c, c["t_open"], c["t_open"] + c["trace_s"]) or [] if "ssm_lanes" in r]
    calls = sum(v for k, v in tr.get("module_calls", {}).items()
                if "step_fn" in k)
    if not rs or not calls:
        return None
    # A decode round's products have a row a pair (6 a slot: hundreds); a
    # prefill chunk's have 6 a chunk position (thousands) and are left out:
    # the rounds' counter does not count their experts.
    rows = re.compile(r"\[(\d+),")
    t = sum(v for k, v in tr.get("op_time_s", {}).items()
            if KERNEL in k
            and (m := rows.search(k)) and int(m.group(1)) < ROWS_A_CHUNK)
    if not t:
        return None
    cfg = c["model_cfg"]
    touched = sum(r["experts_touched"] for r in rs) / len(rs) * calls
    pairs = sum(r["active"] for r in rs) / len(rs) * calls * int(
        cfg.get("experts_per_token", 1)) * cfg["layer_pattern"].count("E")
    least = max(
        counts_nemotron.expert_bytes(cfg) * touched
        / c["peaks"]["hbm_bytes_per_s"],
        counts_nemotron.expert_product_flops(cfg, pairs)
        / c["peaks"]["bf16_flops"])
    return 100.0 * least / t
