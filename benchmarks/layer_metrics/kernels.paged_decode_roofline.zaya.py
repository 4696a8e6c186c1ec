"""K and V bytes of the rows that the traced window's decode rounds attended (the rounds' live_tokens and the row each active slot writes, 1 KiB a row a layer at 2 kv heads of 128 in bfloat16), over the HBM bandwidth, against the summed device time of the paged_decode_attention custom calls (ops/attention.py), here at 4 query rows a kv head. Bound by bytes."""

KERNEL = "paged_decode_attention"


def read(c):
    from benchmarks import counts_zaya, zaya_cell

    tr = c.get("trace") or {}
    t = sum(v for k, v in tr.get("op_time_s", {}).items() if KERNEL in k)
    calls = sum(v for k, v in tr.get("module_calls", {}).items()
                if "step_fn" in k)
    rs = zaya_cell.moe_rounds(c, c["t_open"], c["t_open"] + c["trace_s"])
    if not t or not calls or not rs:
        return None
    rows = sum(r["live_tokens"] + r["active"] for r in rs) / len(rs)
    least = counts_zaya.row_bytes(c["model_cfg"]) * rows * calls
    return 100.0 * least / c["peaks"]["hbm_bytes_per_s"] / t
