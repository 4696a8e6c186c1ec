"""K and V bytes of the rows (window rows and summaries) that the traced window's decode rounds attended, 16 KiB a row a layer, over the HBM bandwidth, against the summed device time of the paged_decode_attention custom calls (ops/attention.py). Bound by bytes."""

KERNEL = "paged_decode_attention"


def read(c):
    from benchmarks import counts_evabyte, evabyte_cell

    tr = c.get("trace") or {}
    t = sum(v for k, v in tr.get("op_time_s", {}).items() if KERNEL in k)
    calls = sum(v for k, v in tr.get("module_calls", {}).items()
                if "step_fn" in k)
    rows = evabyte_cell.eva_rows(c, c["t_open"], c["t_open"] + c["trace_s"])
    if not t or not calls or not rows:
        return None
    least = (counts_evabyte.row_bytes(c["model_cfg"])
             * (rows[0] + rows[1]) / rows[3] * calls)
    return 100.0 * least / c["peaks"]["hbm_bytes_per_s"] / t
