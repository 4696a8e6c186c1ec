"""Least time the decode rounds of the traced window need (EvaByte's weights once and 16 KiB a layer for every window row and summary row attended, over the HBM bandwidth) against the device time of the decode program's events. Bound by bytes."""

PROGRAM = "step_fn"


def read(c):
    from benchmarks import counts_evabyte, evabyte_cell

    tr = c.get("trace") or {}
    t = sum(v for k, v in tr.get("module_time_s", {}).items() if PROGRAM in k)
    calls = sum(v for k, v in tr.get("module_calls", {}).items()
                if PROGRAM in k)
    rows = evabyte_cell.eva_rows(c, c["t_open"], c["t_open"] + c["trace_s"])
    if not t or not calls or not rows:
        return None
    per_round = counts_evabyte.decode_round_bytes(
        c["model_cfg"], (rows[0] + rows[1]) / rows[3])
    return 100.0 * per_round * calls / c["peaks"]["hbm_bytes_per_s"] / t
