"""Least time the decode rounds of the traced window need (ZAYA1's attention, router and head weights once, the experts that got a token from the rounds' counter experts_touched, and 1 KiB a layer for every row attended, over the HBM bandwidth) against the device time of the decode program's events. Bound by bytes."""

PROGRAM = "step_fn"


def read(c):
    from benchmarks import counts_zaya, zaya_cell

    tr = c.get("trace") or {}
    t = sum(v for k, v in tr.get("module_time_s", {}).items() if PROGRAM in k)
    calls = sum(v for k, v in tr.get("module_calls", {}).items()
                if PROGRAM in k)
    rs = zaya_cell.moe_rounds(c, c["t_open"], c["t_open"] + c["trace_s"])
    if not t or not calls or not rs:
        return None
    per_round = counts_zaya.decode_round_bytes(
        c["model_cfg"],
        sum(r["live_tokens"] + r["active"] for r in rs) / len(rs),
        sum(r["experts_touched"] for r in rs) / len(rs))
    return 100.0 * per_round * calls / c["peaks"]["hbm_bytes_per_s"] / t
