"""Least time the decode rounds of the traced window need (weights once and the K and V of every live position, over the HBM bandwidth) against the device time of the decode program events. Bound by bytes."""

PROGRAM = "step_fn"


def read(c):
    from benchmarks import counts

    tr = c.get("trace") or {}
    t = sum(v for k, v in tr.get("module_time_s", {}).items() if PROGRAM in k)
    calls = sum(v for k, v in tr.get("module_calls", {}).items()
                if PROGRAM in k)
    lo, hi = c["t_open"], c["t_open"] + c["trace_s"]
    rounds = [r for r in c["counters"]["decode_rounds"] if lo <= r[0] < hi]
    if not t or not calls or not rounds:
        return None
    per_round = sum(counts.decode_round_bytes(c["model_cfg"], r[3])
                    for r in rounds) / len(rounds)
    least = per_round * calls / c["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / t
