"""K and V bytes of the rows that the traced window's decode rounds attended (each round's live positions and the row each active slot writes, from the registers the round was sent with, as model.decode_roofline counts them; 1 KiB a row a layer at starcoder2-3b's 2 kv heads of 128 in bfloat16: benchmarks/counts.py), over the HBM bandwidth, against the summed device time of the paged_decode_attention custom calls (ops/attention.py), here at 12 query rows a kv head. Bound by bytes."""

KERNEL = "paged_decode_attention"


def read(c):
    from benchmarks import counts

    tr = c.get("trace") or {}
    t = sum(v for k, v in tr.get("op_time_s", {}).items() if KERNEL in k)
    calls = sum(v for k, v in tr.get("module_calls", {}).items()
                if "step_fn" in k)
    lo, hi = c["t_open"], c["t_open"] + c["trace_s"]
    rounds = [r for r in c["counters"]["decode_rounds"] if lo <= r[0] < hi]
    if not t or not calls or not rounds:
        return None
    rows = sum(r[2] + r[3] for r in rounds) / len(rounds)
    least = counts.kv_bytes_per_token(c["model_cfg"]) * rows * calls
    return 100.0 * least / c["peaks"]["hbm_bytes_per_s"] / t
