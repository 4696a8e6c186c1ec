"""Least time the decode rounds of the traced window need (the Nemotron-H stage's Mamba, attention, router, shared-expert and head weights once, the experts that got a pair from the rounds' counter experts_touched, the recurrent and convolution state of every live lane in and out from ssm_lanes, and 1 KiB for every row the one attention layer attended, over the HBM bandwidth) against the device time of the decode program's events. Bound by bytes."""

PROGRAM = "step_fn"


def read(c):
    from benchmarks import counts_nemotron, zaya_cell

    tr = c.get("trace") or {}
    t = sum(v for k, v in tr.get("module_time_s", {}).items() if PROGRAM in k)
    calls = sum(v for k, v in tr.get("module_calls", {}).items()
                if PROGRAM in k)
    rs = [r for r in zaya_cell.moe_rounds(
        c, c["t_open"], c["t_open"] + c["trace_s"]) or [] if "ssm_lanes" in r]
    if not t or not calls or not rs:
        return None
    mean = lambda f: sum(f(r) for r in rs) / len(rs)
    per_round = counts_nemotron.decode_round_bytes(
        c["model_cfg"], mean(lambda r: r["live_tokens"] + r["active"]),
        mean(lambda r: r["experts_touched"]), mean(lambda r: r["ssm_lanes"]))
    return 100.0 * per_round * calls / c["peaks"]["hbm_bytes_per_s"] / t
