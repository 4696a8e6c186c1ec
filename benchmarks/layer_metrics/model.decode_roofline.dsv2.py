"""Least time the decode rounds of the traced window need (the DeepSeek-V2-Lite stage's attention, dense-MLP, router, shared-expert and head weights once, the experts that got a pair from the rounds' counter experts_touched, 1,152 B for every position each layer's latent kernel read from latent_rows, and the live slots' absorbed queries) over the HBM bandwidth, against the device time of the decode program's events. Bound by bytes."""

PROGRAM = "step_fn"


def read(c):
    from benchmarks import counts_dsv2, zaya_cell

    tr = c.get("trace") or {}
    t = sum(v for k, v in tr.get("module_time_s", {}).items() if PROGRAM in k)
    calls = sum(v for k, v in tr.get("module_calls", {}).items()
                if PROGRAM in k)
    rs = [r for r in zaya_cell.moe_rounds(
        c, c["t_open"], c["t_open"] + c["trace_s"]) or []
        if "latent_rows" in r]
    if not t or not calls or not rs:
        return None
    cfg = c["model_cfg"]
    mean = lambda f: sum(f(r) for r in rs) / len(rs)
    per_round = counts_dsv2.decode_round_bytes(
        cfg, mean(lambda r: r["latent_rows"]) / int(cfg["num_layers"]),
        mean(lambda r: r["experts_touched"]), mean(lambda r: r["active"]))
    return 100.0 * per_round * calls / c["peaks"]["hbm_bytes_per_s"] / t
