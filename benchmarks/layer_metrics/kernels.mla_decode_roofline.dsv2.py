"""The latent decode kernel of the traced window's decode rounds (ops/attention.py's paged kernel over one leaf of latent rows, device ops named mla_decode_attention) against the larger of its bytes (the rounds' counter latent_rows, positions read over slots and layers, at 1,152 B a position a layer, plus each live slot's absorbed queries in and out) over the HBM bandwidth and its FLOPs (34,816 a position a layer: 16 heads' scores against the 576-wide row and their value product over the 512-wide latent) over the bf16 peak. The pool's rows are padded to 640 lanes: the padding is not counted and shows as lost share."""

KERNEL = "mla_decode_attention"


def read(c):
    from benchmarks import counts_dsv2, zaya_cell

    tr = c.get("trace") or {}
    t = sum(v for k, v in tr.get("op_time_s", {}).items() if KERNEL in k)
    calls = sum(v for k, v in tr.get("module_calls", {}).items()
                if "step_fn" in k)
    rs = [r for r in zaya_cell.moe_rounds(
        c, c["t_open"], c["t_open"] + c["trace_s"]) or []
        if "latent_rows" in r]
    if not t or not calls or not rs:
        return None
    cfg = c["model_cfg"]
    rows = sum(r["latent_rows"] for r in rs) / len(rs) * calls
    lanes = sum(r["active"] for r in rs) / len(rs) * calls * int(
        cfg["num_layers"])
    least = max(
        (counts_dsv2.latent_row_bytes(cfg) * rows
         + counts_dsv2.latent_query_bytes(cfg) * lanes)
        / c["peaks"]["hbm_bytes_per_s"],
        counts_dsv2.latent_pair_flops(cfg) * rows / c["peaks"]["bf16_flops"])
    return 100.0 * least / t
