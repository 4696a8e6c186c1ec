"""The latent rows among the bytes a decode round is reckoned to move (the program span engine.round's latent_rows at 1,152 B a position a layer, over counts_dsv2.decode_round_bytes of the same rounds, over the window's decoding rounds): how much of a round the mechanism's cache is, beside the weights. Higher means the latent kernel does more of the round's work."""


def read(c):
    from benchmarks import counts_dsv2, zaya_cell

    rs = [r for r in zaya_cell.moe_rounds(c) or [] if r.get("latent_rows")]
    if not rs:
        return None
    cfg = c["model_cfg"]
    n = int(cfg["num_layers"])
    rows = sum(counts_dsv2.latent_row_bytes(cfg) * r["latent_rows"]
               for r in rs)
    whole = sum(counts_dsv2.decode_round_bytes(
        cfg, r["latent_rows"] / n, r["experts_touched"], r["active"])
        for r in rs)
    return 100.0 * rows / whole
