"""(layer, expert) pairs that got a token in a decode round, over the pairs held (the program span engine.round's experts_touched over experts_total, over the window's decoding rounds): the share of the expert weights a round reads. Lower is fewer bytes."""


def read(c):
    from benchmarks import zaya_cell

    rs = [r for r in zaya_cell.moe_rounds(c) or [] if r.get("experts_total")]
    if not rs:
        return None
    return 100.0 * sum(r["experts_touched"] for r in rs) / sum(
        r["experts_total"] for r in rs)
