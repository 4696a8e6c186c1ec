"""Share of the window's decoding rounds (the program span engine.round with active > 0) whose program was queued before the round before it was read (its attribute ahead: SlotEngine.step dispatched it from the device's registers). Nothing on a program whose spans lack the attribute."""


def read(c):
    from benchmarks import program_spans as ps

    rs = [r for r in ps.rounds(c) or [] if r.get("active", 0) > 0]
    if not rs or any("ahead" not in r for r in rs):
        return None
    return 100.0 * sum(bool(r["ahead"]) for r in rs) / len(rs)
