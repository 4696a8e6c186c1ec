"""Seconds in the program span engine.warmup (SlotEngine.warmup: every program of the set compiled or loaded from the cache) of the stack the window ran on: the largest single piece of a serving cell's setup_s."""


def read(c):
    from benchmarks import program_spans as ps

    recs = ps.records("engine.warmup", float("-inf"), c["t_open"])
    return recs[-1][1] - recs[-1][0] if recs else None
