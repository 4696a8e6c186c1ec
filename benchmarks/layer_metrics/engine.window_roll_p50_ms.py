"""Median duration of the program span engine.window_roll in the window: a slot's filled window released, its summary pages moved into its page-table row and the next window's pages bound, on the host between two rounds."""


def read(c):
    from benchmarks import program_spans as ps

    return ps.duration_p50_ms(c, "engine.window_roll")
