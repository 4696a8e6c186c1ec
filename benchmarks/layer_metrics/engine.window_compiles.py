"""XLA compiles that overlap the window (program spans xla.compile): the engine's zero-recompile contract, read where it would break."""


def read(c):
    from benchmarks import runtime_spans as rs

    recs = rs.in_window(c, ("xla.compile",))
    return None if recs is None else len(recs)
