"""Share of the window in which the serving process was in a garbage collection (program spans py.gc.0, py.gc.1 and py.gc.2, their union): a pause of every Python thread, the scheduler's among them."""


def read(c):
    from benchmarks import runtime_spans as rs

    recs = rs.in_window(c, rs.GC)
    return None if recs is None else 100.0 * rs.union_s(
        recs, c["t_open"], c["t_close"]) / c["window_s"]
