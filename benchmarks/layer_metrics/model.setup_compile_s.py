"""Seconds before the window opened in which the process ran an XLA compile or loaded one from the persistent compile cache: the union of the program spans xla.compile."""


def read(c):
    from benchmarks import runtime_spans as rs

    return rs.setup_union_s(c, ("xla.compile",))
