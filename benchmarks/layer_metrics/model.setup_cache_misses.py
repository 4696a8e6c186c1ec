"""XLA compiles before the window opened that the persistent compile cache did not hold: program spans xla.compile whose attribute cache is "miss". 0 on a run after a warm one, unless a program's cache key is unstable."""


def read(c):
    from benchmarks import runtime_spans as rs

    recs = rs.before_open(c, ("xla.compile",))
    return None if recs is None else sum(
        1 for r in recs if (r[2] or {}).get("cache") == "miss")
