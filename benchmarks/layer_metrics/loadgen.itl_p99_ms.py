"""99th percentile of every gap between successive tokens at the client stream handle. In the closed loop a prefill delays one gap of every other slot; those gaps are under 5% of all, so itl_p95_ms cannot see them and this does."""


def read(c):
    from benchmarks import stats

    p = stats.percentile(c["client"]["itl_s"], 99)
    return None if p is None else 1000.0 * p
