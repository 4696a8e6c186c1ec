"""95th percentile of how late the generator sent a request: actual send minus due time. A starved generator must not read as a fast server."""


def read(c):
    from benchmarks import stats

    p = stats.percentile(c["client"]["lateness_s"], 95)
    return None if p is None else 1000.0 * p
