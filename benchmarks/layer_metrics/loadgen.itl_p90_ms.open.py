"""90th percentile of every gap between successive tokens at the client stream handle, in the open loop: inside the population of gaps that one prefill chunk delays (a chunk plus a decode round), which spans the 76th to the 95th percentile there."""


def read(c):
    from benchmarks import stats

    p = stats.percentile(c["client"]["itl_s"], 90)
    return None if p is None else 1000.0 * p
