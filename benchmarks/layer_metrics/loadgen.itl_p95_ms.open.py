"""95th percentile of every gap between successive tokens at the client stream handle, in the open loop. Not end to end there: 4.0-4.7% of the gaps hold two prefill chunks (230 ms) and 19% one (140 ms), so the 95th percentile sits on the edge between the two and reads 141 or 148 or 229 as a handful of gaps fall; loadgen.itl_p90_ms.open and loadgen.two_chunk_gap_share read each side of that edge."""


def read(c):
    from benchmarks import stats

    p = stats.percentile(c["client"]["itl_s"], 95)
    return None if p is None else 1000.0 * p
