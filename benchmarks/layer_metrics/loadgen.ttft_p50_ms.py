"""Median time to first token at the client stream handle in the closed loop: a prefill delays every other slot next token."""


def read(c):
    from benchmarks import stats

    p = stats.percentile(c["client"]["ttft_s"], 50)
    return None if p is None else 1000.0 * p
