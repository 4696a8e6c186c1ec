"""Share of the gaps between successive tokens that are longer than twice the median gap: the gaps a prefill (or anything else) stalled. At 5% the stalled gaps reach itl_p95_ms."""


def read(c):
    from benchmarks import stats

    gaps = c["client"]["itl_s"]
    med = stats.percentile(gaps, 50)
    if not med:
        return None
    return 100.0 * sum(1 for g in gaps if g > 2.0 * med) / len(gaps)
