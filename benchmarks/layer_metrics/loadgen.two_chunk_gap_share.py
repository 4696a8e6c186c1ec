"""Share of the gaps between successive tokens that are longer than four times the median gap: the gaps that two or more prefill chunks (or admissions) delayed. At 5% they reach the 95th percentile of the gaps."""


def read(c):
    from benchmarks import stats

    gaps = c["client"]["itl_s"]
    med = stats.percentile(gaps, 50)
    if not med:
        return None
    return 100.0 * sum(1 for g in gaps if g > 4.0 * med) / len(gaps)
