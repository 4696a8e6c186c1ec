"""Median host-clock duration of one SlotEngine.step, by the benchmark span around it, in the open loop: a round runs between the prefill chunks of a waiting request, so it moves the time to the first token there."""


def read(c):
    from benchmarks import stats

    rounds = c["spans"].within("engine.step", c["t_open"], c["t_close"])
    p = stats.percentile([r[1] - r[0] for r in rounds], 50)
    return None if p is None else 1000.0 * p
