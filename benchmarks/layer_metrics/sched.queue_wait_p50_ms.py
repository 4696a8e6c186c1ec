"""Median wait in the scheduler queue: from Scheduler.submit to the benchmark span entering SlotEngine.start for that request. (ServingMetrics has no queue-wait histogram; the span stands in.)"""


def read(c):
    from benchmarks import stats

    p = stats.percentile(c["client"]["queue_wait_s"], 50)
    return None if p is None else 1000.0 * p
