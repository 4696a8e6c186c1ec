"""Median duration of the program span sched.metrics_sync (record_occupancy and ServingMetrics.sync_engine): what the serving instrumentation costs each round."""


def read(c):
    from benchmarks import program_spans as ps

    return ps.duration_p50_ms(c, "sched.metrics_sync")
