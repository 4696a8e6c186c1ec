"""95th percentile of the program's own sched.queue_wait intervals (Scheduler.submit to engine.start entered) that ended in the window; sched.queue_wait_p50_ms times the same wait from outside, by a benchmark wrapper."""


def read(c):
    from benchmarks import program_spans as ps

    recs = ps.in_window(c, "sched.queue_wait")
    if recs is None:
        return None
    return ps.p_ms([r[1] - r[0] for r in recs
                    if c["t_open"] <= r[1] <= c["t_close"]], 95)
