"""Median duration of the program span sched.deliver: the per-token loop over the round's tokens, push_tokens to the waiting clients, TTFT bookkeeping and record_round."""


def read(c):
    from benchmarks import program_spans as ps

    return ps.duration_p50_ms(c, "sched.deliver")
