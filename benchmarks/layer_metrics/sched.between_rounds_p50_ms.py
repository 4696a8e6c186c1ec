"""Median host time between engine rounds: the end of one program span engine.round to the start of the next, over rounds that left slots decoding (obs/trace.py rings; the program's serve_between_rounds_seconds histogram observes the same stretch)."""


def read(c):
    from benchmarks import program_spans as ps

    rs = ps.rounds(c)
    return None if rs is None else ps.p_ms(ps.between_rounds_s(rs), 50)
