"""Median time in which the device has nothing queued, on the host clock: the end of the program span engine.wait of one round to the end of engine.dispatch of the next, over pairs of rounds that ran no prefill chunk and left slots decoding. It is made of engine.readback, the scheduler between rounds and engine.dispatch."""


def read(c):
    from benchmarks import program_spans as ps

    rs = ps.rounds(c)
    return None if rs is None else ps.p_ms(ps.host_gaps_s(rs), 50)
