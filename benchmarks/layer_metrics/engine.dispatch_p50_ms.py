"""Median duration of the program span engine.dispatch: entry of the decode round to the return of the jitted call, the host registers and the page table handed over."""


def read(c):
    from benchmarks import program_spans as ps

    return ps.duration_p50_ms(c, "engine.dispatch")
