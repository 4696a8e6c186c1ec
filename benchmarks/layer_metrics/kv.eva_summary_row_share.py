"""Summary rows among the rows the window's decode rounds attended (the program span engine.round's summary_rows_read over summary_rows_read + window_rows_read): how far the traffic reached EVA's second kind of page."""


def read(c):
    from benchmarks import evabyte_cell

    rows = evabyte_cell.eva_rows(c)
    if not rows or not rows[0] + rows[1]:
        return None
    return 100.0 * rows[0] / (rows[0] + rows[1])
