"""Share of the window the step loop spends fetching and placing the next batch."""


def read(c):
    s = c["spans"]
    return 100.0 * s.seconds_within(
        "next_batch", c["t_open"], c["t_close"]) / c["window_s"]
