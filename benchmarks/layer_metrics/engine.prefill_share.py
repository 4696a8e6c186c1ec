"""Share of the window the driver thread spends inside SlotEngine.start and the prefill chunks of a round."""


def read(c):
    s = c["spans"]
    busy = (s.seconds_within("engine.start", c["t_open"], c["t_close"])
            + s.seconds_within("engine.prefill", c["t_open"], c["t_close"]))
    return 100.0 * busy / c["window_s"]
