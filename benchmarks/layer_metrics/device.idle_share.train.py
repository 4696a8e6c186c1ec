"""One minus the union of device-operation intervals over the traced window, training."""


def read(c):
    tr = c.get("trace") or {}
    if not tr.get("window_s"):
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
