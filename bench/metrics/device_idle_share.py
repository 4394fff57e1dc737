"""Share of the traced window in which no operation ran on the device."""


def read(w):
    t = w.trace
    if t is None:
        return None
    return (1.0 - t["busy_s"] / t["window_s"]) * 100.0
