"""Device-busy time per step in the traced window: the union of the
device's op intervals, averaged over the chips, over the steps traced."""


def read(w):
    t = w.trace
    if t is None or not t["steps"]:
        return None
    return t["busy_s"] / t["steps"] * 1e3
