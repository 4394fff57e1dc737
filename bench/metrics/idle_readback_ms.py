"""Device idle per traced step while the trainer thread is inside
``repro.train.readback``: the step's stats read back to the host."""


def read(w):
    t = w.trace
    if t is None or "idle_split_s" not in t or not t["steps"]:
        return None
    return t["idle_split_s"]["readback"] / t["steps"] * 1e3
