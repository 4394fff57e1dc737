"""Device idle per traced step while the trainer thread is inside
``repro.train.dispatch`` (``repro.train.inputs`` inside it): staging the
batch and dispatching the jitted step."""


def read(w):
    t = w.trace
    if t is None or "idle_split_s" not in t or not t["steps"]:
        return None
    return t["idle_split_s"]["dispatch"] / t["steps"] * 1e3
