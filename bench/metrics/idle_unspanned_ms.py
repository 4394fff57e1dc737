"""Device idle per traced step while the trainer thread is inside none of
``repro.train.dispatch``, ``repro.train.readback`` and ``repro.feed.get``
(in ``repro.train.step`` alone, or in no program span)."""


def read(w):
    t = w.trace
    if t is None or "idle_split_s" not in t or not t["steps"]:
        return None
    return t["idle_split_s"]["unspanned"] / t["steps"] * 1e3
