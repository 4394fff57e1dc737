"""Device idle per traced step while the trainer thread is inside
``repro.feed.get``: waiting on the feed for the next batch."""


def read(w):
    t = w.trace
    if t is None or "idle_split_s" not in t or not t["steps"]:
        return None
    return t["idle_split_s"]["feed"] / t["steps"] * 1e3
