"""Share of the window the trainer waited on the feed for its next batch
(ClientStats.starved_time_s over the window)."""


def read(w):
    return w.starved_s / w.seconds * 100.0
