"""Rows trained in the window over the window's seconds (host clock)."""


def read(w):
    return w.rows / w.seconds
