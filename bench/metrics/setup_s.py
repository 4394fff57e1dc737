"""Seconds from process start until the window opens: sim, parameters,
compilation (or the persistent cache), the first steps and warm-up."""


def read(w):
    return w.setup_s
