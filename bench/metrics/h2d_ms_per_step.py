"""Host-to-device transfer time of the prefetch stage per step
(ClientStats.h2d_time_s over the window); it overlaps the step."""


def read(w):
    return w.h2d_s / w.steps * 1e3 if w.steps else None
