"""DPP worker busy time (probe + UIH lookup + featurize, summed over the
workers' threads) in the window, per row trained."""


def read(w):
    return w.worker_busy_s / w.rows * 1e6 if w.rows else None
