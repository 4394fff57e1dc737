"""95th percentile of the window's step intervals, each from the end of one
step (its loss read back) to the end of the next, feed waits included."""
import numpy as np


def read(w):
    return float(np.percentile(w.intervals, 95)) * 1e3
