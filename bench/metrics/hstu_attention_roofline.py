"""HSTU attention's share of its roofline: the least time its useful FLOPs
or its bytes (``KERNELS["hstu_attention"]`` of the configuration) need at
the chip's peaks, over the scope's device time; ``bench/roofline.py``."""
from bench import roofline


def read(w):
    s = roofline.share(w, "hstu_attention")
    return None if s is None else s.percent
