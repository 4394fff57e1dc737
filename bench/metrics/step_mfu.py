"""Useful model FLOPs of the rows trained in the window (each
configuration's ``flops_per_row``: valid positions only, no recomputation)
over window x chips x the chip's bf16 peak from ``bench/peaks.json``."""
import json
from pathlib import Path

PEAKS = json.loads((Path(__file__).resolve().parents[1] / "peaks.json")
                   .read_text())["devices"]


def read(w):
    if w.device_kind not in PEAKS:
        raise KeyError(f"no peaks for device {w.device_kind!r} in peaks.json")
    peak = PEAKS[w.device_kind]["bf16_flops_per_s"]
    return w.flops / (w.seconds * w.chips * peak) * 100.0
