"""A kernel's share of its roofline in a traced run.

A configuration's ``KERNELS[scope]`` counts the FLOPs of the kernel that the
program runs under ``jax.named_scope(scope)`` and every byte of its operands
and results, wherever the compiler places them; the harness sums them over
the window's batches (``w.kernel_cost``), and the trace gives the device
seconds under that scope (``w.trace["device_by_scope"]``). The
least time the chips could take is the larger of FLOPs over the peak FLOP/s
and bytes over the peak bytes/s (``bench/peaks.json``, bf16 FLOP/s); the
share is that time over the scope's device time.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import NamedTuple, Optional

PEAKS = json.loads((Path(__file__).resolve().parent / "peaks.json")
                   .read_text())["devices"]


class Share(NamedTuple):
    percent: float
    bound: str          # "flops" or "bytes": which of the two bounds it


def share(w, scope: str) -> Optional[Share]:
    """The share, in %, of ``scope``'s kernel in the run ``w``; None where
    the configuration declares no kernel there or the trace holds no device
    time under the scope."""
    cost = (w.kernel_cost or {}).get(scope)
    seconds = ((w.trace or {}).get("device_by_scope") or {}).get(scope)
    if cost is None or not seconds:
        return None
    if w.device_kind not in PEAKS:
        raise KeyError(f"no peaks for device {w.device_kind!r} in peaks.json")
    peak = PEAKS[w.device_kind]
    flops_s = cost[0] / (w.chips * peak["bf16_flops_per_s"])
    bytes_s = cost[1] / (w.chips * peak["hbm_bytes_per_s"])
    return Share(max(flops_s, bytes_s) / seconds * 100.0,
                 "flops" if flops_s >= bytes_s else "bytes")
