"""From a profiler trace to the device numbers of one measured window.

``from_xplane`` keeps what the reductions need from the ``.xplane.pb`` that
``jax.profiler`` writes: each TPU device plane's op line, the host events
the benchmark annotates (``bench.*``) and those the program records
(``repro.*``, read by ``bench/spans.py``), and, outside ``planes``, each
device's XLA module intervals. ``summarize`` reduces that to busy time, top
device ops, collective time and the longest idle gaps, each gap named by the
benchmark annotation that overlaps it most; it reads the ``bench.*``
annotations only. Times are in nanoseconds on the profiler's clock.
"""
from __future__ import annotations

import re
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
WINDOW = "bench.window"
ANNOTATION = "bench."
PROGRAM = "repro."
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute"
    r"|allreduce|allgather|reducescatter|alltoall|psum", re.I)


def _events(line) -> List[list]:
    return [[e.name, int(e.start_ns), int(e.duration_ns)]
            for e in line.events]


def from_xplane(path: str) -> dict:
    """``{"planes": [{"name", "lines": [{"name", "events": [[name, start,
    duration], ...]}]}], "modules": {device plane: [[module, start,
    duration], ...]}}`` with the device op lines and the host ``bench.*``
    and ``repro.*`` events only. A host line is one thread; every Python
    thread's line is named ``python``, so a thread is its line's place in
    its plane, never the line's name."""
    from jax.profiler import ProfileData

    planes, modules = [], {}
    for plane in ProfileData.from_file(path).planes:
        device = DEVICE_PLANE.match(plane.name)
        lines = []
        for line in plane.lines:
            if device and line.name == MODULE_LINE:
                modules[plane.name] = _events(line)
                continue
            evs = (_events(line) if line.name == OP_LINE else []) \
                if device else [ev for ev in _events(line)
                                if ev[0].startswith((ANNOTATION, PROGRAM))]
            if evs:
                lines.append({"name": line.name, "events": evs})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes, "modules": modules}


def short_name(name: str) -> str:
    """``%fusion.5 = bf16[8,128]{1,0:T(8,128)} fusion(...)`` -> ``fusion.5
    bf16[8,128]``: the op and its result type, without layouts."""
    head, _, rest = name.partition(" = ")
    if not rest:
        return name[:120]
    ty = "(tuple)" if rest.startswith("(") else re.sub(
        r"\{[^}]*\}", "", rest.split(" ")[0])
    return f"{head.lstrip('%')} {ty}"[:120]


def crop(trace: dict, steps: int) -> dict:
    """The trace up to the end of the window's first ``steps`` steps, its
    ``modules`` and ``scopes`` with it (a small recorded trace for tests)."""
    host = [ev for p in trace["planes"] if not DEVICE_PLANE.match(p["name"])
            for ln in p["lines"] for ev in ln["events"]]
    w0 = max((ev for ev in host if ev[0] == WINDOW), key=lambda e: e[2])[1]
    ends = sorted(ev[1] + ev[2] for ev in host
                  if ev[0] == "bench.train_step" and ev[1] >= w0)
    t1 = ends[min(steps, len(ends)) - 1]
    out = []
    for p in trace["planes"]:
        lines = []
        for ln in p["lines"]:
            evs = [[short_name(n) if DEVICE_PLANE.match(p["name"]) else n,
                    s, d] for n, s, d in ln["events"] if s < t1]
            if p["name"] and not DEVICE_PLANE.match(p["name"]):
                evs = [[n, s, min(d, t1 - s) if n == WINDOW else d]
                       for n, s, d in evs]
            if evs:
                lines.append({"name": ln["name"], "events": evs})
        out.append({"name": p["name"], "lines": lines})
    return {"planes": out,
            "modules": {k: [ev for ev in v if ev[1] < t1]
                        for k, v in trace.get("modules", {}).items()},
            "scopes": trace.get("scopes", {})}


def _union(iv: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(s: int, e: int, w0: int, w1: int) -> Optional[Tuple[int, int]]:
    s, e = max(s, w0), min(e, w1)
    return (s, e) if e > s else None


def summarize(trace: dict, top: int = 10) -> Optional[dict]:
    """Device numbers inside the ``bench.window`` annotation, or None where
    the trace holds no window or no device op inside it."""
    host = [ev for p in trace["planes"] if not DEVICE_PLANE.match(p["name"])
            for ln in p["lines"] for ev in ln["events"]]
    windows = [ev for ev in host if ev[0] == WINDOW]
    if not windows:
        return None
    _, w0, wd = max(windows, key=lambda ev: ev[2])
    w1 = w0 + wd
    spans = [(ev[0], s, e) for ev in host
             if ev[0].startswith(ANNOTATION) and ev[0] != WINDOW
             for s, e in [(ev[1], ev[1] + ev[2])] if _clip(s, e, w0, w1)]
    steps = sum(1 for name, s, e in spans
                if name == "bench.train_step" and w0 <= e <= w1)

    chips = [p for p in trace["planes"] if DEVICE_PLANE.match(p["name"])]
    busy, coll, gaps = [], [], []
    by_op: Dict[str, float] = {}
    for p in chips:
        iv = []
        coll_ns = 0
        for ln in p["lines"]:
            for name, s, d in ln["events"]:
                c = _clip(s, s + d, w0, w1)
                if c is None:
                    continue
                iv.append(c)
                op = short_name(name)
                by_op[op] = by_op.get(op, 0.0) + (c[1] - c[0])
                if COLLECTIVE.search(name):
                    coll_ns += c[1] - c[0]
        u = _union(iv)
        busy.append(sum(e - s for s, e in u))
        coll.append(coll_ns)
        if p is chips[0]:
            edges = [w0] + [x for se in u for x in se] + [w1]
            gaps = [(edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i]]
    if not chips or not any(busy):
        return None
    n = len(chips)

    def what(s: int, e: int) -> str:
        best, name = 0, "no annotation"
        for nm, hs, he in spans:
            ov = min(e, he) - max(s, hs)
            if ov > best:
                best, name = ov, nm
        return name

    gaps.sort(key=lambda g: g[1] - g[0], reverse=True)
    ops = sorted(by_op.items(), key=lambda kv: kv[1], reverse=True)[:top]
    return {
        "window_s": wd / 1e9,
        "busy_s": sum(busy) / n / 1e9,
        "collective_s": sum(coll) / n / 1e9,
        "chips": n,
        "steps": steps,
        "device_ops": [[name, ns / n / 1e9] for name, ns in ops],
        "idle_gaps": [[what(s, e), (e - s) / 1e9] for s, e in gaps[:top]],
    }
