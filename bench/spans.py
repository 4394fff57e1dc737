"""The program's spans and model scopes in a profiler trace: what the trainer
thread was doing in each idle gap of the device, how long each program span
ran, and which part of the model each device op belongs to.

``bench.trace.from_xplane`` keeps the program's ``repro.*`` host events and
each device's XLA module intervals (``modules``). ``op_scopes`` maps each
instruction of a compiled program to the model scope in its
``metadata={op_name=...}`` (the TPU's op events carry no op name); the trace
carries that map under ``scopes``. ``summarize`` adds its keys to
``bench.trace.summarize``'s; times are in nanoseconds on the profiler's
clock.
"""
from __future__ import annotations

import bisect
import re
from typing import Dict, Iterable, List, Optional, Tuple

from bench import trace

# the model scopes (jax.named_scope) of the program's recsys models; a
# configuration module adds its own as ``SCOPES``
DEFAULT_SCOPES = ("embed", "encoder", "logits", "optimizer")
# the trainer thread's spans that split the idle time, outermost first
SPLIT = (("repro.train.dispatch", "dispatch"),
         ("repro.train.readback", "readback"),
         ("repro.feed.get", "feed"))
NO_SPAN = "none"
UNSCOPED = "unscoped"

_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?op_name="([^"]*)"')
_PART = re.compile(r"^(?:[\w.\-]+\()*([\w.\-]+)\)*$")


def scope_of(op_name: str, scopes: Iterable[str]) -> str:
    """The innermost of ``scopes`` named in an op name, a scope wrapped by
    a transformation (``transpose(jvp(logits))``) counted as that scope; ""
    where there is none."""
    found = ""
    for part in op_name.split("/"):
        m = _PART.match(part)
        if m and m.group(1) in scopes:
            found = m.group(1)
    return found


def op_scopes(hlo_text: str, scopes: Iterable[str]
              ) -> Dict[str, Dict[str, str]]:
    """``{module: {instruction: scope}}`` from compiled HLO text, for the
    instructions under one of ``scopes``."""
    scopes = frozenset(scopes)
    out: Dict[str, Dict[str, str]] = {}
    ops: Dict[str, str] = {}
    for line in hlo_text.splitlines():
        if line.startswith("HloModule "):
            ops = out.setdefault(line.split()[1].rstrip(","), {})
            continue
        m = _INSTR.match(line)
        if m:
            sc = scope_of(m.group(2), scopes)
            if sc:
                ops[m.group(1)] = sc
    return out


def _layers(evs: List[list]) -> List[Tuple[int, int, Tuple[str, ...]]]:
    """Properly nested events of one line -> ``(start, end, path)``
    segments, ``path`` the names from outermost to innermost; a child is
    cut at its parent's end."""
    out = []
    stack: List[Tuple[str, int]] = []
    t = 0

    def pop_to(x: int) -> None:
        nonlocal t
        while stack and stack[-1][1] <= x:
            end = stack[-1][1]
            if end > t:
                out.append((t, end, tuple(n for n, _ in stack)))
            stack.pop()
            t = max(t, end)

    for name, s, d in sorted(evs, key=lambda ev: (ev[1], -ev[2])):
        pop_to(s)
        if stack and s > t:
            out.append((t, s, tuple(n for n, _ in stack)))
        e = min(s + d, stack[-1][1]) if stack else s + d
        stack.append((name, e))
        t = s
    pop_to(float("inf"))
    return out


def _overlap(iv: List[Tuple[int, int]], gaps: List[Tuple[int, int]]) -> int:
    """Length of the intersection of two sorted lists of disjoint
    intervals."""
    i = j = total = 0
    while i < len(iv) and j < len(gaps):
        s, e = max(iv[i][0], gaps[j][0]), min(iv[i][1], gaps[j][1])
        if e > s:
            total += e - s
        if iv[i][1] < gaps[j][1]:
            i += 1
        else:
            j += 1
    return total


def _split(path: Tuple[str, ...]) -> str:
    for name, key in SPLIT:
        if name in path:
            return key
    return "unspanned"


def summarize(t: dict, top: int = 10) -> Optional[dict]:
    """``bench.trace.summarize``'s readings, and within the
    ``bench.window`` annotation: ``idle_split_s``, the
    device's idle seconds by what the trainer thread (the line holding the
    window) was inside: ``dispatch``, ``readback``, ``feed`` (``feed.get``)
    or ``unspanned`` (none of the three), summing to the idle time;
    ``idle_by_span``, idle seconds by its innermost ``repro.*`` span
    (``none`` outside any); ``idle_elsewhere``, idle seconds during which
    another thread was inside each ``repro.*`` span; ``device_by_scope``,
    device-busy seconds by model scope, each instant counted once by its
    innermost op (``unscoped`` where the op has no scope or the trace no
    ``scopes``); ``span_s``, each ``repro.*`` span's seconds, unioned on
    each thread and summed over the threads; ``idle_gaps`` (in place of
    ``bench.trace.summarize``'s), the longest gaps named ``<bench
    annotation> > <innermost trainer span>``. Per-chip seconds are averaged
    over the chips. None where ``bench.trace.summarize`` reads nothing."""
    base = trace.summarize(t, top)
    if base is None:
        return None
    host = [(pi, li, ln) for pi, p in enumerate(t["planes"])
            if not trace.DEVICE_PLANE.match(p["name"])
            for li, ln in enumerate(p["lines"])]
    _, w0, wd = max((ev for _, _, ln in host for ev in ln["events"]
                     if ev[0] == trace.WINDOW), key=lambda ev: ev[2])
    w1 = w0 + wd
    trainer = next((pi, li) for pi, li, ln in host
                   if any(ev[0] == trace.WINDOW and ev[1] == w0
                          for ev in ln["events"]))

    def program(ln) -> List[list]:
        return [ev for ev in ln["events"] if ev[0].startswith(trace.PROGRAM)]

    own = [(s, e, path) for s, e, path in _layers(next(
        program(ln) for pi, li, ln in host if (pi, li) == trainer))
           if trace._clip(s, e, w0, w1)]
    others: Dict[str, List[Tuple[int, int]]] = {}
    span_ns: Dict[str, int] = {}
    for pi, li, ln in host:
        inside: Dict[str, List[Tuple[int, int]]] = {}
        for name, s, d in program(ln):
            if (pi, li) != trainer:
                others.setdefault(name, []).append((s, s + d))
            c = trace._clip(s, s + d, w0, w1)
            if c:
                inside.setdefault(name, []).append(c)
        for name, iv in inside.items():
            span_ns[name] = span_ns.get(name, 0) + sum(
                e - s for s, e in trace._union(iv))
    others = {k: trace._union(v) for k, v in others.items()}
    annotated = [(ev[0], ev[1], ev[1] + ev[2])
                 for _, _, ln in host for ev in ln["events"]
                 if ev[0].startswith(trace.ANNOTATION)
                 and ev[0] != trace.WINDOW]

    chips = [p for p in t["planes"] if trace.DEVICE_PLANE.match(p["name"])]
    split = dict.fromkeys(("dispatch", "readback", "feed", "unspanned"), 0)
    by_span: Dict[str, int] = {}
    elsewhere: Dict[str, int] = {}
    by_scope: Dict[str, int] = {}
    named = []
    for p in chips:
        ops = [ev for ln in p["lines"] for ev in ln["events"]
               if trace._clip(ev[1], ev[1] + ev[2], w0, w1)]
        ops = [[n, max(s, w0), min(s + d, w1) - max(s, w0)]
               for n, s, d in ops]
        _scope_time(ops, t.get("modules", {}).get(p["name"], []),
                    t.get("scopes", {}), by_scope)
        busy = trace._union([(s, s + d) for _, s, d in ops])
        edges = [w0] + [x for se in busy for x in se] + [w1]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        for name, iv in others.items():
            elsewhere[name] = elsewhere.get(name, 0) + _overlap(iv, gaps)
        k = 0
        for s, e in gaps:
            covered = 0
            while k < len(own) and own[k][1] <= s:
                k += 1
            j = k
            inner: Dict[str, int] = {}
            while j < len(own) and own[j][0] < e:
                ov = min(e, own[j][1]) - max(s, own[j][0])
                if ov > 0:
                    covered += ov
                    path = own[j][2]
                    split[_split(path)] += ov
                    by_span[path[-1]] = by_span.get(path[-1], 0) + ov
                    inner[path[-1]] = inner.get(path[-1], 0) + ov
                j += 1
            if e - s > covered:
                split["unspanned"] += e - s - covered
                by_span[NO_SPAN] = by_span.get(NO_SPAN, 0) + e - s - covered
                inner[NO_SPAN] = inner.get(NO_SPAN, 0) + e - s - covered
            if p is chips[0]:
                named.append((e - s, s, e, max(inner, key=inner.get)))
    n = len(chips)

    def sec(d: Dict[str, int], n: int = n) -> Dict[str, float]:
        return {k: v / n / 1e9 for k, v in
                sorted(d.items(), key=lambda kv: kv[1], reverse=True)}

    def what(s: int, e: int) -> str:
        best, name = 0, "no annotation"
        for nm, hs, he in annotated:
            ov = min(e, he) - max(s, hs)
            if ov > best:
                best, name = ov, nm
        return name

    named.sort(reverse=True)
    return {
        **base,
        "idle_split_s": {k: v / n / 1e9 for k, v in split.items()},
        "idle_by_span": sec(by_span),
        "idle_elsewhere": sec(elsewhere),
        "device_by_scope": sec(by_scope),
        "span_s": sec(span_ns, 1),
        "idle_gaps": [[f"{what(s, e)} > {inner}", d / 1e9]
                      for d, s, e, inner in named[:top]],
    }


def _scope_time(ops: List[list], modules: List[list],
                scopes: Dict[str, Dict[str, str]],
                out: Dict[str, int]) -> None:
    """Add each busy instant of one device, counted once by its innermost
    op (a ``while`` event holds its body's ops), to that op's scope."""
    starts = [s for _, s, _ in modules]
    for s, e, path in _layers(ops):
        op = path[-1]
        i = bisect.bisect_right(starts, s) - 1
        module = (modules[i][0].split("(")[0]
                  if i >= 0 and s < modules[i][1] + modules[i][2] else "")
        # a full op event name, or bench.trace.short_name's form of it
        head = op.partition(" = ")[0].lstrip("%").split(" ")[0]
        sc = scopes.get(module, {}).get(head, "") or UNSCOPED
        out[sc] = out.get(sc, 0) + e - s
