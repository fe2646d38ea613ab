"""Reduction of a profiler trace to the numbers the per-layer readers use.

A trace here is two lists of (name, start_s, end_s) intervals on one clock:
the device operations of every chip used, and the host spans the harness
writes with `jax.profiler.TraceAnnotation` ("chipbench.<span>"). `load`
reads them from the `.xplane.pb` file that `jax.profiler` writes; every
other function works on the lists, so tests can hand-build them.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import List, Tuple

Interval = Tuple[str, float, float]

SPAN_PREFIX = "chipbench."
# marks at the open and the close of the traced window (a span that long
# does not reach the trace)
WINDOW_OPEN, WINDOW_CLOSE = SPAN_PREFIX + "window_open", SPAN_PREFIX + "window_close"
DISPATCH_SPAN = SPAN_PREFIX + "dispatch"
# spans with no child span: what the host was doing at an instant
LEAF_SPANS = tuple(SPAN_PREFIX + s for s in ("assemble", "call", "fetch", "wait"))
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"


@dataclasses.dataclass
class Trace:
    device_ops: List[List[Interval]]  # per chip
    host_spans: List[Interval]


def op_name(hlo: str) -> str:
    """"fusion.3 f32[409600,64] fusion" from the HLO text the trace gives an
    operation ("%fusion.3 = f32[409600,64]{1,0:T(8,128)} fusion(...), ...")."""
    name, _, rest = hlo.partition(" = ")
    shape = re.match(r"\(?\w+\[[\d,]*\]", rest)
    opcode = re.search(r" ([a-z][\w-]*)\(", rest)
    return " ".join([name.lstrip("%")] + [m.group(m.lastindex or 0).lstrip("(")
                                          for m in (shape, opcode) if m])


def load(trace_dir: str) -> Trace:
    """Device ops ("XLA Ops" line of each TPU plane) and the harness's own
    host spans from the one `.xplane.pb` under `trace_dir`."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one trace under {trace_dir}, found {files}")
    data = ProfileData.from_file(files[0])
    device, host = [], []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            device.append([(op_name(e.name), e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
                           for line in plane.lines if line.name == OPS_LINE
                           for e in line.events])
        elif plane.name.startswith("/host:"):
            host.extend((e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
                        for line in plane.lines for e in line.events
                        if e.name.startswith(SPAN_PREFIX))
    return Trace(device_ops=device, host_spans=host)


def window(trace: Trace) -> Tuple[float, float]:
    """From the window's open mark to its close mark."""
    marks = {m: [(s, e) for n, s, e in trace.host_spans if n == m]
             for m in (WINDOW_OPEN, WINDOW_CLOSE)}
    if any(len(v) != 1 for v in marks.values()):
        raise RuntimeError(f"expected one mark each of the traced window, found {marks}")
    return marks[WINDOW_OPEN][0][0], marks[WINDOW_CLOSE][0][1]


def clip(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(n, max(s, lo), min(e, hi)) for n, s, e in intervals if e > lo and s < hi]


def union(intervals: List[Interval]) -> List[Tuple[float, float]]:
    """Disjoint, sorted (start, end) covering every interval."""
    merged: List[List[float]] = []
    for _, s, e in sorted(intervals, key=lambda x: x[1]):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def busy_s(ops: List[Interval], lo: float, hi: float) -> float:
    """Seconds of [lo, hi) in which some operation ran on one chip."""
    return sum(e - s for s, e in union(clip(ops, lo, hi)))


def idle_gaps(ops: List[Interval], lo: float, hi: float) -> List[Tuple[float, float]]:
    """The stretches of [lo, hi) in which no operation ran on one chip."""
    gaps, t = [], lo
    for s, e in union(clip(ops, lo, hi)):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def host_at(spans: List[Interval], lo: float, hi: float) -> str:
    """The leaf span that covers most of [lo, hi), without its prefix, or
    "host_other" where no span of the harness covers it."""
    cover = defaultdict(float)
    for n, s, e in spans:
        if n in LEAF_SPANS:
            cover[n] += max(0.0, min(e, hi) - max(s, lo))
    best = max(cover, key=cover.get, default=None)
    return best[len(SPAN_PREFIX):] if best and cover[best] > 0 else "host_other"


def top_ops(ops: List[Interval], lo: float, hi: float, n: int = 10) -> List[list]:
    """The `n` operation names with the most device time in [lo, hi)."""
    total = defaultdict(float)
    for name, s, e in clip(ops, lo, hi):
        total[name] += e - s
    return [[k, v] for k, v in sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def longest_gaps(trace: Trace, lo: float, hi: float, n: int = 10) -> List[list]:
    """The `n` longest idle stretches of any chip, each named by what the
    harness was doing on the host meanwhile."""
    gaps = [g for ops in trace.device_ops for g in idle_gaps(ops, lo, hi)]
    gaps.sort(key=lambda g: g[0] - g[1])
    return [[host_at(trace.host_spans, s, e), e - s] for s, e in gaps[:n]]


def span_durations(trace: Trace, name: str, lo: float, hi: float) -> List[float]:
    """Durations of the spans `name` that start in [lo, hi)."""
    return [e - s for n, s, e in trace.host_spans if n == name and lo <= s < hi]
