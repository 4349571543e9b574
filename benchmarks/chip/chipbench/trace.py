"""Reduction of a profiler trace to device busy time, idle gaps and the
operations that took most time (``program_trace.read_xplane`` reads the
trace file).

Device operations are the events of the ``XLA Ops`` line of each ``/device:``
plane. Host spans are the events named ``fetch``, ``dispatch`` and ``wait``
(``jax.profiler.TraceAnnotation`` in the benchmark's loop) on the host plane;
the profiler puts both on one clock. The traced window runs from the first
host span's start to the last one's end.
"""
from __future__ import annotations

from collections import defaultdict
from typing import Dict, List, Tuple

HOST_SPANS = ("fetch", "dispatch", "wait")
NAME_CHARS = 160  # an op's name is its HLO instruction, cut to this length
OPS_LINE = "XLA Ops"

Event = Tuple[str, float, float]  # (name, start_ns, end_ns)


def _union(intervals: List[Tuple[float, float]], lo: float, hi: float):
    out = []
    for a, b in sorted(intervals):
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def reduce_events(devices: Dict[str, List[Event]], host: List[Event], top: int = 10):
    """{"window_s", "busy_s" (mean over devices), "idle_pct", "device_ops":
    [[name, seconds per device]], "idle_gaps": [[host span open in the gap,
    seconds]] (the longest gaps of the first device), "idle_by_span":
    {span: seconds}} or None when there is no device event or host span."""
    if not devices or not host:
        return None
    lo = min(a for _, a, _ in host)
    hi = max(b for _, _, b in host)
    window = hi - lo
    if window <= 0:
        return None
    busy, ops = [], defaultdict(float)
    for evs in devices.values():
        merged = _union([(a, b) for _, a, b in evs], lo, hi)
        busy.append(sum(b - a for a, b in merged))
        for name, a, b in evs:
            a, b = max(a, lo), min(b, hi)
            if b > a:
                ops[name] += b - a
    n = len(devices)
    first = sorted(devices)[0]
    merged = _union([(a, b) for _, a, b in devices[first]], lo, hi)
    gaps, edge = [], lo
    for a, b in merged + [[hi, hi]]:
        if a > edge:
            gaps.append((edge, a))
        edge = max(edge, b)
    labelled, by_span = [], defaultdict(float)
    for a, b in gaps:
        cover = defaultdict(float)
        for name, s, e in host:
            o = min(b, e) - max(a, s)
            if o > 0:
                cover[name] += o
        label = max(cover, key=cover.get) if cover else "none"
        labelled.append([label, (b - a) / 1e9])
        by_span[label] += (b - a) / 1e9
    labelled.sort(key=lambda x: -x[1])
    busy_s = sum(busy) / n / 1e9
    return {
        "window_s": window / 1e9,
        "busy_s": busy_s,
        "idle_pct": 100.0 * (1.0 - busy_s / (window / 1e9)),
        "device_ops": sorted(([k, v / n / 1e9] for k, v in ops.items()),
                             key=lambda x: -x[1])[:top],
        "idle_gaps": labelled[:top],
        "idle_by_span": dict(by_span),
    }
