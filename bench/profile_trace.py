"""The profiler trace of a steady stretch of the window, and its reduction
to device busy time, the costliest device operations and the longest
idle gaps labelled by what the host was doing.

``load`` reads the profiler's ``.xplane.pb`` into plain lists; everything
after that is arithmetic on those lists (``summarize``), so it can be
checked on a small recorded trace.
"""

from __future__ import annotations

import glob
import os
from collections import defaultdict
from typing import Dict, List, Sequence, Tuple

Interval = Tuple[str, float, float]          # (name, start_ns, end_ns)

# host spans the benchmark records (innermost wins when labelling a gap)
SPAN_PREFIX = "bench."


def load(logdir: str) -> Dict[str, object]:
    """Device operations per device plane and the benchmark's host spans,
    all on the profiler's clock (nanoseconds)."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    pd = ProfileData.from_file(max(paths, key=os.path.getmtime))
    devices: Dict[str, List[Interval]] = {}
    modules: Dict[str, List[Interval]] = {}
    spans: List[Interval] = []
    t_start = None
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU"):
            for line in plane.lines:
                evs = [(e.name, float(e.start_ns), float(e.end_ns))
                       for e in line.events]
                if line.name == "XLA Ops":
                    devices[plane.name] = evs
                elif line.name == "XLA Modules":
                    modules[plane.name] = evs
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name[len(SPAN_PREFIX):],
                                      float(e.start_ns), float(e.end_ns)))
                    elif e.name.endswith(" start_trace"):
                        t_start = float(e.end_ns)
    return {"devices": devices, "modules": modules, "spans": spans,
            "t_start_ns": t_start}


def union(intervals: Sequence[Interval], lo: float, hi: float
          ) -> List[Tuple[float, float]]:
    """Merged (start, end) of the intervals, clipped to [lo, hi]."""
    iv = sorted((max(s, lo), min(e, hi)) for _, s, e in intervals
                if e > lo and s < hi)
    out: List[List[float]] = []
    for s, e in iv:
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _label(spans: Sequence[Interval], a: float, b: float) -> str:
    """The innermost host span covering the middle of (a, b)."""
    mid = 0.5 * (a + b)
    cover = [(e - s, n) for n, s, e in spans if s <= mid <= e]
    return min(cover)[1] if cover else "no benchmark span"


def summarize(devices: Dict[str, List[Interval]], spans: List[Interval],
              lo: float, hi: float, top: int = 10) -> Dict[str, object]:
    """Busy seconds averaged over the devices, the costliest device
    operations (summed per name over all devices), and idle time per host
    span label (summed over the gaps of all devices)."""
    busy, per_op = [], defaultdict(float)
    gaps = defaultdict(float)
    for evs in devices.values():
        merged = union(evs, lo, hi)
        busy.append(sum(e - s for s, e in merged))
        for name, s, e in evs:
            if e > lo and s < hi:
                per_op[name] += min(e, hi) - max(s, lo)
        edges = [lo] + [x for se in merged for x in se] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps[_label(spans, a, b)] += b - a
    n = max(1, len(devices))
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    idle = sorted(gaps.items(), key=lambda kv: -kv[1])[:top]
    return {"window_s": (hi - lo) * 1e-9,
            "busy_s": sum(busy) / n * 1e-9 if busy else 0.0,
            "devices": len(devices),
            "device_ops": [[k, v * 1e-9] for k, v in ops],
            "idle_gaps": [[k, v / n * 1e-9] for k, v in idle]}


def count_modules(modules: Dict[str, List[Interval]], lo: float, hi: float
                  ) -> int:
    """Device program launches that start inside [lo, hi], on the first
    device."""
    for evs in modules.values():
        return sum(1 for _, s, _ in evs if lo <= s < hi)
    return 0
