"""Device time per named scope and host time per program span, read from
the profile of a ``--trace 1`` run.

The program names each bucket scan of its packed evaluator
(``packed.bucketNN``) and the composition after them (``packed.compose``)
with ``jax.named_scope``, and writes host spans ``repro.<layer>.<step>``
(``repro.tracing``).  A TPU trace names a device operation by its HLO
instruction alone; ``PackedMatrix.stats()["op_scopes"]``, which the sweep
runner keeps as ``run["packed_stats"]``, maps those names to scopes.

``run.Tracer`` hands the metric readers a summary of its trace and removes
the profile only after they have run.  ``of(run)`` finds that profile
again, the newest ``bench_trace_*`` directory under the temp directory
whose device busy time over the stretch equals the summary's, and reduces
it with ``reduce``.  A program without scopes or spans reduces to empty
tables, and the readers then return None.
"""

from __future__ import annotations

import functools
import glob
import os
import tempfile
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

PROGRAM_PREFIX = "repro."            # repro.tracing.PREFIX; not imported,
                                     # a program without it reads None
PROFILE_DIRS = "bench_trace_*"       # run.Tracer's mkdtemp prefix
UNSCOPED = ""

Span = Tuple[str, str, float, float]   # (name, host thread, start, end) ns


def measure(starts, ends, lo: float, hi: float) -> float:
    """Length of the union of the intervals, clipped to [lo, hi]."""
    s = np.clip(np.asarray(starts, np.float64), lo, hi)
    e = np.clip(np.asarray(ends, np.float64), lo, hi)
    keep = e > s
    s, e = s[keep], e[keep]
    if not s.size:
        return 0.0
    order = np.argsort(s, kind="stable")
    s, e = s[order], e[order]
    reach = np.maximum.accumulate(e)
    first = np.flatnonzero(np.r_[True, s[1:] > reach[:-1]])
    last = np.r_[first[1:] - 1, s.size - 1]
    return float((reach[last] - s[first]).sum())


def device_ops(events: Iterable[Tuple[str, float, float]],
               op_scopes: Dict[str, str], scopes: List[str]):
    """A device line's ``(name, start, end)`` operations as arrays of
    start, end and scope index into ``scopes``; a TPU names an operation
    by its HLO instruction text, ``"%fusion.12 = f32[...] fusion(...)"``."""
    index = {sc: i for i, sc in enumerate(scopes)}
    by_name: Dict[str, int] = {}
    st, en, sc = [], [], []
    for name, s, e in events:
        k = by_name.get(name)
        if k is None:
            op = name.split(" ", 1)[0].lstrip("%")
            k = by_name[name] = index[op_scopes.get(op, UNSCOPED)]
        st.append(s)
        en.append(e)
        sc.append(k)
    return (np.asarray(st, np.float64), np.asarray(en, np.float64),
            np.asarray(sc, np.int64))


def load(path: str, op_scopes: Dict[str, str]) -> Dict[str, object]:
    """One pass over a profile: each TPU device's operations
    (``device_ops``), the scope names they index, the program's host
    spans, and when the trace started (profiler clock)."""
    from jax.profiler import ProfileData

    scopes = [UNSCOPED] + sorted(set(op_scopes.values()))
    devices, spans, t_start = {}, [], None
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    devices[plane.name] = device_ops(
                        ((e.name, e.start_ns, e.end_ns) for e in line.events),
                        op_scopes, scopes)
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PROGRAM_PREFIX):
                        spans.append((e.name[len(PROGRAM_PREFIX):],
                                      line.name, float(e.start_ns),
                                      float(e.end_ns)))
                    elif e.name.endswith(" start_trace"):
                        t_start = float(e.end_ns)
    return {"devices": devices, "scopes": scopes, "spans": spans,
            "t_start_ns": t_start}


def span_seconds(spans: Sequence[Span], lo: float, hi: float
                 ) -> Dict[str, Dict[str, float]]:
    """Seconds per program span name, clipped to [lo, hi]: ``total_s``,
    and ``self_s``, the part no other program span on the same host
    thread, nested inside it, covers."""
    out: Dict[str, Dict[str, float]] = {}
    for name, thread, s, e in spans:
        kids = [(s2, e2) for n2, t2, s2, e2 in spans
                if t2 == thread and s <= s2 and e2 <= e
                and (s2, e2) != (s, e)]
        total = measure([s], [e], lo, hi)
        inner = measure([k[0] for k in kids], [k[1] for k in kids],
                        max(s, lo), min(e, hi))
        d = out.setdefault(name, {"total_s": 0.0, "self_s": 0.0})
        d["total_s"] += total * 1e-9
        d["self_s"] += (total - inner) * 1e-9
    return out


def reduce(devices, scopes: List[str], spans: Sequence[Span], lo: float,
           hi: float) -> Dict[str, object]:
    """Busy seconds and device seconds per scope (each the union of its
    operations' intervals, so an operation nested in a loop of the same
    scope counts once), averaged over the devices; host seconds per
    program span."""
    n = max(1, len(devices))
    busy, per_scope = 0.0, np.zeros(len(scopes))
    for st, en, sc in devices.values():
        busy += measure(st, en, lo, hi)
        for k in range(1, len(scopes)):
            m = sc == k
            per_scope[k] += measure(st[m], en[m], lo, hi)
    return {"busy_s": busy / n * 1e-9,
            "scopes": {scopes[k]: per_scope[k] / n * 1e-9
                       for k in range(1, len(scopes)) if per_scope[k] > 0},
            "spans": span_seconds(spans, lo, hi)}


def stretch(t_start: Optional[float], devices, window_s: float
            ) -> Tuple[float, float]:
    """The traced stretch as ``run.Tracer.summary`` sets it: from the
    start of the trace for the host-measured length, moved to the first
    device operation where no operation starts inside it."""
    lo = t_start if t_start is not None else 0.0
    hi = lo + window_s * 1e9
    firsts = [st.min() for st, _, _ in devices.values() if st.size]
    if firsts and not any(((st >= lo) & (st < hi)).any()
                          for st, _, _ in devices.values()):
        lo = min(firsts)
        hi = lo + window_s * 1e9
    return lo, hi


@functools.lru_cache(maxsize=1)
def _reduced(path: str, mtime: float, window_s: float, busy_s: float,
             op_scopes: Tuple[Tuple[str, str], ...]):
    ev = load(path, dict(op_scopes))
    lo, hi = stretch(ev["t_start_ns"], ev["devices"], window_s)
    out = reduce(ev["devices"], ev["scopes"], ev["spans"], lo, hi)
    if abs(out["busy_s"] - busy_s) > 1e-6 * max(busy_s, 1e-9):
        return None                      # another run's profile
    return out


def profiles() -> List[str]:
    """``.xplane.pb`` files under the temp directory's profile
    directories, newest first."""
    pattern = os.path.join(tempfile.gettempdir(), PROFILE_DIRS, "**",
                           "*.xplane.pb")
    return sorted(glob.glob(pattern, recursive=True), key=os.path.getmtime,
                  reverse=True)


def of(run) -> Optional[Dict[str, object]]:
    """The reduction of this traced run's profile, or None where the run
    was not traced or its profile is not found."""
    trace = run.get("trace")
    if not trace:
        return None
    scopes = tuple(sorted(
        (run.get("packed_stats") or {}).get("op_scopes", {}).items()))
    paths = profiles()
    if not paths:
        return None
    return _reduced(paths[0], os.path.getmtime(paths[0]), trace["window_s"],
                    trace["busy_s"], scopes)
