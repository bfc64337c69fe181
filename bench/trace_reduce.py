"""Reduction of a profiler trace to device busy time, top device ops and
idle gaps named by what the host was doing.

The benchmark wraps its own host phases in `jax.profiler.TraceAnnotation`
(see `harness.PHASES`); the profiler writes them on the host plane and
the device's operations on one plane per chip, all on one clock. A
stretch is the span of one annotation (a pass). Within it:

  busy_s      the union of the intervals in which an operation ran on a
              chip, averaged over the chips that ran any;
  device_ops  the operations that took most time, summed by name;
  idle_gaps   the longest gaps in that union on the first busy chip, each
              named by the host phase that overlaps it most.
"""
from __future__ import annotations

import glob
import os

# device-plane lines that hold one event per operation; the "XLA Modules"
# and "Steps" lines hold whole programs and would count a program's own
# gaps as busy
OP_LINES = ("XLA Ops",)


def newest_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def read_planes(path: str) -> list:
    """[(plane name, [(line name, [(name, start_ns, end_ns), ...])])]."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        lines = []
        for line in plane.lines:
            lines.append((line.name, [
                (e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
                for e in line.events]))
        out.append((plane.name, lines))
    return out


def _is_device(plane_name: str) -> bool:
    return plane_name.startswith("/device:") and "CPU" not in plane_name


def _union(intervals):
    """Merged, sorted [(start, end)] of possibly overlapping intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged


def _overlap(a0, a1, b0, b1) -> float:
    return max(0.0, min(a1, b1) - max(a0, b0))


def reduce(planes: list, stretch: str, phases: tuple, top: int = 10):
    """Busy time, top ops and idle gaps inside the first host span named
    `stretch`; None when the trace holds no such span or no device op."""
    host = [(n, s, e) for name, lines in planes if not _is_device(name)
            for _, evs in lines for n, s, e in evs if n in phases]
    span = next(((s, e) for n, s, e in sorted(host, key=lambda x: x[1])
                 if n == stretch), None)
    if span is None:
        return None
    w0, w1 = span
    inner = [(n, s, e) for n, s, e in host if n != stretch
             and _overlap(s, e, w0, w1) > 0]
    busy, ops, gaps = [], {}, None
    for name, lines in planes:
        if not _is_device(name):
            continue
        evs = [(n, max(s, w0), min(e, w1)) for ln, le in lines
               if ln in OP_LINES for n, s, e in le if e > w0 and s < w1]
        if not evs:
            continue
        merged = _union((s, e) for _, s, e in evs)
        busy.append(sum(e - s for s, e in merged))
        for n, s, e in evs:
            ops[n] = ops.get(n, 0.0) + (e - s)
        if gaps is None:
            edges = [w0] + [x for se in merged for x in se] + [w1]
            gaps = [(edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i]]
    if not busy:
        return None

    def phase_of(g0, g1):
        # the innermost of the spans that overlap the gap most
        best = max(inner, key=lambda x: (_overlap(x[1], x[2], g0, g1),
                                         x[1] - x[2]), default=None)
        if best is None or _overlap(best[1], best[2], g0, g1) <= 0:
            return "other"
        return best[0]

    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    ns = 1e-9
    return {
        "busy_s": sum(busy) / len(busy) * ns,
        "window_s": (w1 - w0) * ns,
        "n_devices": len(busy),
        "device_ops": [[n, t * ns] for n, t in
                       sorted(ops.items(), key=lambda x: -x[1])[:top]
                       ] if ops else [],
        "idle_gaps": [[phase_of(g0, g1), (g1 - g0) * ns] for g0, g1 in gaps],
    }
