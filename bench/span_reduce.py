"""Reduction of a profiler trace by the engine's own names: device time
per plane, from the `d3.*` named scopes of the tick program, and device
idle time per host span, from the `d3.*` spans the driver opens
(`src/repro/telemetry/spans.py`).

Within a stretch (the first host span named `stretch`, a pass):

  leaf ops      the events of a device's "XLA Ops" line that contain no
                other event of that line: the scan's `while` (which
                holds its whole body) drops out, its body's ops stay;
  planes_s      leaf time summed by innermost `d3.*` plane scope
                (`round_a`, `route`, `round_b`, `deliver`, `forward`,
                `topo`, `sink`, `query`, `quiet`, `train`); ops under a
                `d3.layer<l>` scope and no plane scope count as `layer`,
                ops under no `d3.*` scope as `unscoped`;
  layers_s      the same leaf time by `d3.layer<l>` scope (`-`: none);
  idle_by_span  every instant of device idle (no op of any kind running)
                split by the innermost `d3.*` host span open then, or
                `none`;
  host_spans    count, total and self seconds of each `d3.*` host span
                (self: the total less its children's).

An op's scope is read from its own stats where the profiler put the
op's `op_name` there; else from `op_names`, {instruction: op_name} of
the compiled super-tick (`op_names_from_hlo`), by the instruction the
event names. A drain launches another program, whose instruction names
overlap the first one's: ops that start inside a host `d3.drain` span
are looked up in `drain_op_names` instead.
"""
from __future__ import annotations

import re

import trace_reduce as tr

PREFIX = "d3."
NONE = "none"
UNSCOPED = "unscoped"
_INSTR = re.compile(r"^%?([\w.\-]+)")
_OP_NAME = re.compile(r'^\s*(?:ROOT )?%([\w.\-]+) = .*?op_name="([^"]*)"',
                      re.M)


def read_planes(path: str, stretch: str) -> list:
    """Like `trace_reduce.read_planes`, with a fourth element, the event's
    stats as a dict, and only what this reduction reads: the host's
    `d3.*` spans and `stretch`, and the devices' op lines."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        dev = tr._is_device(plane.name)
        lines = []
        for line in plane.lines:
            if dev and line.name not in tr.OP_LINES:
                continue
            evs = []
            for e in line.events:
                if not dev and not (e.name.startswith(PREFIX)
                                    or e.name == stretch):
                    continue
                s = float(e.start_ns)
                evs.append((e.name, s, s + float(e.duration_ns),
                            {k: v for k, v in e.stats}))
            lines.append((line.name, evs))
        out.append((plane.name, lines))
    return out


def op_names_from_hlo(text: str) -> dict:
    """{instruction name: op_name} of a compiled program's HLO text."""
    return dict(_OP_NAME.findall(text))


def scope_of(name: str, stats: dict, op_names: dict) -> tuple:
    """The `d3.*` scopes an op ran under, outermost first."""
    path = next((v for v in stats.values()
                 if isinstance(v, str) and "/" + PREFIX in v), None)
    if path is None:
        m = _INSTR.match(name)
        path = op_names.get(m.group(1), "") if m else ""
    return tuple(p[len(PREFIX):] for p in path.split("/")
                 if p.startswith(PREFIX))


def plane_of(scope: tuple) -> str:
    for p in reversed(scope):
        if not p.startswith("layer"):
            return p
    return "layer" if scope else UNSCOPED


def layer_of(scope: tuple) -> str:
    return next((p for p in scope if p.startswith("layer")), "-")


def leaves(events: list) -> list:
    """The events that contain no other event of the list."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    parent = [False] * len(events)
    stack = []
    for i in order:
        s, e = events[i][1], events[i][2]
        while stack and events[stack[-1]][2] <= s:
            stack.pop()
        if stack and e <= events[stack[-1]][2]:
            parent[stack[-1]] = True
        stack.append(i)
    return [ev for ev, p in zip(events, parent) if not p]


def _innermost(spans: list, w0: float, w1: float) -> list:
    """[(start, end, name)] covering [w0, w1]: the innermost (latest
    opened) of `spans` open over each piece, or NONE."""
    cuts = sorted({w0, w1} | {min(max(x, w0), w1)
                              for _, s, e in spans for x in (s, e)})
    label = [NONE] * (len(cuts) - 1)
    at = {c: i for i, c in enumerate(cuts)}
    for name, s, e in sorted(spans, key=lambda x: (x[1], -x[2])):
        for i in range(at[min(max(s, w0), w1)], at[min(max(e, w0), w1)]):
            label[i] = name
    return [(cuts[i], cuts[i + 1], label[i]) for i in range(len(label))]


def split(intervals: list, pieces: list) -> dict:
    """Time of sorted, disjoint `intervals` inside each labelled piece of
    the sorted, disjoint `pieces`, summed by label."""
    out, j = {}, 0
    for s, e in intervals:
        while j < len(pieces) and pieces[j][1] <= s:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < e:
            t = tr._overlap(s, e, pieces[k][0], pieces[k][1])
            if t > 0:
                out[pieces[k][2]] = out.get(pieces[k][2], 0.0) + t
            k += 1
    return out


def _complement(merged: list, w0: float, w1: float) -> list:
    edges = [w0] + [x for se in merged for x in se] + [w1]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def host_spans(spans: list) -> dict:
    """{name: [count, total_s, self_s]} of nested host spans."""
    out, stack = {}, []

    def close(top):
        name, s, e, child = top
        c = out.setdefault(name, [0, 0.0, 0.0])
        c[0] += 1
        c[1] += (e - s) * 1e-9
        c[2] += (e - s - child) * 1e-9
        if stack:
            stack[-1][3] += e - s

    for name, s, e in sorted(spans, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][2] <= s:
            close(stack.pop())
        stack.append([name, s, e, 0.0])
    while stack:
        close(stack.pop())
    return out


def reduce(planes: list, stretch: str, op_names: dict = None,
           drain_op_names: dict = None):
    """The reduction of the first host span named `stretch`; None when the
    trace holds no such span or no device op inside it."""
    op_names = op_names or {}
    drain_op_names = drain_op_names or op_names
    host = [(n, s, e) for name, lines in planes if not tr._is_device(name)
            for _, evs in lines for n, s, e, *_ in evs]
    span = next(((s, e) for n, s, e in sorted(host, key=lambda x: x[1])
                 if n == stretch), None)
    if span is None:
        return None
    w0, w1 = span
    spans = [(n, max(s, w0), min(e, w1)) for n, s, e in host
             if n.startswith(PREFIX) and tr._overlap(s, e, w0, w1) > 0]
    pieces = _innermost(spans, w0, w1)
    drains = tr._union((s, e) for n, s, e in spans
                       if n == PREFIX + "drain")
    ns = 1e-9
    devs = []
    for name, lines in planes:
        if not tr._is_device(name):
            continue
        evs = [(n, max(s, w0), min(e, w1), st) for ln, le in lines
               if ln in tr.OP_LINES for n, s, e, st in le
               if e > w0 and s < w1]
        if not evs:
            continue
        leaf = leaves(evs)
        busy = tr._union((s, e) for _, s, e, _ in evs)
        leaf_busy = tr._union((s, e) for _, s, e, _ in leaf)
        planes_s, layers_s = {}, {}
        for n, s, e, st in leaf:
            in_drain = any(d0 <= s < d1 for d0, d1 in drains)
            sc = scope_of(n, st, drain_op_names if in_drain else op_names)
            p, l = plane_of(sc), layer_of(sc)
            planes_s[p] = planes_s.get(p, 0.0) + (e - s) * ns
            layers_s[l] = layers_s.get(l, 0.0) + (e - s) * ns
        idle = _complement(busy, w0, w1)
        devs.append({
            "busy_s": sum(e - s for s, e in busy) * ns,
            "leaf_busy_s": sum(e - s for s, e in leaf_busy) * ns,
            "leaf_s": sum(e - s for _, s, e, _ in leaf) * ns,
            "idle_s": sum(e - s for s, e in idle) * ns,
            "planes_s": planes_s, "layers_s": layers_s,
            "idle_by_span": {k: v * ns
                             for k, v in split(idle, pieces).items()}})
    if not devs:
        return None

    def mean(key):
        if isinstance(devs[0][key], dict):
            keys = {k for d in devs for k in d[key]}
            return {k: sum(d[key].get(k, 0.0) for d in devs) / len(devs)
                    for k in sorted(keys)}
        return sum(d[key] for d in devs) / len(devs)

    out = {"window_s": (w1 - w0) * ns, "n_devices": len(devs),
           "launches": sum(1 for n, s, _ in host
                           if n == PREFIX + "launch" and w0 <= s < w1)}
    for key in devs[0]:
        out[key] = mean(key)
    out["host_spans"] = host_spans(spans)
    return out
