"""Reduce a profiler trace to device busy time, top device operations and
the longest idle gaps.

``load(path)`` turns an ``.xplane.pb`` file into plain data: planes, their
lines, and events as ``[name, start_ns, duration_ns]``.  ``reduce`` works on
that data only, so the CPU test checks it on a small recorded trace
(``tests/data/``).

The traced window is the host span ``bench.traced``.  Device operations
are the events on ``/device:`` planes, on the line named ``XLA Ops`` where
the plane has one.  Busy time is the union of their intervals inside the
window, averaged over the device planes.  An operation's time is its self
time: a loop's event encloses its body's, so the body's are taken out.
An idle gap is named after the ``bench.*`` host span that covers most of
it.
"""
from __future__ import annotations

import collections

WINDOW_SPAN = "bench.traced"
OPS_LINE = "XLA Ops"


def load(path: str, keep_host_prefix: str = "bench.") -> dict:
    """xplane.pb -> {"planes": [{"name", "lines": [{"name", "events"}]}]}.
    Host events other than the benchmark's own spans are dropped."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith("/device:")
        lines = []
        for line in plane.lines:
            evs = [[short_name(e.name), float(e.start_ns),
                    float(e.duration_ns)]
                   for e in line.events
                   if device or e.name.startswith(keep_host_prefix)]
            if evs:
                lines.append({"name": line.name, "events": evs})
        if lines:
            out.append({"name": plane.name, "lines": lines})
    return {"planes": out}


def short_name(name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return name.split(" = ", 1)[0].lstrip("%")


def _self_times(events):
    """Seconds each event runs with none of the events nested in it
    (a loop's body runs inside the loop's own event)."""
    out = []
    stack = []                      # [end, index] of open events
    for i, (name, s, d) in enumerate(sorted(events, key=lambda e: (e[1], -e[2]))):
        out.append([name, d])
        while stack and stack[-1][0] < s + d:   # not enclosing this one
            stack.pop()
        if stack:
            out[stack[-1][1]][1] -= d
        stack.append([s + d, i])
    return out


def _union(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _device_ops(plane):
    lines = [l for l in plane["lines"] if l["name"] == OPS_LINE] or plane["lines"]
    return [e for l in lines for e in l["events"]]


def reduce(trace: dict, top: int = 10):
    """Returns None when the trace holds no window or no device operation,
    else {"busy_s", "window_s", "device_ops", "idle_gaps"}."""
    host = [e for p in trace["planes"] if not p["name"].startswith("/device:")
            for l in p["lines"] for e in l["events"]]
    spans = [e for e in host if e[0] == WINDOW_SPAN]
    devices = [p for p in trace["planes"] if p["name"].startswith("/device:")]
    if not spans or not devices:
        return None
    t0 = spans[0][1]
    t1 = t0 + spans[0][2]
    busy, per_op, gaps = [], collections.Counter(), []
    for i, plane in enumerate(devices):
        clipped = []
        for name, s, d in _device_ops(plane):
            s, e = max(s, t0), min(s + d, t1)
            if e > s:
                clipped.append([name, s, e - s])
        for name, own in _self_times(clipped):
            per_op[name] += own * 1e-9
        iv = [(s, s + d) for _, s, d in clipped]
        merged = _union(iv)
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        if i == 0:
            edges = [t0] + [x for m in merged for x in m] + [t1]
            gaps = [(edges[k], edges[k + 1]) for k in range(0, len(edges), 2)
                    if edges[k + 1] > edges[k]]
    if not any(busy):
        return None
    marks = [e for e in host if e[0] != WINDOW_SPAN]

    def label(gap):
        s, e = gap
        best, cover = "none", 0.0
        for name, hs, hd in marks:
            c = min(e, hs + hd) - max(s, hs)
            if c > cover:
                best, cover = name, c
        return best

    gaps.sort(key=lambda g: g[0] - g[1])
    return {"busy_s": sum(busy) / len(busy), "window_s": (t1 - t0) * 1e-9,
            "device_ops": [[n, s] for n, s in per_op.most_common(top)],
            "idle_gaps": [[label(g), (g[1] - g[0]) * 1e-9] for g in gaps[:top]]}
