"""Reduce a profiler trace to device busy time, top device operations and
the longest idle gaps.

``load(path)`` turns an ``.xplane.pb`` file into plain data: planes, their
lines, and events as ``[name, start_ns, duration_ns, op_name]``.
``reduce`` works on that data only (an event's fourth field may be left
out), so the CPU test checks it on a small recorded trace (``tests/data/``).

The traced window is the host span ``bench.traced``.  Device operations
are the events on ``/device:`` planes, on the line named ``XLA Ops`` where
the plane has one.  Busy time is the union of their intervals inside the
window, averaged over the device planes.  An operation's time is its self
time: a loop's event encloses its body's, so the body's are taken out.
An idle gap is named after the host span (the program's ``engine.*``, the
harness's ``bench.*``) that holds most of it, each instant going to the
innermost span open then.
"""
from __future__ import annotations

import collections

WINDOW_SPAN = "bench.traced"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIXES = ("engine.", "bench.")
NO_SPAN = "none"


def load(path: str) -> dict:
    """xplane.pb -> {"planes": [{"name", "lines": [{"name", "events"}]}]},
    events ``[name, start_ns, duration_ns, op_name]`` with op_name ''
    (``phases.name_ops`` fills it in).  Device planes keep their ``XLA
    Ops`` and ``XLA Modules`` lines; host planes keep only the ``engine.*``
    and ``bench.*`` spans."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith("/device:")
        lines = []
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            evs = [[short_name(e.name), float(e.start_ns),
                    float(e.duration_ns), ""]
                   for e in line.events
                   if device or e.name.startswith(HOST_PREFIXES)]
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


def idle_by_span(gaps, spans):
    """Seconds of the gaps (``(start_ns, end_ns)``) charged, instant by
    instant, to the innermost host span (``(name, start_ns, duration_ns)``)
    open then (the latest to start; host spans of one thread nest), or to
    ``none``."""
    out = collections.Counter()
    for g0, g1 in gaps:
        live = sorted((s, s + d, n) for n, s, d in spans
                      if s < g1 and s + d > g0)
        cuts = sorted({g0, g1} | {x for s, e, _ in live for x in (s, e)
                                  if g0 < x < g1})
        for a, b in zip(cuts, cuts[1:]):
            open_ = [(s, -e, n) for s, e, n in live if s <= a and e >= b]
            out[max(open_)[2] if open_ else NO_SPAN] += (b - a) * 1e-9
    return out


def _device_ops(plane):
    lines = [l for l in plane["lines"] if l["name"] == OPS_LINE] or plane["lines"]
    return [e for l in lines for e in l["events"]]


def reduce(trace: dict, top: int = 10):
    """Returns None when the trace holds no window or no device operation,
    else {"busy_s", "window_s", "device_ops", "idle_gaps"}."""
    host = [e for p in trace["planes"] if not p["name"].startswith("/device:")
            for l in p["lines"] for e in l["events"]]
    window = [e for e in host if e[0] == WINDOW_SPAN]
    devices = [p for p in trace["planes"] if p["name"].startswith("/device:")]
    if not window or not devices:
        return None
    t0 = window[0][1]
    t1 = t0 + window[0][2]
    busy, per_op, gaps = [], collections.Counter(), []
    for i, plane in enumerate(devices):
        clipped = []
        for name, s, d, *_ in _device_ops(plane):
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
    spans = [(name, s, d) for name, s, d, *_ in host if name != WINDOW_SPAN]

    def label(gap):
        return idle_by_span([gap], spans).most_common(1)[0][0]

    gaps.sort(key=lambda g: g[0] - g[1])
    return {"busy_s": sum(busy) / len(busy), "window_s": (t1 - t0) * 1e-9,
            "device_ops": [[n, s] for n, s in per_op.most_common(top)],
            "idle_gaps": [[label(g), (g[1] - g[0]) * 1e-9] for g in gaps[:top]]}
