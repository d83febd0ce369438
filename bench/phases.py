"""Split a traced window's device time by the program's own phase scopes,
and its device idle time by the program's own host spans.

The unified step names its device phases with ``jax.named_scope``
(``stem.qkv``, ``stem.score``, ``stem.select``, ``stem.attend``, ...; see
``models/transformer.paged_mixed_step``) and the engine writes
``engine.*`` host spans (``runtime/engine.py``) on the profiler's clock.
Each event of a device plane's ``XLA Ops`` line is one HLO instruction of
the running program, and the innermost ``stem.*`` phase in that
instruction's op_name metadata owns it.  A TPU trace names the
instruction but carries no op_name (TPU v5 lite, jax 0.9.0), so
``name_ops`` takes each op's op_name from the compiled step's HLO text.

``load(path)`` turns an ``.xplane.pb`` file into plain data, as
``devtrace.load`` does, with a fourth field on each device op for its
op_name, and the ``engine.*`` and ``bench.*`` host spans; ``reduce`` works
on that data only, so a CPU test checks it on a small recorded trace.  Run as a
script it is ``run.py`` with one more line before the result: ``phases:``
and the reduction of the traced window (``--trace 1``).

    python3 bench/phases.py --workload <cell> --seed <n> --seconds <s> --trace 1
"""
from __future__ import annotations

import argparse
import bisect
import collections
import json
import pathlib
import re
import sys

import devtrace

HOST_PREFIXES = ("engine.", "bench.")
MODULES_LINE = "XLA Modules"
STEP_MODULE = "unified_step"      # the engine's jitted step, by its name
OP_NAME = re.compile(r'op_name="([^"]*)"')
HLO_LINE = re.compile(r"\s*(?:ROOT )?%?([\w.-]+) = ")
UNSCOPED = "unscoped"
NO_SPAN = "none"

# Phases of the unified step by the per-layer metric that reads them.
GROUPS = {
    "select": ("stem.score", "stem.select"),
    "attend": ("stem.kv_write", "stem.attend"),
    "dense": ("stem.embed", "stem.qkv", "stem.o_proj", "stem.mlp",
              "stem.head", "stem.sample"),
    "unscoped": (UNSCOPED,),
}
PHASES = frozenset(p for g in GROUPS.values() for p in g if p != UNSCOPED)
LANES = ("stem.decode_lane", "stem.chunk_lane")


def hlo_op_names(text: str) -> dict:
    """HLO text of a compiled program -> {instruction name: op_name}."""
    out = {}
    for line in text.splitlines():
        m, on = HLO_LINE.match(line), OP_NAME.search(line)
        if m and on:
            out[m.group(1)] = on.group(1)
    return out


def name_ops(trace: dict, programs) -> None:
    """Give each device op without an op_name the one its instruction has
    in the compiled programs' HLO (``hlo_op_names`` maps).  Programs share
    instruction names, so the ops of one module execution take the program
    whose names cover most of them (on the chip the running program's
    names covered 79% of its ops' events, the other signature's 9%)."""
    for plane in trace["planes"]:
        if not plane["name"].startswith("/device:"):
            continue
        mods = [e for l in plane["lines"] if l["name"] == MODULES_LINE
                for e in l["events"]]
        ops = sorted((e for l in plane["lines"]
                      if l["name"] == devtrace.OPS_LINE for e in l["events"]),
                     key=lambda e: e[1])
        starts = [e[1] for e in ops]
        for _, s, d, _ in mods:
            inside = ops[bisect.bisect_left(starts, s):
                         bisect.bisect_right(starts, s + d)]
            best = max(programs, default={},
                       key=lambda m: sum(e[0] in m for e in inside))
            for e in inside:
                e[3] = e[3] or best.get(e[0], "")


def load(path: str) -> dict:
    """xplane.pb -> {"planes": [{"name", "lines": [{"name", "events"}]}]},
    events ``[name, start_ns, duration_ns, op_name]`` with op_name ''
    until ``name_ops``.  Device planes keep their ``XLA Ops`` and ``XLA
    Modules`` lines; host planes keep only the ``engine.*`` and
    ``bench.*`` spans."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith("/device:")
        lines = []
        for line in plane.lines:
            if device and line.name not in (devtrace.OPS_LINE, MODULES_LINE):
                continue
            evs = [[devtrace.short_name(e.name), float(e.start_ns),
                    float(e.duration_ns), ""]
                   for e in line.events
                   if device or e.name.startswith(HOST_PREFIXES)]
            if evs:
                lines.append({"name": line.name, "events": evs})
        if lines:
            out.append({"name": plane.name, "lines": lines})
    return {"planes": out}


def phase_of(op_name: str) -> str:
    """The innermost ``stem.*`` phase of an op_name, else ``unscoped``."""
    for part in reversed(op_name.split("/")):
        if part in PHASES:
            return part
    return UNSCOPED


def lane_of(op_name: str) -> str:
    for part in op_name.split("/"):
        if part in LANES:
            return part
    return UNSCOPED


def _idle_by_span(gaps, spans):
    """Seconds of the gaps charged, instant by instant, to the innermost
    host span open then (the latest to start; host spans of one thread
    nest), or to ``none``."""
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


def reduce(trace: dict, top: int = 10):
    """Returns None when the trace holds no window or no device operation,
    else {"window_s", "busy_s", "steps", "phase_s", "lane_s", "idle_s",
    "unscoped_ops"}: device self seconds by phase and by lane, averaged
    over the device planes; the window's step count (executions of the
    unified step that start inside it, on the first device); idle seconds
    of the first device by host span; the top unscoped ops by self time.
    """
    host = [e for p in trace["planes"] if not p["name"].startswith("/device:")
            for l in p["lines"] for e in l["events"]]
    window = [e for e in host if e[0] == devtrace.WINDOW_SPAN]
    devices = [p for p in trace["planes"] if p["name"].startswith("/device:")]
    if not window or not devices:
        return None
    t0 = window[0][1]
    t1 = t0 + window[0][2]
    phase_s, lane_s, unscoped = (collections.Counter() for _ in range(3))
    busy, gaps, steps = [], [], 0
    for i, plane in enumerate(devices):
        clipped = []
        for name, s, d, op in (e for l in plane["lines"]
                               if l["name"] == devtrace.OPS_LINE
                               for e in l["events"]):
            s, e = max(s, t0), min(s + d, t1)
            if e > s:
                clipped.append((name, s, e - s, op))
        owned = devtrace._self_times([[k, s, d]
                                      for k, (_, s, d, _) in enumerate(clipped)])
        for k, own in owned:
            name, _, _, op = clipped[k]
            phase = phase_of(op)
            phase_s[phase] += own * 1e-9
            lane_s[lane_of(op)] += own * 1e-9
            if phase == UNSCOPED:
                unscoped[name] += own * 1e-9
        merged = devtrace._union([(s, s + d) for _, s, d, _ in clipped])
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        if i == 0:
            edges = [t0] + [x for m in merged for x in m] + [t1]
            gaps = [(edges[k], edges[k + 1]) for k in range(0, len(edges), 2)
                    if edges[k + 1] > edges[k]]
            steps = sum(1 for l in plane["lines"] if l["name"] == MODULES_LINE
                        for e in l["events"]
                        if STEP_MODULE in e[0] and t0 <= e[1] < t1)
    if not any(busy):
        return None
    n = len(devices)
    spans = [(name, s, d) for name, s, d, *_ in host
             if name != devtrace.WINDOW_SPAN]
    idle = _idle_by_span(gaps, spans)
    return {"window_s": (t1 - t0) * 1e-9, "busy_s": sum(busy) / n,
            "steps": steps,
            "phase_s": {k: v / n for k, v in sorted(phase_s.items())},
            "lane_s": {k: v / n for k, v in sorted(lane_s.items())},
            "idle_s": dict(idle.most_common()),
            "unscoped_ops": [[k, v / n] for k, v in unscoped.most_common(top)]}


def ms_per_step(rec, group: str):
    """Device self time of a group of phases per traced step, ms; None
    when the record has no phases or the window held no step."""
    ph = rec.get("phases")
    if not ph or not ph["steps"]:
        return None
    return sum(ph["phase_s"].get(p, 0.0) for p in GROUPS[group]) \
        / ph["steps"] * 1e3


def select_ms_per_step(rec):
    """Output-aware page scoring and budgeted top-k selection."""
    return ms_per_step(rec, "select")


def attend_ms_per_step(rec):
    """K/V and summary writes into the pool, exact attention over kept
    pages."""
    return ms_per_step(rec, "attend")


def dense_ms_per_step(rec):
    """Embedding, projections, MLP, LM head and sampling."""
    return ms_per_step(rec, "dense")


def unscoped_ms_per_step(rec):
    """Busy device time under no phase: XLA-inserted copies and the layer
    scan's plumbing."""
    return ms_per_step(rec, "unscoped")


def _suffix(cell) -> str:
    """The cell's metric suffix (``prefill``, ``chat``, ``decode``), read
    off the accepted per-layer metrics it reports."""
    for m in cell.per_layer:
        if m["name"].startswith("device_idle_share."):
            return m["name"].split(".", 1)[1]
    return ""


def main(argv=None, root=None, platform: str = "tpu") -> int:
    """``run.py`` with the traced window reduced by phase: prints one line
    ``phases: {...}`` (this module's reduction plus the cell's
    ``*_ms_per_step`` metrics) before ``run.py``'s result line, and with
    ``--keep-trace`` writes the loaded trace there as JSON."""
    import harness
    import jax
    import run
    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--keep-trace")
    mine, rest = ap.parse_known_args(argv)
    root = root or run.ROOT
    cell = harness.Cell(root, mine.workload)

    class PhaseTracer(harness.Tracer):
        """Keeps the argument shapes of each signature of the engine's
        step, to name the trace's ops from the compiled step's HLO."""

        def __init__(self, *args):
            super().__init__(*args)
            eng = self.driver.engine
            step, self.signatures = eng._unified, {}

            def recording(*a):
                if (a[-1] is None) not in self.signatures:
                    self.signatures[a[-1] is None] = jax.tree.map(
                        lambda x: jax.ShapeDtypeStruct(
                            x.shape, x.dtype, sharding=x.sharding), a)
                return step(*a)
            eng._unified, self.step = recording, step

        def result(self):
            if self.state == "on":
                self.poll(float("inf"))
            files = sorted(pathlib.Path(self.dir).rglob("*.xplane.pb"))
            trace = load(str(files[0])) if files else {"planes": []}
            if any(p["name"].startswith("/device:") for p in trace["planes"]):
                name_ops(trace, [
                    hlo_op_names(self.step.lower(*a).compile().as_text())
                    for a in self.signatures.values()])
            if mine.keep_trace:
                pathlib.Path(mine.keep_trace).write_text(json.dumps(trace))
            ph = reduce(trace)
            line = dict(ph or {})
            suffix = _suffix(cell)
            for group in GROUPS:
                name = f"{group}_ms_per_step.{suffix}"
                line[name] = cell.reader(name)({"phases": ph})
            print("phases: " + json.dumps(line), flush=True)
            return super().result()

    tracer, harness.Tracer = harness.Tracer, PhaseTracer
    try:
        return run.main(["--workload", mine.workload] + rest, root=root,
                        platform=platform)
    finally:
        harness.Tracer = tracer


if __name__ == "__main__":
    sys.exit(main())
