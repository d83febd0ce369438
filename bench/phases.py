"""Split a traced window's device time by the program's own phase scopes,
and its device idle time by the program's own host spans.

The unified step names its device phases with ``jax.named_scope``
(``stem.qkv``, ``stem.score``, ``stem.select``, ``stem.attend``, ...; see
``models/transformer.paged_mixed_step``) and the engine writes
``engine.*`` host spans (``runtime/engine.py``) on the profiler's clock.
Each event of a device plane's ``XLA Ops`` line is one HLO instruction of
the running program, and the innermost ``stem.*`` scope in that
instruction's op_name, other than a lane, owns it, whatever its name; its
group (``GROUPS``) is that of its innermost scope that has one.  A TPU
trace names the instruction but carries no op_name (TPU v5 lite, jax
0.9.0), so ``name_ops`` takes each op's op_name from the compiled step's
HLO text.

``harness.Tracer`` loads the trace (``devtrace.load``), names its ops and
stores ``reduce`` in every traced run's record as ``phases``; ``reduce``
works on plain data only, so a CPU test checks it on a small recorded
trace.  Run as a script it is ``run.py`` with one more line before the
result: ``phases:`` and the reduction of the traced window (``--trace 1``).

    python3 bench/phases.py --workload <cell> --seed <n> --seconds <s> --trace 1
"""
from __future__ import annotations

import argparse
import bisect
import collections
import json
import re
import sys

import devtrace

STEP_MODULE = "unified_step"      # the engine's jitted step, by its name
OP_NAME = re.compile(r'op_name="([^"]*)"')
HLO_LINE = re.compile(r"\s*(?:ROOT )?%?([\w.-]+) = ")
SCOPE = "stem."
UNSCOPED = "unscoped"

# Phases of the unified step by the per-layer metric that reads them.
GROUPS = {
    "select": ("stem.score", "stem.select"),
    "attend": ("stem.kv_write", "stem.attend"),
    "dense": ("stem.embed", "stem.qkv", "stem.o_proj", "stem.mlp",
              "stem.head", "stem.sample"),
    "unscoped": (UNSCOPED,),
}
GROUP_OF = {p: g for g, ps in GROUPS.items() for p in ps if p != UNSCOPED}
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
        mods = [e for l in plane["lines"] if l["name"] == devtrace.MODULES_LINE
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


def phase_of(op_name: str) -> str:
    """The innermost ``stem.*`` scope of an op_name that is not a lane,
    else ``unscoped``."""
    for part in reversed(op_name.split("/")):
        if part.startswith(SCOPE) and part not in LANES:
            return part
    return UNSCOPED


def group_of(op_name: str) -> str:
    """The group of the innermost scope of an op_name that has one, else
    ``unscoped``."""
    for part in reversed(op_name.split("/")):
        if part in GROUP_OF:
            return GROUP_OF[part]
    return UNSCOPED


def lane_of(op_name: str) -> str:
    for part in op_name.split("/"):
        if part in LANES:
            return part
    return UNSCOPED


def reduce(trace: dict, top: int = 10):
    """Returns None when the trace holds no window or no device operation,
    else {"window_s", "busy_s", "steps", "phase_s", "group_s", "lane_s",
    "idle_s", "unscoped_ops"}: device self seconds by phase, by group and
    by lane, averaged over the device planes; the window's step count
    (executions of the unified step that start inside it, on the first
    device); idle seconds of the first device by host span; the top
    unscoped ops by self time.
    """
    host = [e for p in trace["planes"] if not p["name"].startswith("/device:")
            for l in p["lines"] for e in l["events"]]
    window = [e for e in host if e[0] == devtrace.WINDOW_SPAN]
    devices = [p for p in trace["planes"] if p["name"].startswith("/device:")]
    if not window or not devices:
        return None
    t0 = window[0][1]
    t1 = t0 + window[0][2]
    phase_s, group_s, lane_s, unscoped = (collections.Counter()
                                          for _ in range(4))
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
            group = group_of(op)
            phase_s[phase_of(op)] += own * 1e-9
            group_s[group] += own * 1e-9
            lane_s[lane_of(op)] += own * 1e-9
            if group == UNSCOPED:
                unscoped[name] += own * 1e-9
        merged = devtrace._union([(s, s + d) for _, s, d, _ in clipped])
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        if i == 0:
            edges = [t0] + [x for m in merged for x in m] + [t1]
            gaps = [(edges[k], edges[k + 1]) for k in range(0, len(edges), 2)
                    if edges[k + 1] > edges[k]]
            steps = sum(1 for l in plane["lines"]
                        if l["name"] == devtrace.MODULES_LINE
                        for e in l["events"]
                        if STEP_MODULE in e[0] and t0 <= e[1] < t1)
    if not any(busy):
        return None
    n = len(devices)
    spans = [(name, s, d) for name, s, d, *_ in host
             if name != devtrace.WINDOW_SPAN]
    idle = devtrace.idle_by_span(gaps, spans)
    return {"window_s": (t1 - t0) * 1e-9, "busy_s": sum(busy) / n,
            "steps": steps,
            "phase_s": {k: v / n for k, v in sorted(phase_s.items())},
            "group_s": {k: v / n for k, v in sorted(group_s.items())},
            "lane_s": {k: v / n for k, v in sorted(lane_s.items())},
            "idle_s": dict(idle.most_common()),
            "unscoped_ops": [[k, v / n] for k, v in unscoped.most_common(top)]}


def ms_per_step(rec, group: str):
    """Device self time of a group of phases per traced step, ms; None
    when the record has no phases or the window held no step."""
    ph = rec.get("phases")
    if not ph or not ph["steps"]:
        return None
    return ph["group_s"].get(group, 0.0) / ph["steps"] * 1e3


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
    """Busy device time under no grouped phase: ops that XLA makes without
    an op_name (the f32 casts of gathered pages)."""
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
    ``phases: {...}`` (the record's ``phases`` plus the cell's
    ``*_ms_per_step`` metrics) before ``run.py``'s result line, and with
    ``--keep-trace`` writes the loaded trace there as JSON."""
    import harness
    import run
    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--keep-trace")
    mine, rest = ap.parse_known_args(argv)
    args = run.parse(rest)
    root = root or run.ROOT
    cell = harness.Cell(root, args.workload)
    jax = run.init_jax(root, cell, platform)
    if jax is None:
        return 3
    result, rec = run.measure(jax, cell, args, keep_trace=mine.keep_trace)
    line = dict(rec["phases"] or {})
    suffix = _suffix(cell)
    for group in GROUPS:
        name = f"{group}_ms_per_step.{suffix}"
        line[name] = cell.reader(name)(rec)
    print("phases: " + json.dumps(line), flush=True)
    run.emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
