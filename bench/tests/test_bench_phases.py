"""Trace to phases: device self time by the program's ``stem.*`` scope, the
window's steps, and device idle time charged to the innermost host span."""
from __future__ import annotations

import gzip
import json

import pytest

import conftest
import harness
import phases
import run

DATA = conftest.BENCH / "tests" / "data"
STEP = "jit(unified_step)/while/body/closed_call"


def _ev(name, start_ms, dur_ms, op=""):
    return [name, start_ms * 1e6, dur_ms * 1e6, op]


def _trace():
    """A 100 ms window holding two steps of the unified step and one of
    another program; the host sits in engine spans between them."""
    host = [
        _ev("bench.traced", 0, 100),
        _ev("bench.step", 0, 50), _ev("engine.step", 1, 48),
        _ev("engine.dispatch", 2, 2), _ev("engine.wait", 4, 36),
        _ev("engine.emit", 40, 6),
        _ev("bench.step", 50, 50), _ev("engine.step", 51, 48),
        _ev("engine.inputs", 51, 3), _ev("engine.wait", 54, 36),
    ]
    ops = [
        # step 1: 4..38 ms
        _ev("fusion.1", 4, 4, "jit(unified_step)/stem.embed/gather"),
        _ev("while.2", 8, 28, "jit(unified_step)/while"),
        _ev("convert.3", 8, 10,
            f"{STEP}/stem.decode_lane/stem.attend/convert_element_type"),
        _ev("fusion.4", 18, 6,
            f"{STEP}/stem.decode_lane/stem.score/dot_general"),
        _ev("copy.5", 24, 8, ""),
        _ev("custom-call.6", 32, 2,
            f"{STEP}/stem.chunk_lane/stem.select/stem_x/pallas_call"),
        _ev("fusion.7", 36, 2, "jit(unified_step)/stem.head/dot_general"),
        # step 2: 55..85 ms; a third starts at 96 ms, clipped at the close
        _ev("fusion.1", 55, 10, "jit(unified_step)/stem.embed/gather"),
        _ev("fusion.8", 65, 20,
            f"{STEP}/stem.decode_lane/stem.o_proj/add"),
        _ev("fusion.9", 96, 8, "jit(unified_step)/stem.mlp/dot"),  # clipped
    ]
    modules = [_ev("jit_unified_step(4)", 4, 34), _ev("jit_reset(9)", 40, 1),
               _ev("jit_unified_step(4)", 55, 30),
               _ev("jit_unified_step(4)", 96, 8)]
    return {"planes": [
        {"name": "/host:CPU", "lines": [{"name": "python", "events": host}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": modules},
            {"name": "XLA Ops", "events": ops}]}]}


def test_phase_of_takes_the_innermost_phase():
    assert phases.phase_of(f"{STEP}/stem.chunk_lane/stem.attend/dot") \
        == "stem.attend"
    assert phases.phase_of(f"{STEP}/stem.decode_lane/add") == "unscoped"
    assert phases.phase_of("") == "unscoped"
    assert phases.lane_of(f"{STEP}/stem.chunk_lane/stem.qkv/dot") \
        == "stem.chunk_lane"
    # a scope no group names is keyed by its own name, and its ops count
    # in the group of the innermost scope around it that has one
    moe = f"{STEP}/stem.decode_lane/stem.mlp/stem.moe/dot"
    assert phases.phase_of(moe) == "stem.moe"
    assert phases.group_of(moe) == "dense"
    assert phases.group_of(f"{STEP}/stem.new/add") == "unscoped"


def test_self_time_by_phase_steps_and_idle_spans():
    r = phases.reduce(_trace())
    assert r["window_s"] == pytest.approx(0.1)
    assert r["steps"] == 3
    ph = r["phase_s"]
    assert ph["stem.embed"] == pytest.approx(0.014)
    assert ph["stem.attend"] == pytest.approx(0.010)
    assert ph["stem.score"] == pytest.approx(0.006)
    assert ph["stem.select"] == pytest.approx(0.002)
    assert ph["stem.o_proj"] == pytest.approx(0.020)
    assert ph["stem.mlp"] == pytest.approx(0.004)
    assert ph["stem.head"] == pytest.approx(0.002)
    # the loop's own 2 ms (34..36 holds nothing of its body) and the copy
    assert ph["unscoped"] == pytest.approx(0.002 + 0.008)
    assert sum(ph.values()) == pytest.approx(r["busy_s"])
    assert r["busy_s"] == pytest.approx(0.034 + 0.030 + 0.004)
    assert r["lane_s"]["stem.chunk_lane"] == pytest.approx(0.002)
    assert dict(r["unscoped_ops"]) == pytest.approx(
        {"copy.5": 0.008, "while.2": 0.002})
    # idle 0..4: bench.step (0..1), engine.step (1..2), dispatch (2..4);
    # 38..55: wait (38..40), emit (40..46), engine.step (46..49),
    # bench.step (49..51), inputs (51..54), wait (54..55); 85..96: wait
    # (85..90), engine.step (90..96)
    idle = r["idle_s"]
    assert idle["engine.wait"] == pytest.approx(0.002 + 0.001 + 0.005)
    assert idle["engine.emit"] == pytest.approx(0.006)
    assert idle["engine.dispatch"] == pytest.approx(0.002)
    assert idle["engine.inputs"] == pytest.approx(0.003)
    assert idle["engine.step"] == pytest.approx(0.001 + 0.003 + 0.006)
    assert idle["bench.step"] == pytest.approx(0.001 + 0.002)
    assert sum(idle.values()) == pytest.approx(r["window_s"] - r["busy_s"])


def test_idle_outside_every_span_is_none():
    tr = _trace()
    tr["planes"][0]["lines"][0]["events"] = [_ev("bench.traced", 0, 100)]
    r = phases.reduce(tr)
    assert r["idle_s"] == {"none": pytest.approx(0.1 - r["busy_s"])}


def test_per_step_metrics_sum_to_busy_per_step():
    r = phases.reduce(_trace())
    rec = {"phases": r}
    got = {g: phases.ms_per_step(rec, g) for g in phases.GROUPS}
    assert got["select"] == pytest.approx(8 / 3)
    assert got["attend"] == pytest.approx(10 / 3)
    assert got["unscoped"] == pytest.approx(10 / 3)
    assert sum(got.values()) == pytest.approx(r["busy_s"] / r["steps"] * 1e3)


def test_op_names_from_compiled_hlo():
    """Each op of a module execution takes its op_name from the program
    whose instruction names cover most of that execution's ops."""
    mixed = phases.hlo_op_names(
        '  %fusion.1 = f32[2]{0} fusion(%a), metadata={op_name="m/stem.qkv/dot"}\n'
        '  ROOT %copy.2 = f32[2]{0} copy(%fusion.1), metadata={op_name="m/x"}\n'
        '  %fusion.9 = f32[2]{0} fusion(%a), metadata={op_name="m/stem.mlp/dot"}')
    decode = phases.hlo_op_names(
        '  %fusion.1 = f32[2]{0} fusion(%a), metadata={op_name="d/stem.head/dot"}')
    assert mixed == {"fusion.1": "m/stem.qkv/dot", "copy.2": "m/x",
                     "fusion.9": "m/stem.mlp/dot"}
    tr = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [_ev("jit_unified_step(1)", 0, 10),
                                           _ev("jit_unified_step(2)", 20, 10)]},
        {"name": "XLA Ops", "events": [
            _ev("fusion.1", 1, 2), _ev("fusion.9", 3, 2),     # mixed
            _ev("fusion.1", 21, 2, "kept")]}]}]}             # decode-only
    phases.name_ops(tr, [decode, mixed])
    ops = tr["planes"][0]["lines"][1]["events"]
    assert [e[3] for e in ops] == ["m/stem.qkv/dot", "m/stem.mlp/dot", "kept"]


def test_nothing_to_read():
    assert phases.reduce({"planes": []}) is None
    tr = _trace()
    tr["planes"] = tr["planes"][:1]
    assert phases.reduce(tr) is None
    assert phases.ms_per_step({}, "select") is None
    assert phases.ms_per_step({"phases": {"steps": 0}}, "select") is None


NEW_METRICS = [f"{g}_ms_per_step.{s}" for g in phases.GROUPS
               for s in ("prefill", "chat", "decode")] + ["queue_wait_ms.chat"]


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_readers_are_found_and_read_nothing_without_data(name):
    """Each new reader is a file found by name, and a record of a program
    without scopes or counters (no ``phases``, stats without
    ``admissions``) reads None rather than raising."""
    read = harness.Cell(conftest.REPO, "qwen3-0.6b.chat").reader(name)
    assert read({"stats": {"step_calls": 3}, "trace": None}) is None


def test_queue_wait_reads_the_engine_counters():
    read = harness.Cell(conftest.REPO, "qwen3-0.6b.chat").reader(
        "queue_wait_ms.chat")
    assert read({"stats": {"admissions": 4, "queue_wait_s": 0.2}}) \
        == pytest.approx(50.0)


@pytest.mark.parametrize("path", sorted(DATA.glob("phases_*.json.gz")),
                         ids=lambda p: p.name.split(".")[0])
def test_recorded_trace(path):
    """Three steps of a traced qwen1.5-4b.decode run on a TPU v5 lite, as
    ``phases.load`` read them, with each op's op_name named from the
    compiled step; the expected reduction sits beside it."""
    with gzip.open(path, "rt") as f:
        got = phases.reduce(json.load(f))
    want = json.loads(path.with_suffix("").with_suffix(".expected")
                      .read_text())
    assert got["steps"] == want["steps"] > 0
    assert got["phase_s"] == pytest.approx(want["phase_s"], rel=1e-9)
    assert got["idle_s"] == pytest.approx(want["idle_s"], rel=1e-9)
    assert sum(got["phase_s"].values()) == pytest.approx(got["busy_s"],
                                                         rel=1e-6)
    assert set(want["phase_s"]) - {"unscoped"}, "no op carried a phase"
    idle = got["window_s"] - got["busy_s"]
    assert got["idle_s"].get("none", 0.0) < 0.1 * idle


def test_nested_new_scope_keeps_the_group_sums():
    """Every op of the recorded trace under ``stem.mlp`` gains an inner
    scope no group names: ``phase_s`` keys it by that name, and each
    group's sum stays as recorded."""
    path = DATA / "phases_v5lite_decode4b.json.gz"
    with gzip.open(path, "rt") as f:
        trace = json.load(f)
    nested = 0
    for plane in trace["planes"]:
        for line in plane["lines"]:
            for e in line["events"]:
                if len(e) > 3 and e[3].split("/")[-2:-1] == ["stem.mlp"]:
                    e[3] = e[3].replace("stem.mlp/", "stem.mlp/stem.moe/")
                    nested += 1
    assert nested
    got = phases.reduce(trace)
    want = json.loads(path.with_suffix("").with_suffix(".expected")
                      .read_text())["phase_s"]
    assert got["phase_s"]["stem.moe"] > 0
    assert got["phase_s"].get("stem.mlp", 0.0) < want["stem.mlp"]
    sums = {g: sum(want.get(p, 0.0) for p in ps)
            for g, ps in phases.GROUPS.items()}
    assert got["group_s"] == pytest.approx(sums, rel=1e-9)


def test_script_prints_phases_before_the_result(monkeypatch, capsys,
                                                tiny_root):
    """The script is ``run.py`` plus one ``phases:`` line; on the CPU the
    trace has no device plane, so the reduction and its metrics are
    empty, and the result line is ``run.py``'s own."""
    monkeypatch.setattr(run, "init_jax", conftest.cpu_jax)
    rc = phases.main(["--workload", "tiny.tinychat", "--seed", "31",
                      "--seconds", "2", "--trace", "1"], root=tiny_root,
                     platform="cpu")
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    lines = [o for o in out[:-1] if o.startswith("phases: ")]
    assert len(lines) == 1
    line = json.loads(lines[0][len("phases: "):])
    assert sorted(k.split(".")[0] for k in line) == sorted(
        f"{g}_ms_per_step" for g in phases.GROUPS)
    assert set(line.values()) == {None}
    res = json.loads(out[-1])
    assert list(res)[-1] == "checks" and res["correct"] is True
    assert harness.Tracer is not None and harness.Tracer.__name__ == "Tracer"
