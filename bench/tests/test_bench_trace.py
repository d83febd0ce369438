"""Trace to metrics: device busy time as the union of operations inside the
traced window, averaged over devices; idle gaps named after the host span
that covers them."""
from __future__ import annotations

import json

import pytest

import devtrace
from conftest import BENCH

DATA = BENCH / "tests" / "data"


def _ev(name, start_ms, dur_ms):
    return [name, start_ms * 1e6, dur_ms * 1e6]


def test_union_window_and_gaps():
    tr = {"planes": [
        {"name": "/host:CPU", "lines": [{"name": "python", "events": [
            _ev("bench.traced", 10, 100),
            _ev("bench.step", 10, 40), _ev("bench.idle", 50, 30),
            _ev("bench.step", 80, 40)]}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [_ev("jit_step", 0, 200)]},
            {"name": "XLA Ops", "events": [
                _ev("fusion.1", 5, 15),     # clipped to 10..20
                _ev("fusion.2", 15, 10),    # overlaps: union 10..25
                _ev("dot.3", 60, 5),        # inside the idle span
                _ev("fusion.1", 90, 10),
                _ev("copy.4", 105, 20)]}]},  # clipped to 105..110
    ]}
    r = devtrace.reduce(tr)
    assert r["window_s"] == pytest.approx(0.1)
    assert r["busy_s"] == pytest.approx((15 + 5 + 10 + 5) * 1e-3)
    ops = dict(r["device_ops"])
    assert ops["fusion.1"] == pytest.approx(0.02)
    assert ops["copy.4"] == pytest.approx(0.005)
    # Gaps, longest first: 25..60 ms (25 ms of it in a step span), 65..90
    # (15 ms of it idle), 100..105 (in a step).
    assert r["idle_gaps"][0] == ["bench.step", pytest.approx(0.035)]
    assert r["idle_gaps"][1] == ["bench.idle", pytest.approx(0.025)]
    assert r["idle_gaps"][2] == ["bench.step", pytest.approx(0.005)]


def test_idle_gaps_go_to_the_innermost_span():
    """The program's ``engine.*`` spans nest inside ``bench.step``; a gap
    is named after the innermost span that holds most of it."""
    tr = {"planes": [
        {"name": "/host:CPU", "lines": [{"name": "python", "events": [
            _ev("bench.traced", 0, 100), _ev("bench.step", 0, 100),
            _ev("engine.step", 1, 98), _ev("engine.inputs", 10, 30),
            _ev("engine.wait", 60, 8)]}]},
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
            _ev("fusion.1", 0, 5), _ev("fusion.2", 50, 10),
            _ev("fusion.3", 65, 35)]}]}]}
    r = devtrace.reduce(tr)
    # gaps: 5..50 (inputs 30 of it, engine.step 15), 60..65 (wait)
    assert r["idle_gaps"] == [["engine.inputs", pytest.approx(0.045)],
                              ["engine.wait", pytest.approx(0.005)]]


def test_busy_is_averaged_over_devices():
    host = {"name": "/host:CPU", "lines": [{"name": "p", "events": [
        _ev("bench.traced", 0, 10)]}]}
    dev = lambda i, dur: {"name": f"/device:TPU:{i}", "lines": [
        {"name": "XLA Ops", "events": [_ev("op", 0, dur)]}]}
    r = devtrace.reduce({"planes": [host, dev(0, 10), dev(1, 4)]})
    assert r["busy_s"] == pytest.approx(7e-3)


def test_nothing_to_read():
    assert devtrace.reduce({"planes": []}) is None
    host = {"name": "/host:CPU", "lines": [{"name": "p", "events": [
        _ev("bench.step", 0, 10)]}]}
    assert devtrace.reduce({"planes": [host]}) is None


@pytest.mark.parametrize("path", sorted(DATA.glob("trace_*.json")),
                         ids=lambda p: p.stem)
def test_recorded_trace(path):
    """A few steps of a traced run on a TPU v5 lite, kept as recorded; the
    expected reduction sits beside it."""
    rec = json.loads(path.read_text())
    want = json.loads(path.with_suffix(".expected").read_text())
    got = devtrace.reduce(rec)
    assert got["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert got["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert 0 < got["busy_s"] <= got["window_s"]
    assert [n for n, _ in got["device_ops"]] == [n for n, _ in
                                                 want["device_ops"]]
    assert len(got["idle_gaps"]) == len(want["idle_gaps"])
