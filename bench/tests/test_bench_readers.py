"""End-to-end readers on a record made under a fake clock: TTFT from the
due time, censoring at the close, inter-token gaps inside the window."""
from __future__ import annotations

import pytest

import readers


def _rec(requests, steps=()):
    return {"open": 100.0, "close": 110.0, "setup_s": 7.5,
            "requests": requests, "steps": list(steps)}


def test_ttft_counts_from_due_time_and_censors_at_close():
    reqs = {
        0: {"due": 99.0, "times": [100.5]},          # due before the open
        1: {"due": 101.0, "times": [101.2, 101.3]},  # 0.2 s
        2: {"due": 102.0, "times": [104.0]},         # 2.0 s
        3: {"due": 108.0, "times": []},              # no token: 2.0 s
        4: {"due": 109.0, "times": [111.0]},         # after close: 1.0 s
    }
    waits = sorted([0.2, 2.0, 2.0, 1.0])
    rank = 0.95 * (len(waits) - 1)
    lo = int(rank)
    want = waits[lo] + (waits[lo + 1] - waits[lo]) * (rank - lo)
    assert readers.ttft_ms(_rec(reqs), 95) == pytest.approx(want * 1e3)
    assert readers.ttft_ms(_rec(reqs), 50) == pytest.approx(1.5e3)
    assert readers.ttft_mean_ms(_rec(reqs)) == pytest.approx(1.3e3)


def test_itl_keeps_gaps_inside_the_window():
    reqs = {0: {"due": 90.0, "times": [99.0, 100.5, 100.7, 109.9, 110.5]},
            1: {"due": 100.0, "times": [101.0, 101.1]}}
    gaps = [0.2, 109.9 - 100.7, 0.1]
    got = readers.itl_p95_ms(_rec(reqs))
    assert got == pytest.approx(1e3 * sorted(gaps)[1] + 1e3 * 0.9 * (
        sorted(gaps)[2] - sorted(gaps)[1]))


def test_rates_cover_the_whole_window():
    steps = [{"t0": 100.0, "t1": 100.1, "decode": [5, 9],
              "chunks": [[300, 256, 256, True]]},
             {"t0": 100.1, "t1": 100.2, "decode": [6, 10, 301], "chunks": []}]
    rec = _rec({}, steps)
    assert readers.prompt_tokens_per_s(rec) == pytest.approx(44 / 10.0)
    assert readers.output_tokens_per_s(rec) == pytest.approx(6 / 10.0)
    assert readers.setup_s(rec) == 7.5


def test_readers_return_nothing_without_data():
    rec = _rec({}, [])
    rec["stats"] = {"step_calls": 0, "sync_wait_s": 0.0}
    assert readers.ttft_mean_ms(rec) is None
    assert readers.itl_p95_ms(rec) is None
    assert readers.host_ms_per_step(rec) is None
    assert readers.device_idle_share(rec) is None
    assert readers.mfu(rec) is None
