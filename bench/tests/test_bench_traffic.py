"""The generator: same seed, same requests; every seed the same sizes and
gaps, in another order."""
from __future__ import annotations

import json

import numpy as np
import pytest

import traffic
from conftest import BENCH

MIXES = sorted((BENCH / "traffic").glob("*.json"))


@pytest.mark.parametrize("path", MIXES, ids=lambda p: p.stem)
def test_same_seed_same_requests(path):
    spec = json.loads(path.read_text())
    big = 2 ** 31 + 12345
    a = traffic.generate(spec, big, 10.0, 1000)
    b = traffic.generate(spec, big, 10.0, 1000)
    assert len(a) == len(b) == sum(n for n, _ in traffic.blocks(spec, 10.0))
    for x, y in zip(a, b):
        assert (x.uid, x.max_new, x.due_s) == (y.uid, y.max_new, y.due_s)
        np.testing.assert_array_equal(x.prompt, y.prompt)


@pytest.mark.parametrize("path", MIXES, ids=lambda p: p.stem)
def test_seeds_share_sizes_and_gaps(path):
    """Seeds differ in token ids only: the work is the same."""
    spec = json.loads(path.read_text())
    a = traffic.generate(spec, 1, 10.0, 1000)
    b = traffic.generate(spec, 2, 10.0, 1000)
    for key in (lambda i: len(i.prompt), lambda i: i.max_new,
                lambda i: i.due_s):
        assert list(map(key, a)) == list(map(key, b))
    assert any((x.prompt != y.prompt).any() for x, y in zip(a, b))
    if spec["kind"] == "open_loop":
        assert [i.due_s for i in a if i.due_s >= 0][0] == 0.0
        assert a[0].due_s == -spec["preroll_s"]
    lo, hi = spec["prompt"]["min"], spec["prompt"]["max"]
    assert all(lo <= len(i.prompt) <= hi for i in a)
    assert all(0 <= int(i.prompt.max()) < 1000 for i in a)


@pytest.mark.parametrize("base", [2, 3, 5])
def test_every_prefix_spreads_over_the_range(base):
    for n in (1, 7, 16, 48):
        order = traffic.spread_order(n, base)
        assert sorted(order) == list(range(n))
        for k in range(base, n + 1):
            # Any prefix of k ranks leaves no gap wider than ~base*n/k.
            gaps = np.diff(np.sort(np.r_[-1, order[:k], n]))
            assert gaps.max() <= base * n / k + 1


def test_open_loop_rate_and_quantiles():
    spec = {"kind": "open_loop", "rate_per_s": 5.0, "preroll_s": 2.0,
            "prompt": {"dist": "uniform", "min": 10, "max": 20},
            "output": {"dist": "lognormal", "median": 8, "sigma": 0.5,
                       "min": 2, "max": 40}}
    items = traffic.generate(spec, 7, 20.0, 50)
    due = np.asarray([i.due_s for i in items])
    assert np.all(np.diff(due) >= 0)
    span = due[-1] - due[0]
    assert len(items) / span == pytest.approx(5.0, rel=0.1)
    assert np.median([i.max_new for i in items]) == pytest.approx(8, abs=1)
