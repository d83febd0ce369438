"""The one request generator: a traffic file's parameters plus a seed give
the requests of a run.

Every seed gets the same prompt lengths, output lengths and
inter-arrival gaps, in the same order: stratified quantiles of the stated
distributions, ordered so that every prefix spreads over the whole range
(a van der Corput sequence, in a different base for each quantity so that
they pair without correlation).  The seed draws the token ids.  So every
run asks for the same work: a window that reaches only the first few
requests of a backlog sees the same sizes whatever the seed, and an open
loop draws the pre-roll and the window as two such sets.

Kinds:
* ``offline``: ``requests`` requests, all due when the window opens (a
  backlog).  ``fill_slots`` admits the first ``slots`` of them during
  set-up and runs them until every one has left prefill, so the window
  opens in steady decode.
* ``open_loop``: Poisson arrivals at ``rate_per_s``, due on a wall-clock
  schedule that starts ``preroll_s`` before the window opens.

Distributions: ``{"dist": "uniform" | "loguniform", "min", "max"}`` or
``{"dist": "lognormal", "median", "sigma", "min", "max"}``.
"""
from __future__ import annotations

import dataclasses
import statistics

import numpy as np


@dataclasses.dataclass
class Item:
    uid: int
    prompt: np.ndarray       # int32 token ids
    max_new: int
    due_s: float             # seconds after the window opens (< 0: pre-roll)


def quantiles(spec: dict, n: int) -> np.ndarray:
    """``n`` stratified draws (the (i + 1/2)/n quantiles) as ints."""
    u = (np.arange(n) + 0.5) / n
    lo, hi = spec["min"], spec["max"]
    kind = spec["dist"]
    if kind == "uniform":
        x = lo + u * (hi - lo)
    elif kind == "loguniform":
        x = lo * (hi / lo) ** u
    elif kind == "lognormal":
        z = np.asarray([statistics.NormalDist().inv_cdf(p) for p in u])
        x = np.clip(spec["median"] * np.exp(spec["sigma"] * z), lo, hi)
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    return np.clip(np.round(x), lo, hi).astype(np.int64)


def spread_order(n: int, base: int) -> np.ndarray:
    """Ranks 0..n-1 in the order of the van der Corput sequence in
    ``base``: each prefix takes quantiles from across the whole range."""
    def vdc(i):
        x, f = 0.0, 1.0 / base
        while i:
            x, i, f = x + (i % base) * f, i // base, f / base
        return x
    return np.argsort(np.argsort([vdc(i) for i in range(n)]))


def blocks(spec: dict, seconds: float) -> list:
    """[(requests, first due second)] of each stratified set."""
    if spec["kind"] == "offline":
        return [(int(spec["requests"]), 0.0)]
    if spec["kind"] == "open_loop":
        rate, pre = spec["rate_per_s"], spec.get("preroll_s", 0.0)
        out = [(int(round(rate * pre)), -pre)] if pre else []
        return out + [(max(1, int(round(rate * seconds))), 0.0)]
    raise ValueError(f"unknown traffic kind {spec['kind']!r}")


def generate(spec: dict, seed: int, seconds: float, vocab: int) -> list:
    rng = np.random.default_rng(int(seed))
    items = []
    for n, start in blocks(spec, seconds):
        prompts = quantiles(spec["prompt"], n)[spread_order(n, 2)]
        outputs = quantiles(spec["output"], n)[spread_order(n, 3)]
        if spec["kind"] == "offline":
            due = np.zeros(n)
        else:
            u = (np.arange(n) + 0.5) / n
            gaps = (-np.log1p(-u) / spec["rate_per_s"])[spread_order(n, 5)]
            due = start + np.cumsum(gaps) - gaps[0]
        items += [Item(uid=len(items) + i,
                       prompt=rng.integers(0, vocab, int(p), dtype=np.int32),
                       max_new=int(o), due_s=float(d))
                  for i, (p, o, d) in enumerate(zip(prompts, outputs, due))]
    return items
