"""The benchmark's own copy of Stem's serving budget rules.

Two rules decide how many key pages a query keeps, and each family's
operation count and plain reference (``families/<family>.py``, with
``stem_reference.py``) read them from here, never from the program:

* prefill (Token Position-Decay, paper Eq. 3 at block granularity): query
  block row ``i`` of a prompt padded to ``nk`` pages keeps
  ``min(max(floor(k0 - k0 (1 - mu) i / nk), 1, min_budget), i + 1)`` pages,
  with ``k0 = max(1, int(frac * nk))`` and ``frac`` 0.2 up to 16k keys and
  0.1 above (paper section 3.1);
* decode: a query whose cache holds ``n`` valid pages keeps
  ``max(min_budget, min(n, sink + local), floor(n * budget_frac))`` pages.

Both keep the sink page(s) and the page of the query's own position first;
the rest are the best-scoring causal pages.  ``tests/test_bench_budgets.py``
pins these functions to ``repro.core.policy`` for every cell's settings.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class StemRule:
    page: int = 128          # page = Stem block, tokens
    stride: int = 4          # anti-diagonal pooling stride
    beta: float = 0.2        # value-magnitude weight of the output-aware metric
    mu: float = 0.7          # decay ratio of the prefill budget
    min_budget: int = 2      # per-row floor, pages
    sink: int = 1            # leading pages always kept
    local: int = 1           # pages at the query's own position always kept
    budget_frac: float = 0.5  # decode: share of valid pages kept

    @classmethod
    def from_config(cls, serving: dict) -> "StemRule":
        keys = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in serving.items() if k in keys})

    def k_start(self, kv_len: int) -> int:
        frac = 0.2 if kv_len <= 16384 else 0.1
        return max(1, int(frac * (-(-kv_len // self.page))))

    def prefill_budgets(self, padded_len: int) -> np.ndarray:
        """(nk,) pages kept by each query block row of a prompt padded to
        ``padded_len`` tokens (a page multiple)."""
        nk = -(-padded_len // self.page)
        k0 = self.k_start(padded_len)
        i = np.arange(nk, dtype=np.float64)
        raw = np.floor(k0 - (k0 * (1.0 - self.mu) / nk) * i)
        raw = np.maximum(np.maximum(raw, 1.0), float(self.min_budget))
        return np.minimum(raw, i + 1).astype(np.int32)

    def prefill_bound(self, max_prompt: int) -> int:
        """The most pages any prefill row keeps, over every prompt length up
        to ``max_prompt``."""
        pages = -(-max_prompt // self.page)
        return max(int(self.prefill_budgets(n * self.page).max())
                   for n in range(1, pages + 1))

    def decode_budget(self, n_valid):
        """Pages kept by a decode query whose cache spans ``n_valid`` pages
        (array or int)."""
        n = np.asarray(n_valid, np.int64)
        forced = np.minimum(n, self.sink + self.local)
        kept = np.maximum(np.maximum(self.min_budget, forced),
                          np.floor(n * self.budget_frac).astype(np.int64))
        return np.minimum(kept, n)
