"""Beyond-paper extension: policy-driven sparse *decode* attention.

The paper scopes Stem to the pre-filling phase.  The same coarse-to-fine
shape extends to decoding against a long KV cache (cf. Quest), and fits
our serving stack naturally because prefill already computes the
block-pooled representations:

  * keep the anti-diagonal-pooled K-block group means and the block
    max-pooled log||V|| alongside the KV cache (tiny: stride x d + 1 floats
    per 128-token block),
  * each decode step scores cache *blocks* against the single query with
    the policy's ``BlockMetric`` (``decode_scores``), applies the policy's
    budget + selection rule to the cache (for the top-k selector: a fixed
    fraction of cache blocks, floored, with forced sink + local blocks),
    and attends exactly over the selected blocks only.

This turns decode attention from O(L) per token to O(k_avg * B) — the same
coarse-to-fine shape as Algorithm 1 with nq = 1.

Everything is vectorized over *per-sequence* cache lengths: ``cache_lens``
may be a scalar (uniform batch, the seed behaviour) or a ``(b,)`` vector
(continuous batching — every row carries its own valid prefix, lengths need
not be multiples of ``block_size``).  The pipeline is factored into three
stages shared with the paged-cache executor (``runtime/paged.py``); all of
them accept a ``SparsityPolicy``, a registered policy name, or a legacy
``StemConfig`` (converted via ``cfg.policy()``):

  ``decode_block_metric``  — policy metric of the query vs every cache block;
  ``select_decode_blocks`` — policy budget + validity + forced floors
                             (``Selector.select_decode``);
  ``attend_selected``      — exact masked attention over gathered blocks.
"""
from __future__ import annotations

import os
from typing import NamedTuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import metric as metric_lib
from repro.core import policy as policy_lib
from repro.core.selection import DecodeSelection  # noqa: F401  (re-export)

NEG_INF = -1e30
# The one shared decode sparsity default.  Every decode entry point
# (``sparse_decode_attention``, ``select_decode_blocks``,
# ``runtime.paged.paged_sparse_decode``, ``models.attention.apply_decode*``)
# reads this constant, so a caller omitting ``budget_frac`` gets the same
# behaviour on every path: **dense** (1.0) — the safe spelling, since
# forgetting the knob can cost throughput but never quality.  Sparse serving
# passes its fraction explicitly (the engine threads
# ``EngineConfig.budget_frac``).
DEFAULT_BUDGET_FRAC = 1.0
# summarize_cache() of an all-zero block yields this v_mag (log of the norm
# floor); fresh/partial pages are initialized to it so incremental appends
# reproduce the batch summary exactly.
V_MAG_FLOOR = float(np.log(1e-20))


class BlockSummary(NamedTuple):
    """Pooled per-block cache summaries (built at prefill, O(L) memory/B)."""
    k_groups: jnp.ndarray   # (b, hk, nblocks, stride, d) anti-diag group means
    v_mag: jnp.ndarray      # (b, hk, nblocks) max-pooled log ||V||


def summarize_cache(k: jnp.ndarray, v: jnp.ndarray, cfg) -> BlockSummary:
    """k, v: (b, hk, L, d) with L % block_size == 0.  ``cfg``: StemConfig,
    SparsityPolicy or policy name (block_size/stride are read off it)."""
    p = policy_lib.as_policy(cfg)
    return BlockSummary(
        k_groups=metric_lib.antidiag_pool(k, p.block_size, p.stride),
        v_mag=metric_lib.value_block_magnitude(v, p.block_size),
    )


# ---------------------------------------------------------------------------
# Stage 1: coarse metric — single query row vs all cache blocks
# ---------------------------------------------------------------------------

def decode_block_metric(q: jnp.ndarray, k_groups: jnp.ndarray,
                        v_mag: jnp.ndarray, cfg) -> jnp.ndarray:
    """Policy metric at block granularity for one decode query per sequence.

    q: (b, hq, 1, d); k_groups: (b, hk, n, stride, d); v_mag: (b, hk, n).
    Returns (b, hk, group, n) float32 — higher = more important.
    """
    return policy_lib.as_policy(cfg).decode_scores(q, k_groups, v_mag)


# ---------------------------------------------------------------------------
# Stage 2: per-row budget + static-width top-k selection
# ---------------------------------------------------------------------------

def decode_budget_bound(nblk: int, cfg, budget_frac: float) -> int:
    """Static top-k width of the policy's decode selection — the gather
    width the executors allocate (O(k_avg * B), not O(L), for budget-driven
    selectors)."""
    return policy_lib.as_policy(cfg).decode_budget_bound(nblk, budget_frac)


def select_decode_blocks(
    m: jnp.ndarray,                       # (b, hk, g, nblk) coarse metric
    cache_lens: jnp.ndarray,              # scalar or (b,) valid prefix
    cfg,
    budget_frac: float = DEFAULT_BUDGET_FRAC,
) -> DecodeSelection:
    """Policy budget + forced floors + validity, vectorized per row."""
    return policy_lib.as_policy(cfg).decode_select(
        m, cache_lens, budget_frac=budget_frac)


def debug_assert_live_rows(sel: DecodeSelection,
                           context: str = "decode selection") -> None:
    """Opt-in invariant check (``REPRO_DEBUG_DECODE=1``): every row with a
    non-empty cache must keep at least one live selected block per head —
    otherwise its attention output is a *silent zero vector* (see
    ``attend_selected``).  Normal policies cannot trip this (selectors force
    sink/local floors and budgets are floored at the forced count), so a
    failure means a broken schedule/selector composition; checking costs a
    host callback and is therefore gated behind the env var."""
    if not os.environ.get("REPRO_DEBUG_DECODE"):
        return

    has_live = sel.live.any(axis=-1)                     # (b, hk, g)
    nonempty = sel.n_valid > 0                           # (b,)

    def _check(has_live, nonempty, context=context):
        bad = np.asarray(nonempty)[:, None, None] & ~np.asarray(has_live)
        if bad.any():
            raise AssertionError(
                f"{context}: rows with a non-empty cache selected zero live "
                f"blocks at (row, kv_head, group) = "
                f"{np.argwhere(bad).tolist()}; their attention output will "
                "be a silent zero vector (schedule/selector produced a zero "
                "budget with no forced sink/local floor)")

    jax.debug.callback(_check, has_live, nonempty)


# ---------------------------------------------------------------------------
# Stage 3: exact attention over gathered blocks
# ---------------------------------------------------------------------------

def attend_selected(
    q: jnp.ndarray,            # (b, hq, 1, d)
    gk: jnp.ndarray,           # (b, hk, g, k_max, bs, d) gathered key blocks
    gv: jnp.ndarray,           # (b, hk, g, k_max, bs, dv)
    sel: DecodeSelection,
    cache_lens: jnp.ndarray,   # scalar or (b,)
    block_size: int,
) -> jnp.ndarray:
    """Masked softmax over the selected blocks only.  Returns (b, hq, 1, dv).

    **Zero-live-row contract:** a row whose selection carries no live slot
    (``sel.live`` all False — e.g. ``cache_lens == 0`` trash slots riding in
    a paged serving batch) softmaxes over an all-``NEG_INF`` score row; the
    uniform probabilities that produces are then zeroed by the ``keep``
    mask, so the row returns an **exact zero output vector** — not NaN, not
    garbage.  The fused Pallas path (``kernels/paged_attn.py``) honors the
    same contract: its accumulator never runs and the finalize step divides
    zero by the 1e-20 normalizer floor.  Rows with a *non-empty* cache must
    always have at least one live slot (selectors force sink/local floors);
    ``REPRO_DEBUG_DECODE=1`` asserts that invariant via
    ``debug_assert_live_rows``.  Pinned by
    tests/test_paged_kernel.py::TestZeroLiveRows on both paths.
    """
    debug_assert_live_rows(sel, context="attend_selected")
    b, hq, _, d = q.shape
    hk = gk.shape[1]
    group = hq // hk
    bs = block_size
    cache_lens = jnp.broadcast_to(jnp.asarray(cache_lens, jnp.int32), (b,))
    qg = q.reshape(b, hk, group, 1, d).astype(jnp.float32)
    s = jnp.einsum("bhgqd,bhgnkd->bhgqnk", qg, gk.astype(jnp.float32))
    s = s * (d ** -0.5)                                    # (b,hk,g,1,kmax,bs)
    tok_pos = sel.indices[..., None] * bs + jnp.arange(bs)  # (b,hk,g,kmax,bs)
    keep = (tok_pos < cache_lens[:, None, None, None, None]) & sel.live[..., None]
    s = jnp.where(keep[:, :, :, None], s, NEG_INF)
    p = jax.nn.softmax(s.reshape(b, hk, group, 1, -1), axis=-1).reshape(s.shape)
    p = jnp.where(keep[:, :, :, None], p, 0.0)
    o = jnp.einsum("bhgqnk,bhgnkd->bhgqd", p, gv.astype(jnp.float32))
    return o.reshape(b, hq, 1, gv.shape[-1]).astype(q.dtype)


def attend_latent(
    qt: jnp.ndarray,           # (b, h, 1, D) absorbed queries, D latent width
    pages: jnp.ndarray,        # (b, k_max, bs, D) kept latent pages, one set
    sel: DecodeSelection,      # indices / live (b, 1, 1, k_max)
    cache_lens: jnp.ndarray,   # (b,)
    block_size: int,
    rank: int,
) -> jnp.ndarray:
    """Absorbed MLA attention over one shared set of kept latent pages:
    logits ``qt . [c, k_rope] / sqrt(D)``, PV in the latent (the first
    ``rank`` entries, ``c``).  Both products take the pages in their stored
    dtype with float32 accumulation, so no page is copied per head or cast.
    Zero live slots give a zero row (``attend_selected``'s contract).
    Returns (b, h, 1, rank) float32."""
    b, h, _, d = qt.shape
    bs = block_size
    lens = jnp.broadcast_to(jnp.asarray(cache_lens, jnp.int32), (b,))
    s = jnp.einsum("bhd,bnkd->bhnk", qt[:, :, 0].astype(pages.dtype), pages,
                   preferred_element_type=jnp.float32) * (d ** -0.5)
    idx, live = sel.indices[:, 0, 0], sel.live[:, 0, 0]     # (b, k_max)
    tok_pos = idx[..., None] * bs + jnp.arange(bs)           # (b, k_max, bs)
    keep = ((tok_pos < lens[:, None, None]) & live[..., None])[:, None]
    s = jnp.where(keep, s, NEG_INF)
    p = jax.nn.softmax(s.reshape(b, h, -1), axis=-1).reshape(s.shape)
    p = jnp.where(keep, p, 0.0)
    o = jnp.einsum("bhnk,bnkr->bhr", p.astype(pages.dtype), pages[..., :rank],
                   preferred_element_type=jnp.float32)
    return o[:, :, None]


def sparse_decode_attention(
    q: jnp.ndarray,           # (b, hq, 1, d) — one new query token
    cache_k: jnp.ndarray,     # (b, hk, L, d)
    cache_v: jnp.ndarray,
    summary: BlockSummary,
    cache_lens: Union[jnp.ndarray, int],   # scalar or (b,) valid prefixes
    cfg,
    budget_frac: float = DEFAULT_BUDGET_FRAC,
) -> jnp.ndarray:
    """Policy block selection + exact attention over selected cache blocks.

    ``cache_lens`` is per-sequence: a scalar applies one length to every
    row; a ``(b,)`` vector gives each row its own valid prefix (lengths not
    multiples of ``block_size`` are handled by token-level masking of the
    partial block).  At ``budget_frac=1.0`` every valid block is selected,
    so the result equals dense decode over each row's prefix exactly.
    """
    policy = policy_lib.as_policy(cfg)
    b, hq, _, d = q.shape
    hk = cache_k.shape[1]
    bs = policy.block_size
    nblk = cache_k.shape[2] // bs

    m = policy.decode_scores(q, summary.k_groups, summary.v_mag)
    sel = policy.decode_select(m, cache_lens, budget_frac=budget_frac)

    dv = cache_v.shape[-1]
    kb = cache_k.reshape(b, hk, nblk, bs, d)
    vb = cache_v.reshape(b, hk, nblk, bs, dv)
    # gather along the block axis (3 after the g broadcast dim is inserted)
    gk = jnp.take_along_axis(kb[:, :, None], sel.indices[..., None, None], axis=3)
    gv = jnp.take_along_axis(vb[:, :, None], sel.indices[..., None, None], axis=3)
    return attend_selected(q, gk, gv, sel, cache_lens, bs)
