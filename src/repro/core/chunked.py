"""Chunked sparse prefill over the paged Stem KV cache.

The serving engine used to prefill each prompt in one monolithic pass —
one jitted trace per padded prompt length, stalling every in-flight decode
slot until it finished.  This module is the core of the unified alternative:
the prompt is processed in fixed-size chunks ``[t0, t0 + C)`` that ride in
the same batched step as decode tokens, and each chunk's queries run the
policy's full coarse-to-fine pipeline against the page pool:

  1. **metric** — the chunk's queries are anti-diagonal-pooled per query
     block (block-aligned, so the group means equal one-shot pooling) and
     scored against every visible page's stored summaries
     (``PagePool.kg`` / ``PagePool.vm``) via ``policy.chunk_scores``.  The
     in-chunk blocks are scored the same way: the chunk's own K/V pages are
     written *before* attention, so "history" and "current chunk" pages are
     indistinguishable to the metric — exactly the one-shot geometry.
  2. **schedule** — per-row block budgets are evaluated at **absolute**
     query-block rows of the *full* prompt (the paper's position-decay rule
     keyed to absolute positions), not chunk-relative ones: row ``i`` of
     chunk ``c`` gets ``prefill_budgets(padded_len)[t0/B + i]``.  Budgets
     stay static numpy per request and enter the trace as data
     (``chunk_budget_rows``), so one fixed-shape trace serves every prompt
     length and every chunk size — including unaligned final chunks.
  3. **selection** — top-k with forced sink/local floors at the absolute
     diagonal, mirroring ``selection.select_blocks`` bit-for-bit on the
     shared candidates (the chunked top-k runs at width ``max_pages``; the
     extra causally-masked candidates sort last and never go live).
  4. **execution** — only the selected pages are gathered from the pool and
     attended exactly, with token-level causal masking at absolute
     positions (exact on the diagonal block).

Because every stage evaluates at absolute positions, chunked prefill is
selection-equivalent to one-shot prefill for any chunk size with
``C % block_size == 0`` (``tests/test_chunked.py`` pins logits to <=1e-4
fp32 across policies, GQA groups, and aligned/unaligned prompt lengths).

Only budget-driven selectors are supported (``validate_chunked_policy``):
threshold selectors (cumulative-mass) have data-dependent budgets that
cannot be sliced per chunk on the host.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import policy as policy_lib
from repro.core.selection import FORCE_BONUS

NEG_INF = -1e30


class ChunkSelection(NamedTuple):
    """Per-query-block-row page selection for one prefill chunk.

    indices: (b, hq, nc, k_max) int32 *logical* block ids (page-table slot
      order); dead slots point at block 0 and are masked by ``live``.
    live: (b, hq, nc, k_max) bool — slot carries a selected, in-budget,
      causally admissible block.
    """

    indices: jnp.ndarray
    live: jnp.ndarray


def validate_chunked_policy(policy) -> None:
    """Fail fast (clear message, outside jit) for policies chunked prefill
    cannot serve: threshold selectors and metrics without ``chunk_scores``."""
    policy = policy_lib.as_policy(policy)
    if not getattr(policy.selector, "budget_driven", False):
        raise NotImplementedError(
            f"chunked prefill needs a budget-driven selector; "
            f"{type(policy.selector).__name__} is threshold-based — run the "
            "engine with monolithic_prefill=True for this policy")
    if getattr(policy.metric, "chunk_scores", None) is None:
        raise NotImplementedError(
            f"metric {type(policy.metric).__name__} lacks chunk_scores — "
            "required for chunked prefill")


# ---------------------------------------------------------------------------
# Host-side schedule slicing (static numpy, fed to the trace as data)
# ---------------------------------------------------------------------------

def chunk_budget_rows(policy, padded_len: int, chunk_start: int,
                      n_rows: int) -> np.ndarray:
    """TPD (or any schedule's) budgets for the chunk's absolute query-block
    rows: the one-shot ``prefill_budgets(padded_len)`` vector sliced at
    ``chunk_start / block_size``, zero-padded past the prompt (rows beyond
    the prompt carry budget 0 and never go live).  int32 numpy, (n_rows,).
    """
    policy = policy_lib.as_policy(policy)
    full = policy.prefill_budgets(padded_len)
    j0 = chunk_start // policy.block_size
    out = np.zeros((n_rows,), np.int32)
    rows = full[j0:j0 + n_rows]
    out[:len(rows)] = rows
    return out


# ---------------------------------------------------------------------------
# Selection at absolute query-block rows
# ---------------------------------------------------------------------------

def chunk_budget_bound(policy, max_pages: int) -> int:
    """Static upper bound on any chunk row's block budget — the top-k /
    gather width the chunked executor allocates.  Computed as the exact max
    over every admissible padded prompt length (schedules need not be
    monotone: the paper's k_start fraction steps down at 16k keys), falling
    back to ``max_pages`` when the sweep would be too costly at init."""
    policy = policy_lib.as_policy(policy)
    if max_pages > 4096:
        return max_pages
    bound = 1
    for n in range(1, max_pages + 1):
        bound = max(bound, int(policy.prefill_budgets(
            n * policy.block_size).max()))
    return max(1, min(bound, max_pages))


def select_chunk_blocks(m: jnp.ndarray, block_rows: jnp.ndarray,
                        budgets: jnp.ndarray, policy,
                        k_max: int = 0) -> ChunkSelection:
    """Top-k + forced sink/local floors + causal validity, at absolute rows.

    m: (b, hq, nc, P) chunk metric; block_rows: (b, nc) absolute query-block
    row per chunk row; budgets: (b, nc) int32 per-row block budgets;
    k_max: static selection width (0 = all P candidates — always safe;
    ``chunk_budget_bound`` gives the tight value).  Semantics mirror
    ``selection.select_blocks`` evaluated on the full (nq_total, nk_total)
    grid, restricted to the chunk's rows: the top-k cut is a prefix of the
    same descending order, so any width >= the largest live budget selects
    the identical set.
    """
    policy = policy_lib.as_policy(policy)
    b, hq, nc, maxp = m.shape
    k_max = maxp if k_max <= 0 else min(k_max, maxp)
    blk = jnp.arange(maxp)
    causal = blk[None, None, :] <= block_rows[:, :, None]          # (b, nc, P)
    is_sink = (blk < policy.sink_blocks)[None, None, :]
    is_local = blk[None, None, :] > block_rows[:, :, None] - policy.local_blocks
    forced = (is_sink | is_local) & causal                         # (b, nc, P)

    biased = jnp.where(forced[:, None], m + FORCE_BONUS, m)
    biased = jnp.where(causal[:, None], biased, NEG_INF)
    vals, idx = jax.lax.top_k(biased, k_max)              # (b, hq, nc, k_max)
    live = (vals > NEG_INF / 2) & (
        jnp.arange(k_max)[None, None, None, :] < budgets[:, None, :, None])
    return ChunkSelection(indices=jnp.where(live, idx, 0).astype(jnp.int32),
                          live=live)


# ---------------------------------------------------------------------------
# Exact attention over the gathered pages
# ---------------------------------------------------------------------------

def attend_chunk(
    q: jnp.ndarray,            # (b, hq, C, d) chunk queries
    gk: jnp.ndarray,           # (b, hk, g, nc, k_max, bs, d) gathered pages
    gv: jnp.ndarray,           # (b, hk, g, nc, k_max, bs, dv)
    sel: ChunkSelection,
    chunk_start: jnp.ndarray,  # (b,) absolute first query position
    block_size: int,
) -> jnp.ndarray:
    """Masked softmax over the selected pages only, token-causal at
    absolute positions.  Both products take the gathered pages in their
    stored dtype with float32 accumulation, q and the probabilities
    entering at the pages' dtype (what a default-precision matrix product
    takes on the TPU); masking and the softmax stay in float32, and no
    page is copied or cast.  Returns (b, hq, C, dv)."""
    b, hq, c, d = q.shape
    hk = gk.shape[1]
    group = hq // hk
    bs = block_size
    nc = c // bs
    k_max = gk.shape[4]
    dv = gv.shape[-1]
    qg = q.reshape(b, hk, group, nc, bs, d).astype(gk.dtype)
    s = jnp.einsum("bhgnqd,bhgnkcd->bhgnqkc", qg, gk,
                   preferred_element_type=jnp.float32)
    s = s * (d ** -0.5)                         # (b, hk, g, nc, bs_q, kmax, bs_k)
    live = sel.live.reshape(b, hk, group, nc, k_max)
    tok_pos = sel.indices.reshape(b, hk, group, nc, k_max)[..., None] * bs \
        + jnp.arange(bs)                        # (b, hk, g, nc, kmax, bs_k)
    q_pos = chunk_start[:, None, None] + (jnp.arange(nc) * bs)[None, :, None] \
        + jnp.arange(bs)[None, None, :]         # (b, nc, bs_q)
    keep = (tok_pos[:, :, :, :, None]
            <= q_pos[:, None, None, :, :, None, None])
    keep = keep & live[:, :, :, :, None, :, None]
    s = jnp.where(keep, s, NEG_INF)
    p = jax.nn.softmax(s.reshape(b, hk, group, nc, bs, -1), axis=-1)
    p = jnp.where(keep, p.reshape(s.shape), 0.0)
    o = jnp.einsum("bhgnqkc,bhgnkcd->bhgnqd", p.astype(gv.dtype), gv,
                   preferred_element_type=jnp.float32)
    return o.reshape(b, hq, c, dv).astype(q.dtype)


def attend_latent_chunk(
    qt: jnp.ndarray,           # (b, h, C, D) absorbed chunk queries
    pages: jnp.ndarray,        # (b, nc, k_max, bs, D) kept latent pages
    sel: ChunkSelection,       # indices / live (b, 1, nc, k_max), head-shared
    chunk_start: jnp.ndarray,  # (b,)
    block_size: int,
    rank: int,
) -> jnp.ndarray:
    """``attend_chunk`` over latent pages, absorbed (see
    ``core.decode.attend_latent``): each query-block row's one page set
    serves every head.  Returns (b, h, C, rank) float32."""
    b, h, c, d = qt.shape
    bs = block_size
    nc = c // bs
    qb = qt.reshape(b, h, nc, bs, d).astype(pages.dtype)
    s = jnp.einsum("bhnqd,bnkcd->bhnqkc", qb, pages,
                   preferred_element_type=jnp.float32) * (d ** -0.5)
    tok_pos = sel.indices[:, 0][..., None] * bs + jnp.arange(bs)  # (b,nc,k,bs)
    q_pos = chunk_start[:, None, None] + (jnp.arange(nc) * bs)[None, :, None] \
        + jnp.arange(bs)[None, None, :]                          # (b, nc, bs_q)
    keep = (tok_pos[:, :, None] <= q_pos[..., None, None]) \
        & sel.live[:, 0][:, :, None, :, None]                    # (b,nc,q,k,bs)
    keep = keep[:, None]
    s = jnp.where(keep, s, NEG_INF)
    p = jax.nn.softmax(s.reshape(b, h, nc, bs, -1), axis=-1).reshape(s.shape)
    p = jnp.where(keep, p, 0.0)
    o = jnp.einsum("bhnqkc,bnkcr->bhnqr", p.astype(pages.dtype),
                   pages[..., :rank], preferred_element_type=jnp.float32)
    return o.reshape(b, h, c, rank)


# ---------------------------------------------------------------------------
# The full phase: metric -> select -> gather -> attend
# ---------------------------------------------------------------------------

def chunked_prefill_attention(
    q: jnp.ndarray,              # (b, hq, C, d) chunk queries (rope'd)
    pool,                        # runtime.paged.PagePool (chunk already written)
    page_table: jnp.ndarray,     # (b, max_pages) global page ids
    chunk_start: jnp.ndarray,    # (b,) absolute position of the chunk start
    budgets: jnp.ndarray,        # (b, C // block) int32 absolute-row budgets
    policy,
    k_max: int = 0,              # static gather width (0 = max_pages)
    executor=None,               # paged backend name (None = policy.executor)
    layer=None,                  # layer of a stacked pool (None = one layer's)
) -> jnp.ndarray:
    """Policy-sparse prefill attention for one chunk, straight off the page
    pool.  The chunk's own pages must already be written
    (``paged.write_chunk_pages`` runs first in ``attention.apply_chunk_paged``)
    so in-chunk blocks score and gather exactly like history blocks.
    ``executor`` picks the paged backend from the ``core/policy.py``
    registry — "xla" (the gather oracle below) or "pallas" (the fused
    kernels in ``kernels/paged_attn.py``).  Returns (b, hq, C, dv).
    """
    from repro.runtime import paged as paged_lib

    policy = policy_lib.as_policy(policy)
    spec = policy_lib.get_paged_executor(executor or policy.executor)
    if layer is None:
        pool, layer = paged_lib.stack_layer(pool), 0
    return spec.chunk_fn(q, pool, layer, page_table, chunk_start, budgets,
                         policy, k_max)


def _chunked_prefill_xla(
    q: jnp.ndarray,
    pools,
    layer,
    page_table: jnp.ndarray,
    chunk_start: jnp.ndarray,
    budgets: jnp.ndarray,
    policy,
    k_max: int = 0,
) -> jnp.ndarray:
    """The XLA gather backend (and the fused kernel's differential oracle):
    summary gather -> chunk metric -> selection -> page gather -> masked
    attend, each a separate inspectable op.  ``pools`` is the stacked pool,
    read at ``layer`` without slicing it."""
    from repro.runtime import paged as paged_lib

    policy = policy_lib.as_policy(policy)
    b, hq, c, d = q.shape
    hk = pools.k.shape[1]
    group = hq // hk
    bs = policy.block_size
    nc = c // bs
    maxp = page_table.shape[1]

    with jax.named_scope("stem.score"):
        # Page summaries through the page table (cheap: pooled reps only).
        kg_rows, vm_rows = paged_lib.gather_summaries(pools, layer,
                                                      page_table)
        m = policy.chunk_scores(q, kg_rows, vm_rows)      # (b, hq, nc, P)

    with jax.named_scope("stem.select"):
        rows = chunk_start[:, None] // bs + jnp.arange(nc)[None, :]
        sel = select_chunk_blocks(m, rows, budgets, policy, k_max)
        kk = sel.indices.shape[-1]
        # Logical slot -> global page id of each selected page.
        idx = sel.indices.reshape(b, hk, group, nc, kk)
        gp = jnp.take_along_axis(
            jnp.broadcast_to(page_table[:, None, None, None, :],
                             (b, hk, group, nc, maxp)),
            idx, axis=-1)                                  # (b,hk,g,nc,kmax)

    with jax.named_scope("stem.attend"):
        gk, gv = paged_lib.gather_pages(pools, layer, gp)  # (b,hk,g,nc,kmax,bs,d)
        return attend_chunk(q, gk, gv, sel, chunk_start, bs)
