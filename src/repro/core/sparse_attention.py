"""Sparse attention execution: policy orchestration (Algorithm 1 shape).

Pipeline per (batch, head), for *any* ``SparsityPolicy`` (core/policy.py):
  1. the policy's ``BlockMetric`` scores key blocks (metric.py),
  2. its ``BudgetSchedule`` fixes per-row block budgets (schedule.py),
  3. its ``Selector`` turns scores + budgets into a BlockSelection
     (selection.py),
  4. an *executor* runs exact attention over the selected blocks only.

Executors are resolved through the policy registry
(``policy.register_executor`` — DESIGN.md describes the contract in
detail):
  * "xla"    — gather-based flash-style executor in pure jnp.  This is the
               path lowered in the distributed dry-run; it is mathematically
               identical to the Pallas kernel.  With ``policy.ragged`` it
               runs a budget-sorted segment schedule so cost tracks the
               *average* budget instead of the padded k_max, and with
               GQA-shared selection it fetches each K/V block once per KV
               head.
  * "pallas" — TPU kernel (kernels/block_sparse_attn.py) driven by the same
               selection indices via scalar prefetch; dead slots revisit the
               previous K/V block (zero new DMAs) and rows finalize at their
               own live count.
  * "dense"  — O(N^2) masked oracle for tests.

``sparse_attention(q, k, v, policy)`` is the primary entry point;
``stem_attention(q, k, v, cfg)`` is the flag-record shim
(``policy = cfg.policy()``, executor from ``cfg.backend``).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import policy as policy_lib
from repro.core import selection as selection_lib
from repro.core.config import StemConfig
from repro.sharding.context import constrain

NEG_INF = -1e30


class StemStats(NamedTuple):
    density: jnp.ndarray          # realized fraction of admissible blocks
    avg_budget_blocks: jnp.ndarray
    k_max: int


def dense_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    mask: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Reference dense attention with GQA support.

    q: (b, hq, sq, d); k: (b, hk, sk, d); v: (b, hk, sk, dv) — dv may differ
    from d (MLA).  O(N^2) — baseline & oracle.
    """
    b, hq, sq, d = q.shape
    hk = k.shape[1]
    dv = v.shape[-1]
    group = hq // hk
    scale = (d ** -0.5) if scale is None else scale
    qg = q.reshape(b, hk, group, sq, d)
    scores = jnp.einsum("bhgqd,bhkd->bhgqk", qg.astype(jnp.float32), k.astype(jnp.float32))
    scores = scores * scale
    sk = k.shape[2]
    if causal:
        offset = sk - sq
        qi = jnp.arange(sq)[:, None]
        kj = jnp.arange(sk)[None, :]
        cmask = kj <= qi + offset
        scores = jnp.where(cmask, scores, NEG_INF)
    if mask is not None:
        # mask: (b, hq, sq, sk) boolean keep-mask.
        scores = jnp.where(mask.reshape(b, hk, group, sq, sk), scores, NEG_INF)
    # Guard fully-masked rows (can occur only in pathological configs).
    row_max = scores.max(axis=-1, keepdims=True)
    probs = jax.nn.softmax(jnp.where(row_max > NEG_INF / 2, scores, 0.0), axis=-1)
    out = jnp.einsum("bhgqk,bhkd->bhgqd", probs, v.astype(jnp.float32))
    return out.reshape(b, hq, sq, dv).astype(q.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "scale", "q_chunk", "kv_chunk"))
def dense_attention_chunked(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    q_chunk: int = 1024,
    kv_chunk: int = 1024,
) -> jnp.ndarray:
    """Flash-style dense attention in pure XLA: streams KV chunks with an
    online-softmax accumulator, so peak memory is O(N * chunk) instead of
    O(N^2).  This is the memory shape the Pallas flash kernel has on TPU;
    it's what train/prefill lower in the dry-run.

    Note: causal masking is applied by masking, not by skipping chunks, so
    the *compute* is 2x the causal-triangle minimum (documented in
    DESIGN.md; the Stem path avoids this entirely by gathering only
    selected blocks).
    """
    b, hq, sq, d = q.shape
    _, hk, sk, _ = k.shape
    dv = v.shape[-1]
    group = hq // hk
    scale = (d ** -0.5) if scale is None else scale
    qc = min(q_chunk, sq)
    kc = min(kv_chunk, sk)
    if sq % qc or sk % kc:
        return dense_attention(q, k, v, causal=causal, scale=scale)
    nq, nk = sq // qc, sk // kc

    qb = (q.reshape(b, hk, group, nq, qc, d).astype(jnp.float32) * scale)
    kb = k.reshape(b, hk, nk, kc, d)
    vb = v.reshape(b, hk, nk, kc, dv)
    q_pos = jnp.arange(sq).reshape(nq, qc)

    def body(carry, j):
        acc, m, l = carry
        k_j = jax.lax.dynamic_index_in_dim(kb, j, axis=2, keepdims=False)
        v_j = jax.lax.dynamic_index_in_dim(vb, j, axis=2, keepdims=False)
        s = jnp.einsum("bhgnqd,bhkd->bhgnqk", qb, k_j.astype(jnp.float32))
        if causal:
            k_pos = j * kc + jnp.arange(kc)
            keep = k_pos[None, None] <= (sk - sq) + q_pos[:, :, None]
            s = jnp.where(keep[None, None, None], s, NEG_INF)
        s_max = s.max(axis=-1)
        m_new = jnp.maximum(m, s_max)
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        if causal:
            p = jnp.where(keep[None, None, None], p, 0.0)
        l_new = l * corr + p.sum(axis=-1)
        pv = jnp.einsum("bhgnqk,bhkd->bhgnqd", p, v_j.astype(jnp.float32))
        acc_new = acc * corr[..., None] + pv
        return (acc_new, m_new, l_new), None

    acc0 = jnp.zeros((b, hk, group, nq, qc, dv), jnp.float32)
    m0 = jnp.full((b, hk, group, nq, qc), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hk, group, nq, qc), jnp.float32)
    (acc, m, l), _ = jax.lax.scan(body, (acc0, m0, l0), jnp.arange(nk))
    out = acc / jnp.maximum(l, 1e-20)[..., None]
    return out.reshape(b, hq, sq, dv).astype(q.dtype)


def dense_attention_auto(q, k, v, *, causal=True, scale=None,
                         mask=None, threshold: int = 2048):
    """Dispatch: chunked flash path for long sequences (no custom mask),
    direct masked softmax otherwise."""
    if mask is None and q.shape[2] >= threshold and k.shape[2] >= threshold:
        return dense_attention_chunked(q, k, v, causal=causal, scale=scale)
    return dense_attention(q, k, v, causal=causal, scale=scale, mask=mask)


def _gather_executor(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    indices: jnp.ndarray,
    slot_mask: jnp.ndarray,
    *,
    block_size: int,
    scale: float,
    slot_chunk: int,
    budgets: Optional[np.ndarray] = None,
    group_dedup: bool = False,
) -> jnp.ndarray:
    """Flash-style sparse executor: per query-block row, stream the selected
    key/value blocks in chunks with an online-softmax accumulator.

    The executor folds (head-in-group, query-block) pairs into a single
    "row" axis per KV head, so one code path covers both layouts:

      * ``group_dedup=False`` — indices/slot_mask are per query head,
        (b, hq, nq, k_max); rows = group * nq, each with a (block_q, d)
        query tile.
      * ``group_dedup=True`` — selection is shared across the query heads
        of each KV group (``cfg.group_reduce != "none"``), so indices are
        (b, hk, nq, k_max); rows = nq with a fused (group * block_q, d)
        query tile.  Each K/V block is gathered once per *KV head*, cutting
        gather traffic by the group factor.

    ``budgets`` (static numpy, per query-block row) enables the ragged
    schedule: rows are budget-sorted and segmented (selection.
    budget_sorted_segments) and each segment scans only the slot chunks its
    rows actually use — the chunk-level early-out that makes cost track the
    average TPD budget instead of k_max.  ``budgets=None`` runs the padded
    schedule (every row pays ceil(k_max / slot_chunk) chunks).

    q: (b, hq, sq, d); k, v: (b, hk, sk, d).
    """
    b, hq, sq, d = q.shape
    _, hk, sk, _ = k.shape
    dv = v.shape[-1]
    group = hq // hk
    bs = block_size
    nq, nk = sq // bs, sk // bs
    k_max = indices.shape[-1]
    chunk = max(1, min(slot_chunk, k_max))
    # Pad slot dim to a multiple of the chunk size.
    pad = (-k_max) % chunk
    if pad:
        indices = jnp.pad(indices, ((0, 0), (0, 0), (0, 0), (0, pad)))
        slot_mask = jnp.pad(slot_mask, ((0, 0), (0, 0), (0, 0), (0, pad)))
    n_chunks = (k_max + pad) // chunk

    kb = k.reshape(b, hk, nk, bs, d)
    vb = v.reshape(b, hk, nk, bs, dv)
    # Pin K/V blocks to (batch, heads) sharding: if a seq-sharded layout
    # propagates in (e.g. from a kv_seq-sharded cache output), GSPMD cannot
    # partition the data-dependent block gather and emits a full masked
    # all-reduce of the gathered tensor (34 GB/layer at glm4-9b 32k —
    # §Perf glm4 iteration 2, DESIGN.md).
    kb = constrain(kb, ("batch", "kv_heads", None, None, None))
    vb = constrain(vb, ("batch", "kv_heads", None, None, None))

    offset = sk - sq  # 0 for self-attention prefill/train
    q_pos = offset + np.arange(sq).reshape(nq, bs)  # global query positions

    qg = q.reshape(b, hk, group, nq, bs, d)
    if group_dedup:
        # Rows = query-block rows; fused (group * bs) query tile per row.
        qrows = qg.transpose(0, 1, 3, 2, 4, 5).reshape(b, hk, nq, group * bs, d)
        idx = indices
        msk = slot_mask
        q_pos_rows = np.tile(q_pos, (1, group))            # (nq, group*bs)
        row_budgets = budgets
    else:
        # Rows = (head-in-group, query-block) pairs, plain (bs) query tile.
        qrows = qg.reshape(b, hk, group * nq, bs, d)
        idx = indices.reshape(b, hk, group * nq, -1)
        msk = slot_mask.reshape(b, hk, group * nq, -1)
        q_pos_rows = np.tile(q_pos, (group, 1))            # (group*nq, bs)
        row_budgets = None if budgets is None else np.tile(budgets, group)
    qrows = qrows.astype(jnp.float32) * scale
    q_pos_rows = jnp.asarray(q_pos_rows)

    def run_rows(q_r, pos_r, idx_r, msk_r, seg_chunks):
        """Online-softmax scan over ``seg_chunks`` slot chunks for one row
        set: q_r (b, hk, R, Bq, d); idx_r/msk_r (b, hk, R, seg_chunks*chunk).
        """
        R, Bq = q_r.shape[2], q_r.shape[3]
        idx_s = idx_r.reshape(b, hk, R, seg_chunks, chunk)
        msk_s = msk_r.reshape(b, hk, R, seg_chunks, chunk)

        def body(carry, c):
            acc, m, l = carry
            idx_c = jax.lax.dynamic_index_in_dim(idx_s, c, axis=3, keepdims=False)
            msk_c = jax.lax.dynamic_index_in_dim(msk_s, c, axis=3, keepdims=False)
            # Gather selected key/value blocks once per KV head:
            # (b, hk, R, chunk, bs, d).
            gidx = idx_c[..., None, None]
            k_c = jnp.take_along_axis(kb[:, :, None], gidx, axis=3)
            v_c = jnp.take_along_axis(vb[:, :, None], gidx, axis=3)
            # Scores: (b, hk, R, Bq, chunk, bs_k).
            s = jnp.einsum("bhrqd,bhrckd->bhrqck", q_r, k_c.astype(jnp.float32))
            # Token-level causal mask (exact on diagonal blocks) + validity.
            k_pos = idx_c[..., None] * bs + jnp.arange(bs)   # (b,hk,R,chunk,bs)
            keep = k_pos[:, :, :, None] <= pos_r[None, None, :, :, None, None]
            keep = keep & msk_c[:, :, :, None, :, None]
            s = jnp.where(keep, s, NEG_INF)
            # Online softmax update.
            s_max = s.max(axis=(-1, -2))                     # (b, hk, R, Bq)
            m_new = jnp.maximum(m, s_max)
            corr = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None, None])
            p = jnp.where(keep, p, 0.0)
            l_new = l * corr + p.sum(axis=(-1, -2))
            pv = jnp.einsum("bhrqck,bhrckd->bhrqd", p, v_c.astype(jnp.float32))
            acc_new = acc * corr[..., None] + pv
            return (acc_new, m_new, l_new), None

        acc0 = jnp.zeros((b, hk, R, Bq, dv), jnp.float32)
        m0 = jnp.full((b, hk, R, Bq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, hk, R, Bq), jnp.float32)
        (acc, m, l), _ = jax.lax.scan(body, (acc0, m0, l0), jnp.arange(seg_chunks))
        return acc / jnp.maximum(l, 1e-20)[..., None]

    if row_budgets is None:
        out_rows = run_rows(qrows, q_pos_rows, idx, msk, n_chunks)
    else:
        # Ragged schedule: budget-sorted segments, each scanning only the
        # chunks its rows need.  All indexing below is static numpy, so each
        # segment lowers to its own (smaller) fused scan.
        segments = selection_lib.budget_sorted_segments(row_budgets, chunk)
        outs = []
        for seg in segments:
            rows = np.asarray(seg.rows)
            n_slots = min(seg.n_chunks, n_chunks) * chunk
            outs.append(run_rows(
                jnp.take(qrows, rows, axis=2),
                jnp.take(q_pos_rows, rows, axis=0),
                jnp.take(idx, rows, axis=2)[..., :n_slots],
                jnp.take(msk, rows, axis=2)[..., :n_slots],
                min(seg.n_chunks, n_chunks),
            ))
        inv = np.argsort(np.concatenate([np.asarray(s.rows) for s in segments]))
        out_rows = jnp.take(jnp.concatenate(outs, axis=2), inv, axis=2)

    if group_dedup:
        out = out_rows.reshape(b, hk, nq, group, bs, dv)
        out = out.transpose(0, 1, 3, 2, 4, 5)
    else:
        out = out_rows.reshape(b, hk, group, nq, bs, dv)
    return out.reshape(b, hq, sq, dv).astype(q.dtype)


def select_for(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    cfg,
    *,
    with_block_mask: bool = True,
) -> tuple[selection_lib.BlockSelection, int]:
    """Phase 1: metric + schedule + selection.  ``cfg`` may be a
    ``StemConfig``, a ``SparsityPolicy`` or a registered policy name."""
    return policy_lib.as_policy(cfg).prefill_select(
        q, k, v, with_block_mask=with_block_mask)


# ---------------------------------------------------------------------------
# Executors (registered under policy.register_executor; resolved by name)
# ---------------------------------------------------------------------------

def _dense_oracle_executor(q, k, v, sel, *, policy, scale, **_):
    """O(N^2) masked softmax over the selection's dense block mask."""
    token_mask = selection_lib.block_mask_to_token_mask(
        sel.block_mask, policy.block_size, policy.block_size,
        q.shape[2], k.shape[2])
    return dense_attention(q, k, v, causal=True, scale=scale, mask=token_mask)


def _xla_gather_executor(q, k, v, sel, *, policy, scale, indices, slot_mask,
                         dedup, budgets, **_):
    return _gather_executor(
        q, k, v, indices, slot_mask,
        block_size=policy.block_size, scale=scale,
        slot_chunk=policy.slot_chunk, budgets=budgets, group_dedup=dedup)


def _pallas_executor(q, k, v, sel, *, policy, scale, indices, slot_mask,
                     live_counts, dedup, **_):
    from repro.kernels import block_sparse_attn  # deferred: optional dep

    return block_sparse_attn.block_sparse_attention(
        q, k, v, indices, slot_mask,
        block_size=policy.block_size, scale=scale, group_dedup=dedup,
        live_counts=live_counts)


policy_lib.register_executor("dense", _dense_oracle_executor,
                             needs_block_mask=True)
policy_lib.register_executor("xla", _xla_gather_executor)
policy_lib.register_executor("pallas", _pallas_executor)


@functools.partial(jax.jit, static_argnames=("policy", "executor", "return_stats"))
def sparse_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    policy,
    executor: Optional[str] = None,
    return_stats: bool = False,
):
    """Block-sparse causal attention under a composable ``SparsityPolicy``.

    Args:
      q: (batch, q_heads, seq, head_dim)
      k, v: (batch, kv_heads, seq, head_dim)
      policy: SparsityPolicy | registered policy name | legacy StemConfig.
      executor: execution backend name from the executor registry
        ("xla" | "pallas" | "dense"); None uses ``policy.executor``.
      return_stats: also return StemStats.

    Returns:
      (batch, q_heads, seq, head_dim) attention output [, StemStats].
    """
    policy = policy_lib.as_policy(policy)
    spec = policy_lib.get_executor(executor or policy.executor)
    b, hq, sq, d = q.shape
    sk = k.shape[2]
    scale = d ** -0.5
    nk = sk // policy.block_size
    # selection_density works from slot_mask, so stats never force the
    # dense block-mask scatter onto a production executor.
    sel, k_max = policy.prefill_select(
        q, k, v, with_block_mask=spec.needs_block_mask)

    # GQA block dedup: with group-shared selection every query head of a KV
    # group picks identical blocks, so the executors only need the indices
    # of one head per group (DESIGN.md §GQA dedup invariant).
    group = hq // k.shape[1]
    dedup = policy.ragged and policy.group_reduce != "none" and group > 1
    idx, msk, cnt = sel.indices, sel.slot_mask, sel.live_counts
    if dedup:
        idx, msk, cnt = idx[:, ::group], msk[:, ::group], cnt[:, ::group]

    # Budgets are static per (policy, shape) — recompute in numpy so the
    # ragged segment schedule resolves at trace time.  Threshold selectors
    # have data-dependent budgets, so they run the padded schedule.
    budgets_np = None
    if policy.ragged and policy.selector.budget_driven:
        budgets_np = policy.prefill_budgets(sq, sk)

    out = spec.fn(q, k, v, sel, policy=policy, scale=scale, indices=idx,
                  slot_mask=msk, live_counts=cnt, dedup=dedup,
                  budgets=budgets_np)

    if return_stats:
        stats = StemStats(
            density=selection_lib.selection_density(sel, nk),
            avg_budget_blocks=sel.budgets.mean(),
            k_max=k_max,
        )
        return out, stats
    return out


def stem_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    cfg: StemConfig,
    return_stats: bool = False,
):
    """Stem sparse causal attention (Algorithm 1) — flag-record shim.

    Stable entry point for existing call sites: converts the frozen
    ``StemConfig`` into its equivalent ``SparsityPolicy`` (OAM/SAM x TPD x
    top-k, executor from ``cfg.backend``) and delegates to
    :func:`sparse_attention`.  Bit-identical to the policy spelling.
    """
    return sparse_attention(q, k, v, cfg, return_stats=return_stats)
