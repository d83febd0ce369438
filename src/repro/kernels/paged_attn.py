"""Fused Pallas paged-attention for the serving hot path (decode + chunk).

The XLA paged executors (``runtime.paged.paged_sparse_decode`` /
``core.chunked.chunked_prefill_attention``) run score -> top-k -> gather ->
attend as separate ops, and two of those stages materialize per-step copies
that dominate the decode hot loop:

  * the summary gather of kg through the page table — a full
    (b, hk, max_pages, stride, d) copy of every visible page's pooled keys,
    rebuilt every step just to feed one einsum;
  * the page gather of the selected K/V pages — a materialized
    (b, hk, g, k_max, bs, d) K/V copy before the attention einsum reads it
    exactly once.

This module replaces both with scalar-prefetch kernels (the PR 1
``block_sparse_attn.py`` machinery, generalized from a contiguous cache to
the page pool):

  * **scoring** — the layer index and the page table ride as
    scalar-prefetch operands and the kg BlockSpec ``index_map`` resolves
    ``(layer, kv_head, page_table[b, p])`` directly, so the DMA engine
    streams each page's summary tile from the *stacked pool* into VMEM;
    routing scores are reduced in-kernel and only the tiny (b, hq, maxp)
    score matrix is ever materialized.
  * **attention** — selected pages are attended flash-style with an online
    softmax.  Scalar-prefetched revisit-filled global page ids drive the
    K/V ``index_map`` (dead slots re-point at the row's last live page ->
    zero new DMAs), logical ids rebuild token positions for length/causal
    masks, and per-row live counts bound the inner grid
    (``@pl.when(s < cnt)``) with the ragged finalize at ``cnt - 1``.

Selection itself (budgets + forced floors + top-k over the (b, h, maxp)
score matrix) stays in XLA via the *shared* ``policy.decode_select`` /
``select_chunk_blocks`` — it is O(heads * maxp) scalars, not memory-bound,
and reusing the policy code makes the fused path selection-identical to the
XLA oracle by construction (no duplicated tie-breaking to drift).

Numerics: both paths reduce in fp32; the flash-style online softmax equals
the XLA masked softmax to ~1e-6, pinned at 1e-4 by
``tests/test_paged_kernel.py``.  Zero-live rows (cache_lens == 0 trash
slots) emit exact zeros on both paths — the kernel's accumulator never runs
and finalize divides 0 by the 1e-20 floor; see
``core.decode.attend_selected`` for the contract.

Metric support: ``OutputAwareMetric`` / ``RoutingMetric`` (any pooling for
decode; "antidiag" and "mean" for chunks — the kernel computes the shared
``sum_u qp'[u] . kg[u]`` contraction after an XLA-side permutation of the
pooled queries) and ``StreamingMetric`` (content-free zeros, no kernel
needed).  Policies with custom metric classes fall back to the XLA oracle
wholesale, so registering ``executor="pallas"`` is always safe.

``interpret=None`` (every entry point's default) leaves the mode to the
backend (``repro.backend.interpret_kernels``): the Pallas interpreter on the
CPU test backend, compiled Mosaic kernels on TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import chunked as chunked_lib
from repro.core import metric as metric_lib
from repro.core import policy as policy_lib
from repro.core.selection import revisit_indices
from repro.backend import resolve_interpret
from repro.runtime import paged as paged_lib

NEG_INF = -1e30

# Process-wide tally of silent XLA fallbacks, keyed by call site
# ("decode" / "chunk").  Fallbacks fire at TRACE time (once per engine
# signature, not per step); ``StemEngine`` snapshots this at init and
# surfaces the delta as ``stats["pallas_fallbacks"]``, and the first hit
# per site warns so an operator asking for "pallas" learns they are
# running the oracle.
FALLBACKS: dict = {}
_WARNED: set = set()


def _note_fallback(site: str, reason: str) -> None:
    FALLBACKS[site] = FALLBACKS.get(site, 0) + 1
    if site not in _WARNED:
        _WARNED.add(site)
        import warnings
        warnings.warn(
            f"fused_paged_{site}: falling back to the XLA gather oracle "
            f"({reason}); counted in engine.stats['pallas_fallbacks']",
            RuntimeWarning, stacklevel=3)


def _metric_kind(metric) -> str | None:
    """"zero" (content-free), "routing" (kernel-scorable), or None (fall
    back to the XLA oracle for the whole call)."""
    if isinstance(metric, policy_lib.StreamingMetric):
        return "zero"
    if isinstance(metric, (policy_lib.OutputAwareMetric,
                           policy_lib.RoutingMetric)):
        return "routing"
    return None


# ---------------------------------------------------------------------------
# Shared scalar-prefetch packing
# ---------------------------------------------------------------------------

def pack_selection(indices, live, page_table):
    """Selection -> the kernel's scalar-prefetch triple.

    indices/live: (b, heads..., k_max) logical page-table slots + validity
    (live slots form a prefix — the selector contract); page_table:
    (b, max_pages) global page ids.

    Returns (gp, idx, cnt) int32: revisit-filled *global* page ids (drive
    the K/V DMAs; dead slots repeat the last live page so consecutive dead
    grid steps fetch nothing new), revisit-filled *logical* ids (rebuild
    token positions for masking), and per-row live counts.
    """
    b = page_table.shape[0]
    maxp = page_table.shape[1]
    lead = indices.shape[:-1]
    pt = jnp.broadcast_to(
        page_table.reshape((b,) + (1,) * (len(lead) - 1) + (maxp,)),
        lead + (maxp,))
    gp = jnp.take_along_axis(pt, indices, axis=-1)
    return (revisit_indices(gp, live).astype(jnp.int32),
            revisit_indices(indices, live).astype(jnp.int32),
            live.sum(axis=-1, dtype=jnp.int32))


# ---------------------------------------------------------------------------
# Summary-resident page scoring (decode: one query row per slot)
# ---------------------------------------------------------------------------

def _score_kernel(layer_ref, pt_ref, q_ref, kg_ref, o_ref, *, scale):
    """Routing score of one (row, page) pair straight off the pool summary.

    q tile (1, nc, s, d) holds the row's pooled queries (nc = 1 for decode),
    kg tile (1, 1, 1, s, d) is DMA'd from
    ``pools.kg[layer, kv_head, page_table[b, p]]`` by the index map.  The
    (1, nc, maxp) output block stays resident across the page axis; step p
    selects its column into the whole block, since Mosaic cannot store at a
    dynamic lane offset.
    """
    p = pl.program_id(1)
    nc = q_ref.shape[1]
    kg = kg_ref[0, 0, 0].astype(jnp.float32)                  # (s, d)
    rows = jax.lax.broadcasted_iota(jnp.int32, (nc, 1), 0)
    col = jnp.zeros((nc, 1), jnp.float32)
    for i in range(nc):
        qk = q_ref[0, i].astype(jnp.float32) * kg              # (s, d)
        sc = jnp.sum(jnp.sum(qk, axis=1, keepdims=True), axis=0,
                     keepdims=True)                            # (1, 1)
        col = jnp.where(rows == i, sc * scale, col)

    @pl.when(p == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    lanes = jax.lax.broadcasted_iota(jnp.int32, o_ref.shape[1:], 1)
    o_ref[0] = jnp.where(lanes == p, col, o_ref[0])


def _layer_operand(layer):
    """A layer index as the (1,) int32 scalar-prefetch operand."""
    return jnp.asarray(layer, jnp.int32).reshape(1)


def _score_pages(qp, kg_pool, layer, page_table, *, group, scale, interpret,
                 name):
    """qp: (b, hq, nc, s, d) pooled/permuted queries; kg_pool:
    (n, hk, P, s, d) stacked pool summaries, read at ``layer``.  Returns
    (b, hq, nc, maxp) fp32 routing scores computed without materializing
    ``kg_pool[layer][:, page_table]``."""
    b, hq, nc, s, d = qp.shape
    maxp = page_table.shape[1]
    qr = qp.reshape(b * hq, nc, s, d)

    def q_map(bh, p, layer_ref, pt_ref):
        return (bh, 0, 0, 0)

    def kg_map(bh, p, layer_ref, pt_ref):
        bi = bh // hq
        hi = bh % hq
        return (layer_ref[0], hi // group, pt_ref[bi, p], 0, 0)

    def o_map(bh, p, layer_ref, pt_ref):
        return (bh, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b * hq, maxp),
        in_specs=[
            pl.BlockSpec((1, nc, s, d), q_map),
            pl.BlockSpec((1, 1, 1, s, d), kg_map),
        ],
        out_specs=pl.BlockSpec((1, nc, maxp), o_map),
    )
    out = pl.pallas_call(
        functools.partial(_score_kernel, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b * hq, nc, maxp), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name=name,
    )(_layer_operand(layer), page_table.astype(jnp.int32), qr, kg_pool)
    return out.reshape(b, hq, nc, maxp)


def decode_page_scores(q, kg_pool, layer, page_table, *, group,
                       interpret=None):
    """Kernel-backed ``metric_lib.decode_routing_scores`` against the pool.

    q: (b, hq, 1, d); kg_pool: (n, hk, P, stride, d) stacked, read at
    ``layer``.  Returns (b, hk, g, maxp) fp32 — bit-compatible (up to fp32
    reduction order) with ``decode_routing_scores`` of the layer's kg rows
    gathered through the page table.
    """
    b, hq, _, d = q.shape
    s = kg_pool.shape[-2]
    scale = 1.0 / (s * float(d) ** 0.5)
    # One "pooled" query group per row: nc = 1, the s axis broadcasts the
    # single query against every summary group (the decode routing score
    # sums over all s groups).
    qp = jnp.broadcast_to(q[:, :, :, None, :], (b, hq, 1, s, d))
    out = _score_pages(qp, kg_pool, layer, page_table, group=group,
                       scale=scale, interpret=resolve_interpret(interpret),
                       name="stem_paged_decode_score")
    return out.reshape(b, hq // group, group, page_table.shape[1])


def chunk_page_scores(q, kg_pool, layer, page_table, *, block_size, pooling,
                      group, interpret=None):
    """Kernel-backed ``metric_lib.chunk_routing_scores`` against the pool.

    The anti-diagonal pairing ``pair(u) = (s - u) % s`` is an involution, so
    permuting the *pooled queries* by it in XLA (tiny: nc * s * d per row)
    turns the paired contraction into the plain ``sum_u qp'[u] . kg[u]`` the
    shared scoring kernel computes against unpermuted in-pool summaries.
    Mean pooling reduces to the same form with the query group axis averaged
    and broadcast.  q: (b, hq, C, d); kg_pool: (n, hk, P, s, d) stacked,
    read at ``layer`` -> (b, hq, nc, maxp) fp32.
    """
    b, hq, c, d = q.shape
    s = kg_pool.shape[-2]
    qp = metric_lib.antidiag_pool(q, block_size, s)       # (b, hq, nc, s, d)
    if pooling == "antidiag":
        pair = (s - jnp.arange(s)) % s
        qp = jnp.take(qp, pair, axis=-2)
        scale = 1.0 / (s * float(d) ** 0.5)
    else:  # mean: block mean = mean of the equal-sized group means
        qp = jnp.broadcast_to(qp.mean(axis=-2, keepdims=True), qp.shape)
        scale = 1.0 / (s * float(d) ** 0.5)
    return _score_pages(qp, kg_pool, layer, page_table, group=group,
                        scale=scale, interpret=resolve_interpret(interpret),
                        name="stem_paged_chunk_score")


# ---------------------------------------------------------------------------
# Fused attention over selected pages (online softmax, ragged live counts)
# ---------------------------------------------------------------------------

def _attend_kernel(
    layer_ref, gp_ref, idx_ref, cnt_ref, pos_ref,  # scalar prefetch (SMEM)
    q_ref, k_ref, v_ref,                           # VMEM tiles
    o_ref,
    acc_ref, m_ref, l_ref,                         # VMEM scratch
    *,
    scale: float,
    block_k: int,
    rows: int,
    heads: int,
    causal: bool,
):
    """Flash-style attention over one row's selected pages.

    Grid (b * hq, nc, k_max).  ``pos_ref`` is the per-slot length vector:
    for decode (causal=False, rows=1) it holds ``cache_lens`` and masks
    ``tok_pos < len``; for chunks (causal=True, rows=block) it holds
    ``chunk_start`` and masks ``tok_pos <= q_pos`` at absolute positions.
    Rows with cnt == 0 never run ``_compute``; finalize then divides the
    zero accumulator by the 1e-20 floor — the exact-zero-output contract of
    ``core.decode.attend_selected``.
    """
    bh = pl.program_id(0)
    i = pl.program_id(1)
    s = pl.program_id(2)
    bi = bh // heads
    hi = bh % heads

    @pl.when(s == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    cnt = cnt_ref[bi, hi, i]

    @pl.when(s < cnt)
    def _compute():
        j = idx_ref[bi, hi, i, s]
        q = q_ref[0, 0].astype(jnp.float32) * scale       # (rows, d)
        k = k_ref[0, 0, 0].astype(jnp.float32)            # (bk, d)
        sc = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )                                                  # (rows, bk)
        tok = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (rows, block_k), 1)
        if causal:
            q_pos = pos_ref[bi] + i * rows + jax.lax.broadcasted_iota(
                jnp.int32, (rows, block_k), 0)
            keep = tok <= q_pos
        else:
            keep = tok < pos_ref[bi]
        sc = jnp.where(keep, sc, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, sc.max(axis=-1))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(sc - m_new[:, None])
        p = jnp.where(keep, p, 0.0)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=-1)
        v = v_ref[0, 0, 0].astype(jnp.float32)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        acc_ref[...] = acc_ref[...] * corr[:, None] + pv
        m_ref[...] = m_new

    @pl.when(s == jnp.maximum(cnt - 1, 0))
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-20)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def _attend_pages(q, k_pool, v_pool, layer, gp, idx, cnt, pos, *,
                  block_size, causal, interpret, name):
    """q: (b, hq, nc, rows, d); k/v_pool: (n, hk, P, bs, d) stacked, read
    at ``layer``; gp/idx: (b, hq, nc, k_max) int32; cnt: (b, hq, nc) int32;
    pos: (b,) int32.  Returns (b, hq, nc, rows, dv)."""
    b, hq, nc, rows, d = q.shape
    hk = k_pool.shape[1]
    group = hq // hk
    dv = v_pool.shape[-1]
    k_max = gp.shape[-1]
    scale = float(d) ** -0.5
    qr = q.reshape(b * hq, nc, rows, d)

    def q_map(bh, i, s, layer_ref, gp_ref, idx_ref, cnt_ref, pos_ref):
        return (bh, i, 0, 0)

    def kv_map(bh, i, s, layer_ref, gp_ref, idx_ref, cnt_ref, pos_ref):
        bi = bh // hq
        hi = bh % hq
        return (layer_ref[0], hi // group, gp_ref[bi, hi, i, s], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(b * hq, nc, k_max),
        in_specs=[
            pl.BlockSpec((1, 1, rows, d), q_map),
            pl.BlockSpec((1, 1, 1, block_size, d), kv_map),
            pl.BlockSpec((1, 1, 1, block_size, dv), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, rows, dv), q_map),
        scratch_shapes=[
            pltpu.VMEM((rows, dv), jnp.float32),
            pltpu.VMEM((rows,), jnp.float32),
            pltpu.VMEM((rows,), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _attend_kernel, scale=scale, block_k=block_size, rows=rows,
            heads=hq, causal=causal),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b * hq, nc, rows, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name=name,
    )(_layer_operand(layer), gp, idx, cnt, pos, qr, k_pool, v_pool)
    return out.reshape(b, hq, nc, rows, dv)


# ---------------------------------------------------------------------------
# Fused entry points (drop-in for the XLA paged executors)
# ---------------------------------------------------------------------------

def fused_paged_decode(q, pools, layer, page_table, cache_lens, cfg,
                       budget_frac=None, *, interpret=None):
    """Kernel-backed ``runtime.paged._paged_decode_xla``.

    Same signature and semantics (stacked pools read at ``layer``); scoring
    and attention run as Pallas kernels against the pool, selection is the
    shared policy code.  Falls back to the XLA oracle for metric classes
    the scorer cannot serve.
    """
    from repro.core.decode import DEFAULT_BUDGET_FRAC, debug_assert_live_rows
    policy = policy_lib.as_policy(cfg)
    if budget_frac is None:
        budget_frac = DEFAULT_BUDGET_FRAC
    kind = _metric_kind(policy.metric)
    if kind is None:
        _note_fallback(
            "decode", f"unsupported metric {type(policy.metric).__name__}")
        return paged_lib._paged_decode_xla(
            q, pools, layer, page_table, cache_lens, policy, budget_frac)
    interpret = resolve_interpret(interpret)

    b, hq, _, d = q.shape
    hk = pools.k.shape[1]
    group = hq // hk
    maxp = page_table.shape[1]
    lens = jnp.broadcast_to(jnp.asarray(cache_lens, jnp.int32), (b,))

    with jax.named_scope("stem.score"):
        if kind == "zero":
            m = jnp.zeros((b, hk, group, maxp), jnp.float32)
        else:
            m = decode_page_scores(q, pools.kg, layer, page_table,
                                   group=group, interpret=interpret)
            beta = getattr(policy.metric, "beta", 0.0)
            if beta:
                vm_rows = paged_lib.gather_summaries(pools, layer,
                                                     page_table)[1]
                m = m + beta * jnp.maximum(vm_rows, 0.0)[:, :, None, :]

    with jax.named_scope("stem.select"):
        sel = policy.decode_select(m, lens, budget_frac=budget_frac)
        debug_assert_live_rows(sel, context="fused_paged_decode")
        gp, idx, cnt = pack_selection(sel.indices, sel.live, page_table)
    with jax.named_scope("stem.attend"):
        out = _attend_pages(
            q.reshape(b, hq, 1, 1, d),
            pools.k, pools.v, layer,
            gp.reshape(b, hq, 1, -1), idx.reshape(b, hq, 1, -1),
            cnt.reshape(b, hq, 1), lens,
            block_size=policy.block_size, causal=False, interpret=interpret,
            name="stem_paged_decode_attend")
        return out.reshape(b, hq, 1, -1)


def fused_paged_chunk(q, pools, layer, page_table, chunk_start, budgets, cfg,
                      k_max=0, *, interpret=None):
    """Kernel-backed ``core.chunked._chunked_prefill_xla``.

    Same signature and semantics (stacked pools read at ``layer``, chunk
    pages already written); selection-identical to the XLA oracle via the
    shared ``select_chunk_blocks``.  Falls back to the oracle for metric
    classes or poolings the scorer cannot serve.
    """
    policy = policy_lib.as_policy(cfg)
    kind = _metric_kind(policy.metric)
    pooling = getattr(policy.metric, "pooling", "antidiag")
    if kind is None or (kind == "routing" and pooling not in ("antidiag",
                                                              "mean")):
        _note_fallback(
            "chunk",
            (f"unsupported metric {type(policy.metric).__name__}"
             if kind is None else f"unsupported pooling {pooling!r}"))
        return chunked_lib._chunked_prefill_xla(
            q, pools, layer, page_table, chunk_start, budgets, policy, k_max)
    interpret = resolve_interpret(interpret)

    b, hq, c, d = q.shape
    hk = pools.k.shape[1]
    group = hq // hk
    bs = policy.block_size
    nc = c // bs
    maxp = page_table.shape[1]
    start = jnp.asarray(chunk_start, jnp.int32)

    with jax.named_scope("stem.score"):
        if kind == "zero":
            m = jnp.zeros((b, hq, nc, maxp), jnp.float32)
        else:
            m = chunk_page_scores(q, pools.kg, layer, page_table,
                                  block_size=bs, pooling=pooling, group=group,
                                  interpret=interpret)
            beta = getattr(policy.metric, "beta", 0.0)
            if beta:
                vm_rows = paged_lib.gather_summaries(pools, layer,
                                                     page_table)[1]
                mv = jnp.repeat(vm_rows, group, axis=1)    # (b, hq, maxp)
                m = m + beta * jnp.maximum(mv, 0.0)[..., None, :]
            m = metric_lib.group_reduce_metric(m, group, policy.group_reduce)

    with jax.named_scope("stem.select"):
        rows = start[:, None] // bs + jnp.arange(nc)[None, :]
        sel = chunked_lib.select_chunk_blocks(m, rows, budgets, policy, k_max)
        gp, idx, cnt = pack_selection(sel.indices, sel.live, page_table)
    with jax.named_scope("stem.attend"):
        out = _attend_pages(
            q.reshape(b, hq, nc, bs, d),
            pools.k, pools.v, layer,
            gp, idx, cnt, start,
            block_size=bs, causal=True, interpret=interpret,
            name="stem_paged_chunk_attend")
        return out.reshape(b, hq, c, -1)


# Both fused lanes read head counts from the pool shapes and reduce only
# within a head, so a shard-local KV-head slice is served unchanged.
policy_lib.register_paged_executor(
    "pallas", decode_fn=fused_paged_decode, chunk_fn=fused_paged_chunk,
    sharding="kv-head")
