"""Stem block-sparse attention as a Pallas TPU kernel (scalar prefetch).

TPU adaptation of the paper's Triton Block-Sparse-Attention execution phase
(Algorithm 1, lines 18-22).  The per-query-block Top-k(i) key-block indices
are computed outside the kernel (the coarse metric is only (N/B)^2) and
passed as **scalar-prefetch** operands so the DMA engine streams exactly the
selected HBM key/value blocks into VMEM — the TPU-native replacement for a
GPU gather:

  * ``pltpu.PrefetchScalarGridSpec(num_scalar_prefetch=2)`` carries
    ``indices`` (b, h_sel, nq, k_max) and per-row ``live_counts``
    (b, h_sel, nq) int32.
  * The K/V ``BlockSpec.index_map`` reads ``indices[b, h, i, s]`` to pick the
    HBM block for grid step (bh, i, s).  Indices are *revisit-filled*
    (selection.revisit_indices): every dead (padded) slot re-points at the
    row's last live block, so consecutive dead steps map to the same block
    index and the Pallas pipeline skips the DMA entirely — dead slots cost
    **zero new DMAs** (splash-attention's revisit trick), not one redundant
    fetch each as in the padded layout.
  * Per-row variable budget k(i) (Token Position-Decay) is exactly the
    pattern this supports: rows compute only their ``live_count`` slots
    (``@pl.when(s < cnt)``) and finalize at ``live_count - 1`` instead of
    ``k_max - 1``.
  * GQA block dedup (``group_dedup=True``): when selection is shared across
    the query heads of a KV group (cfg.group_reduce != "none"), the grid
    iterates KV heads and the query tile fuses the whole group,
    (group * block_q, d) — each K/V block is fetched once per *KV head*,
    cutting DMA traffic by the group factor (8x on glm4-9b).

VMEM per program: q tile (group x block x d) + k/v tiles (block x d) + acc
(group * block_q x d fp32) + m/l vectors — ~0.5 MiB at B = 128, d = 128,
group 1 (double-buffered K/V included) and still < 4 MiB at group 8,
comfortably inside the ~16 MiB budget.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# No import cycle: repro.core.selection depends only on jax/numpy, and
# repro.core.sparse_attention defers its kernels import to call time.
from repro.core.selection import revisit_indices
from repro.backend import resolve_interpret

NEG_INF = -1e30


def _sparse_kernel(
    idx_ref, cnt_ref,          # scalar prefetch (SMEM)
    q_ref, k_ref, v_ref,       # VMEM tiles
    o_ref,
    acc_ref, m_ref, l_ref,     # VMEM scratch
    *,
    scale: float,
    block_q: int,
    block_k: int,
    group: int,
    sel_heads: int,
):
    bh = pl.program_id(0)
    i = pl.program_id(1)
    s = pl.program_id(2)
    bi = bh // sel_heads
    hi = bh % sel_heads
    rows = group * block_q

    @pl.when(s == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    cnt = cnt_ref[bi, hi, i]

    @pl.when(s < cnt)
    def _compute():
        j = idx_ref[bi, hi, i, s]
        # (group, bq, d) -> fused (group * bq, d) query tile.
        q = q_ref[0, ...].reshape(rows, q_ref.shape[-1])
        q = q.astype(jnp.float32) * scale
        k = k_ref[0, 0, ...].astype(jnp.float32)          # (bk, d)
        sc = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        # Row r of the fused tile is query position i*bq + (r % bq) (the
        # group axis is the leading tile dim, so positions repeat per head).
        r = jax.lax.broadcasted_iota(jnp.int32, (rows, block_k), 0)
        q_pos = i * block_q + jax.lax.rem(r, block_q)
        k_pos = j * block_k + jax.lax.broadcasted_iota(jnp.int32, (rows, block_k), 1)
        causal = k_pos <= q_pos
        sc = jnp.where(causal, sc, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, sc.max(axis=-1))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(sc - m_new[:, None])
        p = jnp.where(causal, p, 0.0)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=-1)
        v = v_ref[0, 0, ...].astype(jnp.float32)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        acc_ref[...] = acc_ref[...] * corr[:, None] + pv
        m_ref[...] = m_new

    # Ragged finalize: each row writes its output at its *own* last live
    # slot; the trailing dead steps touch nothing (and fetch nothing, thanks
    # to the revisit index map).  max() guards pathological cnt == 0 rows.
    @pl.when(s == jnp.maximum(cnt - 1, 0))
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-20)
        out = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)
        o_ref[0, ...] = out.reshape(group, block_q, o_ref.shape[-1])


@functools.partial(
    jax.jit, static_argnames=("block_size", "scale", "interpret", "group_dedup")
)
def block_sparse_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    indices: jnp.ndarray,
    slot_mask: jnp.ndarray,
    *,
    block_size: int = 128,
    scale: float | None = None,
    interpret: bool | None = None,
    group_dedup: bool = False,
    live_counts: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Sparse attention over selected key blocks.

    Args:
      q: (b, hq, n, d); k, v: (b, hk, n_k, d).
      indices: (b, h_sel, nq, k_max) int32 selected key-block ids, where
        h_sel = hq normally or hk with ``group_dedup`` (selection shared
        across each KV group, e.g. one head sliced out per group).
      slot_mask: (b, h_sel, nq, k_max) bool validity of each slot.  Live
        slots must form a prefix (the select_blocks contract); the kernel
        consumes the per-row count, not the mask.
      live_counts: (b, h_sel, nq) int32 per-row live-slot counts
        (BlockSelection.live_counts); derived from slot_mask when omitted.
      block_size: B (query and key tiles share it, as in the paper).
      group_dedup: fetch K/V once per KV head with a fused
        (group * block_q, d) query tile; requires identical selection across
        each group (cfg.group_reduce != "none").

    Returns:
      (b, hq, n, d) attention output.
    """
    b, hq, n, d = q.shape
    _, hk, n_k, _ = k.shape
    dv = v.shape[-1]
    nq = n // block_size
    k_max = indices.shape[-1]
    scale = (d ** -0.5) if scale is None else scale

    sel_heads = indices.shape[1]
    if group_dedup:
        if sel_heads != hk:
            raise ValueError(f"group_dedup expects {hk} selection heads, got {sel_heads}")
        group = hq // hk
        kv_div = 1
    else:
        if sel_heads != hq:
            raise ValueError(f"expected {hq} selection heads, got {sel_heads}")
        group = 1
        kv_div = hq // hk

    cnt = (slot_mask.astype(jnp.int32).sum(axis=-1)
           if live_counts is None else live_counts.astype(jnp.int32))
    idx = revisit_indices(indices, slot_mask)
    # (b, hk, group, n, d) -> grid rows over selection heads, fused q tile.
    qr = q.reshape(b, sel_heads, group, n, d).reshape(b * sel_heads, group, n, d)

    def q_map(bh, i, s, idx_ref, cnt_ref):
        return (bh, 0, i, 0)

    def kv_map(bh, i, s, idx_ref, cnt_ref):
        bi = bh // sel_heads
        hi = bh % sel_heads
        j = idx_ref[bi, hi, i, s]
        return (bi, hi // kv_div, j, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b * sel_heads, nq, k_max),
        in_specs=[
            pl.BlockSpec((1, group, block_size, d), q_map),
            pl.BlockSpec((1, 1, block_size, d), kv_map),
            pl.BlockSpec((1, 1, block_size, dv), kv_map),
        ],
        out_specs=pl.BlockSpec((1, group, block_size, dv), q_map),
        scratch_shapes=[
            pltpu.VMEM((group * block_size, dv), jnp.float32),
            pltpu.VMEM((group * block_size,), jnp.float32),
            pltpu.VMEM((group * block_size,), jnp.float32),
        ],
    )

    kernel = functools.partial(
        _sparse_kernel,
        scale=scale,
        block_q=block_size,
        block_k=block_size,
        group=group,
        sel_heads=sel_heads,
    )

    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b * sel_heads, group, n, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=resolve_interpret(interpret),
        name="stem_block_sparse_attention",
    )(idx, cnt, qr, k, v)
    return out.reshape(b, sel_heads * group, n, dv)
