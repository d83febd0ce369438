"""Multi-head / grouped-query attention layer with pluggable sparse policy.

Modes:
  * ``full``   — training / prefill over a whole sequence.  Dense flash-style
    attention by default; when a sparsity policy is supplied (a
    ``SparsityPolicy``, a registered policy name, or a legacy ``StemConfig``)
    and the layer is causal self-attention, the policy-sparse path
    (core/sparse_attention.sparse_attention) is used — the paper's technique
    as a first-class integration point, with per-layer policy overrides
    supported at the transformer level.
  * ``decode`` — one new token against a KV cache (global or ring/windowed).
  * ``cross``  — encoder-decoder cross attention (whisper).

Local (windowed) attention runs as a chunked band so FLOPs scale with
N * window rather than N^2 — required for recurrentgemma's 500k decode cell.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core import policy as policy_lib
from repro.core.config import StemConfig
from repro.core.decode import DEFAULT_BUDGET_FRAC
from repro.core.sparse_attention import (dense_attention, dense_attention_auto,
                                          sparse_attention)
from repro.models import common


class KVCache(NamedTuple):
    k: jnp.ndarray        # (b, hk, L, dh)
    v: jnp.ndarray
    pos: jnp.ndarray      # int32 next write position: scalar (uniform batch)
                          # or (b,) per-sequence (ragged/continuous batching)


def init(ini: common.Initializer, cfg: ArchConfig) -> dict:
    d, h, hk, dh = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    p = {
        "wq": ini.normal((d, h, dh), ("embed", "heads", "head_dim")),
        "wk": ini.normal((d, hk, dh), ("embed", "kv_heads", "head_dim")),
        "wv": ini.normal((d, hk, dh), ("embed", "kv_heads", "head_dim")),
        "wo": ini.normal((h, dh, d), ("heads", "head_dim", "embed")),
    }
    if cfg.qkv_bias:
        p["bq"] = ini.zeros((h, dh), ("heads", "head_dim"))
        p["bk"] = ini.zeros((hk, dh), ("kv_heads", "head_dim"))
        p["bv"] = ini.zeros((hk, dh), ("kv_heads", "head_dim"))
    if cfg.qk_norm:
        p["q_norm"] = ini.zeros((dh,), ("head_dim",))
        p["k_norm"] = ini.zeros((dh,), ("head_dim",))
    return p


def _project(params, x, cfg: ArchConfig, positions, *, use_rope: bool = True):
    q = jnp.einsum("bsd,dhk->bhsk", x, params["wq"])
    k = jnp.einsum("bsd,dhk->bhsk", x, params["wk"])
    v = jnp.einsum("bsd,dhk->bhsk", x, params["wv"])
    if cfg.qkv_bias:
        q = q + params["bq"][None, :, None, :]
        k = k + params["bk"][None, :, None, :]
        v = v + params["bv"][None, :, None, :]
    if cfg.qk_norm:
        q = common.rms_norm(q, params["q_norm"])
        k = common.rms_norm(k, params["k_norm"])
    if use_rope:
        q = common.apply_rope(q, positions, cfg.rope_theta, cfg.rope_scaling)
        k = common.apply_rope(k, positions, cfg.rope_theta, cfg.rope_scaling)
    return q, k, v


def local_attention(q, k, v, window: int):
    """Banded sliding-window attention, chunked so cost is O(N * 2w).

    q, k, v: (b, h, n, d) with n % window == 0 (configs guarantee this).
    Each query chunk of length w attends to its own and the previous chunk
    with an exact |i-j| < w mask.
    """
    b, h, n, d = q.shape
    w = window
    if n <= w:
        return _masked_window_dense(q, k, v, w)
    n_orig = n
    if n % w:
        # pad to a window multiple; padded queries are sliced off and padded
        # keys sit strictly in the future of every real query (causal band).
        pad = w - n % w
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
        n = n + pad
    nc = n // w
    qc = q.reshape(b, h, nc, w, d)
    kc = k.reshape(b, h, nc, w, d)
    vc = v.reshape(b, h, nc, w, d)
    k_prev = jnp.concatenate([jnp.zeros_like(kc[:, :, :1]), kc[:, :, :-1]], axis=2)
    v_prev = jnp.concatenate([jnp.zeros_like(vc[:, :, :1]), vc[:, :, :-1]], axis=2)
    kk = jnp.concatenate([k_prev, kc], axis=3)          # (b,h,nc,2w,d)
    vv = jnp.concatenate([v_prev, vc], axis=3)
    s = jnp.einsum("bhcqd,bhckd->bhcqk", qc.astype(jnp.float32), kk.astype(jnp.float32))
    s = s * (d ** -0.5)
    qi = jnp.arange(w)[:, None] + w                     # position within 2w band
    kj = jnp.arange(2 * w)[None, :]
    mask = (kj <= qi) & (kj > qi - w)
    first_chunk = jnp.arange(nc)[:, None, None] == 0
    valid = jnp.where(first_chunk, mask & (kj >= w), mask)
    s = jnp.where(valid[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhcqk,bhckd->bhcqd", p, vv.astype(jnp.float32))
    return o.reshape(b, h, n, d)[:, :, :n_orig].astype(q.dtype)


def _masked_window_dense(q, k, v, window: int):
    n = q.shape[2]
    qi = jnp.arange(n)[:, None]
    kj = jnp.arange(n)[None, :]
    mask = (kj <= qi) & (kj > qi - window)
    b, hq = q.shape[0], q.shape[1]
    return dense_attention(q, k, v, causal=True,
                           mask=jnp.broadcast_to(mask, (b, hq, n, n)))


def apply_full(
    params,
    x: jnp.ndarray,
    cfg: ArchConfig,
    *,
    positions: jnp.ndarray,
    stem_cfg=None,
    window: Optional[int] = None,
    use_rope: bool = True,
    causal: bool = True,
    return_stats: bool = False,
):
    """Training / prefill attention over the full sequence.

    ``stem_cfg``: SparsityPolicy | registered policy name | StemConfig |
    None (dense).  ``return_stats`` additionally returns the realized
    ``StemStats`` of the sparse path (None when the dense/local path ran) —
    the transformer's per-layer density diagnostics use this.
    """
    pol = policy_lib.as_policy_opt(stem_cfg)
    q, k, v = _project(params, x, cfg, positions, use_rope=use_rope)
    stats = None
    if window is not None:
        group = q.shape[1] // k.shape[1]
        o = local_attention(q, jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1), window)
    elif pol is not None and causal and x.shape[1] % pol.block_size == 0 \
            and x.shape[1] // pol.block_size >= 2:
        if return_stats:
            o, stats = sparse_attention(q, k, v, pol, return_stats=True)
        else:
            o = sparse_attention(q, k, v, pol)
    else:
        o = dense_attention_auto(q, k, v, causal=causal)
    out = jnp.einsum("bhsk,hkd->bsd", o, params["wo"])
    return (out, stats) if return_stats else out


def apply_decode(
    params,
    x: jnp.ndarray,                  # (b, 1, d) — one new token
    cfg: ArchConfig,
    cache: KVCache,
    *,
    window: Optional[int] = None,
    use_rope: bool = True,
    stem_cfg=None,
    budget_frac: float = DEFAULT_BUDGET_FRAC,
) -> tuple[jnp.ndarray, KVCache]:
    """One decode step against the cache (ring buffer when windowed).

    ``cache.pos`` may be a scalar (every row at the same length — the seed
    behaviour) or a ``(b,)`` vector (ragged batch: each sequence writes and
    masks at its own length; rope uses the per-row position).

    With ``stem_cfg`` (any policy spelling; global attention only) the step
    is POLICY-SPARSE over the contiguous cache: the cache is re-summarized
    per step (O(L) — a test/reference arm, not a serving path) and the
    policy's metric + budget rule select blocks exactly as the paged
    engine's ``apply_decode_paged`` does over pages.  This is the
    fixed-batch differential reference for every registered policy."""
    pos = cache.pos
    b = x.shape[0]
    if stem_cfg is not None:
        # Validate before any projection work: the sparse path summarizes
        # the cache at block granularity, so its capacity must be a block
        # multiple.
        if window is not None:
            raise NotImplementedError(
                "policy-sparse decode needs global attention, not windowed")
        pol = policy_lib.as_policy(stem_cfg)
        L0 = cache.k.shape[2]
        if L0 % pol.block_size != 0:
            raise ValueError(
                f"policy-sparse decode needs the cache capacity to be a "
                f"multiple of the policy block size, but cache len {L0} % "
                f"block {pol.block_size} != 0. Allocate the cache padded to "
                f"a block/page multiple — ceil(max_len / {pol.block_size}) "
                f"* {pol.block_size} — as the paged engine does with whole "
                f"pages (per-row valid lengths may still be ragged; only "
                f"the buffer capacity must align).")
    rope_pos = pos[None] if pos.ndim == 0 else pos[:, None]      # (1,)|(b,1)
    q, k_new, v_new = _project(params, x, cfg, rope_pos, use_rope=use_rope)
    L = cache.k.shape[2]
    posv = jnp.broadcast_to(pos, (b,))                           # (b,)
    if window is None:
        ck, cv = common.update_cache(cache.k, cache.v, pos, k_new, v_new)
        valid = jnp.arange(L)[None, :] <= posv[:, None]          # (b, L)
    else:
        ck, cv = common.update_ring_cache(cache.k, cache.v, pos, k_new, v_new, L)
        slot_age = posv[:, None] - ((posv[:, None] - jnp.arange(L)[None, :]) % L)
        valid = (slot_age >= 0) & (slot_age > posv[:, None] - L)
    if stem_cfg is not None:
        from repro.core import decode as decode_lib

        summary = decode_lib.summarize_cache(ck, cv, pol)
        o = decode_lib.sparse_decode_attention(
            q, ck, cv, summary, posv + 1, pol, budget_frac=budget_frac)
        out = jnp.einsum("bhsk,hkd->bsd", o.astype(x.dtype), params["wo"])
        return out, KVCache(k=ck, v=cv, pos=pos + 1)
    h = q.shape[1]
    hk = ck.shape[1]
    group = h // hk
    s = jnp.einsum("bhgd,bhkd->bhgk",
                   q[:, :, 0].reshape(b, hk, group, -1).astype(jnp.float32),
                   ck.astype(jnp.float32)) * (cfg.head_dim ** -0.5)
    s = jnp.where(valid[:, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgk,bhkd->bhgd", p, cv.astype(jnp.float32))
    o = o.reshape(b, h, 1, cfg.head_dim).astype(x.dtype)
    out = jnp.einsum("bhsk,hkd->bsd", o, params["wo"])
    return out, KVCache(k=ck, v=cv, pos=pos + 1)


def apply_decode_paged(
    params,
    x: jnp.ndarray,                  # (slots, 1, d) — one new token per slot
    cfg: ArchConfig,
    pool,                            # PagePool: stacked layers or one layer's
    page_table: jnp.ndarray,         # (slots, max_pages) global page ids
    cache_lens: jnp.ndarray,         # (slots,) tokens already cached
    stem_cfg,                        # any policy spelling (see apply_full)
    *,
    layer=None,                      # this layer's index into ``pool``
    budget_frac: float = DEFAULT_BUDGET_FRAC,
    executor: Optional[str] = None,  # paged backend (None = policy.executor)
    use_rope: bool = True,
):
    """One decode step against the paged Stem KV cache.

    Appends the new token's K/V (+ summary increments) to each slot's
    current page, then runs OAM page selection + exact attention over the
    selected pages only.  ``budget_frac=1.0`` (the shared default) is the
    dense-equivalent oracle arm (every valid page attends).  ``executor``
    picks the paged backend — "xla" gather oracle or the fused "pallas"
    kernels.  ``pool`` holds every layer's leaves stacked ``(n, hk, P,
    ...)`` and this layer is written and read at ``layer`` in place; with
    ``layer=None`` it is one layer's pool.  Returns (out, new_pool)."""
    from repro.runtime import paged as paged_lib

    from repro.sharding import serving as serving_lib

    stem_cfg = policy_lib.as_policy(stem_cfg)
    lens = jnp.asarray(cache_lens, jnp.int32)
    with jax.named_scope("stem.qkv"):
        q, k_new, v_new = _project(params, x, cfg, lens[:, None],
                                   use_rope=use_rope)
        # Under the tensor-parallel head-sharding context the full
        # projections above are computed replicated; each shard keeps its
        # contiguous block of (query and KV) heads, appends/attends
        # shard-local against its pool slice, and the per-head outputs are
        # all-gathered back into full head order before the (replicated)
        # output projection — bitwise identical to the single-device step.
        # All three calls are no-ops outside a mesh.
        q = serving_lib.local_heads(q, axis=1)
        k_new = serving_lib.local_heads(k_new, axis=1)
        v_new = serving_lib.local_heads(v_new, axis=1)
    pool = paged_lib.append_token(pool, page_table, lens, k_new, v_new,
                                  stem_cfg, layer=layer)
    o = paged_lib.paged_sparse_decode(q, pool, page_table, lens + 1, stem_cfg,
                                      budget_frac=budget_frac,
                                      executor=executor, layer=layer)
    with jax.named_scope("stem.attend"):
        o = serving_lib.gather_heads(o, axis=1)
    with jax.named_scope("stem.o_proj"):
        out = jnp.einsum("bhsk,hkd->bsd", o.astype(x.dtype), params["wo"])
    return out, pool


def apply_chunk_paged(
    params,
    x: jnp.ndarray,                  # (slots, C, d) — one prefill chunk per slot
    cfg: ArchConfig,
    pool,                            # PagePool: stacked layers or one layer's
    page_table: jnp.ndarray,         # (slots, max_pages) global page ids
    chunk_start: jnp.ndarray,        # (slots,) absolute chunk start positions
    true_len: jnp.ndarray,           # (slots,) true prompt lengths
    budgets: jnp.ndarray,            # (slots, C // block) absolute-row budgets
    stem_cfg,                        # any policy spelling (see apply_full)
    *,
    layer=None,                      # this layer's index into ``pool``
    k_max: int = 0,                  # static gather width (0 = max_pages)
    executor: Optional[str] = None,  # paged backend (None = policy.executor)
    use_rope: bool = True,
):
    """One chunked-prefill step against the paged Stem KV cache.

    Writes the chunk's K/V pages + summaries first (``write_chunk_pages``),
    then runs the policy's chunked selection + exact attention over history
    *and* in-chunk pages uniformly (``core.chunked``), with rope, TPD
    budgets and sink/local floors all at absolute positions — so any chunk
    size is selection-equivalent to one-shot prefill.  Slots without a
    chunk this step carry an all-zero page table row (writes land in the
    trash page; outputs are ignored).  ``pool`` and ``layer`` as in
    ``apply_decode_paged``.  Returns (out, new_pool)."""
    from repro.core import chunked as chunked_lib
    from repro.runtime import paged as paged_lib
    from repro.sharding import serving as serving_lib

    stem_cfg = policy_lib.as_policy(stem_cfg)
    c = x.shape[1]
    with jax.named_scope("stem.qkv"):
        positions = chunk_start[:, None] + jnp.arange(c)[None, :]  # (slots, C)
        q, k_new, v_new = _project(params, x, cfg, positions,
                                   use_rope=use_rope)
        # Same TP head slicing as apply_decode_paged: replicated
        # projections, shard-local chunk write + selection + attention,
        # all-gather before wo.
        q = serving_lib.local_heads(q, axis=1)
        k_new = serving_lib.local_heads(k_new, axis=1)
        v_new = serving_lib.local_heads(v_new, axis=1)
    pool = paged_lib.write_chunk_pages(pool, page_table, chunk_start, k_new,
                                       v_new, true_len, stem_cfg, layer=layer)
    o = chunked_lib.chunked_prefill_attention(q, pool, page_table,
                                              chunk_start, budgets, stem_cfg,
                                              k_max, executor=executor,
                                              layer=layer)
    with jax.named_scope("stem.attend"):
        o = serving_lib.gather_heads(o, axis=1)
    with jax.named_scope("stem.o_proj"):
        out = jnp.einsum("bhsk,hkd->bsd", o.astype(x.dtype), params["wo"])
    return out, pool


# ---------------------------------------------------------------------------
# Cross attention (encoder-decoder)
# ---------------------------------------------------------------------------

def init_cross(ini: common.Initializer, cfg: ArchConfig) -> dict:
    d, h, dh = cfg.d_model, cfg.num_heads, cfg.head_dim
    return {
        "wq": ini.normal((d, h, dh), ("embed", "heads", "head_dim")),
        "wk": ini.normal((d, h, dh), ("embed", "heads", "head_dim")),
        "wv": ini.normal((d, h, dh), ("embed", "heads", "head_dim")),
        "wo": ini.normal((h, dh, d), ("heads", "head_dim", "embed")),
    }


def cross_kv(params, enc_out: jnp.ndarray):
    """Precompute cross-attention K/V from encoder output (b, F, d)."""
    k = jnp.einsum("bsd,dhk->bhsk", enc_out, params["wk"])
    v = jnp.einsum("bsd,dhk->bhsk", enc_out, params["wv"])
    return k, v


def apply_cross(params, x: jnp.ndarray, ck: jnp.ndarray, cv: jnp.ndarray,
                head_dim: int) -> jnp.ndarray:
    """Bidirectional cross attention: decoder x attends encoder K/V."""
    q = jnp.einsum("bsd,dhk->bhsk", x, params["wq"])
    o = dense_attention_auto(q, ck, cv, causal=False, scale=head_dim ** -0.5)
    return jnp.einsum("bhsk,hkd->bsd", o, params["wo"])


def init_cache(cfg: ArchConfig, batch: int, max_len: int,
               window: Optional[int] = None, dtype=jnp.bfloat16) -> KVCache:
    L = min(max_len, window) if window else max_len
    shape = (batch, cfg.num_kv_heads, L, cfg.head_dim)
    return KVCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype),
                   pos=jnp.zeros((), jnp.int32))


def prefill_into_cache(
    params, x, cfg: ArchConfig, *, positions, max_len: int,
    stem_cfg=None, window: Optional[int] = None,
    use_rope: bool = True,
):
    """Prefill attention AND return the populated cache for decode.
    ``stem_cfg`` accepts any policy spelling (see ``apply_full``)."""
    stem_cfg = policy_lib.as_policy_opt(stem_cfg)
    q, k, v = _project(params, x, cfg, positions, use_rope=use_rope)
    if window is not None:
        group = q.shape[1] // k.shape[1]
        o = local_attention(q, jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1), window)
        L = min(max_len, window)
        # Keep the trailing `window` keys, aligned to their ring slots
        # (position p lives at slot p % L).
        n = x.shape[1]
        ck = jnp.roll(k[:, :, -L:], shift=(n % L), axis=2)
        cv = jnp.roll(v[:, :, -L:], shift=(n % L), axis=2)
    else:
        if stem_cfg is not None and x.shape[1] % stem_cfg.block_size == 0 \
                and x.shape[1] // stem_cfg.block_size >= 2:
            o = sparse_attention(q, k, v, stem_cfg)
        else:
            o = dense_attention_auto(q, k, v, causal=True)
        L = max_len
        pad = L - k.shape[2]
        ck = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        cv = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    out = jnp.einsum("bhsk,hkd->bsd", o, params["wo"])
    cache = KVCache(k=ck, v=cv, pos=jnp.asarray(x.shape[1], jnp.int32))
    return out, cache
