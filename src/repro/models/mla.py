"""Multi-head Latent Attention (DeepSeek-V2/V3) with Stem integration.

MLA compresses K/V into a small latent c_kv (512) plus a shared 64-dim
rotary key.  The KV cache stores only (c_kv, k_rope) — that *is* the
memory win.  Queries come through a low-rank down/up projection
(``q_lora_rank``, DeepSeek-V3) or straight from ``wq`` (``q_lora_rank``
None, DeepSeek-V2-Lite).  With YaRN rope the softmax scale is
``(nope + rope)^-0.5 * yarn_mscale(factor, mscale_all_dim)^2``.

Paged serving (``apply_decode_paged`` / ``apply_chunk_paged``, the MLA
lanes of ``transformer.paged_mixed_step``).  Each layer's page pool is a
``runtime.paged.LatentPool``: one latent "KV head" per token, ``c_kv``
after its norm beside ``k_rope`` after rotary (zero-padded to the lane
width, ``latent_width``), with Stem's summaries of
that latent (stride-group means; the value term is the latent proxy
``log ||c_kv||``, DESIGN.md §5).  Both lanes attend absorbed: each head's
query becomes ``[q_nope W_UK, q_rope]``, scored against the stored latent,
the probabilities weight ``c_kv`` (PV in the latent), and ``W_UV`` then
``wo`` follow.  The heads' Stem page scores are reduced over the heads
(``core.policy.latent_group_reduce``), so a slot keeps one page set per
layer and query-block row and reads each kept page once, never a copy per
head.  The chunk lane attends absorbed too: a query-block row (one page,
128 queries) selects its own pages.  Expanding a kept page into per-head
keys and values costs ``rank * h * (nope + v)`` MACs a token, once;
absorbed attention costs ``h * (2 rank - nope - v)`` more MACs per (query,
key) than expanded, so expanding pays only above ``rank (nope + v) /
(2 rank - nope - v)`` = 171 queries a page at DeepSeek's widths.  The latent lanes run the XLA
path under either paged executor.

Stem integration in full prefill (paper §3, the DSA + Stem experiment):
the TPD schedule wraps block selection over the expanded keys, and OAM's
value-magnitude term uses ||c_j|| as the latent proxy for ||W_UV c_j||
(W_UV is shared across positions so rankings are preserved up to its
spectrum — noted in DESIGN.md §5).
"""
from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, MLAConfig
from repro.core import policy as policy_lib
from repro.core.sparse_attention import dense_attention_auto, sparse_attention
from repro.models import common


class MLACache(NamedTuple):
    c_kv: jnp.ndarray     # (b, L, kv_rank) compressed latents
    k_rope: jnp.ndarray   # (b, L, rope_dim) shared rotary key
    pos: jnp.ndarray


def init(ini: common.Initializer, cfg: ArchConfig) -> dict:
    m: MLAConfig = cfg.mla
    d, h = cfg.d_model, cfg.num_heads
    dh_q = m.nope_head_dim + m.rope_head_dim
    if m.q_lora_rank is None:
        q = {"wq": ini.normal((d, h, dh_q), ("embed", "heads", "head_dim"))}
    else:
        q = {
            "w_dq": ini.normal((d, m.q_lora_rank), ("embed", "q_lora")),
            "q_norm": ini.zeros((m.q_lora_rank,), ("q_lora",)),
            "w_uq": ini.normal((m.q_lora_rank, h, dh_q),
                               ("q_lora", "heads", "head_dim")),
        }
    return {
        **q,
        "w_dkv": ini.normal((d, m.kv_lora_rank), ("embed", "kv_lora")),
        "kv_norm": ini.zeros((m.kv_lora_rank,), ("kv_lora",)),
        "w_uk": ini.normal((m.kv_lora_rank, h, m.nope_head_dim), ("kv_lora", "heads", "head_dim")),
        "w_uv": ini.normal((m.kv_lora_rank, h, m.v_head_dim), ("kv_lora", "heads", "head_dim")),
        "w_kr": ini.normal((d, m.rope_head_dim), ("embed", "head_dim")),
        "wo": ini.normal((h, m.v_head_dim, d), ("heads", "head_dim", "embed")),
    }


def logit_gain(cfg: ArchConfig) -> float:
    """Factor on the queries that turns the default ``(nope + rope)^-0.5``
    softmax scale into the model's: YaRN's ``mscale(factor,
    mscale_all_dim)^2``, else 1."""
    y = cfg.rope_scaling
    if y is None or not y.mscale_all_dim:
        return 1.0
    return common.yarn_mscale(y.factor, y.mscale_all_dim) ** 2


def _queries(params, x, cfg: ArchConfig, positions):
    m = cfg.mla
    if m.q_lora_rank is None:
        q = jnp.einsum("bsd,dhk->bhsk", x, params["wq"])
    else:
        cq = common.rms_norm(jnp.einsum("bsd,dr->bsr", x, params["w_dq"]),
                             params["q_norm"])
        q = jnp.einsum("bsr,rhk->bhsk", cq, params["w_uq"])
    q_nope, q_rope = q[..., : m.nope_head_dim], q[..., m.nope_head_dim :]
    q_rope = common.apply_rope(q_rope, positions, cfg.rope_theta,
                               cfg.rope_scaling)
    return q_nope, q_rope


def _latents(params, x, cfg: ArchConfig, positions):
    c = common.rms_norm(jnp.einsum("bsd,dr->bsr", x, params["w_dkv"]), params["kv_norm"])
    kr = jnp.einsum("bsd,dk->bsk", x, params["w_kr"])
    kr = common.apply_rope(kr[:, None], positions, cfg.rope_theta,
                           cfg.rope_scaling)[:, 0]
    return c, kr


# A latent row is stored padded with zeros to a multiple of the TPU's 128
# lanes: the tiled layout pads it there anyway, and with an unaligned minor
# dim XLA copies the whole stacked pool around each token's scatter.
LANES = 128


def latent_width(cfg: ArchConfig) -> int:
    """Stored width of a latent row: ``c_kv``, ``k_rope``, zero padding."""
    m = cfg.mla
    return -(-(m.kv_lora_rank + m.rope_head_dim) // LANES) * LANES


def _absorbed(params, x, cfg: ArchConfig, positions):
    """Absorbed queries qt (b, h, s, W), scaled so ``qt . latent / sqrt(W)``
    is the softmax logit, and the tokens' latents (b, s, W): ``c_kv``, then
    ``k_rope``, then zeros to ``W = latent_width(cfg)``."""
    m = cfg.mla
    q_nope, q_rope = _queries(params, x, cfg, positions)
    c, kr = _latents(params, x, cfg, positions)
    q_abs = jnp.einsum("bhsk,rhk->bhsr", q_nope, params["w_uk"])
    width = latent_width(cfg)
    pad = [(0, 0)] * (x.ndim - 1) + [(0, width - m.kv_lora_rank - m.rope_head_dim)]
    gain = logit_gain(cfg) * math.sqrt(width / (m.nope_head_dim + m.rope_head_dim))
    qt = jnp.concatenate([q_abs, q_rope], axis=-1) * gain
    qt = jnp.pad(qt, [(0, 0)] + pad).astype(x.dtype)
    return qt, jnp.pad(jnp.concatenate([c, kr], axis=-1), pad)


def _expand(params, c, kr, cfg: ArchConfig):
    """Expand latents to per-head keys/values; concat the shared rope key."""
    k_nope = jnp.einsum("bsr,rhk->bhsk", c, params["w_uk"])
    v = jnp.einsum("bsr,rhk->bhsk", c, params["w_uv"])
    kr_h = jnp.broadcast_to(kr[:, None], (kr.shape[0], cfg.num_heads) + kr.shape[1:])
    k = jnp.concatenate([k_nope, kr_h], axis=-1)
    return k, v


def apply_full(
    params, x, cfg: ArchConfig, *, positions,
    stem_cfg=None, return_stats: bool = False,
):
    """``stem_cfg``: SparsityPolicy | policy name | StemConfig | None."""
    m = cfg.mla
    pol = policy_lib.as_policy_opt(stem_cfg)
    q_nope, q_rope = _queries(params, x, cfg, positions)
    c, kr = _latents(params, x, cfg, positions)
    k, v = _expand(params, c, kr, cfg)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    if logit_gain(cfg) != 1.0:
        q = (q * logit_gain(cfg)).astype(x.dtype)
    scale = (m.nope_head_dim + m.rope_head_dim) ** -0.5
    stats = None
    if pol is not None and x.shape[1] % pol.block_size == 0 \
            and x.shape[1] // pol.block_size >= 2:
        if return_stats:
            o, stats = sparse_attention(q, k, v, pol, return_stats=True)
        else:
            o = sparse_attention(q, k, v, pol)
    else:
        o = dense_attention_auto(q, k, v, causal=True, scale=scale)
    out = jnp.einsum("bhsk,hkd->bsd", o, params["wo"])
    return (out, stats) if return_stats else out


def init_cache(cfg: ArchConfig, batch: int, max_len: int, dtype=jnp.bfloat16) -> MLACache:
    m = cfg.mla
    return MLACache(
        c_kv=jnp.zeros((batch, max_len, m.kv_lora_rank), dtype),
        k_rope=jnp.zeros((batch, max_len, m.rope_head_dim), dtype),
        pos=jnp.zeros((), jnp.int32),
    )


def prefill_into_cache(params, x, cfg: ArchConfig, *, positions, max_len: int,
                       stem_cfg=None):
    out = apply_full(params, x, cfg, positions=positions, stem_cfg=stem_cfg)
    c, kr = _latents(params, x, cfg, positions)
    pad = max_len - x.shape[1]
    cache = MLACache(
        c_kv=jnp.pad(c, ((0, 0), (0, pad), (0, 0))).astype(jnp.bfloat16),
        k_rope=jnp.pad(kr, ((0, 0), (0, pad), (0, 0))).astype(jnp.bfloat16),
        pos=jnp.asarray(x.shape[1], jnp.int32),
    )
    return out, cache


def apply_decode(params, x, cfg: ArchConfig, cache: MLACache):
    """One decode step.  Latent cache only: expand per step.  ``cache.pos``
    scalar or (b,) — per-row positions for ragged/continuous batching."""
    m = cfg.mla
    pos = cache.pos
    b = x.shape[0]
    rope_pos = pos[None] if pos.ndim == 0 else pos[:, None]
    q_nope, q_rope = _queries(params, x, cfg, rope_pos)
    c_new, kr_new = _latents(params, x, cfg, rope_pos)
    if pos.ndim == 0:
        ck = jax.lax.dynamic_update_slice_in_dim(
            cache.c_kv, c_new.astype(cache.c_kv.dtype), pos, axis=1)
        ckr = jax.lax.dynamic_update_slice_in_dim(
            cache.k_rope, kr_new.astype(cache.k_rope.dtype), pos, axis=1)
    else:
        bidx = jnp.arange(b)
        ck = cache.c_kv.at[bidx, pos].set(c_new[:, 0].astype(cache.c_kv.dtype))
        ckr = cache.k_rope.at[bidx, pos].set(kr_new[:, 0].astype(cache.k_rope.dtype))
    L = ck.shape[1]
    posv = jnp.broadcast_to(pos, (b,))
    valid = jnp.arange(L)[None, :] <= posv[:, None]

    # Absorbed attention: score = q_nope . (W_UK c) + q_rope . k_rope.
    q_abs = jnp.einsum("bhsk,rhk->bhsr", q_nope, params["w_uk"])   # (b,h,1,r)
    s = jnp.einsum("bhsr,blr->bhsl", q_abs.astype(jnp.float32), ck.astype(jnp.float32))
    s = s + jnp.einsum("bhsk,blk->bhsl", q_rope.astype(jnp.float32), ckr.astype(jnp.float32))
    s = s * ((m.nope_head_dim + m.rope_head_dim) ** -0.5 * logit_gain(cfg))
    s = jnp.where(valid[:, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o_lat = jnp.einsum("bhsl,blr->bhsr", p, ck.astype(jnp.float32))  # (b,h,1,r)
    o = jnp.einsum("bhsr,rhk->bhsk", o_lat.astype(x.dtype), params["w_uv"])
    out = jnp.einsum("bhsk,hkd->bsd", o, params["wo"])
    return out, MLACache(c_kv=ck, k_rope=ckr, pos=pos + 1)


def apply_decode_paged(params, x, cfg: ArchConfig, pool, page_table,
                       cache_lens, stem_cfg, *, layer=None,
                       budget_frac: float = 1.0, executor=None):
    """One decode token per slot against the paged latent cache (module
    docstring): write the token's latent, select the slot's pages once for
    all heads, attend absorbed.  ``pool`` is a ``LatentPool`` (stacked with
    ``layer``, or one layer's); ``executor`` has no latent kernel and is
    not read.  Returns (out (slots, 1, d), new pool)."""
    from repro.runtime import paged as paged_lib

    del executor
    policy = policy_lib.as_policy(stem_cfg)
    rank = cfg.mla.kv_lora_rank
    lens = jnp.asarray(cache_lens, jnp.int32)
    with jax.named_scope("stem.qkv"):
        qt, lat = _absorbed(params, x, cfg, lens[:, None])
    pool = paged_lib.append_latent(pool, page_table, lens, lat, rank, policy,
                                   layer=layer)
    o = paged_lib.paged_latent_decode(qt, pool, page_table, lens + 1, rank,
                                      policy, budget_frac, layer=layer)
    with jax.named_scope("stem.attend"), jax.named_scope("stem.mla_decode"):
        o = jnp.einsum("bhsr,rhk->bhsk", o.astype(x.dtype), params["w_uv"])
    with jax.named_scope("stem.o_proj"):
        out = jnp.einsum("bhsk,hkd->bsd", o, params["wo"])
    return out, pool


def apply_chunk_paged(params, x, cfg: ArchConfig, pool, page_table,
                      chunk_start, true_len, budgets, stem_cfg, *,
                      layer=None, k_max: int = 0, executor=None):
    """One prefill chunk per lane against the paged latent cache: write the
    chunk's latent pages, select each query-block row's pages once for all
    heads, attend absorbed (``attention.apply_chunk_paged``'s contract).
    Returns (out (lanes, C, d), new pool)."""
    from repro.runtime import paged as paged_lib

    del executor
    policy = policy_lib.as_policy(stem_cfg)
    rank = cfg.mla.kv_lora_rank
    with jax.named_scope("stem.qkv"):
        positions = chunk_start[:, None] + jnp.arange(x.shape[1])[None, :]
        qt, lat = _absorbed(params, x, cfg, positions)
    pool = paged_lib.write_latent_chunk(pool, page_table, chunk_start, lat,
                                        true_len, rank, policy, layer=layer)
    o = paged_lib.latent_chunk_attention(qt, pool, page_table, chunk_start,
                                         budgets, rank, policy, k_max,
                                         layer=layer)
    with jax.named_scope("stem.attend"), jax.named_scope("stem.mla_chunk"):
        o = jnp.einsum("bhsr,rhk->bhsk", o.astype(x.dtype), params["w_uv"])
    with jax.named_scope("stem.o_proj"):
        out = jnp.einsum("bhsk,hkd->bsd", o, params["wo"])
    return out, pool
