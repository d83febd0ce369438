"""Shared model building blocks (pure JAX, no flax).

Parameters are nested dicts of ``Param(value, axes)`` where ``axes`` is a
tuple of *logical* axis names consumed by sharding/rules.py.  ``unzip``
splits a param tree into a value tree (fed to jit) and an axes tree (used to
build NamedShardings); ``zip_trees`` re-attaches them.
"""
from __future__ import annotations

import math
from typing import Any, Callable, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp


class Param(NamedTuple):
    value: Any                 # jnp.ndarray | ShapeDtypeStruct
    axes: tuple[Optional[str], ...]


def is_param(x) -> bool:
    return isinstance(x, Param)


def unzip(tree):
    """Param tree -> (values, axes)."""
    values = jax.tree.map(lambda p: p.value, tree, is_leaf=is_param)
    axes = jax.tree.map(lambda p: p.axes, tree, is_leaf=is_param)
    return values, axes


def zip_trees(values, axes):
    return jax.tree.map(Param, values, axes, is_leaf=lambda x: x is None or isinstance(x, tuple))


class Initializer:
    """Splits one PRNG key on demand — keeps init functions linear to read."""

    def __init__(self, key: jax.Array, dtype=jnp.bfloat16):
        self._key = key
        self.dtype = dtype

    def next_key(self) -> jax.Array:
        self._key, sub = jax.random.split(self._key)
        return sub

    def normal(self, shape: Sequence[int], axes, scale: float | None = None,
               dtype=None) -> Param:
        fan_in = max(int(math.prod(shape[:-1])) or shape[-1], 1)
        scale = (1.0 / math.sqrt(fan_in)) if scale is None else scale
        v = jax.random.normal(self.next_key(), tuple(shape), jnp.float32) * scale
        return Param(v.astype(dtype or self.dtype), tuple(axes))

    def zeros(self, shape: Sequence[int], axes, dtype=None) -> Param:
        return Param(jnp.zeros(tuple(shape), dtype or self.dtype), tuple(axes))

    def ones(self, shape: Sequence[int], axes, dtype=None) -> Param:
        return Param(jnp.ones(tuple(shape), dtype or self.dtype), tuple(axes))

    def value(self, v: jnp.ndarray, axes) -> Param:
        return Param(v, tuple(axes))


# ---------------------------------------------------------------------------
# Normalization
# ---------------------------------------------------------------------------

def rms_norm(x: jnp.ndarray, weight: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + weight.astype(jnp.float32))).astype(dtype)


def layer_norm_simple(x: jnp.ndarray, weight: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    """Bias-free LayerNorm (whisper layers; bias dropped — noted in DESIGN)."""
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    mean = x32.mean(axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mean) * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + weight.astype(jnp.float32))).astype(dtype)


def layer_norm(x: jnp.ndarray, weight: jnp.ndarray, bias: jnp.ndarray,
               eps: float = 1e-5) -> jnp.ndarray:
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    mean = x32.mean(axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mean) * jax.lax.rsqrt(var + eps)
    return (y * weight.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dtype)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------

def yarn_mscale(scale: float, mscale: float = 1.0) -> float:
    """YaRN's attention temperature ``0.1 * mscale * ln(scale) + 1``."""
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def _yarn_correction_dim(rotations: float, dim: int, theta: float,
                         max_pos: int) -> float:
    return dim * math.log(max_pos / (rotations * 2 * math.pi)) / (
        2 * math.log(theta))


def rope_freqs(head_dim: int, theta: float, scaling=None) -> jnp.ndarray:
    """Inverse frequencies of the ``head_dim / 2`` rotated pairs.  With
    ``scaling`` (``configs.base.YarnScaling``) they blend YaRN's
    interpolated ``f / factor`` and extrapolated ``f`` over the linear ramp
    between the correction dims of ``beta_fast`` and ``beta_slow``
    rotations (DeepSeek-V2's ``DeepseekV2YarnRotaryEmbedding``)."""
    power = theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    if scaling is None:
        return 1.0 / power
    lo = max(math.floor(_yarn_correction_dim(
        scaling.beta_fast, head_dim, theta, scaling.original_max_position)), 0)
    hi = min(math.ceil(_yarn_correction_dim(
        scaling.beta_slow, head_dim, theta, scaling.original_max_position)),
        head_dim - 1)
    hi = hi + 0.001 if lo == hi else hi
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - lo)
                    / (hi - lo), 0.0, 1.0)
    extra = 1.0 - ramp                                # 1: keep f, 0: f / factor
    return 1.0 / (scaling.factor * power) * (1.0 - extra) + 1.0 / power * extra


def rope_mscale(scaling) -> float:
    """Scale of YaRN's cos/sin tables: mscale over mscale_all_dim (1 when
    they are equal, as in DeepSeek-V2)."""
    if scaling is None:
        return 1.0
    return (yarn_mscale(scaling.factor, scaling.mscale)
            / yarn_mscale(scaling.factor, scaling.mscale_all_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float = 10000.0,
               scaling=None) -> jnp.ndarray:
    """x: (..., seq, head_dim); positions: (seq,) or (batch, seq).  Rotates
    adjacent pairs ``(2i, 2i+1)`` by frequency ``i``; ``scaling`` as in
    ``rope_freqs``."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta, scaling)             # (d/2,)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..., seq, d/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if scaling is not None:
        cos, sin = cos * rope_mscale(scaling), sin * rope_mscale(scaling)
    # Broadcast over head dims: x is (b, h, s, d); angles (s, d/2) or (b, s, d/2).
    while cos.ndim < x.ndim:
        cos, sin = cos[..., None, :, :], sin[..., None, :, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    out = jnp.stack([y1, y2], axis=-1).reshape(x.shape)
    return out.astype(x.dtype)


def sinusoidal_positions(length: int, dim: int) -> jnp.ndarray:
    pos = jnp.arange(length, dtype=jnp.float32)[:, None]
    inv = jnp.exp(-jnp.arange(0, dim, 2, dtype=jnp.float32) * (math.log(10000.0) / dim))
    ang = pos * inv[None, :]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------------------
# Embedding / LM head / loss
# ---------------------------------------------------------------------------

def embed_init(ini: Initializer, vocab: int, d_model: int) -> Param:
    return ini.normal((vocab, d_model), ("vocab", "embed"), scale=0.02, dtype=jnp.float32)


def embed_lookup(table: jnp.ndarray, tokens: jnp.ndarray, dtype=jnp.bfloat16) -> jnp.ndarray:
    return jnp.take(table, tokens, axis=0).astype(dtype)


def lm_logits(x: jnp.ndarray, table: jnp.ndarray) -> jnp.ndarray:
    """Tied LM head: (b, s, d) @ (vocab, d)^T -> (b, s, vocab)."""
    return jnp.einsum("bsd,vd->bsv", x.astype(jnp.float32), table.astype(jnp.float32))


def cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray,
                  mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Mean CE over non-masked positions.  Vocab-sharding friendly: no
    full-vocab gather materialization beyond take_along_axis (GSPMD lowers it
    to a local gather + small collective on the sharded vocab axis)."""
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - tgt
    if mask is None:
        return nll.mean()
    mask = mask.astype(jnp.float32)
    return (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)


# ---------------------------------------------------------------------------
# KV-cache helpers (functional; caches are plain dicts of arrays)
# ---------------------------------------------------------------------------

def update_cache(cache_k: jnp.ndarray, cache_v: jnp.ndarray, pos: jnp.ndarray,
                 new_k: jnp.ndarray, new_v: jnp.ndarray):
    """Insert one step at position ``pos``.  cache: (b, hk, L, d);
    new: (b, hk, 1, d).  ``pos`` is a scalar (uniform batch) or a ``(b,)``
    vector (ragged batch — every row writes at its own length)."""
    if pos.ndim == 0:
        ck = jax.lax.dynamic_update_slice_in_dim(
            cache_k, new_k.astype(cache_k.dtype), pos, axis=2)
        cv = jax.lax.dynamic_update_slice_in_dim(
            cache_v, new_v.astype(cache_v.dtype), pos, axis=2)
        return ck, cv
    bidx = jnp.arange(cache_k.shape[0])
    ck = cache_k.at[bidx, :, pos].set(new_k[:, :, 0].astype(cache_k.dtype))
    cv = cache_v.at[bidx, :, pos].set(new_v[:, :, 0].astype(cache_v.dtype))
    return ck, cv


def update_ring_cache(cache_k, cache_v, pos, new_k, new_v, window: int):
    """Ring-buffer cache for windowed attention: O(window) memory at any
    sequence length (what makes recurrentgemma's 500k decode sub-quadratic).
    ``pos`` scalar or (b,) — see ``update_cache``."""
    slot = pos % window
    if pos.ndim == 0:
        ck = jax.lax.dynamic_update_slice_in_dim(
            cache_k, new_k.astype(cache_k.dtype), slot, axis=2)
        cv = jax.lax.dynamic_update_slice_in_dim(
            cache_v, new_v.astype(cache_v.dtype), slot, axis=2)
        return ck, cv
    bidx = jnp.arange(cache_k.shape[0])
    ck = cache_k.at[bidx, :, slot].set(new_k[:, :, 0].astype(cache_k.dtype))
    cv = cache_v.at[bidx, :, slot].set(new_v[:, :, 0].astype(cache_v.dtype))
    return ck, cv
