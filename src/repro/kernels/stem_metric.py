"""Fused metric-downsampling Pallas kernels (Algorithm 1, lines 4-6).

Two small memory-bound kernels that stream Q/K/V once through VMEM:

  * ``antidiag_pool``     — per 128-token block, the ``stride`` group-mean
    vectors used by separable anti-diagonal scoring (DESIGN.md §3).
  * ``value_magnitude``   — per block, max-pooled log ||V_j||_2.

Both read each HBM element exactly once (arithmetic intensity ~ O(1)), so a
fused single-pass kernel is the right TPU shape — the jnp fallback
materializes a (n, d) reshape + reduce which XLA usually also fuses, but the
kernel guarantees it and keeps the block layout aligned with the attention
kernel's 128-token granularity.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.backend import resolve_interpret


def _pool_kernel(x_ref, o_ref, *, block_size: int, stride: int):
    x = x_ref[0, ...].astype(jnp.float32)           # (block, d)
    d = x.shape[-1]
    xg = x.reshape(block_size // stride, stride, d)  # position p = g*stride + u
    o_ref[0, 0, ...] = xg.mean(axis=0).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_size", "stride", "interpret"))
def antidiag_pool(
    x: jnp.ndarray, *, block_size: int = 128, stride: int = 16, interpret: bool | None = None
) -> jnp.ndarray:
    """(b, h, n, d) -> (b, h, n/block, stride, d) group means."""
    b, h, n, d = x.shape
    nb = n // block_size
    xr = x.reshape(b * h, n, d)
    out = pl.pallas_call(
        functools.partial(_pool_kernel, block_size=block_size, stride=stride),
        grid=(b * h, nb),
        in_specs=[pl.BlockSpec((1, block_size, d), lambda bh, i: (bh, i, 0))],
        out_specs=pl.BlockSpec((1, 1, stride, d), lambda bh, i: (bh, i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, nb, stride, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=resolve_interpret(interpret),
        name="stem_antidiag_pool",
    )(xr)
    return out.reshape(b, h, nb, stride, d)


def _vmag_kernel(v_ref, o_ref, *, block_size: int):
    v = v_ref[0, ...].astype(jnp.float32)  # (block, d)
    sq = jnp.sum(v * v, axis=-1, keepdims=True)                # (block, 1)
    m = jnp.max(jnp.log(jnp.sqrt(jnp.maximum(sq, 1e-40))),
                axis=0, keepdims=True)                         # (1, 1)
    o_ref[0, 0] = jnp.broadcast_to(m, o_ref.shape[2:])


@functools.partial(jax.jit, static_argnames=("block_size", "interpret"))
def value_magnitude(
    v: jnp.ndarray, *, block_size: int = 128, interpret: bool | None = None
) -> jnp.ndarray:
    """(b, h, n, d) -> (b, h, n/block) block-max log ||V_j||_2."""
    b, h, n, d = v.shape
    nb = n // block_size
    vr = v.reshape(b * h, n, d)
    # Each block's scalar fills one lane-dense (1, 128) output row: an
    # out block whose trailing dims equal the array's satisfies Mosaic's
    # 8x128 tiling rule, where a (1, 1) block of an (nb, 1) array does not.
    out = pl.pallas_call(
        functools.partial(_vmag_kernel, block_size=block_size),
        grid=(b * h, nb),
        in_specs=[pl.BlockSpec((1, block_size, d), lambda bh, i: (bh, i, 0))],
        out_specs=pl.BlockSpec((1, 1, 1, 128), lambda bh, i: (bh, i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, nb, 1, 128), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=resolve_interpret(interpret),
        name="stem_value_magnitude",
    )(vr)
    return out[:, :, 0, 0].reshape(b, h, nb)
