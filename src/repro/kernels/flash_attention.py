"""Dense causal flash attention as a Pallas TPU kernel.

This is the dense baseline of the paper (FlashAttention-2 role) expressed
TPU-natively:

  * grid = (batch * q_heads, num_q_blocks, num_k_blocks); the last grid
    dimension is sequential ("arbitrary") so the online-softmax state lives
    in VMEM scratch across key steps,
  * Q/K/V tiles are (block, head_dim) VMEM blocks (BlockSpec index maps fold
    the GQA head mapping: key/value blocks come from head h // group),
  * causal masking skips whole key blocks above the diagonal via
    ``@pl.when`` and applies an exact intra-block mask on the diagonal,
  * accumulation in fp32, output cast back to the input dtype.

VMEM working set per program (fp32): q(bq x d) + k,v(bk x d each, double
buffered) + acc(bq x d) + m,l(bq) — for bq = bk = 128, d <= 256 this is
< 1 MiB, far under the ~16 MiB/core budget; the MXU sees native 128-wide
matmuls.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.backend import resolve_interpret

NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref,  # VMEM tiles
    o_ref,                # output tile
    acc_ref, m_ref, l_ref,  # VMEM scratch
    *,
    scale: float,
    block_q: int,
    block_k: int,
    num_k_blocks: int,
):
    i = pl.program_id(1)  # query block
    j = pl.program_id(2)  # key block (sequential)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # Causal: key block j is admissible iff j <= i (aligned grids).
    @pl.when(j <= i)
    def _compute():
        q = q_ref[0, ...].astype(jnp.float32) * scale    # (bq, d)
        k = k_ref[0, 0, ...].astype(jnp.float32)         # (bk, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (bq, bk)

        # Exact intra-block causal mask on the diagonal block.
        q_pos = i * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        k_pos = j * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        s = jnp.where(k_pos <= q_pos, s, NEG_INF)

        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(k_pos <= q_pos, p, 0.0)
        l_ref[...] = l_prev * corr + p.sum(axis=-1)
        v = v_ref[0, 0, ...].astype(jnp.float32)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        acc_ref[...] = acc_ref[...] * corr[:, None] + pv
        m_ref[...] = m_new

    @pl.when(j == num_k_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-20)
        o_ref[0, ...] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("block_q", "block_k", "scale", "interpret"),
)
def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    block_q: int = 128,
    block_k: int = 128,
    scale: float | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Causal flash attention.  q: (b, hq, n, d); k, v: (b, hk, n, d)."""
    b, hq, n, d = q.shape
    _, hk, nk_len, _ = k.shape
    dv = v.shape[-1]
    if n != nk_len:
        raise ValueError("flash_attention requires seq_q == seq_k (causal self-attn)")
    if n % block_q or n % block_k:
        raise ValueError("sequence length must be divisible by block sizes")
    group = hq // hk
    scale = (d ** -0.5) if scale is None else scale
    num_q, num_k = n // block_q, n // block_k

    qr = q.reshape(b * hq, n, d)

    grid = (b * hq, num_q, num_k)
    kernel = functools.partial(
        _flash_kernel,
        scale=scale,
        block_q=block_q,
        block_k=block_k,
        num_k_blocks=num_k,
    )

    def q_map(bh, i, j):
        return (bh, i, 0)

    def kv_map(bh, i, j):
        # Fold GQA: query head bh % hq maps to kv head (bh % hq) // group.
        return (bh // hq, (bh % hq) // group, j, 0)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), q_map),
            pl.BlockSpec((1, 1, block_k, d), kv_map),
            pl.BlockSpec((1, 1, block_k, dv), kv_map),
        ],
        out_specs=pl.BlockSpec((1, block_q, dv), q_map),
        out_shape=jax.ShapeDtypeStruct((b * hq, n, dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, dv), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=resolve_interpret(interpret),
        name="dense_flash_attention",
    )(qr, k, v)
    return out.reshape(b, hq, n, dv)
