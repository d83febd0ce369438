"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs ref.py oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import StemConfig
from repro.core.sparse_attention import select_for
from repro.kernels import block_sparse_attn, flash_attention, ref, stem_metric


def _qkv(seed, b, hq, hk, n, d, dtype):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (
        jax.random.normal(ks[0], (b, hq, n, d), dtype),
        jax.random.normal(ks[1], (b, hk, n, d), dtype),
        jax.random.normal(ks[2], (b, hk, n, d), dtype),
    )


def _tol(dtype):
    return dict(atol=2e-6, rtol=2e-6) if dtype == jnp.float32 else dict(atol=3e-2, rtol=3e-2)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,hq,hk,n,d,bq,bk",
    [
        (1, 1, 1, 128, 32, 64, 64),
        (2, 4, 2, 256, 64, 64, 64),
        (1, 8, 1, 256, 128, 128, 128),   # MQA, head_dim 128
        (1, 2, 2, 512, 256, 128, 128),   # gemma-style head_dim 256
        (2, 2, 1, 384, 64, 128, 128),    # non-power-of-two block count
    ],
)
def test_flash_attention_sweep(b, hq, hk, n, d, bq, bk, dtype):
    q, k, v = _qkv(0, b, hq, hk, n, d, dtype)
    got = flash_attention.flash_attention(q, k, v, block_q=bq, block_k=bk)
    want = ref.flash_attention_ref(q, k, v)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), **_tol(dtype)
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,hq,hk,n,d,bs,frac",
    [
        (1, 2, 2, 256, 32, 64, 0.5),
        (2, 4, 2, 512, 64, 64, 0.3),
        (1, 4, 1, 512, 128, 128, 0.5),
        (1, 2, 2, 1024, 64, 128, 0.2),
    ],
)
def test_block_sparse_attention_sweep(b, hq, hk, n, d, bs, frac, dtype):
    q, k, v = _qkv(1, b, hq, hk, n, d, dtype)
    cfg = StemConfig(block_size=bs, k_start_frac=frac, mu=0.7, sink_blocks=1,
                     local_blocks=1, min_budget_blocks=1, stride=8)
    sel, _ = select_for(q, k, v, cfg)
    got = block_sparse_attn.block_sparse_attention(
        q, k, v, sel.indices, sel.slot_mask, block_size=bs)
    want = ref.block_sparse_attention_ref(q, k, v, sel.indices, sel.slot_mask, block_size=bs)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), **_tol(dtype)
    )


def test_block_sparse_full_budget_equals_flash():
    """With every block selected, the sparse kernel must equal dense flash."""
    q, k, v = _qkv(2, 1, 2, 2, 256, 64, jnp.float32)
    cfg = StemConfig(block_size=64, k_start_frac=1.0, mu=1.0, sink_blocks=0,
                     local_blocks=1, min_budget_blocks=0, stride=8)
    sel, _ = select_for(q, k, v, cfg)
    got = block_sparse_attn.block_sparse_attention(
        q, k, v, sel.indices, sel.slot_mask, block_size=64)
    want = flash_attention.flash_attention(q, k, v, block_q=64, block_k=64)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-6, rtol=3e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("bs,stride,d", [(64, 8, 32), (128, 16, 64), (128, 16, 128)])
def test_antidiag_pool_sweep(bs, stride, d, dtype):
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 3, 512, d), dtype)
    got = stem_metric.antidiag_pool(x, block_size=bs, stride=stride)
    want = ref.antidiag_pool_ref(x, bs, stride)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), **_tol(dtype)
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("bs,d", [(64, 32), (128, 64), (128, 256)])
def test_value_magnitude_sweep(bs, d, dtype):
    v = jax.random.normal(jax.random.PRNGKey(4), (1, 2, 512, d), dtype) * 3.0
    got = stem_metric.value_magnitude(v, block_size=bs)
    want = ref.value_magnitude_ref(v, bs)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=1e-5 if dtype == jnp.float32 else 3e-2, rtol=3e-2,
    )


def test_kernel_vmem_budget_static():
    """Static check: the declared VMEM working set fits a TPU core.

    q + k + v + out tiles + fp32 accumulators, double-buffered inputs —
    must stay well under the ~16 MiB VMEM of a v5e core for every tile
    configuration the configs use.
    """
    VMEM = 16 * 1024 * 1024
    for bs, d, in_bytes in [(128, 128, 2), (128, 256, 2), (128, 64, 4)]:
        tiles = 2 * (bs * d * in_bytes) * 2      # k + v, double buffered
        tiles += bs * d * in_bytes               # q
        tiles += bs * d * in_bytes               # out
        tiles += bs * d * 4 + 2 * bs * 4         # fp32 acc + m + l scratch
        assert tiles < 0.25 * VMEM, (bs, d, tiles)
