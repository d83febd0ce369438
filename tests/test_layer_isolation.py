"""Layer isolation of the unified step's in-place pool writes.

The unified step (``transformer.paged_mixed_step``) carries every layer's
page pools as one stack through its layer scan, and each layer's lanes
write into and gather from that stack at ``[layer, head, page, ...]``.
This pins the addressing: after one step at 3 layers, each layer's pool
leaves equal, bit for bit, a reference that runs the same lane calls on
that layer's pool sliced out by hand, and every page the step does not
write keeps its contents in every layer.  The pools start from distinct
random contents per layer and head, so a write or read at the wrong layer
or head changes the result.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core import chunked as chunked_lib
from repro.core import policy as policy_lib
from repro.core.config import StemConfig
from repro.models import attention, common, mlp, registry, transformer
from repro.runtime import paged as paged_lib

CFG = ArchConfig(
    name="isolation-tiny", family="dense", num_layers=3, d_model=32,
    num_heads=4, num_kv_heads=2, head_dim=8, d_ff=64, vocab_size=64,
    qk_norm=True, dtype="float32",
)
POL = policy_lib.as_policy(StemConfig(block_size=8, sink_blocks=1,
                                      local_blocks=1, min_budget_blocks=2,
                                      stride=4))
BS, MAXP, CHUNK = 8, 4, 16
NUM_PAGES = 1 + 3 * MAXP
# Two decode slots and one chunk lane, each on its own pages; the slots
# append at in-page offsets 5 and 3, the chunk covers the lane's pages
# 2 and 3 (positions 16-31 of a 27-token prompt).
TABLE = np.array([[1, 2, 3, 4], [5, 6, 7, 8]], np.int32)
LENS = np.array([13, 19], np.int32)
CHUNK_ROW = np.array([[9, 10, 11, 12]], np.int32)
CHUNK_START, TRUE_LEN = 16, 27
BUDGET = 0.5


def _random_pools(key):
    """The engine's stacked pools filled with distinct random contents."""
    pools = transformer.init_page_pools(CFG, NUM_PAGES, POL)
    leaves, tree = jax.tree.flatten(pools)
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(tree, [
        jax.random.normal(k, l.shape, l.dtype) for k, l in zip(keys, leaves)])


def _chunk():
    nc = CHUNK // BS
    budgets = chunked_lib.chunk_budget_rows(POL, 4 * BS, CHUNK_START, nc)
    toks = np.random.RandomState(1).randint(0, CFG.vocab_size, (1, CHUNK))
    return {"tokens": jnp.asarray(toks, jnp.int32),
            "page_table": jnp.asarray(CHUNK_ROW),
            "start": jnp.full((1,), CHUNK_START, jnp.int32),
            "true_len": jnp.full((1,), TRUE_LEN, jnp.int32),
            "budgets": jnp.asarray(budgets, jnp.int32)[None],
            "last": jnp.full((1,), TRUE_LEN - 1 - CHUNK_START, jnp.int32)}


def _reference_step(params, tokens, pools, chunk, executor):
    """The step's layers one at a time, each lane call on the layer's own
    pool sliced out of the stack by hand.  Returns (decode logits, chunk
    logits | None, pools restacked)."""
    k_max = chunked_lib.chunk_budget_bound(POL, MAXP)
    x = common.embed_lookup(params["embed"], tokens, CFG.jnp_dtype)
    xc = (None if chunk is None else
          common.embed_lookup(params["embed"], chunk["tokens"],
                              CFG.jnp_dtype))

    def ffn(h, p):
        return h + mlp.apply(p["ffn"], common.rms_norm(h, p["norm2"]),
                             CFG.activation)

    (n, kinds), = transformer.layer_program(CFG)
    assert kinds == ("dense",)
    layers = []
    for layer in range(n):
        p = jax.tree.map(lambda t: t[layer], params["segment0"])["sub0"]
        pool = jax.tree.map(lambda t: t[layer], pools[0]["sub0"])
        if chunk is not None:
            mix_c, pool = attention.apply_chunk_paged(
                p["attn"], common.rms_norm(xc, p["norm1"]), CFG, pool,
                chunk["page_table"], chunk["start"], chunk["true_len"],
                chunk["budgets"], POL, k_max=k_max, executor=executor)
            xc = xc + mix_c
        mix, pool = attention.apply_decode_paged(
            p["attn"], common.rms_norm(x, p["norm1"]), CFG, pool,
            jnp.asarray(TABLE), jnp.asarray(LENS), POL,
            budget_frac=BUDGET, executor=executor)
        x = ffn(x + mix, p)
        if chunk is not None:
            xc = ffn(xc, p)
        layers.append(pool)
    stacked = [{"sub0": jax.tree.map(lambda *t: jnp.stack(t), *layers)}]
    dec = transformer._logits(params, x, CFG)[:, 0]
    chunk_logits = None
    if chunk is not None:
        xl = jnp.take_along_axis(xc, chunk["last"][:, None, None], axis=1)
        chunk_logits = transformer._logits(params, xl, CFG)[:, 0]
    return dec, chunk_logits, stacked


def _written_pages(mixed: bool):
    """Pages the step writes: each decode slot's current page, and the
    chunk lane's pages under the chunk."""
    pages = {int(TABLE[s, LENS[s] // BS]) for s in range(len(LENS))}
    if mixed:
        first = CHUNK_START // BS
        pages |= set(CHUNK_ROW[0, first:first + CHUNK // BS].tolist())
    return sorted(pages)


@pytest.mark.parametrize("mixed", [False, True], ids=["decode", "mixed"])
@pytest.mark.parametrize("executor", ["xla", "pallas"])
def test_layers_write_only_their_own_pool(executor, mixed):
    bundle = registry.build(CFG)
    params = bundle.init_params(jax.random.PRNGKey(0))
    pools = _random_pools(jax.random.PRNGKey(7))
    tokens = jnp.asarray([[3], [11]], jnp.int32)
    chunk = _chunk() if mixed else None

    step = jax.jit(lambda params, tokens, pools, chunk:
                   transformer.paged_mixed_step(
                       params, tokens, pools, jnp.asarray(TABLE),
                       jnp.asarray(LENS), CFG, stem_cfg=POL,
                       budget_frac=BUDGET, chunk=chunk,
                       chunk_k_max=chunked_lib.chunk_budget_bound(POL, MAXP),
                       executor=executor))
    dec, chunk_logits, out = step(params, tokens, pools, chunk)
    ref_dec, ref_chunk, ref = jax.jit(
        lambda params, tokens, pools, chunk: _reference_step(
            params, tokens, pools, chunk, executor))(
        params, tokens, pools, chunk)

    before, got, want = (jax.tree.map(np.asarray, t[0]["sub0"])
                         for t in (pools, out, ref))
    written = _written_pages(mixed)
    kept = [p for p in range(NUM_PAGES) if p not in written]
    for name in paged_lib.PagePool._fields:
        b, g, w = getattr(before, name), getattr(got, name), getattr(want, name)
        assert g.shape[0] == CFG.num_layers
        for layer in range(CFG.num_layers):
            np.testing.assert_array_equal(
                g[layer], w[layer], err_msg=f"{name} layer {layer}")
            np.testing.assert_array_equal(
                g[layer][:, kept], b[layer][:, kept],
                err_msg=f"{name} layer {layer}: unwritten pages changed")
            if name == "k":
                assert not np.array_equal(g[layer][:, written],
                                          b[layer][:, written]), (
                    f"layer {layer}: the step wrote no K")
    # The logits come out of two different programs (a scan against
    # unrolled layers), which XLA may fuse and round differently after the
    # last pool write; the pools above are the bit-exact check.
    np.testing.assert_allclose(np.asarray(dec), np.asarray(ref_dec),
                               rtol=1e-5, atol=1e-6)
    if mixed:
        np.testing.assert_allclose(np.asarray(chunk_logits),
                                   np.asarray(ref_chunk), rtol=1e-5, atol=1e-6)
