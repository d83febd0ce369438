"""Chunk-boundary equivalence of chunked prefill (core/chunked.py).

The load-bearing property: prefilling a prompt in chunks through the paged
pool — TPD budgets and sink/local floors evaluated at *absolute* query
positions, history scored from stored page summaries — must be
differentially equivalent to one-shot prefill (``prefill_kv_pages``), for
any chunk size (aligned or not to the prompt), any budget-driven policy,
and any GQA group.  Plus the page-summary lifecycle property: building a
prompt up chunk by chunk via ``write_chunk_pages`` reproduces the one-shot
``write_prefill_pages`` pooling page-for-page (extending the
``append_token == write_prefill_pages`` pin in ``tests/test_engine.py``).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # degrade to fixed-seed parametrized sampling
    from _hypothesis_compat import given, settings, st

from repro.configs.base import ArchConfig
from repro.core import chunked as chunked_lib
from repro.core import policy as policy_lib
from repro.core.config import StemConfig
from repro.models import registry, transformer
from repro.runtime import paged as paged_lib

BS = 8          # block/page size for all test policies

ARCH_BY_GROUP = {
    1: ArchConfig(name="chunk-tiny-g1", family="dense", num_layers=2,
                  d_model=32, num_heads=4, num_kv_heads=4, head_dim=8,
                  d_ff=64, vocab_size=64, qk_norm=True, dtype="float32"),
    4: ArchConfig(name="chunk-tiny-g4", family="dense", num_layers=2,
                  d_model=32, num_heads=4, num_kv_heads=1, head_dim=8,
                  d_ff=64, vocab_size=64, qk_norm=True, dtype="float32"),
}


def _policy(name: str):
    return policy_lib.get_policy(name).with_updates(
        block_size=BS, stride=4, sink_blocks=1, local_blocks=1,
        min_budget_blocks=2, ignore_missing=True)


@pytest.fixture(scope="module")
def built():
    out = {}
    for group, cfg in ARCH_BY_GROUP.items():
        bundle = registry.build(cfg)
        out[group] = (bundle, bundle.init_params(jax.random.PRNGKey(0)))
    return out


def _one_shot(params, cfg, pol, prompt, page_row, num_pages):
    pools = transformer.init_page_pools(cfg, num_pages, pol)
    npages_prompt = -(-len(prompt) // BS)
    toks = np.zeros((1, npages_prompt * BS), np.int32)
    toks[0, :len(prompt)] = prompt
    logits, pools = transformer.prefill_kv_pages(
        params, jnp.asarray(toks), jnp.asarray(len(prompt), jnp.int32),
        pools, jnp.asarray(page_row), cfg, pol)
    return np.asarray(logits), pools


def _chunked(params, cfg, pol, prompt, page_row, num_pages, chunk):
    """Drive the prompt through ``paged_mixed_step`` chunk lane, one lane,
    dummy (trash) decode lane — exactly the engine's dataflow."""
    pools = transformer.init_page_pools(cfg, num_pages, pol)
    pools = paged_lib.reset_pools_stacked(pools, jnp.asarray(page_row))
    plen = len(prompt)
    padded_len = -(-plen // BS) * BS
    ptoks = np.zeros((padded_len,), np.int32)
    ptoks[:plen] = prompt
    k_bound = chunked_lib.chunk_budget_bound(pol, len(page_row))
    nc = chunk // BS
    dec_tokens = jnp.zeros((1, 1), jnp.int32)
    dec_table = jnp.zeros((1, len(page_row)), jnp.int32)
    dec_lens = jnp.zeros((1,), jnp.int32)
    logits = None
    for t0 in range(0, padded_len, chunk):
        ctoks = np.zeros((1, chunk), np.int32)
        avail = ptoks[t0:t0 + chunk]
        ctoks[0, :len(avail)] = avail
        cbud = chunked_lib.chunk_budget_rows(pol, padded_len, t0, nc)[None]
        cd = {"tokens": jnp.asarray(ctoks),
              "page_table": jnp.asarray(page_row)[None],
              "start": jnp.asarray([t0], jnp.int32),
              "true_len": jnp.asarray([plen], jnp.int32),
              "budgets": jnp.asarray(cbud),
              "last": jnp.asarray([min(max(plen - 1 - t0, 0), chunk - 1)],
                                  jnp.int32)}
        _, logits, pools, _ = transformer.paged_mixed_step(
            params, dec_tokens, pools, dec_table, dec_lens, cfg,
            stem_cfg=pol, budget_frac=1.0, chunk=cd, chunk_k_max=k_bound)
    return np.asarray(logits)[0], pools


# Prompt 43 is deliberately awkward: padded to 48 (6 pages), partial final
# page, and 43 % chunk != 0 for every tested chunk size.
PROMPT_LEN = 43


@pytest.mark.parametrize("group", [1, 4])
@pytest.mark.parametrize("policy_name", ["stem", "uniform-sam", "dense"])
@pytest.mark.parametrize("chunk", [BS, 2 * BS, 3 * BS])
def test_chunked_matches_one_shot(built, group, policy_name, chunk):
    bundle, params = built[group]
    cfg = bundle.cfg
    pol = _policy(policy_name)
    rng = np.random.RandomState(17 + group)
    prompt = rng.randint(0, cfg.vocab_size, size=(PROMPT_LEN,)).astype(np.int32)
    npages_prompt = -(-PROMPT_LEN // BS)
    n_reserved = npages_prompt + 2          # a couple of decode-spill pages
    num_pages = 1 + n_reserved + 2          # spare pages stay untouched
    page_row = np.asarray(
        list(range(1, n_reserved + 1)), np.int32)

    ref_logits, ref_pools = _one_shot(params, cfg, pol, prompt, page_row,
                                      num_pages)
    got_logits, got_pools = _chunked(params, cfg, pol, prompt, page_row,
                                     num_pages, chunk)

    np.testing.assert_allclose(got_logits, ref_logits, atol=1e-4, rtol=1e-4)
    # The page pools must agree too — prompt pages *and* summaries (what
    # decode selection will read) are written identically.
    prompt_pages = page_row[:npages_prompt]
    for si in range(len(ref_pools)):
        for sub in ref_pools[si]:
            rp, gp = ref_pools[si][sub], got_pools[si][sub]
            for name in ("k", "v", "kg", "vm"):
                r = np.asarray(getattr(rp, name))[:, :, prompt_pages]
                g = np.asarray(getattr(gp, name))[:, :, prompt_pages]
                np.testing.assert_allclose(g, r, atol=1e-5, rtol=1e-5,
                                           err_msg=f"{sub}.{name}")


def test_threshold_selector_rejected():
    """Cumulative-mass selection has data-dependent budgets — chunked
    prefill must refuse it with a clear error (monolithic still serves it).
    """
    with pytest.raises(NotImplementedError, match="budget-driven"):
        chunked_lib.validate_chunked_policy(policy_lib.get_policy("xattention"))
    chunked_lib.validate_chunked_policy(policy_lib.get_policy("stem"))


# ---------------------------------------------------------------------------
# Page-summary lifecycle property: chunk-by-chunk == one-shot pooling
# ---------------------------------------------------------------------------

STEM = StemConfig(block_size=BS, sink_blocks=1, local_blocks=1,
                  min_budget_blocks=2, stride=4)


@settings(max_examples=25, deadline=None)
@given(
    seed=st.integers(0, 2**16),
    npages=st.integers(2, 6),
    chunk_pages=st.integers(1, 4),
    len_frac=st.floats(0.1, 1.0),
)
def test_chunk_summaries_match_one_shot(seed, npages, chunk_pages, len_frac):
    """Incremental per-chunk page writes (K/V, anti-diag group means, max
    log||V||) equal ``write_prefill_pages`` of the full sequence for every
    (chunk size, prompt length) — including prompts that end mid-page and
    chunk grids that overrun the prompt."""
    hk, d = 2, 16
    L = npages * BS
    plen = max(1, int(len_frac * L))
    chunk = chunk_pages * BS
    keys = jax.random.split(jax.random.PRNGKey(seed), 2)
    k = jax.random.normal(keys[0], (hk, L, d))
    v = jax.random.normal(keys[1], (hk, L, d))
    n_pool = npages + 2
    rng = np.random.RandomState(seed)
    page_ids = rng.permutation(np.arange(1, n_pool))[:npages].astype(np.int32)

    one = paged_lib.init_pool(1 + n_pool, hk, BS, d, STEM.stride)
    one = paged_lib.write_prefill_pages(one, jnp.asarray(page_ids), k, v,
                                        jnp.asarray(plen), STEM)

    grow = paged_lib.init_pool(1 + n_pool, hk, BS, d, STEM.stride)
    table = jnp.asarray(page_ids)[None]                   # (1, npages)
    for t0 in range(0, L, chunk):
        kc = np.zeros((1, hk, chunk, d), np.float32)
        vc = np.zeros((1, hk, chunk, d), np.float32)
        n_av = min(chunk, L - t0)
        kc[0, :, :n_av] = np.asarray(k[:, t0:t0 + n_av])
        vc[0, :, :n_av] = np.asarray(v[:, t0:t0 + n_av])
        grow = paged_lib.write_chunk_pages(
            grow, table, jnp.asarray([t0], jnp.int32), jnp.asarray(kc),
            jnp.asarray(vc), jnp.asarray([plen], jnp.int32), STEM)

    for got, want, name in zip(grow, one, ("k", "v", "kg", "vm")):
        np.testing.assert_allclose(
            np.asarray(got)[:, page_ids], np.asarray(want)[:, page_ids],
            rtol=1e-5, atol=1e-5, err_msg=name)
