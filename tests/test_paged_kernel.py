"""Differential + contract tests for the fused paged-attention kernels
(kernels/paged_attn.py) against the XLA gather oracle (runtime/paged.py,
core/chunked.py).

Both executors are reached through the public entry points
(``paged_sparse_decode`` / ``chunked_prefill_attention``) with the
``executor`` knob, exactly like the serving engine — so the differential
also pins the ``core/policy.py`` paged-executor registry dispatch.  The
Pallas side runs in interpret mode on the CPU backend (repro/backend.py);
the same tests compile to Mosaic on TPU.

Covers the ISSUE matrix: GQA groups {1, 2, 4}, unaligned per-slot cache
lengths (including zero-length trash slots), budget_frac {0.25, 1.0},
shared-prefix page tables (two slots aliasing leading physical pages),
antidiag/mean metric pooling, group_reduce none/mean, and the streaming
(content-free metric) policy.  Plus the decode zero-live-row contract
(TestZeroLiveRows — referenced from ``core/decode.attend_selected``) and
the REPRO_DEBUG_DECODE assert.
"""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # degrade to fixed-seed parametrized sampling
    from _hypothesis_compat import given, settings, st

from repro.core import chunked as chunked_lib
from repro.core import decode as decode_lib
from repro.core import policy as policy_lib
from repro.kernels import paged_attn  # noqa: F401  (registers "pallas")
from repro.runtime import paged as paged_lib

BS = 8        # block/page size for all test policies
STRIDE = 4
D = 8         # head dim
HQ = 4        # query heads (hk = HQ // group)
TOL = 1e-4

GROUPS = (1, 2, 4)
FRACS = (0.25, 1.0)


def _policy(name: str = "stem", **updates):
    base = dict(block_size=BS, stride=STRIDE, sink_blocks=1, local_blocks=1,
                min_budget_blocks=2)
    base.update(updates)
    return policy_lib.get_policy(name).with_updates(ignore_missing=True,
                                                    **base)


def test_pallas_executor_registered():
    assert "pallas" in policy_lib.available_paged_executors()
    assert "xla" in policy_lib.available_paged_executors()
    spec = policy_lib.get_paged_executor("pallas")
    assert spec.decode_fn is paged_attn.fused_paged_decode
    assert spec.chunk_fn is paged_attn.fused_paged_chunk


# ---------------------------------------------------------------------------
# Decode lane
# ---------------------------------------------------------------------------

def _decode_pool(rng, lens, hk, npages, pol, shared_prefix=0):
    """Pool + page table for len(lens) slots, npages pages each.  With
    ``shared_prefix=p`` slot 1 aliases slot 0's first p physical pages
    (the prefix cache's copy-on-write layout)."""
    b = len(lens)
    pool = paged_lib.init_pool(1 + b * npages, hk, BS, D, STRIDE)
    pt = np.zeros((b, npages), np.int32)
    kv = []
    for i in range(b):
        ids = 1 + i * npages + np.arange(npages, dtype=np.int32)
        pt[i] = ids
        k = rng.standard_normal((hk, npages * BS, D)).astype(np.float32)
        v = rng.standard_normal((hk, npages * BS, D)).astype(np.float32)
        kv.append((k, v))
    if shared_prefix:
        # identical prefix content, then alias the physical pages
        kv[1][0][:, : shared_prefix * BS] = kv[0][0][:, : shared_prefix * BS]
        kv[1][1][:, : shared_prefix * BS] = kv[0][1][:, : shared_prefix * BS]
        pt[1, :shared_prefix] = pt[0, :shared_prefix]
    for i in range(b):
        pool = paged_lib.write_prefill_pages(
            pool, jnp.asarray(pt[i]), jnp.asarray(kv[i][0]),
            jnp.asarray(kv[i][1]), jnp.asarray(int(lens[i]), jnp.int32), pol)
    return pool, jnp.asarray(pt)


def _decode_diff(group, lens, budget_frac, policy_name="stem", seed=0,
                 npages=4, shared_prefix=0):
    hk = HQ // group
    rng = np.random.default_rng(seed)
    pol = _policy(policy_name)
    pool, pt = _decode_pool(rng, lens, hk, npages, pol,
                            shared_prefix=shared_prefix)
    q = jnp.asarray(
        rng.standard_normal((len(lens), HQ, 1, D)).astype(np.float32))
    lens_a = jnp.asarray(lens, jnp.int32)
    ref = paged_lib.paged_sparse_decode(q, pool, pt, lens_a, pol,
                                        budget_frac, executor="xla")
    out = paged_lib.paged_sparse_decode(q, pool, pt, lens_a, pol,
                                        budget_frac, executor="pallas")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=TOL, rtol=0)
    return np.asarray(out), lens


@settings(max_examples=20, deadline=None)
@given(gi=st.integers(0, 2), fi=st.integers(0, 1),
       l0=st.integers(0, 32), l1=st.integers(0, 32),
       seed=st.integers(0, 1 << 16))
def test_decode_fused_matches_xla(gi, fi, l0, l1, seed):
    """Fused decode == XLA gather decode, per-slot ragged cache lengths
    (any alignment, including empty slots), both budget fractions, all
    GQA groups."""
    _decode_diff(GROUPS[gi], [l0, l1], FRACS[fi], seed=seed)


@pytest.mark.parametrize("group", GROUPS)
@pytest.mark.parametrize("budget_frac", FRACS)
def test_decode_shared_prefix_pages(group, budget_frac):
    """Two slots whose page tables alias the same leading physical pages
    (prefix-cache CoW): the kernel's scalar-prefetched indirection must
    fetch the shared pages for both rows."""
    _decode_diff(group, [29, 23], budget_frac, seed=7, shared_prefix=2)


def test_decode_streaming_policy():
    """Content-free metric: the fused path skips the scoring kernel and
    feeds a zero metric into the same selection — still must match."""
    _decode_diff(2, [17, 32, 5], 1.0, policy_name="streaming", seed=3)


class _OddMetric:
    """Behaves like RoutingMetric without being an instance of any class
    the kernel classifies — forces the full-XLA fallback branch."""

    stride = STRIDE

    def __init__(self):
        self._inner = policy_lib.RoutingMetric(stride=STRIDE)

    def prefill_scores(self, q, k, v, *, block_size):
        return self._inner.prefill_scores(q, k, v, block_size=block_size)

    def decode_scores(self, q, k_groups, v_mag):
        return self._inner.decode_scores(q, k_groups, v_mag)

    def chunk_scores(self, q, k_groups, v_mag, *, block_size):
        return self._inner.chunk_scores(q, k_groups, v_mag,
                                        block_size=block_size)


def test_decode_unsupported_metric_falls_back():
    """A metric class the kernel does not know routes to the XLA oracle
    inside the fused entry point (no crash, identical output)."""
    base = _policy()
    pol = base.__class__(metric=_OddMetric(), schedule=base.schedule,
                         selector=base.selector, block_size=BS, name="odd")
    assert paged_attn._metric_kind(pol.metric) is None
    rng = np.random.default_rng(0)
    pool, pt = _decode_pool(rng, [19, 11], 2, 4, pol)
    q = jnp.asarray(rng.standard_normal((2, HQ, 1, D)).astype(np.float32))
    lens = jnp.asarray([19, 11], jnp.int32)
    ref = paged_lib.paged_sparse_decode(q, pool, pt, lens, pol, 1.0,
                                        executor="xla")
    out = paged_lib.paged_sparse_decode(q, pool, pt, lens, pol, 1.0,
                                        executor="pallas")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=TOL)


# ---------------------------------------------------------------------------
# Chunk lane
# ---------------------------------------------------------------------------

def _chunk_diff(group, hist_pages, tail, policy_name="stem", seed=0,
                nc=2, pooling=None, group_reduce=None):
    """History pages + one written chunk, differential across executors.
    ``tail``: valid tokens of the chunk (1..nc*BS, any alignment)."""
    hk = HQ // group
    rng = np.random.default_rng(seed)
    updates = {}
    if pooling is not None:
        updates["pooling"] = pooling
    if group_reduce is not None:
        updates["group_reduce"] = group_reduce
    pol = _policy(policy_name, **updates)

    b = 2
    maxp = hist_pages + nc
    chunk = nc * BS
    pool = paged_lib.init_pool(1 + b * maxp, hk, BS, D, STRIDE)
    pt = np.zeros((b, maxp), np.int32)
    start = np.full((b,), hist_pages * BS, np.int32)
    true_len = np.asarray([start[0] + tail,
                           start[1] + max(1, tail - 3)], np.int32)
    for i in range(b):
        ids = 1 + i * maxp + np.arange(maxp, dtype=np.int32)
        pt[i] = ids
        if hist_pages:
            k = rng.standard_normal((hk, hist_pages * BS, D)).astype(np.float32)
            v = rng.standard_normal((hk, hist_pages * BS, D)).astype(np.float32)
            pool = paged_lib.write_prefill_pages(
                pool, jnp.asarray(ids[:hist_pages]), jnp.asarray(k),
                jnp.asarray(v), jnp.asarray(int(start[i]), jnp.int32), pol)
    kc = rng.standard_normal((b, hk, chunk, D)).astype(np.float32)
    vc = rng.standard_normal((b, hk, chunk, D)).astype(np.float32)
    pool = paged_lib.write_chunk_pages(
        pool, jnp.asarray(pt), jnp.asarray(start), jnp.asarray(kc),
        jnp.asarray(vc), jnp.asarray(true_len), pol)

    q = jnp.asarray(rng.standard_normal((b, HQ, chunk, D)).astype(np.float32))
    budgets = np.stack([
        chunked_lib.chunk_budget_rows(pol, maxp * BS, int(start[i]), nc)
        for i in range(b)])
    args = (q, pool, jnp.asarray(pt), jnp.asarray(start),
            jnp.asarray(budgets), pol)
    ref = chunked_lib.chunked_prefill_attention(*args, executor="xla")
    out = chunked_lib.chunked_prefill_attention(*args, executor="pallas")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=TOL, rtol=0)


@settings(max_examples=15, deadline=None)
@given(gi=st.integers(0, 2), hist=st.integers(0, 3),
       tail=st.integers(1, 2 * BS), seed=st.integers(0, 1 << 16))
def test_chunk_fused_matches_xla(gi, hist, tail, seed):
    """Fused chunk attention == XLA oracle for any history depth, any
    (unaligned) chunk tail, all GQA groups — in-chunk causal masking and
    history pages both exercised."""
    _chunk_diff(GROUPS[gi], hist, tail, seed=seed)


@pytest.mark.parametrize("group,pooling,group_reduce", [
    (1, "antidiag", None),
    (2, "antidiag", "mean"),
    (4, "mean", None),
])
def test_chunk_pooling_and_group_reduce(group, pooling, group_reduce):
    """Antidiag vs mean query pooling and GQA group_reduce variants route
    through the same kernel scoring + XLA-side reduce as the oracle."""
    _chunk_diff(group, 2, 11, pooling=pooling, group_reduce=group_reduce,
                seed=5)


def test_chunk_routing_metric_policy():
    _chunk_diff(2, 1, 13, policy_name="stem-sam", seed=9)


# ---------------------------------------------------------------------------
# Zero-live-row contract (referenced from core/decode.attend_selected)
# ---------------------------------------------------------------------------

class TestZeroLiveRows:
    """A slot with ``cache_lens == 0`` (trash slot riding in a serving
    batch) selects no live blocks and must return an *exact zero* output
    vector — not NaN, not garbage — on every executor."""

    @pytest.mark.parametrize("executor", ["xla", "pallas"])
    def test_paged_decode_empty_slot_exact_zero(self, executor):
        rng = np.random.default_rng(11)
        pol = _policy()
        lens = [37, 0, 13]
        pool, pt = _decode_pool(rng, lens, 2, 5, pol)
        q = jnp.asarray(rng.standard_normal((3, HQ, 1, D)).astype(np.float32))
        out = np.asarray(paged_lib.paged_sparse_decode(
            q, pool, pt, jnp.asarray(lens, jnp.int32), pol, 0.25,
            executor=executor))
        assert np.all(np.isfinite(out))
        assert np.all(out[1] == 0.0), "empty slot must be exactly zero"
        assert np.any(out[0] != 0.0) and np.any(out[2] != 0.0)

    def test_attend_selected_contract(self):
        """The fixed-batch core path honors the same contract."""
        rng = np.random.default_rng(2)
        pol = _policy()
        L = 4 * BS
        k = jnp.asarray(rng.standard_normal((2, 2, L, D)).astype(np.float32))
        v = jnp.asarray(rng.standard_normal((2, 2, L, D)).astype(np.float32))
        q = jnp.asarray(rng.standard_normal((2, HQ, 1, D)).astype(np.float32))
        summ = decode_lib.summarize_cache(k, v, pol)
        out = np.asarray(decode_lib.sparse_decode_attention(
            q, k, v, summ, jnp.asarray([27, 0], jnp.int32), pol, 0.25))
        assert np.all(np.isfinite(out))
        assert np.all(out[1] == 0.0)
        assert np.any(out[0] != 0.0)


class TestDebugAssert:
    """REPRO_DEBUG_DECODE=1 turns the silent-zero failure mode (non-empty
    cache, zero live selection) into a loud AssertionError."""

    def _degenerate_case(self):
        # no forced floors, no minimum budget, budget_frac 0 -> every row
        # with a non-empty cache selects zero live blocks
        pol = _policy(sink_blocks=0, local_blocks=0, min_budget_blocks=0)
        rng = np.random.default_rng(4)
        pool, pt = _decode_pool(rng, [21], HQ, 3, pol)
        q = jnp.asarray(rng.standard_normal((1, HQ, 1, D)).astype(np.float32))
        return q, pool, pt, pol

    def test_fires_on_zero_live_selection(self, monkeypatch):
        monkeypatch.setenv("REPRO_DEBUG_DECODE", "1")
        q, pool, pt, pol = self._degenerate_case()
        with pytest.raises(Exception, match="zero live"):
            out = paged_lib.paged_sparse_decode(
                q, pool, pt, jnp.asarray([21], jnp.int32), pol, 0.0,
                executor="xla")
            jax.block_until_ready(out)

    def test_silent_without_env(self, monkeypatch):
        monkeypatch.delenv("REPRO_DEBUG_DECODE", raising=False)
        q, pool, pt, pol = self._degenerate_case()
        out = np.asarray(paged_lib.paged_sparse_decode(
            q, pool, pt, jnp.asarray([21], jnp.int32), pol, 0.0,
            executor="xla"))
        assert np.all(out == 0.0)  # the documented silent-zero behaviour

    def test_empty_cache_rows_allowed(self, monkeypatch):
        """Trash slots (cache_lens == 0) must NOT trip the assert."""
        monkeypatch.setenv("REPRO_DEBUG_DECODE", "1")
        rng = np.random.default_rng(6)
        pol = _policy()
        pool, pt = _decode_pool(rng, [15, 0], 2, 3, pol)
        q = jnp.asarray(rng.standard_normal((2, HQ, 1, D)).astype(np.float32))
        out = paged_lib.paged_sparse_decode(
            q, pool, pt, jnp.asarray([15, 0], jnp.int32), pol, 0.25,
            executor="pallas")
        jax.block_until_ready(out)  # no raise


# ---------------------------------------------------------------------------
# Attention over gathered pages in their stored dtype
# ---------------------------------------------------------------------------

def _f32_attend(q, k, v, keep):
    """The float32-cast formula: q (..., nq, d), k and v (..., nk, d) cast
    to float32, masked softmax over ``keep`` (..., nq, nk); a query row
    with nothing kept gives zero."""
    q, k, v = (np.asarray(x, np.float32) for x in (q, k, v))
    s = q @ np.swapaxes(k, -1, -2) / np.float32(np.sqrt(q.shape[-1]))
    s = np.where(keep, s, -np.inf)
    m = np.max(s, axis=-1, keepdims=True)
    e = np.where(keep, np.exp(s - np.where(np.isfinite(m), m, 0)), 0)
    z = np.sum(e, axis=-1, keepdims=True)
    return (e / np.where(z > 0, z, 1)) @ v


def _bf16(rng, *shape):
    return jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)


# Output error allowed against the float32-cast formula, per element:
# one bf16 rounding of the output (2^-9 of it) and of the probabilities
# (2^-9 of the largest |v| they weigh).
def _bf16_tol(ref, v):
    return 2 ** -9 * (np.abs(ref) + np.max(np.abs(np.asarray(v, np.float32))))


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("hq,group", [(HQ, 1), (HQ, 2), (3, 1)])
def test_attend_selected_bf16_pages_match_f32_formula(hq, group, seed):
    """Decode lane over bf16 pages == the float32-cast formula up to bf16
    rounding of the output: dead slots, a partial last page, and a row
    with no live slot (exactly zero); query heads in pairs, or alone
    when their count is odd."""
    rng = np.random.default_rng(seed)
    b, hk, k_max, nblk, d = 3, hq // group, 3, 5, 16
    q = _bf16(rng, b, hq, 1, d)
    gk = _bf16(rng, b, hk, group, k_max, BS, d)
    gv = _bf16(rng, b, hk, group, k_max, BS, d)
    lens = np.asarray([nblk * BS - 3, 2 * BS + 1, 0], np.int32)
    idx = np.stack([rng.permutation(nblk)[:k_max]
                    for _ in range(b * hk * group)]).reshape(
                        b, hk, group, k_max)
    live = rng.random(idx.shape) < 0.7                  # dead slots
    live[0, :, :, 0], idx[0, :, :, 0] = True, nblk - 1  # the partial page
    live[1, 0, 0] = False      # a head of a non-empty row with none live
    live[2] = False                                     # the empty row
    sel = decode_lib.DecodeSelection(
        indices=jnp.asarray(idx, jnp.int32), live=jnp.asarray(live),
        budgets=jnp.full((b,), k_max, jnp.int32),
        n_valid=jnp.asarray(-(-lens // BS), jnp.int32))
    out = decode_lib.attend_selected(q, gk, gv, sel, jnp.asarray(lens), BS)
    assert out.dtype == jnp.bfloat16

    pos = idx[..., None] * BS + np.arange(BS)           # (b, hk, g, k, BS)
    keep = (pos < lens[:, None, None, None, None]) & live[..., None]
    ref = _f32_attend(
        np.asarray(q, np.float32).reshape(b, hk, group, 1, d),
        np.asarray(gk, np.float32).reshape(b, hk, group, k_max * BS, d),
        np.asarray(gv, np.float32).reshape(b, hk, group, k_max * BS, d),
        keep.reshape(b, hk, group, 1, k_max * BS)).reshape(b, hq, 1, d)
    got = np.asarray(out, np.float32)
    assert np.all(np.abs(got - ref) <= _bf16_tol(ref, gv))
    assert np.all(got[2] == 0.0) and np.all(got[1, :1] == 0.0)
    assert np.all(np.abs(got[0]).max(axis=-1) > 0)


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("group", [1, 2])
def test_attend_chunk_bf16_pages_match_f32_formula(group, seed):
    """Chunk lane over bf16 pages == the float32-cast formula up to bf16
    rounding of the output: dead slots, the chunk's own pages causal to
    the token, and a query-block row with no live page (exactly zero)."""
    rng = np.random.default_rng(seed)
    b, hk, nc, k_max, d = 2, HQ // group, 2, 3, 16
    hist = 2                                   # pages before the chunk
    c = nc * BS
    q = _bf16(rng, b, HQ, c, d)
    gk = _bf16(rng, b, hk, group, nc, k_max, BS, d)
    gv = _bf16(rng, b, hk, group, nc, k_max, BS, d)
    start = np.full((b,), hist * BS, np.int32)
    rows = hist + np.arange(nc)                 # absolute query-block rows
    idx = np.stack([rng.permutation(rows[j] + 1)[:k_max]
                    for _ in range(b * HQ) for j in range(nc)]).reshape(
                        b, HQ, nc, k_max)
    live = rng.random(idx.shape) < 0.7                  # dead slots
    idx[..., 0] = rows                          # each row's own page ...
    live[..., 0] = True                         # ... partly causal
    live[1, 0, 1] = False                       # a row with nothing live
    sel = chunked_lib.ChunkSelection(indices=jnp.asarray(idx, jnp.int32),
                                     live=jnp.asarray(live))
    out = chunked_lib.attend_chunk(q, gk, gv, sel, jnp.asarray(start), BS)
    assert out.dtype == jnp.bfloat16

    tok = (idx[..., None] * BS + np.arange(BS)).reshape(b, HQ, nc, 1, -1)
    qpos = (start[:, None] + np.arange(c)).reshape(b, 1, nc, BS, 1)
    keep = (tok <= qpos) & np.repeat(live, BS, axis=-1)[:, :, :, None]
    as_heads = lambda x: np.asarray(x, np.float32).reshape(
        b, HQ, nc, k_max * BS, d)
    ref = _f32_attend(np.asarray(q, np.float32).reshape(b, HQ, nc, BS, d),
                      as_heads(gk), as_heads(gv), keep).reshape(b, HQ, c, d)
    got = np.asarray(out, np.float32)
    assert np.all(np.abs(got - ref) <= _bf16_tol(ref, gv))
    assert np.all(got[1, 0, BS:] == 0.0)
    assert np.all(np.abs(got[0]).max(axis=-1) > 0)


_STABLEHLO = re.compile(r"stablehlo\.(\w+)\"?[^\n]*-> tensor<([\dx]*)x(\w+)>$")


def _page_copies_cast_to_f32(text: str, page_elems: int) -> list:
    """``convert``s of a lowered module whose f32 result holds as many
    elements as one gathered page copy."""
    found = []
    for line in text.splitlines():
        m = _STABLEHLO.search(line.strip())
        if m and m.group(1) == "convert" and m.group(3) == "f32":
            dims = [int(x) for x in m.group(2).split("x") if x]
            if int(np.prod(dims)) == page_elems:
                found.append(line.strip())
    return found


@pytest.mark.parametrize("lane", ["decode", "chunk"])
@pytest.mark.parametrize("group", [1, 2])
def test_xla_executor_casts_no_gathered_page(lane, group):
    """The xla executor, lowered with bf16 pools, converts no gathered K/V
    page copy to float32: the pages enter the attention products as
    stored."""
    d, maxp, nc = 16, 6, 2              # d != BS keeps pages apart from p
    hk = HQ // group
    pol = _policy()
    f = jax.ShapeDtypeStruct
    pools = paged_lib.PagePool(
        k=f((2, hk, 20, BS, d), jnp.bfloat16),
        v=f((2, hk, 20, BS, d), jnp.bfloat16),
        kg=f((2, hk, 20, STRIDE, d), jnp.float32),
        vm=f((2, hk, 20), jnp.float32))
    b = 3
    layer, pt = f((), jnp.int32), f((b, maxp), jnp.int32)
    if lane == "decode":
        fn = lambda q, pools, layer, pt, lens: paged_lib._paged_decode_xla(
            q, pools, layer, pt, lens, pol, 0.5)
        args = (f((b, HQ, 1, d), jnp.bfloat16), pools, layer, pt,
                f((b,), jnp.int32))
        k_max = decode_lib.decode_budget_bound(maxp, pol, 0.5)
        rows = 1
    else:
        fn = lambda q, pools, layer, pt, start, budgets: \
            chunked_lib._chunked_prefill_xla(q, pools, layer, pt, start,
                                             budgets, pol, 0)
        args = (f((b, HQ, nc * BS, d), jnp.bfloat16), pools, layer, pt,
                f((b,), jnp.int32), f((b, nc), jnp.int32))
        k_max, rows = maxp, nc
    text = jax.jit(fn).lower(*args).as_text()
    page_elems = b * HQ * rows * k_max * BS * d
    assert f"{b}x{hk}x{group}x" in text and "bf16>" in text
    assert not _page_copies_cast_to_f32(text, page_elems)
