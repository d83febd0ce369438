"""Block-paged Stem KV cache: page pool, per-page summaries, paged decode.

The serving engine (``runtime/engine.py``) stores every attention layer's
KV cache in a shared *page pool* instead of per-sequence contiguous
buffers.  A page holds ``page_size`` tokens (= the Stem ``block_size``, so
a page **is** a Stem block) and carries the block-pooled representations —
the anti-diagonal K group means and the max-pooled log||V|| — alongside the
raw K/V.  That makes Stem's coarse-to-fine decode native to the paged
layout: the page table *is* the block index, OAM scores pages directly
from the pooled summaries, and only the selected pages are gathered.

Layout (one attention layer):

  k, v : (hk, num_pages, page_size, d)    raw cache tokens
  kg   : (hk, num_pages, stride, d)       anti-diag group means (fp32)
  vm   : (hk, num_pages)                  max-pooled log ||V||  (fp32)

The engine stacks every layer's pool along a leading ``(n_layers,)`` axis
and the unified step carries that stack through its layer scan: the write
paths and the paged executors take the stack plus a traced ``layer`` index
and scatter into / gather from ``[layer, head, page, ...]`` in place, so no
step slices a layer out of the stack or restacks it.  Each head is indexed
explicitly, which keeps every update window inside the minor page dims.

Page 0 is **reserved as the trash page**: inactive engine slots carry an
all-zero page table, so their (masked-out) decode writes land in page 0 and
never alias a live sequence.  The allocator never hands out page 0.

Per-slot logical state (page table row + cache length) lives *outside* the
pool and is passed to the jitted steps as plain ``(slots, max_pages)`` /
``(slots,)`` arrays — the pool itself is sequence-agnostic, which is what
makes admission/recycling a pure host-side page-table edit.
"""
from __future__ import annotations

import hashlib
from collections import Counter, OrderedDict
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import chunked as chunked_lib
from repro.core import decode as decode_lib
from repro.core import metric as metric_lib
from repro.core import policy as policy_lib
from repro.core.config import StemConfig  # noqa: F401  (legacy annotation)

TRASH_PAGE = 0

# ``cfg`` arguments below accept a legacy StemConfig, a SparsityPolicy or a
# registered policy name; the write paths only need ``block_size``/``stride``
# (duck-typed on both spellings), and the decode path routes metric +
# selection through the policy objects — the exact same ones the prefill
# and fixed-batch decode paths consume.


class PagePool(NamedTuple):
    """Paged KV + Stem summary storage: one attention layer's leaves as
    below, or every layer's stacked with a leading ``(n_layers,)`` axis
    (the engine's pools; address a layer by passing ``layer``)."""

    k: jnp.ndarray    # ([n,] hk, P, page, d)
    v: jnp.ndarray    # ([n,] hk, P, page, d)
    kg: jnp.ndarray   # ([n,] hk, P, stride, d) fp32 anti-diag group means
    vm: jnp.ndarray   # ([n,] hk, P) fp32 max-pooled log ||V||


def stack_layer(pool: PagePool) -> PagePool:
    """One layer's pool as a one-layer stack (address it with layer 0)."""
    return jax.tree.map(lambda t: t[None], pool)


def _head_index(hk: int, ndim: int) -> jnp.ndarray:
    """Explicit KV-head index, shaped (hk, 1, ..., 1) with ``ndim - 1``
    trailing ones, to index a pool beside page ids of that rank.  Indexing
    each head keeps a scatter's update window to the minor dims, so TPU
    layout assignment never relays out the whole stack for it."""
    return jnp.arange(hk, dtype=jnp.int32).reshape((hk,) + (1,) * (ndim - 1))


def _page_index(layer, hk: int, pages: jnp.ndarray) -> tuple:
    """Index of ``pages`` (a 1-D id array) in a pool leaf, broadcasting to
    (hk, len(pages)): ``[:, pages]`` in one layer's pool (``layer`` None),
    ``[layer, head, pages]`` with each head explicit in a stacked pool."""
    if layer is None:
        return (slice(None), pages)
    return (layer, _head_index(hk, 2), pages)


def init_pool(num_pages: int, num_kv_heads: int, page_size: int, head_dim: int,
              stride: int, dtype=jnp.float32) -> PagePool:
    hk, p = num_kv_heads, num_pages
    return PagePool(
        k=jnp.zeros((hk, p, page_size, head_dim), dtype),
        v=jnp.zeros((hk, p, page_size, head_dim), dtype),
        kg=jnp.zeros((hk, p, stride, head_dim), jnp.float32),
        vm=jnp.full((hk, p), decode_lib.V_MAG_FLOOR, jnp.float32),
    )


def reset_pages(pool: PagePool, page_ids: jnp.ndarray) -> PagePool:
    """Return pages to their pristine state (zero K/V and group means, vm at
    the norm floor).  Must run on every page a request reserves *before* its
    first write: the allocator recycles pages without touching the pool, and
    ``append_token``'s kg-add / vm-max increments assume a fresh page — a
    previous tenant's summaries would otherwise leak into OAM selection.
    Duplicate ids (e.g. trash-page padding) are harmless: every write is the
    same pristine value."""
    return PagePool(
        k=pool.k.at[:, page_ids].set(0),
        v=pool.v.at[:, page_ids].set(0),
        kg=pool.kg.at[:, page_ids].set(0),
        vm=pool.vm.at[:, page_ids].set(decode_lib.V_MAG_FLOOR),
    )


def write_prefill_pages(pool: PagePool, page_ids: jnp.ndarray,
                        k: jnp.ndarray, v: jnp.ndarray, true_len: jnp.ndarray,
                        cfg) -> PagePool:
    """Scatter one prefilled sequence's K/V + summaries into the pool.

    k, v: (hk, L, d) with L = len(page_ids) * page_size (right-padded
    prompt).  Positions >= true_len are zeroed before the write so page
    contents and summaries match the zero-padded-cache semantics that
    ``append_token`` extends incrementally.
    """
    cfg = policy_lib.as_policy(cfg)
    hk, L, d = k.shape
    bs = cfg.block_size
    npages = L // bs
    keep = (jnp.arange(L) < true_len)[None, :, None]
    k = jnp.where(keep, k, 0)
    v = jnp.where(keep, v, 0)
    kp = k.reshape(hk, npages, bs, d)
    vp = v.reshape(hk, npages, bs, d)
    kg = metric_lib.antidiag_pool(k, bs, cfg.stride)        # (hk, npages, s, d)
    vm = metric_lib.value_block_magnitude(v, bs)            # (hk, npages)
    return PagePool(
        k=pool.k.at[:, page_ids].set(kp.astype(pool.k.dtype)),
        v=pool.v.at[:, page_ids].set(vp.astype(pool.v.dtype)),
        kg=pool.kg.at[:, page_ids].set(kg.astype(jnp.float32)),
        vm=pool.vm.at[:, page_ids].set(vm.astype(jnp.float32)),
    )


def reset_pools_stacked(pools, page_ids: jnp.ndarray):
    """``reset_pages`` over the engine's per-layer pool tree (PagePool
    leaves stacked ``(n_layers, hk, P, ...)``).  Runs once per admission in
    the chunked engine: chunk writes fully rewrite the prompt pages, but the
    decode-spill pages and the chunk grid's overrun pages must start
    pristine (the allocator recycles pages dirty, and ``append_token``'s
    kg-add / vm-max increments assume fresh pages)."""
    def one(pool: PagePool) -> PagePool:
        return PagePool(
            k=pool.k.at[:, :, page_ids].set(0),
            v=pool.v.at[:, :, page_ids].set(0),
            kg=pool.kg.at[:, :, page_ids].set(0),
            vm=pool.vm.at[:, :, page_ids].set(decode_lib.V_MAG_FLOOR),
        )

    return jax.tree.map(one, pools,
                        is_leaf=lambda x: isinstance(x, PagePool))


def copy_pages_stacked(pools, src: jnp.ndarray, dst: jnp.ndarray):
    """Copy one page's full contents (K/V + kg/vm summaries) ``src`` -> ``dst``
    across every layer's pool — the device half of copy-on-write.  A write
    into a prefix-shared page first redirects the writer to a fresh page via
    ``PageAllocator.cow``; this op then duplicates the shared contents so the
    writer's view is unchanged while other tenants keep the original.

    src, dst: scalar global page ids (static or traced int32)."""
    def one(pool: PagePool) -> PagePool:
        return PagePool(
            k=pool.k.at[:, :, dst].set(pool.k[:, :, src]),
            v=pool.v.at[:, :, dst].set(pool.v[:, :, src]),
            kg=pool.kg.at[:, :, dst].set(pool.kg[:, :, src]),
            vm=pool.vm.at[:, :, dst].set(pool.vm[:, :, src]),
        )

    return jax.tree.map(one, pools,
                        is_leaf=lambda x: isinstance(x, PagePool))


def prefix_page_keys(tokens, budgets, page_size: int) -> list:
    """Chained content keys for every FULL page of a prompt.

    Page j's K/V (and summaries) at layer l>0 depend on the *entire* token
    prefix up to page j — not just page j's tokens — and chunked prefill's
    per-row sparsity budgets depend on the prompt's padded length (the TPD
    schedule allots budget by row position over the whole prompt).  So the
    key for page j chains: key_j = H(key_{j-1} || tokens[j*bs:(j+1)*bs] ||
    budget_row_j).  Two tenants share page j iff every token through page j
    AND every budget row through page j agree — exactly the condition under
    which the engine's chunked prefill writes bit-identical pages.

    tokens: int sequence (the prompt).  budgets: per-block prefill budget
    rows for the prompt's padded length (``policy.prefill_budgets``).  The
    partial tail page (len(tokens) % page_size != 0 remainder) gets no key:
    it is always privately held.
    """
    full = len(tokens) // page_size
    keys = []
    h = b"stem-prefix-v1"
    for j in range(full):
        page = np.asarray(
            tokens[j * page_size:(j + 1) * page_size], np.int32).tobytes()
        row = int(budgets[j]).to_bytes(4, "little")
        h = hashlib.blake2b(h + page + row, digest_size=16).digest()
        keys.append(h.hex())
    return keys


@jax.named_scope("stem.kv_write")
def write_chunk_pages(pool: PagePool, page_table: jnp.ndarray,
                      chunk_start: jnp.ndarray, k_chunk: jnp.ndarray,
                      v_chunk: jnp.ndarray, true_len: jnp.ndarray,
                      cfg, layer=None) -> PagePool:
    """Scatter one prefill *chunk* per slot into the pool, summaries included.

    The chunked-prefill write path: chunk starts are block-aligned and the
    chunk width is a page multiple, so every page a chunk touches is written
    whole — k/v zeroed at positions >= ``true_len`` (matching the
    zero-padded-cache semantics of ``write_prefill_pages``), kg/vm pooled
    from the zeroed chunk.  Building a prompt up chunk by chunk therefore
    reproduces ``write_prefill_pages`` of the full sequence page-for-page
    (pinned by ``tests/test_chunked.py``), and the partial final page is
    left exactly where ``append_token`` can continue it incrementally.

    page_table: (slots, max_pages) global page ids (all-zero rows for slots
    without a chunk this step — their writes land in the trash page).
    chunk_start, true_len: (slots,) int32 absolute positions.
    k_chunk, v_chunk: (slots, hk, C, d) with C % page_size == 0.
    Chunk-grid overrun past the prompt's pages writes the pristine value
    (zeros + the vm floor) into reserved-but-unused spill pages — harmless,
    decode has not started for a slot still prefilling.
    layer: None for one layer's pool, else the (traced) layer of a stacked
    pool to write in place at ``[layer, head, page]``.
    """
    cfg = policy_lib.as_policy(cfg)
    slots, hk, c, d = k_chunk.shape
    bs = cfg.block_size
    nc = c // bs
    pos = chunk_start[:, None] + jnp.arange(c)                  # (slots, C)
    keep = (pos < true_len[:, None])[:, None, :, None]
    k = jnp.where(keep, k_chunk, 0)
    v = jnp.where(keep, v_chunk, 0)
    kg = metric_lib.antidiag_pool(k, bs, cfg.stride)      # (slots, hk, nc, s, d)
    vm = metric_lib.value_block_magnitude(v, bs)          # (slots, hk, nc)
    kp = k.reshape(slots, hk, nc, bs, d)
    vp = v.reshape(slots, hk, nc, bs, d)

    maxp = page_table.shape[1]
    j_abs = chunk_start[:, None] // bs + jnp.arange(nc)[None, :]  # (slots, nc)
    # Chunk-grid blocks past the page-table width go to the trash page —
    # never clamp onto page maxp-1, which may hold real data from this very
    # chunk (all-zero payload either way: overrun positions are >= true_len).
    pids = jnp.where(
        j_abs < maxp,
        jnp.take_along_axis(page_table, jnp.minimum(j_abs, maxp - 1), axis=1),
        TRASH_PAGE)
    idx = _page_index(layer, hk, pids.reshape(-1))

    def per_head(x):
        # (slots, hk, nc, ...) -> (hk, slots*nc, ...) aligned with ``idx``.
        return jnp.swapaxes(x, 0, 1).reshape((hk, slots * nc) + x.shape[3:])

    return PagePool(
        k=pool.k.at[idx].set(per_head(kp).astype(pool.k.dtype)),
        v=pool.v.at[idx].set(per_head(vp).astype(pool.v.dtype)),
        kg=pool.kg.at[idx].set(per_head(kg).astype(jnp.float32)),
        vm=pool.vm.at[idx].set(per_head(vm).astype(jnp.float32)),
    )


@jax.named_scope("stem.kv_write")
def append_token(pool: PagePool, page_table: jnp.ndarray,
                 cache_lens: jnp.ndarray, k_new: jnp.ndarray,
                 v_new: jnp.ndarray, cfg, layer=None) -> PagePool:
    """Write one new token per slot into its current page + fold summaries.

    The increments reproduce ``write_prefill_pages`` of the grown sequence
    exactly (pinned by tests/test_engine.py): group means divide by the
    *full* group population (block_size / stride), so adding
    ``k_new / per_group`` into the token's group matches the batch pooling
    once the page fills — and the zero-dilution of a partial page in the
    meantime, which is the forced-local block anyway.

    page_table: (slots, max_pages) global page ids; cache_lens: (slots,)
    tokens already present (the new token lands at this position).
    k_new, v_new: (slots, hk, 1, d).  Slots whose page table points at the
    trash page (inactive) scribble page 0 harmlessly.  layer: None for one
    layer's pool, else the (traced) layer of a stacked pool to write in
    place at ``[layer, head, page, offset]``.
    """
    cfg = policy_lib.as_policy(cfg)
    hk = k_new.shape[1]
    bs, stride = cfg.block_size, cfg.stride
    per_group = bs // stride
    lens = jnp.asarray(cache_lens, jnp.int32)
    pids = jnp.take_along_axis(page_table, (lens // bs)[:, None], axis=1)[:, 0]
    offs = lens % bs
    kn = k_new[:, :, 0]                                     # (slots, hk, d)
    vn = v_new[:, :, 0]
    knh = jnp.swapaxes(kn, 0, 1)                            # (hk, slots, d)
    vnh = jnp.swapaxes(vn, 0, 1)
    log_norm = jnp.log(jnp.maximum(
        jnp.linalg.norm(vnh.astype(jnp.float32), axis=-1), 1e-20))
    page = _page_index(layer, hk, pids)                     # (hk, slots)
    return PagePool(
        k=pool.k.at[page + (offs,)].set(knh.astype(pool.k.dtype)),
        v=pool.v.at[page + (offs,)].set(vnh.astype(pool.v.dtype)),
        kg=pool.kg.at[page + (offs % stride,)].add(
            (knh / per_group).astype(jnp.float32)),
        vm=pool.vm.at[page].max(log_norm),
    )


def paged_sparse_decode(
    q: jnp.ndarray,             # (slots, hq, 1, d)
    pool: PagePool,
    page_table: jnp.ndarray,    # (slots, max_pages) global page ids
    cache_lens: jnp.ndarray,    # (slots,) valid tokens per slot
    cfg,
    budget_frac: float = decode_lib.DEFAULT_BUDGET_FRAC,
    executor: Optional[str] = None,
    layer=None,
) -> jnp.ndarray:
    """Policy-sparse decode attention straight off the page pool.

    Identical math to ``core.decode.sparse_decode_attention`` over the
    logical (page-table-ordered) cache.  At ``budget_frac=1.0`` (top-k
    selector, the shared default) this equals dense decode over each slot's
    prefix.  ``executor`` picks the paged backend from the
    ``core/policy.py`` registry — "xla" (the gather oracle below) or
    "pallas" (the fused scalar-prefetch kernels in
    ``kernels/paged_attn.py``); None defers to ``policy.executor``.
    ``pool`` is one layer's pool, or with ``layer`` a stacked pool read at
    that (traced) layer.
    """
    cfg = policy_lib.as_policy(cfg)
    spec = policy_lib.get_paged_executor(executor or cfg.executor)
    if layer is None:
        pool, layer = stack_layer(pool), 0
    return spec.decode_fn(q, pool, layer, page_table, cache_lens, cfg,
                          budget_frac)


def gather_summaries(pools: PagePool, layer, page_table: jnp.ndarray):
    """Per-slot page summaries of one layer of a stacked pool through the
    page table: kg rows (b, hk, maxp, s, d) and vm rows (b, hk, maxp)."""
    hk = pools.kg.shape[1]
    idx = (layer, _head_index(hk, 2)[None], page_table[:, None, :])
    return pools.kg[idx], pools.vm[idx]


def gather_pages(pools: PagePool, layer, gp: jnp.ndarray):
    """Selected K/V pages of one layer of a stacked pool: gp (b, hk, ...)
    global page ids -> k, v (b, hk, ..., page, d)."""
    hk = pools.k.shape[1]
    idx = (layer, _head_index(hk, gp.ndim - 1)[None], gp)
    return pools.k[idx], pools.v[idx]


def _paged_decode_xla(
    q: jnp.ndarray,
    pools: PagePool,
    layer,
    page_table: jnp.ndarray,
    cache_lens: jnp.ndarray,
    cfg,
    budget_frac: float,
) -> jnp.ndarray:
    """The XLA gather backend: summaries are gathered per slot via the page
    table, the policy's metric + budget rule select *logical* page slots per
    row, and only the selected pages are fetched from the pool.  Kept as the
    differential oracle for the fused kernel (and the CPU-friendly default):
    every stage is a separate inspectable XLA op.  A metric registered once
    in ``core/policy.py`` serves the engine with no paged-specific code.
    ``pools`` is the stacked pool, read at ``layer`` without slicing it.
    """
    cfg = policy_lib.as_policy(cfg)
    b, hq, _, d = q.shape
    hk = pools.k.shape[1]
    group = hq // hk
    bs = cfg.block_size
    maxp = page_table.shape[1]

    with jax.named_scope("stem.score"):
        # Gather per-slot summaries through the page table (cheap: pooled
        # reps).
        kg_rows, vm_rows = gather_summaries(pools, layer, page_table)
        m = decode_lib.decode_block_metric(q, kg_rows, vm_rows, cfg)

    with jax.named_scope("stem.select"):
        sel = decode_lib.select_decode_blocks(m, cache_lens, cfg, budget_frac)
        # Logical slot index -> global page id of each selected page.
        gp = jnp.take_along_axis(
            jnp.broadcast_to(page_table[:, None, None, :],
                             (b, hk, group, maxp)),
            sel.indices, axis=-1)                           # (b, hk, g, kmax)

    with jax.named_scope("stem.attend"):
        gk, gv = gather_pages(pools, layer, gp)             # (b,hk,g,kmax,bs,d)
        return decode_lib.attend_selected(q, gk, gv, sel, cache_lens, bs)


# The gather oracle is the registry's "xla" backend for both serving lanes
# (kernels/paged_attn.py registers "pallas").
policy_lib.register_paged_executor(
    "xla", decode_fn=_paged_decode_xla,
    chunk_fn=chunked_lib._chunked_prefill_xla,
    sharding="kv-head")


# ---------------------------------------------------------------------------
# Host-side page allocator (pure python; page 0 reserved)
# ---------------------------------------------------------------------------

class PageAllocator:
    """Ref-counted free-list page allocator with a hash-keyed prefix index.
    Page 0 (the trash page for inactive slots) is never handed out.

    Every page id is in exactly one of THREE places at all times — the free
    list, the cached set (registered prefix pages at refcount 0, contents
    retained for future hits, reclaimable LRU-first), or the allocated set
    (refcount >= 1) — and ``check_conservation`` asserts that partition plus
    refcount bookkeeping.  ``evict``/``restore`` are the preemption-facing
    spellings of ``free``/``alloc``: a victim's pages return to the free
    list while its contents move to host memory (``runtime/offload.py``),
    and re-admission draws a fresh (possibly different) set of physical
    pages to scatter the snapshot back into.

    Prefix caching (``runtime/engine.py`` drives this):

    * ``register(page, key)`` content-addresses a full prompt page by its
      chained hash (``prefix_page_keys``) once its contents are final.
    * ``probe(key)`` answers admission's per-page lookup; ``share(page)``
      takes a reference on a hit (reviving a cached page if needed).
    * ``free`` decrements: a page leaves the allocated set only at ref 0,
      and a *registered* page then parks in the cached set instead of the
      free list, so a later tenant with the same prefix still hits.
    * ``cow(page)`` is the bookkeeping half of copy-on-write: it redirects
      the caller's reference on a shared page to a freshly allocated private
      page (the device copy is ``copy_pages_stacked``).

    ``evict_policy`` picks which cached (ref-0) page ``alloc`` cannibalizes
    when the free list runs dry: "lru" (default, least-recently parked) or
    "hit-rate" (fewest prefix hits since registration, LRU breaking ties) —
    a page that keeps getting shared is worth keeping over one that parked
    earlier but never hit.
    """

    EVICT_POLICIES = ("lru", "hit-rate")

    def __init__(self, num_pages: int, evict_policy: str = "lru"):
        if num_pages < 2:
            raise ValueError("need at least 2 pages (page 0 is reserved)")
        if evict_policy not in self.EVICT_POLICIES:
            raise ValueError(f"evict_policy must be one of "
                             f"{self.EVICT_POLICIES}, got {evict_policy!r}")
        self.num_pages = num_pages
        self.evict_policy = evict_policy
        self._free = list(range(num_pages - 1, 0, -1))  # pop() -> lowest id
        self._allocated: set = set()
        self._ref: dict = {}            # page -> live reference count (>= 1)
        self._index: dict = {}          # prefix key -> page id (injective)
        self._key_of: dict = {}         # page id -> its prefix key
        self._cached: OrderedDict = OrderedDict()   # ref-0 registered, LRU
        self._hits: dict = {}           # registered page -> prefix-hit count
        self.evictions = 0
        self.restores = 0
        self.total_alloced = 0          # pages handed out, lifetime
        self.shares = 0                 # references taken via prefix hits
        self.cows = 0
        self.cache_reclaims = 0         # cached pages cannibalized by alloc

    @property
    def available(self) -> int:
        """Pages an ``alloc`` could obtain: truly free plus reclaimable
        (ref-0 cached prefix pages)."""
        return len(self._free) + len(self._cached)

    @property
    def cached_pages(self) -> int:
        return len(self._cached)

    def refcount(self, page: int) -> int:
        return self._ref.get(page, 0)

    def alloc(self, n: int) -> Optional[list]:
        """Return n page ids at refcount 1, or None (all-or-nothing).
        Draws from the free list first, then reclaims cached prefix pages
        per ``evict_policy`` (unregistering them — their contents are
        gone)."""
        if n > self.available:
            return None
        pages = []
        for _ in range(n):
            if self._free:
                p = self._free.pop()
            else:
                p = self._reclaim_cached()
            pages.append(p)
            self._ref[p] = 1
        self._allocated.update(pages)
        self.total_alloced += n
        return pages

    def _reclaim_cached(self) -> int:
        """Pick a cached (ref-0) prefix page to cannibalize.  "lru" takes
        the least-recently parked page; "hit-rate" takes the page with the
        fewest prefix hits since registration, breaking ties LRU-first."""
        if self.evict_policy == "hit-rate":
            lru_rank = {q: i for i, q in enumerate(self._cached)}
            p = min(self._cached,
                    key=lambda q: (self._hits.get(q, 0), lru_rank[q]))
            del self._cached[p]
        else:
            p, _ = self._cached.popitem(last=False)
        self._unregister(p)
        self.cache_reclaims += 1
        return p

    def free(self, pages) -> None:
        """Drop one reference per listed page.  A page leaves the allocated
        set only when its refcount hits 0; registered pages then park in the
        cached set (contents retained for prefix hits), others return to the
        free list."""
        for p in pages:
            if not (0 < p < self.num_pages):
                raise ValueError(f"bad page id {p}")
            if p not in self._allocated:
                raise ValueError(f"double free of page {p}")
            self._ref[p] -= 1
            if self._ref[p] > 0:
                continue
            del self._ref[p]
            self._allocated.discard(p)
            if p in self._key_of:
                self._cached[p] = None          # most-recently-used end
            else:
                self._free.append(p)

    def probe(self, key) -> Optional[int]:
        """Page currently holding the content addressed by ``key`` (live or
        cached), or None.  Probing does NOT pin — callers must ``share``
        every hit before any ``alloc`` that could reclaim a cached page."""
        return self._index.get(key)

    def share(self, page: int) -> int:
        """Take one reference on an indexed page (a prefix-cache hit).  A
        cached (ref-0) page is revived into the allocated set."""
        if page in self._cached:
            del self._cached[page]
            self._allocated.add(page)
            self._ref[page] = 1
        elif page in self._allocated:
            self._ref[page] += 1
        else:
            raise ValueError(f"page {page} is neither allocated nor cached")
        self.shares += 1
        self._hits[page] = self._hits.get(page, 0) + 1
        return page

    def register(self, page: int, key) -> None:
        """Content-address an allocated page under ``key``.  First writer
        wins: if an equivalent page is already canonical for the key the
        call is a no-op (both pages hold identical contents; the newcomer
        stays an ordinary private page)."""
        if page not in self._allocated:
            raise ValueError(f"cannot register unallocated page {page}")
        old = self._key_of.get(page)
        if old == key:
            return
        if key in self._index:
            return
        if old is not None:
            del self._index[old]
        self._index[key] = page
        self._key_of[page] = key

    def cow(self, page: int) -> Optional[int]:
        """Copy-on-write bookkeeping: exchange the caller's reference on a
        shared page for a fresh private page (all-or-nothing; None if no
        page is available, caller's reference untouched).  The caller then
        copies device contents via ``copy_pages_stacked``."""
        fresh = self.alloc(1)
        if fresh is None:
            return None
        self.free([page])
        self.cows += 1
        return fresh[0]

    def _unregister(self, page: int) -> None:
        key = self._key_of.pop(page, None)
        if key is not None and self._index.get(key) == page:
            del self._index[key]
        self._hits.pop(page, None)

    def evict(self, pages) -> None:
        """Free a preemption victim's pages (contents live on in the host
        snapshot; the device pages are immediately reusable)."""
        self.free(pages)
        self.evictions += 1

    def restore(self, n: int) -> Optional[list]:
        """Allocate pages for a re-admitted (offloaded) request.  The ids
        need not match the evicted ones — the page table re-maps."""
        pages = self.alloc(n)
        if pages is not None:
            self.restores += 1
        return pages

    def check_conservation(self, held=None) -> bool:
        """Assert the three-way partition: free list, cached set and
        allocated set are disjoint and together cover pages 1..num_pages-1;
        every allocated page has a refcount >= 1, every cached page is
        registered, and the prefix index is consistent.  With ``held`` (a
        MULTISET of page ids — one entry per live reference the caller
        believes it holds, e.g. slot_pages plus preempted pins), the
        per-page counts must equal the refcounts exactly — no orphaned pages
        or leaked references after any recycle/preempt/restore/share path."""
        free = set(self._free)
        if len(free) != len(self._free):
            raise AssertionError("duplicate page ids in the free list")
        cached = set(self._cached)
        parts = [("free", free), ("cached", cached),
                 ("allocated", self._allocated)]
        for i in range(len(parts)):
            for j in range(i + 1, len(parts)):
                (na, a), (nb, b) = parts[i], parts[j]
                if a & b:
                    raise AssertionError(
                        f"pages both {na} and {nb}: {sorted(a & b)}")
        universe = set(range(1, self.num_pages))
        if free | cached | self._allocated != universe:
            lost = sorted(universe - free - cached - self._allocated)
            raise AssertionError(f"orphaned pages (neither free, cached nor "
                                 f"allocated): {lost}")
        if set(self._ref) != self._allocated:
            raise AssertionError(
                f"refcount table out of sync with allocated set: "
                f"refs {sorted(self._ref)} vs {sorted(self._allocated)}")
        if any(r < 1 for r in self._ref.values()):
            bad = {p: r for p, r in self._ref.items() if r < 1}
            raise AssertionError(f"allocated pages with refcount < 1: {bad}")
        for p in cached:
            if p not in self._key_of:
                raise AssertionError(f"cached page {p} has no prefix key")
        for key, p in self._index.items():
            if self._key_of.get(p) != key:
                raise AssertionError(
                    f"prefix index out of sync: key {key!r} -> page {p} but "
                    f"page maps to {self._key_of.get(p)!r}")
            if p in free:
                raise AssertionError(f"indexed page {p} is on the free list")
        if held is not None:
            counts = dict(Counter(held))
            if counts != self._ref:
                over = {p: c for p, c in counts.items()
                        if c != self._ref.get(p, 0)}
                under = {p: r for p, r in self._ref.items()
                         if r != counts.get(p, 0)}
                raise AssertionError(
                    f"allocator/holder refcount mismatch: held {over} vs "
                    f"allocated {under}")
        return True
