"""Composable sparsity policies: metric x schedule x selector (+ executor).

The paper's pitch is that Stem is *plug-and-play*, and the baselines it
compares against (uniform top-k, StreamingLLM sink+local, XAttention
threshold selection) differ from Stem along exactly three independent
axes.  This module makes those axes first-class so a policy is declared
once and runs on **all three execution paths** — prefill
(``core/sparse_attention.sparse_attention``), fixed-batch decode
(``core/decode.py``) and paged serving (``runtime/paged.py``):

  * ``BlockMetric``     — how key blocks are scored per query row
                          (``oam``, ``sam``/``xattention`` routing-only,
                          ``streaming`` content-free).
  * ``BudgetSchedule``  — how many blocks each query row may keep
                          (``tpd``, ``uniform``, ``dense``,
                          ``sink-local``).  Budgets are static numpy per
                          (policy, shape): they resolve at trace time and
                          drive the ragged execution schedule.
  * ``Selector``        — how scores + budgets become a block set
                          (``topk`` with forced sink/local floors,
                          ``cumulative-mass`` threshold).

``SparsityPolicy`` composes the three with the execution knobs
(block_size, GQA group_reduce, executor, ragged schedule).  Policies are
frozen dataclasses — hashable, so they ride through ``jax.jit`` as static
arguments exactly like ``StemConfig`` used to.

Registries map declarative names to instances so configs and CLIs can say
``--policy stem`` / ``--policy streaming``:

  * ``register_policy`` / ``get_policy`` / ``available_policies``
  * ``register_metric`` / ``register_schedule`` / ``register_selector``
  * ``register_executor`` / ``get_executor`` — execution backends
    (``xla`` / ``pallas`` / ``dense``), registered by
    ``core/sparse_attention.py``.

``as_policy`` accepts a ``SparsityPolicy``, a registered name, or a legacy
``StemConfig`` (converted via ``policy_from_config``) — every historical
call site keeps working through that shim.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional, Protocol, Union, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import metric as metric_lib
from repro.core import schedule as schedule_lib
from repro.core import selection as selection_lib
from repro.core.config import (StemConfig, k_start_blocks_for,
                               uniform_equivalent_budget,
                               validate_sparse_segment)

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Protocols (structural contracts; see DESIGN.md §Policy architecture)
# ---------------------------------------------------------------------------

@runtime_checkable
class BlockMetric(Protocol):
    """Scores key blocks per query row; higher = more important."""

    def prefill_scores(self, q, k, v, *, block_size: int) -> jnp.ndarray:
        """(b, hq, sq, d) x (b, hk, sk, d) -> (b, hq, nq, nk)."""
        ...

    def decode_scores(self, q, k_groups, v_mag) -> jnp.ndarray:
        """One decode query vs pooled cache-block summaries.

        q: (b, hq, 1, d); k_groups: (b, hk, n, stride, d); v_mag: (b, hk, n).
        Returns (b, hk, group, n) float32.
        """
        ...

    def chunk_scores(self, q, k_groups, v_mag, *, block_size: int) -> jnp.ndarray:
        """A chunk of queries vs pooled cache-block summaries (chunked
        prefill, ``core/chunked.py``).  Must reproduce ``prefill_scores`` on
        full key blocks so chunked selection matches one-shot prefill.

        q: (b, hq, C, d) with C % block_size == 0; k_groups / v_mag as in
        ``decode_scores``.  Returns (b, hq, nc, n).
        """
        ...


@runtime_checkable
class BudgetSchedule(Protocol):
    """Per-query-row block budgets (static for prefill, per-row for decode)."""

    def prefill_budgets(self, nq: int, nk: int, *, block_size: int,
                        kv_len: int) -> np.ndarray:
        """Static int32 numpy budgets of shape (nq,), causally clamped."""
        ...

    def decode_budgets(self, n_valid, n_forced, budget_frac: float):
        """(b,) int32 budgets for one decode step (n_valid/n_forced: (b,))."""
        ...

    def decode_budget_bound(self, nblk: int, forced_bound: int,
                            budget_frac: float) -> int:
        """Static top-k width: upper bound on any row's decode budget."""
        ...


@runtime_checkable
class Selector(Protocol):
    """Turns (metric, budgets) into a concrete block selection."""

    budget_driven: bool  # True: k_max = max budget; False: threshold, k_max = nk

    def select(self, metric, budgets, k_max: int, *,
               with_block_mask: bool) -> selection_lib.BlockSelection:
        ...

    def select_decode(self, metric, cache_lens, *, block_size: int,
                      schedule: BudgetSchedule,
                      budget_frac: float) -> selection_lib.DecodeSelection:
        ...


# ---------------------------------------------------------------------------
# Metrics
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class OutputAwareMetric:
    """Eq. (7): pooled routing scores + beta * max(0, maxpool log ||V||)."""

    beta: float = 0.2
    pooling: str = "antidiag"
    stride: int = 16

    def prefill_scores(self, q, k, v, *, block_size: int) -> jnp.ndarray:
        return metric_lib.oam_scores(
            q, k, v, block_size=block_size, stride=self.stride,
            pooling=self.pooling, beta=self.beta)

    def decode_scores(self, q, k_groups, v_mag) -> jnp.ndarray:
        route = metric_lib.decode_routing_scores(q, k_groups)
        if self.beta == 0.0:
            return route
        return route + self.beta * jnp.maximum(v_mag, 0.0)[:, :, None, :]

    def chunk_scores(self, q, k_groups, v_mag, *, block_size: int) -> jnp.ndarray:
        route = metric_lib.chunk_routing_scores(
            q, k_groups, block_size=block_size, pooling=self.pooling)
        if self.beta == 0.0:
            return route
        group = q.shape[1] // k_groups.shape[1]
        mv = jnp.repeat(v_mag, group, axis=1)              # (b, hq, n)
        return route + self.beta * jnp.maximum(mv, 0.0).astype(
            route.dtype)[..., None, :]


@dataclasses.dataclass(frozen=True)
class RoutingMetric:
    """Routing-only scores (the paper's SAM ablation; also XAttention's
    anti-diagonal block scores) — no value-magnitude term."""

    pooling: str = "antidiag"
    stride: int = 16

    def prefill_scores(self, q, k, v, *, block_size: int) -> jnp.ndarray:
        return metric_lib.blockwise_routing_scores(
            q, k, block_size=block_size, stride=self.stride,
            pooling=self.pooling)

    def decode_scores(self, q, k_groups, v_mag) -> jnp.ndarray:
        return metric_lib.decode_routing_scores(q, k_groups)

    def chunk_scores(self, q, k_groups, v_mag, *, block_size: int) -> jnp.ndarray:
        return metric_lib.chunk_routing_scores(
            q, k_groups, block_size=block_size, pooling=self.pooling)


@dataclasses.dataclass(frozen=True)
class StreamingMetric:
    """Content-free zero metric: selection is driven entirely by the forced
    sink/local floors and the budget schedule (StreamingLLM)."""

    def prefill_scores(self, q, k, v, *, block_size: int) -> jnp.ndarray:
        b, hq, sq, _ = q.shape
        nq, nk = sq // block_size, k.shape[2] // block_size
        return jnp.zeros((b, hq, nq, nk), jnp.float32)

    def decode_scores(self, q, k_groups, v_mag) -> jnp.ndarray:
        b, hq = q.shape[0], q.shape[1]
        hk, n = k_groups.shape[1], k_groups.shape[2]
        return jnp.zeros((b, hk, hq // hk, n), jnp.float32)

    def chunk_scores(self, q, k_groups, v_mag, *, block_size: int) -> jnp.ndarray:
        b, hq, c, _ = q.shape
        n = k_groups.shape[2]
        return jnp.zeros((b, hq, c // block_size, n), jnp.float32)


# ---------------------------------------------------------------------------
# Budget schedules
# ---------------------------------------------------------------------------

def _validate_fractional(mu: float, min_budget_blocks: int) -> None:
    if not (0.0 < mu <= 1.0):
        raise ValueError(f"mu must be in (0, 1], got {mu}")
    if min_budget_blocks < 0:
        raise ValueError(f"min_budget_blocks must be >= 0, got {min_budget_blocks}")


def _validate_sink_local(sink_blocks: int, local_blocks: int) -> None:
    if sink_blocks < 0 or local_blocks < 0:
        raise ValueError(
            f"sink/local blocks must be >= 0, got ({sink_blocks}, {local_blocks})")


def _fractional_decode_budgets(min_budget_blocks: int, n_valid, n_forced,
                               budget_frac: float):
    """Decode budget rule shared by the budget-driven schedules: a fixed
    fraction of the valid cache blocks, floored at min_budget and at the
    forced sink/local count."""
    return jnp.maximum(
        jnp.maximum(jnp.int32(min_budget_blocks), n_forced),
        (n_valid * budget_frac).astype(jnp.int32))


def _fractional_decode_bound(min_budget_blocks: int, nblk: int,
                             forced_bound: int, budget_frac: float) -> int:
    """Static upper bound on _fractional_decode_budgets — the decode top-k
    width the executors allocate."""
    k_max = min(nblk, int(np.ceil(nblk * budget_frac))
                + min_budget_blocks + forced_bound)
    return max(k_max, 1)


@dataclasses.dataclass(frozen=True)
class TPDSchedule:
    """Token Position-Decay (Eq. 3): linear decay k_start -> mu * k_start."""

    k_start_frac: Optional[float] = None
    mu: float = 0.7
    min_budget_blocks: int = 54
    # Fig. 3 analysis mode: only rows in [lo*N, hi*N) are sparsified.
    sparse_segment: Optional[tuple] = None

    def __post_init__(self) -> None:
        _validate_fractional(self.mu, self.min_budget_blocks)
        validate_sparse_segment(self.sparse_segment)

    def prefill_budgets(self, nq: int, nk: int, *, block_size: int,
                        kv_len: int) -> np.ndarray:
        budgets = schedule_lib.tpd_budget_blocks(
            nq, nk, k_start_blocks_for(self.k_start_frac, kv_len, block_size),
            self.mu, min_budget_blocks=self.min_budget_blocks)
        return schedule_lib.apply_sparse_segment(budgets, nq, nk,
                                                 self.sparse_segment)

    def decode_budgets(self, n_valid, n_forced, budget_frac: float):
        return _fractional_decode_budgets(self.min_budget_blocks, n_valid,
                                          n_forced, budget_frac)

    def decode_budget_bound(self, nblk: int, forced_bound: int,
                            budget_frac: float) -> int:
        return _fractional_decode_bound(self.min_budget_blocks, nblk,
                                        forced_bound, budget_frac)


@dataclasses.dataclass(frozen=True)
class UniformSchedule:
    """Constant per-row budget, causally clamped.

    ``k_blocks=None`` selects the budget-matched uniform equivalent of the
    TPD schedule (paper Table 5): k_uni = k_start (1+mu)/2, floored at
    ``min(min_budget_blocks, nk)``.
    """

    k_blocks: Optional[int] = None
    k_start_frac: Optional[float] = None
    mu: float = 0.7
    min_budget_blocks: int = 54

    def __post_init__(self) -> None:
        _validate_fractional(self.mu, self.min_budget_blocks)
        if self.k_blocks is not None and self.k_blocks < 1:
            raise ValueError(f"k_blocks must be >= 1, got {self.k_blocks}")

    def _k_uni(self, nk: int, block_size: int, kv_len: int) -> int:
        if self.k_blocks is not None:
            return self.k_blocks
        k_start = k_start_blocks_for(self.k_start_frac, kv_len, block_size)
        k_uni = uniform_equivalent_budget(k_start, self.mu)
        return max(k_uni, min(self.min_budget_blocks, nk))

    def prefill_budgets(self, nq: int, nk: int, *, block_size: int,
                        kv_len: int) -> np.ndarray:
        return schedule_lib.uniform_budget_blocks(
            nq, nk, self._k_uni(nk, block_size, kv_len))

    def decode_budgets(self, n_valid, n_forced, budget_frac: float):
        return _fractional_decode_budgets(self.min_budget_blocks, n_valid,
                                          n_forced, budget_frac)

    def decode_budget_bound(self, nblk: int, forced_bound: int,
                            budget_frac: float) -> int:
        return _fractional_decode_bound(self.min_budget_blocks, nblk,
                                        forced_bound, budget_frac)


@dataclasses.dataclass(frozen=True)
class DenseSchedule:
    """Every causally admissible block — with the top-k selector this
    reproduces dense attention through the sparse executors (oracle arm);
    with the cumulative-mass selector it leaves budgeting to the threshold."""

    def prefill_budgets(self, nq: int, nk: int, *, block_size: int,
                        kv_len: int) -> np.ndarray:
        return schedule_lib.dense_budget_blocks(nq, nk)

    def decode_budgets(self, n_valid, n_forced, budget_frac: float):
        return jnp.asarray(n_valid, jnp.int32)

    def decode_budget_bound(self, nblk: int, forced_bound: int,
                            budget_frac: float) -> int:
        return max(nblk, 1)


@dataclasses.dataclass(frozen=True)
class SinkLocalSchedule:
    """StreamingLLM budget: exactly the forced sink + local blocks per row.
    Must agree with the selector's sink/local floors."""

    sink_blocks: int = 4
    local_blocks: int = 4

    def __post_init__(self) -> None:
        _validate_sink_local(self.sink_blocks, self.local_blocks)
        if self.sink_blocks + self.local_blocks < 1:
            raise ValueError("sink-local schedule needs sink + local >= 1")

    def prefill_budgets(self, nq: int, nk: int, *, block_size: int,
                        kv_len: int) -> np.ndarray:
        return schedule_lib.sink_local_budget_blocks(
            nq, nk, self.sink_blocks, self.local_blocks)

    def decode_budgets(self, n_valid, n_forced, budget_frac: float):
        return jnp.asarray(n_forced, jnp.int32)

    def decode_budget_bound(self, nblk: int, forced_bound: int,
                            budget_frac: float) -> int:
        return max(1, min(nblk, forced_bound))


# ---------------------------------------------------------------------------
# Selectors
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TopKSelector:
    """Top-k(i) over the metric with forced sink/local floors
    (``selection.select_blocks``); the decode path is the vectorized
    per-row variant shared by the contiguous and paged caches."""

    sink_blocks: int = 4
    local_blocks: int = 4
    budget_driven = True

    def __post_init__(self) -> None:
        _validate_sink_local(self.sink_blocks, self.local_blocks)

    def select(self, metric, budgets, k_max: int, *,
               with_block_mask: bool) -> selection_lib.BlockSelection:
        return selection_lib.select_blocks(
            metric, budgets, k_max,
            sink_blocks=self.sink_blocks, local_blocks=self.local_blocks,
            with_block_mask=with_block_mask)

    def select_decode(self, m, cache_lens, *, block_size: int,
                      schedule: BudgetSchedule,
                      budget_frac: float) -> selection_lib.DecodeSelection:
        """Per-row budget + validity + forced floors, static-width top-k.

        m: (b, hk, g, nblk) coarse metric; cache_lens scalar or (b,).
        """
        b, _, _, nblk = m.shape
        bs = block_size
        cache_lens = jnp.broadcast_to(jnp.asarray(cache_lens, jnp.int32), (b,))

        n_valid = (cache_lens + bs - 1) // bs                        # (b,)
        # Forced sink/local floors ride on top of the budget: the per-row
        # union of sink + local blocks is min(n_valid, sink + local) wide,
        # and every forced block stays live regardless of budget_frac.
        n_forced = jnp.minimum(
            n_valid, jnp.int32(self.sink_blocks + self.local_blocks))
        k_budget = schedule.decode_budgets(n_valid, n_forced, budget_frac)
        blk = jnp.arange(nblk)
        is_valid = blk[None, :] < n_valid[:, None]                   # (b, n)
        is_sink = blk < self.sink_blocks                             # (n,)
        is_local = (blk[None, :] >= n_valid[:, None] - self.local_blocks) & is_valid
        forced = (is_sink[None, :] | is_local)[:, None, None, :]     # (b,1,1,n)
        biased = jnp.where(forced, m + selection_lib.FORCE_BONUS, m)
        biased = jnp.where(is_valid[:, None, None, :], biased, NEG_INF)

        k_max = schedule.decode_budget_bound(
            nblk, self.sink_blocks + self.local_blocks, budget_frac)
        vals, idx = jax.lax.top_k(biased, k_max)                # (b,hk,g,kmax)
        live = (vals > NEG_INF / 2) & (
            jnp.arange(k_max)[None, None, None, :] < k_budget[:, None, None, None])
        return selection_lib.DecodeSelection(
            indices=idx.astype(jnp.int32), live=live,
            budgets=k_budget, n_valid=n_valid)


def _cumulative_mass_keep(probs: jnp.ndarray, tau: float) -> jnp.ndarray:
    """Keep mask over the last axis: a block is kept iff the cumulative
    (descending-sorted) probability mass *before* it is < tau — the
    smallest prefix reaching tau, scattered back to block ids."""
    order = jnp.argsort(-probs, axis=-1)
    sorted_p = jnp.take_along_axis(probs, order, axis=-1)
    cum = jnp.cumsum(sorted_p, axis=-1)
    keep_sorted = (cum - sorted_p) < tau
    onehot = jax.nn.one_hot(order, probs.shape[-1], dtype=jnp.bool_)
    return jnp.any(onehot & keep_sorted[..., None], axis=-2)


@dataclasses.dataclass(frozen=True)
class CumulativeMassSelector:
    """XAttention-style: per-row softmax over the (causal) metric, keep the
    smallest prefix of blocks whose cumulative mass reaches ``tau``;
    sink/local blocks are forced for stability.  Budget-free — the schedule
    only matters for rows the threshold leaves empty (never, since forced
    floors exist), so pair it with ``DenseSchedule``."""

    tau: float = 0.9
    sink_blocks: int = 4
    local_blocks: int = 4
    budget_driven = False

    def __post_init__(self) -> None:
        _validate_sink_local(self.sink_blocks, self.local_blocks)
        if not (0.0 < self.tau <= 1.0):
            raise ValueError(f"tau must be in (0, 1], got {self.tau}")

    def select(self, metric, budgets, k_max: int, *,
               with_block_mask: bool) -> selection_lib.BlockSelection:
        nq, nk = metric.shape[-2], metric.shape[-1]
        causal = selection_lib.causal_block_mask(nq, nk)
        m = jnp.where(causal, metric, NEG_INF)
        probs = jax.nn.softmax(m, axis=-1)
        block_mask = _cumulative_mass_keep(probs, self.tau) & causal
        forced = selection_lib.forced_block_mask(
            nq, nk, self.sink_blocks, self.local_blocks)
        block_mask = block_mask | (forced & causal)
        score = jnp.where(block_mask, probs + 1.0, NEG_INF)
        vals, idx = jax.lax.top_k(score, int(nk))
        slot_mask = vals > NEG_INF / 2
        indices = jnp.where(slot_mask, idx, 0).astype(jnp.int32)
        row_budgets = jnp.max(block_mask.sum(axis=-1), axis=(0, 1)).astype(jnp.int32)
        return selection_lib.BlockSelection(
            indices=indices, slot_mask=slot_mask,
            block_mask=block_mask if with_block_mask else None,
            budgets=row_budgets,
            live_counts=slot_mask.sum(axis=-1, dtype=jnp.int32))

    def select_decode(self, m, cache_lens, *, block_size: int,
                      schedule: BudgetSchedule,
                      budget_frac: float) -> selection_lib.DecodeSelection:
        """Threshold selection over cache blocks (k_max = nblk: the gather
        stays O(L) — threshold decode trades the static bound for
        budget-free selection)."""
        b, _, _, nblk = m.shape
        bs = block_size
        cache_lens = jnp.broadcast_to(jnp.asarray(cache_lens, jnp.int32), (b,))
        n_valid = (cache_lens + bs - 1) // bs
        blk = jnp.arange(nblk)
        is_valid = blk[None, :] < n_valid[:, None]
        is_sink = blk < self.sink_blocks
        is_local = (blk[None, :] >= n_valid[:, None] - self.local_blocks) & is_valid
        forced = (is_sink[None, :] | is_local)[:, None, None, :]

        mm = jnp.where(is_valid[:, None, None, :], m, NEG_INF)
        probs = jax.nn.softmax(mm, axis=-1)
        keep = _cumulative_mass_keep(probs, self.tau)
        keep = (keep | forced) & is_valid[:, None, None, :]
        score = jnp.where(keep, probs + 1.0, NEG_INF)
        vals, idx = jax.lax.top_k(score, int(nblk))
        live = vals > NEG_INF / 2
        row_budgets = keep.sum(axis=-1).max(axis=(1, 2)).astype(jnp.int32)
        return selection_lib.DecodeSelection(
            indices=idx.astype(jnp.int32), live=live,
            budgets=row_budgets, n_valid=n_valid)


# ---------------------------------------------------------------------------
# The composed policy
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SparsityPolicy:
    """Metric x schedule x selector + execution knobs.  Frozen/hashable —
    rides through jit as a static argument; equal policies share traces.

    One instance drives all three execution paths:
      * prefill — ``sparse_attention(q, k, v, policy)`` (core/sparse_attention);
      * fixed-batch decode — ``core.decode.sparse_decode_attention``;
      * paged serving — ``runtime.paged.paged_sparse_decode`` and the
        continuous-batching engine.
    """

    metric: Any
    schedule: Any
    selector: Any
    block_size: int = 128
    group_reduce: str = "none"     # "none" | "mean" | "max" (GQA sharing)
    executor: str = "xla"          # default execution backend (registry name)
    slot_chunk: int = 8
    ragged: bool = True
    name: str = ""

    def __post_init__(self) -> None:
        # Same construction-time invariants StemConfig enforces — a bad
        # composition must fail here with a clear message, not deep inside
        # jit tracing.  (The executor name is validated lazily at dispatch:
        # executors register after this module's built-in policies exist.)
        if self.block_size <= 0 or self.block_size % 8 != 0:
            raise ValueError(
                f"block_size must be a positive multiple of 8, got {self.block_size}")
        stride = self.stride
        if stride <= 0 or self.block_size % stride != 0:
            raise ValueError(
                f"metric stride {stride} must divide block_size {self.block_size}")
        if self.group_reduce not in ("none", "mean", "max"):
            raise ValueError(f"unknown group_reduce {self.group_reduce!r}")
        if self.slot_chunk < 1:
            raise ValueError(f"slot_chunk must be >= 1, got {self.slot_chunk}")

    # -- derived attributes the cache/pool machinery needs ------------------

    @property
    def stride(self) -> int:
        """Anti-diagonal pooling stride of the metric (1 for content-free
        metrics) — sizes the per-block K group-mean summaries."""
        return getattr(self.metric, "stride", 1)

    @property
    def sink_blocks(self) -> int:
        return getattr(self.selector, "sink_blocks", 0)

    @property
    def local_blocks(self) -> int:
        return getattr(self.selector, "local_blocks", 0)

    # -- prefill ------------------------------------------------------------

    def prefill_budgets(self, seq_len: int, kv_len: Optional[int] = None) -> np.ndarray:
        """Static numpy (nq,) budgets — resolves at trace time."""
        kv_len = seq_len if kv_len is None else kv_len
        nq = -(-seq_len // self.block_size)
        nk = -(-kv_len // self.block_size)
        return self.schedule.prefill_budgets(
            nq, nk, block_size=self.block_size, kv_len=kv_len)

    def prefill_scores(self, q, k, v) -> jnp.ndarray:
        m = self.metric.prefill_scores(q, k, v, block_size=self.block_size)
        group = q.shape[1] // k.shape[1]
        return metric_lib.group_reduce_metric(m, group, self.group_reduce)

    def prefill_select(self, q, k, v, *, with_block_mask: bool = True):
        """Phase 1 of Algorithm 1: metric + schedule + selection.

        Returns (BlockSelection, k_max).
        """
        sq, sk = q.shape[2], k.shape[2]
        m = self.prefill_scores(q, k, v)
        budgets = self.prefill_budgets(sq, sk)
        nk = sk // self.block_size
        k_max = int(budgets.max()) if self.selector.budget_driven else int(nk)
        sel = self.selector.select(
            m, schedule_lib.budgets_as_jax(budgets), k_max,
            with_block_mask=with_block_mask)
        return sel, k_max

    # -- chunked prefill (core/chunked.py) -----------------------------------

    def chunk_scores(self, q, k_groups, v_mag) -> jnp.ndarray:
        """Chunk-of-queries metric against pooled page summaries, with the
        policy's GQA group reduction applied — the chunked-prefill analogue
        of ``prefill_scores``.  Returns (b, hq, nc, n)."""
        fn = getattr(self.metric, "chunk_scores", None)
        if fn is None:
            raise NotImplementedError(
                f"metric {type(self.metric).__name__} does not implement "
                "chunk_scores(q, k_groups, v_mag, block_size=...) — required "
                "for chunked prefill (core/chunked.py)")
        m = fn(q, k_groups, v_mag, block_size=self.block_size)
        group = q.shape[1] // k_groups.shape[1]
        return metric_lib.group_reduce_metric(m, group, self.group_reduce)

    # -- decode (contiguous and paged caches share these) --------------------

    def decode_scores(self, q, k_groups, v_mag) -> jnp.ndarray:
        return self.metric.decode_scores(q, k_groups, v_mag)

    def decode_select(self, m, cache_lens, *,
                      budget_frac: float = 0.25) -> selection_lib.DecodeSelection:
        return self.selector.select_decode(
            m, cache_lens, block_size=self.block_size,
            schedule=self.schedule, budget_frac=budget_frac)

    def decode_budget_bound(self, nblk: int, budget_frac: float) -> int:
        """Static decode top-k width (the gather allocation)."""
        if not self.selector.budget_driven:
            return max(nblk, 1)
        return self.schedule.decode_budget_bound(
            nblk, self.sink_blocks + self.local_blocks, budget_frac)

    # -- ergonomics ----------------------------------------------------------

    def with_updates(self, *, ignore_missing: bool = False,
                     **kw) -> "SparsityPolicy":
        """Copy with knobs rewritten, routing each key to every component
        (policy / metric / schedule / selector) that defines a field of
        that name — e.g. ``sink_blocks`` updates both the top-k selector
        and a sink-local schedule so they stay consistent.  The final
        object is built in one step so cross-component invariants (stride
        vs block_size) are validated against the *combined* update, not an
        intermediate.  Unknown keys raise unless ``ignore_missing`` (CLIs
        rescaling heterogeneous policies pass True)."""
        top_fields = {f.name for f in dataclasses.fields(self)}
        top = {k: v for k, v in kw.items() if k in top_fields}
        known = set(top)
        final = dict(top)
        for comp_name in ("metric", "schedule", "selector"):
            comp = top.get(comp_name, getattr(self, comp_name))
            fields = {f.name for f in dataclasses.fields(comp)}
            known |= fields
            sub = {k: v for k, v in kw.items() if k in fields}
            if sub:
                final[comp_name] = dataclasses.replace(comp, **sub)
        if not ignore_missing:
            unknown = set(kw) - known
            if unknown:
                raise ValueError(
                    f"with_updates: no component defines {sorted(unknown)}")
        return dataclasses.replace(self, **final) if final else self


# ---------------------------------------------------------------------------
# Head-shared selection over a latent (MLA) cache
# ---------------------------------------------------------------------------

# A latent cache holds one "KV head" that every query head reads through its
# absorbed projections, so the heads of a slot share one page set: each
# head's Stem score is computed as usual and the scores are reduced over the
# heads before the budgeted top-k.  "mean" is the policy's own
# ``group_reduce`` where it shares already, else this default.
LATENT_GROUP_REDUCE = "mean"


def latent_group_reduce(policy: "SparsityPolicy") -> str:
    """How the query heads over a latent cache share their page scores."""
    return (policy.group_reduce if policy.group_reduce != "none"
            else LATENT_GROUP_REDUCE)


def share_heads(m: jnp.ndarray, axis: int, mode: str) -> jnp.ndarray:
    """Reduce page scores over the head ``axis`` ("mean" or "max"), keeping
    it as size 1."""
    if mode == "max":
        return m.max(axis=axis, keepdims=True)
    return m.mean(axis=axis, keepdims=True)


# ---------------------------------------------------------------------------
# Registries
# ---------------------------------------------------------------------------

_METRICS: dict = {}
_SCHEDULES: dict = {}
_SELECTORS: dict = {}
_POLICIES: dict = {}


def _register(table: dict, kind: str, name: str, obj, overwrite: bool):
    if not overwrite and name in table:
        raise ValueError(f"{kind} {name!r} already registered")
    table[name] = obj
    return obj


def _lookup(table: dict, kind: str, name: str):
    try:
        return table[name]
    except KeyError:
        raise KeyError(
            f"unknown {kind} {name!r}; registered: {sorted(table)}") from None


def register_metric(name: str, m, *, overwrite: bool = False):
    return _register(_METRICS, "metric", name, m, overwrite)


def get_metric(name: str):
    return _lookup(_METRICS, "metric", name)


def register_schedule(name: str, s, *, overwrite: bool = False):
    return _register(_SCHEDULES, "schedule", name, s, overwrite)


def get_schedule(name: str):
    return _lookup(_SCHEDULES, "schedule", name)


def register_selector(name: str, s, *, overwrite: bool = False):
    return _register(_SELECTORS, "selector", name, s, overwrite)


def get_selector(name: str):
    return _lookup(_SELECTORS, "selector", name)


def register_policy(name: str, policy: SparsityPolicy, *,
                    overwrite: bool = False) -> SparsityPolicy:
    if not policy.name:
        policy = dataclasses.replace(policy, name=name)
    return _register(_POLICIES, "policy", name, policy, overwrite)


def get_policy(name: str) -> SparsityPolicy:
    return _lookup(_POLICIES, "policy", name)


def available_policies() -> tuple:
    return tuple(sorted(_POLICIES))


@functools.lru_cache(maxsize=None)
def policy_from_config(cfg: StemConfig) -> SparsityPolicy:
    """Equivalent policy of a legacy flag record (the ``cfg.policy()``
    shim).  ``metric="sam"`` maps to the routing-only metric on *both*
    phases (prefill parity is exact; decode historically always added the
    value term — routing-only decode is the corrected SAM semantics)."""
    if cfg.metric == "oam":
        m: Any = OutputAwareMetric(beta=cfg.beta, pooling=cfg.pooling,
                                   stride=cfg.stride)
    else:
        m = RoutingMetric(pooling=cfg.pooling, stride=cfg.stride)
    return SparsityPolicy(
        metric=m,
        schedule=TPDSchedule(
            k_start_frac=cfg.k_start_frac, mu=cfg.mu,
            min_budget_blocks=cfg.min_budget_blocks,
            sparse_segment=cfg.sparse_segment),
        selector=TopKSelector(sink_blocks=cfg.sink_blocks,
                              local_blocks=cfg.local_blocks),
        block_size=cfg.block_size, group_reduce=cfg.group_reduce,
        executor=cfg.backend, slot_chunk=cfg.slot_chunk, ragged=cfg.ragged,
        name="stem" if cfg.metric == "oam" else "stem-sam")


PolicyLike = Union[SparsityPolicy, StemConfig, str]


def as_policy(obj: PolicyLike) -> SparsityPolicy:
    """Normalize a policy spelling: instance | registered name | StemConfig."""
    if isinstance(obj, SparsityPolicy):
        return obj
    if isinstance(obj, StemConfig):
        return policy_from_config(obj)
    if isinstance(obj, str):
        return get_policy(obj)
    raise TypeError(f"cannot interpret {type(obj).__name__} as a SparsityPolicy")


def as_policy_opt(obj: Optional[PolicyLike]) -> Optional[SparsityPolicy]:
    return None if obj is None else as_policy(obj)


# ---------------------------------------------------------------------------
# Executor registry (backends registered by core/sparse_attention.py)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ExecutorSpec:
    """One execution backend for a block selection.

    ``fn(q, k, v, sel, *, policy, scale, indices, slot_mask, live_counts,
    dedup, budgets)`` — ``indices``/``slot_mask``/``live_counts`` are the
    (possibly GQA-deduplicated) views of ``sel``; ``budgets`` is the static
    numpy schedule (None = padded execution / threshold selection)."""

    fn: Callable
    needs_block_mask: bool = False


_EXECUTORS: dict = {}


def register_executor(name: str, fn: Callable, *,
                      needs_block_mask: bool = False,
                      overwrite: bool = False) -> ExecutorSpec:
    return _register(_EXECUTORS, "executor", name,
                     ExecutorSpec(fn=fn, needs_block_mask=needs_block_mask),
                     overwrite)


def get_executor(name: str) -> ExecutorSpec:
    return _lookup(_EXECUTORS, "executor", name)


def available_executors() -> tuple:
    return tuple(sorted(_EXECUTORS))


# ---------------------------------------------------------------------------
# Paged executor registry (serving decode + chunk lanes; backends registered
# by runtime/paged.py — "xla", the gather oracle — and kernels/paged_attn.py
# — "pallas", the fused scalar-prefetch kernels)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PagedExecutorSpec:
    """One execution backend for the paged serving attention lanes.

    ``decode_fn(q, pools, layer, page_table, cache_lens, policy,
    budget_frac)`` mirrors ``runtime.paged.paged_sparse_decode``;
    ``chunk_fn(q, pools, layer, page_table, chunk_start, budgets, policy,
    k_max)`` mirrors ``core.chunked.chunked_prefill_attention``.  ``pools``
    is a ``PagePool`` whose leaves are stacked over layers
    (``(n, hk, P, ...)``) and ``layer`` a traced int32 scalar: a backend
    reads that layer's summaries and pages from the stack in place, never a
    per-layer slice of it.  Both return the attention output and must be
    selection-identical to the "xla" oracle (the differential suite in
    tests/test_paged_kernel.py pins this).

    ``sharding`` declares the backend's tensor-parallel contract for
    mesh-sharded serving (``sharding/serving.py``): "kv-head" means both
    lanes are per-KV-head independent — they read head count from the pool
    shapes and never reduce across heads — so a shard-local pool slice plus
    sliced q/k/v is bitwise equivalent to the full run restricted to those
    heads.  "replicated" marks a backend that must see all heads; tp>1
    refuses it at engine construction.
    """

    decode_fn: Callable
    chunk_fn: Callable
    sharding: str = "kv-head"


_PAGED_EXECUTORS: dict = {}


def register_paged_executor(name: str, *, decode_fn: Callable,
                            chunk_fn: Callable, sharding: str = "kv-head",
                            overwrite: bool = False) -> PagedExecutorSpec:
    if sharding not in ("kv-head", "replicated"):
        raise ValueError(f"sharding must be 'kv-head' or 'replicated', "
                         f"got {sharding!r}")
    return _register(_PAGED_EXECUTORS, "paged executor", name,
                     PagedExecutorSpec(decode_fn=decode_fn, chunk_fn=chunk_fn,
                                       sharding=sharding),
                     overwrite)


def get_paged_executor(name: str) -> PagedExecutorSpec:
    """Resolve a paged backend, lazily importing the module that registers
    it.  Prefill-only executor names (a policy's ``executor`` field may name
    e.g. "dense", which only exists for the monolithic prefill registry)
    fall back to the XLA gather oracle — always correct, never fused."""
    if name not in _PAGED_EXECUTORS:
        if name == "pallas":
            from repro.kernels import paged_attn  # noqa: F401 (registers)
        else:
            from repro.runtime import paged  # noqa: F401 (registers "xla")
    if name in _PAGED_EXECUTORS:
        return _PAGED_EXECUTORS[name]
    if "xla" not in _PAGED_EXECUTORS:
        from repro.runtime import paged  # noqa: F401 (registers "xla")
    return _PAGED_EXECUTORS["xla"]


def available_paged_executors() -> tuple:
    return tuple(sorted(_PAGED_EXECUTORS))


# ---------------------------------------------------------------------------
# Built-in registrations (paper defaults: B=128, mu=0.7, beta=0.2, 4+4
# sink/local, floor 54 — rescale with .with_updates for small shapes)
# ---------------------------------------------------------------------------

register_metric("oam", OutputAwareMetric())
register_metric("sam", RoutingMetric())
register_metric("xattention", RoutingMetric())   # alias: antidiag routing
register_metric("streaming", StreamingMetric())

register_schedule("tpd", TPDSchedule())
register_schedule("uniform", UniformSchedule())
register_schedule("dense", DenseSchedule())
register_schedule("sink-local", SinkLocalSchedule())

register_selector("topk", TopKSelector())
register_selector("cumulative-mass", CumulativeMassSelector())

register_policy("stem", SparsityPolicy(
    metric=OutputAwareMetric(), schedule=TPDSchedule(),
    selector=TopKSelector()))
register_policy("stem-sam", SparsityPolicy(
    metric=RoutingMetric(), schedule=TPDSchedule(),
    selector=TopKSelector()))
register_policy("uniform-sam", SparsityPolicy(
    metric=RoutingMetric(), schedule=UniformSchedule(),
    selector=TopKSelector()))
register_policy("uniform-oam", SparsityPolicy(
    metric=OutputAwareMetric(), schedule=UniformSchedule(),
    selector=TopKSelector()))
register_policy("streaming", SparsityPolicy(
    metric=StreamingMetric(), schedule=SinkLocalSchedule(),
    selector=TopKSelector()))
register_policy("xattention", SparsityPolicy(
    metric=RoutingMetric(), schedule=DenseSchedule(),
    selector=CumulativeMassSelector()))
register_policy("dense", SparsityPolicy(
    metric=StreamingMetric(), schedule=DenseSchedule(),
    selector=TopKSelector(sink_blocks=0, local_blocks=0)))
