"""Continuous-batching serving engine over the paged Stem KV cache.

Requests with arbitrary prompt lengths arrive over time, are admitted into
a fixed set of *slots* as capacity frees up, and make progress together in
**one jitted mixed-batch step per iteration** — vLLM-shaped continuous
batching with Stem's coarse-to-fine selection running natively on the page
pool (a page *is* a Stem block; see ``runtime/paged.py``).

Unified step (the default): prefill is **chunked**.  A slot admitted with a
long prompt does not stall its co-tenants behind a monolithic prefill;
instead it advances ``chunk_size`` tokens per engine step through the
chunked-prefill lane of the single jitted ``unified_step``
(``launch/steps.make_unified_step`` -> ``transformer.paged_mixed_step``),
riding in the same trace as every decode token.  The step's shapes are
fixed — a (slots, 1) decode lane plus a narrow (chunk_lanes, chunk_size)
prefill lane (lanes = the most whole chunks the token budget admits,
typically 1) — so the engine compiles each of its two signatures (mixed,
and decode-only for chunk-free steps) **exactly once**, independent of
prompt lengths (``stats["traces"]``; pinned by ``tests/test_engine.py``).
The old monolithic path retraced per padded prompt-length bucket.

Engine loop (one ``step()``):

  1. **Load shedding** — when ``EngineConfig.max_waiting`` bounds the
     waiting queue, overflow rejects the lowest-priority (newest among
     ties) pending request as an explicitly *failed* ``FinishedRequest``
     (``error="shed: ..."``) instead of growing the queue without bound.
  2. **Admission** — ordered by ``(priority desc, arrival order)`` under
     the SLO scheduler (``EngineConfig.scheduler="slo"``; ``"fcfs"`` is
     the PR 5 baseline), gated on arrival step, a free slot, and an
     all-or-nothing page reservation for the request's whole lifetime.
     When a high-priority request is slot- or memory-blocked, admission
     may **preempt** a strictly-lower-priority running request: the
     victim's pages (K/V + kg/vm selection summaries) are gathered to a
     host snapshot (``runtime/offload.py``), its device pages are evicted
     back to the allocator, and it re-admits later by scattering the
     snapshot into freshly allocated pages **bit-identically** — a page
     carries its own selection summaries, so re-admission needs *zero*
     prefill recompute and adds zero traces.
  3. **Token-budget scheduling** — each step spends at most
     ``step_token_budget`` tokens.  Decode tokens go first, ordered by
     ``(priority, SLO headroom, least-recently-served)`` (FCFS: admission
     order); decodes beyond the budget are deferred to later steps.  Then
     whole prefill chunks fill the remaining budget in the same priority
     order.  When decode-lane TPOT pressure is high (a decode was deferred
     or a TPOT SLO is being violated) the chunk grant is adaptively capped
     at one lane; a prefill-phase slot that has gone ``chunk_starve_steps``
     engine steps without any chunk grant receives one anyway (bounded
     overdraft — decode saturation cannot starve prefill forever).
  4. **Mixed step** — one jitted call advances every granted lane, wrapped
     in the failure boundary: an injected/step exception *before* any pool
     mutation is retried up to ``max_step_retries`` times, after which the
     engine degrades by aborting its lowest-priority active request (a
     failed ``FinishedRequest``, never a crashed engine) and retrying with
     the smaller batch.  ``StragglerMonitor`` times every working step;
     flagged outliers surface in ``engine.metrics``.
  5. **Recycling** — slots hitting EOS / max-new-tokens free their pages
     and return to the free list; the next ``step()`` re-admits.  Page
     accounting is asserted (``PageAllocator.check_conservation``) after
     every recovery path: no orphaned pages, no double bookkeeping.

Latency accounting: ``token_latencies_s`` records **inter-token gaps** as
experienced by the request (time between consecutive emissions — this is
what surfaces head-of-line blocking *and* swapped-out time), ``ttft_s``
the admission -> first-token wall, and ``tpot_s`` the mean per-output-token
time after the first.  ``benchmarks/serving.py`` reports them separately,
split by priority class in the overload study (``BENCH_slo.json``).

Determinism / batch-invariance: every per-slot computation in both lanes
is row-parallel (selection, gather, softmax), and chunk boundaries depend
only on ``chunk_size`` — so a request's token stream is bitwise independent
of which slot it occupies, who its co-tenants are, how the token budget
interleaves its chunks, and whether it was preempted and restored along
the way.  ``tests/test_engine.py`` and ``tests/test_preemption.py`` pin
this differentially.

Async pipeline (``EngineConfig.async_depth=1``): sampling moves inside
the jitted step (``runtime/sampling.py`` + ``paged_sampled_step``), the
fed-back decode tokens live in a device-resident buffer, and the host
dispatches step N+1 from the previous scheduler state while step N's
sampled ids are still in flight — EOS is reconciled one step late, the
single speculative step of a finished request writes only into its own
still-reserved pages, and the emitted streams stay bit-identical to the
``async_depth=0`` synchronous oracle (``tests/test_async_engine.py``).
The only per-step transfer is the ``(slots,) int32`` id array, and
``stats["host_syncs"]`` (blocking fetches with no newer step queued
behind them) drops from O(steps) to O(finished requests).

Host spans: every ``step()`` writes ``jax.profiler.TraceAnnotation`` spans
onto the profiler's clock, the same clock as the device trace, so an idle
gap on the device can be charged to what the host was doing:
``engine.step`` (the whole iteration) encloses ``engine.admit``,
``engine.schedule``, ``engine.inputs`` (host arrays and their transfer),
``engine.dispatch`` (the jitted call), ``engine.wait`` (the blocking fetch)
and ``engine.emit`` (argmax, token append, recycle, prefix registration);
the async loop wraps each reconcile's wait and emit in
``engine.reconcile``.  ``stats["dispatch_s"]`` and ``stats["sync_wait_s"]``
are the host-clock lengths of the dispatch and wait spans.  With the
profiler off a span costs about a microsecond.
"""
from __future__ import annotations

import collections
import dataclasses
import os
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation as _span

from repro.core import chunked as chunked_lib
from repro.launch import steps as steps_lib
from repro.models import transformer
from repro.runtime import offload as offload_lib
from repro.runtime import paged as paged_lib
from repro.runtime import sampling as sampling_lib
from repro.runtime.fault_tolerance import InjectedFailure
from repro.runtime.straggler import StragglerMonitor


class EngineStalledError(RuntimeError):
    """``StemEngine.run`` hit its step cap with requests still in flight.

    Carries the stuck uids so the operator can see *what* is wedged
    (running / waiting / preempted) instead of a silent partial result."""

    def __init__(self, max_steps: int, running: list, waiting: list,
                 preempted: list):
        self.running, self.waiting, self.preempted = running, waiting, preempted
        super().__init__(
            f"engine stalled: {max_steps} steps without draining; stuck "
            f"requests: running uids {running}, waiting uids {waiting}, "
            f"preempted uids {preempted}")


@dataclasses.dataclass
class Request:
    """One generation request.

    ``priority``: higher wins admission, decode-token grants, and may
    preempt strictly-lower-priority running requests (SLO scheduler only).
    ``ttft_slo_s`` / ``tpot_slo_s``: optional latency targets; the
    scheduler orders equal-priority work by remaining SLO headroom."""
    uid: int
    prompt: np.ndarray            # (prompt_len,) int32 token ids
    max_new_tokens: int
    arrival_step: int = 0         # engine step at which the request exists
    priority: int = 0
    ttft_slo_s: Optional[float] = None
    tpot_slo_s: Optional[float] = None


@dataclasses.dataclass
class FinishedRequest:
    uid: int
    prompt_len: int
    tokens: list                  # generated token ids (greedy)
    slot: int
    admitted_step: int
    finished_step: int
    ttft_s: float                 # arrival -> first token (queueing included)
    tpot_s: float                 # mean per-output-token time after the
                                  # first (NaN when only one token: undefined)
    token_latencies_s: list       # inter-token gaps (includes HOL stalls
                                  # and swapped-out time while preempted)
    priority: int = 0
    preemptions: int = 0          # times swapped out to host and restored
    queue_s: float = 0.0          # arrival -> admission wait (in ttft_s too)
    error: Optional[str] = None   # None = finished; else shed/abort reason


def pages_needed(prompt_len: int, max_new: int, page_size: int) -> int:
    """Pages a request holds for its whole lifetime.  Tokens ever cached:
    the prompt plus every generated token that is fed back (the final one
    is not)."""
    cached = prompt_len + max(max_new - 1, 0)
    return -(-cached // page_size)


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Sizing + policy knobs of the serving engine.

    ``num_pages`` includes the reserved trash page 0.  A request needs
    ``pages_needed(prompt_len, max_new_tokens, page_size)`` pages for its
    whole lifetime (conservative up-front reservation — no mid-flight OOM),
    and at most ``max_pages_per_slot`` (the static page-table width).

    ``chunk_size`` (tokens, a multiple of the page size; None = 2 pages)
    fixes the prefill-lane width of the unified step;
    ``step_token_budget`` (None = max_slots + chunk_size) caps the tokens
    one step may spend — decode tokens first, then whole prefill chunks.
    ``monolithic_prefill`` switches to the legacy per-length-trace
    admission prefill (the chunked-vs-monolithic A/B baseline, and the
    fallback for threshold selectors chunked prefill cannot serve).

    ``prefix_cache`` enables hash-keyed prefix-page sharing: admission
    probes the allocator's prefix index per whole prompt page, maps hits
    read-only into the slot's page table, and starts the chunked
    ``prefill_pos`` cursor past the matched prefix — only the unmatched
    suffix is prefilled and only suffix pages are newly allocated.
    Completed prompts register their full pages for future tenants.
    Requires chunked prefill (the skip is chunk-granular), so it is
    mutually exclusive with ``monolithic_prefill``.

    Overload-resilience knobs:
      ``scheduler``          "slo" (priority + SLO-headroom ordering,
                             preemption-capable) or "fcfs" (the PR 5
                             baseline: admission order everywhere, no
                             preemption).  With every request at the
                             default priority and no SLOs, "slo" degrades
                             to exactly "fcfs".
      ``preemption``         allow admission to evict strictly-lower-
                             priority running requests to host memory.
      ``max_waiting``        waiting-queue bound; overflow sheds the
                             lowest-priority pending request as a failed
                             FinishedRequest (None = unbounded).
      ``max_step_retries``   bounded retry of a failed mixed step before
                             degrading (abort lowest-priority active).
      ``max_restore_retries``retries of a failed offload-restore before
                             the request is aborted with an error.
      ``chunk_starve_steps`` max engine steps a waiting prefill can go
                             without any chunk grant before one is forced
                             (budget overdraft; liveness under decode
                             saturation).
      ``straggler_threshold``step-time outlier factor for the wired-in
                             StragglerMonitor (``engine.metrics``).

    Mesh-sharded serving (``sharding/serving.py``):
      ``mesh``               ``(dp, tp)`` — shard the page pools over a
                             device mesh: tp splits the KV-head axis
                             (shard-local selection + attention, one
                             all-gather at the output projection, bitwise
                             identical to single-device), dp adds
                             independent slot groups each with
                             ``max_slots`` slots and ``num_pages`` pages
                             driven through the same two traces.  None =
                             single device (the default, untouched path).
      ``prefix_evict``       cached prefix-page reclaim order: "lru"
                             (default) or "hit-rate" (fewest prefix hits
                             first; ties LRU).
      ``admission_control``  SLO-aware admission control: reject an
                             arrived request with an explicit error when
                             its TTFT SLO is infeasible given the queued
                             prefill tokens and the chunk-lane capacity
                             (off by default).

    Async pipeline:
      ``async_depth``        0 (default) = the synchronous loop: fetch
                             logits, sample on host, block every step —
                             kept as the differential oracle.  1 = the
                             async pipeline: on-device sampling, a
                             device-resident fed-back-token buffer, and
                             one-step-lookahead dispatch (step N+1 is
                             dispatched while step N's sampled ids are
                             in flight; EOS reconciles one step late
                             with a free discard).  Streams are
                             bit-identical between the two.
      ``sampler``            registered on-device sampler name
                             (``runtime/sampling.py``); "greedy" is the
                             default and the only stream-deterministic
                             choice."""
    max_slots: int = 4
    num_pages: int = 64
    max_pages_per_slot: int = 16
    budget_frac: float = 1.0      # 1.0 = dense-equivalent oracle arm
    executor: Optional[str] = None  # paged attention backend: "xla" gather
                                    # oracle | fused "pallas" kernels
                                    # (kernels/paged_attn.py); None defers
                                    # to policy.executor
    eos_id: Optional[int] = None
    chunk_size: Optional[int] = None
    step_token_budget: Optional[int] = None
    monolithic_prefill: bool = False
    prefix_cache: bool = False
    scheduler: str = "slo"
    preemption: bool = True
    max_waiting: Optional[int] = None
    max_step_retries: int = 2
    max_restore_retries: int = 2
    chunk_starve_steps: int = 4
    straggler_threshold: float = 3.0
    mesh: Optional[tuple] = None    # (dp, tp) serving mesh; None = 1 device
    prefix_evict: str = "lru"       # cached prefix reclaim: lru | hit-rate
    admission_control: bool = False  # reject-on-infeasible-TTFT at admission
    async_depth: int = 0            # 0 = synchronous oracle; 1 = one-step
                                    # lookahead async pipeline
    sampler: str = "greedy"         # on-device sampler (runtime/sampling.py)

    def __post_init__(self):
        if self.scheduler not in ("slo", "fcfs"):
            raise ValueError(f"unknown scheduler {self.scheduler!r} "
                             "(expected 'slo' or 'fcfs')")
        if self.prefix_cache and self.monolithic_prefill:
            raise ValueError(
                "prefix_cache needs chunked prefill (the matched-prefix "
                "skip is chunk-granular); disable monolithic_prefill")
        if self.prefix_evict not in paged_lib.PageAllocator.EVICT_POLICIES:
            raise ValueError(
                f"prefix_evict must be one of "
                f"{paged_lib.PageAllocator.EVICT_POLICIES}, "
                f"got {self.prefix_evict!r}")
        if self.mesh is not None:
            if len(self.mesh) != 2 or any(int(a) < 1 for a in self.mesh):
                raise ValueError(f"mesh must be (dp >= 1, tp >= 1), "
                                 f"got {self.mesh!r}")
            if self.monolithic_prefill:
                raise ValueError(
                    "mesh serving runs through the unified chunked step; "
                    "disable monolithic_prefill")
        if self.async_depth not in (0, 1):
            raise ValueError(
                f"async_depth must be 0 (synchronous) or 1 (one-step "
                f"lookahead), got {self.async_depth!r}")
        if self.async_depth and self.monolithic_prefill:
            raise ValueError(
                "the async pipeline runs through the unified chunked step "
                "(monolithic admission prefill blocks the host per "
                "admission); disable monolithic_prefill")
        sampling_lib.get_sampler(self.sampler)   # validate the name early

    @classmethod
    def for_trace(cls, *, max_slots: int, max_prompt: int,
                  max_new_tokens: int, page_size: int,
                  budget_frac: float = 1.0,
                  eos_id: Optional[int] = None,
                  chunk_size: Optional[int] = None,
                  step_token_budget: Optional[int] = None,
                  monolithic_prefill: bool = False,
                  **knobs) -> "EngineConfig":
        """Size the pool so every slot can hold the largest trace request —
        the one place the reservation rule is encoded for drivers.  Extra
        ``knobs`` pass through to the config (scheduler, max_waiting, ...)."""
        per_slot = pages_needed(max_prompt, max_new_tokens, page_size)
        return cls(max_slots=max_slots, num_pages=1 + max_slots * per_slot,
                   max_pages_per_slot=per_slot, budget_frac=budget_frac,
                   eos_id=eos_id, chunk_size=chunk_size,
                   step_token_budget=step_token_budget,
                   monolithic_prefill=monolithic_prefill, **knobs)


@dataclasses.dataclass
class _SlotState:
    req: Request
    tokens: list
    admitted_step: int
    admit_t: float
    arrival_t: float              # when the request became schedulable
    phase: str                    # "prefill" | "decode"
    prefill_pos: int              # next absolute prompt position to process
    padded: np.ndarray            # (Lp,) prompt right-padded to a page multiple
    true_len: int
    ttft_s: float = 0.0
    first_token_t: float = 0.0
    last_token_t: float = 0.0
    token_latencies_s: list = dataclasses.field(default_factory=list)
    preemptions: int = 0
    last_sched_step: int = 0      # last step granted a decode token
    prefix_keys: list = dataclasses.field(default_factory=list)
                                  # chained hash per full prompt page, to
                                  # register once prefill completes
    inflight: int = 0             # async: dispatched-but-unreconciled tokens
    finished: bool = False        # async: terminal — in-flight reconciles
                                  # for this request are discarded


@dataclasses.dataclass
class _Preempted:
    """A swapped-out request: slot state frozen, PRIVATE pages on the host.
    Shared prefix pages are never snapshotted — their contents belong to
    the prefix index (other tenants may be reading them); the record keeps
    one pinned reference per shared page so they survive until restore."""
    st: _SlotState
    npages: int                   # private device pages to re-reserve
    cache_len: int                # cache_lens value at preemption
    seq: int                      # original submission order
    preempt_step: int
    restore_attempts: int = 0
    shared_pages: list = dataclasses.field(default_factory=list)
    group: int = 0                # slot group — restores are pinned to it
                                  # (the snapshot's bytes belong to that
                                  # group's pool shard)


@dataclasses.dataclass
class _InFlight:
    """One dispatched-but-unreconciled async step.  ``dec_ids`` /
    ``chunk_ids`` are DEVICE arrays of sampled token ids — touching them
    with ``np.asarray`` is the reconcile-time fetch.  The slot-state
    references pin the requests the values belong to: a slot may be
    recycled and re-admitted before reconcile, but ``st`` cannot — its
    ``finished`` flag marks stale entries for free discard."""
    dec_ids: object               # (T,) / (G, S) int32 device array
    chunk_ids: object             # (L,) / (G, L) int32 device array | None
    dec: list                     # [(slot, _SlotState), ...]
    chunks: list                  # [(g, lane, slot, _SlotState, completes)]
    step: int                     # engine step at dispatch
    dispatch_t: float


@dataclasses.dataclass
class _PrefixMatch:
    """Admission-time prefix probe result, refs already pinned.

    ``shared``: matched pages mapped read-only into the page table (before
    the replay window — never written again).  ``cow``: matched pages that
    overlap the replay window (only the final full page of an
    exact-page-multiple fully-matched prompt: its logits must be recomputed,
    so the chunk REWRITES that page — copy-on-write redirects the write to
    a private copy).  ``keys``: chained hash of every full prompt page."""
    keys: list
    shared: list
    cow: list


class StemEngine:
    """Continuous-batching engine; host-side scheduler + one jitted step.

    ``stem_cfg`` names the engine's sparsity policy: a ``SparsityPolicy``,
    a registered policy name (``"stem"``, ``"streaming"``, …) or a legacy
    ``StemConfig``.  One policy drives chunked prefill page summaries,
    chunk selection, and decode page selection alike.

    ``chaos`` (a ``runtime.chaos.ChaosInjector``) optionally injects
    allocator exhaustion, step failures, and restore failures at configured
    engine steps — the engine must survive all of them (bounded retry,
    per-request abort-with-error, load shedding), which
    ``tests/test_chaos.py`` asserts."""

    def __init__(self, bundle, params, stem_cfg,
                 ecfg: EngineConfig = EngineConfig(), chaos=None):
        from repro.core import policy as policy_lib

        transformer.assert_paged_servable(bundle.cfg)
        self.bundle = bundle
        self.cfg = bundle.cfg
        self.params = params
        self.policy = policy_lib.as_policy(stem_cfg)
        self.stem_cfg = self.policy          # legacy attribute name
        self.ecfg = ecfg
        self.chaos = chaos
        self.page_size = self.policy.block_size
        self.chunk_size = ecfg.chunk_size or 2 * self.page_size
        if self.chunk_size % self.page_size:
            raise ValueError(
                f"chunk_size {self.chunk_size} must be a multiple of the "
                f"page size {self.page_size}")
        self.token_budget = (ecfg.step_token_budget
                             or ecfg.max_slots + self.chunk_size)
        # Static width of the chunked-prefill lane: the most whole chunks
        # the token budget could ever admit in one step.
        self.chunk_lanes = min(ecfg.max_slots,
                               max(1, self.token_budget // self.chunk_size))
        if not ecfg.monolithic_prefill:
            chunked_lib.validate_chunked_policy(self.policy)

        S, P = ecfg.max_slots, ecfg.max_pages_per_slot
        # Serving mesh: dp independent slot groups (flat slot ids
        # [g*max_slots, (g+1)*max_slots) per group, each with its own
        # allocator and num_pages pages), tp sharding the KV-head axis of
        # every pool leaf.  smesh=None is the unchanged single-device path
        # with one group.
        self.smesh = None
        if ecfg.mesh is not None:
            from repro.sharding import serving as serving_lib
            dp, tp = (int(a) for a in ecfg.mesh)
            self.smesh = serving_lib.make_serving_mesh(dp, tp)
            serving_lib.validate_serving(
                bundle.cfg, ecfg.executor or self.policy.executor, self.smesh)
        self.groups = self.smesh.dp if self.smesh else 1
        self.slots_per_group = S
        self.total_slots = self.groups * S
        T = self.total_slots
        self.pools = transformer.init_page_pools(
            bundle.cfg, ecfg.num_pages, self.policy, smesh=self.smesh)
        self.allocators = [
            paged_lib.PageAllocator(ecfg.num_pages,
                                    evict_policy=ecfg.prefix_evict)
            for _ in range(self.groups)]
        self.allocator = self.allocators[0]    # single-group alias
        self.page_table = np.zeros((T, P), np.int32)
        self.cache_lens = np.zeros((T,), np.int32)
        self.slot_pages: list = [None] * T     # page ids held by each slot
        self.slot_nshared = [0] * T            # leading prefix-shared pages
        self.slots: list = [None] * T          # _SlotState | None
        self.waiting: collections.deque = collections.deque()
        self.preempted: list = []              # _Preempted records
        self.finished: list = []
        self.host_store = offload_lib.HostPageStore()
        self.step_count = 0
        self.stats = {"prefills": 0, "chunks": 0, "decode_steps": 0,
                      "step_calls": 0, "tokens_generated": 0,
                      "slots_reused": 0, "max_concurrency": 0,
                      "traces": 0, "prefill_traces": 0,
                      "preemptions": 0, "restores": 0, "restore_failures": 0,
                      "step_failures": 0, "aborts": 0, "shed": 0,
                      "decode_deferrals": 0, "chunk_caps": 0,
                      "starvation_grants": 0, "alloc_denials": 0,
                      "straggler_steps": 0,
                      "prefix_hits": 0, "prefix_pages_shared": 0,
                      "prefix_cows": 0, "admission_rejects": 0,
                      "host_syncs": 0, "id_fetches": 0,
                      "lookahead_discards": 0, "pallas_fallbacks": 0,
                      "restore_bytes": 0, "admissions": 0,
                      "dispatch_s": 0.0, "sync_wait_s": 0.0,
                      "queue_wait_s": 0.0}
        self._slot_ever_used = [False] * T
        self._seq: dict = {}                   # uid -> submission order
        self._arrival_t: dict = {}             # uid -> when it could first
                                               # be scheduled (perf_counter)
        self._next_seq = 0
        self._last_chunk_step = [0] * self.groups
                                               # last step a chunk ran (or no
                                               # prefill work existed), per
                                               # slot group
        self.monitor = StragglerMonitor(
            threshold=ecfg.straggler_threshold,
            on_straggler=lambda s, dt, ema: self.stats.__setitem__(
                "straggler_steps", self.stats["straggler_steps"] + 1))

        def _count(key):
            def bump():
                self.stats[key] += 1
            return bump

        # THE step: decode lane + chunked-prefill lane, fixed shapes.
        # ``chunk_k_max`` is the static chunk-selection/gather width: the
        # largest block budget any admissible prompt can reach, so chunk
        # cost tracks the policy's budget, not the page-table width.
        # ``stats["traces"]`` counts (re)compiles via a trace-time side
        # effect — the regression test pins it to the two lane signatures
        # (mixed / decode-only) across heterogeneous prompt lengths;
        # preemption's extract/restore are their own jits and never touch
        # this counter.
        k_bound = (0 if ecfg.monolithic_prefill else
                   chunked_lib.chunk_budget_bound(self.policy, P))
        self._async = ecfg.async_depth > 0
        self.sampler = sampling_lib.get_sampler(ecfg.sampler)
        if self._async:
            # Async pipeline: sampling runs inside the trace, the decode
            # inputs come from the device-resident fed-back-token buffer,
            # and the step returns (slots,) int32 sampled ids — the only
            # per-step transfer.  Donation caveat: XLA:CPU blocks the
            # *dispatch* of a call whose donated input is still being
            # computed, which would re-serialize the pipeline (the pools
            # chain step to step).  On a multi-core CPU host the pipeline
            # is worth more than zero-copy, so the async step runs
            # undonated there (double-buffered pools, host free-running);
            # on a single-core host nothing can overlap anyway, so the
            # zero-copy donated update wins.  Accelerator backends
            # dispatch donated calls asynchronously and keep both.
            donate = (() if jax.default_backend() == "cpu"
                      and (os.cpu_count() or 1) > 1 else (1, 2))
            self._unified = jax.jit(steps_lib.make_unified_step(
                bundle, stem_cfg=self.policy, budget_frac=ecfg.budget_frac,
                chunk_k_max=k_bound, executor=ecfg.executor,
                on_trace=_count("traces"), smesh=self.smesh,
                sampler=self.sampler), donate_argnums=donate)
        else:
            self._unified = jax.jit(steps_lib.make_unified_step(
                bundle, stem_cfg=self.policy, budget_frac=ecfg.budget_frac,
                chunk_k_max=k_bound, executor=ecfg.executor,
                on_trace=_count("traces"), smesh=self.smesh),
                donate_argnums=(1,))
        if self.smesh is not None:
            # Group-vmapped page-management jits: every argument gains a
            # leading (dp,) axis — non-target groups ride along with
            # trash-page rows (page 0 is garbage by design), so each still
            # compiles exactly once.  out_shardings pins the pool layout so
            # extract/restore shards map 1:1 onto mesh coordinates.
            from repro.sharding import serving as serving_lib
            pool_sh = serving_lib.pool_sharding(self.smesh)
            self._reset = jax.jit(jax.vmap(paged_lib.reset_pools_stacked),
                                  donate_argnums=(0,), out_shardings=pool_sh)
            self._extract = jax.jit(jax.vmap(steps_lib.make_page_extract()),
                                    out_shardings=pool_sh)
            self._restore_pages = jax.jit(
                jax.vmap(steps_lib.make_page_restore()),
                donate_argnums=(0,), out_shardings=pool_sh)
            self._page_copy = jax.jit(jax.vmap(steps_lib.make_page_copy()),
                                      donate_argnums=(0,),
                                      out_shardings=pool_sh)
        else:
            self._reset = jax.jit(paged_lib.reset_pools_stacked,
                                  donate_argnums=(0,))
            self._extract = jax.jit(steps_lib.make_page_extract())
            self._restore_pages = jax.jit(steps_lib.make_page_restore(),
                                          donate_argnums=(0,))
            # Copy-on-write device copy (prefix caching); traced page ids,
            # so this compiles once and never touches the trace counters.
            self._page_copy = jax.jit(steps_lib.make_page_copy(),
                                      donate_argnums=(0,))
        self._prefill = None
        if ecfg.monolithic_prefill:
            # Legacy A/B arm: one trace per padded prompt-length bucket.
            # The first token is sampled on-device (same sampler op as the
            # async step), so the admission fetch is one int32, not a
            # vocab-sized logits row.
            self._prefill = jax.jit(steps_lib.make_monolithic_prefill(
                bundle, stem_cfg=self.policy,
                on_trace=_count("prefill_traces"),
                sampler=self.sampler), donate_argnums=(3,))

        # Async pipeline state.  ``token_buf`` is the device-resident
        # fed-back-token buffer — decode lanes read last step's sampled
        # ids from it without a host round trip; only restores write it
        # from the host side (``_set_token``, traced indices: one trace).
        self._inflight: collections.deque = collections.deque()
        self.token_buf = None
        self._set_token = None
        if self._async:
            if self.smesh is not None:
                from repro.sharding import serving as serving_lib
                grp_sh = serving_lib.group_sharding(self.smesh)
                self.token_buf = jax.device_put(
                    jnp.zeros((self.groups, S), jnp.int32), grp_sh)
                self._set_token = jax.jit(
                    lambda buf, g, s, val: buf.at[g, s].set(val),
                    donate_argnums=(0,), out_shardings=grp_sh)
            else:
                self.token_buf = jnp.zeros((T,), jnp.int32)
                self._set_token = jax.jit(
                    lambda buf, s, val: buf.at[s].set(val),
                    donate_argnums=(0,))

        # Restore-cost model: preemption victims are priced by the bytes
        # their restore moves host->device over a measured-bandwidth EMA
        # (seeded pessimistically until the first timed restore).
        self._page_nbytes = (
            sum(l.nbytes for l in jax.tree_util.tree_leaves(self.pools))
            // (self.groups * ecfg.num_pages))
        self._h2d_bw_ema: Optional[float] = None

        # Pallas-fallback observability: the fused kernels silently hand
        # unsupported configurations back to the XLA gather oracle at
        # trace time; surface that in stats instead (kernels module keeps
        # a process-wide counter — snapshot the baseline at init).
        self._track_fallbacks = (
            (ecfg.executor or self.policy.executor) == "pallas")
        self._pallas_fb_base = 0
        if self._track_fallbacks:
            from repro.kernels import paged_attn
            self._pallas_fb_base = sum(paged_attn.FALLBACKS.values())

    # -- scheduling ---------------------------------------------------------

    def submit(self, req: Request) -> None:
        npages = self._pages_needed(len(req.prompt), req.max_new_tokens)
        if npages > self.ecfg.max_pages_per_slot:
            raise ValueError(
                f"request {req.uid} needs {npages} pages > max_pages_per_slot "
                f"{self.ecfg.max_pages_per_slot}")
        if req.uid in self._seq:
            raise ValueError(f"duplicate request uid {req.uid}")
        self._seq[req.uid] = self._next_seq
        self._next_seq += 1
        if req.arrival_step <= self.step_count:
            self._arrival_t[req.uid] = time.perf_counter()
        self.waiting.append(req)

    def _pages_needed(self, prompt_len: int, max_new: int) -> int:
        return pages_needed(prompt_len, max_new, self.page_size)

    def reset_metrics(self) -> None:
        """Zero the workload observability state (finished list, counters,
        slot-reuse tracking, straggler flags) without touching pools, slots,
        or the allocator — e.g. after a benchmark warmup pass.  Trace
        counters are *kept*: they record compiles over the engine's lifetime
        (a warmed engine adds zero), and benchmarks report them as evidence
        of the no-retrace property.  The straggler EMA is kept warm too —
        only its flag history resets."""
        self.finished.clear()
        keep = ("traces", "prefill_traces", "pallas_fallbacks")
        self.stats.update({k: 0 for k in self.stats if k not in keep})
        for k in ("dispatch_s", "sync_wait_s", "queue_wait_s"):
            self.stats[k] = 0.0
        self._slot_ever_used = [False] * self.total_slots
        self.monitor.flagged.clear()

    def _refresh_fallbacks(self) -> None:
        """Mirror the kernels module's process-wide fallback counter into
        ``stats`` (delta since this engine was built)."""
        if not self._track_fallbacks:
            return
        from repro.kernels import paged_attn
        self.stats["pallas_fallbacks"] = (
            sum(paged_attn.FALLBACKS.values()) - self._pallas_fb_base)

    @property
    def metrics(self) -> dict:
        """Live observability beside ``stats``: in-flight steps, the
        restore-cost model's bandwidth estimate, straggler flags, offload
        peak and chaos counters."""
        return {
            "inflight_steps": len(self._inflight),
            "h2d_bw_bytes_per_s": self._h2d_bw_ema,
            "step_time_ema_s": self.monitor.ema,
            "straggler_steps": list(self.monitor.flagged),
            "offload_peak_bytes": self.host_store.peak_nbytes,
            "chaos": self.chaos.counts if self.chaos else None,
        }

    def _group_of(self, slot: int) -> int:
        return slot // self.slots_per_group

    def _group_slots(self, g: int) -> range:
        S = self.slots_per_group
        return range(g * S, (g + 1) * S)

    def _free_slot_in(self, g: int) -> Optional[int]:
        for s in self._group_slots(g):
            if self.slots[s] is None:
                return s
        return None

    def _check_pages(self) -> None:
        """Refcount conservation after any path that moves pages: each
        group's live references — one per slot-held page, plus one per
        shared prefix page pinned by an offloaded request — must match that
        group's allocator refcounts exactly (a MULTISET: a page shared by k
        slots appears k times)."""
        for g, alloc in enumerate(self.allocators):
            held = [p for s in self._group_slots(g)
                    if self.slot_pages[s] for p in self.slot_pages[s]]
            held += [p for rec in self.preempted if rec.group == g
                     for p in rec.shared_pages]
            alloc.check_conservation(held)

    # -- preemption + host offload ------------------------------------------

    def preempt(self, slot: int) -> None:
        """Swap a running request out to host memory: gather its PRIVATE
        pages (K/V + kg/vm summaries) into a host snapshot, evict them, and
        park the frozen slot state on the preempted list.  Prefix-shared
        pages are neither snapshotted nor evicted — their contents stay
        live for co-tenants; the record re-pins them (keeps this request's
        reference) so they cannot be reclaimed before restore.
        Re-admission restores bit-identically with zero recompute.

        Async: the in-flight step is drained first — the host token list
        and ``cache_lens`` must agree with the page contents the snapshot
        gathers, and an unreconciled sampled id would otherwise be lost
        with the eviction."""
        if self._async and self._inflight:
            self._drain()
        st = self.slots[slot]
        if st is None:
            raise ValueError(f"slot {slot} is not active")
        g = self._group_of(slot)
        pages = self.slot_pages[slot]
        nshared = self.slot_nshared[slot]
        shared, private = pages[:nshared], pages[nshared:]
        W = self.ecfg.max_pages_per_slot
        if self.smesh is not None:
            # Extract the victim's rows for its own group only; other
            # groups gather their trash page.  The snapshot stays sharded
            # per mesh coordinate on the host, so restore puts each tp
            # shard's bytes back exactly where they came from.
            rows = np.zeros((self.groups, W), np.int32)
            rows[g, :len(private)] = private
            snap = self._extract(self.pools, jnp.asarray(rows))
            snap = offload_lib.shard_snapshot_to_host(snap, self.smesh, g)
        else:
            row = np.zeros((W,), np.int32)
            row[:len(private)] = private
            snap = self._extract(self.pools, jnp.asarray(row))
        self.host_store.put(st.req.uid, snap, pinned=shared)
        st.preemptions += 1
        self.preempted.append(_Preempted(
            st=st, npages=len(private), cache_len=int(self.cache_lens[slot]),
            seq=self._seq[st.req.uid], preempt_step=self.step_count,
            shared_pages=list(shared), group=g))
        self.allocators[g].evict(private)
        self.page_table[slot] = 0
        self.cache_lens[slot] = 0
        self.slot_pages[slot] = None
        self.slot_nshared[slot] = 0
        self.slots[slot] = None
        self.stats["preemptions"] += 1
        self._check_pages()

    def _admit_restore(self, rec: _Preempted, slot: int, pages: list) -> bool:
        """Swap a preempted request back in: scatter the private snapshot
        into the fresh pages; the pinned shared prefix pages re-enter the
        page table untouched (their contents never left the device).  On an
        injected restore failure: free the fresh pages (conservation), keep
        the snapshot + pins, retry on a later step — or abort the request
        with an explicit error once ``max_restore_retries`` is exhausted
        (releasing the pins)."""
        g = rec.group
        W = self.ecfg.max_pages_per_slot
        try:
            if self.chaos:
                self.chaos.maybe_fail_restore(self.step_count)
        except InjectedFailure as e:
            self.allocators[g].free(pages)
            rec.restore_attempts += 1
            self.stats["restore_failures"] += 1
            if rec.restore_attempts > self.ecfg.max_restore_retries:
                self.host_store.drop(rec.st.req.uid)
                if rec.shared_pages:
                    self.allocators[g].free(rec.shared_pages)
                self.stats["aborts"] += 1
                self._finish_with_error(
                    rec.st, slot=-1,
                    error=f"aborted: restore failed "
                          f"{rec.restore_attempts} times ({e})")
            else:
                self.preempted.append(rec)
            self._check_pages()
            return False
        snap = self.host_store.pop(rec.st.req.uid)
        # Time the host->device scatter to feed the restore-cost model's
        # bandwidth EMA.  Only an un-overlapped restore is a clean sample:
        # with an async step in flight the block would also wait out the
        # step and undersell the link.
        measure = not (self._async and self._inflight)
        t0 = time.perf_counter()
        if self.smesh is not None:
            rows = np.zeros((self.groups, W), np.int32)
            rows[g, :rec.npages] = pages
            snap = offload_lib.assemble_sharded_snapshot(snap, self.smesh, g)
            self.pools = self._restore_pages(self.pools, jnp.asarray(rows),
                                             snap)
        else:
            row = np.zeros((W,), np.int32)
            row[:rec.npages] = pages
            self.pools = self._restore_pages(self.pools, jnp.asarray(row),
                                             snap)
        nbytes = rec.npages * self._page_nbytes
        self.stats["restore_bytes"] += nbytes
        if measure and nbytes:
            jax.block_until_ready(jax.tree_util.tree_leaves(self.pools)[0])
            bw = nbytes / max(time.perf_counter() - t0, 1e-9)
            self._h2d_bw_ema = (bw if self._h2d_bw_ema is None
                                else 0.5 * self._h2d_bw_ema + 0.5 * bw)
        if self._async and rec.st.tokens:
            # Re-seed the device-resident fed-back-token buffer: the
            # restored request's next decode step feeds its last emitted
            # token, which left the device with the preemption drain.
            last = jnp.asarray(rec.st.tokens[-1], jnp.int32)
            if self.smesh is not None:
                local = jnp.asarray(slot - g * self.slots_per_group,
                                    jnp.int32)
                self.token_buf = self._set_token(
                    self.token_buf, jnp.asarray(g, jnp.int32), local, last)
            else:
                self.token_buf = self._set_token(
                    self.token_buf, jnp.asarray(slot, jnp.int32), last)
        all_pages = list(rec.shared_pages) + list(pages)
        full_row = np.zeros((self.ecfg.max_pages_per_slot,), np.int32)
        full_row[:len(all_pages)] = all_pages
        if self._slot_ever_used[slot]:
            self.stats["slots_reused"] += 1
        self._slot_ever_used[slot] = True
        self.page_table[slot] = full_row
        self.cache_lens[slot] = rec.cache_len
        self.slot_pages[slot] = all_pages
        self.slot_nshared[slot] = len(rec.shared_pages)
        self.slots[slot] = rec.st
        self.stats["restores"] += 1
        self._check_pages()
        return True

    def _try_preempt_for(self, priority: int, need_pages: int,
                         group: int) -> bool:
        """Preempt one strictly-lower-priority running request in slot
        group ``group`` to make room (a slot and/or pages) for an admission
        at ``priority``.  Refuses when evicting every eligible victim still
        could not free enough pages — no pointless offloads."""
        if (self.ecfg.scheduler != "slo" or not self.ecfg.preemption):
            return False

        def eligible():
            return [s for s in self._group_slots(group)
                    if self.slots[s] is not None
                    and self.slots[s].req.priority < priority]

        if not eligible():
            # Nobody could be evicted: return without draining, or every
            # step with a blocked equal-priority waiter would serialize
            # the async pipeline.
            return False
        if self._async and self._inflight:
            # Reconcile before evicting anyone: an in-flight step may
            # finish a request outright, freeing a slot and its pages —
            # in which case the preemption is moot and the caller can
            # retry its allocation directly.
            self._drain()
            if (self._free_slot_in(group) is not None
                    and self.allocators[group].available >= need_pages):
                return True
        victims = eligible()
        if not victims:
            return False
        # Only a victim's PRIVATE pages come back (shared prefix pages stay
        # pinned by its preemption record); still an upper bound when a
        # private page is also shared by another slot.
        reclaimable = sum(len(self.slot_pages[s]) - self.slot_nshared[s]
                          for s in victims)
        if self.allocators[group].available + reclaimable < need_pages:
            return False
        # Restore-cost model: the victim class is the LOWEST priority
        # present (never climb the ladder for a cheaper restore); within
        # it, evict the request whose restore is cheapest in SECONDS —
        # private pages x page nbytes over the measured host->device
        # bandwidth EMA (``_restore_cost_s``).  Only PRIVATE pages price
        # in: shared prefix pages stay on-device either way.  Ties break
        # toward most-recently-admitted (least sunk progress), then the
        # higher slot id, keeping the pick deterministic.
        lowest = min(self.slots[s].req.priority for s in victims)
        cls = [s for s in victims if self.slots[s].req.priority == lowest]
        victim = min(cls, key=lambda s: (
            self._restore_cost_s(s),
            -self.slots[s].admitted_step, -s))
        self.preempt(victim)
        return True

    # Pessimistic PCIe-class seed bandwidth until the first timed restore.
    _BW_SEED = 8e9

    def _restore_cost_s(self, slot: int) -> float:
        """Estimated seconds to swap ``slot`` back in: the host->device
        bytes its restore would move (private pages x page nbytes — the
        snapshot round-trips exactly those) over the measured restore
        bandwidth EMA.  With the uniform page size this is monotone in
        the private-page count, so victim ordering is stable as the EMA
        moves; the seconds scale is what ``metrics`` and future
        multi-tier offload decisions consume."""
        private = len(self.slot_pages[slot]) - self.slot_nshared[slot]
        return (private * self._page_nbytes
                / (self._h2d_bw_ema or self._BW_SEED))

    # -- failure paths ------------------------------------------------------

    def _finish_with_error(self, st: _SlotState, slot: int, error: str) -> None:
        st.finished = True   # async: discard any in-flight work for it
        tpot = (float("nan") if len(st.tokens) < 2 else
                (st.last_token_t - st.first_token_t) / (len(st.tokens) - 1))
        self.finished.append(FinishedRequest(
            uid=st.req.uid, prompt_len=len(st.req.prompt), tokens=st.tokens,
            slot=slot, admitted_step=st.admitted_step,
            finished_step=self.step_count,
            ttft_s=st.ttft_s if st.tokens else float("nan"), tpot_s=tpot,
            token_latencies_s=st.token_latencies_s,
            priority=st.req.priority, preemptions=st.preemptions,
            queue_s=st.admit_t - st.arrival_t, error=error))
        self._seq.pop(st.req.uid, None)   # uid may be resubmitted later

    def _abort(self, slot: int, error: str) -> None:
        """Terminate an active request with an explicit error; its pages go
        back to the free list and the slot frees up."""
        st = self.slots[slot]
        self._finish_with_error(st, slot, error)
        self.allocators[self._group_of(slot)].free(self.slot_pages[slot])
        self.page_table[slot] = 0
        self.cache_lens[slot] = 0
        self.slot_pages[slot] = None
        self.slot_nshared[slot] = 0
        self.slots[slot] = None
        self.stats["aborts"] += 1
        self._check_pages()

    def _shed(self) -> None:
        """Bound the waiting queue: overflow rejects the lowest-priority
        (newest among ties; FCFS: the newest, period) pending request as an
        explicitly failed FinishedRequest."""
        lim = self.ecfg.max_waiting
        if lim is None:
            return
        while len(self.waiting) > lim:
            if self.ecfg.scheduler == "fcfs":
                i = len(self.waiting) - 1
            else:
                i = min(range(len(self.waiting)),
                        key=lambda j: (self.waiting[j].priority,
                                       -self._seq[self.waiting[j].uid]))
            req = self.waiting[i]
            del self.waiting[i]
            self.finished.append(FinishedRequest(
                uid=req.uid, prompt_len=len(req.prompt), tokens=[], slot=-1,
                admitted_step=-1, finished_step=self.step_count,
                ttft_s=float("nan"), tpot_s=float("nan"),
                token_latencies_s=[], priority=req.priority,
                error=f"shed: waiting queue exceeded max_waiting={lim}"))
            self._seq.pop(req.uid, None)
            self.stats["shed"] += 1

    def _admission_control(self) -> None:
        """SLO-aware admission control (off by default): reject a waiting
        request up front, with an explicit error, when its TTFT SLO is
        already infeasible at the current measured step time.

        The feasibility model is deliberately coarse — prefill throughput
        is bounded by ``groups * chunk_lanes * chunk_size`` tokens per
        step, so a request behind ``ahead`` backlogged prompt tokens needs
        at least ``ceil((ahead + own) / cap)`` more steps before its first
        token, each costing the engine's step-time EMA.  Queueing time
        already spent counts too.  Requests without a TTFT SLO are never
        rejected; with no EMA yet (cold engine) everything is admitted."""
        if not self.ecfg.admission_control:
            return
        ema = self.monitor.ema
        if not ema:
            return
        now = time.perf_counter()
        cap = self.groups * self.chunk_lanes * self.chunk_size
        backlog = sum(len(st.padded) - st.prefill_pos
                      for st in self.slots
                      if st is not None and st.phase == "prefill")
        arrived = [r for r in self.waiting
                   if r.arrival_step <= self.step_count]
        if self.ecfg.scheduler == "slo":
            arrived.sort(key=lambda r: (-r.priority, self._seq[r.uid]))
        ahead = backlog
        reject = []
        for r in arrived:
            padded = -(-len(r.prompt) // self.page_size) * self.page_size
            if r.ttft_slo_s is not None:
                steps = -(-(ahead + padded) // cap)
                est = ((now - self._arrival_t.get(r.uid, now))
                       + steps * ema)
                if est > r.ttft_slo_s:
                    reject.append((r, est, steps))
                    continue
            ahead += padded
        for r, est, steps in reject:
            self.waiting.remove(r)
            self.finished.append(FinishedRequest(
                uid=r.uid, prompt_len=len(r.prompt), tokens=[], slot=-1,
                admitted_step=-1, finished_step=self.step_count,
                ttft_s=float("nan"), tpot_s=float("nan"),
                token_latencies_s=[], priority=r.priority,
                error=(f"rejected: TTFT SLO {r.ttft_slo_s * 1e3:.1f} ms "
                       f"infeasible (>= {steps} prefill steps "
                       f"~ {est * 1e3:.1f} ms at current load)")))
            self._seq.pop(r.uid, None)
            self.stats["admission_rejects"] += 1

    def _lowest_priority_active(self) -> Optional[int]:
        active = [s for s, st in enumerate(self.slots) if st is not None]
        if not active:
            return None
        return min(active, key=lambda s: (self.slots[s].req.priority,
                                          -self.slots[s].admitted_step, -s))

    def _try_alloc(self, n: int, group: int, restore: bool = False):
        """(pages | None, chaos_denied) from ``group``'s allocator.  An
        injected denial models transient allocator exhaustion: the
        admission blocks this step and retries on the next — it must never
        trigger preemption."""
        if self.chaos and self.chaos.deny_alloc(self.step_count):
            self.stats["alloc_denials"] += 1
            return None, True
        alloc = self.allocators[group]
        pages = alloc.restore(n) if restore else alloc.alloc(n)
        return pages, False

    # -- engine iteration ---------------------------------------------------

    def _next_candidate(self):
        """Head-of-line admission candidate, or None.  FCFS: strictly the
        waiting head.  SLO: the best of (preempted + *arrived* waiting) by
        (priority desc, submission order) — re-admissions compete with
        fresh work on equal terms, and admission never skips past a better
        candidate that is blocked (no priority inversion via bypass)."""
        if self.ecfg.scheduler == "fcfs":
            if self.waiting and self.waiting[0].arrival_step <= self.step_count:
                return ("new", 0)
            return None
        best, best_key = None, None
        for i, rec in enumerate(self.preempted):
            key = (-rec.st.req.priority, rec.seq)
            if best_key is None or key < best_key:
                best, best_key = ("pre", i), key
        for i, req in enumerate(self.waiting):
            if req.arrival_step > self.step_count:
                continue
            key = (-req.priority, self._seq[req.uid])
            if best_key is None or key < best_key:
                best, best_key = ("new", i), key
        return best

    def _admit(self) -> None:
        # Admit first, shed after: the queue bound applies to what remains
        # waiting once this step's capacity is used — never to a request a
        # free slot could serve right now.
        self._admit_loop()
        self._shed()

    def _probe_prefix(self, req: Request, group: int) -> _PrefixMatch:
        """Probe ``group``'s prefix index for the request's whole prompt
        pages and PIN every hit (take a reference) before any allocation —
        an alloc drawing on the cached pool could otherwise reclaim a
        just-probed page.  The caller must ``_release_prefix`` if admission
        blocks.  The longest matched *chain* wins: a miss at page j stops
        the scan (page j+1's contents depend on page j's tokens).  Prefix
        indexes are per slot group: pages only exist in their group's pool
        shard (cross-group sharing is the ROADMAP cross-engine item)."""
        alloc = self.allocators[group]
        plen = len(req.prompt)
        bs = self.page_size
        padded_len = -(-plen // bs) * bs
        budgets = self.policy.prefill_budgets(padded_len)
        keys = paged_lib.prefix_page_keys(req.prompt, budgets, bs)
        # The page holding the prompt's LAST token is always replayed (its
        # position produces the first generated token's logits), and the
        # replay chunk rewrites it — a hit there goes to the CoW list.
        last_page = (plen - 1) // bs
        shared, cow = [], []
        for j, key in enumerate(keys):
            p = alloc.probe(key)
            if p is None:
                break
            alloc.share(p)
            (shared if j < last_page else cow).append(p)
        return _PrefixMatch(keys=keys, shared=shared, cow=cow)

    def _release_prefix(self, prefix: Optional[_PrefixMatch],
                        group: int) -> None:
        if prefix is not None and (prefix.shared or prefix.cow):
            self.allocators[group].free(prefix.shared + prefix.cow)

    def _candidate_groups(self) -> list:
        """Placement preference for a NEW request: groups with a free slot
        first, then most available pages, then the lowest group id — cheap
        host-side balancing across the dp slot groups.  Restores never get
        a choice: a preempted request's snapshot bytes belong to its
        original group's pool shard."""
        def key(g):
            return (self._free_slot_in(g) is None,
                    -self.allocators[g].available, g)
        return sorted(range(self.groups), key=key)

    def _admit_loop(self) -> None:
        while True:
            cand = self._next_candidate()
            if cand is None:
                return
            kind, idx = cand
            if kind == "new":
                req = self.waiting[idx]
                prio = req.priority
                npages_full = self._pages_needed(len(req.prompt),
                                                 req.max_new_tokens)
                groups = self._candidate_groups()
            else:
                rec = self.preempted[idx]
                prio = rec.st.req.priority
                groups = [rec.group]
            # Try each eligible group in preference order; the head-of-line
            # candidate waits (no bypass) only when EVERY group is blocked.
            placed = False
            for g in groups:
                prefix = None
                if kind == "new":
                    npages = npages_full
                    if self.ecfg.prefix_cache:
                        prefix = self._probe_prefix(req, g)
                        npages -= len(prefix.shared)
                else:
                    npages = rec.npages
                slot = self._free_slot_in(g)
                if slot is None:
                    if not self._try_preempt_for(prio, npages, g):
                        self._release_prefix(prefix, g)
                        continue            # slot-blocked in this group
                    slot = self._free_slot_in(g)
                pages, denied = self._try_alloc(npages, g,
                                                restore=(kind == "pre"))
                if denied:
                    self._release_prefix(prefix, g)
                    return                  # transient exhaustion — retry later
                while pages is None:
                    if not self._try_preempt_for(prio, npages, g):
                        break
                    pages, denied = self._try_alloc(npages, g,
                                                    restore=(kind == "pre"))
                    if denied:
                        self._release_prefix(prefix, g)
                        return
                if pages is None:
                    self._release_prefix(prefix, g)
                    continue                # memory-blocked in this group
                placed = True
                break
            if not placed:
                return                      # head-of-line waits everywhere
            if kind == "pre":
                del self.preempted[idx]
                if not self._admit_restore(rec, slot, pages):
                    return                  # restore failed — handled inside
                continue
            del self.waiting[idx]
            self._admit_new(req, slot, pages, prefix)

    def _admit_new(self, req: Request, slot: int, pages: list,
                   prefix: Optional[_PrefixMatch] = None) -> None:
        plen = len(req.prompt)
        npages_prompt = -(-plen // self.page_size)
        padded_len = npages_prompt * self.page_size
        shared = list(prefix.shared) if prefix else []
        n_share = len(shared)
        all_pages = shared + list(pages)
        # Full reservation, trash-padded: shared prefix pages first (the
        # page table is position-ordered), then the private allocation.
        row = np.zeros((self.ecfg.max_pages_per_slot,), np.int32)
        row[:len(all_pages)] = all_pages
        if self._slot_ever_used[slot]:
            self.stats["slots_reused"] += 1
        self._slot_ever_used[slot] = True
        self.page_table[slot] = row
        self.slot_pages[slot] = all_pages
        self.slot_nshared[slot] = n_share
        now = time.perf_counter()
        arrival = self._arrival_t.pop(req.uid, now)
        self.stats["admissions"] += 1
        self.stats["queue_wait_s"] += now - arrival

        if self.ecfg.monolithic_prefill:
            # Legacy: prefill the whole prompt at admission (resets the
            # reserved pages inside prefill_kv_pages), per-length trace.
            # The first token is sampled ON-DEVICE (same op as the async
            # step) — the admission fetch is one int32, not a logits row.
            toks = np.zeros((1, padded_len), np.int32)
            toks[0, :plen] = req.prompt
            first_id, self.pools = self._prefill(
                self.params, jnp.asarray(toks),
                jnp.asarray(plen, jnp.int32), self.pools,
                jnp.asarray(row))
            first = int(first_id)
            self.stats["id_fetches"] += 1
            done = time.perf_counter()
            self.stats["prefills"] += 1
            self.stats["tokens_generated"] += 1
            self.cache_lens[slot] = plen
            st = _SlotState(
                req=req, tokens=[first], admitted_step=self.step_count,
                admit_t=now, arrival_t=arrival, phase="decode",
                prefill_pos=padded_len,
                padded=np.zeros((0,), np.int32), true_len=plen,
                ttft_s=done - arrival, first_token_t=done, last_token_t=done,
                last_sched_step=self.step_count)
            self.slots[slot] = st
            if self._is_finished(st):
                self._recycle(slot)
            return

        # Chunked: reset the PRIVATE reservation to pristine (recycled
        # pages are dirty; chunk writes + decode increments assume fresh
        # pages).  Shared prefix pages carry live canonical contents and
        # must NOT be reset.  The reset row is the same fixed trash-padded
        # width either way — no new traces.
        g = self._group_of(slot)
        if self.smesh is not None:
            fresh_rows = np.zeros((self.groups, self.ecfg.max_pages_per_slot),
                                  np.int32)
            fresh_rows[g, :len(pages)] = pages
            self.pools = self._reset(self.pools, jnp.asarray(fresh_rows))
        else:
            fresh_row = np.zeros((self.ecfg.max_pages_per_slot,), np.int32)
            fresh_row[:len(pages)] = pages
            self.pools = self._reset(self.pools, jnp.asarray(fresh_row))
        if prefix and prefix.cow:
            # Copy-on-write: a fully-matched exact-page-multiple prompt
            # still replays its final page (first-token logits), and the
            # replay chunk REWRITES that page — so the matched page's
            # contents are copied into the private page at table index
            # n_share and the probe's pin on the original is dropped.
            src = prefix.cow[0]
            dst = pages[0]
            if self.smesh is not None:
                # Non-target groups copy trash page 0 onto itself (no-op).
                srcv = np.zeros((self.groups,), np.int32)
                dstv = np.zeros((self.groups,), np.int32)
                srcv[g], dstv[g] = src, dst
                self.pools = self._page_copy(self.pools, jnp.asarray(srcv),
                                             jnp.asarray(dstv))
            else:
                self.pools = self._page_copy(self.pools,
                                             jnp.asarray(src, jnp.int32),
                                             jnp.asarray(dst, jnp.int32))
            self.allocators[g].free([src])
            self.allocators[g].cows += 1  # private dst came from the bulk
                                          # alloc, not allocator.cow()
            self.stats["prefix_cows"] += 1
        if prefix and (prefix.shared or prefix.cow):
            self.stats["prefix_hits"] += 1
            self.stats["prefix_pages_shared"] += n_share
        ptoks = np.zeros((padded_len,), np.int32)
        ptoks[:plen] = req.prompt
        self.cache_lens[slot] = 0
        # The prefill cursor starts past the matched prefix: only the
        # unmatched suffix (always >= one page — the last-token page is
        # replayed) flows through the chunk lane.
        self.slots[slot] = _SlotState(
            req=req, tokens=[], admitted_step=self.step_count,
            admit_t=now, arrival_t=arrival, phase="prefill",
            prefill_pos=n_share * self.page_size,
            padded=ptoks, true_len=plen, last_sched_step=self.step_count,
            prefix_keys=list(prefix.keys) if prefix else [])

    def _is_finished(self, st: _SlotState) -> bool:
        if len(st.tokens) >= st.req.max_new_tokens:
            return True
        return self.ecfg.eos_id is not None and st.tokens[-1] == self.ecfg.eos_id

    def _recycle(self, slot: int) -> None:
        st = self.slots[slot]
        st.finished = True   # async: the one speculative EOS-lookahead
                             # step reconciles against this flag and is
                             # discarded for free
        # TPOT is undefined for a single-output-token request (no
        # post-first token) — record NaN so means can exclude it.
        tpot = (float("nan") if len(st.tokens) < 2 else
                (st.last_token_t - st.first_token_t) / (len(st.tokens) - 1))
        self.finished.append(FinishedRequest(
            uid=st.req.uid, prompt_len=len(st.req.prompt), tokens=st.tokens,
            slot=slot, admitted_step=st.admitted_step,
            finished_step=self.step_count, ttft_s=st.ttft_s, tpot_s=tpot,
            token_latencies_s=st.token_latencies_s,
            priority=st.req.priority, preemptions=st.preemptions,
            queue_s=st.admit_t - st.arrival_t))
        # Retire the uid: submission order only matters while the request is
        # schedulable, and benchmarks legitimately replay a trace (same
        # uids) against a warmed engine.
        self._seq.pop(st.req.uid, None)
        # Shared refs decrement (co-tenants keep the pages); a registered
        # page at ref 0 parks in the allocator's cached set, contents
        # intact, so the NEXT tenant with this prefix still hits.
        self.allocators[self._group_of(slot)].free(self.slot_pages[slot])
        self.page_table[slot] = 0
        self.cache_lens[slot] = 0
        self.slot_pages[slot] = None
        self.slot_nshared[slot] = 0
        self.slots[slot] = None

    def _decode_key(self, s: int, now: float):
        """Decode-token grant order.  SLO: priority first, then remaining
        TPOT headroom (violators and near-deadline slots first; no-SLO
        slots last within the tier), then least-recently-served for
        round-robin fairness under budget pressure."""
        st = self.slots[s]
        if self.ecfg.scheduler == "fcfs":
            return (0, 0.0, st.admitted_step, s)
        slo = st.req.tpot_slo_s
        headroom = (slo - (now - st.last_token_t)) if slo else float("inf")
        return (-st.req.priority, headroom, st.last_sched_step, s)

    def _chunk_key(self, s: int, now: float):
        """Chunk grant order: priority, then remaining TTFT headroom."""
        st = self.slots[s]
        if self.ecfg.scheduler == "fcfs":
            return (0, 0.0, st.admitted_step, s)
        slo = st.req.ttft_slo_s
        headroom = (slo - (now - st.arrival_t)) if slo else float("inf")
        return (-st.req.priority, headroom, st.admitted_step, s)

    def _grantable_decodes(self) -> list:
        """Decode-phase slots still owed a token.  Sync: every active
        decode slot (a finished slot recycles immediately, so the grant
        condition is vacuous).  Async: the grant accounting counts
        IN-FLIGHT tokens too — a max-new-tokens finish is deterministic
        at dispatch time and never speculates; only an unknowable EOS
        earns the single lookahead step, whose discard is free."""
        return [s for s, st in enumerate(self.slots)
                if st is not None and st.phase == "decode"
                and len(st.tokens) + st.inflight < st.req.max_new_tokens]

    def _schedule(self, dec_all: list, pre_all: list,
                  sched_now: float) -> tuple:
        """The token-budget grant pass, shared verbatim by the sync and
        async paths: partition this step's decode grants and prefill-chunk
        grants per slot group.  Pure host bookkeeping — nothing here
        touches the device, which is what lets the async loop run it for
        step N+1 while step N is still in flight.  Returns
        ``(dec, grants)``: the granted decode slots (all groups) and the
        per-group lists of granted chunk slots."""
        self.stats["max_concurrency"] = max(self.stats["max_concurrency"],
                                            len(dec_all) + len(pre_all))
        G, Sg = self.groups, self.slots_per_group
        C = self.chunk_size
        cap = max(1, self.token_budget)         # per slot group
        dec, grants = [], []
        for g in range(G):
            # Token budget: decode tokens first — ordered by (priority, SLO
            # headroom, least-recently-served); FCFS: admission order —
            # with decodes beyond the budget deferred to later steps.
            dec_g_all = sorted((s for s in dec_all if s // Sg == g),
                               key=lambda s: self._decode_key(s, sched_now))
            dec_g = dec_g_all[:cap]
            deferred = dec_g_all[cap:]
            self.stats["decode_deferrals"] += len(deferred)

            # Adaptive chunk sizing: under this group's decode-lane TPOT
            # pressure (a decode was deferred, or a TPOT SLO is currently
            # violated) cap the chunk grant at one lane — prefill yields
            # to the decode SLOs.
            pre_g = sorted((s for s in pre_all if s // Sg == g),
                           key=lambda s: self._chunk_key(s, sched_now))
            pressure = False
            if self.ecfg.scheduler == "slo":
                violating = any(
                    self.slots[s].req.tpot_slo_s is not None
                    and sched_now - self.slots[s].last_token_t
                        > self.slots[s].req.tpot_slo_s
                    for s in dec_g_all)
                pressure = bool(deferred) or violating
            lanes_cap = 1 if pressure else self.chunk_lanes
            if pressure and pre_g and lanes_cap < self.chunk_lanes:
                self.stats["chunk_caps"] += 1

            # Whole chunks into the static chunk lanes, priority/TTFT-
            # headroom order (FCFS: admission order).  Always grant at
            # least one chunk when prefill work exists and nothing else
            # would run in this group, and force one when prefill has
            # starved ``chunk_starve_steps`` steps — the bounded overdraft
            # that keeps decode saturation from starving prefill forever.
            remaining = self.token_budget - len(dec_g)
            grant_g = []
            for s in pre_g:
                if len(grant_g) >= lanes_cap:
                    break
                if remaining >= C or (not grant_g and not dec_g):
                    grant_g.append(s)
                    remaining -= C
            if (not grant_g and pre_g and
                    self.step_count - self._last_chunk_step[g]
                    >= self.ecfg.chunk_starve_steps):
                grant_g = [pre_g[0]]
                self.stats["starvation_grants"] += 1
            if grant_g or not pre_g:
                self._last_chunk_step[g] = self.step_count
            dec += dec_g
            grants.append(grant_g)
        return dec, grants

    def _mixed_step(self) -> bool:
        """One SYNCHRONOUS unified-step invocation: the scheduled decode
        tokens plus as many prefill chunks as the token budget admits, for
        EVERY slot group at once — the replicated host scheduler
        partitions its grants per group (each group gets the full
        per-group token budget and its own chunk lanes), and one jitted
        call advances all of them; the host then blocks on the logits
        fetch and samples with ``np.argmax``.  This is the
        ``async_depth=0`` differential oracle.  Returns whether any work
        ran (for straggler timing)."""
        dec_all = self._grantable_decodes()
        pre_all = [s for s, st in enumerate(self.slots)
                   if st is not None and st.phase == "prefill"]
        if not dec_all and not pre_all:
            self._last_chunk_step = [self.step_count] * self.groups
            return False
        # Injection point: strictly BEFORE any pool mutation, so a bounded
        # retry of this step never double-applies summary increments.
        if self.chaos:
            self.chaos.maybe_fail_step(self.step_count)
        with _span("engine.schedule"):
            dec, grants = self._schedule(dec_all, pre_all,
                                         time.perf_counter())
        with _span("engine.inputs"):
            dec_in, tab_in, len_in, chunk = self._sync_inputs(dec, grants)
        any_grant = any(grants)
        t_dispatch = time.perf_counter()
        with _span("engine.dispatch"):
            dec_logits, chunk_logits, self.pools = self._unified(
                self.params, self.pools, dec_in, tab_in, len_in, chunk)
        t_fetch = time.perf_counter()
        self.stats["dispatch_s"] += t_fetch - t_dispatch
        # The ONLY per-step host syncs, mesh or not: one logits fetch per
        # active lane kind (tracked so the scaling benchmark can assert the
        # mesh adds none).
        with _span("engine.wait"):
            if dec:
                dec_logits = np.asarray(dec_logits)
                if self.smesh is not None:
                    dec_logits = dec_logits.reshape(self.total_slots, -1)
                self.stats["host_syncs"] += 1
            if any_grant:
                chunk_logits = np.asarray(chunk_logits)
                if self.smesh is None:
                    chunk_logits = chunk_logits[None]   # (1, L, vocab)
                self.stats["host_syncs"] += 1
        now = time.perf_counter()
        self.stats["sync_wait_s"] += now - t_fetch
        self.stats["step_calls"] += 1
        if dec:
            self.stats["decode_steps"] += 1
        with _span("engine.emit"):
            self._emit_sync(dec, grants, dec_logits, chunk_logits, now)
        return True

    def _sync_inputs(self, dec: list, grants: list):
        """The synchronous step's device inputs: decode tokens, page-table
        rows and lengths for the granted decode slots, and the chunk lane
        (None when no chunk was granted)."""
        G, Sg = self.groups, self.slots_per_group
        C = self.chunk_size
        T, P = self.total_slots, self.ecfg.max_pages_per_slot
        tokens = np.zeros((T, 1), np.int32)
        dec_table = np.zeros((T, P), np.int32)
        dec_lens = np.zeros((T,), np.int32)
        for s in dec:
            tokens[s, 0] = self.slots[s].tokens[-1]
            dec_table[s] = self.page_table[s]
            dec_lens[s] = self.cache_lens[s]
            self.slots[s].last_sched_step = self.step_count

        any_grant = any(grants)
        chunk = None
        if any_grant:
            # Narrow chunked-prefill lane: L = chunk_lanes rows PER GROUP,
            # lane i carrying that group's i-th granted chunk.  With no
            # grants anywhere the step runs the decode-only signature —
            # two static traces total, never per-prompt-length.
            L, nc = self.chunk_lanes, C // self.page_size
            ctoks = np.zeros((G, L, C), np.int32)
            ctable = np.zeros((G, L, P), np.int32)
            cstart = np.zeros((G, L), np.int32)
            ctrue = np.zeros((G, L), np.int32)
            cbud = np.zeros((G, L, nc), np.int32)
            clast = np.zeros((G, L), np.int32)
            for g, grant_g in enumerate(grants):
                for lane, s in enumerate(grant_g):
                    st = self.slots[s]
                    pos = st.prefill_pos
                    avail = st.padded[pos:pos + C]
                    ctoks[g, lane, :len(avail)] = avail
                    ctable[g, lane] = self.page_table[s]
                    cstart[g, lane] = pos
                    ctrue[g, lane] = st.true_len
                    cbud[g, lane] = chunked_lib.chunk_budget_rows(
                        self.policy, len(st.padded), pos, nc)
                    clast[g, lane] = min(max(st.true_len - 1 - pos, 0), C - 1)
            grp = (lambda a: a) if self.smesh is not None else (lambda a: a[0])
            chunk = {"tokens": jnp.asarray(grp(ctoks)),
                     "page_table": jnp.asarray(grp(ctable)),
                     "start": jnp.asarray(grp(cstart)),
                     "true_len": jnp.asarray(grp(ctrue)),
                     "budgets": jnp.asarray(grp(cbud)),
                     "last": jnp.asarray(grp(clast))}

        if self.smesh is not None:
            dec_in = jnp.asarray(tokens.reshape(G, Sg, 1))
            tab_in = jnp.asarray(dec_table.reshape(G, Sg, P))
            len_in = jnp.asarray(dec_lens.reshape(G, Sg))
        else:
            dec_in = jnp.asarray(tokens)
            tab_in = jnp.asarray(dec_table)
            len_in = jnp.asarray(dec_lens)
        return dec_in, tab_in, len_in, chunk

    def _emit_sync(self, dec: list, grants: list, dec_logits, chunk_logits,
                   now: float) -> None:
        """Absorb one synchronous step's logits: argmax each granted lane,
        append and time-stamp the tokens, finish prefills (registering
        their prefix pages) and recycle finished slots."""
        C = self.chunk_size
        for s in dec:
            self.cache_lens[s] += 1       # the fed-back token is now cached
            st = self.slots[s]
            st.tokens.append(int(np.argmax(dec_logits[s])))
            st.token_latencies_s.append(now - st.last_token_t)
            st.last_token_t = now
            self.stats["tokens_generated"] += 1
            if self._is_finished(st):
                self._recycle(s)

        for g, grant_g in enumerate(grants):
            for lane, s in enumerate(grant_g):
                st = self.slots[s]
                st.prefill_pos += C
                self.stats["chunks"] += 1
                if st.prefill_pos >= len(st.padded):
                    # This chunk completed the prompt: its logits at the
                    # true last token are the request's first generated
                    # token.
                    st.tokens = [int(np.argmax(chunk_logits[g, lane]))]
                    st.phase = "decode"
                    self.cache_lens[s] = st.true_len
                    if st.prefix_keys:
                        # Contents of every full prompt page are now final
                        # — content-address them for future tenants
                        # (idempotent for pages this request itself
                        # shared; the partial tail page has no key and
                        # stays private).
                        for j, key in enumerate(st.prefix_keys):
                            self.allocators[g].register(
                                self.slot_pages[s][j], key)
                    st.first_token_t = st.last_token_t = now
                    st.ttft_s = now - st.arrival_t
                    self.stats["prefills"] += 1
                    self.stats["tokens_generated"] += 1
                    if self._is_finished(st):
                        self._recycle(s)

    # -- async pipeline -----------------------------------------------------

    def _dispatch(self, dec: list, grants: list) -> None:
        """Launch one sampled unified step and return WITHOUT waiting for
        its results.  All value-independent state advances here, at
        dispatch time, so the next ``_schedule`` sees it: ``cache_lens``
        (+1 per granted decode — the fed-back token will be cached),
        ``prefill_pos``/phase flips, prefix registration (the completing
        chunk's writes land before any later-dispatched reader, by
        per-device program order), and the step/chunk/prefill counters.
        Token VALUES — emissions, EOS, timestamps — wait for
        ``_reconcile``.  Decode inputs come from the device-resident
        ``token_buf``; idle lanes are masked out and their trash-page
        writes discarded, exactly like the sync step."""
        with _span("engine.inputs"):
            mask_in, tab_in, len_in, chunk, dec_entries, chunk_entries = (
                self._async_inputs(dec, grants))
        t0 = time.perf_counter()
        with _span("engine.dispatch"):
            dec_ids, chunk_ids, self.token_buf, self.pools = self._unified(
                self.params, self.pools, self.token_buf, mask_in, tab_in,
                len_in, chunk)
        t1 = time.perf_counter()
        self.stats["dispatch_s"] += t1 - t0
        self.stats["step_calls"] += 1
        if dec:
            self.stats["decode_steps"] += 1

        for s in dec:
            self.cache_lens[s] += 1   # the fed-back token is now cached
            self.slots[s].inflight += 1
        for g, lane, s, st, completes in chunk_entries:
            st.prefill_pos += self.chunk_size
            self.stats["chunks"] += 1
            if completes:
                st.phase = "decode"
                self.cache_lens[s] = st.true_len
                st.inflight += 1      # the first token is in flight
                if st.prefix_keys:
                    for j, key in enumerate(st.prefix_keys):
                        self.allocators[g].register(
                            self.slot_pages[s][j], key)
                self.stats["prefills"] += 1
        self._inflight.append(_InFlight(
            dec_ids=dec_ids, chunk_ids=chunk_ids, dec=dec_entries,
            chunks=chunk_entries, step=self.step_count, dispatch_t=t1))

    def _async_inputs(self, dec: list, grants: list):
        """The async step's device inputs (decode mask, page-table rows and
        lengths, the chunk lane or None) and the host entries its reconcile
        will absorb: ``(slot, state)`` per decode and ``(group, lane, slot,
        state, completes)`` per chunk."""
        G, Sg, C = self.groups, self.slots_per_group, self.chunk_size
        T, P = self.total_slots, self.ecfg.max_pages_per_slot
        mask = np.zeros((T,), bool)
        dec_table = np.zeros((T, P), np.int32)
        dec_lens = np.zeros((T,), np.int32)
        dec_entries = []
        for s in dec:
            st = self.slots[s]
            mask[s] = True
            dec_table[s] = self.page_table[s]
            dec_lens[s] = self.cache_lens[s]
            st.last_sched_step = self.step_count
            dec_entries.append((s, st))

        any_grant = any(grants)
        chunk = None
        chunk_entries = []
        if any_grant:
            L, nc = self.chunk_lanes, C // self.page_size
            ctoks = np.zeros((G, L, C), np.int32)
            ctable = np.zeros((G, L, P), np.int32)
            cstart = np.zeros((G, L), np.int32)
            ctrue = np.zeros((G, L), np.int32)
            cbud = np.zeros((G, L, nc), np.int32)
            clast = np.zeros((G, L), np.int32)
            # Chunk-lane feedback routing: a COMPLETING chunk's sampled id
            # is the request's first token — "emit" steers it into the
            # lane's slot entry of token_buf inside the trace, so the
            # decode that follows next step reads it with no host hop.
            cslot = np.zeros((G, L), np.int32)
            cemit = np.zeros((G, L), bool)
            for g, grant_g in enumerate(grants):
                for lane, s in enumerate(grant_g):
                    st = self.slots[s]
                    pos = st.prefill_pos
                    avail = st.padded[pos:pos + C]
                    ctoks[g, lane, :len(avail)] = avail
                    ctable[g, lane] = self.page_table[s]
                    cstart[g, lane] = pos
                    ctrue[g, lane] = st.true_len
                    cbud[g, lane] = chunked_lib.chunk_budget_rows(
                        self.policy, len(st.padded), pos, nc)
                    clast[g, lane] = min(max(st.true_len - 1 - pos, 0),
                                         C - 1)
                    completes = pos + C >= len(st.padded)
                    cslot[g, lane] = s - g * Sg
                    cemit[g, lane] = completes
                    chunk_entries.append((g, lane, s, st, completes))
            grp = ((lambda a: a) if self.smesh is not None
                   else (lambda a: a[0]))
            chunk = {"tokens": jnp.asarray(grp(ctoks)),
                     "page_table": jnp.asarray(grp(ctable)),
                     "start": jnp.asarray(grp(cstart)),
                     "true_len": jnp.asarray(grp(ctrue)),
                     "budgets": jnp.asarray(grp(cbud)),
                     "last": jnp.asarray(grp(clast)),
                     "slot": jnp.asarray(grp(cslot)),
                     "emit": jnp.asarray(grp(cemit))}

        if self.smesh is not None:
            mask_in = jnp.asarray(mask.reshape(G, Sg))
            tab_in = jnp.asarray(dec_table.reshape(G, Sg, P))
            len_in = jnp.asarray(dec_lens.reshape(G, Sg))
        else:
            mask_in = jnp.asarray(mask)
            tab_in = jnp.asarray(dec_table)
            len_in = jnp.asarray(dec_lens)
        return mask_in, tab_in, len_in, chunk, dec_entries, chunk_entries

    def _reconcile(self, infl: _InFlight) -> None:
        """Absorb one in-flight step's sampled ids into host state: append
        decode tokens, materialize chunk-completion first tokens, stamp
        emission timestamps, detect EOS/max-tokens, recycle.  Entries
        whose request finished in the meantime (the EOS one-step
        lookahead, or an abort) are DISCARDED — their speculative step
        wrote only into the request's own still-reserved pages, so the
        discard costs nothing and streams stay bit-identical to the sync
        oracle.  ``host_syncs`` counts only non-overlapped reconciles
        (no newer dispatched step behind this one): those are the fetches
        that can leave the device idle — O(finished requests), not
        O(steps)."""
        with _span("engine.reconcile"):
            overlapped = bool(self._inflight)
            t0 = time.perf_counter()
            dec_ids = chunk_ids = None
            with _span("engine.wait"):
                if infl.dec:
                    dec_ids = np.asarray(infl.dec_ids)
                    if self.smesh is not None:
                        dec_ids = dec_ids.reshape(-1)
                    self.stats["id_fetches"] += 1
                if infl.chunks:
                    chunk_ids = np.asarray(infl.chunk_ids)
                    if self.smesh is None:
                        chunk_ids = chunk_ids[None]     # (1, L)
                    self.stats["id_fetches"] += 1
            now = time.perf_counter()
            self.stats["sync_wait_s"] += now - t0
            if not overlapped and (infl.dec or infl.chunks):
                self.stats["host_syncs"] += 1
            self.monitor.observe(infl.step, now - infl.dispatch_t)
            with _span("engine.emit"):
                self._emit_async(infl, dec_ids, chunk_ids, now)

    def _emit_async(self, infl: _InFlight, dec_ids, chunk_ids,
                    now: float) -> None:
        """Absorb one reconciled step's sampled ids: append and time-stamp
        the tokens of requests still running, discard the lookahead of
        finished ones, recycle."""
        for s, st in infl.dec:
            st.inflight -= 1
            if st.finished:
                self.stats["lookahead_discards"] += 1
                continue
            st.tokens.append(int(dec_ids[s]))
            st.token_latencies_s.append(now - st.last_token_t)
            st.last_token_t = now
            self.stats["tokens_generated"] += 1
            if self._is_finished(st):
                self._recycle(s)
        for g, lane, s, st, completes in infl.chunks:
            if not completes:
                continue
            st.inflight -= 1
            if st.finished:
                self.stats["lookahead_discards"] += 1
                continue
            st.tokens = [int(chunk_ids[g, lane])]
            st.first_token_t = st.last_token_t = now
            st.ttft_s = now - st.arrival_t
            self.stats["tokens_generated"] += 1
            if self._is_finished(st):
                self._recycle(s)

    def _drain(self) -> None:
        """Reconcile every in-flight step, oldest first.  Callers that
        mutate pools or host token state out of band (preemption/offload,
        injected-failure aborts, the run() tail) must drain first: the
        device pipeline is always safe under program order, but host-side
        ``st.tokens`` runs one step behind it."""
        while self._inflight:
            self._reconcile(self._inflight.popleft())

    def drain(self) -> None:
        """Public: block until every dispatched step is reconciled.
        No-op for the synchronous engine.  Drivers stepping the engine
        manually (rather than through ``run``) call this before reading
        ``finished``/``stats`` as final."""
        self._drain()

    def _async_step(self) -> bool:
        """One ASYNC engine iteration: schedule from the current (one step
        stale in values, exact in structure) host state, dispatch without
        blocking, then reconcile only what exceeds ``async_depth``.  With
        depth 1 the host prepares and launches step N+1 while the device
        crunches step N — the logits-fetch stall of the sync loop
        disappears from the critical path."""
        dec_all = self._grantable_decodes()
        pre_all = [s for s, st in enumerate(self.slots)
                   if st is not None and st.phase == "prefill"]
        if not dec_all and not pre_all:
            self._last_chunk_step = [self.step_count] * self.groups
            self._drain()
            return False
        # Same injection point as the sync loop: strictly before this
        # step's dispatch, so a bounded retry never double-applies — and
        # the already-in-flight step is untouched by the failure.
        if self.chaos:
            self.chaos.maybe_fail_step(self.step_count)
        with _span("engine.schedule"):
            dec, grants = self._schedule(dec_all, pre_all,
                                         time.perf_counter())
        if not dec and not any(grants):
            # Every grantable token is already in flight (e.g. the final
            # token of the last active request): reconcile to make
            # progress instead of dispatching an empty step.
            self._drain()
            return False
        self._dispatch(dec, grants)
        while len(self._inflight) > self.ecfg.async_depth:
            self._reconcile(self._inflight.popleft())
        return True

    def _guarded_step(self) -> None:
        """The failure boundary around the mixed step: bounded retry of a
        failed step (injection precedes pool mutation, so retry is sound),
        then graceful degradation — abort the lowest-priority active
        request and retry with the smaller batch.  Working steps are timed
        by the StragglerMonitor; failed/idle ones don't pollute its EMA."""
        retries = 0
        while True:
            if not self._async:
                self.monitor.start()
            try:
                did_work = (self._async_step() if self._async
                            else self._mixed_step())
            except InjectedFailure as e:
                if not self._async:
                    self.monitor.cancel()
                self.stats["step_failures"] += 1
                retries += 1
                if retries > self.ecfg.max_step_retries:
                    if self._async:
                        # Drain before degrading: the in-flight step may
                        # finish (or already hold tokens for) the victim
                        # we are about to abort, and the abort frees
                        # pages the pipeline still references host-side.
                        self._drain()
                    victim = self._lowest_priority_active()
                    if victim is None:
                        if self._async:
                            continue   # drain cleared the actives; the
                                       # retry sees no work and returns
                        raise
                    self._abort(victim,
                                f"aborted: step failed {retries} times ({e})")
                    retries = 0
                continue
            if self._async:
                # Step latency is observed per reconcile (dispatch ->
                # ids materialized), not start/stop around the host-only
                # dispatch — see ``_reconcile``.
                return
            if did_work:
                self.monitor.stop(self.step_count)
            else:
                self.monitor.cancel()
            return

    def step(self) -> None:
        """One engine iteration: admit (with preemption) + shed, one guarded
        mixed batched step, recycle."""
        with _span("engine.step"):
            # TTFT and TTFT-SLO headroom count queueing time from when a
            # request could first be scheduled, so a scheduler cannot hide
            # latency in the waiting queue: ``submit`` stamps a request
            # that is schedulable at once, this loop one that arrives at a
            # later step.
            now = time.perf_counter()
            for r in self.waiting:
                if (r.arrival_step <= self.step_count
                        and r.uid not in self._arrival_t):
                    self._arrival_t[r.uid] = now
            with _span("engine.admit"):
                self._admission_control()
                self._admit()
            self._guarded_step()
            self.step_count += 1
            if self._track_fallbacks:
                self._refresh_fallbacks()

    @property
    def pending(self) -> int:
        return (len(self.waiting) + len(self.preempted)
                + sum(st is not None for st in self.slots))

    def run(self, requests=(), max_steps: int = 100_000) -> list:
        """Drive submitted (+ given) requests to completion; returns
        FinishedRequests sorted by uid (failed ones carry ``.error``).
        Raises ``EngineStalledError`` naming the stuck requests if the
        engine cannot drain within ``max_steps`` further steps."""
        for r in requests:
            self.submit(r)
        start = self.step_count
        while self.pending:
            if self.step_count - start >= max_steps:
                raise EngineStalledError(
                    max_steps,
                    running=[st.req.uid for st in self.slots
                             if st is not None],
                    waiting=[r.uid for r in self.waiting],
                    preempted=[rec.st.req.uid for rec in self.preempted])
            self.step()
        if self._inflight:          # belt-and-braces: pending==0 implies
            self._drain()           # drained, but keep the invariant local
        return sorted(self.finished, key=lambda f: f.uid)
