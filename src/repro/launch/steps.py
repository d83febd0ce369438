"""jit-able step functions (train / prefill / serve) + their shardings.

The same builders serve the real drivers (train.py, serve.py) and the
multi-pod dry-run (dryrun.py lowers them from ShapeDtypeStructs).
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import optim
from repro.configs.base import ArchConfig, RunShape
from repro.core.config import StemConfig
from repro.models import attention, mla, registry, transformer
from repro.sharding import rules as rules_lib

PAPER_STEM = StemConfig()   # paper defaults: B=128, mu=0.7, beta=0.2, floor 54

# Every ``stem_cfg`` argument in this module accepts a SparsityPolicy, a
# registered policy name, or a legacy StemConfig (core/policy.py
# ``as_policy``); ``policies`` is the per-layer override map forwarded to
# the transformer ({global layer index: policy}).


def default_stem_cfg(cfg: ArchConfig) -> Optional[StemConfig]:
    return PAPER_STEM if cfg.use_stem else None


def make_train_step(bundle: registry.ModelBundle, opt_cfg: optim.AdamWConfig,
                    *, stem_cfg=None, policies=None,
                    remat: bool = True, microbatches: int = 1,
                    grad_shardings=None):
    """(opt_state, batch) -> (opt_state, metrics).

    Forward in the arch dtype from the fp32 master, optional gradient
    accumulation over ``microbatches``, bf16 gradient compression before the
    data-parallel all-reduce, AdamW on the master.  ``grad_shardings``
    (usually the ZeRO-1 master shardings) pins gradients to the optimizer
    shard so the DP reduction lowers to a reduce-scatter instead of a full
    all-reduce + replicated accumulator.
    """
    cfg = bundle.cfg

    def pin(grads):
        if grad_shardings is None:
            return grads
        return jax.tree.map(jax.lax.with_sharding_constraint, grads, grad_shardings)

    def loss_of(master, mb):
        params = jax.tree.map(lambda m: m.astype(cfg.jnp_dtype), master)
        kw = {"policies": policies} if policies else {}
        loss, metrics = bundle.loss_fn(params, mb, stem_cfg=stem_cfg,
                                       remat=remat, **kw)
        return loss, metrics

    grad_fn = jax.value_and_grad(loss_of, has_aux=True)

    def train_step(opt_state: optim.OptState, batch: dict):
        if microbatches == 1:
            (loss, metrics), grads = grad_fn(opt_state.master, batch)
            grads = pin(grads)
        else:
            mbs = jax.tree.map(
                lambda x: x.reshape((microbatches, x.shape[0] // microbatches)
                                    + x.shape[1:]), batch)

            def acc_body(carry, mb):
                g_acc, l_acc = carry
                (l, m), g = grad_fn(opt_state.master, mb)
                g_acc = jax.tree.map(jnp.add, g_acc, pin(g))
                return (pin(g_acc), l_acc + l), m

            g0 = pin(jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                                  opt_state.master))
            (grads, loss), ms = jax.lax.scan(acc_body, (g0, 0.0), mbs)
            grads = jax.tree.map(lambda g: g / microbatches, grads)
            loss = loss / microbatches
            metrics = jax.tree.map(lambda m: m[-1], ms)
        grads = optim.adamw.compress_grads(grads, opt_cfg)
        new_state, opt_metrics = optim.update(grads, opt_state, opt_cfg)
        return new_state, {**metrics, **opt_metrics, "loss": loss}

    return train_step


def make_prefill_step(bundle: registry.ModelBundle, *, max_len: int,
                      stem_cfg=None, policies=None):
    def prefill_step(params, batch):
        kw = {"policies": policies} if policies else {}
        return bundle.prefill(params, batch, max_len=max_len,
                              stem_cfg=stem_cfg, **kw)
    return prefill_step


def set_cache_positions(caches, cache_lens: jnp.ndarray):
    """Pin every attention/MLA cache's write position to per-sequence
    lengths (``(b,)`` int32).  Cache leaves are stacked ``(n_layers, ...)``,
    so the position leaf becomes ``(n_layers, b)`` and the layer scan hands
    each layer its ``(b,)`` row.  Recurrent/SSM states are position-free and
    pass through untouched."""
    lens = jnp.asarray(cache_lens, jnp.int32)

    def fix(c):
        if isinstance(c, attention.KVCache):
            return c._replace(pos=jnp.broadcast_to(lens, (c.k.shape[0],) + lens.shape))
        if isinstance(c, mla.MLACache):
            return c._replace(pos=jnp.broadcast_to(lens, (c.c_kv.shape[0],) + lens.shape))
        return c

    return jax.tree.map(
        fix, caches,
        is_leaf=lambda x: isinstance(x, (attention.KVCache, mla.MLACache)))


def make_serve_step(bundle: registry.ModelBundle, *, stem_cfg=None,
                    budget_frac: float = 1.0):
    """(params, tokens, caches[, cache_lens]) -> (logits, caches).

    ``cache_lens`` (``(b,)`` int32) overrides the caches' write positions
    per sequence — the ragged fixed-batch path: each row decodes against
    its own prompt length instead of one shared scalar.  Positions advance
    inside the caches afterwards, so pass it only on the first step.

    With ``stem_cfg`` the decode is policy-sparse over the contiguous cache
    (``attention.apply_decode`` summarizes + selects per step) — the
    fixed-batch reference arm for the paged engine's sparse decode."""
    def serve_step(params, tokens, caches, cache_lens=None):
        if cache_lens is not None:
            caches = set_cache_positions(caches, cache_lens)
        if stem_cfg is None:
            return bundle.decode_step(params, tokens, caches)
        return bundle.decode_step(params, tokens, caches,
                                  stem_cfg=stem_cfg, budget_frac=budget_frac)
    return serve_step


# ---------------------------------------------------------------------------
# Paged-engine steps (runtime/engine.py)
# ---------------------------------------------------------------------------

def make_unified_step(bundle: registry.ModelBundle, *, stem_cfg,
                      budget_frac: float = 1.0, chunk_k_max: int = 0,
                      executor=None, on_trace=None, smesh=None,
                      sampler=None):
    """The engine's single step: (params, pools, tokens (S,1),
    page_table (S,P), cache_lens (S,), chunk) ->
    (decode logits (S, vocab), chunk logits (S, vocab) | None, pools,
    moe counts (2,)).

    One mixed batch of decode tokens + prefill chunks per call
    (``transformer.paged_mixed_step``).  With fixed S/P/C the chunked
    engine compiles this **exactly once** for arbitrary prompt lengths —
    the per-length retraces of the old monolithic ``insert_prefill`` are
    gone.  ``chunk=None`` is the decode-only view (one extra trace),
    used by the legacy monolithic arm.  ``executor`` picks the paged
    attention backend ("xla" gather oracle / fused "pallas" kernels; None
    defers to the policy).  ``on_trace`` fires as a Python
    side effect at trace time — the engine's retrace counter.

    With ``smesh`` (a ``sharding.serving.ServingMesh``) every batch
    argument gains a leading slot-group axis — tokens (dp, S, 1),
    page_table (dp, S, P), cache_lens (dp, S), chunk leaves (dp, ...) —
    and the step runs under ``shard_map``: each dp shard vmaps the
    single-device mixed step over its local slot group against its pool
    slice, and each tp shard computes its KV-head block with one
    all-gather at the attention output (``sharding/serving.py``).  Still
    exactly two traces, and bitwise identical per group to the
    single-device step.

    With ``sampler`` (``runtime/sampling.py``) the builder returns the
    SAMPLED signature instead — the async engine's step: (params, pools,
    token_buf (S,), dec_mask (S,), page_table, cache_lens, chunk) ->
    (dec_ids (S,), chunk_ids (L,) | None, token_buf', pools, moe counts).
    Sampling
    runs inside the trace (``transformer.paged_sampled_step``), decode
    inputs come from the device-resident ``token_buf``, and the only
    per-step transfer left is the int32 id arrays.  Under the mesh,
    ``token_buf`` / ``dec_mask`` gain the (dp,) slot-group axis like
    every other batch argument, and the tiny replicated-over-tp id
    arrays replace the per-group logits fetch — the sampled mesh step
    moves O(slots) bytes to the host instead of O(slots * vocab)."""
    cfg = bundle.cfg
    transformer.assert_paged_servable(cfg)

    def mixed_step(params, tokens, pools, page_table, cache_lens, chunk):
        return transformer.paged_mixed_step(
            params, tokens, pools, page_table, cache_lens, cfg,
            stem_cfg=stem_cfg, budget_frac=budget_frac, chunk=chunk,
            chunk_k_max=chunk_k_max, executor=executor)

    def sampled_step(params, buf, mask, pools, page_table, cache_lens,
                     chunk):
        return transformer.paged_sampled_step(
            params, buf, pools, page_table, cache_lens, mask, cfg,
            stem_cfg=stem_cfg, sampler=sampler, budget_frac=budget_frac,
            chunk=chunk, chunk_k_max=chunk_k_max, executor=executor)

    if smesh is None:
        if sampler is None:
            def unified_step(params, pools, tokens, page_table, cache_lens,
                             chunk=None):
                if on_trace is not None:
                    on_trace()
                return mixed_step(params, tokens, pools, page_table,
                                  cache_lens, chunk)
            return unified_step

        def unified_sampled(params, pools, token_buf, dec_mask, page_table,
                            cache_lens, chunk=None):
            if on_trace is not None:
                on_trace()
            return sampled_step(params, token_buf, dec_mask, pools,
                                page_table, cache_lens, chunk)
        return unified_sampled

    from jax.experimental.shard_map import shard_map

    from repro.sharding import serving as serving_lib

    POOL = serving_lib.POOL_SPEC
    GRP = serving_lib.GROUP_SPEC
    REP = serving_lib.REPLICATED

    # Two shard-mapped bodies (mixed / decode-only) mirror the two engine
    # traces — chunk=None is a pytree structure change, not a spec change.
    def _mixed_body(params, pools, tokens, page_table, cache_lens, chunk):
        def one(pools_g, tokens_g, table_g, lens_g, chunk_g):
            return mixed_step(params, tokens_g, pools_g, table_g, lens_g,
                              chunk_g)
        return jax.vmap(one)(pools, tokens, page_table, cache_lens, chunk)

    def _decode_body(params, pools, tokens, page_table, cache_lens):
        def one(pools_g, tokens_g, table_g, lens_g):
            dec, _, new_pools, counts = mixed_step(params, tokens_g, pools_g,
                                                   table_g, lens_g, None)
            return dec, new_pools, counts
        return jax.vmap(one)(pools, tokens, page_table, cache_lens)

    # Sampled twins: same lane structure, id outputs + the fed-back token
    # buffer instead of logits.  The ids are sampled from tp-replicated
    # logits, so they are bitwise replicated over tp by construction.
    def _mixed_sampled_body(params, pools, buf, mask, page_table,
                            cache_lens, chunk):
        def one(pools_g, buf_g, mask_g, table_g, lens_g, chunk_g):
            return sampled_step(params, buf_g, mask_g, pools_g, table_g,
                                lens_g, chunk_g)
        return jax.vmap(one)(pools, buf, mask, page_table, cache_lens, chunk)

    def _decode_sampled_body(params, pools, buf, mask, page_table,
                             cache_lens):
        def one(pools_g, buf_g, mask_g, table_g, lens_g):
            ids, _, new_buf, new_pools, counts = sampled_step(
                params, buf_g, mask_g, pools_g, table_g, lens_g, None)
            return ids, new_buf, new_pools, counts
        return jax.vmap(one)(pools, buf, mask, page_table, cache_lens)

    # check_rep=False: outputs are bitwise replicated over tp by
    # construction (full projections + all-gather before wo), which the
    # replication checker cannot prove through the collectives.
    if sampler is None:
        smapped_mixed = shard_map(
            _mixed_body, mesh=smesh.mesh,
            in_specs=(REP, POOL, GRP, GRP, GRP, GRP),
            out_specs=(GRP, GRP, POOL, GRP), check_rep=False)
        smapped_decode = shard_map(
            _decode_body, mesh=smesh.mesh,
            in_specs=(REP, POOL, GRP, GRP, GRP),
            out_specs=(GRP, POOL, GRP), check_rep=False)

        def unified_step(params, pools, tokens, page_table, cache_lens,
                         chunk=None):
            if on_trace is not None:
                on_trace()
            # The head-sharding context is active while jit traces the
            # shard_map bodies, turning on the TP slicing inside
            # models/attention.py for exactly this trace.
            with serving_lib.head_sharding(smesh.tp):
                if chunk is None:
                    dec, new_pools, counts = smapped_decode(
                        params, pools, tokens, page_table, cache_lens)
                    return dec, None, new_pools, counts
                return smapped_mixed(params, pools, tokens, page_table,
                                     cache_lens, chunk)
        return unified_step

    smapped_mixed_s = shard_map(
        _mixed_sampled_body, mesh=smesh.mesh,
        in_specs=(REP, POOL, GRP, GRP, GRP, GRP, GRP),
        out_specs=(GRP, GRP, GRP, POOL, GRP), check_rep=False)
    smapped_decode_s = shard_map(
        _decode_sampled_body, mesh=smesh.mesh,
        in_specs=(REP, POOL, GRP, GRP, GRP, GRP),
        out_specs=(GRP, GRP, POOL, GRP), check_rep=False)

    def unified_sampled(params, pools, token_buf, dec_mask, page_table,
                        cache_lens, chunk=None):
        if on_trace is not None:
            on_trace()
        with serving_lib.head_sharding(smesh.tp):
            if chunk is None:
                ids, buf, new_pools, counts = smapped_decode_s(
                    params, pools, token_buf, dec_mask, page_table,
                    cache_lens)
                return ids, None, buf, new_pools, counts
            return smapped_mixed_s(params, pools, token_buf, dec_mask,
                                   page_table, cache_lens, chunk)
    return unified_sampled


def make_page_extract():
    """(pools, page_row) -> snapshot: gather one slot's pages (K/V + kg/vm
    summaries) out of every layer's pool for host offload.  ``page_row`` is
    the fixed-width ``(max_pages_per_slot,)`` trash-padded page-id row, so
    the engine jits this exactly once — preemption adds zero traces."""
    from repro.runtime import offload as offload_lib

    def extract_pages(pools, page_row):
        return offload_lib.gather_pages(pools, page_row)
    return extract_pages


def make_page_restore():
    """(pools, page_row, snapshot) -> pools: scatter an offloaded snapshot
    back into freshly allocated pages.  Bit-identical inverse of
    ``make_page_extract`` modulo page renaming (the page-table row carries
    the new mapping); jitted once, donates the pools."""
    from repro.runtime import offload as offload_lib

    def restore_pages(pools, page_row, snapshot):
        return offload_lib.scatter_pages(pools, page_row, snapshot)
    return restore_pages


def make_page_copy():
    """(pools, src, dst) -> pools: duplicate one page (K/V + kg/vm) across
    every layer's pool — the device half of copy-on-write.  ``src``/``dst``
    are traced scalar page ids, so the engine jits this exactly once."""
    from repro.runtime import paged as paged_lib

    def page_copy(pools, src, dst):
        return paged_lib.copy_pages_stacked(pools, src, dst)
    return page_copy


def make_monolithic_prefill(bundle: registry.ModelBundle, *, stem_cfg,
                            on_trace=None, sampler=None):
    """(params, tokens (1, Lp), true_len, pools, page_row) ->
    (next-token logits (vocab,), pools) — or, with ``sampler``,
    (sampled first token id (scalar int32), pools).

    The legacy one-shot admission prefill: one request, right-padded to a
    page multiple, scattered into the pools with its block summaries
    (``transformer.prefill_kv_pages``).  jit retraces one instance per
    padded-length bucket — kept as the A/B baseline for the unified
    chunked step (``benchmarks/serving.py --chunked``) and as the
    fallback for threshold selectors that chunked prefill cannot serve.
    With ``sampler`` the first token is sampled on-device too, so the
    admission fetch is one int32 instead of a vocab-sized logits row."""
    cfg = bundle.cfg
    transformer.assert_paged_servable(cfg)

    def monolithic_prefill(params, tokens, true_len, pools, page_row):
        if on_trace is not None:
            on_trace()
        logits, new_pools = transformer.prefill_kv_pages(
            params, tokens, true_len, pools, page_row, cfg, stem_cfg)
        if sampler is not None:
            return sampler(logits), new_pools
        return logits, new_pools
    return monolithic_prefill


# ---------------------------------------------------------------------------
# Shardings for the step arguments
# ---------------------------------------------------------------------------

def opt_state_shardings(cfg: ArchConfig, mesh, param_sh, abstract_values=None):
    """OptState sharded like the parameters, plus ZeRO-1 sharding over the
    `pod` axis when one exists (abstract_values supplies shapes)."""
    rep = NamedSharding(mesh, P())
    opt_sh = param_sh
    if abstract_values is not None:
        opt_sh = rules_lib.zero1_shardings(cfg, mesh, abstract_values, param_sh)
    return optim.OptState(step=rep, master=opt_sh, mu=opt_sh, nu=opt_sh)


def train_arg_shardings(cfg: ArchConfig, mesh, abstract_values, axes_tree,
                        batch_specs):
    param_sh = rules_lib.param_shardings(cfg, mesh, abstract_values, axes_tree)
    state_sh = opt_state_shardings(cfg, mesh, param_sh)
    batch_sh = rules_lib.batch_sharding(cfg, mesh, batch_specs)
    return state_sh, batch_sh


def abstract_opt_state(abstract_values, opt_cfg: Optional[optim.AdamWConfig] = None):
    mdt = jnp.bfloat16 if (opt_cfg and opt_cfg.moment_dtype == "bfloat16") else jnp.float32
    f32 = lambda v: jax.ShapeDtypeStruct(v.shape, jnp.float32)
    mom = lambda v: jax.ShapeDtypeStruct(v.shape, mdt)
    return optim.OptState(
        step=jax.ShapeDtypeStruct((), jnp.int32),
        master=jax.tree.map(f32, abstract_values),
        mu=jax.tree.map(mom, abstract_values),
        nu=jax.tree.map(mom, abstract_values),
    )
