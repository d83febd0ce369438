"""Decoder-only LM assembly: layer programs, scan-over-layers, loss.

A model is described by a **layer program** — a list of segments
``(num_groups, kinds)`` where ``kinds`` is the tuple of sub-layer kinds in
one group.  Homogeneous stacks compile as a single ``lax.scan`` over stacked
parameters (small HLO, fast 512-way SPMD compiles); heterogeneous patterns
(griffin's rec/rec/attn, deepseek's 3 dense + 58 MoE) become several
segments.  Examples:

  dense 28L:        [(28, ("dense",))]
  deepseek 61L:     [(3, ("mla_dense",)), (58, ("mla_moe",))]
  recurrentgemma:   [(8, ("rec", "rec", "dense_local")), (1, ("rec", "rec"))]
  mamba2 48L:       [(48, ("ssd",))]

Sub-layer kinds: dense | dense_local | moe | mla_dense | mla_moe | rec | ssd.
Every kind supports three phases: full (train/prefill), prefill-with-cache,
and decode-step.

Sparsity is policy-driven: every ``stem_cfg`` argument accepts a
``SparsityPolicy``, a registered policy name, or a legacy ``StemConfig``,
and the full/prefill phases additionally take ``policies`` — a
``{global_layer_index: policy}`` override map, so deep layers can run
leaner budgets than early ones (the paper's cumulative-dependency
argument).  Layers with the same effective policy still compile as one
``lax.scan``; an override only splits the scan at its boundaries.
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core import policy as policy_lib
from repro.core.config import StemConfig
from repro.models import attention, common, mla, mlp, moe, rglru, ssd
from repro.sharding.context import constrain


# ---------------------------------------------------------------------------
# Layer programs
# ---------------------------------------------------------------------------

def layer_program(cfg: ArchConfig) -> list[tuple[int, tuple[str, ...]]]:
    if cfg.family == "ssm":
        return [(cfg.num_layers, ("ssd",))]
    if cfg.family == "hybrid":
        period = cfg.rglru.attn_period
        full = cfg.num_layers // period
        rem = cfg.num_layers - full * period
        group = ("rec",) * (period - 1) + ("dense_local",)
        prog = [(full, group)]
        if rem:
            prog.append((1, ("rec",) * rem))
        return prog
    if cfg.family == "moe":
        kind = "mla_moe" if cfg.mla else "moe"
        first = cfg.moe.first_k_dense
        prog = []
        if first:
            prog.append((first, ("mla_dense" if cfg.mla else "dense",)))
        prog.append((cfg.num_layers - first, (kind,)))
        return prog
    # dense / vlm backbones
    return [(cfg.num_layers, ("dense",))]


def num_layer_groups(cfg: ArchConfig) -> int:
    """Number of layer groups — the index space of per-layer ``policies``."""
    return sum(n for n, _ in layer_program(cfg))


def _layer_policies(cfg: ArchConfig, stem_cfg, policies):
    """Per-group effective policy list (length ``num_layer_groups``).

    ``policies`` maps a global layer-group index to an override (any policy
    spelling); unlisted groups use ``stem_cfg``.  Entries are normalized to
    ``SparsityPolicy`` so equal policies — however spelled — coalesce into
    one scan run."""
    total = num_layer_groups(cfg)
    base = policy_lib.as_policy_opt(stem_cfg)
    if not policies:
        return [base] * total
    bad = sorted(i for i in policies if not (isinstance(i, int) and 0 <= i < total))
    if bad:
        raise ValueError(
            f"policies keys {bad} out of range for {total} layer groups")
    return [policy_lib.as_policy_opt(policies[i]) if i in policies else base
            for i in range(total)]


def _policy_runs(eff_seg):
    """Coalesce consecutive equal policies into (start, length, policy) runs
    — each run compiles as one scan over a static slice of the stacked
    segment parameters."""
    runs: list = []
    for i, p in enumerate(eff_seg):
        if runs and runs[-1][2] == p:
            runs[-1] = (runs[-1][0], runs[-1][1] + 1, p)
        else:
            runs.append((i, 1, p))
    return runs


# ---------------------------------------------------------------------------
# Single sub-layer: init / full / prefill / decode
# ---------------------------------------------------------------------------

def _init_sublayer(ini: common.Initializer, cfg: ArchConfig, kind: str) -> dict:
    p: dict[str, Any] = {"norm1": ini.zeros((cfg.d_model,), ("embed",))}
    if kind in ("dense", "dense_local", "moe"):
        p["attn"] = attention.init(ini, cfg)
    elif kind in ("mla_dense", "mla_moe"):
        p["attn"] = mla.init(ini, cfg)
    elif kind == "rec":
        p["mixer"] = rglru.init(ini, cfg)
    elif kind == "ssd":
        p["mixer"] = ssd.init(ini, cfg)
    else:
        raise ValueError(kind)
    if kind != "ssd":   # mamba blocks have no separate FFN
        p["norm2"] = ini.zeros((cfg.d_model,), ("embed",))
        if kind in ("moe", "mla_moe"):
            p["ffn"] = moe.init(ini, cfg.d_model, cfg.moe, cfg.activation)
        else:
            d_ff = cfg.d_ff
            if kind == "mla_dense" and cfg.moe and cfg.moe.first_dense_d_ff:
                d_ff = cfg.moe.first_dense_d_ff
            p["ffn"] = mlp.init(ini, cfg.d_model, d_ff, cfg.activation)
    return p


def _sublayer_full(params, x, cfg: ArchConfig, kind: str, *, positions,
                   stem_cfg, return_stats: bool = False):
    """Returns (x, aux_loss) — or (x, aux_loss, StemStats | None) when
    ``return_stats`` (stats exist only when the sparse attention path ran)."""
    h = common.rms_norm(x, params["norm1"])
    stats = None
    if kind in ("dense", "moe"):
        if return_stats:
            mix, stats = attention.apply_full(
                params["attn"], h, cfg, positions=positions,
                stem_cfg=stem_cfg, return_stats=True)
        else:
            mix = attention.apply_full(params["attn"], h, cfg,
                                       positions=positions, stem_cfg=stem_cfg)
    elif kind == "dense_local":
        mix = attention.apply_full(params["attn"], h, cfg, positions=positions,
                                   stem_cfg=None, window=cfg.rglru.window)
    elif kind in ("mla_dense", "mla_moe"):
        if return_stats:
            mix, stats = mla.apply_full(params["attn"], h, cfg,
                                        positions=positions, stem_cfg=stem_cfg,
                                        return_stats=True)
        else:
            mix = mla.apply_full(params["attn"], h, cfg, positions=positions,
                                 stem_cfg=stem_cfg)
    elif kind == "rec":
        mix = rglru.apply_full(params["mixer"], h, cfg)
    elif kind == "ssd":
        mix = ssd.apply_full(params["mixer"], h, cfg)
    x = constrain(x + mix, ("batch", None, None))
    aux = jnp.zeros((), jnp.float32)
    if kind == "ssd":
        return (x, aux, stats) if return_stats else (x, aux)
    h2 = common.rms_norm(x, params["norm2"])
    if kind in ("moe", "mla_moe"):
        y, aux = moe.apply(params["ffn"], h2, cfg.moe, cfg.activation)
    else:
        y = mlp.apply(params["ffn"], h2, cfg.activation)
    x = constrain(x + y, ("batch", None, None))
    return (x, aux, stats) if return_stats else (x, aux)


def _sublayer_cache(cfg: ArchConfig, kind: str, batch: int, max_len: int, dtype):
    if kind in ("dense", "moe"):
        return attention.init_cache(cfg, batch, max_len, dtype=dtype)
    if kind == "dense_local":
        return attention.init_cache(cfg, batch, max_len, window=cfg.rglru.window, dtype=dtype)
    if kind in ("mla_dense", "mla_moe"):
        return mla.init_cache(cfg, batch, max_len, dtype=dtype)
    if kind == "rec":
        return rglru.init_state(cfg, batch)
    if kind == "ssd":
        return ssd.init_state(cfg, batch, dtype)
    raise ValueError(kind)


def _sublayer_prefill(params, x, cfg: ArchConfig, kind: str, *, positions,
                      stem_cfg, max_len: int):
    """Returns (x, aux, cache)."""
    h = common.rms_norm(x, params["norm1"])
    if kind in ("dense", "moe"):
        mix, cache = attention.prefill_into_cache(
            params["attn"], h, cfg, positions=positions, max_len=max_len,
            stem_cfg=stem_cfg)
    elif kind == "dense_local":
        mix, cache = attention.prefill_into_cache(
            params["attn"], h, cfg, positions=positions, max_len=max_len,
            window=cfg.rglru.window)
    elif kind in ("mla_dense", "mla_moe"):
        mix, cache = mla.prefill_into_cache(
            params["attn"], h, cfg, positions=positions, max_len=max_len,
            stem_cfg=stem_cfg)
    elif kind == "rec":
        mix, cache = rglru.prefill_into_state(params["mixer"], h, cfg)
    elif kind == "ssd":
        mix, cache = ssd.prefill_into_state(params["mixer"], h, cfg)
    x = x + mix
    aux = jnp.zeros((), jnp.float32)
    if kind == "ssd":
        return x, aux, cache
    h2 = common.rms_norm(x, params["norm2"])
    if kind in ("moe", "mla_moe"):
        y, aux = moe.apply(params["ffn"], h2, cfg.moe, cfg.activation)
    else:
        y = mlp.apply(params["ffn"], h2, cfg.activation)
    return x + y, aux, cache


def _sublayer_decode(params, x, cfg: ArchConfig, kind: str, cache, *,
                     stem_cfg=None, budget_frac: float = 1.0):
    h = common.rms_norm(x, params["norm1"])
    if kind in ("dense", "moe"):
        mix, cache = attention.apply_decode(params["attn"], h, cfg, cache,
                                            stem_cfg=stem_cfg,
                                            budget_frac=budget_frac)
    elif kind == "dense_local":
        mix, cache = attention.apply_decode(params["attn"], h, cfg, cache,
                                            window=cfg.rglru.window)
    elif kind in ("mla_dense", "mla_moe"):
        mix, cache = mla.apply_decode(params["attn"], h, cfg, cache)
    elif kind == "rec":
        mix, cache = rglru.apply_decode(params["mixer"], h, cfg, cache)
    elif kind == "ssd":
        mix, cache = ssd.apply_decode(params["mixer"], h, cfg, cache)
    x = x + mix
    if kind == "ssd":
        return x, cache
    h2 = common.rms_norm(x, params["norm2"])
    if kind in ("moe", "mla_moe"):
        y, _ = moe.apply(params["ffn"], h2, cfg.moe, cfg.activation)
    else:
        y = mlp.apply(params["ffn"], h2, cfg.activation)
    return x + y, cache


# ---------------------------------------------------------------------------
# Group (scan body) = sequence of sub-layers
# ---------------------------------------------------------------------------

def _init_group(ini, cfg, kinds) -> dict:
    return {f"sub{i}": _init_sublayer(ini, cfg, k) for i, k in enumerate(kinds)}


def _group_full(params, x, cfg, kinds, *, positions, stem_cfg):
    aux = jnp.zeros((), jnp.float32)
    for i, k in enumerate(kinds):
        x, a = _sublayer_full(params[f"sub{i}"], x, cfg, k,
                              positions=positions, stem_cfg=stem_cfg)
        aux = aux + a
    return x, aux


def _stacked_group_init(ini: common.Initializer, cfg, kinds, n: int):
    """Stack n group-param trees along a leading 'layers' axis (for scan)."""
    def one(key):
        sub = common.Initializer(key, ini.dtype)
        values, _ = common.unzip(_init_group(sub, cfg, kinds))
        return values
    keys = jax.random.split(ini.next_key(), n)
    values = jax.vmap(one)(keys)
    _, axes = common.unzip(_init_group(
        common.Initializer(jax.random.PRNGKey(0), ini.dtype), cfg, kinds))
    axes = jax.tree.map(lambda a: ("layers",) + a, axes,
                        is_leaf=lambda t: isinstance(t, tuple))
    return common.zip_trees(values, axes)


# ---------------------------------------------------------------------------
# Whole-model init
# ---------------------------------------------------------------------------

def init_lm(key: jax.Array, cfg: ArchConfig) -> dict:
    ini = common.Initializer(key, cfg.jnp_dtype)
    p: dict[str, Any] = {
        "embed": common.embed_init(ini, cfg.padded_vocab, cfg.d_model),
        "final_norm": ini.zeros((cfg.d_model,), ("embed",)),
    }
    for si, (n, kinds) in enumerate(layer_program(cfg)):
        p[f"segment{si}"] = _stacked_group_init(ini, cfg, kinds, n)
    if not cfg.tie_embeddings:
        p["head"] = ini.normal((cfg.d_model, cfg.padded_vocab), ("embed", "vocab"),
                               scale=0.02, dtype=jnp.float32)
    if cfg.mtp:
        p["mtp_proj"] = ini.normal((2 * cfg.d_model, cfg.d_model), (None, "embed"))
        p["mtp_norm"] = ini.zeros((cfg.d_model,), ("embed",))
        p["mtp_layer"] = _stacked_group_init(ini, cfg, ("dense",), 1)
    return p


def init_params(key: jax.Array, cfg: ArchConfig):
    """Concrete parameter values (plain-array tree).  All apply functions
    consume this values-only tree."""
    return common.unzip(init_lm(key, cfg))[0]


def abstract_params(cfg: ArchConfig):
    """(ShapeDtypeStruct values tree, logical-axes tree) — no allocation.

    The axes tree is captured as a tracing side effect (axes are static
    Python tuples, not arrays, so they can't flow through eval_shape
    outputs)."""
    captured = {}

    def f(key):
        values, axes = common.unzip(init_lm(key, cfg))
        captured["axes"] = axes
        return values

    values = jax.eval_shape(f, jax.random.PRNGKey(0))
    return values, captured["axes"]


# ---------------------------------------------------------------------------
# Forward passes
# ---------------------------------------------------------------------------

def _embed_inputs(params, batch: dict, cfg: ArchConfig):
    """Token (+ optional stub modality prefix) embeddings -> (b, s, d)."""
    parts = []
    if cfg.vlm_stub and "patch_embeds" in batch:
        parts.append(batch["patch_embeds"].astype(cfg.jnp_dtype))
    emb = common.embed_lookup(params["embed"], batch["tokens"], cfg.jnp_dtype)
    parts.append(emb * (cfg.d_model ** 0.5) if cfg.embed_scale_flag else emb)
    return jnp.concatenate(parts, axis=1) if len(parts) > 1 else parts[0]


def _run_segments(params, x, cfg: ArchConfig, *, positions, stem_cfg,
                  remat: bool, policies=None):
    eff = _layer_policies(cfg, stem_cfg, policies)
    aux_total = jnp.zeros((), jnp.float32)
    off = 0
    for si, (n, kinds) in enumerate(layer_program(cfg)):
        seg = params[f"segment{si}"]
        for start, length, pol in _policy_runs(eff[off:off + n]):

            def body(carry, layer_params, kinds=kinds, pol=pol):
                x, aux = carry
                x, a = _group_full(layer_params, x, cfg, kinds,
                                   positions=positions, stem_cfg=pol)
                return (x, aux + a), None

            if remat:
                body = jax.checkpoint(body, prevent_cse=False)
            if length == 1:
                (x, aux_total), _ = body(
                    (x, aux_total), jax.tree.map(lambda t, s=start: t[s], seg))
            else:
                sub = seg if length == n else jax.tree.map(
                    lambda t, s=start, m=length: t[s:s + m], seg)
                (x, aux_total), _ = jax.lax.scan(body, (x, aux_total), sub)
        off += n
    return x, aux_total


def _logits(params, x, cfg: ArchConfig):
    x = common.rms_norm(x, params["final_norm"])
    if cfg.tie_embeddings:
        return common.lm_logits(x, params["embed"])
    return jnp.einsum("bsd,dv->bsv", x.astype(jnp.float32),
                      params["head"].astype(jnp.float32))


def loss_fn(params, batch: dict, cfg: ArchConfig, *,
            stem_cfg=None, remat: bool = True, policies=None):
    """Next-token CE (+ MoE aux, + MTP).  batch: tokens (b,s), labels (b,s).

    ``stem_cfg`` accepts any policy spelling; ``policies`` optionally
    overrides it per layer group ({index: policy})."""
    x = _embed_inputs(params, batch, cfg)
    positions = jnp.arange(x.shape[1])
    x, aux = _run_segments(params, x, cfg, positions=positions,
                           stem_cfg=stem_cfg, remat=remat, policies=policies)
    txt_len = batch["tokens"].shape[1]
    x_txt = x[:, -txt_len:]
    logits = _logits(params, x_txt, cfg)
    mask = batch.get("loss_mask")
    ce = common.cross_entropy(logits, batch["labels"], mask)
    metrics = {"ce": ce, "aux": aux}
    total = ce + aux
    if cfg.mtp:
        h = common.rms_norm(x_txt[:, :-1], params["mtp_norm"])
        nxt = common.embed_lookup(params["embed"], batch["labels"][:, :-1], cfg.jnp_dtype)
        hm = jnp.einsum("bse,ed->bsd", jnp.concatenate([h, nxt], -1), params["mtp_proj"])
        hm, _ = _group_full(jax.tree.map(lambda t: t[0], params["mtp_layer"]),
                            hm, cfg, ("dense",), positions=positions[:-1], stem_cfg=None)
        mtp_logits = _logits(params, hm, cfg)
        mtp_ce = common.cross_entropy(mtp_logits, batch["labels"][:, 1:])
        metrics["mtp_ce"] = mtp_ce
        total = total + cfg.mtp_weight * mtp_ce
    metrics["loss"] = total
    return total, metrics


def forward_hiddens(params, batch: dict, cfg: ArchConfig, *,
                    stem_cfg: Optional[StemConfig] = None):
    """Forward pass that also returns every layer's residual stream —
    used by the benchmark harness for the paper's per-layer sparse-vs-dense
    MSE measurements (Table 1 / Figure 3 quantities).

    Returns (logits (b, s, vocab) fp32, list of (n_layers_i, b, s, d)).
    """
    x = _embed_inputs(params, batch, cfg)
    positions = jnp.arange(x.shape[1])
    hiddens = []
    for si, (n, kinds) in enumerate(layer_program(cfg)):
        seg = params[f"segment{si}"]

        def body(carry, layer_params, kinds=kinds):
            x, aux = carry
            x, a = _group_full(layer_params, x, cfg, kinds,
                               positions=positions, stem_cfg=stem_cfg)
            return (x, aux + a), x

        if n == 1:
            (x, _), y = body((x, 0.0), jax.tree.map(lambda t: t[0], seg))
            y = y[None]
        else:
            (x, _), y = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)), seg)
        hiddens.append(y)
    logits = _logits(params, x, cfg)
    return logits, hiddens


def forward_with_stats(params, batch: dict, cfg: ArchConfig, *,
                       stem_cfg=None, policies=None):
    """Diagnostic forward pass with per-sub-layer sparse-attention stats.

    Runs the layer program unrolled (no scan / remat — small models only)
    so every attention sub-layer can report the realized ``StemStats`` of
    its *own* effective policy; this is how per-layer policy overrides are
    observed (realized density per layer).

    Returns (logits (b, s, vocab), records) where each record is a dict
    ``{"layer": global group index, "kind": sub-layer kind, "policy":
    policy name or None, "stats": StemStats | None}`` (stats is None for
    sub-layers where the sparse path did not run).
    """
    x = _embed_inputs(params, batch, cfg)
    positions = jnp.arange(x.shape[1])
    eff = _layer_policies(cfg, stem_cfg, policies)
    records = []
    li = 0
    for si, (n, kinds) in enumerate(layer_program(cfg)):
        seg = params[f"segment{si}"]
        for j in range(n):
            layer_params = jax.tree.map(lambda t, j=j: t[j], seg)
            pol = eff[li]
            for i, kind in enumerate(kinds):
                x, _, st = _sublayer_full(
                    layer_params[f"sub{i}"], x, cfg, kind, positions=positions,
                    stem_cfg=pol, return_stats=True)
                records.append({
                    "layer": li, "kind": kind,
                    "policy": (pol.name or None) if pol is not None else None,
                    "stats": st,
                })
            li += 1
    logits = _logits(params, x, cfg)
    return logits, records


# ---------------------------------------------------------------------------
# Serving: prefill + decode over stacked caches
# ---------------------------------------------------------------------------

def init_caches(cfg: ArchConfig, batch: int, max_len: int):
    caches = []
    for n, kinds in layer_program(cfg):
        group = {f"sub{i}": _sublayer_cache(cfg, k, batch, max_len, cfg.jnp_dtype)
                 for i, k in enumerate(kinds)}
        stacked = jax.tree.map(lambda t: jnp.broadcast_to(t, (n,) + t.shape), group)
        caches.append(stacked)
    return caches


def prefill(params, batch: dict, cfg: ArchConfig, *, max_len: int,
            stem_cfg=None, last_pos: Optional[jnp.ndarray] = None,
            policies=None):
    """Process the full prompt.  Returns (last-position logits, caches).

    The sparsity policy (the paper's contribution) runs here — this is the
    pre-filling phase whose latency the paper optimizes.  ``stem_cfg``
    accepts any policy spelling; ``policies`` optionally overrides it per
    layer group ({index: policy}).

    ``last_pos`` (scalar or (b,) int32) selects which position's logits to
    return per row — required for right-padded ragged prompts where row i's
    real last token sits at ``len_i - 1``, not at ``seq - 1``.  Default:
    the final position (uniform batch).
    """
    x = _embed_inputs(params, batch, cfg)
    positions = jnp.arange(x.shape[1])
    eff = _layer_policies(cfg, stem_cfg, policies)
    caches = []
    off = 0
    for si, (n, kinds) in enumerate(layer_program(cfg)):
        seg = params[f"segment{si}"]
        run_caches = []
        for start, length, pol in _policy_runs(eff[off:off + n]):

            def body(x, layer_params, kinds=kinds, pol=pol):
                cache = {}
                for i, k in enumerate(kinds):
                    x, _, c = _sublayer_prefill(
                        layer_params[f"sub{i}"], x, cfg, k, positions=positions,
                        stem_cfg=pol, max_len=max_len)
                    cache[f"sub{i}"] = c
                return x, cache

            if length == 1:
                x, cache = body(x, jax.tree.map(lambda t, s=start: t[s], seg))
                cache = jax.tree.map(lambda t: t[None], cache)
            else:
                sub = seg if length == n else jax.tree.map(
                    lambda t, s=start, m=length: t[s:s + m], seg)
                x, cache = jax.lax.scan(body, x, sub)
            run_caches.append(cache)
        off += n
        cache = run_caches[0] if len(run_caches) == 1 else jax.tree.map(
            lambda *ts: jnp.concatenate(ts, axis=0), *run_caches)
        caches.append(cache)
    if last_pos is None:
        x_last = x[:, -1:]
    else:
        lp = jnp.broadcast_to(jnp.asarray(last_pos, jnp.int32), (x.shape[0],))
        x_last = jnp.take_along_axis(x, lp[:, None, None], axis=1)
    logits = _logits(params, x_last, cfg)[:, 0]
    return logits, caches


# ---------------------------------------------------------------------------
# Paged serving: page pools + batched ragged decode (runtime/engine.py)
# ---------------------------------------------------------------------------

PAGED_KINDS = ("dense", "moe")   # attention sub-layers the paged engine serves


def assert_paged_servable(cfg: ArchConfig) -> None:
    """The paged engine needs every mixer to be causal global attention —
    ring/windowed, MLA-latent, and recurrent states have no page layout."""
    for _, kinds in layer_program(cfg):
        for k in kinds:
            if k not in PAGED_KINDS:
                raise NotImplementedError(
                    f"paged serving supports {PAGED_KINDS} sub-layers, got {k!r} "
                    f"(arch {cfg.name})")


def init_page_pools(cfg: ArchConfig, num_pages: int, stem_cfg, smesh=None):
    """Per-layer page pools, stacked along the scan axis like init_caches.
    Every attention layer gets its own (hk, P, page, d) pool; the page
    table (slot -> pages) is shared across layers and lives in the engine.
    ``stem_cfg`` accepts any policy spelling (page = policy block).

    With ``smesh`` (a ``sharding.serving.ServingMesh``) every leaf gains a
    leading slot-group axis and is placed sharded — ``(dp, n, hk, P, ...)``
    with dp over slot groups and the KV-head axis split over tp."""
    from repro.runtime import paged as paged_lib

    stem_cfg = policy_lib.as_policy(stem_cfg)
    assert_paged_servable(cfg)
    pools = []
    for n, kinds in layer_program(cfg):
        one = {f"sub{i}": paged_lib.init_pool(
                   num_pages, cfg.num_kv_heads, stem_cfg.block_size,
                   cfg.head_dim, stem_cfg.stride, cfg.jnp_dtype)
               for i, _ in enumerate(kinds)}
        pools.append(jax.tree.map(
            lambda t: jnp.broadcast_to(t, (n,) + t.shape), one))
    if smesh is not None:
        from repro.sharding import serving as serving_lib
        pools = serving_lib.shard_pools(pools, smesh)
    return pools


def prefill_kv_pages(params, tokens: jnp.ndarray, true_len: jnp.ndarray,
                     pools, page_row: jnp.ndarray, cfg: ArchConfig,
                     stem_cfg):
    """Prefill ONE request and write its pages + summaries into the pools.

    tokens: (1, Lp) right-padded to a page multiple; true_len: scalar int32;
    page_row: (max_pages_per_slot,) — *every* page reserved for the request
    (prompt pages first, then decode-spill pages), padded with the trash
    page.  All of them are reset to pristine before the prompt's
    (Lp / page_size) leading pages are written: the allocator recycles pages
    without clearing them, and the decode-time summary increments assume
    fresh pages.  Returns (next-token logits (vocab,), new pools).
    jit-able: one trace per padded length bucket.
    """
    from repro.runtime import paged as paged_lib

    stem_cfg = policy_lib.as_policy(stem_cfg)
    logits, caches = prefill(params, {"tokens": tokens}, cfg,
                             max_len=tokens.shape[1], stem_cfg=stem_cfg,
                             last_pos=true_len - 1)
    prompt_pages = page_row[:tokens.shape[1] // stem_cfg.block_size]
    new_pools = []
    for si, (n, kinds) in enumerate(layer_program(cfg)):
        seg = {}
        for i, _ in enumerate(kinds):
            cache = caches[si][f"sub{i}"]          # KVCache k: (n, 1, hk, Lp, d)
            pool = pools[si][f"sub{i}"]            # PagePool k: (n, hk, P, pg, d)
            seg[f"sub{i}"] = jax.vmap(
                lambda p, k, v: paged_lib.write_prefill_pages(
                    paged_lib.reset_pages(p, page_row), prompt_pages,
                    k[0], v[0], true_len, stem_cfg)
            )(pool, cache.k, cache.v)
        new_pools.append(seg)
    return logits[0], new_pools


def prefill_kv_pages_suffix(params, tokens: jnp.ndarray,
                            true_len: jnp.ndarray, start: int, pools,
                            page_row: jnp.ndarray, cfg: ArchConfig,
                            stem_cfg, budget_frac: float = 1.0,
                            executor=None):
    """Prefill ONE request's unmatched suffix against already-written
    prefix pages — the prefix-caching admission entry.

    Positions ``[start, Lp)`` run as a single chunk lane of
    ``paged_mixed_step``: the chunk's queries attend causally over the
    whole prompt *through the page table*, so the leading ``start /
    page_size`` pages of ``page_row`` may be prefix pages SHARED with other
    slots — they are read, never written (chunk writes cover only the
    chunk's own pages).  The caller must reset the private (suffix + spill)
    pages beforehand and must NOT reset the shared prefix pages.

    tokens: (1, Lp) right-padded to a page multiple; true_len: scalar int32
    (> start); start: static block-aligned matched-prefix offset; page_row:
    (max_pages_per_slot,) trash-padded.  Returns (next-token logits
    (vocab,), new pools).  jit-able: one trace per (Lp, start) bucket.
    """
    from repro.core import chunked as chunked_lib

    stem_cfg = policy_lib.as_policy(stem_cfg)
    bs = stem_cfg.block_size
    lp = tokens.shape[1]
    if start % bs != 0 or not 0 <= start < lp:
        raise ValueError(f"matched-prefix offset {start} must be a block "
                         f"multiple inside the padded prompt (Lp={lp})")
    nc = (lp - start) // bs
    budgets = chunked_lib.chunk_budget_rows(stem_cfg, lp, start, nc)
    tl = jnp.asarray(true_len, jnp.int32)
    chunk = {
        "tokens": tokens[:, start:],
        "page_table": page_row[None],
        "start": jnp.full((1,), start, jnp.int32),
        "true_len": tl[None],
        "budgets": jnp.asarray(budgets, jnp.int32)[None],
        "last": (tl - 1 - start)[None],
    }
    # Idle decode lane: zero page table -> its masked write lands in the
    # trash page, exactly like an inactive engine slot.
    _, chunk_logits, new_pools = paged_mixed_step(
        params, jnp.zeros((1, 1), jnp.int32), pools,
        jnp.zeros((1, page_row.shape[0]), jnp.int32),
        jnp.zeros((1,), jnp.int32), cfg, stem_cfg=stem_cfg,
        budget_frac=budget_frac, chunk=chunk, executor=executor)
    return chunk_logits[0], new_pools


def paged_mixed_step(params, tokens: jnp.ndarray, pools,
                     page_table: jnp.ndarray, cache_lens: jnp.ndarray,
                     cfg: ArchConfig, *, stem_cfg,
                     budget_frac: float = 1.0, chunk=None,
                     chunk_k_max: int = 0, executor=None):
    """One mixed batch of decode tokens + prefill chunks over the page pool.

    The unified serving step: every layer processes a decode lane
    (one token per slot, ``apply_decode_paged``) and — when ``chunk`` is
    given — a chunked-prefill lane (``apply_chunk_paged``) against the
    *same* per-layer pools, in one trace.  The chunk lane is *narrow*:
    ``L`` lanes (typically 1, sized by the engine's token budget), each
    carrying one slot's next chunk and that slot's page-table row — a slot
    is active in at most one lane per step, and both lanes are
    row-parallel, so batch-invariance holds across arbitrary decode/prefill
    mixes.

    tokens: (slots, 1).  ``chunk`` is None (decode-only; this degenerates to
    the legacy paged decode step) or a dict with, for L chunk lanes:
      tokens     (L, C) int32, C a multiple of the policy block;
      page_table (L, max_pages) — a zero row for an idle lane;
      start      (L,) absolute chunk start (block-aligned);
      true_len   (L,) true prompt length (K/V zeroed at/after it);
      budgets    (L, C // block) int32 absolute-row block budgets;
      last       (L,) in-chunk index whose logits to return (the
                 prompt's final token, for chunks that finish a prefill).

    Returns (decode logits (slots, vocab),
             chunk logits (L, vocab) | None, new pools).

    Device phases carry ``jax.named_scope`` names, so each op of the
    compiled step is owned by one phase in a profiler trace: the lanes
    ``stem.decode_lane`` / ``stem.chunk_lane`` enclose ``stem.qkv``,
    ``stem.kv_write``, ``stem.score``, ``stem.select``, ``stem.attend``,
    ``stem.o_proj`` and ``stem.mlp``; ``stem.embed`` and ``stem.head`` sit
    outside the layers.

    The layer scan carries each segment's stacked pools ``(n, hk, P, ...)``
    with the hidden states and hands every layer its index: the lanes write
    into and gather from the stack at ``[layer, head, page, ...]`` in
    place, so no layer's pool is sliced out, relaid out or restacked, and
    a donated stack is updated where it lies.
    """
    with jax.named_scope("stem.embed"):
        x = common.embed_lookup(params["embed"], tokens, cfg.jnp_dtype)
        xc = None
        if chunk is not None:
            xc = common.embed_lookup(params["embed"], chunk["tokens"],
                                     cfg.jnp_dtype)
        if cfg.embed_scale_flag:
            x = x * (cfg.d_model ** 0.5)
            xc = None if xc is None else xc * (cfg.d_model ** 0.5)
    new_pools = []
    for si, (n, kinds) in enumerate(layer_program(cfg)):
        seg = params[f"segment{si}"]

        def body(carry, scanned, kinds=kinds):
            x, xc, pool = carry
            layer_params, layer = scanned
            pool = dict(pool)
            for i, k in enumerate(kinds):
                p = layer_params[f"sub{i}"]
                pl = pool[f"sub{i}"]

                def ffn(h, k=k, p=p):
                    with jax.named_scope("stem.mlp"):
                        h2 = common.rms_norm(h, p["norm2"])
                        if k == "moe":
                            y, _ = moe.apply(p["ffn"], h2, cfg.moe,
                                             cfg.activation)
                            return h + y
                        return h + mlp.apply(p["ffn"], h2, cfg.activation)

                if chunk is not None:
                    with jax.named_scope("stem.chunk_lane"):
                        with jax.named_scope("stem.qkv"):
                            hc = common.rms_norm(xc, p["norm1"])
                        mix_c, pl = attention.apply_chunk_paged(
                            p["attn"], hc, cfg, pl, chunk["page_table"],
                            chunk["start"], chunk["true_len"],
                            chunk["budgets"], stem_cfg, layer=layer,
                            k_max=chunk_k_max, executor=executor)
                        with jax.named_scope("stem.o_proj"):
                            xc = xc + mix_c
                with jax.named_scope("stem.decode_lane"):
                    with jax.named_scope("stem.qkv"):
                        h = common.rms_norm(x, p["norm1"])
                    mix, pl = attention.apply_decode_paged(
                        p["attn"], h, cfg, pl, page_table,
                        cache_lens, stem_cfg, layer=layer,
                        budget_frac=budget_frac, executor=executor)
                    with jax.named_scope("stem.o_proj"):
                        x = x + mix
                    x = ffn(x)
                pool[f"sub{i}"] = pl
                if chunk is not None:
                    with jax.named_scope("stem.chunk_lane"):
                        xc = ffn(xc)
            return (x, xc, pool), None

        carry = (x, xc, pools[si])
        if n == 1:
            carry, _ = body(carry, (jax.tree.map(lambda t: t[0], seg), 0))
        else:
            carry, _ = jax.lax.scan(
                body, carry, (seg, jnp.arange(n, dtype=jnp.int32)))
        x, xc, npool = carry
        new_pools.append(npool)
    with jax.named_scope("stem.head"):
        dec_logits = _logits(params, x, cfg)[:, 0]
        chunk_logits = None
        if chunk is not None:
            xl = jnp.take_along_axis(xc, chunk["last"][:, None, None], axis=1)
            chunk_logits = _logits(params, xl, cfg)[:, 0]
    return dec_logits, chunk_logits, new_pools


def paged_sampled_step(params, token_buf: jnp.ndarray, pools,
                       page_table: jnp.ndarray, cache_lens: jnp.ndarray,
                       dec_mask: jnp.ndarray, cfg: ArchConfig, *, stem_cfg,
                       sampler, budget_frac: float = 1.0, chunk=None,
                       chunk_k_max: int = 0, executor=None):
    """``paged_mixed_step`` with sampling fused into the trace — the async
    engine's step.  Decode inputs come from ``token_buf`` (slots,), the
    device-resident fed-back token buffer, instead of a host-built tokens
    array; logits never leave the device — the sampler reduces them to
    int32 ids in the same trace, and the buffer is advanced in place:

      * decode lanes granted this step (``dec_mask`` (slots,) bool) write
        their sampled id back into the buffer (the next step's input);
        ungranted lanes keep their pending token;
      * a chunk lane that completes a prefill (``chunk["emit"]`` (L,)
        bool) scatters its sampled first token into ``chunk["slot"]``'s
        buffer entry — the request's decode stream starts on-device too.

    The only thing a host ever needs to fetch is the tiny id arrays
    (``dec_ids`` (slots,), ``chunk_ids`` (L,)) — one int32 per lane
    instead of a vocab-sized logits row.

    Returns (dec_ids (slots,) int32, chunk_ids (L,) int32 | None,
             new token_buf (slots,), new pools).
    """
    dec_logits, chunk_logits, new_pools = paged_mixed_step(
        params, token_buf[:, None], pools, page_table, cache_lens, cfg,
        stem_cfg=stem_cfg, budget_frac=budget_frac, chunk=chunk,
        chunk_k_max=chunk_k_max, executor=executor)
    with jax.named_scope("stem.sample"):
        dec_ids = sampler(dec_logits)
        new_buf = jnp.where(dec_mask, dec_ids, token_buf)
        chunk_ids = None
        if chunk is not None:
            chunk_ids = sampler(chunk_logits)
            # Completed-prefill lanes feed their first token into the
            # buffer; idle / mid-prompt lanes scatter out of bounds and are
            # dropped.
            slots = token_buf.shape[0]
            target = jnp.where(chunk["emit"], chunk["slot"], slots)
            new_buf = new_buf.at[target].set(chunk_ids, mode="drop")
    return dec_ids, chunk_ids, new_buf, new_pools


def paged_decode_step(params, tokens: jnp.ndarray, pools,
                      page_table: jnp.ndarray, cache_lens: jnp.ndarray,
                      cfg: ArchConfig, *, stem_cfg,
                      budget_frac: float = 1.0, executor=None):
    """One token for every engine slot against the paged Stem KV cache —
    the decode-only view of ``paged_mixed_step`` (kept for direct callers).
    Returns (logits (slots, vocab), new pools)."""
    logits, _, new_pools = paged_mixed_step(
        params, tokens, pools, page_table, cache_lens, cfg,
        stem_cfg=stem_cfg, budget_frac=budget_frac, chunk=None,
        executor=executor)
    return logits, new_pools


def decode_step(params, tokens: jnp.ndarray, caches, cfg: ArchConfig, *,
                stem_cfg=None, budget_frac: float = 1.0):
    """One token for every sequence in the batch.  tokens: (b, 1).

    With ``stem_cfg`` the attention sub-layers decode POLICY-SPARSE over
    the contiguous cache (summarize + select per step) — the fixed-batch
    reference for the paged engine's sparse decode.  Only global-attention
    architectures support it (same constraint as paged serving)."""
    if stem_cfg is not None:
        assert_paged_servable(cfg)
    x = common.embed_lookup(params["embed"], tokens, cfg.jnp_dtype)
    if cfg.embed_scale_flag:
        x = x * (cfg.d_model ** 0.5)
    new_caches = []
    for si, (n, kinds) in enumerate(layer_program(cfg)):
        seg = params[f"segment{si}"]
        cache = caches[si]

        def body(x, scanned, kinds=kinds):
            layer_params, cache = scanned
            new_cache = {}
            for i, k in enumerate(kinds):
                x, c = _sublayer_decode(layer_params[f"sub{i}"], x, cfg, k,
                                        cache[f"sub{i}"], stem_cfg=stem_cfg,
                                        budget_frac=budget_frac)
                new_cache[f"sub{i}"] = c
            return x, new_cache

        if n == 1:
            x, nc = body(x, (jax.tree.map(lambda t: t[0], seg),
                             jax.tree.map(lambda t: t[0], cache)))
            nc = jax.tree.map(lambda t: t[None], nc)
        else:
            x, nc = jax.lax.scan(body, x, (seg, cache))
        new_caches.append(nc)
    logits = _logits(params, x, cfg)[:, 0]
    return logits, new_caches
