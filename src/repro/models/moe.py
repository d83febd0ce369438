"""Mixture-of-Experts FFN: expert-parallel shard_map dispatch.

Why shard_map (DESIGN.md §4): the dispatch is a data-dependent scatter,
which GSPMD either replicates (hundreds of GB of dispatch buffers) or wraps
in enormous masked all-reduces.  Writing the communication pattern
explicitly gives the textbook expert-parallel layer:

  * routing (softmax -> top-k -> per-row cumsum positions) is elementwise /
    local — computed under normal GSPMD, batch-sharded on `data`;
  * inside ``shard_map``: each `model` shard owns E/|model| experts, scatters
    *its own* tokens into a local (b, E_local, C, d) buffer (tokens routed
    to remote experts contribute zero), runs the expert FFN on local
    weights, gathers back, and the partial outputs are combined with ONE
    ``psum`` over `model` per layer (Megatron-MLP pattern);
  * FSDP archs all-gather the expert weights over `data` on entry —
    backward automatically reduce-scatters the weight grads (ZeRO-3).

Capacity is per batch row (GShard group = sequence): position-in-expert is
a cumsum along the row's own (s x K) slots, so there are no cross-shard
prefix sums and every shape is static; overflow drops (Switch-style).

Covers: plain top-k routed experts, deepseek (+1 shared expert, first-k
dense in the assembly), arctic (+parallel dense residual FFN),
Switch load-balance aux loss.

Serving (``apply_dropless``, the paged step's FFN) is different: it drops
nothing.  The (token, expert) pairs are sorted by expert and multiplied
group by group, so every pair the router picks is computed at any load.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import MoEConfig
from repro.models import common, mlp
from repro.sharding import context as shctx
from repro.sharding import rules as rules_lib


def init(ini: common.Initializer, d_model: int, moe: MoEConfig, activation: str) -> dict:
    e, f = moe.num_experts, moe.expert_d_ff
    p = {
        "router": ini.normal((d_model, e), ("embed", "experts"), dtype=jnp.float32),
        "w_gate": ini.normal((e, d_model, f), ("experts", "embed", "expert_mlp")),
        "w_up": ini.normal((e, d_model, f), ("experts", "embed", "expert_mlp")),
        "w_down": ini.normal((e, f, d_model), ("experts", "expert_mlp", "embed")),
    }
    if moe.shared_experts:
        p["shared"] = mlp.init(ini, d_model, moe.shared_d_ff * moe.shared_experts, activation)
    if moe.residual_dense:
        p["residual"] = mlp.init(ini, d_model, moe.residual_d_ff, activation)
    return p


def _gate(params, x, moe: MoEConfig):
    """Softmax router and greedy top-k: (gates (..., E), weights (..., K),
    experts (..., K)).  The top-k weights are renormalised to sum to 1 only
    with ``norm_topk_prob``; otherwise they are scaled by
    ``routed_scaling_factor`` (DeepSeek-V2's ``MoEGate``)."""
    router_logits = jnp.einsum("...d,de->...e", x.astype(jnp.float32),
                               params["router"])
    gates = jax.nn.softmax(router_logits, axis=-1)
    weights, experts = jax.lax.top_k(gates, moe.top_k)
    if moe.norm_topk_prob:
        weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)
    else:
        weights = weights * moe.routed_scaling_factor
    return gates, weights, experts


def _route(params, x, moe: MoEConfig):
    """Top-k routing + per-row positions.  All local/elementwise.

    Position-in-expert uses a **sort-based ranking** instead of the classic
    cumsum over a (T*K, E) one-hot: that one-hot costs O(s*K*E) int32 per
    layer (67 GB/device/layer at deepseek scale) while the stable argsort
    costs O(s*K log) on int32 vectors (§Perf deepseek iteration 1)."""
    b, s, d = x.shape
    E, K = moe.num_experts, moe.top_k
    capacity = max(1, int(moe.capacity_factor * s * K / E))
    gates, weights, experts = _gate(params, x, moe)             # (b, s, K)

    flat_e = experts.reshape(b, s * K)                          # slot-major
    order = jnp.argsort(flat_e, axis=1, stable=True)
    sorted_e = jnp.take_along_axis(flat_e, order, axis=1)
    idx = jnp.arange(s * K, dtype=jnp.int32)[None]
    is_start = jnp.concatenate(
        [jnp.ones((b, 1), bool), sorted_e[:, 1:] != sorted_e[:, :-1]], axis=1)
    start_idx = jax.lax.cummax(jnp.where(is_start, idx, 0), axis=1)
    pos_sorted = idx - start_idx                                # rank in group
    inv = jnp.argsort(order, axis=1)
    pos = jnp.take_along_axis(pos_sorted, inv, axis=1).reshape(b, s, K)
    keep = pos < capacity
    return gates, weights, experts, pos.astype(jnp.int32), keep, capacity


def _expert_ffn_local(x, experts, pos, keep, weights, wg, wu, wd,
                      *, e_offset, e_local, capacity, activation):
    """Dispatch + expert FFN + combine for the experts [e_offset,
    e_offset + e_local) on this shard.  Everything local; the caller psums.
    """
    b, s, d = x.shape
    K = experts.shape[-1]
    act = jax.nn.silu if activation == "silu" else jax.nn.gelu
    e_rel = experts - e_offset
    own = (e_rel >= 0) & (e_rel < e_local) & keep
    e_rel = jnp.clip(e_rel, 0, e_local - 1)
    rows = jnp.arange(b)[:, None]

    buf = jnp.zeros((b, e_local, capacity, d), x.dtype)
    for k in range(K):
        p_k = jnp.where(own[..., k], pos[..., k], capacity - 1)
        contrib = jnp.where(own[..., k, None], x, 0)
        buf = buf.at[rows, e_rel[..., k], p_k].add(contrib, mode="drop")

    g = act(jnp.einsum("becd,edf->becf", buf, wg))
    u = jnp.einsum("becd,edf->becf", buf, wu)
    out_buf = jnp.einsum("becf,efd->becd", g * u, wd)

    y = jnp.zeros((b, s, d), x.dtype)
    for k in range(K):
        p_k = jnp.where(own[..., k], pos[..., k], capacity - 1)
        got = out_buf[rows, e_rel[..., k], p_k]
        w_k = (weights[..., k] * own[..., k]).astype(x.dtype)
        y = y + got * w_k[..., None]
    return y


def apply(
    params: dict,
    x: jnp.ndarray,                  # (b, s, d)
    moe: MoEConfig,
    activation: str,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (output (b, s, d), aux_loss scalar)."""
    E, K = moe.num_experts, moe.top_k
    gates, weights, experts, pos, keep, capacity = _route(params, x, moe)

    ctx = shctx.current()
    if ctx is not None and "model" in ctx[1].axis_names \
            and ctx[1].shape["model"] > 1 and E % ctx[1].shape["model"] == 0:
        rules, mesh = ctx
        n_model = mesh.shape["model"]
        e_local = E // n_model
        batch_axes = rules["batch"]
        bspec = P(batch_axes if len(batch_axes) > 1 else batch_axes[0])
        fsdp = rules.get("expert_mlp", ()) == ("data",)
        wspec = P("model", None, None)

        def local_fn(x, experts, pos, keep, weights, wg, wu, wd):
            shard = jax.lax.axis_index("model")
            if fsdp:
                # ZeRO-3: weights additionally sharded on data over d_model /
                # d_ff; gather on use, reduce-scatter grads on the way back.
                wg = jax.lax.all_gather(wg, "data", axis=1, tiled=True)
                wu = jax.lax.all_gather(wu, "data", axis=1, tiled=True)
                wd = jax.lax.all_gather(wd, "data", axis=2, tiled=True)
            y = _expert_ffn_local(
                x, experts, pos, keep, weights, wg, wu, wd,
                e_offset=shard * e_local, e_local=e_local,
                capacity=capacity, activation=activation)
            return jax.lax.psum(y, "model")

        if fsdp:
            wspec_g = P("model", "data", None)
            wspec_d = P("model", None, "data")
        else:
            wspec_g = wspec_d = wspec
        tok_spec = P(*bspec, None, None)
        small = P(*bspec, None, None)
        y = jax.shard_map(
            local_fn, mesh=mesh,
            in_specs=(tok_spec, small, small, small, small,
                      wspec_g, wspec_g, wspec_d),
            out_specs=tok_spec,
            check_vma=False,
        )(x, experts, pos, keep, weights,
          params["w_gate"], params["w_up"], params["w_down"])
    else:
        y = _expert_ffn_local(
            x, experts, pos, keep, weights,
            params["w_gate"], params["w_up"], params["w_down"],
            e_offset=0, e_local=E, capacity=capacity, activation=activation)

    if moe.shared_experts:
        y = y + mlp.apply(params["shared"], x, activation)
    if moe.residual_dense:
        y = y + mlp.apply(params["residual"], x, activation)

    # Switch-style load-balance aux loss: E * sum_e f_e * p_e.
    # (bincount scatter, not a (b,s,K,E) one-hot.)
    me = gates.mean(axis=(0, 1))                              # (E,)
    b_, s_ = x.shape[0], x.shape[1]
    ce = jnp.zeros((E,), jnp.float32).at[experts.reshape(-1)].add(1.0) / (b_ * s_)
    aux = (me * ce).sum() * E * moe.router_aux_weight
    return y, aux


def apply_dropless(params: dict, x: jnp.ndarray, moe: MoEConfig,
                   activation: str, real: Optional[jnp.ndarray] = None):
    """The serving FFN: every (token, expert) pair the router picks is
    computed, at any load.  The pairs are sorted by expert and each expert
    multiplies its own contiguous rows (``jax.lax.ragged_dot``), so nothing
    is dropped and no capacity is set.  The shared experts (and arctic's
    dense residual) run on every token.

    x: (b, s, d); ``real`` (b, s) marks the tokens that are not padding
    (all by default).  Returns (y (b, s, d), load (E,) int32: the pairs of
    real tokens routed to each expert)."""
    with jax.named_scope("stem.moe"):
        b, s, d = x.shape
        E, K = moe.num_experts, moe.top_k
        act = jax.nn.silu if activation == "silu" else jax.nn.gelu
        xf = x.reshape(b * s, d)
        _, weights, experts = _gate(params, xf, moe)          # (T, K)
        flat = experts.reshape(-1)
        order = jnp.argsort(flat, stable=True)
        sizes = jnp.bincount(flat, length=E).astype(jnp.int32)
        xs = jnp.take(xf, order // K, axis=0)                  # (T*K, d)
        h = (act(jax.lax.ragged_dot(xs, params["w_gate"], sizes))
             * jax.lax.ragged_dot(xs, params["w_up"], sizes))
        ys = jax.lax.ragged_dot(h, params["w_down"], sizes)
        ys = jnp.take(ys, jnp.argsort(order), axis=0)          # (token, k) order
        y = jnp.einsum("tkd,tk->td", ys.reshape(b * s, K, d).astype(jnp.float32),
                       weights).astype(x.dtype).reshape(b, s, d)
        if moe.shared_experts:
            y = y + mlp.apply(params["shared"], x, activation)
        if moe.residual_dense:
            y = y + mlp.apply(params["residual"], x, activation)
        mask = (jnp.ones((b * s,), jnp.int32) if real is None
                else real.reshape(-1).astype(jnp.int32))
        load = jnp.zeros((E,), jnp.int32).at[flat].add(jnp.repeat(mask, K))
    return y, load


def expert_flops_per_token(d_model: int, moe: MoEConfig) -> float:
    """Active FLOPs per token for MODEL_FLOPS accounting."""
    per_expert = 3 * 2 * d_model * moe.expert_d_ff
    total = moe.top_k * per_expert
    if moe.shared_experts:
        total += 3 * 2 * d_model * moe.shared_d_ff * moe.shared_experts
    if moe.residual_dense:
        total += 3 * 2 * d_model * moe.residual_d_ff
    return total
