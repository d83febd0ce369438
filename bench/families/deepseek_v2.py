"""The DeepSeek-V2 family (Hugging Face ``DeepseekV2ForCausalLM``, e.g.
DeepSeek-V2-Lite): multi-head latent attention with queries straight from
``q_proj`` (``q_lora_rank`` null), a compressed KV latent plus one shared
rotary key, YaRN rope; the first ``first_k_dense_replace`` layers a dense
SwiGLU, then a softmax router picking ``num_experts_per_tok`` of
``n_routed_experts`` (greedy) plus ``n_shared_experts`` always-on experts;
an untied LM head.  The interface is ``qwen_dense.py``'s (see its
docstring).

Weights.  ``canonical`` draws every published weight in the published
dtype, ``program_params`` lays the same values out as the program holds
them, one jitted call per program leaf so that no more than one weight's
temporaries sit beside the parameters on the device:

* ``q_proj`` (d, h * 192) becomes ``wq`` (d, h, 192), nope then rope dims;
* ``kv_a_proj_with_mqa`` (d, 512 + 64) splits into ``w_dkv`` and ``w_kr``;
  ``kv_b_proj`` (512, h * (128 + 128)) into ``w_uk`` and ``w_uv`` per head;
* ``o_proj`` (h * 128, d) becomes ``wo`` (h, 128, d);
* the rope dims keep their order: the published rotary de-interleaves
  them and rotates halves, which turns the same adjacent pairs as the
  program's rotation, so queries and keys differ by one shared
  permutation and every ``q . k`` agrees (``configs/deepseek_v2_lite.py``);
* RMSNorm weights are ``1 + delta`` and the program is handed ``delta``;
  the two shared experts are one SwiGLU of width ``2 * 1408``, as
  published.

Reference.  ``jax.numpy`` in float32 at ``Precision.HIGHEST``, written from
the published ``modeling_deepseek.py``, importing nothing of the program.
Departures, each for size or for Stem:

* attention expands the latent into per-head keys (``k_nope`` from
  ``kv_b_proj``, the shared ``k_pe``) and values, as published, and runs
  Stem's rule (``stem_rule.py``) over them with one page set per query
  block (prefill) or per token (decode) for all heads: each head's page
  score is Stem's, computed from that head's pooled expanded keys, and the
  scores are averaged over the heads (``HEAD_SHARE``, the program's
  ``core.policy.latent_group_reduce``) before the budgeted top-k.  The
  value term is ``beta * max(0, max log ||c_kv||)`` over the page, the
  latent proxy the program stores;
* queries are scaled by YaRN's ``mscale(40, 0.707)^2``, so that the shared
  ``1 / sqrt(192)`` makes the published ``softmax_scale``;
* keys past a prompt's length are zero (the page pool's padding), and a
  prompt is right-padded with token 0 to a page multiple, as in
  ``stem_reference.py``;
* each token goes to its own top-k experts: the (token, expert) pairs are
  sorted by expert and computed ``TILE`` rows at a time, one expert's
  weights per tile, so a 64k prompt never computes all 64 experts; the
  tiles add into the output as they go;
* prefill attention runs one query block at a time (``lax.map``), and the
  dense and shared SwiGLUs ``ROWS`` tokens at a time, so a 64k prompt's
  reference fits one chip beside its weights.

``fp8=True`` rounds both operands of every linear layer (each expert's
matrices per tile, the SwiGLUs' activations per block of ``ROWS`` tokens),
the LM head, and the queries, keys and values, to float8 e4m3 with one
scale per tensor: the control.

Operations and bytes, counted as the model requires them whatever
implements it (lower bounds of what a step does):

* every linear layer once per real token, 2 FLOPs per multiply-add:
  ``q_proj``, ``kv_a_proj``, ``kv_b_proj`` (each token's key and value
  expanded once), ``o_proj``, the dense FFN or the router, the
  ``num_experts_per_tok`` routed and the shared experts; the LM head once
  per emitted token;
* attention in expanded form over the keys the rule keeps: ``h * (192 +
  128)`` multiply-adds per (query, kept key); page scoring one pooled dot
  product per (head, visible page) and anti-diagonal group;
* bytes: the weights outside the routed experts once per step in the
  published dtype, plus the kept latent pages of every decode token
  (``(kept - 1)`` whole pages and the last one up to the token, 576 values
  a token a layer).  The routed experts' bytes depend on routing: the MoE
  roofline share reads them from the engine's ``moe_experts_hit``.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

import stem_reference as sref
from stem_reference import gaps  # noqa: F401  (the family's comparison)
from stem_rule import StemRule

HEAD_SHARE = "mean"      # how the heads' page scores become one page set
TILE = 256               # reference expert rows per tile
ROWS = 4096              # reference SwiGLU tokens per block
GROUP = 4                # reference expert tiles per scan step

# -- the program's configuration ---------------------------------------------

FIXED = {"hidden_act": "silu", "scoring_func": "softmax",
         "topk_method": "greedy", "n_group": 1, "topk_group": 1,
         "moe_layer_freq": 1, "attention_bias": False, "q_lora_rank": None,
         "rms_norm_eps": 1e-6}


def program_config(model: dict, base):
    """The program's ``ArchConfig`` preset ``base`` with every published
    size of ``model``."""
    for k, v in FIXED.items():
        if model.get(k, v) != v:
            raise ValueError(f"this family serves {k} = {v!r}, the "
                             f"configuration states {model[k]!r}")
    y = model["rope_scaling"]
    if y.get("type") != "yarn":
        raise ValueError(f"rope_scaling type {y.get('type')!r}; this family "
                         "serves yarn")
    return base.replace(
        num_layers=model["num_hidden_layers"], d_model=model["hidden_size"],
        num_heads=model["num_attention_heads"],
        num_kv_heads=model["num_key_value_heads"],
        head_dim=model["qk_nope_head_dim"] + model["qk_rope_head_dim"],
        d_ff=model["moe_intermediate_size"], vocab_size=model["vocab_size"],
        rope_theta=float(model["rope_theta"]),
        tie_embeddings=model["tie_word_embeddings"],
        dtype=model["torch_dtype"],
        rope_scaling=dataclasses.replace(
            base.rope_scaling, factor=float(y["factor"]),
            original_max_position=y["original_max_position_embeddings"],
            beta_fast=float(y["beta_fast"]), beta_slow=float(y["beta_slow"]),
            mscale=float(y["mscale"]),
            mscale_all_dim=float(y["mscale_all_dim"])),
        mla=dataclasses.replace(
            base.mla, q_lora_rank=None, kv_lora_rank=model["kv_lora_rank"],
            rope_head_dim=model["qk_rope_head_dim"],
            nope_head_dim=model["qk_nope_head_dim"],
            v_head_dim=model["v_head_dim"]),
        moe=dataclasses.replace(
            base.moe, num_experts=model["n_routed_experts"],
            top_k=model["num_experts_per_tok"],
            expert_d_ff=model["moe_intermediate_size"],
            shared_experts=model["n_shared_experts"],
            shared_d_ff=model["moe_intermediate_size"],
            first_k_dense=model["first_k_dense_replace"],
            first_dense_d_ff=model["intermediate_size"],
            norm_topk_prob=model["norm_topk_prob"],
            routed_scaling_factor=float(model["routed_scaling_factor"])))


def vocab(model: dict) -> int:
    return model["vocab_size"]


# -- weights -------------------------------------------------------------------

MATRIX_STD = 0.02        # DeepSeek-V2's initializer_range
NORM_DELTA_STD = 0.05    # spread of RMSNorm weights around 1


def _dims(cfg):
    return dict(L=cfg["num_hidden_layers"], d=cfg["hidden_size"],
                h=cfg["num_attention_heads"], r=cfg["kv_lora_rank"],
                dr=cfg["qk_rope_head_dim"], dn=cfg["qk_nope_head_dim"],
                dv=cfg["v_head_dim"], I=cfg["intermediate_size"],
                f=cfg["moe_intermediate_size"], E=cfg["n_routed_experts"],
                K=cfg["num_experts_per_tok"], S=cfg["n_shared_experts"],
                k=cfg["first_k_dense_replace"], V=cfg["vocab_size"])


def shapes(cfg: dict) -> dict:
    n = _dims(cfg)
    L, d, h, k = n["L"], n["d"], n["h"], n["k"]
    Lm, sf = L - k, n["S"] * n["f"]
    s = {"embed": (n["V"], d), "final_norm": (d,),
         "attn_norm": (L, d), "mlp_norm": (L, d),
         "q_proj": (L, d, h * (n["dn"] + n["dr"])),
         "kv_a_proj": (L, d, n["r"] + n["dr"]), "kv_a_norm": (L, n["r"]),
         "kv_b_proj": (L, n["r"], h * (n["dn"] + n["dv"])),
         "o_proj": (L, h * n["dv"], d),
         "dense_gate": (k, d, n["I"]), "dense_up": (k, d, n["I"]),
         "dense_down": (k, n["I"], d),
         "router": (Lm, d, n["E"]),
         "expert_gate": (Lm, n["E"], d, n["f"]),
         "expert_up": (Lm, n["E"], d, n["f"]),
         "expert_down": (Lm, n["E"], n["f"], d),
         "shared_gate": (Lm, d, sf), "shared_up": (Lm, d, sf),
         "shared_down": (Lm, sf, d)}
    if not cfg["tie_word_embeddings"]:
        s["head"] = (d, n["V"])
    return s


NORMS = ("final_norm", "attn_norm", "mlp_norm", "kv_a_norm")


def canonical(cfg: dict, key) -> dict:
    """Every weight, drawn in the published dtype.  Norm entries hold the
    delta from 1."""
    dtype = jnp.dtype(cfg["torch_dtype"])
    out = {}
    for i, (name, shape) in enumerate(sorted(shapes(cfg).items())):
        std = NORM_DELTA_STD if name in NORMS else MATRIX_STD
        out[name] = (jax.random.normal(jax.random.fold_in(key, i), shape,
                                       jnp.float32) * std).astype(dtype)
    return out


def to_program(cfg: dict, w: dict, like) -> dict:
    """The program's parameter tree (structure and dtypes of ``like``)
    holding the canonical values ``w``."""
    n = _dims(cfg)
    L, d, h, k = n["L"], n["d"], n["h"], n["k"]
    kvb = w["kv_b_proj"].reshape(L, n["r"], h, n["dn"] + n["dv"])
    attn = {"wq": w["q_proj"].reshape(L, d, h, n["dn"] + n["dr"]),
            "w_dkv": w["kv_a_proj"][..., :n["r"]], "kv_norm": w["kv_a_norm"],
            "w_uk": kvb[..., :n["dn"]], "w_uv": kvb[..., n["dn"]:],
            "w_kr": w["kv_a_proj"][..., n["r"]:],
            "wo": w["o_proj"].reshape(L, h, n["dv"], d)}

    def layers(sl, ffn):
        return {"sub0": {"norm1": w["attn_norm"][sl],
                         "attn": {a: v[sl] for a, v in attn.items()},
                         "norm2": w["mlp_norm"][sl], "ffn": ffn}}

    moe = {"router": w["router"], "w_gate": w["expert_gate"],
           "w_up": w["expert_up"], "w_down": w["expert_down"],
           "shared": {"w_gate": w["shared_gate"], "w_up": w["shared_up"],
                      "w_down": w["shared_down"]}}
    vp = like["embed"].shape[0]
    tree = {"embed": jnp.pad(w["embed"], ((0, vp - n["V"]), (0, 0))),
            "final_norm": w["final_norm"],
            "segment0": layers(slice(0, k), {"w_gate": w["dense_gate"],
                                             "w_up": w["dense_up"],
                                             "w_down": w["dense_down"]}),
            "segment1": layers(slice(k, L), moe)}
    if "head" in w:
        tree["head"] = jnp.pad(w["head"], ((0, 0), (0, vp - n["V"])))
    if jax.tree.structure(tree) != jax.tree.structure(like):
        raise ValueError(f"program parameter layout changed: "
                         f"{jax.tree.structure(like)}")

    def cast(x, l):
        if x.shape != l.shape:
            raise ValueError(f"weight shape {x.shape} where the program "
                             f"holds {l.shape}")
        return x.astype(l.dtype)

    return jax.tree.map(cast, tree, like)


def program_params(cfg: dict, like, seed: int):
    """Seed -> the program's parameters, one jitted call per leaf (XLA
    keeps only the draws that leaf needs)."""
    key = sref.jax_key(seed)
    leaves, tree = jax.tree.flatten(like)
    out = [jax.jit(lambda k, i=i: jax.tree.leaves(
        to_program(cfg, canonical(cfg, k), like))[i])(key)
        for i in range(len(leaves))]
    return jax.tree.unflatten(tree, out)


# -- the plain reference -------------------------------------------------------

HI = sref.HI


def _linear(x, w, fp8):
    """x (..., k) @ w (k, n)."""
    if fp8:
        x, w = sref.fp8(x), sref.fp8(w)
    return jnp.einsum("...k,kn->...n", x, w, precision=HI)


def _rms(x, w, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + w)


def yarn_mscale(scale, mscale):
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def _inv_freq(cfg):
    """``DeepseekV2YarnRotaryEmbedding``'s blended inverse frequencies."""
    dim, base = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    y = cfg["rope_scaling"]

    def corr(rot):
        return dim * math.log(y["original_max_position_embeddings"]
                              / (rot * 2 * math.pi)) / (2 * math.log(base))
    lo = max(math.floor(corr(y["beta_fast"])), 0)
    hi = min(math.ceil(corr(y["beta_slow"])), dim - 1)
    hi = hi + 0.001 if lo == hi else hi
    extra = 1.0 / base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    inter = extra / y["factor"]
    mask = 1.0 - jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - lo)
                          / (hi - lo), 0.0, 1.0)
    return inter * (1.0 - mask) + extra * mask


def _rope(cfg, x, pos):
    """x (s, h, dr): de-interleave, then ``rotate_half`` (published)."""
    y = cfg["rope_scaling"]
    m = (yarn_mscale(y["factor"], y["mscale"])
         / yarn_mscale(y["factor"], y["mscale_all_dim"]))
    ang = pos[:, None].astype(jnp.float32) * _inv_freq(cfg)[None]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None] * m
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None] * m
    d = x.shape[-1]
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], -1)
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + rot * sin


def _attn_inputs(cfg, lw, x, pos, live, fp8):
    """Queries (s, h, 192) pre-scaled by mscale^2, expanded keys (s, h,
    192), values (s, h, 128) and the latent c_kv (s, 512); rows where
    ``live`` is False hold zeros (padding)."""
    n = _dims(cfg)
    s_ = x.shape[0]
    hh = _rms(x, lw["attn_norm"])
    q = _linear(hh, lw["q_proj"], fp8).reshape(s_, n["h"], n["dn"] + n["dr"])
    kv = _linear(hh, lw["kv_a_proj"], fp8)
    c = _rms(kv[:, :n["r"]], lw["kv_a_norm"])
    kpe = _rope(cfg, kv[:, None, n["r"]:], pos)
    c = jnp.where(live[:, None], c, 0.0)
    kpe = jnp.where(live[:, None, None], kpe, 0.0)
    # ``kv_b_proj`` as published, its key and value columns multiplied
    # apart so that the (s, h, 256) product is never held whole.
    wkv = lw["kv_b_proj"].reshape(n["r"], n["h"], n["dn"] + n["dv"])
    if fp8:
        c8, wkv = sref.fp8(c), sref.fp8(wkv)
    else:
        c8 = c
    k = jnp.concatenate([
        jnp.einsum("sr,rhk->shk", c8, wkv[..., :n["dn"]], precision=HI),
        jnp.broadcast_to(kpe, (s_, n["h"], n["dr"]))], -1)
    v = jnp.einsum("sr,rhk->shk", c8, wkv[..., n["dn"]:], precision=HI)
    y = cfg["rope_scaling"]
    q = jnp.concatenate([q[..., :n["dn"]], _rope(cfg, q[..., n["dn"]:], pos)],
                        -1) * yarn_mscale(y["factor"], y["mscale_all_dim"]) ** 2
    if fp8:
        q, k, v = sref.fp8(q), sref.fp8(k), sref.fp8(v)
    return q, k, v, c


def _summaries(k, c, page, stride):
    """Per page: anti-diagonal group means of the expanded keys (n, h,
    stride, d) and the max log ||c_kv|| (n,), the latent proxy of the
    value magnitude."""
    s, h, d = k.shape
    n = s // page
    kg = k.reshape(n, page // stride, stride, h, d).mean(1)
    vn = jnp.log(jnp.maximum(jnp.linalg.norm(c, axis=-1), 1e-20))
    return jnp.swapaxes(kg, 1, 2), vn.reshape(n, page).max(1)


def _prefill_attention(rule: StemRule, kmax, q, k, v, c, budgets):
    """Stem prefill over the padded prompt with one page set per query
    block for all heads.  q, k (s, h, d); v (s, h, dv); c (s, r)."""
    hq, hd, dv = q.shape[1], q.shape[2], v.shape[2]
    page, stride = rule.page, rule.stride
    n = q.shape[0] // page
    kg, vm = _summaries(k, c, page, stride)                 # (n,h,u,d),(n,)
    qp = q.reshape(n, page // stride, stride, hq, hd).mean(1)
    pair = (stride - np.arange(stride)) % stride
    route = jnp.einsum("ruhd,jhud->hrj", qp, kg[:, :, pair], precision=HI) / (
        stride * np.sqrt(hd))
    score = route.mean(0) + rule.beta * jnp.maximum(vm, 0.0)[None, :]
    r = jnp.arange(n)[:, None]
    j = jnp.arange(n)[None, :]
    forced = ((j < rule.sink) | (j > r - rule.local)) & (j <= r)
    biased = jnp.where(forced, score + sref.BIG, score)
    biased = jnp.where(j <= r, biased, sref.NEG)
    vals, idx = jax.lax.top_k(biased, kmax)                  # (n, kmax)
    keep_slot = (vals > sref.NEG / 2) & (jnp.arange(kmax) < budgets[:, None])
    kb = k.reshape(n, page, hq, hd)
    vb = v.reshape(n, page, hq, dv)
    qb = q.reshape(n, page, hq, hd)

    def row(args):
        rr, qr, ir, lr = args                  # qr (page, h, d); ir, lr (kmax,)
        kk, vv = kb[ir], vb[ir]                # (kmax, page, h, d)
        s = jnp.einsum("qhd,kphd->hqkp", qr, kk, precision=HI) / np.sqrt(hd)
        qpos = rr * page + jnp.arange(page)
        kpos = ir[:, None] * page + jnp.arange(page)          # (kmax, page)
        keep = (kpos[None] <= qpos[:, None, None]) & lr[None, :, None]
        s = jnp.where(keep[None], s, sref.NEG)
        p = jax.nn.softmax(s.reshape(hq, page, -1), -1).reshape(s.shape)
        p = jnp.where(keep[None], p, 0.0)
        return jnp.einsum("hqkp,kphd->qhd", p, vv, precision=HI)

    o = jax.lax.map(row, (jnp.arange(n), qb, idx, keep_slot))
    return o.reshape(q.shape[0], hq, dv)


def _decode_attention(rule: StemRule, q, k, v, c, pos, n_dec, kp, vp, cp,
                      prompt_len):
    """Stem decode of the fed-back tokens (q (db, h, d) at ``pos``) over the
    prompt's keys, values and latents (``kp``, ``vp``, ``cp``) and their
    own, one page set per token for all heads."""
    hq, hd = q.shape[1], q.shape[2]
    page = rule.page
    db = q.shape[0]
    live = (jnp.arange(db) < n_dec)
    k = jnp.where(live[:, None, None], k, 0.0)
    v = jnp.where(live[:, None, None], v, 0.0)
    c = jnp.where(live[:, None], c, 0.0)

    def place(prompt_part, own):
        pad = jnp.zeros((db,) + prompt_part.shape[1:], prompt_part.dtype)
        return jax.lax.dynamic_update_slice_in_dim(
            jnp.concatenate([prompt_part, pad]), own, prompt_len, 0)

    kc, vc, cc = place(kp, k), place(vp, v), place(cp, c)
    kg, vm = _summaries(kc, cc, page, rule.stride)
    kmean = kg.mean(2)                                        # (n, h, d)
    n = kc.shape[0] // page

    def rows(args):
        qi, pi = args
        rp = pi // page
        j = jnp.arange(n)
        valid = j <= rp
        forced = ((j < rule.sink) | (j > rp - rule.local)) & valid
        nv = rp + 1
        budget = jnp.maximum(jnp.maximum(rule.min_budget,
                                         jnp.minimum(nv, rule.sink + rule.local)),
                             jnp.floor(nv * rule.budget_frac).astype(jnp.int32))
        score = jnp.einsum("hd,jhd->hj", qi, kmean, precision=HI).mean(0) / (
            np.sqrt(hd)) + rule.beta * jnp.maximum(vm, 0.0)
        biased = jnp.where(forced, score + sref.BIG, score)
        biased = jnp.where(valid, biased, sref.NEG)
        rank = jnp.argsort(jnp.argsort(-biased))
        keep_page = (rank < budget) & valid                   # (n,)
        t = jnp.arange(kc.shape[0])
        keep = keep_page[t // page] & (t <= pi)
        s = jnp.einsum("hd,thd->ht", qi, kc, precision=HI) / np.sqrt(hd)
        s = jnp.where(keep[None], s, sref.NEG)
        p = jnp.where(keep[None], jax.nn.softmax(s, -1), 0.0)
        return jnp.einsum("ht,thd->hd", p, vc, precision=HI)

    return jax.lax.map(rows, (q, pos), batch_size=32)


def _experts(cfg, lw, x, fp8):
    """Routed experts: each token's own top-k, computed in tiles of TILE
    (token, expert) pairs sorted by expert, GROUP tiles at a time, each
    group adding into the output."""
    n = _dims(cfg)
    T, d, E, K = x.shape[0], n["d"], n["E"], n["K"]
    gates = jax.nn.softmax(_linear(x, lw["router"], fp8), -1)
    w, e = jax.lax.top_k(gates, K)                              # (T, K)
    if cfg["norm_topk_prob"]:
        w = w / w.sum(-1, keepdims=True)
    else:
        w = w * cfg["routed_scaling_factor"]
    flat = e.reshape(-1)
    order = jnp.argsort(flat, stable=True)
    counts = jnp.zeros((E,), jnp.int32).at[flat].add(1)
    first = jnp.cumsum(counts) - counts                      # sorted row start
    tiles = (counts + TILE - 1) // TILE
    tile_end = jnp.cumsum(tiles)
    n_tiles = -(-(-(-T * K // TILE) + E) // GROUP) * GROUP
    t = jnp.arange(n_tiles)
    ex = jnp.minimum(jnp.searchsorted(tile_end, t, side="right"), E - 1)
    row = first[ex][:, None] + (t - (tile_end - tiles)[ex])[:, None] * TILE \
        + jnp.arange(TILE)[None]                              # (tiles, TILE)
    ok = (t < tile_end[-1])[:, None] & (row < (first + counts)[ex][:, None])
    pair = order[jnp.clip(row, 0, T * K - 1)]
    tok = pair // K
    wt = jnp.where(ok, w.reshape(-1)[pair], 0.0)

    def tile(ei, ti, wi):
        xt = x[ti]
        g = jax.nn.silu(_linear(xt, lw["expert_gate"][ei].astype(jnp.float32), fp8))
        u = _linear(xt, lw["expert_up"][ei].astype(jnp.float32), fp8)
        y = _linear(g * u, lw["expert_down"][ei].astype(jnp.float32), fp8)
        return y * wi[:, None]

    def group(y, args):
        ei, ti, wi = args
        ys = jax.vmap(tile)(ei, ti, wi)                       # (GROUP, TILE, d)
        return y.at[ti.reshape(-1)].add(ys.reshape(-1, d)), None

    split = lambda a: a.reshape((n_tiles // GROUP, GROUP) + a.shape[1:])
    y, _ = jax.lax.scan(group, jnp.zeros((T, d), jnp.float32),
                        (split(ex), split(tok), split(wt)))
    return y


def _swiglu(h, gate, up, down, fp8):
    """SwiGLU of h (T, d), ROWS tokens at a time when T is a multiple."""
    def rows(hb):
        g = jax.nn.silu(_linear(hb, gate, fp8))
        return _linear(g * _linear(hb, up, fp8), down, fp8)

    t = h.shape[0]
    if t <= ROWS or t % ROWS:
        return rows(h)
    return jax.lax.map(rows, h.reshape(t // ROWS, ROWS, -1)).reshape(t, -1)


def _ffn(cfg, lw, x, fp8, moe):
    h = _rms(x, lw["mlp_norm"])
    if not moe:
        return x + _swiglu(h, lw["dense_gate"], lw["dense_up"],
                           lw["dense_down"], fp8)
    shared = _swiglu(h, lw["shared_gate"], lw["shared_up"], lw["shared_down"],
                     fp8)
    return x + shared + _experts(cfg, lw, h, fp8)


ATTN_KEYS = ("attn_norm", "mlp_norm", "q_proj", "kv_a_proj", "kv_a_norm",
             "kv_b_proj", "o_proj")
DENSE_KEYS = ("dense_gate", "dense_up", "dense_down")
MOE_KEYS = ("router", "shared_gate", "shared_up", "shared_down")
EXPERT_KEYS = ("expert_gate", "expert_up", "expert_down")


@functools.partial(jax.jit, static_argnames=("cfg_items", "rule", "kmax", "fp8"))
def _logits(w, prompt, true_len, budgets, dec, n_dec, *, cfg_items, rule,
            kmax, fp8):
    cfg = dict(cfg_items)
    cfg["rope_scaling"] = dict(cfg["rope_scaling"])
    n = _dims(cfg)
    pos = jnp.arange(prompt.shape[0])
    dpos = true_len + jnp.arange(dec.shape[0])
    live_p = pos < true_len
    live_d = jnp.arange(dec.shape[0]) < n_dec

    def layer(carry, lw, moe):
        x, xd = carry
        wide = {k_: v.astype(jnp.float32) for k_, v in lw.items()
                if k_ not in EXPERT_KEYS}
        wide.update({k_: lw[k_] for k_ in EXPERT_KEYS if k_ in lw})
        q, k, v, c = _attn_inputs(cfg, wide, x, pos, live_p, fp8)
        o = _prefill_attention(rule, kmax, q, k, v, c, budgets)
        x = x + _linear(o.reshape(x.shape[0], -1), wide["o_proj"], fp8)
        qd, kd, vd, cd = _attn_inputs(cfg, wide, xd, dpos, live_d, fp8)
        od = _decode_attention(rule, qd, kd, vd, cd, dpos, n_dec, k, v, c,
                               true_len)
        xd = xd + _linear(od.reshape(xd.shape[0], -1), wide["o_proj"], fp8)
        return (_ffn(cfg, wide, x, fp8, moe), _ffn(cfg, wide, xd, fp8, moe)), None

    emb = w["embed"]
    carry = (emb[prompt].astype(jnp.float32), emb[dec].astype(jnp.float32))
    k = n["k"]
    dense = {key: w[key][:k] for key in ATTN_KEYS + DENSE_KEYS}
    carry, _ = jax.lax.scan(functools.partial(layer, moe=False), carry, dense)
    moe = {key: w[key][k:] for key in ATTN_KEYS}
    moe.update({key: w[key] for key in MOE_KEYS + EXPERT_KEYS})
    (x, xd), _ = jax.lax.scan(functools.partial(layer, moe=True), carry, moe)
    last = jax.lax.dynamic_index_in_dim(x, true_len - 1, 0)
    h = _rms(jnp.concatenate([last, xd]), w["final_norm"].astype(jnp.float32))
    table = w["head"] if "head" in w else w["embed"].T
    return _linear(h, table.astype(jnp.float32), fp8)


def _items(cfg):
    return tuple(sorted(
        (k, tuple(sorted(v.items())) if isinstance(v, dict) else v)
        for k, v in cfg.items()
        if isinstance(v, (int, float, str, dict)) or v is None))


def logits(cfg: dict, rule: StemRule, w: dict, prompt, served, *,
           kmax: int, prompt_bucket: int, fp8: bool = False) -> np.ndarray:
    """(len(served), vocab) next-token logits: row 0 at the last prompt
    position, row i at the position of ``served[i - 1]`` fed back."""
    out = _logits(w, *sref.inputs(rule, prompt, served, prompt_bucket),
                  cfg_items=_items(cfg), rule=rule, kmax=kmax, fp8=fp8)
    return np.asarray(out[:len(served)])


# -- operations and bytes ------------------------------------------------------

ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


def attn_params(cfg) -> int:
    """Attention weights one token multiplies through, per layer."""
    n = _dims(cfg)
    return (n["d"] * n["h"] * (n["dn"] + n["dr"]) + n["d"] * (n["r"] + n["dr"])
            + n["r"] * n["h"] * (n["dn"] + n["dv"]) + n["h"] * n["dv"] * n["d"])


def expert_params(cfg) -> int:
    """One routed expert's weights."""
    n = _dims(cfg)
    return 3 * n["d"] * n["f"]


def token_params(cfg) -> int:
    """Weights one token multiplies through, LM head excluded."""
    n = _dims(cfg)
    lm = n["L"] - n["k"]
    return (n["L"] * attn_params(cfg) + n["k"] * 3 * n["d"] * n["I"]
            + lm * (n["d"] * n["E"] + (n["K"] + n["S"]) * expert_params(cfg)))


def moe_token_params(cfg) -> int:
    """Weights one token multiplies through in the MoE layers' FFN (router,
    routed and shared experts): the work of ``stem.moe``."""
    n = _dims(cfg)
    return (n["L"] - n["k"]) * (n["d"] * n["E"]
                                + (n["K"] + n["S"]) * expert_params(cfg))


def moe_fixed_bytes(cfg) -> int:
    """Bytes every step's ``stem.moe`` reads whatever the routing: the
    routers and shared experts of the MoE layers."""
    n = _dims(cfg)
    return ((n["L"] - n["k"]) * (n["d"] * n["E"] + n["S"] * expert_params(cfg))
            * ITEMSIZE[cfg["torch_dtype"]])


def expert_bytes(cfg) -> int:
    """Bytes of one routed expert's weights."""
    return expert_params(cfg) * ITEMSIZE[cfg["torch_dtype"]]


def weight_bytes(cfg) -> int:
    """Weights every step reads in the published dtype: all but the routed
    experts, LM head included (embedding rows gathered not counted)."""
    n = _dims(cfg)
    lm = n["L"] - n["k"]
    per = (n["L"] * attn_params(cfg) + n["k"] * 3 * n["d"] * n["I"]
           + lm * (n["d"] * n["E"] + n["S"] * expert_params(cfg))
           + n["d"] * n["V"])
    return per * ITEMSIZE[cfg["torch_dtype"]]


def _kept_keys(rule: StemRule, pos: int) -> int:
    kept = int(rule.decode_budget(pos // rule.page + 1))
    return (kept - 1) * rule.page + pos % rule.page + 1


def latent_decode_bytes(cfg, rule: StemRule, step: dict) -> float:
    """Kept latent bytes the step's decode tokens read: each kept page
    once per slot and layer, the last up to the token."""
    n = _dims(cfg)
    per_key = n["L"] * (n["r"] + n["dr"]) * ITEMSIZE[cfg["torch_dtype"]]
    return float(sum(_kept_keys(rule, p) for p in step["decode"]) * per_key)


def step_tokens(step: dict) -> int:
    """Real tokens a step carries: its decode tokens and chunk tokens."""
    return len(step["decode"]) + sum(
        max(0, min(width, plen - start))
        for plen, start, width, _ in step["chunks"])


def moe_flops(cfg, step: dict) -> float:
    """FLOPs of the MoE layers' FFN for the step's real tokens."""
    return 2.0 * moe_token_params(cfg) * step_tokens(step)


def _attn_flops(cfg, keys: int) -> float:
    n = _dims(cfg)
    return 2.0 * n["L"] * n["h"] * (n["dn"] + n["dr"] + n["dv"]) * keys


def decode_flops(cfg, rule: StemRule, pos: int) -> float:
    """One fed-back token at position ``pos`` (attending keys 0..pos)."""
    n = _dims(cfg)
    nv = pos // rule.page + 1
    return (2.0 * token_params(cfg) + 2.0 * n["d"] * n["V"]
            + _attn_flops(cfg, _kept_keys(rule, pos))
            + 2.0 * n["L"] * n["h"] * (n["dn"] + n["dr"]) * nv)


def chunk_flops(cfg, rule: StemRule, prompt_len: int, start: int,
                width: int, completes: bool) -> float:
    """One prefill chunk of ``width`` tokens from ``start`` of a prompt of
    ``prompt_len`` tokens; only real prompt tokens count."""
    n = _dims(cfg)
    padded = -(-prompt_len // rule.page) * rule.page
    budgets = rule.prefill_budgets(padded)
    t = np.arange(start, min(start + width, prompt_len))
    if t.size == 0:
        return 0.0
    r = t // rule.page
    keys = (budgets[r].astype(np.int64) - 1) * rule.page + t % rule.page + 1
    rows = np.unique(r)
    score = (float(np.sum((rows + 1) * rule.stride)) * 2.0
             * (n["dn"] + n["dr"]) * n["h"] * n["L"])
    return (2.0 * token_params(cfg) * t.size + _attn_flops(cfg, int(keys.sum()))
            + score + (2.0 * n["d"] * n["V"] if completes else 0.0))


def step_flops(cfg, rule: StemRule, step: dict) -> float:
    """``step``: {"decode": [positions], "chunks": [[prompt_len, start,
    width, completes], ...]}."""
    return (sum(decode_flops(cfg, rule, p) for p in step["decode"])
            + sum(chunk_flops(cfg, rule, *c) for c in step["chunks"]))


def step_bytes(cfg, rule: StemRule, step: dict) -> float:
    return weight_bytes(cfg) + latent_decode_bytes(cfg, rule, step)
