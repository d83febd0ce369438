"""The Qwen-family dense model (Hugging Face ``Qwen2``/``Qwen3``): GQA or MHA
with optional QKV bias and per-head q/k RMSNorm, rotary embedding with
``rotate_half``, SwiGLU MLP, tied or untied LM head.

A configuration names its family (``"family": "qwen_dense"``) and the
harness finds this file by that name.  A family module provides:

* ``program_config(model, base)``: the published keys mapped onto the
  program's ``ArchConfig`` ``base``;
* ``vocab(model)``: the vocabulary size;
* ``canonical(model, key)`` and ``program_params(model, like, seed)``:
  seeded weights in the published layout, and the same values in the
  program's parameter tree, made on the device in one jitted call;
* ``logits(model, rule, w, prompt, served, *, kmax, prompt_bucket,
  fp8=False)`` and ``gaps(ref, served)``: the plain reference;
* ``step_flops(model, rule, step)`` and ``step_bytes(model, rule, step)``:
  the operations and bytes one engine step needs.

Weights.  ``canonical`` draws every weight in the published dtype
(``torch_dtype``).  ``program_params`` lays the same values out the way the
serving program stores them:

* matrices become the program's ``(d, heads, head_dim)`` einsum shapes;
* the program rotates adjacent pairs ``(2i, 2i+1)`` where the published
  model rotates ``(i, i + head_dim/2)``, so the query and key head
  dimensions (and their biases and norms) are permuted to match, exactly
  as a checkpoint converter does;
* RMSNorm weights are stored as ``w - 1`` (the program computes
  ``x * (1 + w)``): each norm weight is drawn as ``1 + delta`` and the
  program is handed ``delta``;
* the vocabulary is padded with zero rows to the program's table size.

No value is rounded twice: both sides see the same draws.

Reference.  Straightforward ``jax.numpy`` in float32 at
``Precision.HIGHEST``, written from the published model, with Stem's
attention over kept pages from ``stem_reference.py``.  It imports nothing
of the program and takes none of its arrays: weights come from
``canonical`` and the seed, stay in the dtype they are served in, and are
widened to float32 one layer at a time.  ``logits`` returns the
next-token logits at the last prompt position and at every fed-back
position.  With ``fp8=True`` both operands of every linear layer and of
the LM head, and the queries, keys and values, are rounded to float8 e4m3
with one scale per tensor: the control, one precision step below the bf16
the model is served in.

Operations and bytes, counted as the served model requires them whatever
implements it:

* every linear layer (projections, MLP) once per real token, 2 FLOPs per
  multiply-add; the LM head once per emitted token;
* attention over the keys the Stem rule keeps (``stem_rule.py``): for a
  prompt token at position ``t`` in query block ``r``, the ``budget[r] - 1``
  earlier kept pages in full plus its own page up to ``t``; for a decode
  token, the decode budget's pages likewise; 4 FLOPs per (query head, key,
  head dim) for QK^T and PV;
* page scoring: one pooled dot product per (query head, visible page) and
  anti-diagonal group.

Padding, gather copies and recomputation are not counted.  Bytes are the
weights once per step in the published dtype plus the kept K/V pages of
every decode token (per KV head, at the budget of one query head).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

import stem_reference as sref
from stem_reference import gaps  # noqa: F401  (the family's comparison)
from stem_rule import StemRule

# -- the program's configuration ---------------------------------------------

# Published config keys -> the program's ArchConfig fields.
PROGRAM_FIELDS = {
    "num_hidden_layers": "num_layers", "hidden_size": "d_model",
    "num_attention_heads": "num_heads", "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim", "intermediate_size": "d_ff",
    "vocab_size": "vocab_size", "rope_theta": "rope_theta",
    "tie_word_embeddings": "tie_embeddings", "attention_bias": "qkv_bias",
    "qk_norm": "qk_norm", "torch_dtype": "dtype",
}
PROGRAM_RMS_EPS = 1e-6


def program_config(model: dict, base):
    """The program's ``ArchConfig`` preset ``base`` with every published
    size of ``model``."""
    if model["rms_norm_eps"] != PROGRAM_RMS_EPS:
        raise ValueError("the program's RMSNorm epsilon is fixed at "
                         f"{PROGRAM_RMS_EPS}")
    return base.replace(**{f: model[k] for k, f in PROGRAM_FIELDS.items()
                           if k in model})


def vocab(model: dict) -> int:
    return model["vocab_size"]


# -- weights -------------------------------------------------------------------

MATRIX_STD = 0.02        # Qwen's initializer_range
NORM_DELTA_STD = 0.05    # spread of RMSNorm weights around 1


def shapes(cfg: dict) -> dict:
    d, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    hq, hk, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    ff, V = cfg["intermediate_size"], cfg["vocab_size"]
    s = {"embed": (V, d), "final_norm": (d,),
         "attn_norm": (L, d), "mlp_norm": (L, d),
         "wq": (L, d, hq * hd), "wk": (L, d, hk * hd), "wv": (L, d, hk * hd),
         "wo": (L, hq * hd, d),
         "w_gate": (L, d, ff), "w_up": (L, d, ff), "w_down": (L, ff, d)}
    if cfg.get("attention_bias"):
        s.update(bq=(L, hq * hd), bk=(L, hk * hd), bv=(L, hk * hd))
    if cfg.get("qk_norm"):
        s.update(q_norm=(L, hd), k_norm=(L, hd))
    if not cfg["tie_word_embeddings"]:
        s["head"] = (d, V)
    return s


NORMS = ("final_norm", "attn_norm", "mlp_norm", "q_norm", "k_norm")


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


def rope_perm(head_dim: int) -> np.ndarray:
    """Published head-dim index for each of the program's positions."""
    half = head_dim // 2
    return np.stack([np.arange(half), np.arange(half) + half], -1).reshape(-1)


def to_program(cfg: dict, w: dict, like) -> dict:
    """The program's parameter tree (structure and dtypes of ``like``, the
    program's abstract parameters) holding the canonical values ``w``."""
    L, d = cfg["num_hidden_layers"], cfg["hidden_size"]
    hq, hk, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    perm = rope_perm(hd)

    def heads(x, h, roped):          # (..., h * hd) -> (..., h, hd)
        x = x.reshape(x.shape[:-1] + (h, hd))
        return x[..., perm] if roped else x

    attn = {"wq": heads(w["wq"], hq, True), "wk": heads(w["wk"], hk, True),
            "wv": heads(w["wv"], hk, False),
            "wo": w["wo"].reshape(L, hq, hd, d)}
    if "bq" in w:
        attn.update(bq=heads(w["bq"], hq, True), bk=heads(w["bk"], hk, True),
                    bv=heads(w["bv"], hk, False))
    if "q_norm" in w:
        attn.update(q_norm=w["q_norm"][..., perm], k_norm=w["k_norm"][..., perm])
    sub = {"norm1": w["attn_norm"], "attn": attn, "norm2": w["mlp_norm"],
           "ffn": {"w_gate": w["w_gate"], "w_up": w["w_up"],
                   "w_down": w["w_down"]}}
    vp = like["embed"].shape[0]
    tree = {"embed": jnp.pad(w["embed"], ((0, vp - w["embed"].shape[0]), (0, 0))),
            "final_norm": w["final_norm"], "segment0": {"sub0": sub}}
    if "head" in w:
        tree["head"] = jnp.pad(w["head"], ((0, 0), (0, vp - w["head"].shape[1])))
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
    """One jitted call on the device: seed -> the program's parameters."""
    return jax.jit(lambda k: to_program(cfg, canonical(cfg, k), like))(
        sref.jax_key(seed))


# -- the plain reference -------------------------------------------------------

HI = sref.HI


def _linear(x, w, fp8):
    """x (..., k) @ w (k, n)."""
    if fp8:
        x, w = sref.fp8(x), sref.fp8(w)
    return jnp.einsum("...k,kn->...n", x, w, precision=HI)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + w)


def _rope(x, pos, theta):
    """x (s, h, d); rotate_half convention."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos[:, None].astype(jnp.float32) * inv            # (s, d/2)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None]
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + rot * sin


def _qkv(cfg, lw, h, pos, fp8):
    hq, hk, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    q, k, v = (_linear(h, lw[n], fp8) for n in ("wq", "wk", "wv"))
    if "bq" in lw:
        q, k, v = q + lw["bq"], k + lw["bk"], v + lw["bv"]
    s = h.shape[0]
    q, k, v = (q.reshape(s, hq, hd), k.reshape(s, hk, hd), v.reshape(s, hk, hd))
    if "q_norm" in lw:
        q = _rms(q, lw["q_norm"], cfg["rms_norm_eps"])
        k = _rms(k, lw["k_norm"], cfg["rms_norm_eps"])
    theta = cfg["rope_theta"]
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    if fp8:
        q, k, v = sref.fp8(q), sref.fp8(k), sref.fp8(v)
    return q, k, v


def _mlp(cfg, lw, x, fp8):
    h = _rms(x, lw["mlp_norm"], cfg["rms_norm_eps"])
    g = jax.nn.silu(_linear(h, lw["w_gate"], fp8))
    return _linear(g * _linear(h, lw["w_up"], fp8), lw["w_down"], fp8)


def _attn_out(cfg, lw, x, o, fp8):
    """The residual after attention's output projection, then the MLP's."""
    x = x + _linear(o.reshape(x.shape[0], -1), lw["wo"], fp8)
    return x + _mlp(cfg, lw, x, fp8)


LAYER_KEYS = ("attn_norm", "mlp_norm", "wq", "wk", "wv", "wo", "bq", "bk",
              "bv", "q_norm", "k_norm", "w_gate", "w_up", "w_down")


def _head(cfg, w, x, fp8):
    h = _rms(x, w["final_norm"].astype(jnp.float32), cfg["rms_norm_eps"])
    table = w["head"] if "head" in w else w["embed"].T
    return _linear(h, table.astype(jnp.float32), fp8)


@functools.partial(jax.jit, static_argnames=("cfg_items", "rule", "kmax", "fp8"))
def _logits(w, prompt, true_len, budgets, dec, n_dec, *, cfg_items, rule,
            kmax, fp8):
    cfg = dict(cfg_items)
    layers = {k: w[k] for k in LAYER_KEYS if k in w}
    pos = jnp.arange(prompt.shape[0])
    dpos = true_len + jnp.arange(dec.shape[0])
    eps = cfg["rms_norm_eps"]

    def layer(carry, lw):
        x, xd = carry
        lw = {k: v.astype(jnp.float32) for k, v in lw.items()}
        q, k, v = _qkv(cfg, lw, _rms(x, lw["attn_norm"], eps), pos, fp8)
        o, k, v = sref.prefill_attention(rule, kmax, q, k, v, pos, true_len,
                                         budgets)
        x = _attn_out(cfg, lw, x, o, fp8)
        qd, kd, vd = _qkv(cfg, lw, _rms(xd, lw["attn_norm"], eps), dpos, fp8)
        od = sref.decode_attention(rule, qd, kd, vd, dpos, n_dec, k, v,
                                   true_len)
        return (x, _attn_out(cfg, lw, xd, od, fp8)), None

    emb = w["embed"]
    (x, xd), _ = jax.lax.scan(
        layer, (emb[prompt].astype(jnp.float32),
                emb[dec].astype(jnp.float32)), layers)
    last = jax.lax.dynamic_index_in_dim(x, true_len - 1, 0)
    return _head(cfg, w, jnp.concatenate([last, xd]), fp8)


def logits(cfg: dict, rule: StemRule, w: dict, prompt, served, *,
           kmax: int, prompt_bucket: int, fp8: bool = False) -> np.ndarray:
    """(len(served), vocab) next-token logits: row 0 at the last prompt
    position, row i at the position of ``served[i - 1]`` fed back."""
    out = _logits(w, *sref.inputs(rule, prompt, served, prompt_bucket),
                  cfg_items=tuple(sorted((k, v) for k, v in cfg.items()
                                         if isinstance(v, (int, float, str)))),
                  rule=rule, kmax=kmax, fp8=fp8)
    return np.asarray(out[:len(served)])


# -- operations and bytes ------------------------------------------------------

def _dims(cfg):
    return (cfg["num_hidden_layers"], cfg["hidden_size"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], cfg["intermediate_size"], cfg["vocab_size"])


def linear_params(cfg) -> int:
    """Weights a token multiplies through, LM head excluded."""
    L, d, hq, hk, hd, ff, _ = _dims(cfg)
    return L * (d * hd * (hq + 2 * hk) + hq * hd * d + 3 * d * ff)


ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


def weight_bytes(cfg) -> int:
    """The weights one step reads, in the published dtype: every linear
    layer and the LM head (the embedding rows it gathers are not counted)."""
    L, d, hq, hk, hd, ff, V = _dims(cfg)
    return (linear_params(cfg) + d * V) * ITEMSIZE[cfg["torch_dtype"]]


def decode_flops(cfg, rule: StemRule, pos: int) -> float:
    """One fed-back token at position ``pos`` (attending keys 0..pos)."""
    L, d, hq, hk, hd, ff, V = _dims(cfg)
    nv = pos // rule.page + 1
    kept = int(rule.decode_budget(nv))
    keys = (kept - 1) * rule.page + pos % rule.page + 1
    return (2.0 * linear_params(cfg) + 2.0 * d * V
            + L * hq * (4.0 * hd * keys + 2.0 * hd * nv))


def decode_bytes(cfg, rule: StemRule, pos: int) -> float:
    """K/V bytes a decode token reads at the budget (weights excluded)."""
    L, d, hq, hk, hd, ff, V = _dims(cfg)
    kept = int(rule.decode_budget(pos // rule.page + 1))
    return float(L * hk * kept * rule.page * hd * 2
                 * ITEMSIZE[cfg["torch_dtype"]])


def chunk_flops(cfg, rule: StemRule, prompt_len: int, start: int,
                width: int, completes: bool) -> float:
    """One prefill chunk of ``width`` tokens from ``start`` of a prompt of
    ``prompt_len`` tokens; only real prompt tokens count."""
    L, d, hq, hk, hd, ff, V = _dims(cfg)
    padded = -(-prompt_len // rule.page) * rule.page
    budgets = rule.prefill_budgets(padded)
    t = np.arange(start, min(start + width, prompt_len))
    if t.size == 0:
        return 0.0
    r = t // rule.page
    keys = (budgets[r].astype(np.int64) - 1) * rule.page + t % rule.page + 1
    rows = np.unique(r)
    score = float(np.sum((rows + 1) * rule.stride)) * 2.0 * hd * hq * L
    return (2.0 * linear_params(cfg) * t.size + 4.0 * L * hq * hd * keys.sum()
            + score + (2.0 * d * V if completes else 0.0))


def step_flops(cfg, rule: StemRule, step: dict) -> float:
    """``step``: {"decode": [positions], "chunks": [[prompt_len, start,
    width, completes], ...]}."""
    return (sum(decode_flops(cfg, rule, p) for p in step["decode"])
            + sum(chunk_flops(cfg, rule, *c) for c in step["chunks"]))


def step_bytes(cfg, rule: StemRule, step: dict) -> float:
    return weight_bytes(cfg) + sum(decode_bytes(cfg, rule, p)
                                   for p in step["decode"])
